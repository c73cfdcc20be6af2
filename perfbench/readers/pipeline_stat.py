"""One number of the program's own pipeline timer
(``net.last_pipeline_stats`` over the window's fit call): ``scale`` *
``stats[key]``, over ``stats[per]`` where ``per`` is given."""


def read(obs, trace, cell, args):
    stats = obs.get("pipeline_stats") or {}
    v = stats.get(args["key"])
    if v is None:
        return None
    if "per" in args:
        den = stats.get(args["per"])
        if not den:
            return None
        v = v / den
    return float(args.get("scale", 1.0) * v)
