"""A share of the program's own pipeline timer: 100 * num / den of
``net.last_pipeline_stats`` over the window's fit call."""


def read(obs, trace, cell, args):
    stats = obs.get("pipeline_stats") or {}
    num, den = stats.get(args["num"]), stats.get(args["den"])
    if num is None or not den:
        return None
    return 100.0 * num / den
