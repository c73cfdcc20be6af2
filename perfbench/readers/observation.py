"""A number the job observed itself, by its key (an exact count)."""


def read(obs, trace, cell, args):
    v = obs.get(args["key"])
    return None if v is None else float(v)
