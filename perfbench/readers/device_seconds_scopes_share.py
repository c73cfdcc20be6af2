"""The share of the step's device time under some scopes inside a layer:
100 * the seconds of the traced window's operations whose innermost such
scope is one of ``args["scopes"]`` (forward, replay and backward) over all
operations' seconds, from the job's own reduction of the device plane
(``device_seconds``: perfbench/jobs/fit_sparse_lm.py). A scope under which
nothing ran counts 0; without the reduction, or where it knows none of the
scopes, there is nothing to read."""


def read(obs, trace, cell, args):
    ds = obs.get("device_seconds")
    if not ds or not ds.get("total_s") or not ds.get("inner"):
        return None
    if not any(s in ds["inner"] for s in args["scopes"]):
        return None
    return 100.0 * sum(ds["inner"].get(s, 0.0)
                       for s in args["scopes"]) / ds["total_s"]
