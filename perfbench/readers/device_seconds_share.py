"""A layer class's share of the step's device time: 100 * seconds of the
traced window's operations under that class's scopes (forward, replay and
backward) over all operations' seconds, from the job's own reduction of
the device plane (``device_seconds``: perfbench/jobs/fit_lm.py)."""


def read(obs, trace, cell, args):
    ds = obs.get("device_seconds")
    if not ds or not ds.get("total_s"):
        return None
    sec = ds["by_kind"].get(args["kind"])
    return None if sec is None else 100.0 * sec / ds["total_s"]
