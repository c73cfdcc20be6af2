"""A phase's share of the step's device time: 100 * seconds of the traced
window's operations of that phase (``lib/scopes.py``: ``recompute`` is what
``jax.checkpoint`` runs again in the backward pass) over all operations'
seconds, from the job's own reduction of the device plane
(``device_seconds``: perfbench/jobs/fit_lm.py). A phase in which no
operation ran reads 0; without the reduction there is nothing to read."""


def read(obs, trace, cell, args):
    ds = obs.get("device_seconds")
    if not ds or not ds.get("total_s") or "by_phase" not in ds:
        return None
    return 100.0 * ds["by_phase"].get(args["phase"], 0.0) / ds["total_s"]
