"""The comparison that decides ``correct`` for a training cell.

Numbers compared, each against a limit of its own from the cell's limits
file: each observed step's loss (relative gap to the reference's), and by
the worst leaf the gap between the program's norm and the reference's
(never the norm of a difference) for the momentum trace after the first
call, the parameters' change after the checked steps, and the change of
BatchNorm's statistics. A leaf's gap is measured against the reference's
norm of that leaf or of the median leaf, whichever is larger.
"""

from __future__ import annotations

import numpy as np

# a leaf whose first gradient in the reference is under this share of the
# median leaf's moves by round-off alone, and is left out of the change
QUIET_LEAF = 1e-3


def leaf_gaps(prog, ref, keep=None):
    """Per leaf |prog - ref| / max(ref, median(ref)); leaves not kept read
    0, a leaf that is not finite reads inf."""
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    if prog.shape != ref.shape:
        return np.full(ref.shape, np.inf), np.ones(ref.shape, bool)
    if keep is None:
        keep = np.ones(ref.shape, bool)
    if not keep.any():
        return np.zeros(ref.shape), keep
    floor = float(np.median(ref[keep]))
    gaps = np.abs(prog - ref) / np.maximum(np.maximum(ref, floor), 1e-30)
    gaps = np.where(keep, gaps, 0.0)
    return np.where(np.isfinite(gaps), gaps, np.inf), keep


def worst_gap(prog, ref, keep=None):
    """Largest over leaves of |prog - ref| / max(ref, median(ref))."""
    gaps, _ = leaf_gaps(prog, ref, keep)
    i = int(np.argmax(gaps))
    return float(gaps[i]), i


def median_gap(prog, ref, keep=None):
    """The median leaf's gap, over the leaves kept."""
    gaps, keep = leaf_gaps(prog, ref, keep)
    return float(np.median(gaps[keep])) if keep.any() else 0.0


def readings(seen, ref):
    """{name: value} of every number compared, limits aside."""
    out = {}
    for step, loss in sorted(seen["losses"].items()):
        r = ref["losses"][step - 1]
        gap = abs(loss - r) / max(abs(r), 1e-30)
        out[f"loss_step{step}"] = gap if np.isfinite(gap) else float("inf")
    rt = np.asarray(ref["trace_norms"], np.float64)
    out["trace_norm_gap"], _ = worst_gap(seen["trace_norms"], rt)
    out["trace_norm_gap_median"] = median_gap(seen["trace_norms"], rt)
    moving = rt >= QUIET_LEAF * np.median(rt)
    out["delta_norm_gap"], _ = worst_gap(seen["delta_norms"],
                                         ref["delta_norms"], moving)
    out["delta_norm_gap_median"] = median_gap(seen["delta_norms"],
                                              ref["delta_norms"], moving)
    if len(ref["state_delta_norms"]):
        out["state_norm_gap"], _ = worst_gap(seen["state_delta_norms"],
                                             ref["state_delta_norms"])
    return out


def numbers(seen, ref, limits):
    """[{name, value, limit}] for the result line; a number the limits
    file does not hold is not compared and not listed."""
    vals = readings(seen, ref)
    return [{"name": k, "value": v, "limit": limits[k]}
            for k, v in vals.items() if k in limits]


def correct(compared) -> bool:
    return bool(compared) and all(
        np.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in compared)
