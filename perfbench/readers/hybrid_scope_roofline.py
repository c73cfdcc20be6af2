"""A share of the roofline for a scope inside a hybrid decoder's mixers,
whichever implementation runs: the time the needed work takes at the chip's
peaks (perfbench/lib/counts_hybrid_lm.py) over the device seconds of
everything under the scope in the traced window (forward, replay and
backward). ``work``: ``scan``, the Mamba layers' scans, the larger of their
operations over the bf16 peak and their bytes over the memory bandwidth
(bound by memory at the benchmark's shapes); ``latent_experts``, the grouped
products of the pairs the program's counters counted in the window (bound
by operations: a few hundred rows against 11 MB of an expert's weights)."""

from perfbench.lib import counts_hybrid_lm as counts


def read(obs, trace, cell, args):
    ds = obs.get("device_seconds")
    if not ds or not cell.get("peaks"):
        return None
    seconds = (ds.get("inner") or {}).get(args["scope"])
    if not seconds:
        return None
    if args["work"] == "scan":
        needed = counts.scan_seconds_at_peak(
            cell["cfg"], obs["seq"], obs["examples"], cell["peaks"])
    else:
        needed = counts.latent_experts_flops(cell["cfg"], obs["moe_pairs"]) \
            / cell["peaks"]["bf16_flops_per_s"]
    return 100.0 * needed / seconds
