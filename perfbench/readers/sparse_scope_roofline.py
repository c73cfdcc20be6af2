"""A share of the roofline for attention over a learned selection of keys,
whichever implementation runs: the operations the mathematics needs
(perfbench/lib/counts_sparse_lm.py: for ``selected_attention`` the scores
and values over the SELECTED keys only, for ``index_scores`` the index
scores over every visible key; forward and two backward products, the
replay not counted) over the device seconds of everything under the scope
in the traced window times the chip's bf16 peak. A form that scores every
visible key under a mask executes more than is needed and reads low."""

from perfbench.lib import counts_sparse_lm as counts


def read(obs, trace, cell, args):
    ds = obs.get("device_seconds")
    if not ds or not cell.get("peaks"):
        return None
    seconds = (ds.get("inner") or {}).get(args["scope"])
    if not seconds:
        return None
    flops = getattr(counts, args["flops"] + "_flops")(
        cell["cfg"], obs["seq"], obs["examples"])
    return 100.0 * flops / (seconds * cell["peaks"]["bf16_flops_per_s"])
