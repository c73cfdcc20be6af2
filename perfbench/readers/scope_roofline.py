"""A kernel's share of its roofline, whichever implementation runs: the
operations the mathematics needs (perfbench/lib/counts_lm.py: for the
grouped products the pairs the program's counters counted in the window,
for attention the MACs inside the band or triangle; forward and two
backward products, the replay not counted) over the device seconds of
everything under the kernel's scope in the traced window (forward, replay
and backward) times the chip's bf16 peak. Both kernels are bound by
operations at these shapes."""

from perfbench.lib import counts_lm


def read(obs, trace, cell, args):
    ds = obs.get("device_seconds")
    if not ds or not cell.get("peaks"):
        return None
    seconds = ds.get(args["seconds"])
    if not seconds:
        return None
    if args["flops"] == "grouped_matmul":
        flops = counts_lm.grouped_matmul_flops(cell["cfg"], obs["moe_pairs"])
    else:
        flops = counts_lm.attention_flops(cell["cfg"], obs["seq"],
                                          obs["examples"])
    return 100.0 * flops / (seconds * cell["peaks"]["bf16_flops_per_s"])
