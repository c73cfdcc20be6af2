"""The whole step's share of the chips' peak: operations the forward and
backward passes need per example (perfbench/lib/arch.py, from layer
shapes; recomputation not counted) times examples completed, over window
seconds x chips x the bf16 peak of peaks.json."""

from perfbench.lib import arch


def read(obs, trace, cell, args):
    if not cell.get("peaks") or not obs.get("window_s"):
        return None
    flops = arch.train_flops_per_example(cell["cfg"]) * obs["examples"]
    peak = cell["peaks"]["bf16_flops_per_s"] * cell["chips"]
    return 100.0 * flops / (obs["window_s"] * peak)
