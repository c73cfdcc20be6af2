"""A kernel's share of its roofline where the counts module is named by the
metric: the operations the module's ``attention_flops(cfg, seq,
sequences)`` says the layers' scores and values need (forward and two
backward products, the replay not counted) over the device seconds of
everything under the scope ``args["scope"]`` in the traced window (the
job's ``device_seconds``: forward, replay and backward) times the chip's
bf16 peak. ``args["counts"]``: the module under ``perfbench/lib``. Bound by
operations at the cells' shapes."""

import importlib


def read(obs, trace, cell, args):
    ds = obs.get("device_seconds")
    if not ds or not cell.get("peaks"):
        return None
    seconds = (ds.get("inner") or {}).get(args["scope"])
    if not seconds:
        return None
    counts = importlib.import_module(f"perfbench.lib.{args['counts']}")
    flops = counts.attention_flops(cell["cfg"], obs["seq"], obs["examples"])
    return 100.0 * flops / (seconds * cell["peaks"]["bf16_flops_per_s"])
