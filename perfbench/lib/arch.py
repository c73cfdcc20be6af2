"""A configuration file's node list: shapes, parameter leaves, and the
operations the mathematics needs, all from layer shapes.

A node is ``{"key", "op", "in": [keys], ...}``; ops are conv, bn, maxpool,
add, relu, gap, dense, output. Layout is NHWC, kernels HWIO, dense (in, out).
"""

from __future__ import annotations

import json
from pathlib import Path

PARAM_OPS = ("conv", "bn", "dense", "output")


def load_config(path, rehearse: bool = False) -> dict:
    """The configuration as it is run; with ``rehearse`` its tiny stand-in
    (the file's ``rehearsal`` table: smaller image, fewer classes, every
    channel count divided) for a CPU run of the control flow."""
    cfg = json.loads(Path(path).read_text())
    if rehearse:
        r = cfg["rehearsal"]
        div = int(r.get("width_div", 1))
        cfg["image"] = r["image"]
        cfg["num_classes"] = r["num_classes"]
        last = cfg["nodes"][-1]["key"]
        for n in cfg["nodes"]:
            if "out" in n:
                n["out"] = r["num_classes"] if n["key"] == last \
                    else max(8, n["out"] // div)
        cfg["program"]["kwargs"].update(r.get("program_kwargs", {}))
        cfg["rehearsed"] = True
    return cfg


def _conv_out(size, k, s, p):
    return (size + 2 * p - k) // s + 1


def shapes(cfg) -> dict:
    """key -> output shape without the batch: (H, W, C) or (N,)."""
    out = {"input": (cfg["image"], cfg["image"], cfg["channels"])}
    for n in cfg["nodes"]:
        src = out[n["in"][0]]
        op = n["op"]
        if op == "conv":
            h = _conv_out(src[0], n["k"], n["s"], n["p"])
            w = _conv_out(src[1], n["k"], n["s"], n["p"])
            out[n["key"]] = (h, w, n["out"])
        elif op == "maxpool":
            h = _conv_out(src[0], n["k"], n["s"], n["p"])
            w = _conv_out(src[1], n["k"], n["s"], n["p"])
            out[n["key"]] = (h, w, src[2])
        elif op in ("bn", "relu", "add"):
            out[n["key"]] = src
        elif op == "gap":
            out[n["key"]] = (src[-1],)
        elif op in ("dense", "output"):
            out[n["key"]] = (n["out"],)
        else:
            raise ValueError(f"unknown op {op!r} in node {n['key']!r}")
    return out


def _size(shape):
    k = 1
    for s in shape:
        k *= s
    return k


def param_leaves(cfg) -> list:
    """[(key, leaf name, shape, kind)] in node order, leaf names sorted as
    a dict flattens: kind is 'weight' (He-normal), 'zero' or 'one'."""
    sh = shapes(cfg)
    out = []
    for n in cfg["nodes"]:
        op = n["op"]
        if op not in PARAM_OPS:
            continue
        src = sh[n["in"][0]]
        if op == "conv":
            out.append((n["key"], "W", (n["k"], n["k"], src[2], n["out"]),
                        "weight"))
            if n.get("bias"):
                out.append((n["key"], "b", (n["out"],), "zero"))
        elif op == "bn":
            out.append((n["key"], "beta", (src[-1],), "zero"))
            out.append((n["key"], "gamma", (src[-1],), "one"))
        else:
            out.append((n["key"], "W", (_size(src), n["out"]), "weight"))
            if n.get("bias", True):
                out.append((n["key"], "b", (n["out"],), "zero"))
    return out


def state_leaves(cfg) -> list:
    """BatchNorm's running statistics: [(key, 'mean'|'var', shape)]."""
    sh = shapes(cfg)
    out = []
    for n in cfg["nodes"]:
        if n["op"] == "bn":
            c = sh[n["key"]][-1]
            out += [(n["key"], "mean", (c,)), (n["key"], "var", (c,))]
    return out


def num_params(cfg) -> int:
    return sum(_size(s) for _, _, s, _ in param_leaves(cfg))


def matmul_layers(cfg) -> list:
    """Every convolution and dense layer with the multiply-accumulates
    of one example's forward pass."""
    sh = shapes(cfg)
    out = []
    for n in cfg["nodes"]:
        if n["op"] not in ("conv", "dense", "output"):
            continue
        src, dst = sh[n["in"][0]], sh[n["key"]]
        if n["op"] == "conv":
            macs = dst[0] * dst[1] * n["k"] * n["k"] * src[2] * n["out"]
        else:
            macs = _size(src) * n["out"]
        out.append({"key": n["key"], "op": n["op"], "macs": macs})
    return out


def forward_macs(cfg) -> int:
    """Multiply-accumulates of one example's forward pass."""
    return sum(l["macs"] for l in matmul_layers(cfg))


def train_flops_per_example(cfg) -> int:
    """Operations one example's training step needs: 2 per MAC, forward
    plus the two backward products of every convolution and dense layer
    (x3). The first layer's input gradient is counted although unused,
    recomputation is not counted, and elementwise work is left out."""
    return 3 * 2 * forward_macs(cfg)
