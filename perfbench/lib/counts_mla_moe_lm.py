"""Operations of the training step of a latent-attention decoder on the
plain residual path with softmax-routed experts, from layer shapes: the
count its cell is held to. It imports nothing of the program.

``perfbench/lib/arch.py`` counts from a configuration file's node list; for
this configuration that list is itself a count (every matrix product of one
sequence's forward pass as a ``dense`` node), and
``perfbench/tests/test_counts_mla_moe_lm.py`` holds it equal to this
module's.

Needed, not executed: attention over the triangle (scores over
``qk_nope_head_dim + qk_rope_head_dim`` dims, values over ``v_head_dim``),
the routed experts at the pairs an even routing sends to the experts held;
a block's replay, the balance loss and elementwise work are not counted.
"""

from __future__ import annotations

from perfbench.lib import reference_mla_moe_lm as ref

PARTS = ("mla_proj", "attention", "dense", "router", "routed", "shared",
         "head")


def keys_seen(t) -> int:
    """Sum over the queries of one sequence of the keys each sees."""
    return t * (t + 1) // 2


def even_pairs_per_token(cfg) -> float:
    """(token, expert) pairs a token sends to the experts held under an
    even routing."""
    d = ref.dims(cfg)
    return d["top_k"] * d["experts_held"] / d["experts"]


def attention_macs_per_sequence(cfg, seq) -> int:
    """One latent-attention layer's causal scores and values."""
    d = ref.dims(cfg)
    return d["heads"] * (d["nope"] + d["rope_dim"] + d["v"]) * keys_seen(seq)


def forward_macs_per_sequence(cfg, seq) -> dict:
    """Multiply-accumulates of one sequence's forward pass by part
    (``PARTS``)."""
    d = ref.dims(cfg)
    c, h = d["hidden"], d["heads"]
    qk, kvr = d["nope"] + d["rope_dim"], d["kv_rank"]
    out = dict.fromkeys(PARTS, 0)
    for i in range(d["layers"]):
        out["mla_proj"] += seq * (c * h * qk + c * (kvr + d["rope_dim"])
                                  + kvr * h * (d["nope"] + d["v"])
                                  + h * d["v"] * c)
        out["attention"] += attention_macs_per_sequence(cfg, seq)
        if i < d["dense"]:
            out["dense"] += seq * 3 * c * d["dense_width"]
        else:
            out["router"] += seq * c * d["experts"]
            out["routed"] += int(seq * even_pairs_per_token(cfg) * 3 * c
                                 * d["expert_width"])
            out["shared"] += seq * 3 * c * d["shared_width"]
    out["head"] = seq * c * d["vocab"]
    return out


def train_flops_per_sequence(cfg, seq) -> int:
    """2 per MAC, forward and the two backward products of every matrix
    product; recomputation and elementwise work are not counted."""
    return 6 * sum(forward_macs_per_sequence(cfg, seq).values())


def attention_flops(cfg, seq, sequences) -> int:
    """What every latent-attention layer's scores and values need over
    training steps of ``sequences`` sequences: forward and two backward
    products."""
    d = ref.dims(cfg)
    return 6 * sequences * d["layers"] * attention_macs_per_sequence(cfg, seq)
