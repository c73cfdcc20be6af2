"""Operations of a sparse decoder's training step, from layer shapes: the
count the benchmark's language-model cells are held to. It imports nothing
of the program.

``perfbench/lib/arch.py`` counts from a configuration file's node list;
for a language-model configuration that list is itself a count (every
matrix product of one sequence's forward pass as a ``dense`` node), and
``perfbench/tests/test_counts_lm.py`` holds it equal to this module's.
"""

from __future__ import annotations

from perfbench.lib import reference_lm


def keys_seen(t, window=None) -> int:
    """Sum over the queries of one sequence of the keys each sees: the
    triangle, or the band of a window."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def forward_macs_per_sequence(cfg, seq) -> dict:
    """Multiply-accumulates of one sequence's forward pass by part:
    ``attention_proj`` (q, k, v, gate, out), ``attention`` (scores and
    values, inside the band or triangle only), ``dense_mlp``, ``router``,
    ``routed`` (at the pairs an even routing sends to the experts held),
    ``shared``, ``head``."""
    d = reference_lm.dims(cfg)
    c, hd, kv = d["hidden"], d["head_dim"], d["kv_heads"]
    out = dict.fromkeys(("attention_proj", "attention", "dense_mlp",
                         "router", "routed", "shared", "head"), 0)
    for i in range(d["layers"]):
        h = d["heads"][i]
        window = d["window"] if d["layer_types"][i] == "sliding_attention" \
            else None
        out["attention_proj"] += seq * c * (2 * h * hd + 2 * kv * hd
                                            + (h if d["head_gate"] else 0))
        out["attention"] += 2 * h * hd * keys_seen(seq, window)
        if d["mlp_types"][i] == "dense":
            out["dense_mlp"] += seq * 3 * c * d["dense_width"]
        else:
            out["router"] += seq * c * d["experts"]
            out["routed"] += int(seq * even_pairs_per_token(cfg)
                                 * pair_macs(cfg))
            out["shared"] += seq * 3 * c * d["shared_width"]
    out["head"] = seq * c * d["vocab"]
    return out


def even_pairs_per_token(cfg) -> float:
    """(token, expert) pairs a token sends to the experts held under an
    even routing."""
    d = reference_lm.dims(cfg)
    return d["top_k"] * d["experts_held"] / d["experts"]


def pair_macs(cfg) -> int:
    """One pair through one expert: three products."""
    d = reference_lm.dims(cfg)
    return 3 * d["hidden"] * d["expert_width"]


def train_flops_per_sequence(cfg, seq) -> int:
    """2 per MAC, forward and the two backward products of every matrix
    product; recomputation and elementwise work are not counted."""
    return 6 * sum(forward_macs_per_sequence(cfg, seq).values())


def grouped_matmul_flops(cfg, pairs) -> int:
    """What the routed experts' grouped products need for ``pairs`` pairs
    over training steps: three products, forward and two backward each."""
    return 6 * pairs * pair_macs(cfg)


def attention_flops(cfg, seq, sequences) -> int:
    """What attention's two products need over training steps of
    ``sequences`` sequences: the MACs inside the band or triangle, forward
    and two backward products each."""
    return 6 * sequences * forward_macs_per_sequence(cfg, seq)["attention"]
