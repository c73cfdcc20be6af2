"""Operations of the training step of a decoder whose attention reads a
learned selection of keys, from layer shapes: the count its cell is held
to. It imports nothing of the program.

``perfbench/lib/arch.py`` counts from a configuration file's node list; for
a language-model configuration that list is itself a count (every matrix
product of one sequence's forward pass as a ``dense`` node), and
``perfbench/tests/test_counts_sparse_lm.py`` holds it equal to this
module's.

Needed, not executed: the index scores are counted over the visible
triangle, the main scores and values over the SELECTED keys only. A form
that scores every visible key under a mask runs more and reads low on its
roofline share; the mask's extra work, the recomputation for the head-mean
weights and a block's replay are not counted.
"""

from __future__ import annotations

from perfbench.lib import reference_sparse_lm as ref


def keys_visible(t) -> int:
    """Sum over the queries of one sequence of the keys each sees."""
    return t * (t + 1) // 2


def keys_selected(t, top_k) -> int:
    """Sum over the queries of one sequence of the keys each attends:
    min(t + 1, top_k)."""
    if top_k >= t:
        return keys_visible(t)
    return top_k * (top_k + 1) // 2 + (t - top_k) * top_k


def selected_pair_macs(cfg) -> int:
    """One (query, selected key) pair over all main heads: the score and
    the value product."""
    d = ref.dims(cfg)
    return 2 * d["heads"] * d["head_dim"]


def index_pair_macs(cfg) -> int:
    """One (query, visible key) pair over all index heads: the score."""
    d = ref.dims(cfg)
    return d["index_heads"] * d["index_dim"]


def even_pairs_per_token(cfg) -> float:
    """(token, expert) pairs a token sends to the experts held under an
    even routing."""
    d = ref.dims(cfg)
    return d["top_k"] * d["experts_held"] / d["experts"]


def forward_macs_per_sequence(cfg, seq) -> dict:
    """Multiply-accumulates of one sequence's forward pass by part:
    ``attention_proj`` (q, k, v, out), ``index_proj`` (qI, kI, wI),
    ``index_scores`` (over the triangle), ``selected_attention`` (scores
    and values over selected keys), ``dense_mlp``, ``router``, ``routed``
    (at the pairs an even routing sends to the experts held), ``head``."""
    d = ref.dims(cfg)
    c, hd, h, kv = d["hidden"], d["head_dim"], d["heads"], d["kv_heads"]
    out = dict.fromkeys(("attention_proj", "index_proj", "index_scores",
                         "selected_attention", "dense_mlp", "router",
                         "routed", "head"), 0)
    for i in range(d["layers"]):
        out["attention_proj"] += seq * c * (2 * h * hd + 2 * kv * hd)
        out["index_proj"] += seq * c * (
            d["index_heads"] * d["index_dim"] + d["index_dim"]
            + d["index_heads"])
        out["index_scores"] += index_pair_macs(cfg) * keys_visible(seq)
        out["selected_attention"] += selected_pair_macs(cfg) * keys_selected(
            seq, d["index_top_k"])
        if d["mlp_types"][i] == "dense":
            out["dense_mlp"] += seq * 3 * c * d["dense_width"]
        else:
            out["router"] += seq * c * d["experts"]
            out["routed"] += int(seq * even_pairs_per_token(cfg)
                                 * 3 * c * d["expert_width"])
    out["head"] = seq * c * d["vocab"]
    return out


def train_flops_per_sequence(cfg, seq) -> int:
    """2 per MAC, forward and the two backward products of every matrix
    product; recomputation and elementwise work are not counted."""
    return 6 * sum(forward_macs_per_sequence(cfg, seq).values())


def selected_attention_flops(cfg, seq, sequences) -> int:
    """What attention over the selected keys needs over training steps of
    ``sequences`` sequences, all layers: forward and two backward products
    of the scores and of the values."""
    return 6 * sequences * forward_macs_per_sequence(
        cfg, seq)["selected_attention"]


def index_scores_flops(cfg, seq, sequences) -> int:
    """What the index scores need over training steps of ``sequences``
    sequences, all layers: forward and two backward products over every
    visible (query, key) pair."""
    return 6 * sequences * forward_macs_per_sequence(cfg, seq)["index_scores"]
