"""Operations and bytes of the training step of a hybrid decoder (one mixer
a layer: Mamba-2, latent experts, attention), from layer shapes: the count
its cell is held to. It imports nothing of the program.

``perfbench/lib/arch.py`` counts from a configuration file's node list; for
a language-model configuration that list is itself a count (every matrix
product of one sequence's forward pass as a ``dense`` node), and
``perfbench/tests/test_counts_hybrid_lm.py`` holds it equal to this
module's.

Needed, not executed: the scan is counted as the mathematics of its chunked
form at the configuration's ``chunk_size`` with the (Q, Q) products over
the triangle alone, attention over the triangle, the routed experts at the
pairs an even routing sends to the experts held; a block's replay and
elementwise work are not counted.
"""

from __future__ import annotations

from perfbench.lib import reference_hybrid_lm as ref


def keys_seen(t) -> int:
    """Sum over the queries of one sequence of the keys each sees."""
    return t * (t + 1) // 2


def scan_macs_per_token(cfg) -> float:
    """One Mamba layer's scan at chunk Q: C B^T (G N a pair) and the masked
    product with delta X (H P a pair) over the (Q + 1) / 2 pairs a position
    has inside its chunk, the chunk's state (H P N) and its read-out
    (H P N)."""
    d = ref.dims(cfg)
    h, p, g, n, q = (d["ssm_heads"], d["ssm_head_dim"], d["ssm_groups"],
                     d["ssm_state"], d["chunk"])
    return (g * n + h * p) * (q + 1) / 2 + 2 * h * p * n


def scan_bytes_per_token(cfg) -> int:
    """What one pass of one Mamba layer's scan has to move for a position:
    X, B and C read and y written in two bytes, delta read in four. The
    gate z is not the scan's: the program reads it under ``gate_norm``,
    whose seconds the share is not taken over."""
    d = ref.dims(cfg)
    hp = d["ssm_heads"] * d["ssm_head_dim"]
    return 2 * (2 * hp + 2 * d["ssm_groups"] * d["ssm_state"]) \
        + 4 * d["ssm_heads"]


def even_pairs_per_token(cfg) -> float:
    """(token, expert) pairs a token sends to the experts held under an
    even routing."""
    d = ref.dims(cfg)
    return d["top_k"] * d["experts_held"] / d["experts"]


def pair_macs(cfg) -> int:
    """One pair through one latent expert: two products."""
    d = ref.dims(cfg)
    return 2 * d["latent"] * d["expert_width"]


def forward_macs_per_sequence(cfg, seq) -> dict:
    """Multiply-accumulates of one sequence's forward pass by part:
    ``ssm_proj`` (in and out), ``ssm_scan``, ``router``, ``latent`` (down
    and up), ``routed`` (at an even routing's pairs), ``shared``,
    ``attention_proj`` (q, k, v, out), ``attention`` (scores and values
    over the triangle), ``head``."""
    d = ref.dims(cfg)
    c = d["hidden"]
    hp = d["ssm_heads"] * d["ssm_head_dim"]
    gn = d["ssm_groups"] * d["ssm_state"]
    hq, kv = d["heads"] * d["head_dim"], d["kv_heads"] * d["head_dim"]
    out = dict.fromkeys(("ssm_proj", "ssm_scan", "router", "latent", "routed",
                         "shared", "attention_proj", "attention", "head"), 0)
    for kind in d["pattern"]:
        if kind == "M":
            out["ssm_proj"] += seq * c * (2 * hp + 2 * gn + d["ssm_heads"]) \
                + seq * hp * c
            out["ssm_scan"] += int(seq * scan_macs_per_token(cfg))
        elif kind == "E":
            out["router"] += seq * c * d["experts"]
            out["latent"] += seq * 2 * c * d["latent"]
            out["routed"] += int(seq * even_pairs_per_token(cfg)
                                 * pair_macs(cfg))
            out["shared"] += seq * 2 * c * d["shared_width"]
        else:
            out["attention_proj"] += seq * c * (2 * hq + 2 * kv)
            out["attention"] += 2 * hq * keys_seen(seq)
    out["head"] = seq * c * d["vocab"]
    return out


def train_flops_per_sequence(cfg, seq) -> int:
    """2 per MAC, forward and the two backward products of every matrix
    product; recomputation and elementwise work are not counted."""
    return 6 * sum(forward_macs_per_sequence(cfg, seq).values())


def scan_seconds_at_peak(cfg, seq, sequences, peaks) -> float:
    """The time the needed work of every Mamba layer's scan takes at the
    chip's peaks over training steps of ``sequences`` sequences: the larger
    of its operations (forward and two backward products) over the bf16
    peak and its bytes (a forward and a backward pass) over the memory
    bandwidth."""
    d = ref.dims(cfg)
    tokens = sequences * seq * d["pattern"].count("M")
    return max(6 * tokens * scan_macs_per_token(cfg)
               / peaks["bf16_flops_per_s"],
               2 * tokens * scan_bytes_per_token(cfg)
               / peaks["hbm_bytes_per_s"])


def latent_experts_flops(cfg, pairs) -> int:
    """What the routed experts' grouped products need for ``pairs`` pairs
    over training steps: two products, forward and two backward each."""
    return 6 * pairs * pair_macs(cfg)
