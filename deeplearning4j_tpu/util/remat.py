"""Shared backward-rematerialization dispatch for the network containers.

See GlobalConf.remat (nn/conf/configuration.py) for the modes.
"""

from __future__ import annotations

import contextlib
import threading

import jax
from jax.ad_checkpoint import checkpoint_name

_MODES = (False, True, "full", "save_convs", "selective", "blocks")


def check_remat_mode(mode):
    """Fail fast on an invalid mode (builder/zoo entry points call this so
    a typo surfaces at configuration time, not at the first train step)."""
    if mode not in _MODES:
        raise ValueError(
            f"unknown remat mode {mode!r} "
            "(False | True | 'full' | 'save_convs' | 'selective' | 'blocks')")
    return mode


def remat_loss(loss_fn, mode):
    """``loss_fn`` wrapped per the configured remat ``mode``:
    False → unchanged; True/'full' → jax.checkpoint;
    'save_convs'/'selective' → checkpoint saving only named values: conv
    outputs (ConvolutionLayer tags them "conv_out") and BatchNorm's batch
    mean and inverse deviation (``batch_norm_train`` tags them "bn_stats":
    a few KB a layer that spare the replay a reduction over activations);
    'blocks' → unchanged here: the containers' forward wraps each block of
    ``remat_segments`` in a ``block_checkpoint`` of its own, so the backward
    pass keeps each block's input and the ``BLOCK_KEPT`` values, and
    replays the rest of one block at a time."""
    if not mode or mode == "blocks":
        return loss_fn          # 'blocks': the checkpoints are in the forward
    if mode in (True, "full"):
        return jax.checkpoint(loss_fn)
    if mode in ("save_convs", "selective"):
        return jax.checkpoint(
            loss_fn,
            policy=jax.checkpoint_policies.save_only_these_names(
                "conv_out", "bn_stats"))
    check_remat_mode(mode)                     # raises; not a known mode
    raise AssertionError("unreachable")


# What a block's replay under 'blocks' does not run again, by the name the
# decoder's layers (nn/layers/decoder.py, ops/flash_attention.py) give it
# through ``keep``: q, k after rotary and v; the attention kernel's output
# and log-sum-exp; the router's product, its top k with their indices, the
# sort of the pairs and the group sizes; round 0's grouped gate and up
# products; a SwiGLU's gate and up products (a dense layer's and a shared
# expert's); of an attention layer with an indexer the selection (the int8
# mask and its count: without it a replay scores and selects every key
# again) and the indexer loss's gradient by the indexer's queries, head
# weights and keys, taken in the forward pass (``index_loss``), so that the
# index products need no name: nothing reads them again (the loss's own
# value rides with them: the layer ties its output to it); of a Mamba mixer
# (nn/layers/ssm.py) the input projection's result [z | x B C | dt], two
# thirds of the layer's operations in 38 MB a layer at 8192 positions. Each
# costs the replay a kernel, a sort or a matrix product and is small beside
# what a step holds. Left to the replay: what is cheap to
# compute again (norms, the head gate, silu(g) * u, the gather of the expert
# rows) and the output projection, whose result is as large as q and spares
# one product.
BLOCK_KEPT = ("qkv", "attn_out", "routing", "expert_gate_up", "gate_up",
              "selection", "index_grads", "ssm_proj")

_counting = threading.local()


def keep(x, name):
    """``x`` under ``name`` for a checkpoint policy: the identity wherever
    no policy names it. While ``counting_kept`` is open its bytes are added
    to that count."""
    into = getattr(_counting, "into", None)
    if into is not None:
        into[name] = into.get(name, 0) + x.size * x.dtype.itemsize
    return checkpoint_name(x, name)


@contextlib.contextmanager
def counting_kept(into):
    """While open on this thread, ``keep`` sums the bytes it names into the
    dict ``into``, by name."""
    prev = getattr(_counting, "into", None)
    _counting.into = into
    try:
        yield into
    finally:
        _counting.into = prev


def block_checkpoint(block, kept=None):
    """``block`` as one replay unit of 'blocks': its arguments and the
    values inside it named ``kept`` (None: ``BLOCK_KEPT``) are kept for the
    backward pass; the rest is replayed."""
    return jax.checkpoint(
        block, policy=jax.checkpoint_policies.save_only_these_names(
            *(BLOCK_KEPT if kept is None else kept)))


def block_of(name):
    """The block a node or layer belongs to: its name up to the first
    ``.`` (``b3.attn`` -> ``b3``), or None for a name without one."""
    name = str(name)
    return name.split(".", 1)[0] if "." in name else None


def remat_segments(conf):
    """A graph's topological order cut into replay units for
    ``remat='blocks'``: ``[(names, outs)]`` where a run of consecutive nodes
    of one block is a unit with ``outs`` the activations that leave it
    (read by a later node, or a network output), and the nodes outside any
    block form runs with ``outs`` None, applied as they are."""
    order = [n for n in conf.topological_order
             if conf.nodes[n].kind != "input"]
    runs = []
    for n in order:
        b = block_of(n)
        if runs and runs[-1][0] == b:
            runs[-1][1].append(n)
        else:
            runs.append((b, [n]))
    out = []
    for b, names in runs:
        if b is None:
            out.append((names, None))
            continue
        inside = set(names)
        leaving = [n for n in names
                   if n in conf.network_outputs
                   or any(n in conf.nodes[m].inputs
                          for m in order if m not in inside)]
        out.append((names, leaving))
    return out
