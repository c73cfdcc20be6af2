"""Sequence/context parallelism: ring attention over a mesh axis.

The reference handles long sequences only via truncated BPTT (SURVEY.md §5);
this module provides the TPU-native long-context capability the build plan
requires: the sequence axis is sharded over the mesh, each device holds a
(B, T/n, H, Dh) block of Q/K/V, and K/V blocks rotate around the ring via
``lax.ppermute`` while each device accumulates its attention output with the
streaming-softmax (flash) recurrence — max/denominator carried in log-space,
so the result is EXACT full attention, never materializing the (T, T) score
matrix and overlapping compute with ICI transfers.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map


def _ring_attention_local(q, k, v, axis_name, causal):
    """Runs INSIDE shard_map. q/k/v: (B, Tl, H, Dh) local blocks."""
    # psum of 1 = the axis size (lax.axis_size is gone in this jax line)
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    B, Tl, H, Dh = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(Dh, q.dtype))
    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(r, carry):
        k_blk, v_blk, m, l, o = carry
        src = (my - r) % n                      # global block id of k_blk
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk) * scale
        if causal:
            qpos = my * Tl + jnp.arange(Tl)
            kpos = src * Tl + jnp.arange(Tl)
            s = jnp.where(qpos[None, None, :, None] >= kpos[None, None, None, :],
                          s, -jnp.inf)
        m_blk = s.max(-1)                       # (B,H,Tq)
        m_new = jnp.maximum(m, m_blk)
        # guard -inf - -inf = nan for fully-masked rows
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * corr + p.sum(-1)
        o_new = o * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, v_blk)
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        return (k_next, v_next, m_new, l_new, o_new)

    m0 = jnp.full((B, H, Tl), -jnp.inf, q.dtype)
    l0 = jnp.zeros((B, H, Tl), q.dtype)
    o0 = jnp.zeros((B, H, Tl, Dh), q.dtype)
    _, _, m, l, o = lax.fori_loop(0, n, step, (k, v, m0, l0, o0))
    out = o / jnp.maximum(l, 1e-30)[..., None]     # (B,H,Tq,Dh)
    return out.transpose(0, 2, 1, 3)               # (B,Tq,H,Dh)


def ring_attention(q, k, v, mesh: Mesh, axis: str = "seq", causal: bool = False):
    """Exact attention with the sequence axis sharded over ``mesh[axis]``.

    q/k/v: (B, T, H, Dh) global arrays (T divisible by mesh axis size).
    Returns (B, T, H, Dh) with the same sharding.
    """
    spec = P(None, axis, None, None)
    fn = shard_map(
        partial(_ring_attention_local, axis_name=axis, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    sharding = NamedSharding(mesh, spec)
    q, k, v = (jax.device_put(t, sharding) for t in (q, k, v))
    return fn(q, k, v)


class SequenceParallelAttention:
    """Module-level wrapper: applies a MultiHeadAttention layer's projections
    locally (sequence-sharded GEMMs) and its attention via the ring —
    the drop-in long-context execution path for the attention layer."""

    def __init__(self, layer, mesh: Mesh, axis: str = "seq"):
        self.layer = layer
        self.mesh = mesh
        self.axis = axis

    def __call__(self, params, x):
        B, T, C = x.shape
        q, k, v = self.layer._project(params, x)
        o = ring_attention(q, k, v, self.mesh, self.axis,
                           causal=self.layer.causal)
        o = o.reshape(B, T, self.layer.n_out) @ params["Wo"]
        if self.layer.has_bias:
            o = o + params["bo"]
        return o
