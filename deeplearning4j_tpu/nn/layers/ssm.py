"""A state-space mixer (Mamba-2, arXiv:2405.21060) for a chip that holds a
share of the heads: the layer that carries a state along the sequence, in a
form that is parallel over time.

A head's recurrence, from S_0 = 0 with a = -exp(A_log) and
delta = softplus(dt + dt_bias):

    S_t = exp(delta_t a) S_{t-1} + delta_t X_t B_t^T       (P x N)
    y_t = S_t C_t + D X_t

is computed in chunks (``ssd_scan``): inside a chunk of Q positions the
masked product ((C B^T) * decay) (delta X), a (Q, Q) tile a head; across
chunks the state each chunk starts from, carried by a ``lax.scan`` over the
T / Q chunks. No (T, T) array and no loop over positions, forward or
backward: autodiff of the chunked form is chunked too. The reference (DL4J
0.9.2) has no such layer; it is a plain ``Layer`` like the decoder's others.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer, require_dims
from deeplearning4j_tpu.nn.layers.decoder import (
    _add_wide, _seq_n_in, _w, _wide)
from deeplearning4j_tpu.util.remat import keep


def causal_conv(x, w, b):
    """Depthwise convolution over time: out[t] = sum_k w[k] x[t - (K-1) + k]
    + b, zeros before the sequence (tap K-1 reads the position itself).
    x (B, T, C), w (K, C), b (C,); the sum in float32, the result in x's
    dtype."""
    t, k = x.shape[1], w.shape[0]
    pad = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    out = sum(pad[:, j:j + t] * w[j] for j in range(k))
    return out + b.astype(jnp.float32)


def ssd_scan(x, dt, a, b, c, chunk):
    """The selective state-space recurrence in its chunked form.
    x (B, T, H, P); dt (B, T, H) float32, positive; a (H,) float32,
    negative; b, c (B, T, G, N), head j reading group j // (H / G).
    Returns y (B, T, H, P) float32 without the D term. The decays and their
    running sums are float32; the products run in x's dtype and accumulate
    in float32. T is padded to whole chunks with steps of dt = 0, which
    leave every state as it is and whose outputs are dropped."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    rep, q = h // g, min(chunk, t)
    pad = -t % q
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    nc = (t + pad) // q
    xq = x.reshape(bsz, nc, q, g, rep, p)
    bq, cq = b.reshape(bsz, nc, q, g, n), c.reshape(bsz, nc, q, g, n)
    dtq = dt.reshape(bsz, nc, q, h)
    # log decays summed from the chunk's start, position last: (B, nc, H, Q)
    cs = jnp.cumsum(dtq * a, axis=2).transpose(0, 1, 3, 2)
    dtx = (xq * dtq.reshape(bsz, nc, q, g, rep, 1)).astype(x.dtype)

    # inside a chunk: y_i += sum_{j<=i} (C_i . B_j) exp(cs_i - cs_j) dt_j X_j
    cb = jnp.einsum("bcign,bcjgn->bcgij", cq, bq,
                    preferred_element_type=jnp.float32)
    seen = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(seen, cs[..., :, None] - cs[..., None, :],
                              -jnp.inf)).reshape(bsz, nc, g, rep, q, q)
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp",
                   (cb[:, :, :, None] * decay).astype(x.dtype), dtx,
                   preferred_element_type=jnp.float32)

    # what a chunk adds to the state it hands on, decayed to its end
    to_end = jnp.exp(cs[..., -1:] - cs).transpose(0, 1, 3, 2).reshape(
        bsz, nc, q, g, rep, 1)
    local = jnp.einsum("bcjgrp,bcjgn->bcgrpn",
                       (dtx * to_end).astype(x.dtype), bq,
                       preferred_element_type=jnp.float32)

    # across chunks: the state each chunk starts from
    def carry(s, step):
        dec, add = step
        return dec[..., None, None] * s + add, s

    whole = jnp.exp(cs[..., -1]).reshape(bsz, nc, g, rep)
    _, start = jax.lax.scan(
        carry, jnp.zeros((bsz, g, rep, p, n), jnp.float32),
        (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(local, 1, 0)))
    start = jnp.moveaxis(start, 0, 1)                 # (B, nc, G, rep, P, N)
    off = jnp.einsum("bcign,bcgrpn->bcigrp", cq, start.astype(x.dtype),
                     preferred_element_type=jnp.float32)
    y = y + off * jnp.exp(cs).transpose(0, 1, 3, 2).reshape(
        bsz, nc, q, g, rep, 1)
    return y.reshape(bsz, t + pad, h, p)[:, :t]


@register_layer
@dataclass
class Mamba2Mixer(Layer):
    """Mamba-2 over (B, T, C): ``n_heads`` heads of ``head_dim`` channels
    in ``n_groups`` groups that share their B and C (state ``state_size``),
    a causal depthwise convolution of ``conv_kernel`` taps before the
    recurrence, a gate and an RMSNorm over each group's channels after it
    (gate first). No biases but the convolution's.

    A chip that holds a share of the heads builds the layer with the
    counts it holds, whole groups only (``n_heads`` a multiple of
    ``n_groups``): a group's norm needs all of its heads, and then a share
    is self-contained: the shares' outputs add up to the uncut layer's.

    Param keys, with H = n_heads, G = n_groups, X = H * head_dim + 2 G N:
    W_in (n_in, H * head_dim + X + H) giving [z | x B C | dt], conv_w
    (K, X), conv_b (X,), A_log, D, dt_bias (H,), norm_g (H * head_dim,),
    W_out (H * head_dim, n_out). State (training steps): ``tokens_total``
    as (low, high) uint32 words and ``decay_mean``, the mean over positions
    and heads of exp(delta a) in the last step: how far the state
    remembers. ``chunk_size`` is the tile of the chunked algorithm and has
    no effect on the result."""
    n_in: int = 0
    n_out: int = 0          # model dim (defaults to n_in)
    n_heads: int = 8
    head_dim: int = 0
    n_groups: int = 1
    state_size: int = 0
    conv_kernel: int = 4
    chunk_size: int = 128
    norm_eps: float = 1e-5

    def set_n_in(self, input_type):
        if self.n_in == 0:
            self.n_in = _seq_n_in(input_type)
        if self.n_out == 0:
            self.n_out = self.n_in

    def output_type(self, input_type):
        return InputType.recurrent(self.n_out or self.n_in,
                                   input_type.timeseries_length)

    def init(self, rng, dtype=jnp.float32):
        self.n_out = self.n_out or self.n_in
        require_dims(self, n_in=self.n_in, head_dim=self.head_dim,
                     state_size=self.state_size)
        h, g = self.n_heads, self.n_groups
        if g <= 0 or h % g:
            raise ValueError(f"n_heads={h} is not whole groups: a multiple "
                             f"of n_groups={g}")
        hp = h * self.head_dim
        xbc = hp + 2 * g * self.state_size
        k = jax.random.split(rng, 5)
        # a's 1..16 and steps log-uniform in [0.001, 0.1], as the family
        # starts them (Mamba-2's A_init_range and dt_min/dt_max)
        step = jnp.exp(jax.random.uniform(
            k[4], (h,), jnp.float32, math.log(1e-3), math.log(1e-1)))
        return {
            "W_in": _w(self, k[0], (self.n_in, hp + xbc + h), dtype),
            "conv_w": _w(self, k[1], (self.conv_kernel, xbc), dtype),
            "conv_b": jnp.zeros((xbc,), dtype),
            "A_log": jnp.log(jax.random.uniform(
                k[2], (h,), jnp.float32, 1.0, 16.0)).astype(dtype),
            "D": jnp.ones((h,), dtype),
            "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dtype),
            "norm_g": jnp.ones((hp,), dtype),
            "W_out": _w(self, k[3], (hp, self.n_out), dtype)}

    def init_state(self, dtype=jnp.float32):
        # one buffer each: the step donates its state
        return {"tokens_total": jnp.zeros((2,), jnp.uint32),
                "decay_mean": jnp.zeros((), jnp.float32)}

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        if mask is not None:
            raise ValueError("Mamba2Mixer takes no padding mask: pack "
                             "sequences to full length")
        bsz, t, _ = x.shape
        h, g, p, n = (self.n_heads, self.n_groups, self.head_dim,
                      self.state_size)
        hp, gn = h * p, g * n
        f32 = jnp.float32
        with jax.named_scope("in_proj"):
            # kept across a block's replay (util/remat.py): the layer's
            # largest product is not run again there
            zxbcdt = keep(x @ params["W_in"], "ssm_proj")
            z, xbc, dt = jnp.split(zxbcdt, [hp, 2 * hp + 2 * gn], axis=-1)
        with jax.named_scope("conv"):
            xbc = jax.nn.silu(causal_conv(
                xbc, params["conv_w"], params["conv_b"])).astype(x.dtype)
            xs = xbc[..., :hp].reshape(bsz, t, h, p)
            b = xbc[..., hp:hp + gn].reshape(bsz, t, g, n)
            c = xbc[..., hp + gn:].reshape(bsz, t, g, n)
        with jax.named_scope("scan"):
            delta = jax.nn.softplus(
                dt.astype(f32) + params["dt_bias"].astype(f32))
            a = -jnp.exp(params["A_log"].astype(f32))
            y = ssd_scan(xs, delta, a, b, c, self.chunk_size)
        with jax.named_scope("gate_norm"):
            y = y + params["D"].astype(f32)[:, None] * xs.astype(f32)
            y = y.reshape(bsz, t, hp) * jax.nn.silu(z.astype(f32))
            y = y.reshape(bsz, t, g, hp // g)
            y = y * jax.lax.rsqrt(
                jnp.mean(y * y, axis=-1, keepdims=True) + self.norm_eps)
            y = (y.reshape(bsz, t, hp)
                 * params["norm_g"].astype(f32)).astype(x.dtype)
        with jax.named_scope("out_proj"):
            out = y @ params["W_out"]
        if train and state:
            state = {
                "tokens_total": _add_wide(state["tokens_total"],
                                          _wide(bsz * t)),
                "decay_mean": jax.lax.stop_gradient(
                    jnp.exp(delta * a).mean())}
        return out, state

    def init_decode_state(self, params, batch, max_len, dtype=jnp.float32):
        raise NotImplementedError(
            "Mamba2Mixer trains through fit(); decoding it needs a "
            "recurrent state and the convolution's tail beside the "
            "attention layers' cache (ROADMAP, Reach)")
