"""Layers of a sparse decoder block: RMSNorm, rotary grouped-query
attention with a window, a head gate or a learned selection of keys (an
indexer with a loss of its own), a SwiGLU MLP, and a dropless top-k expert
layer that holds a share of the experts (SwiGLU or relu^2 experts, softmax
or sigmoid scores, on the hidden width or inside a latent). The state-space
mixer of a hybrid decoder is nn/layers/ssm.py.

The reference (DL4J 0.9.2) has none of them. Each is a plain ``Layer``: the
containers hold it, ``model_serializer`` writes it, ``util/scopes.py`` names
it. Sequences are (B, T, C) as everywhere in ``nn/layers``.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers.base import Layer, register_layer, require_dims
from deeplearning4j_tpu.nn.weights import init_weights
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.util.remat import keep


def _seq_n_in(input_type):
    return input_type.size or input_type.flat_size()


def _w(layer, rng, shape, dtype):
    return init_weights(rng, shape, layer.weight_init or "xavier",
                        layer.dist, dtype)


# ------------------------------------------------------------------ RMSNorm

def rms_norm(x, gamma, eps):
    """x / sqrt(mean(x^2) + eps) * gamma, the statistics in float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * gamma.astype(jnp.float32)).astype(x.dtype)


@register_layer
@dataclass
class RMSNorm(Layer):
    """Root-mean-square norm over the feature axis: one gain, no mean, no
    bias."""
    n_in: int = 0
    eps: float = 1e-6

    def set_n_in(self, input_type):
        if self.n_in == 0:
            self.n_in = _seq_n_in(input_type)

    def init(self, rng, dtype=jnp.float32):
        require_dims(self, n_in=self.n_in)
        return {"gamma": jnp.ones((self.n_in,), dtype)}

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        return rms_norm(x, params["gamma"], self.eps), state


# ------------------------------------------------------------------- SwiGLU

def swiglu(x, wg, wu, wd):
    """(silu(x Wg) * (x Wu)) Wd. The two products come out in x's dtype (a
    float32 copy of a (tokens, width) activation is the step's largest
    buffer); the gate itself is taken in float32. The two products are
    kept across a block's replay (util/remat.py)."""
    g, u = keep(jnp.dot(x, wg), "gate_up"), keep(jnp.dot(x, wu), "gate_up")
    h = jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
    return jnp.dot(h.astype(x.dtype), wd)


@register_layer
@dataclass
class SwiGLU(Layer):
    """Gated MLP without biases. Param keys: Wg, Wu (n_in, width) and Wd
    (width, n_out)."""
    n_in: int = 0
    n_out: int = 0          # defaults to n_in
    width: int = 0

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
        require_dims(self, n_in=self.n_in, width=self.width)
        k = jax.random.split(rng, 3)
        return {"Wg": _w(self, k[0], (self.n_in, self.width), dtype),
                "Wu": _w(self, k[1], (self.n_in, self.width), dtype),
                "Wd": _w(self, k[2], (self.width, self.n_out), dtype)}

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        return swiglu(x, params["Wg"], params["Wu"], params["Wd"]), state


# ---------------------------------------------------------------- attention

def rotary_inv_freq(rotary):
    """Inverse frequencies of the rotated pairs, a Python list. ``rotary``:
    ``theta``, ``dims`` (how many leading dims of a head rotate) and, for
    YaRN, ``factor``, ``original_max_position``, ``beta_fast``,
    ``beta_slow`` (arXiv:2309.00071: frequencies that turn fewer than
    beta_slow times over the original context are divided by ``factor``,
    those that turn more than beta_fast times are kept, a linear ramp
    between)."""
    dims, theta = int(rotary["dims"]), float(rotary["theta"])
    half = dims // 2
    freq = [theta ** (-2.0 * i / dims) for i in range(half)]
    factor = rotary.get("factor")
    if not factor:
        return freq
    orig = float(rotary["original_max_position"])

    def correction_dim(turns):
        return dims * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(rotary["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rotary["beta_slow"]))),
               dims - 1)
    if low == high:
        high += 0.001
    out = []
    for i, f in enumerate(freq):
        keep = 1.0 - min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(f / factor * (1.0 - keep) + f * keep)
    return out


def apply_rotary(x, rotary):
    """Rotate the first ``rotary['dims']`` dims of every head in halves
    (x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin); the rest pass through.
    x: (B, H, T, Dh). ``attention_factor`` multiplies cos and sin."""
    t, dims = x.shape[2], int(rotary["dims"])
    half = dims // 2
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(rotary_inv_freq(rotary), jnp.float32)
    scale = float(rotary.get("attention_factor") or 1.0)
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:dims]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x32[..., dims:]], axis=-1)
    return out.astype(x.dtype)


def banded_attention(q, k, v, window=None, scale=None):
    """The plain path: causal grouped-query attention by a masked softmax
    over the whole (T, T) score matrix. Shapes and ``scale`` as
    ``gqa_flash_attention``."""
    b, hq, t, dh = q.shape
    hkv = k.shape[1]
    qg = q.reshape(b, hkv, hq // hkv, t, dh)
    s = jnp.einsum("bkgqd,bksd->bkgqs", qg, k,
                   preferred_element_type=jnp.float32)
    s = s / math.sqrt(dh) if scale is None else s * scale
    i = jnp.arange(t)[:, None]
    j = jnp.arange(t)[None, :]
    ok = i >= j
    if window is not None:
        ok = ok & (i - j < window)
    p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqs,bksd->bkgqd", p, v).reshape(b, hq, t, -1)


# below this length the score matrix is small and XLA's fused path is used
_KERNEL_MIN_SEQ = 1024


def _kernels_on(t):
    """The rule that routes this module's attention to its Pallas kernels,
    at ``t`` positions: helpers on, no partitioned trace (XLA will not
    partition a Mosaic call), and a length that pays (any, interpreted)."""
    from deeplearning4j_tpu import ops
    from deeplearning4j_tpu.exec.executor import tracing_partitioned
    return (ops.helpers_enabled() and not tracing_partitioned()
            and (ops.interpret_mode() or t >= _KERNEL_MIN_SEQ))


# ---------------------------------------------- a learned selection of keys
# An indexer (J small heads over one shared key head) scores every visible
# key of a query, I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]); the query
# attends the ``top_k`` keys of largest score (all of them while t < top_k;
# a tie at the last place goes to the lower position). The selection is not
# differentiable: the indexer learns from the KL of its distribution over
# the selected keys to the main attention's head-mean weights there
# (arXiv:2512.02556, the sparse training stage), and the main parameters
# never see it. Scores exist a chunk of query rows at a time, and only over
# the keys a chunk can see.

_INDEX_ROWS = 512          # query rows scored at once
_INDEX_GROUPS = 8          # row ranges, each scoring keys up to its end


def _row_chunks(t, rows=None):
    """(rows of a chunk, [(first chunk, chunks, key extent)]): the T query
    rows in chunks, the chunks in at most ``_INDEX_GROUPS`` runs; a run
    scores the keys before its own end, so that about half of the square
    above the diagonal is never computed."""
    if rows is None or t % rows:
        rows = next((r for r in (_INDEX_ROWS, 256, 128, 64, 32, 16, 8)
                     if t % r == 0), t)
    n = t // rows
    per = -(-n // _INDEX_GROUPS)
    return rows, [(c, min(per, n - c), (c + min(per, n - c)) * rows)
                  for c in range(0, n, per)]


def index_scores_xla(qi, wi, ki):
    """The plain form of ``index_scores``: the per-head product as one
    (J, R, S) float32 array, then ``relu``, the head weights and the sum
    over heads."""
    s = jnp.einsum("rjd,sd->jrs", qi, ki, preferred_element_type=jnp.float32)
    return (jax.nn.relu(s) * wi.T[:, :, None]).sum(axis=0)


@jax.named_scope("index")
def index_scores(qi, wi, ki):
    """I = sum_j w_j relu(q_j . k): qi (R, J, D), wi (R, J) float32,
    ki (S, D) -> (R, S) float32. Under the scope ``index`` wherever it is
    called (for the selection, and for the indexer's loss with its
    derivative), so that a trace finds every index product there. By the
    kernel of ops/index_scores.py, which makes each tile in VMEM, where the
    layer's rule for kernels holds at this key extent and the kernel's
    shape screen accepts; else by ``index_scores_xla``. Which form a step
    traced is counted (``index_scores.counting_calls``)."""
    from deeplearning4j_tpu import ops
    from deeplearning4j_tpu.ops import index_scores as kernel
    (r, j, d), s = qi.shape, ki.shape[0]
    if _kernels_on(s) and kernel.supported(r, j, d, s, qi.dtype.itemsize):
        kernel.note_call("kernel")
        return kernel.index_scores(qi, wi, ki, ops.interpret_mode())
    kernel.note_call("xla")
    return index_scores_xla(qi, wi, ki)


@jax.jit
def _select_rows(scores, first, top_k):
    """The selection of one chunk of query rows: scores (R, S) float32 of
    rows ``first..first+R-1`` against keys 0..S-1 -> (R, S) int8, 1 on the
    ``top_k`` visible keys of largest score of each row (every visible key
    of a row that sees fewer), a tie at the last place to the lower
    position. Exact, by bisection on the scores' bits: 32 counting passes
    over the chunk find the top_k-th largest value of every row, and where
    some row has more keys at that value than places left, 14 more find
    the position up to which they are taken."""
    r, s = scores.shape
    kpos = jnp.arange(s, dtype=jnp.int32)[None, :]
    vis = kpos <= first + jnp.arange(r, dtype=jnp.int32)[:, None]
    # float32 -> uint32 of the same order (-0.0 made +0.0 first); an
    # invisible key is 0, under every visible one
    b = jax.lax.bitcast_convert_type(
        jnp.where(scores == 0, 0.0, scores).astype(jnp.float32), jnp.uint32)
    u = jnp.where(vis, jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(1 << 31)),
                  jnp.uint32(0))

    def count(ok):
        return ok.sum(axis=-1, keepdims=True, dtype=jnp.int32)

    def value_bit(i, res):
        cand = res | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        return jnp.where(count(u >= cand) >= top_k, cand, res)

    # the largest value that top_k or more keys reach (0: the row sees
    # fewer than top_k)
    at = jax.lax.fori_loop(0, 32, value_bit, jnp.zeros((r, 1), jnp.uint32))
    over, tie = u > at, (u == at) & vis
    need = top_k - count(over)

    def lowest(tie):
        bits = max(int(s - 1).bit_length(), 1)

        def pos_bit(i, x):
            cand = x | (jnp.int32(1) << (bits - 1 - i))
            return jnp.where(count(tie & (kpos < cand)) < need, cand, x)

        # the largest position with fewer than ``need`` ties before it is
        # the need-th tie's own
        x = jax.lax.fori_loop(0, bits, pos_bit, jnp.zeros((r, 1), jnp.int32))
        return tie & (kpos <= x)

    tie = jax.lax.cond(jnp.any(count(tie) > need), lowest, lambda t: t, tie)
    return (over | tie).astype(jnp.int8)


def selected_keys_mask(qi, wi, ki, top_k, rows=None):
    """The selection as a mask: qi (B, T, J, D), wi (B, T, J), ki (B, T, D)
    -> (B, T, T) int8, 1 where the query (row) attends the key (column),
    0 above the diagonal; chunk by chunk, the scores of one chunk alive at
    a time."""
    t = qi.shape[1]
    rows, groups = _row_chunks(t, rows)

    def one(args):
        q1, w1, k1 = args
        out = []
        for c0, n, extent in groups:
            firsts = (c0 + jnp.arange(n, dtype=jnp.int32)) * rows

            def chunk(xs, extent=extent):
                qc, wc, first = xs
                if extent <= top_k:      # these rows see top_k keys or fewer
                    kpos = jnp.arange(extent, dtype=jnp.int32)[None, :]
                    return (kpos <= first + jnp.arange(
                        rows, dtype=jnp.int32)[:, None]).astype(jnp.int8)
                return _select_rows(index_scores(qc, wc, k1[:extent]), first,
                                    top_k)

            m = jax.lax.map(chunk, (
                q1[c0 * rows:(c0 + n) * rows].reshape(n, rows, *q1.shape[1:]),
                w1[c0 * rows:(c0 + n) * rows].reshape(n, rows, -1), firsts))
            out.append(jnp.pad(m.reshape(n * rows, extent),
                               ((0, 0), (0, t - extent))))
        return jnp.concatenate(out, axis=0)

    return jax.lax.map(one, (qi, wi, ki))


def _kl_rows(qc, wc, k1, mc, pc):
    """Sum over a chunk's rows of KL(P || softmax of the index scores over
    the selected keys)."""
    sel = mc != 0
    logq = jax.nn.log_softmax(
        jnp.where(sel, index_scores(qc, wc, k1), -jnp.inf), axis=-1)
    return jnp.where(sel, jax.scipy.special.xlogy(pc, pc)
                     - pc * jnp.where(sel, logq, 0.0), 0.0).sum()


def _index_kl(qi, wi, ki, mask, p, rows, grads):
    """mean over the B*T queries of KL(P[t] || indexer[t]) on the selected
    keys and, with ``grads``, its gradient by qi, wi, ki, chunk by chunk:
    a chunk's scores are made, differentiated and dropped before the
    next's."""
    b, t = qi.shape[:2]
    rows, groups = _row_chunks(t, rows)

    def one(args):
        q1, w1, k1, m1, p1 = args
        total, dk = 0.0, jnp.zeros(k1.shape, jnp.float32)
        dq, dw = [], []
        for c0, n, extent in groups:
            span = slice(c0 * rows, (c0 + n) * rows)
            xs = (q1[span].reshape(n, rows, *q1.shape[1:]),
                  w1[span].reshape(n, rows, -1),
                  m1[span, :extent].reshape(n, rows, extent),
                  p1[span, :extent].reshape(n, rows, extent))
            ks = k1[:extent]
            if not grads:
                total = total + jax.lax.map(
                    lambda x: _kl_rows(x[0], x[1], ks, x[2], x[3]), xs).sum()
                continue

            def chunk(acc, x):
                val, back = jax.vjp(
                    lambda a, w, k: _kl_rows(a, w, k, x[2], x[3]),
                    x[0], x[1], ks)
                ga, gw, gk = back(jnp.ones((), val.dtype))
                return acc + gk.astype(jnp.float32), (val, ga, gw)

            gk, (val, ga, gw) = jax.lax.scan(
                chunk, jnp.zeros(ks.shape, jnp.float32), xs)
            total = total + val.sum()
            dk = dk.at[:extent].add(gk)
            dq.append(ga.reshape(n * rows, *q1.shape[1:]))
            dw.append(gw.reshape(n * rows, -1))
        if not grads:
            return total
        return total, jnp.concatenate(dq), jnp.concatenate(dw), dk

    out = jax.lax.map(one, (qi, wi, ki, mask, p))
    scale = 1.0 / (b * t)
    if not grads:
        return out.sum() * scale
    loss, dq, dw, dk = out
    return loss.sum() * scale, (
        (dq * scale).astype(qi.dtype), (dw * scale).astype(wi.dtype),
        (dk * scale).astype(ki.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def index_loss(qi, wi, ki, mask, p, rows=None):
    """The indexer's own loss: the mean over queries of the KL from the
    main attention's head-mean weights ``p`` (B, T, T) to the softmax of the
    index scores, both over the keys ``mask`` selects. qi, wi, ki as
    ``selected_keys_mask``. Differentiable in qi, wi, ki alone. Its
    gradient is taken chunk by chunk in the forward pass, where each chunk's
    scores exist anyway, and kept by name (util/remat.py), so that neither
    the backward pass nor a block's replay scores a key again."""
    return _index_kl(qi, wi, ki, mask, p, rows, False)


def _index_loss_fwd(qi, wi, ki, mask, p, rows):
    # the value is kept beside its gradient: a replay that reads it (the
    # layer ties its output to it) does not take the loss again
    loss, g = _index_kl(qi, wi, ki, mask, p, rows, True)
    return keep(loss, "index_grads"), tuple(keep(a, "index_grads") for a in g)


def _index_loss_bwd(rows, g, ct):
    return tuple((ct * a.astype(jnp.float32)).astype(a.dtype) for a in g) \
        + (None, None)


index_loss.defvjp(_index_loss_fwd, _index_loss_bwd)


def selected_attention(q, k, v, mask):
    """The plain path of attention over selected keys: a masked softmax over
    the whole (T, T) score matrix. Shapes as ``gqa_selected_attention``.
    Returns (output, head-mean weights (B, T, T) float32)."""
    b, hq, t, dh = q.shape
    hkv = k.shape[1]
    qg = q.reshape(b, hkv, hq // hkv, t, dh)
    s = jnp.einsum("bkgqd,bksd->bkgqs", qg, k,
                   preferred_element_type=jnp.float32) / math.sqrt(dh)
    p = jax.nn.softmax(
        jnp.where((mask != 0)[:, None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("bkgqs,bksd->bkgqd", p.astype(v.dtype), v)
    return o.reshape(b, hq, t, dh), p.mean(axis=(1, 2))


def _wide(n):
    """A count known at trace time as a (lo, hi) pair of uint32."""
    return jnp.asarray([n & 0xFFFFFFFF, n >> 32], jnp.uint32)


def _add_wide(a, b):
    """The sum of two (lo, hi) pairs of uint32."""
    lo = a[0] + b[0]
    return jnp.stack([lo, a[1] + b[1] + (lo < a[0]).astype(jnp.uint32)])


def _fold_wide(per):
    """The sum of up to 65,536 uint32 counts as a (lo, hi) pair of uint32."""
    lo = (per & 0xFFFF).sum(dtype=jnp.uint32)
    hi = (per >> 16).sum(dtype=jnp.uint32)
    return _add_wide(jnp.stack([lo, jnp.zeros_like(lo)]),
                     jnp.stack([hi << 16, hi >> 16]))


@register_layer
@dataclass
class RotaryGQAttention(Layer):
    """Causal self-attention over (B, T, C) with ``n_heads`` query heads
    reading ``n_kv_heads`` key/value heads (query head h reads kv head
    ``h // (n_heads / n_kv_heads)``), rotary positions on q and k, an
    optional ``window`` (key j is seen from query i only if i - j < window)
    and an optional sigmoid gate per head on the attention output
    (arXiv:2505.06708). No biases. Param keys: Wq (n_in, H*Dh), Wk, Wv
    (n_in, Hkv*Dh), Wo (H*Dh, n_out), Wgate (n_in, H) with ``head_gate``,
    q_gamma, k_gamma (Dh,) with ``qk_norm`` (an RMSNorm over each head of q
    and of k before rotary).

    ``rotary``: a dict as ``rotary_inv_freq`` reads it, or None.
    The head count is the layer's own: layers of one model may differ.

    ``indexer``: ``{"heads": J, "head_dim": D, "top_k": n}`` gives the layer
    a learned selection of keys (see the section above): every query attends
    the ``top_k`` visible keys its indexer scores highest, one selection for
    all heads. Param keys WqI (n_in, J*D), WkI (n_in, D), WwI (n_in, J),
    kI_gamma, kI_beta (D,: a LayerNorm on the one index key head). The
    indexer reads the layer's input cut from the graph and learns from
    ``index_loss`` alone, which the layer hands back in its state under
    ``loss_state`` for the container to add to the step's loss; the other
    parameters never see that term. State (training steps): ``index_loss``
    of the last step, and ``keys_selected``, ``keys_visible`` of the last
    step and their running sums ``keys_selected_total``,
    ``keys_visible_total``, each count as (low, high) uint32 words. A
    training step of a layer with an indexer needs that state."""
    n_in: int = 0
    n_out: int = 0          # model dim (defaults to n_in)
    n_heads: int = 4
    n_kv_heads: int = 1
    head_dim: int = 0
    window: Optional[int] = None
    rotary: Optional[dict] = None
    head_gate: bool = False
    qk_norm: bool = False
    norm_eps: float = 1e-6
    indexer: Optional[dict] = None

    @property
    def loss_state(self):
        return "index_loss" if self.indexer else None

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
        require_dims(self, n_in=self.n_in, head_dim=self.head_dim)
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"n_heads={self.n_heads} is not a multiple of "
                             f"n_kv_heads={self.n_kv_heads}")
        if self.rotary and int(self.rotary["dims"]) > self.head_dim:
            raise ValueError("rotary dims exceed head_dim")
        if self.indexer and self.window is not None:
            raise ValueError("a layer selects its keys by an indexer or by "
                             "a window, not both")
        k = jax.random.split(rng, 8)
        hq, hkv = self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim
        p = {"Wq": _w(self, k[0], (self.n_in, hq), dtype),
             "Wk": _w(self, k[1], (self.n_in, hkv), dtype),
             "Wv": _w(self, k[2], (self.n_in, hkv), dtype),
             "Wo": _w(self, k[3], (hq, self.n_out), dtype)}
        if self.head_gate:
            p["Wgate"] = _w(self, k[4], (self.n_in, self.n_heads), dtype)
        if self.qk_norm:
            p["q_gamma"] = jnp.ones((self.head_dim,), dtype)
            p["k_gamma"] = jnp.ones((self.head_dim,), dtype)
        if self.indexer:
            j, d = int(self.indexer["heads"]), int(self.indexer["head_dim"])
            p.update(WqI=_w(self, k[5], (self.n_in, j * d), dtype),
                     WkI=_w(self, k[6], (self.n_in, d), dtype),
                     WwI=_w(self, k[7], (self.n_in, j), dtype),
                     kI_gamma=jnp.ones((d,), dtype),
                     kI_beta=jnp.zeros((d,), dtype))
        return p

    def init_state(self, dtype=jnp.float32):
        if not self.indexer:
            return {}
        # one buffer each: the step donates its state
        return {"index_loss": jnp.zeros((), jnp.float32),
                "keys_selected": jnp.zeros((2,), jnp.uint32),
                "keys_visible": jnp.zeros((2,), jnp.uint32),
                "keys_selected_total": jnp.zeros((2,), jnp.uint32),
                "keys_visible_total": jnp.zeros((2,), jnp.uint32)}

    def _kernel_path(self, t):
        from deeplearning4j_tpu.ops.flash_attention import gqa_supported
        return _kernels_on(t) and gqa_supported(
            t, self.head_dim, self.n_heads, self.n_kv_heads)

    def _attend(self, q, k, v):
        from deeplearning4j_tpu import ops
        from deeplearning4j_tpu.ops.flash_attention import gqa_flash_attention
        if self._kernel_path(q.shape[2]):
            return gqa_flash_attention(q, k, v, self.window, None,
                                       ops.interpret_mode())
        return banded_attention(q, k, v, self.window)

    def _attend_selected(self, q, k, v, mask):
        """(output, head-mean weights) over the keys ``mask`` selects."""
        from deeplearning4j_tpu import ops
        from deeplearning4j_tpu.ops.flash_attention import (
            gqa_head_mean_probs, gqa_selected_attention)
        if not self._kernel_path(q.shape[2]):
            return selected_attention(q, k, v, mask)
        o, lse = gqa_selected_attention(q, k, v, mask, None,
                                        ops.interpret_mode())
        with jax.named_scope("index_loss"):
            p = gqa_head_mean_probs(*map(jax.lax.stop_gradient, (q, k, lse)),
                                    mask, None, ops.interpret_mode())
        return o, p

    def _index(self, params, x):
        """The indexer's queries (B, T, J, D), head weights (B, T, J)
        float32 and keys (B, T, D), from the layer's input cut from the
        graph."""
        b, t, _ = x.shape
        j, d = int(self.indexer["heads"]), int(self.indexer["head_dim"])
        x = jax.lax.stop_gradient(x)
        qi = (x @ params["WqI"]).reshape(b, t, j, d).transpose(0, 2, 1, 3)
        ki = (x @ params["WkI"]).astype(jnp.float32)
        ki = ki - ki.mean(axis=-1, keepdims=True)
        ki = ki * jax.lax.rsqrt(
            jnp.mean(ki * ki, axis=-1, keepdims=True) + self.norm_eps)
        ki = (ki * params["kI_gamma"].astype(jnp.float32)
              + params["kI_beta"].astype(jnp.float32)).astype(x.dtype)
        if self.rotary:
            rot = dict(self.rotary, dims=d)
            qi = apply_rotary(qi, rot)
            ki = apply_rotary(ki[:, None], rot)[:, 0]
        wi = jnp.dot(x, params["WwI"], preferred_element_type=jnp.float32) \
            / math.sqrt(j * d)
        return qi.transpose(0, 2, 1, 3), wi, ki

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        if mask is not None:
            raise ValueError("RotaryGQAttention takes no padding mask: pack "
                             "sequences to full length")
        b, t, _ = x.shape

        def heads(w, n, gamma=None):
            h = (x @ w).reshape(b, t, n, self.head_dim).transpose(0, 2, 1, 3)
            return h if gamma is None else rms_norm(h, gamma, self.norm_eps)

        norm = self.qk_norm
        q = heads(params["Wq"], self.n_heads, params["q_gamma"] if norm else None)
        k = heads(params["Wk"], self.n_kv_heads,
                  params["k_gamma"] if norm else None)
        v = heads(params["Wv"], self.n_kv_heads)
        if self.rotary:
            q, k = apply_rotary(q, self.rotary), apply_rotary(k, self.rotary)
        # what the kernel's backward pass reads: a block's replay runs
        # neither the three projections nor rotary again (util/remat.py)
        q, k, v = (keep(a, "qkv") for a in (q, k, v))
        if self.indexer:
            o, state = self._apply_selected(params, x, q, k, v, state, train)
        else:
            with jax.named_scope("attend"):
                o = self._attend(q, k, v).transpose(0, 2, 1, 3)  # (B, T, H, Dh)
        if self.head_gate:
            gate = jax.nn.sigmoid(jnp.dot(
                x, params["Wgate"], preferred_element_type=jnp.float32))
            o = o * gate[..., None].astype(o.dtype)
        return o.reshape(b, t, -1) @ params["Wo"], state

    def _apply_selected(self, params, x, q, k, v, state, train):
        """Attention over the keys the indexer selects, and on a training
        step the indexer's loss and the counters, in the layer's state."""
        b, _, t, _ = q.shape
        top_k = int(self.indexer["top_k"])
        with jax.named_scope("index"):
            qi, wi, ki = self._index(params, x)
        if train and not state:
            raise ValueError(
                "a training step of a layer with an indexer needs the "
                "layer's state (init_state()): without it the indexer's "
                "loss is dropped and the indexer never learns")
        visible = _wide(b * (t * (t + 1) // 2))
        with jax.named_scope("select"):
            if t <= top_k:          # every query attends every visible key
                sel = jnp.tril(jnp.ones((t, t), jnp.int8))[None].repeat(b, 0)
                selected = visible
            else:
                # the selection is kept across a block's replay
                # (util/remat.py): no key is scored or counted again there
                sel = keep(selected_keys_mask(
                    *map(jax.lax.stop_gradient, (qi, wi, ki)), top_k),
                    "selection")
                # a sequence's own count fits one word, the batch's sum
                # need not
                selected = keep(_fold_wide(sel.sum(axis=(1, 2),
                                                   dtype=jnp.uint32)),
                                "selection")
        with jax.named_scope("attend"):
            if t <= top_k and not train:
                return self._attend(q, k, v).transpose(0, 2, 1, 3), state
            o, p = self._attend_selected(q, k, v, sel)
            o = o.transpose(0, 2, 1, 3)
        if not train:
            return o, state
        with jax.named_scope("index_loss"):
            loss = index_loss(qi, wi, ki, sel, jax.lax.stop_gradient(p))
            # the layer goes on when its loss is taken: left free, the
            # compiler may schedule it after later layers' forward passes
            # and hold p, (B, T, T) float32, until then
            o, loss = jax.lax.optimization_barrier((o, loss))
        return o, {
            "index_loss": loss.astype(jnp.float32),
            "keys_selected": selected,
            "keys_visible": visible,
            "keys_selected_total": _add_wide(state["keys_selected_total"],
                                             selected),
            "keys_visible_total": _add_wide(state["keys_visible_total"],
                                            visible)}

    def init_decode_state(self, params, batch, max_len, dtype=jnp.float32):
        raise NotImplementedError(
            "RotaryGQAttention trains through fit(); decoding it needs a "
            "cache that keeps a window for some layers and every position "
            "for others, and the indexer's keys beside it (ROADMAP, Reach)")


# ------------------------------------------------------------ expert layer

# the sorted-pair buffer of one round holds this many times the pairs an
# even routing would send to the experts held
_ROUND_SLACK = 1.5


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _top_k(s, k):
    """``jax.lax.top_k`` whose values and indices are kept across a block's
    replay (util/remat.py). ``top_k``'s own derivative reads the indices
    that it returns itself, so a name put on them afterwards keeps nothing
    and the replay would run it again; here the backward pass reads the
    named indices."""
    return tuple(jax.lax.top_k(s, k))


def _top_k_fwd(s, k):
    val, idx = (keep(a, "routing") for a in jax.lax.top_k(s, k))
    return (val, idx), (idx, jax.ShapeDtypeStruct(s.shape, s.dtype))


def _top_k_bwd(k, res, ct):
    idx, s = res
    return jax.linear_transpose(
        lambda t: jnp.take_along_axis(t, idx, axis=-1), s)(ct[0])


_top_k.defvjp(_top_k_fwd, _top_k_bwd)


def route_top_k(x2, wr, k, norm_topk, routed_scale, score="softmax",
                bias=None, with_scores=False):
    """Scores over all experts, the k chosen, their weights. x2: (N, C).
    ``score``: ``"softmax"`` over the experts, the k largest chosen and
    weighed by their scores; or ``"sigmoid"``, a score an expert, the k
    largest of score + ``bias`` chosen (a selection bias (E,), None: zero)
    and weighed by the score alone: the bias chooses and does not weigh,
    and no gradient reaches it. Returns (idx (N, k) int32, p (N, k)
    float32), and with ``with_scores`` the scores over all experts (N, E)
    float32 after them. The router's product is kept across a block's replay
    (util/remat.py), named before the softmax or sigmoid because their
    derivatives read their own results, not a name put on them; the top k
    are kept by ``_top_k``, or, where nothing differentiates the choice
    (``sigmoid``), the indices by name."""
    logits = keep(jnp.dot(x2, wr, preferred_element_type=jnp.float32),
                  "routing")
    if score == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
        val, idx = _top_k(s, k)
    else:
        s = jax.nn.sigmoid(logits)
        pick = s if bias is None else s + bias.astype(jnp.float32)
        idx = keep(jax.lax.top_k(jax.lax.stop_gradient(pick), k)[1],
                   "routing")
        val = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk:
        val = val / val.sum(axis=-1, keepdims=True)
    out = idx.astype(jnp.int32), val * routed_scale
    return out + (s,) if with_scores else out


def sequence_balance_loss(s, idx, batch, alpha):
    """DeepSeek's sequence-wise balance loss (arXiv:2405.04434): for each
    of ``batch`` sequences of T tokens, with E experts scored and k chosen,
    P_i = mean_t s_{i,t} and f_i = E / (k T) #{t : i in top-k(t)}; the
    loss is ``alpha`` sum_i f_i P_i, the mean over the sequences. ``s``:
    the scores over all experts (batch*T, E) float32; ``idx`` (batch*T, k):
    the experts chosen. f is a count and carries no gradient; P carries
    one, into the router's logits."""
    n, e = s.shape
    k = idx.shape[-1]
    t = n // batch
    p = s.reshape(batch, t, e).mean(axis=1)
    hits = (idx.reshape(batch, t * k)[..., None]
            == jnp.arange(e, dtype=idx.dtype)).sum(axis=1, dtype=jnp.int32)
    f = hits.astype(jnp.float32) * (e / (k * t))
    return alpha * (f * p).sum(axis=-1).mean()


# a derivative taken inside a derivative rule wraps the first scope it meets
# (``jvp(round)``): this one takes the wrapper, so that ``dispatch``,
# ``experts`` and ``combine`` keep the names device traces are read by
@jax.named_scope("round")
def _expert_round(rows, k, r, x2, ws, pw, order, starts, ends, total,
                  kept=False):
    """Round ``r`` of the routed part: ``rows`` of the pairs sorted by
    expert go through the grouped products and are added to their tokens,
    weighted. x2: (N, C); ``ws``: the experts' stacked weights, (Eg, Eu
    (E, C, W), Ed (E, W, C)) for SwiGLU experts or (E1 (E, C, W), E2
    (E, W, C)) for relu^2 experts, which have no gate; pw: the pairs'
    weights, flat (N*k,); order: the pairs sorted by expert (N*k,), of
    which the first ``total`` fall on experts held; starts, ends: each
    expert's sorted rows. The round takes what it needs from ``order`` and
    ``pw`` itself, so nothing is sized by the rounds there could be. It owns
    sorted rows ``r*rows`` and up; its buffer is the window of ``order``
    that starts there, or that ends with ``order`` if that comes first (the
    last round of a routing that fills every round), and a row before its
    own or past the last held pair points past the tokens: the gathers fill
    it with zeros and the scatters drop it. ``kept``: the products into the
    experts' width are kept across a block's replay (util/remat.py): round
    0, which every step runs; the later rounds are loops, where a name keeps
    nothing. Returns ((N, C) float32, the pairs this round computed: rows
    inside an expert's group that carry a token, int32)."""
    n, c = x2.shape
    lo = r * rows
    with jax.named_scope("dispatch"):
        at = jnp.minimum(lo, n * k - rows)
        o_r = jax.lax.dynamic_slice(order, (at,), (rows,))
        rank = at + jnp.arange(rows, dtype=jnp.int32)
        own = (rank >= lo) & (rank < total)
        t_r = jnp.where(own, (o_r // k).astype(jnp.int32), n)
        w_r = jnp.take(pw, jnp.where(own, o_r, n * k), mode="fill",
                       fill_value=0)
        sizes = jnp.clip(ends, at, at + rows) - jnp.clip(starts, at, at + rows)
        xs = jnp.take(x2, t_r, axis=0, mode="fill", fill_value=0)
    with jax.named_scope("experts"):
        def gmm(a, w, out=None):
            return jax.lax.ragged_dot(a, w, sizes, preferred_element_type=out)
        if len(ws) == 3:
            eg, eu, ed = ws
            g, u = gmm(xs, eg), gmm(xs, eu)
            if kept:
                g, u = keep(g, "expert_gate_up"), keep(u, "expert_gate_up")
            h = (jax.nn.silu(g.astype(jnp.float32))
                 * u.astype(jnp.float32)).astype(x2.dtype)
        else:
            e1, ed = ws
            u = gmm(xs, e1)
            if kept:
                u = keep(u, "expert_gate_up")
            h = jnp.square(jax.nn.relu(u.astype(jnp.float32))).astype(x2.dtype)
        ys = gmm(h, ed, jnp.float32)
    with jax.named_scope("combine"):
        ys = jnp.where((t_r < n)[:, None], ys * w_r[:, None], 0.0)
        done = ((jnp.arange(rows) < sizes.sum()) & (t_r < n)).sum(
            dtype=jnp.int32)
        return jnp.zeros((n, c), jnp.float32).at[t_r].add(ys, mode="drop"), \
            done


def _rounds_needed(rows, total):
    return (total + rows - 1) // rows


def _later_rounds(rows, rounds, k, first, diff, rest):
    """Rounds 1.. added to round 0's ``first`` = (y, pairs computed): as
    many as the pairs left need, none under a routing that fits round 0.
    ``rounds``: how many the worst routing needs (``round_rows``): a layer
    whose round 0 holds that traces no loop. ``diff``: x2, ws, pw;
    ``rest``: order, starts, ends, total."""
    if rounds == 1:
        return first

    def body(r, acc):
        y, done = _expert_round(rows, k, r, *diff, *rest)
        return acc[0] + y, acc[1] + done

    return jax.lax.fori_loop(1, _rounds_needed(rows, rest[-1]), body, first)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _expert_rounds(rows, rounds, k, x2, ws, pw, order, starts, ends, total):
    """Every round of the routed part that the routing needs: round 0, then
    a loop whose trip count is the data's. One derivative rule over all of
    them, so that a step whose routing fits round 0 pays for round 0 alone:
    the later rounds' loop is carried from round 0's result, and their
    gradients are added to round 0's only where one ran (a rule for the
    later rounds alone would hand autodiff zero gradients in the shape of
    x2 and the experts' weights to write and add in every step). Returns
    (y, the pairs computed)."""
    diff, rest = (x2, ws, pw), (order, starts, ends, total)
    return _later_rounds(rows, rounds, k,
                         _expert_round(rows, k, 0, *diff, *rest), diff, rest)


def _rounds_fwd(rows, rounds, k, x2, ws, pw, order, starts, ends, total):
    diff, rest = (x2, ws, pw), (order, starts, ends, total)
    # round 0 is left to autodiff, whose residuals a block's replay keeps
    # by name; the later rounds keep nothing and run again backward
    y, back, done = jax.vjp(
        lambda *d: _expert_round(rows, k, 0, *d, *rest, kept=True), *diff,
        has_aux=True)
    return _later_rounds(rows, rounds, k, (y, done), diff, rest), \
        (back, diff, rest)


def _rounds_bwd(rows, rounds, k, res, ct):
    back, diff, rest = res
    dy = ct[0]                     # the count is an integer: no cotangent
    grads = back(dy)
    tmap = jax.tree_util.tree_map

    def later(grads):
        """Round 0's gradients plus those of rounds 1.., summed in
        float32."""
        def body(r, acc):
            _, vjp = jax.vjp(
                lambda *d: _expert_round(rows, k, r, *d, *rest)[0], *diff)
            return tmap(lambda a, g: a + g.astype(jnp.float32), acc, vjp(dy))

        acc = jax.lax.fori_loop(
            1, _rounds_needed(rows, rest[-1]), body,
            tmap(lambda g: g.astype(jnp.float32), grads))
        return tmap(lambda a, g: a.astype(g.dtype), acc, grads)

    # a branch, not the loop alone: seeded with round 0's gradients the
    # loop would widen them to float32 and narrow them again in a step that
    # runs no later round
    if rounds > 1:
        grads = jax.lax.cond(rest[-1] > rows, later, lambda g: g, grads)
    return grads + (None,) * len(rest)


_expert_rounds.defvjp(_rounds_fwd, _rounds_bwd)


@register_layer
@dataclass
class ExpertLayer(Layer):
    """Top-k mixture of experts plus an ungated shared expert, for a chip
    that holds a share of the experts.

    The router scores all ``n_experts`` and picks ``experts_per_token``;
    this layer holds ``experts_held = (count, first)``: experts
    ``first .. first+count-1`` (None: all of them). It computes, for every
    (token, expert) pair that falls on an expert it holds, that expert's
    output times the pair's weight, and leaves out what absent experts
    would add; the shared expert is added whole. No capacity: the pairs
    are sorted by expert and go through one grouped matrix product per
    projection (``jax.lax.ragged_dot``) in rounds of a fixed buffer; the
    first round holds ``_ROUND_SLACK`` times an even routing's pairs, and
    further rounds run only when the routing is so uneven that pairs are
    left, so nothing is dropped.

    What a configuration sets beside the sizes: ``expert_form``,
    ``"swiglu"`` ((silu(x Wg) * (x Wu)) Wd) or ``"relu2"`` (relu(x W1)^2 W2,
    no gate), the routed experts and the shared one alike; ``score``,
    ``"softmax"`` over the experts or ``"sigmoid"`` with a selection bias
    that chooses and does not weigh (``route_top_k``); ``latent_width``
    (0: none): the routed experts act inside a latent of that width, x
    projected down before the dispatch and the combined result up after it,
    while the router and the shared expert read the full width;
    ``balance_alpha`` (0: none), DeepSeek's sequence-wise balance loss at
    that weight (``sequence_balance_loss`` over a softmax router's scores
    of all ``n_experts``, per sequence of the (B, T, C) input), handed back
    in the state under ``loss_state`` for the container to add to the
    step's loss.

    Param keys: Wr (n_in, n_experts); Eg, Eu (count, C, expert_width), Ed
    (count, expert_width, C) or, for ``relu2``, E1, E2 of those shapes,
    with C the latent width where there is one; Sg, Su, Sd or S1, S2 the
    shared expert; Wdown (n_in, latent_width), Wup (latent_width, n_in).
    State (updated on training steps, read at the fit loop's boundary):
    ``pairs_total`` and ``pairs_dropped_total`` (int32, wrapping; dropped
    is the pairs routed here minus the pairs the rounds that ran counted as
    computed), ``pairs`` and ``load_max`` of the last step; with
    ``sigmoid`` also ``select_bias`` (n_experts,), which no step changes
    and the updater never sees: it is state, not a parameter; with
    ``balance_alpha`` also ``balance_loss``, the last step's term."""
    n_in: int = 0
    n_experts: int = 8
    experts_per_token: int = 2
    expert_width: int = 0
    shared_width: int = 0
    routed_scale: float = 1.0
    norm_topk: bool = True
    experts_held: Optional[tuple] = None     # (count, first index)
    expert_form: str = "swiglu"
    score: str = "softmax"
    latent_width: int = 0
    balance_alpha: float = 0.0

    @property
    def loss_state(self):
        return "balance_loss" if self.balance_alpha else None

    def set_n_in(self, input_type):
        if self.n_in == 0:
            self.n_in = _seq_n_in(input_type)

    def output_type(self, input_type):
        return InputType.recurrent(self.n_in, input_type.timeseries_length)

    @property
    def held(self):
        """(count, first) of the experts this layer holds."""
        return tuple(self.experts_held) if self.experts_held \
            else (self.n_experts, 0)

    @property
    def _leaves(self):
        """The names of the routed experts' stacked weights and of the
        shared expert's, the projection out of the width last."""
        if self.expert_form == "relu2":
            return ("E1", "E2"), ("S1", "S2")
        return ("Eg", "Eu", "Ed"), ("Sg", "Su", "Sd")

    def init(self, rng, dtype=jnp.float32):
        require_dims(self, n_in=self.n_in, expert_width=self.expert_width)
        if self.expert_form not in ("swiglu", "relu2") \
                or self.score not in ("softmax", "sigmoid"):
            raise ValueError(f"expert_form={self.expert_form!r} (swiglu | "
                             f"relu2), score={self.score!r} (softmax | "
                             "sigmoid)")
        if self.balance_alpha and self.score != "softmax":
            raise ValueError("the sequence-wise balance loss reads a softmax "
                             f"router's scores; score={self.score!r}")
        count, first = self.held
        if not (0 <= first and first + count <= self.n_experts and count > 0):
            raise ValueError(f"experts_held={self.experts_held} lies outside "
                             f"0..{self.n_experts}")
        k = jax.random.split(rng, 7)
        c, w = self.n_in, self.expert_width
        inner = self.latent_width or c

        def stack(key, shape):
            return jnp.stack([_w(self, kk, shape, dtype)
                              for kk in jax.random.split(key, count)])

        routed, shared = self._leaves
        p = {"Wr": _w(self, k[0], (c, self.n_experts), dtype)}
        for i, name in enumerate(routed):
            p[name] = stack(k[1 + i], (w, inner) if name == routed[-1]
                            else (inner, w))
        if self.shared_width:
            for i, name in enumerate(shared):
                p[name] = _w(self, k[4 + i], (self.shared_width, c)
                             if name == shared[-1] else (c, self.shared_width),
                             dtype)
        if self.latent_width:
            kd, ku = jax.random.split(jax.random.fold_in(rng, 7))
            p["Wdown"] = _w(self, kd, (c, inner), dtype)
            p["Wup"] = _w(self, ku, (inner, c), dtype)
        return p

    def init_state(self, dtype=jnp.float32):
        # one buffer each: the step donates its state
        state = {k: jnp.zeros((), jnp.int32) for k in
                 ("pairs_total", "pairs_dropped_total", "pairs", "load_max")}
        if self.score == "sigmoid":
            state["select_bias"] = jnp.zeros((self.n_experts,), jnp.float32)
        if self.balance_alpha:
            state["balance_loss"] = jnp.zeros((), jnp.float32)
        return state

    # -- the routed part ---------------------------------------------------
    def round_rows(self, n_tokens):
        """Rows of one round's buffer, and how many rounds hold the worst
        routing (every token on as many held experts as it can pick)."""
        count = self.held[0]
        worst = n_tokens * min(self.experts_per_token, count)
        even = n_tokens * self.experts_per_token * count / self.n_experts
        rows = min(worst, -(-int(math.ceil(_ROUND_SLACK * even)) // 8) * 8)
        return rows, -(-worst // rows)

    def routed(self, params, x2, first=None, bias=None, batch=0):
        """Sum over the held experts of weight * expert(x) for the pairs
        routed to them. x2: (N, C). ``first``: index of the first expert
        held (a traced value under ``shard_map``; None: the layer's own).
        ``bias``: the selection bias of a ``sigmoid`` router (None: zero).
        ``batch``: how many sequences x2 holds, one after the other (0: the
        balance loss is not taken). Returns (y (N, C) float32, counters
        dict; with ``balance_alpha`` and ``batch`` its ``balance_loss``)."""
        n, c = x2.shape
        count = self.held[0]
        first = self.held[1] if first is None else first
        k = self.experts_per_token
        rows, rounds = self.round_rows(n)
        balance = {}
        with jax.named_scope("route"):
            if self.balance_alpha and batch:
                idx, p, s = route_top_k(x2, params["Wr"], k, self.norm_topk,
                                        self.routed_scale, self.score, bias,
                                        with_scores=True)
                with jax.named_scope("balance"):
                    balance["balance_loss"] = sequence_balance_loss(
                        s, idx, batch, self.balance_alpha)
            else:
                idx, p = route_top_k(x2, params["Wr"], k, self.norm_topk,
                                     self.routed_scale, self.score, bias)
        if self.latent_width:
            with jax.named_scope("latent_down"):
                x2 = x2 @ params["Wdown"]
        with jax.named_scope("dispatch"):
            local = (idx - first).reshape(-1)
            key = jnp.where((local >= 0) & (local < count), local, count)
            # the sort and the group sizes: a block's replay (util/remat.py)
            # neither sorts nor counts again
            order = keep(jnp.argsort(key, stable=True), "routing")
            counts = keep((key[:, None] == jnp.arange(count)[None, :]).sum(
                axis=0, dtype=jnp.int32), "routing")
            ends = jnp.cumsum(counts)
            starts, total = ends - counts, ends[-1]

        y, done = _expert_rounds(
            rows, rounds, k, x2, tuple(params[w] for w in self._leaves[0]),
            p.reshape(-1), order, starts, ends, total)
        if self.latent_width:
            with jax.named_scope("latent_up"):
                y = jnp.dot(y.astype(x2.dtype), params["Wup"],
                            preferred_element_type=jnp.float32)
        # ``done`` is counted by the rounds that ran, so a round left out
        # reads as pairs dropped
        return y, {"pairs": total, "pairs_dropped": total - done,
                   "load_max": counts.max(), **balance}

    def shared(self, params, x2):
        with jax.named_scope("shared"):
            if self.expert_form == "swiglu":
                return swiglu(x2, params["Sg"], params["Su"], params["Sd"])
            u = keep(jnp.dot(x2, params["S1"]), "gate_up")
            h = jnp.square(jax.nn.relu(u.astype(jnp.float32)))
            return jnp.dot(h.astype(x2.dtype), params["S2"])

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        b, t, c = x.shape
        x2 = x.reshape(b * t, c)
        bias = state.get("select_bias") if state else None
        y, seen = self.routed(params, x2, bias=bias,
                              batch=b if train and state else 0)
        if self.shared_width:
            y = y + self.shared(params, x2)
        if train and state:
            new = {
                "pairs_total": state["pairs_total"] + seen["pairs"],
                "pairs_dropped_total": (state["pairs_dropped_total"]
                                        + seen["pairs_dropped"]),
                "pairs": seen["pairs"], "load_max": seen["load_max"]}
            if bias is not None:
                new["select_bias"] = bias
            if "balance_loss" in seen:
                new["balance_loss"] = seen["balance_loss"]
            state = new
        return y.astype(x.dtype).reshape(b, t, c), state
