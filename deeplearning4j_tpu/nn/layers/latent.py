"""Layers of a latent-attention decoder with hyper-connections and a
multi-token-prediction head: latent attention (MLA, arXiv:2405.04434),
the maps of manifold-constrained hyper-connections (mHC; "Hyper-Connections",
arXiv:2409.19606) with the graph vertices that mix their residual streams,
and the pieces of one multi-token-prediction module (arXiv:2412.19437).

The reference (DL4J 0.9.2) has none of them. Each is a plain ``Layer`` or
``GraphVertex``; sequences are (B, T, C) as everywhere in ``nn/layers``,
and a residual state of ``n`` streams is (B, T, n*C), stream i in columns
i*C .. (i+1)*C - 1.

mHC, for a sublayer F on the residual state X (n x C a token):

    x~     = RMSNorm(vec X)                      (no gain)
    H_pre  = sigmoid(a_pre x~ phi_pre + b_pre)               (n,)
    H_post = 2 sigmoid(a_post x~ phi_post + b_post)          (n,)
    H_res  = Sinkhorn(exp(clamp(a_res mat(x~ phi_res) + b_res)))   (n, n)
    X'     = H_res X + H_post^T F(H_pre X)

``HyperConnection`` makes the three maps of a token (one layer: it owns
phi, a and b); the vertices ``HyperIn`` and ``HyperOut``
(nn/conf/graph_conf.py, beside ``StreamExpand``, ``StreamCollapse`` and
the module's ``NextTokenJoin``) read H_pre X for the sublayer and write
X'. Sinkhorn alternates a row and a column normalisation
``sinkhorn_iters`` times (the column last), each sum with ``eps`` added.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.ops import CallForms
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer, require_dims
from deeplearning4j_tpu.nn.layers.decoder import (
    _kernels_on, _seq_n_in, _w, apply_rotary, banded_attention, rms_norm)
from deeplearning4j_tpu.nn.layers.rnn import RnnOutputLayer
from deeplearning4j_tpu.nn.losses import sparse_mcxent_from_features
from deeplearning4j_tpu.util.remat import keep


# ---------------------------------------------------------- latent attention

@register_layer
@dataclass
class LatentAttention(Layer):
    """Causal multi-head latent attention over (B, T, C). Queries through a
    rank ``q_rank`` bottleneck, c_q = RMSNorm(x W_qa), q = c_q W_qb, or, with
    ``q_rank`` 0 (a file's ``q_lora_rank: null``), straight from the input,
    q = x W_q: per head ``nope_dim`` dims without position and ``rope_dim``
    rotated. Keys and
    values through a rank ``kv_rank`` latent and one rotated key that every
    head shares: [c_kv | k_rope] = x W_kva, c_kv = RMSNorm(c_kv),
    [k_nope | v] = c_kv W_kvb per head. Scores q.k over nope_dim + rope_dim
    times ``rotary["score_scale"]`` / sqrt(nope_dim + rope_dim) (YaRN's
    mscale^2 in DeepSeek's form, 1 without); the values are ``v_dim`` wide.
    ``n_heads`` is what this layer holds (a chip's share: W_qb or W_q, W_kvb
    and Wo sliced by head; W_qa, W_kva and the norms whole). Rotary in
    halves, as ``apply_rotary`` does, on the ``rope_dim`` dims alone. Each
    call counts the form its attention took (``counting_calls``):
    ``kernel``, the grouped-query flash kernels, or ``xla``,
    ``banded_attention``.

    Param keys: Wqa (n_in, q_rank), q_gamma (q_rank,), Wqb (q_rank,
    H*(nope+rope)), or without the bottleneck Wq (n_in, H*(nope+rope));
    Wkva (n_in, kv_rank+rope), kv_gamma (kv_rank,), Wkvb (kv_rank,
    H*(nope+v)), Wo (H*v, n_out)."""
    n_in: int = 0
    n_out: int = 0
    n_heads: int = 0
    q_rank: int = 0
    kv_rank: int = 0
    nope_dim: int = 0
    rope_dim: int = 0
    v_dim: int = 0
    rotary: Optional[dict] = None
    norm_eps: float = 1e-6

    def set_n_in(self, input_type):
        if self.n_in == 0:
            self.n_in = _seq_n_in(input_type)
        if self.n_out == 0:
            self.n_out = self.n_in

    def output_type(self, input_type):
        return InputType.recurrent(self.n_out or self.n_in,
                                   input_type.timeseries_length)

    @property
    def qk_dim(self):
        return self.nope_dim + self.rope_dim

    def init(self, rng, dtype=jnp.float32):
        self.n_out = self.n_out or self.n_in
        require_dims(self, n_in=self.n_in, n_heads=self.n_heads,
                     kv_rank=self.kv_rank, rope_dim=self.rope_dim,
                     v_dim=self.v_dim)
        k = jax.random.split(rng, 5)
        h = self.n_heads
        if self.q_rank:
            q = {"Wqa": _w(self, k[0], (self.n_in, self.q_rank), dtype),
                 "q_gamma": jnp.ones((self.q_rank,), dtype),
                 "Wqb": _w(self, k[1], (self.q_rank, h * self.qk_dim), dtype)}
        else:
            q = {"Wq": _w(self, k[1], (self.n_in, h * self.qk_dim), dtype)}
        return {
            **q,
            "Wkva": _w(self, k[2], (self.n_in, self.kv_rank + self.rope_dim),
                       dtype),
            "kv_gamma": jnp.ones((self.kv_rank,), dtype),
            "Wkvb": _w(self, k[3], (self.kv_rank,
                                    h * (self.nope_dim + self.v_dim)), dtype),
            "Wo": _w(self, k[4], (h * self.v_dim, self.n_out), dtype)}

    def _rotary(self):
        return dict(self.rotary or {}, dims=self.rope_dim)

    def _attend(self, q, k, v):
        from deeplearning4j_tpu import ops
        from deeplearning4j_tpu.ops.flash_attention import (
            gqa_flash_attention, gqa_supported)
        scale = float((self.rotary or {}).get("score_scale") or 1.0) \
            / math.sqrt(self.qk_dim)
        t = q.shape[2]
        on = _kernels_on(t) and gqa_supported(t, self.qk_dim, self.n_heads,
                                              self.n_heads, self.v_dim)
        note_call("kernel" if on else "xla")
        if on:
            return gqa_flash_attention(q, k, v, None, None,
                                       ops.interpret_mode(), scale)
        return banded_attention(q, k, v, scale=scale)

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        if mask is not None:
            raise ValueError(
                "LatentAttention takes no padding mask: pack whole sequences "
                "to full length; documents packed into one sequence need a "
                "segment mask, which the layer does not take yet (ROADMAP "
                "R12)")
        b, t, _ = x.shape
        h, nope, rope = self.n_heads, self.nope_dim, self.rope_dim
        rot = self._rotary()
        with jax.named_scope("mla"):
            with jax.named_scope("latent_q"):
                if "Wq" in params:
                    q = x @ params["Wq"]
                else:
                    q = rms_norm(x @ params["Wqa"], params["q_gamma"],
                                 self.norm_eps) @ params["Wqb"]
                q = q.reshape(b, t, h, nope + rope).transpose(0, 2, 1, 3)
                q = jnp.concatenate(
                    [q[..., :nope], apply_rotary(q[..., nope:], rot)], -1)
            with jax.named_scope("latent_kv"):
                ckv = x @ params["Wkva"]
                c = rms_norm(ckv[..., :self.kv_rank], params["kv_gamma"],
                             self.norm_eps)
                k_rope = apply_rotary(ckv[:, None, :, self.kv_rank:], rot)
                kv = (c @ params["Wkvb"]).reshape(b, t, h, nope + self.v_dim) \
                    .transpose(0, 2, 1, 3)
                k = jnp.concatenate(
                    [kv[..., :nope],
                     jnp.broadcast_to(k_rope, (b, h, t, rope))], -1)
                v = kv[..., nope:]
            # what the kernel's backward pass reads: a block's replay runs
            # neither the projections nor rotary again (util/remat.py)
            q, k, v = (keep(a, "qkv") for a in (q, k, v))
            with jax.named_scope("attend"):
                o = self._attend(q, k, v).transpose(0, 2, 1, 3)
        return o.reshape(b, t, -1) @ params["Wo"], state

    def init_decode_state(self, params, batch, max_len, dtype=jnp.float32):
        raise NotImplementedError(
            "LatentAttention trains through fit(); decoding it needs a paged "
            "cache of the latent and the shared rotary key (ROADMAP, Reach)")


# ``note_call(form)`` counts one latent-attention call, by the form its
# attention took, into the dict that ``counting_calls(into)`` holds open on
# this thread
_calls = CallForms()
FORMS, note_call, counting_calls = _calls.FORMS, _calls.note, _calls.counting


# --------------------------------------------------------- hyper-connections

def sinkhorn(m, iters, eps):
    """``iters`` rounds of a row then a column normalisation of the
    positive (..., n, n) float32 ``m``, each sum with ``eps`` added."""
    def one(_, m):
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
        return m / (m.sum(axis=-2, keepdims=True) + eps)
    return jax.lax.fori_loop(0, iters, one, m)


def stochastic_gap(h):
    """The largest |row or column sum - 1| of (..., n, n) matrices."""
    return jnp.maximum(jnp.abs(h.sum(axis=-1) - 1).max(),
                       jnp.abs(h.sum(axis=-2) - 1).max())


def hyper_kernels(x, streams, fits=True):
    """Whether a hyper-connection's pass over the residual state ``x`` (...,
    T, n*C) takes the kernels of ops/hyper_connection.py: the decoder's
    rule for kernels at T positions (``_kernels_on``), a stream width the
    kernels' screen accepts and the caller's own condition ``fits``; else
    XLA's form. The pass is counted by the form it takes
    (``hyper_connection.counting_calls``)."""
    from deeplearning4j_tpu.ops import hyper_connection as kernel
    on = fits and _kernels_on(x.shape[-2]) and kernel.supported(
        x.shape[-1] // streams, streams)
    kernel.note_call("kernel" if on else "xla")
    return on


def _maps_split(maps, n):
    """(H_pre (..., n), H_post (..., n), H_res (..., n, n)) of the (...,
    2n + n^2) float32 maps ``HyperConnection`` makes."""
    return (maps[..., :n], maps[..., n:2 * n],
            maps[..., 2 * n:].reshape(*maps.shape[:-1], n, n))


@register_layer
@dataclass
class HyperConnection(Layer):
    """The maps of one manifold-constrained hyper-connection (module
    docstring) from the residual state (B, T, n*C): (B, T, 2n + n^2)
    float32, [H_pre | H_post | H_res row-major]. Param keys: phi (n*C,
    2n + n^2), the three projections side by side; alpha (3,), their
    scales; bias (2n + n^2,). State (training steps): ``sinkhorn_gap``, the
    largest |row or column sum - 1| of H_res over the step's tokens, and
    ``res_diag_mean``, the mean of its diagonal: 1/n where every stream is
    mixed evenly, 1 where each keeps its own."""
    n_in: int = 0
    streams: int = 4
    sinkhorn_iters: int = 20
    eps: float = 1e-6
    clamp: tuple = (-30.0, 30.0)
    norm_eps: float = 1e-6

    @property
    def width(self):
        return 2 * self.streams + self.streams ** 2

    def set_n_in(self, input_type):
        if self.n_in == 0:
            self.n_in = _seq_n_in(input_type)

    def output_type(self, input_type):
        return InputType.recurrent(self.width, input_type.timeseries_length)

    def init(self, rng, dtype=jnp.float32):
        require_dims(self, n_in=self.n_in)
        # the scales start small, the maps near their static part (the
        # dynamic parts of arXiv:2409.19606 start so)
        return {"phi": _w(self, rng, (self.n_in, self.width), dtype),
                "alpha": jnp.full((3,), 0.01, dtype),
                "bias": jnp.zeros((self.width,), dtype)}

    def init_state(self, dtype=jnp.float32):
        return {"sinkhorn_gap": jnp.zeros((), jnp.float32),
                "res_diag_mean": jnp.zeros((), jnp.float32)}

    def _project(self, phi, x):
        """RMSNorm(vec X) phi, (..., 2n + n^2) float32: one pass of the
        kernel where ``hyper_kernels`` routes it (the norm applied after
        the product), else XLA's form (the normalised state rounded to
        X's dtype before the product)."""
        if hyper_kernels(x, self.streams, phi.dtype == x.dtype):
            from deeplearning4j_tpu import ops
            from deeplearning4j_tpu.ops.hyper_connection import (
                maps_projection)
            return maps_projection(
                x.reshape(-1, x.shape[-1]), phi, self.norm_eps, self.streams,
                ops.interpret_mode()).reshape(*x.shape[:-1], -1)
        x32 = x.astype(jnp.float32)
        xt = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.norm_eps)
        return jnp.dot(xt.astype(x.dtype), phi,
                       preferred_element_type=jnp.float32)

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        n = self.streams
        with jax.named_scope("hc"):
            with jax.named_scope("hc_maps"):
                z = self._project(params["phi"], x)
                a = params["alpha"].astype(jnp.float32)
                z = z * jnp.repeat(a, jnp.asarray([n, n, n * n]),
                                   total_repeat_length=self.width) \
                    + params["bias"].astype(jnp.float32)
                pre, post, res = _maps_split(z, n)
                pre, post = jax.nn.sigmoid(pre), 2 * jax.nn.sigmoid(post)
            with jax.named_scope("sinkhorn"):
                res = sinkhorn(jnp.exp(jnp.clip(res, *self.clamp)),
                               self.sinkhorn_iters, self.eps)
            maps = jnp.concatenate(
                [pre, post, res.reshape(*res.shape[:-2], n * n)], axis=-1)
            if train and state:
                r = jax.lax.stop_gradient(res)
                state = {"sinkhorn_gap": stochastic_gap(r),
                         "res_diag_mean": jnp.diagonal(
                             r, axis1=-2, axis2=-1).mean()}
        return maps, state


# ---------------------------------------------------- multi-token prediction

@register_layer
@dataclass
class NextTokenProjection(Layer):
    """A multi-token-prediction module's input: [RMSNorm(h) | RMSNorm(e)] M
    over (B, T, 2C) -> (B, T, C). Param keys: h_gamma, e_gamma (C,),
    M (2C, C)."""
    n_in: int = 0
    norm_eps: float = 1e-6

    def set_n_in(self, input_type):
        if self.n_in == 0:
            self.n_in = _seq_n_in(input_type)

    def output_type(self, input_type):
        return InputType.recurrent(self.n_in // 2, input_type.timeseries_length)

    def init(self, rng, dtype=jnp.float32):
        require_dims(self, n_in=self.n_in)
        c = self.n_in // 2
        return {"h_gamma": jnp.ones((c,), dtype),
                "e_gamma": jnp.ones((c,), dtype),
                "M": _w(self, rng, (self.n_in, c), dtype)}

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        c = self.n_in // 2
        with jax.named_scope("mtp"):
            h = rms_norm(x[..., :c], params["h_gamma"], self.norm_eps)
            e = rms_norm(x[..., c:], params["e_gamma"], self.norm_eps)
            return jnp.concatenate([h, e], axis=-1) @ params["M"], state


@register_layer
@dataclass
class MultiTokenHead(RnnOutputLayer):
    """An untied softmax head that serves the trunk and one
    multi-token-prediction module with ONE weight: its input is
    [trunk | module] (B, T, 2C), W is (C, n_out). The score is the trunk's
    mean token cross entropy on ``labels`` (the next token) plus
    ``mtp_weight`` times the module's on the token after it (labels shifted
    by one; the last position has none and is masked). Inference returns
    the trunk's softmax. State (training steps): ``mtp_loss``, the module's
    term before its weight."""
    mtp_weight: float = 0.3

    def init(self, rng, dtype=jnp.float32):
        require_dims(self, n_in=self.n_in, n_out=self.n_out)
        return {"W": _w(self, rng, (self.n_in // 2, self.n_out), dtype)}

    def init_state(self, dtype=jnp.float32):
        return {"mtp_loss": jnp.zeros((), jnp.float32)}

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        c = x.shape[-1] // 2
        return super().apply(params, x[..., :c], state, train=train, rng=rng)

    def score_and_state(self, params, x, labels, mask=None, *, train=False,
                        rng=None):
        """(score, state) of a training step: ``compute_score`` and the
        module's term."""
        b, t, two_c = x.shape
        c = two_c // 2
        w = params["W"]
        flat = lambda a: a.reshape(b * t, -1)
        m = None if mask is None else mask.reshape(b, t)
        main = sparse_mcxent_from_features(
            labels.reshape(-1), flat(x[..., :c]), w, None,
            None if m is None else m.reshape(-1))
        with jax.named_scope("mtp"):
            nxt = jnp.pad(labels.reshape(b, t)[:, 1:], ((0, 0), (0, 1)))
            keep_ = jnp.ones((b, t), jnp.float32).at[:, -1].set(0.0)
            if m is not None:
                keep_ = keep_ * jnp.pad(m[:, 1:], ((0, 0), (0, 1)))
            mtp = sparse_mcxent_from_features(
                nxt.reshape(-1), flat(x[..., c:]), w, None, keep_.reshape(-1))
        return main + self.mtp_weight * mtp, {
            "mtp_loss": jax.lax.stop_gradient(mtp).astype(jnp.float32)}

    def compute_score(self, params, x, labels, mask=None, *, train=False,
                      rng=None):
        return self.score_and_state(params, x, labels, mask, train=train,
                                    rng=rng)[0]
