"""Flash attention (Pallas TPU): blocked online-softmax attention.

The reference has no attention at all (SURVEY.md §5 — recurrent nets only);
this kernel backs the TPU-first MultiHeadAttention extension
(nn/layers/attention.py) and the ring-attention sequence-parallel path.
O(T) memory instead of the O(T^2) scores matrix: the softmax is computed
online per key block, carrying the running max/denominator in registers,
and the backward pass recomputes scores blockwise from saved (o, lse).

Supported: no key-padding mask (fall back to the reference path), sequence
length divisible by a block size, head dim a multiple of 8 up to 256, and
two whole (T, Dh) operands within the VMEM budget (``supported``).
f32 accumulation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.util.remat import keep

_NEG = -1e30


def _pick_block(t):
    for b in (128, 64, 32, 16, 8):
        if t % b == 0:
            return b
    return None


# Auto-route threshold, from a v5e record that predates PR 1: XLA's
# fused-softmax attention won below T~4096 (0.1-0.6x at T<=2048); the flash
# kernel won above (1.06x @ 4096, 2.1x @ 8192) and avoids the O(T^2) scores
# matrix. Today's compiler refuses T 8192 at Dh 128 (``supported`` below),
# so at Dh <= 128 the seam reaches the kernel at T 4096 only — ROADMAP S2
# decides whether to block K/V or delete it. Direct flash_attention() calls
# are not gated — only the layer seam's silent routing is.
MIN_SEQ_FOR_AUTO_ROUTE = 4096


def supported(t, dh, min_t: int = 0):
    """Shape screen. ``min_t``: minimum sequence length (the layer seam
    passes MIN_SEQ_FOR_AUTO_ROUTE so short sequences stay on the faster
    XLA path; interpret-mode tests pass 0)."""
    # Each (batch*head) program holds two whole (T, Dh) operands in VMEM
    # (K and V in the forward and dq passes, q and do in the dk/dv pass),
    # double-buffered by the pipeline and padded to 128 lanes; the dk/dv
    # pass adds the lse and delta columns, a 128-lane tile row per
    # position. Mosaic scopes a kernel to 16 MiB on v5e; the budget
    # leaves the rest to the q/o blocks and the compiler's temporaries.
    # tests/test_tpu_compile.py holds this to "accepted means it
    # compiles" (T 4096 at Dh <= 128 is the largest that does).
    lanes = -(-dh // 128) * 128
    vmem = 2 * 2 * t * lanes * 4 + 2 * t * 128 * 4
    return (_pick_block(t) is not None and dh % 8 == 0 and dh <= 256
            and t >= min_t and vmem <= 12 * 1024 * 1024)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, blk, t_total, causal,
                scale):
    iq = pl.program_id(1)
    q = q_ref[0]                                    # (blk, Dh)
    num_kb = t_total // blk
    upper = jnp.where(causal, iq + 1, num_kb)

    qpos = iq * blk + lax.broadcasted_iota(jnp.int32, (blk, blk), 0)

    def body(j, carry):
        m, l, acc = carry
        kb = k_ref[0, pl.ds(j * blk, blk), :]       # (blk, Dh)
        vb = v_ref[0, pl.ds(j * blk, blk), :]
        s = lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if causal:
            kpos = j * blk + lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
            s = jnp.where(qpos >= kpos, s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1, keepdims=True)
        acc = acc * alpha + jnp.dot(p, vb, preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((blk, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((blk, 1), jnp.float32)
    a0 = jnp.zeros((blk, q.shape[-1]), jnp.float32)
    m, l, acc = lax.fori_loop(0, upper, body, (m0, l0, a0))
    o_ref[0] = acc / l
    lse_ref[0] = m + jnp.log(l)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               *, blk, t_total, causal, scale):
    iq = pl.program_id(1)
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0]
    delta = delta_ref[0]
    num_kb = t_total // blk
    upper = jnp.where(causal, iq + 1, num_kb)
    qpos = iq * blk + lax.broadcasted_iota(jnp.int32, (blk, blk), 0)

    def body(j, dq):
        kb = k_ref[0, pl.ds(j * blk, blk), :]
        vb = v_ref[0, pl.ds(j * blk, blk), :]
        s = lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if causal:
            kpos = j * blk + lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
            s = jnp.where(qpos >= kpos, s, _NEG)
        p = jnp.exp(s - lse)
        dp = lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        return dq + jnp.dot(ds, kb, preferred_element_type=jnp.float32)

    dq0 = jnp.zeros_like(q)
    dq_ref[0] = lax.fori_loop(0, upper, body, dq0)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, blk, t_total, causal, scale):
    jk = pl.program_id(1)
    kb = k_ref[0]
    vb = v_ref[0]
    num_qb = t_total // blk
    lower = jnp.where(causal, jk, 0)
    kpos = jk * blk + lax.broadcasted_iota(jnp.int32, (blk, blk), 1)

    def body(i, carry):
        dk, dv = carry
        qb = q_ref[0, pl.ds(i * blk, blk), :]
        dob = do_ref[0, pl.ds(i * blk, blk), :]
        lse = lse_ref[0, pl.ds(i * blk, blk), :]
        delta = delta_ref[0, pl.ds(i * blk, blk), :]
        s = lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = i * blk + lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
            s = jnp.where(qpos >= kpos, s, _NEG)
        p = jnp.exp(s - lse)
        dv = dv + lax.dot_general(p, dob, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dp = lax.dot_general(dob, vb, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk = dk + lax.dot_general(ds, qb, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        return dk, dv

    z = jnp.zeros_like(kb)
    dk, dv = lax.fori_loop(lower, num_qb, body, (z, jnp.zeros_like(vb)))
    dk_ref[0] = dk
    dv_ref[0] = dv


def _specs(bh, t, dh, blk):
    qblk = pl.BlockSpec((1, blk, dh), lambda b, i: (b, i, 0),
                        memory_space=pltpu.VMEM)
    full = pl.BlockSpec((1, t, dh), lambda b, i: (b, 0, 0),
                        memory_space=pltpu.VMEM)
    vec_blk = pl.BlockSpec((1, blk, 1), lambda b, i: (b, i, 0),
                           memory_space=pltpu.VMEM)
    vec_full = pl.BlockSpec((1, t, 1), lambda b, i: (b, 0, 0),
                            memory_space=pltpu.VMEM)
    return qblk, full, vec_blk, vec_full


def _fa_fwd_call(q, k, v, causal, interpret):
    bh, t, dh = q.shape
    blk = _pick_block(t)
    scale = 1.0 / (dh ** 0.5)
    qblk, full, vec_blk, _ = _specs(bh, t, dh, blk)
    kern = functools.partial(_fwd_kernel, blk=blk, t_total=t, causal=causal,
                             scale=scale)
    o, lse = pl.pallas_call(
        kern,
        grid=(bh, t // blk),
        in_specs=[qblk, full, full],
        out_specs=(qblk, vec_blk),
        out_shape=(jax.ShapeDtypeStruct((bh, t, dh), jnp.float32),
                   jax.ShapeDtypeStruct((bh, t, 1), jnp.float32)),
        interpret=interpret,
    )(q, k, v)
    return o, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal=False, interpret=False):
    """q/k/v: (BH, T, Dh) float32. Returns (BH, T, Dh)."""
    o, _ = _fa_fwd_call(q, k, v, causal, interpret)
    return o


def _fa_fwd(q, k, v, causal, interpret):
    o, lse = _fa_fwd_call(q, k, v, causal, interpret)
    return o, (q, k, v, o, lse)


def _fa_bwd(causal, interpret, res, do):
    q, k, v, o, lse = res
    bh, t, dh = q.shape
    blk = _pick_block(t)
    scale = 1.0 / (dh ** 0.5)
    delta = (do * o).sum(axis=-1)[..., None]         # (BH, T, 1)
    qblk, full, vec_blk, vec_full = _specs(bh, t, dh, blk)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, blk=blk, t_total=t, causal=causal,
                          scale=scale),
        grid=(bh, t // blk),
        in_specs=[qblk, full, full, qblk, vec_blk, vec_blk],
        out_specs=qblk,
        out_shape=jax.ShapeDtypeStruct((bh, t, dh), jnp.float32),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, blk=blk, t_total=t, causal=causal,
                          scale=scale),
        grid=(bh, t // blk),
        in_specs=[full, qblk, qblk, full, vec_full, vec_full],
        out_specs=(qblk, qblk),
        out_shape=(jax.ShapeDtypeStruct((bh, t, dh), jnp.float32),
                   jax.ShapeDtypeStruct((bh, t, dh), jnp.float32)),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ===================================================== grouped-query, banded
# Causal attention for decoder training: K and V are read by kv head (a
# query head h reads kv head h // group, no copy repeated in memory), keys
# are streamed block by block over the grid with the running max, sum and
# accumulator in VMEM scratch, operands stay in their own dtype (bfloat16 on
# the training path) with float32 accumulation, and with a ``window`` only
# the key blocks inside the band are visited: the grid's last axis is as long
# as the band, not the sequence.

def _band_blocks(t, window, rows, cols):
    """How many blocks of ``cols`` a block of ``rows`` can need: the whole
    sequence without a window, else the band's width."""
    if window is None:
        return t // cols
    return min(t // cols, (rows + window - 2) // cols + 2)


def _kv_range(iq, bq, bk, window):
    """First and last key block a query block needs."""
    hi = ((iq + 1) * bq - 1) // bk
    if window is None:
        return 0, hi
    return jnp.maximum(iq * bq - window + 1, 0) // bk, hi


def _q_range(jk, bq, bk, window, nq):
    """First and last query block that sees a key block."""
    lo = (jk * bk) // bq
    if window is None:
        return lo, nq - 1
    return lo, jnp.minimum(((jk + 1) * bk + window - 2) // bq, nq - 1)


def _visible(qpos, kpos, window):
    ok = qpos >= kpos
    if window is not None:
        ok = jnp.logical_and(ok, qpos - kpos < window)
    return ok


def _block_visible(mask_ref, iq, kb, bq, bk, window):
    """Which (query, key) pairs of one block are attended: by position, or
    where a ``mask`` operand is given by the mask alone (a selection that is
    data already holds causality: ``selected_keys_mask``)."""
    if mask_ref is not None:
        return mask_ref[...].astype(jnp.int32) != 0
    qpos = iq * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = kb * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return _visible(qpos, kpos, window)


def _gqa_fwd_kernel(q_ref, k_ref, v_ref, *rest, bq, bk, window, scale,
                    masked=False):
    mask_ref, rest = (rest[0], rest[1:]) if masked else (None, rest)
    o_ref, lse_ref, m_s, l_s, acc_s = rest
    iq, j = pl.program_id(2), pl.program_id(3)
    lo, hi = _kv_range(iq, bq, bk, window)
    kb = lo + j

    @pl.when(j == 0)
    def _():
        m_s[...] = jnp.full(m_s.shape, _NEG, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    @pl.when(kb <= hi)
    def _():
        q, k, v = q_ref[...], k_ref[...], v_ref[...]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        s = jnp.where(_block_visible(mask_ref, iq, kb, bq, bk, window), s,
                      _NEG)
        m = m_s[...]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_s[...] = l_s[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_s[...] = acc_s[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_s[...] = m_new

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        o_ref[...] = (acc_s[...] / l_s[...]).astype(o_ref.dtype)
        lse_ref[...] = m_s[...] + jnp.log(l_s[...])


def _gqa_dq_kernel(q_ref, k_ref, v_ref, *rest, bq, bk, window, scale,
                   masked=False):
    mask_ref, rest = (rest[0], rest[1:]) if masked else (None, rest)
    do_ref, lse_ref, delta_ref, dq_ref, dq_s = rest
    iq, j = pl.program_id(2), pl.program_id(3)
    lo, hi = _kv_range(iq, bq, bk, window)
    kb = lo + j

    @pl.when(j == 0)
    def _():
        dq_s[...] = jnp.zeros(dq_s.shape, jnp.float32)

    @pl.when(kb <= hi)
    def _():
        q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        p = jnp.where(_block_visible(mask_ref, iq, kb, bq, bk, window),
                      jnp.exp(s - lse_ref[...]), 0.0)
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[...]) * scale).astype(k.dtype)
        dq_s[...] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        dq_ref[...] = dq_s[...].astype(dq_ref.dtype)


def _gqa_dkv_kernel(q_ref, k_ref, v_ref, *rest, bq, bk, window, scale, nq,
                    masked=False):
    mask_ref, rest = (rest[0], rest[1:]) if masked else (None, rest)
    do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_s, dv_s = rest
    jk, g, i = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    lo, hi = _q_range(jk, bq, bk, window, nq)
    qb = lo + i

    @pl.when(jnp.logical_and(g == 0, i == 0))
    def _():
        dk_s[...] = jnp.zeros(dk_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    @pl.when(qb <= hi)
    def _():
        q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        p = jnp.where(_block_visible(mask_ref, qb, jk, bq, bk, window),
                      jnp.exp(s - lse_ref[...]), 0.0)
        dv_s[...] += lax.dot_general(p.astype(do.dtype), do,
                                     (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[...]) * scale).astype(q.dtype)
        dk_s[...] += lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(g == pl.num_programs(3) - 1,
                             i == pl.num_programs(4) - 1))
    def _():
        dk_ref[...] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_s[...].astype(dv_ref.dtype)


def gqa_supported(t, dh, n_heads, n_kv_heads):
    """Shape screen of ``gqa_flash_attention``."""
    return (_pick_gqa_block(t) is not None and dh % 8 == 0 and dh <= 256
            and n_heads % n_kv_heads == 0)


def _pick_gqa_block(t):
    for b in (512, 256, 128, 64, 32, 16, 8):
        if t % b == 0:
            return b
    return None


def _gqa_call(kernel, grid, in_specs, out_specs, out_shape, scratch, args,
              interpret):
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3
            + ("arbitrary",) * (len(grid) - 3)),
        interpret=interpret,
    )(*args)


def _gqa_specs(bq, bk, dh, group, window):
    """Block specs of the kernels whose grid is (B, Hq, query block, key
    step): a query block, the key/value block of the head's kv head at that
    step of the band (held at the last one needed, so that a skipped step
    fetches nothing), a per-row vector, and the (query block, key block)
    tile of a (B, T, T) mask that all heads share."""
    def kv_index(b_, h, i, j):
        lo, hi = _kv_range(i, bq, bk, window)
        return b_, h // group, jnp.minimum(lo + j, hi), 0

    def q_index(b_, h, i, j):
        return b_, h, i, 0

    def mask_index(b_, h, i, j):
        lo, hi = _kv_range(i, bq, bk, window)
        return b_, i, jnp.minimum(lo + j, hi)

    return (pl.BlockSpec((None, None, bq, dh), q_index),
            pl.BlockSpec((None, None, bk, dh), kv_index),
            pl.BlockSpec((None, None, bq, 1), q_index),
            pl.BlockSpec((None, bq, bk), mask_index))


def _gqa_fwd_call(q, k, v, window, block, interpret, mask=None):
    b, hq, t, dh = q.shape
    group = hq // k.shape[1]
    bq = bk = block or _pick_gqa_block(t)
    scale = 1.0 / (dh ** 0.5)
    masked = mask is not None
    # a kernel without a mask is built from the arguments it always had
    opts = {"masked": True} if masked else {}

    qspec, kvspec, vec, mspec = _gqa_specs(bq, bk, dh, group, window)
    return _gqa_call(
        functools.partial(_gqa_fwd_kernel, bq=bq, bk=bk, window=window,
                          scale=scale, **opts),
        (b, hq, t // bq, _band_blocks(t, window, bq, bk)),
        [qspec, kvspec, kvspec] + [mspec] * masked, (qspec, vec),
        (jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct((b, hq, t, 1), jnp.float32)),
        [pltpu.VMEM((bq, 1), jnp.float32), pltpu.VMEM((bq, 1), jnp.float32),
         pltpu.VMEM((bq, dh), jnp.float32)],
        (q, k, v) + (mask,) * masked, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def gqa_flash_attention(q, k, v, window=None, block=None, interpret=False):
    """Causal grouped-query attention. q: (B, Hq, T, Dh); k, v:
    (B, Hkv, T, Dh) with Hq a multiple of Hkv; any float dtype, float32
    accumulation. ``window``: key j is seen from query i only if
    ``i - j < window`` (None: every earlier key). ``block``: the query and
    key block size (None: the largest of 512..8 that divides T). Returns
    (B, Hq, T, Dh) in q's dtype."""
    return _gqa_fwd_call(q, k, v, window, block, interpret)[0]


def _gqa_fwd(q, k, v, window, block, interpret):
    # named inside the forward rule, so that output and residuals are the
    # named values and a block's replay (util/remat.py) runs no kernel
    o, lse = (keep(a, "attn_out")
              for a in _gqa_fwd_call(q, k, v, window, block, interpret))
    return o, (q, k, v, o, lse)


def _gqa_bwd(window, block, interpret, res, do, mask=None):
    q, k, v, o, lse = res
    b, hq, t, dh = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    bq = bk = block or _pick_gqa_block(t)
    nq = t // bq
    scale = 1.0 / (dh ** 0.5)
    masked = mask is not None
    opts = {"masked": True} if masked else {}
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(
        axis=-1, keepdims=True)                      # (B, Hq, T, 1)

    qspec, kvspec, vec, mspec = _gqa_specs(bq, bk, dh, group, window)
    dq = _gqa_call(
        functools.partial(_gqa_dq_kernel, bq=bq, bk=bk, window=window,
                          scale=scale, **opts),
        (b, hq, nq, _band_blocks(t, window, bq, bk)),
        [qspec, kvspec, kvspec] + [mspec] * masked + [qspec, vec, vec], qspec,
        jax.ShapeDtypeStruct(q.shape, q.dtype),
        [pltpu.VMEM((bq, dh), jnp.float32)],
        (q, k, v) + (mask,) * masked + (do, lse, delta), interpret)

    def q_index(b_, h, jk, g, i):
        lo, hi = _q_range(jk, bq, bk, window, nq)
        return b_, h * group + g, jnp.minimum(lo + i, hi), 0

    def mask_index(b_, h, jk, g, i):
        lo, hi = _q_range(jk, bq, bk, window, nq)
        return b_, jnp.minimum(lo + i, hi), jk

    qspec2 = pl.BlockSpec((None, None, bq, dh), q_index)
    vec2 = pl.BlockSpec((None, None, bq, 1), q_index)
    kvspec2 = pl.BlockSpec((None, None, bk, dh),
                           lambda b_, h, jk, g, i: (b_, h, jk, 0))
    mspec2 = pl.BlockSpec((None, bq, bk), mask_index)
    dk, dv = _gqa_call(
        functools.partial(_gqa_dkv_kernel, bq=bq, bk=bk, window=window,
                          scale=scale, nq=nq, **opts),
        (b, hkv, t // bk, group, _band_blocks(t, window, bk, bq)),
        [qspec2, kvspec2, kvspec2] + [mspec2] * masked + [qspec2, vec2, vec2],
        (kvspec2, kvspec2),
        (jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype)),
        [pltpu.VMEM((bk, dh), jnp.float32), pltpu.VMEM((bk, dh), jnp.float32)],
        (q, k, v) + (mask,) * masked + (do, lse, delta), interpret)
    return dq, dk, dv


gqa_flash_attention.defvjp(_gqa_fwd, _gqa_bwd)


# ============================================== attention over selected keys
# The same three kernels under a mask that is data: ``mask[b, i, j] != 0``
# where query i attends key j, one mask for all heads (int8, read a (query
# block, key block) tile at a time beside the key block). The mask holds
# causality itself, so only blocks on or under the diagonal are visited and
# a pair's position is not computed again. Every query must select at least
# one key.

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def gqa_selected_attention(q, k, v, mask, block=None, interpret=False):
    """Grouped-query attention over the keys ``mask`` selects. q, k, v as
    ``gqa_flash_attention``; mask: (B, T, T) int8, nonzero where the query
    (row) attends the key (column), zero above the diagonal. Returns
    ((B, Hq, T, Dh) in q's dtype, the log-sum-exp of each row's selected
    scores (B, Hq, T, 1) float32, which ``gqa_head_mean_probs`` reads)."""
    return _gqa_fwd_call(q, k, v, None, block, interpret, mask)


def _gqa_sel_fwd(q, k, v, mask, block, interpret):
    o, lse = (keep(a, "attn_out")
              for a in _gqa_fwd_call(q, k, v, None, block, interpret, mask))
    return (o, lse), (q, k, v, o, lse, mask)


def _gqa_sel_bwd(block, interpret, res, ct):
    *res, mask = res
    # the log-sum-exp leaves for a pass that takes no gradient
    return _gqa_bwd(None, block, interpret, res, ct[0], mask) + (None,)


gqa_selected_attention.defvjp(_gqa_sel_fwd, _gqa_sel_bwd)


def _head_mean_kernel(q_ref, k_ref, lse_ref, mask_ref, p_ref, acc_s, *, scale,
                      heads):
    i, j, h = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(h == 0)
    def _():
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    @pl.when(j <= i)
    def _():
        s = lax.dot_general(q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        acc_s[...] += jnp.where(mask_ref[...].astype(jnp.int32) != 0,
                                jnp.exp(s - lse_ref[...]), 0.0)

    @pl.when(h == heads - 1)
    def _():
        p_ref[...] = acc_s[...] * (1.0 / heads)


def gqa_head_mean_probs(q, k, lse, mask, block=None, interpret=False):
    """The mean over the query heads of the attention weights of
    ``gqa_selected_attention``: (B, T, T) float32, zero where ``mask`` is.
    q: (B, Hq, T, Dh), k: (B, Hkv, T, Dh), lse: (B, Hq, T, 1) as that call
    returned it. One (query block, key block) tile is summed over the heads
    in VMEM, so no (Hq, T, T) tensor exists. Takes no gradient."""
    b, hq, t, dh = q.shape
    group = hq // k.shape[1]
    blk = block or _pick_gqa_block(t)
    low = lambda j, i: jnp.minimum(j, i)       # above the diagonal: no fetch
    return pl.pallas_call(
        functools.partial(_head_mean_kernel, scale=1.0 / (dh ** 0.5),
                          heads=hq),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(b, t // blk, t // blk, hq),
            in_specs=[
                pl.BlockSpec((None, None, blk, dh),
                             lambda b_, i, j, h: (b_, h, i, 0)),
                pl.BlockSpec((None, None, blk, dh),
                             lambda b_, i, j, h: (b_, h // group, low(j, i),
                                                  0)),
                pl.BlockSpec((None, None, blk, 1),
                             lambda b_, i, j, h: (b_, h, i, 0)),
                pl.BlockSpec((None, blk, blk),
                             lambda b_, i, j, h: (b_, i, low(j, i)))],
            out_specs=pl.BlockSpec((None, blk, blk),
                                   lambda b_, i, j, h: (b_, i, j)),
            scratch_shapes=[pltpu.VMEM((blk, blk), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, t, t), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3 + ("arbitrary",)),
        interpret=interpret,
    )(q, k, lse, mask)
