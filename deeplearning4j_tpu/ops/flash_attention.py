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
from typing import NamedTuple

import numpy as np

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
# query head h reads kv head h // group, no copy repeated in memory) and a
# grid step holds the WHOLE query group of one kv head on one (query block,
# key block) tile: the key block, the value block and the mask tile are
# fetched once a step and serve all ``group`` heads, and what is visible on
# the tile is worked out once a step. Keys are streamed tile by tile with
# the running max, sum and accumulator in VMEM scratch; operands stay in
# their own dtype (bfloat16 on the training path) with float32 accumulation.
# The grid's last axis is the list of the tiles that hold a visible pair
# (``_tile_pairs``, prefetched to SMEM): above the diagonal and outside a
# ``window``'s band there is no step at all.

# what a step's query-side blocks may hold in VMEM (q, do and dq double
# buffered, dq's float32 accumulator, and the log-sum-exp and delta columns,
# which pad to 128 lanes): 2,048 rows at a head of 128 in bfloat16. With the
# key-side blocks and a product's temporaries the dq pass then holds about
# 14 MiB, inside the 16 MiB Mosaic scopes a kernel to on v5e by default
_GQA_STEP_BYTES = 8 * 1024 * 1024
# the rows of one product inside a step: a 512 x 512 tile's float32 scores
# and their temporaries are 4 MiB. On the chip 256 rows ran 17 % slower,
# 1,024 a slower forward pass, and all 2,048 rows 6 % faster for a 64 MiB
# VMEM limit and four times the kernels' code (PERF.md §6, PR 37)
_GQA_PRODUCT_ROWS = 512


class GqaPlan(NamedTuple):
    """How the three kernels tile one sequence (``gqa_plan``)."""
    bq: int         # query rows of a tile
    bk: int         # keys of a tile
    heads: int      # query heads stacked as the rows of one product
    rows: int       # query rows a grid step holds: group * bq
    steps: int      # grid steps a pass: kv heads * tiles with a visible pair
    skipped: int    # steps a (query block, band of key blocks) grid adds


def _pick_gqa_block(t):
    for b in (512, 256, 128, 64, 32, 16, 8):
        if t % b == 0:
            return b
    return None


def _tile_pairs(t, bq, bk, window, by_key=False):
    """The (query block, key block) tiles that hold a visible pair, as int32
    rows (outer block, inner block, first of its outer, last of its outer):
    query-major for the forward and dq passes, key-major (``by_key``) for
    dk/dv, whose outer block is the key block."""
    tiles = []
    for i in range(t // bq):
        lo = 0 if window is None else max(i * bq - window + 1, 0) // bk
        tiles += [(i, j) for j in range(lo, ((i + 1) * bq - 1) // bk + 1)]
    if by_key:
        tiles = sorted((j, i) for i, j in tiles)
    outer, inner = np.asarray(tiles).T
    edge = np.flatnonzero(np.diff(outer)) + 1
    first, last = np.zeros_like(outer), np.zeros_like(outer)
    first[np.r_[0, edge]] = 1
    last[np.r_[edge - 1, len(outer) - 1]] = 1
    return np.stack([outer, inner, first, last]).astype(np.int32)


def gqa_plan(t, n_heads, n_kv_heads, dh, window, block=None, itemsize=2):
    """The tile of ``gqa_flash_attention`` and ``gqa_selected_attention`` at
    ``t`` positions, from the shapes alone. The key block is the largest of
    512..8 that divides ``t``; the query block is the largest of the key
    block's halvings whose ``group * bq`` rows keep a step's query-side
    blocks within ``_GQA_STEP_BYTES``; as many heads as fit
    ``_GQA_PRODUCT_ROWS`` rows are stacked in one product, and the step
    walks the group in such sub-groups. ``block`` sets both blocks."""
    group = n_heads // n_kv_heads
    bq = bk = block or _pick_gqa_block(t)
    if not block:
        lanes = -(-dh // 128) * 128
        row = lanes * (6 * itemsize + 4) + 2 * 2 * 128 * 4
        while bq > 8 and group * bq * row > _GQA_STEP_BYTES:
            bq //= 2
    heads = max(h for h in range(1, group + 1)
                if group % h == 0 and (h == 1 or h * bq <= _GQA_PRODUCT_ROWS))
    tiles = _tile_pairs(t, bq, bk, window).shape[1]
    band = (t // bq) * (t // bk if window is None
                        else min(t // bk, (bq + window - 2) // bk + 2))
    return GqaPlan(bq, bk, heads, group * bq, n_kv_heads * tiles,
                   n_kv_heads * (band - tiles))


def _visible(qpos, kpos, window):
    ok = qpos >= kpos
    if window is not None:
        ok = jnp.logical_and(ok, qpos - kpos < window)
    return ok


def _tile_bias(mask_ref, iq, kb, bq, bk, window):
    """0 where a (query, key) pair of one tile is attended and -1e30 where
    not: by position, or where a ``mask`` operand is given by the mask alone
    (a selection that is data already holds causality:
    ``selected_keys_mask``). Added to float32 scores it leaves a visible
    score as it is and makes a hidden one exactly -1e30."""
    if mask_ref is not None:
        ok = mask_ref[...].astype(jnp.int32) != 0
    else:
        qpos = iq * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = kb * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = _visible(qpos, kpos, window)
    return jnp.where(ok, 0.0, _NEG)


def _each_sub_group(ref, heads, body):
    """``body(rows)`` for every sub-group of ``heads`` heads of the group
    that ``ref``'s leading axis holds, in a rolled loop."""
    n = ref.shape[0] // heads
    if n == 1:
        body(pl.ds(0, heads))
    else:
        lax.fori_loop(0, n, lambda c, _: body(pl.ds(c * heads, heads)), None)


def _stacked(ref, rows):
    """The heads ``rows`` of a (group, block, Dh) ref as one operand."""
    return ref[rows].reshape(-1, ref.shape[-1])


def _scores(q, k, bias_s, scale):
    """(heads, bq, bk) float32 scores of stacked query heads, hidden pairs
    at -1e30."""
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    return s.reshape(-1, *bias_s.shape) + bias_s[...]


def _gqa_fwd_kernel(tiles_ref, q_ref, k_ref, v_ref, *rest, bq, bk, heads,
                    window, scale, masked=False):
    mask_ref, rest = (rest[0], rest[1:]) if masked else (None, rest)
    o_ref, lse_ref, m_s, l_s, acc_s, bias_s = rest
    step = pl.program_id(2)
    iq, kb = tiles_ref[0, step], tiles_ref[1, step]

    @pl.when(tiles_ref[2, step] == 1)
    def _():
        m_s[...] = jnp.full(m_s.shape, _NEG, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    bias_s[...] = _tile_bias(mask_ref, iq, kb, bq, bk, window)

    def sub_group(rows):
        k, v = k_ref[...], v_ref[...]
        s = _scores(_stacked(q_ref, rows), k, bias_s, scale)
        m = m_s[rows]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_s[rows] = l_s[rows] * alpha + p.sum(axis=-1, keepdims=True)
        acc_s[rows] = acc_s[rows] * alpha + jnp.dot(
            p.reshape(-1, bk).astype(v.dtype), v,
            preferred_element_type=jnp.float32).reshape(heads, bq, -1)
        m_s[rows] = m_new

    _each_sub_group(q_ref, heads, sub_group)

    @pl.when(tiles_ref[3, step] == 1)
    def _():
        o_ref[...] = (acc_s[...] / l_s[...]).astype(o_ref.dtype)
        lse_ref[...] = m_s[...] + jnp.log(l_s[...])


def _probs_and_ds(q, k, v, do, lse_ref, delta_ref, rows, bias_s, scale):
    """A sub-group's attention weights and score gradients on one tile,
    (heads * bq, bk) float32 each."""
    p = jnp.exp(_scores(q, k, bias_s, scale) - lse_ref[rows])
    dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    ds = p * (dp.reshape(p.shape) - delta_ref[rows]) * scale
    return p.reshape(dp.shape), ds.reshape(dp.shape)


def _gqa_dq_kernel(tiles_ref, q_ref, k_ref, v_ref, *rest, bq, bk, heads,
                   window, scale, masked=False):
    mask_ref, rest = (rest[0], rest[1:]) if masked else (None, rest)
    do_ref, lse_ref, delta_ref, dq_ref, dq_s, bias_s = rest
    step = pl.program_id(2)
    iq, kb = tiles_ref[0, step], tiles_ref[1, step]

    @pl.when(tiles_ref[2, step] == 1)
    def _():
        dq_s[...] = jnp.zeros(dq_s.shape, jnp.float32)

    bias_s[...] = _tile_bias(mask_ref, iq, kb, bq, bk, window)

    def sub_group(rows):
        k = k_ref[...]
        _, ds = _probs_and_ds(
            _stacked(q_ref, rows), k, v_ref[...], _stacked(do_ref, rows),
            lse_ref, delta_ref, rows, bias_s, scale)
        dq_s[rows] += jnp.dot(
            ds.astype(k.dtype), k,
            preferred_element_type=jnp.float32).reshape(heads, bq, -1)

    _each_sub_group(q_ref, heads, sub_group)

    @pl.when(tiles_ref[3, step] == 1)
    def _():
        dq_ref[...] = dq_s[...].astype(dq_ref.dtype)


def _gqa_dkv_kernel(tiles_ref, q_ref, k_ref, v_ref, *rest, bq, bk, heads,
                    window, scale, masked=False):
    mask_ref, rest = (rest[0], rest[1:]) if masked else (None, rest)
    do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_s, dv_s, bias_s = rest
    step = pl.program_id(2)
    jk, qb = tiles_ref[0, step], tiles_ref[1, step]

    @pl.when(tiles_ref[2, step] == 1)
    def _():
        dk_s[...] = jnp.zeros(dk_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    bias_s[...] = _tile_bias(mask_ref, qb, jk, bq, bk, window)

    def sub_group(rows):
        q, do = _stacked(q_ref, rows), _stacked(do_ref, rows)
        p, ds = _probs_and_ds(q, k_ref[...], v_ref[...], do, lse_ref,
                              delta_ref, rows, bias_s, scale)
        # the group's heads are summed by the products' contraction
        dv_s[...] += lax.dot_general(p.astype(do.dtype), do,
                                     (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        dk_s[...] += lax.dot_general(ds.astype(q.dtype), q,
                                     (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)

    _each_sub_group(q_ref, heads, sub_group)

    @pl.when(tiles_ref[3, step] == 1)
    def _():
        dk_ref[...] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_s[...].astype(dv_ref.dtype)


def gqa_supported(t, dh, n_heads, n_kv_heads):
    """Shape screen of ``gqa_flash_attention``."""
    return (_pick_gqa_block(t) is not None and dh % 8 == 0 and dh <= 256
            and n_heads % n_kv_heads == 0)


def _gqa_call(kernel, plan, tiles, b, hkv, in_specs, out_specs, out_shape,
              scratch, args, interpret):
    """One pass over the grid (B, Hkv, tile of ``tiles``)."""
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, hkv, tiles.shape[1]),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch
            + [pltpu.VMEM((plan.bq, plan.bk), jnp.float32)]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(tiles), *args)


def _gqa_specs(plan, group, dh, by_key=False):
    """Block specs over the grid (B, Hkv, tile): the query block of the kv
    head's ``group`` heads (of q, o, do, dq: (B, Hq, T, Dh) in blocks of
    ``group`` heads), the kv head's key/value block, the group's per-row
    vector, and the tile of a (B, T, T) mask that all heads share, each at
    the blocks the step's tile names."""
    qrow, krow = (1, 0) if by_key else (0, 1)

    def q_index(b_, h, s, tiles):
        return b_, h, tiles[qrow, s], 0

    return (pl.BlockSpec((None, group, plan.bq, dh), q_index),
            pl.BlockSpec((None, None, plan.bk, dh),
                         lambda b_, h, s, tiles: (b_, h, tiles[krow, s], 0)),
            pl.BlockSpec((None, group, plan.bq, 1), q_index),
            pl.BlockSpec((None, plan.bq, plan.bk),
                         lambda b_, h, s, tiles: (b_, tiles[qrow, s],
                                                  tiles[krow, s])))


def _gqa_fwd_call(q, k, v, window, block, interpret, mask=None):
    b, hq, t, dh = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    plan = gqa_plan(t, hq, hkv, dh, window, block, q.dtype.itemsize)
    masked = mask is not None

    qspec, kvspec, vec, mspec = _gqa_specs(plan, group, dh)
    rows = (group, plan.bq)
    return _gqa_call(
        functools.partial(_gqa_fwd_kernel, bq=plan.bq, bk=plan.bk,
                          heads=plan.heads, window=window,
                          scale=1.0 / (dh ** 0.5), masked=masked),
        plan, _tile_pairs(t, plan.bq, plan.bk, window), b, hkv,
        [qspec, kvspec, kvspec] + [mspec] * masked, (qspec, vec),
        (jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct((b, hq, t, 1), jnp.float32)),
        [pltpu.VMEM(rows + (1,), jnp.float32),
         pltpu.VMEM(rows + (1,), jnp.float32),
         pltpu.VMEM(rows + (dh,), jnp.float32)],
        (q, k, v) + (mask,) * masked, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def gqa_flash_attention(q, k, v, window=None, block=None, interpret=False):
    """Causal grouped-query attention. q: (B, Hq, T, Dh); k, v:
    (B, Hkv, T, Dh) with Hq a multiple of Hkv; any float dtype, float32
    accumulation. ``window``: key j is seen from query i only if
    ``i - j < window`` (None: every earlier key). ``block``: the query and
    key block size (None: ``gqa_plan``'s). Returns (B, Hq, T, Dh) in q's
    dtype."""
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
    plan = gqa_plan(t, hq, hkv, dh, window, block, q.dtype.itemsize)
    masked = mask is not None
    opts = dict(bq=plan.bq, bk=plan.bk, heads=plan.heads, window=window,
                scale=1.0 / (dh ** 0.5), masked=masked)
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(
        axis=-1, keepdims=True)                      # (B, Hq, T, 1)
    args = (q, k, v) + (mask,) * masked + (do, lse, delta)

    def specs(by_key):
        qspec, kvspec, vec, mspec = _gqa_specs(plan, group, dh, by_key)
        return ([qspec, kvspec, kvspec] + [mspec] * masked
                + [qspec, vec, vec], qspec, kvspec)

    in_specs, qspec, _ = specs(by_key=False)
    dq = _gqa_call(
        functools.partial(_gqa_dq_kernel, **opts),
        plan, _tile_pairs(t, plan.bq, plan.bk, window), b, hkv,
        in_specs, qspec, jax.ShapeDtypeStruct(q.shape, q.dtype),
        [pltpu.VMEM((group, plan.bq, dh), jnp.float32)], args, interpret)

    in_specs, _, kvspec = specs(by_key=True)
    dk, dv = _gqa_call(
        functools.partial(_gqa_dkv_kernel, **opts),
        plan, _tile_pairs(t, plan.bq, plan.bk, window, by_key=True), b, hkv,
        in_specs, (kvspec, kvspec),
        (jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype)),
        [pltpu.VMEM((plan.bk, dh), jnp.float32),
         pltpu.VMEM((plan.bk, dh), jnp.float32)], args, interpret)
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


# The head-mean weights the indexer's loss reads: the same scores again,
# ``exp(score - lse)`` summed over the query heads tile by tile. A grid step
# holds every query head (or as many kv heads' groups as fit) on one tile
# that holds a visible pair, so q and the log-sum-exp are fetched once a
# query block; the mask is decoded once a tile; the tiles above the
# diagonal are a step each that writes zeros and fetches nothing new.

class HeadMeanPlan(NamedTuple):
    """How ``gqa_head_mean_probs`` tiles one sequence
    (``head_mean_plan``)."""
    bq: int         # query rows of a tile
    bk: int         # keys of a tile
    heads: int      # query heads stacked as the rows of one product
    kv_heads: int   # kv heads whose query groups a step holds
    steps: int      # grid steps: visible tiles x kv blocks, + zero tiles


def _head_mean_steps(t, bq, bk, kv_blocks):
    """The grid as int32 rows (query block, key block, kv block, output key
    block, first, last, zero): each tile that holds a visible pair,
    query-major, once for every one of ``kv_blocks`` blocks of kv heads
    (first and last mark its first and last step), and after a query
    block's last such tile each of its tiles above the diagonal once, as a
    ``zero`` step that names the blocks of the step before it (nothing is
    fetched) and writes zeros."""
    rows = []
    for i, j, _, last in _tile_pairs(t, bq, bk, None).T:
        rows += [(i, j, h, j, h == 0, h == kv_blocks - 1, 0)
                 for h in range(kv_blocks)]
        if last:
            rows += [(i, j, kv_blocks - 1, z, 0, 0, 1)
                     for z in range(j + 1, t // bk)]
    return np.asarray(rows, np.int32).T


def head_mean_plan(t, n_heads, n_kv_heads, dh, block=None, itemsize=2):
    """The tile of ``gqa_head_mean_probs`` at ``t`` positions, from the
    shapes alone. The key block is ``gqa_plan``'s; a step holds the query
    groups of the most kv heads (a divisor of ``n_kv_heads``) whose blocks
    fit ``_GQA_STEP_BYTES`` at a query block of 8 or more: q and the
    log-sum-exp column (which pads to 128 lanes) of every head held and the
    kv heads' key block, each double-buffered; the query block is the
    largest of the key block's halvings that fits. Heads are stacked to
    ``_GQA_PRODUCT_ROWS`` rows a product as ``gqa_plan`` stacks them.
    ``block`` sets both blocks and holds every kv head."""
    group = n_heads // n_kv_heads
    bk = block or _pick_gqa_block(t)
    lanes = -(-dh // 128) * 128

    def fits(kvs, bq):
        return 2 * kvs * (group * bq * (lanes * itemsize + 128 * 4)
                          + bk * lanes * itemsize) <= _GQA_STEP_BYTES

    for kvs in (d for d in range(n_kv_heads, 0, -1) if n_kv_heads % d == 0):
        bq = bk
        while not block and bq > 8 and not fits(kvs, bq):
            bq //= 2
        if block or fits(kvs, bq):
            break
    heads = max(h for h in range(1, group + 1)
                if group % h == 0 and (h == 1 or h * bq <= _GQA_PRODUCT_ROWS))
    tiles = _tile_pairs(t, bq, bk, None).shape[1]
    return HeadMeanPlan(bq, bk, heads, kvs, tiles * (n_kv_heads // kvs)
                        + (t // bq) * (t // bk) - tiles)


def _head_mean_kernel(steps_ref, q_ref, k_ref, lse_ref, mask_ref, p_ref, *,
                      heads, group, scale, inv_hq):
    step = pl.program_id(1)

    @pl.when(steps_ref[4, step] == 1)
    def _():
        p_ref[...] = jnp.zeros(p_ref.shape, jnp.float32)

    # the sub-groups unrolled: a product overlaps the last one's ``exp``
    # (a rolled loop ran 24 % slower on the chip, PERF.md §6, PR 39)
    @pl.when(steps_ref[6, step] == 0)
    def _():
        for c in range(q_ref.shape[0] // heads):
            rows = pl.ds(c * heads, heads)
            s = lax.dot_general(_stacked(q_ref, rows),
                                k_ref[c * heads // group],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            p_ref[...] += jnp.exp(s.reshape(heads, *p_ref.shape)
                                  - lse_ref[rows]).sum(axis=0)

    # the mask is decoded and applied once a tile, to the heads' sum: a
    # hidden pair reads exactly 0 whatever its score (``exp`` may overflow
    # there to inf, never to NaN)
    @pl.when(steps_ref[5, step] == 1)
    def _():
        p_ref[...] = jnp.where(mask_ref[...].astype(jnp.int32) != 0,
                               p_ref[...] * inv_hq, 0.0)

    @pl.when(steps_ref[6, step] == 1)
    def _():
        p_ref[...] = jnp.zeros(p_ref.shape, jnp.float32)


def gqa_head_mean_probs(q, k, lse, mask, block=None, interpret=False):
    """The mean over the query heads of the attention weights of
    ``gqa_selected_attention``: (B, T, T) float32, zero where ``mask`` is,
    above the diagonal included. q: (B, Hq, T, Dh), k: (B, Hkv, T, Dh),
    lse: (B, Hq, T, 1) as that call returned it. Tiled by
    ``head_mean_plan``: a grid step sums ``exp(score - lse)`` over the
    query heads it holds on one tile that holds a visible pair, in VMEM,
    so no (Hq, T, T) tensor exists. Takes no gradient."""
    b, hq, t, dh = q.shape
    hkv = k.shape[1]
    plan = head_mean_plan(t, hq, hkv, dh, block, q.dtype.itemsize)
    n = plan.kv_heads * (hq // hkv)            # query heads a step holds
    steps = _head_mean_steps(t, plan.bq, plan.bk, hkv // plan.kv_heads)
    qmap = lambda b_, s, st: (b_, st[2, s], st[0, s], 0)
    return pl.pallas_call(
        functools.partial(_head_mean_kernel, heads=plan.heads,
                          group=hq // hkv, scale=1.0 / (dh ** 0.5),
                          inv_hq=1.0 / hq),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, steps.shape[1]),
            in_specs=[
                pl.BlockSpec((None, n, plan.bq, dh), qmap),
                pl.BlockSpec((None, plan.kv_heads, plan.bk, dh),
                             lambda b_, s, st: (b_, st[2, s], st[1, s], 0)),
                pl.BlockSpec((None, n, plan.bq, 1), qmap),
                pl.BlockSpec((None, plan.bq, plan.bk),
                             lambda b_, s, st: (b_, st[0, s], st[1, s]))],
            out_specs=pl.BlockSpec((None, plan.bq, plan.bk),
                                   lambda b_, s, st: (b_, st[0, s],
                                                      st[3, s]))),
        out_shape=jax.ShapeDtypeStruct((b, t, t), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(steps), q, k, lse, mask)
