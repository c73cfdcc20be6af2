"""Flash decode-step (Pallas TPU): q-length-1 online-softmax attention
over a cached KV, masked by cache position.

The dense decode step (nn/layers/attention.py ``decode_step``) computes
scores against the FULL cache capacity ``C`` every token and masks the
future with ``-inf`` — O(C) HBM reads and O(C) flops per token no
matter how short the live prefix is. This kernel applies the
FlashAttention decomposition (Dao et al. 2022) to the single-query
case: the softmax is computed online per key block, and the block loop
STOPS at the block containing ``pos`` — work and bytes scale with the
live prefix length, not the allocated capacity. For a capacity-1024
cache at position 63 that is a 16x read reduction; it is the decode-side
companion of the training-side flash kernel (ops/flash_attention.py).

Layout: one grid program per (batch row x head). The query row is
replicated to 8 sublanes OUTSIDE the kernel so every block meets the
f32 (8, 128) tile floor — the 7 duplicate rows are VPU noise next to
the KV stream, and row 0 is written back. f32 accumulation throughout.

Supported: cache capacity divisible by a block size (8..128), head dim
a multiple of 8 (a multiple of 128 for the paged kernel), K+V within the
VMEM budget. Callers screen with ``supported()`` / ``supported_paged()``
and fall back to the dense step (which the bitwise-parity tests pin on
CPU), mirroring the cuDNN-helper seam.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30
_QROWS = 8                     # sublane floor for f32 tiles


def _pick_block(c):
    for b in (128, 64, 32, 16, 8):
        if c % b == 0:
            return b
    return None


# Mosaic scopes one kernel to 16 MiB of VMEM on v5e and lays every block
# out in (8, 128) tiles, so a head dim below 128 still costs 128 lanes.
# The screens below count that padded footprint against a budget that
# leaves room for the small blocks and the compiler's own temporaries;
# tests/test_tpu_compile.py holds them to "accepted means it compiles".
_LANES = 128
_VMEM_BUDGET = 12 * 1024 * 1024


def _pad(n, m):
    return -(-n // m) * m


def supported(c, dh):
    """Shape screen: blockable capacity, head dim a sublane multiple, and
    the K and V rows of one (batch, head) program — each held whole and
    double-buffered by the pipeline — within the VMEM budget."""
    return (_pick_block(c) is not None and dh % 8 == 0
            and 2 * 2 * c * _pad(dh, _LANES) * 4 <= _VMEM_BUDGET)


def supported_paged(block_size, dh, n_heads, interpret=False):
    """Shape screen for the paged kernel. The pools stay in HBM and a KV
    block (block_size, H, Dh) is the DMA unit, so the slice has to respect
    the tiling XLA gives the pool there: Mosaic refuses a head dim that is
    not a whole number of 128-lane tiles (the layout pads the minor dim)
    and, above 128, a head count that is not a whole number of 8-sublane
    tiles. Those shapes take the gather path (the interpreter has no
    tiling, so it takes any). The K and V staging blocks must fit VMEM
    with room to spare."""
    tiled = dh == _LANES or (dh % _LANES == 0 and n_heads % 8 == 0)
    return (block_size % 8 == 0 and (interpret or tiled)
            and 2 * block_size * _pad(n_heads, 8) * _pad(dh, _LANES) * 4
            <= 4 * 1024 * 1024)


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, *, blk, scale):
    p = pos_ref[pl.program_id(0)]               # this row's cache position
    q = q_ref[0]                                # (_QROWS, Dh) replicated query

    def body(j, carry):
        m, l, acc = carry
        kb = k_ref[0, pl.ds(j * blk, blk), :]   # (blk, Dh)
        vb = v_ref[0, pl.ds(j * blk, blk), :]
        s = lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        kpos = j * blk + lax.broadcasted_iota(jnp.int32, (_QROWS, blk), 1)
        s = jnp.where(kpos <= p, s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        pexp = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + pexp.sum(axis=-1, keepdims=True)
        acc = acc * alpha + jnp.dot(pexp, vb,
                                    preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((_QROWS, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((_QROWS, 1), jnp.float32)
    a0 = jnp.zeros((_QROWS, q.shape[-1]), jnp.float32)
    # the flash decode win: stop at the block holding ``pos`` — everything
    # beyond it is masked anyway, so it is never read from HBM
    upper = p // blk + 1
    m, l, acc = lax.fori_loop(0, upper, body, (m0, l0, a0))
    o_ref[0] = acc / l


def flash_decode_step(q, kc, vc, pos, *, interpret=False):
    """One attention decode step for every (batch, head) row.

    ``q``: (B, H, Dh) query at the current position; ``kc``/``vc``:
    (B, C, H, Dh) KV cache with position ``pos`` already written;
    ``pos``: (B,) int32 cache positions. Returns (B, H, Dh) f32 —
    softmax(q·K[:pos+1])·V[:pos+1] per head."""
    B, H, Dh = q.shape
    C = kc.shape[1]
    blk = _pick_block(C)
    if blk is None:
        raise ValueError(f"cache capacity {C} not blockable")
    scale = 1.0 / (Dh ** 0.5)

    fold = lambda a: (a.transpose(0, 2, 1, 3)
                      .reshape(B * H, C, Dh).astype(jnp.float32))
    kf, vf = fold(kc), fold(vc)
    qf = jnp.broadcast_to(q.astype(jnp.float32)[:, :, None, :],
                          (B, H, _QROWS, Dh)).reshape(B * H, _QROWS, Dh)
    # one position per (batch, head) row, scalar-prefetched whole into
    # SMEM: Mosaic refuses a (1, 1) SMEM block of a (B*H, 1) array
    posf = jnp.repeat(jnp.asarray(pos, jnp.int32), H)

    kern = functools.partial(_decode_kernel, blk=blk, scale=scale)
    row = lambda i, pos_ref: (i, 0, 0)
    o = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * H,),
            in_specs=[pl.BlockSpec((1, _QROWS, Dh), row,
                                   memory_space=pltpu.VMEM),
                      pl.BlockSpec((1, C, Dh), row,
                                   memory_space=pltpu.VMEM),
                      pl.BlockSpec((1, C, Dh), row,
                                   memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((1, _QROWS, Dh), row,
                                   memory_space=pltpu.VMEM)),
        out_shape=jax.ShapeDtypeStruct((B * H, _QROWS, Dh), jnp.float32),
        interpret=interpret,
    )(posf, qf, kf, vf)
    return o[:, 0, :].reshape(B, H, Dh)


def _paged_kernel(bt_ref, pos_ref, q_ref, kp_ref, vp_ref, o_ref,
                  kb_ref, vb_ref, sem_k, sem_v, *, bs, mb, scale):
    """One grid program per batch row. The pools stay in ``ANY`` memory
    (HBM); the page table rides in SMEM and steers one manual DMA per
    LIVE block — pos → (block, offset) indexing inside the fori_loop, so
    only ``pos // bs + 1`` physical blocks are ever pulled to VMEM no
    matter how fragmented the pool or how large the capacity. A block
    is copied whole, (bs, H, Dh), and every head reads its rows from the
    VMEM copy: a DMA that slices one head out of the pool cuts through
    the pool's HBM tiling, which Mosaic accepts only for some (H, Dh)."""
    b = pl.program_id(0)
    p = pos_ref[b]                              # this row's cache position
    nh, _, dh = q_ref.shape[1:]

    def body(j, carry):
        phys = bt_ref[b * mb + j]               # logical block j -> pool
        ck = pltpu.make_async_copy(kp_ref.at[phys], kb_ref, sem_k)
        cv = pltpu.make_async_copy(vp_ref.at[phys], vb_ref, sem_v)
        ck.start()
        cv.start()
        ck.wait()
        cv.wait()
        kpos = j * bs + lax.broadcasted_iota(jnp.int32, (_QROWS, bs), 1)
        out = []
        for h, (m, l, acc) in enumerate(carry):
            q = q_ref[0, h]                     # (_QROWS, Dh) replicated
            kb = kb_ref[:, h, :]                # (bs, Dh)
            vb = vb_ref[:, h, :]
            s = lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            s = jnp.where(kpos <= p, s, _NEG)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            pexp = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + pexp.sum(axis=-1, keepdims=True)
            acc = acc * alpha + jnp.dot(pexp, vb,
                                        preferred_element_type=jnp.float32)
            out.append((m_new, l, acc))
        return tuple(out)

    init = tuple((jnp.full((_QROWS, 1), _NEG, jnp.float32),
                  jnp.zeros((_QROWS, 1), jnp.float32),
                  jnp.zeros((_QROWS, dh), jnp.float32)) for _ in range(nh))
    upper = p // bs + 1                 # live blocks only — the paged
    heads = lax.fori_loop(0, upper, body, init)               # flash win
    for h, (_, l, acc) in enumerate(heads):
        o_ref[0, h] = acc / l


def flash_decode_step_paged(q, pk, pv, pos, block_tables, *,
                            interpret=False):
    """Paged decode step: attention over a block-pool KV cache.

    ``q``: (B, H, Dh) query at the current position; ``pk``/``pv``:
    (num_blocks, block_size, H, Dh) pool arrays with position ``pos``
    already scattered in; ``block_tables``: (B, max_blocks) int32 page
    tables; ``pos``: (B,) int32. Returns (B, H, Dh) f32 — bitwise role
    identical to ``flash_decode_step`` on the gathered dense cache."""
    B, H, Dh = q.shape
    bs = pk.shape[1]
    MB = block_tables.shape[1]
    scale = 1.0 / (Dh ** 0.5)
    qf = jnp.broadcast_to(q.astype(jnp.float32)[:, :, None, :],
                          (B, H, _QROWS, Dh))
    kern = functools.partial(_paged_kernel, bs=bs, mb=MB, scale=scale)
    row = lambda b, bt_ref, pos_ref: (b, 0, 0, 0)
    # page tables (flattened: a 2-D SMEM array pads every row to 128
    # words) and positions are scalar-prefetched whole into SMEM
    o = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, H, _QROWS, Dh), row,
                             memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, H, _QROWS, Dh), row,
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((bs, H, Dh), jnp.float32),
                            pltpu.VMEM((bs, H, Dh), jnp.float32),
                            pltpu.SemaphoreType.DMA,
                            pltpu.SemaphoreType.DMA]),
        out_shape=jax.ShapeDtypeStruct((B, H, _QROWS, Dh), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32).reshape(B * MB),
      jnp.asarray(pos, jnp.int32),
      qf, pk.astype(jnp.float32), pv.astype(jnp.float32))
    return o[:, :, 0, :]
