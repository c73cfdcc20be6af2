"""Index scores of a learned selection of keys (Pallas TPU).

``I[r, s] = sum_j w[r, j] relu(q[r, j] . k[s])``: J small heads over one
shared key head score every key of a chunk of query rows
(nn/layers/decoder.py, "a learned selection of keys"). XLA's form writes the
per-head product, a (J, R, S) float32 array, to HBM and reads it back for
the ``relu``, the head weights and the sum over heads, and its derivative
does so again for the two gradient products. Here a (rows, key block) tile
of ``I`` is made in VMEM: per head a (R, D) x (D, bk) product with float32
accumulation, ``relu``, the head weight, added into one float32 tile; only
the (R, S) result is written. The backward pass is one kernel of the same
kind: the per-head scores are made again in VMEM, ``dq_j`` accumulates over
the key blocks in scratch, ``dk`` of a key block is complete when its grid
step ends (a chunk's rows are one block), ``dw`` accumulates in its output
block. No array with a heads axis over (rows, keys) exists in HBM.

Operands stay in the dtype they arrive in (bfloat16 on the training path)
with float32 accumulation and float32 head weights, as the einsum has them;
the score's cotangent times the head weight is rounded to the operands'
dtype for the two gradient products, as the flash kernels round theirs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import CallForms

# the VMEM the kernels ask Mosaic for (its default scope on v5e is 16 MiB,
# which the benchmark's chunk of 512 rows of 16 heads fits; XLA sets HBM
# aside around a kernel by what it asks for, about 40 MB a step program
# from 20 to 32 MiB)
_VMEM_LIMIT = 20 * 1024 * 1024


def _key_block(s):
    for b in (512, 256, 128):
        if s % b == 0:
            return b
    return None


def supported(r, j, d, s, itemsize):
    """Shape screen: ``r`` query rows of ``j`` heads of ``d`` against ``s``
    keys, operands of ``itemsize`` bytes. The key extent a whole number of
    128-lane blocks, the rows whole sublane tiles, the head dim as the flash
    kernels' (a multiple of 8 up to 256), the head weights one lane tile,
    and the backward pass within the VMEM asked for: a chunk's rows are ONE
    block there, with all heads' queries, their gradient and its float32
    scratch (D padded to 128 lanes) beside about three (rows, key block)
    float32 tiles. Held to the compiler at the benchmark's 16 heads of 64
    by tests/test_tpu_compile.py (896 rows of bfloat16 and 640 of float32
    compile, 1024 and 896 do not)."""
    bk = _key_block(s)
    if bk is None or r % 8 or d % 8 or not (0 < d <= 256 and 0 < j <= 128):
        return False
    heads = j * -(-d // 128) * 128 * (4 + 2 * itemsize)
    return (heads + 3 * bk * 4) * r <= _VMEM_LIMIT


def _scores(q, k):
    return lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


# The forward pass unrolls its heads (a Python loop): the compiler then
# overlaps one head's product with another's elementwise passes, and a chunk
# takes half the time of a rolled loop's (3 % of the benchmark's step for
# 26 MB of code). The backward pass keeps the rolled loop: unrolled it is a
# fifth faster (1 % of that step) for 49 MB more code in a step program
# that has 2 GB of the chip left.

def _fwd_kernel(q_ref, w_ref, k_ref, o_ref, *, heads):
    k, w = k_ref[...], w_ref[...]
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for j in range(heads):
        s = _scores(q_ref[j], k)                           # (R, bk)
        acc = acc + jnp.maximum(s, 0.0) * w[:, j:j + 1]
    o_ref[...] = acc


def _bwd_kernel(q_ref, w_ref, k_ref, g_ref, dq_ref, dw_ref, dk_ref, dq_s, *,
                heads):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _():
        dq_s[...] = jnp.zeros(dq_s.shape, jnp.float32)
        dw_ref[...] = jnp.zeros(dw_ref.shape, jnp.float32)

    k, w, g = k_ref[...], w_ref[...], g_ref[...]
    lane = lax.broadcasted_iota(jnp.int32, w.shape, 1)

    def head(j, carry):
        dk, dw = carry
        q = q_ref[j]
        s = _scores(q, k)                                  # (R, bk)
        dw = dw + jnp.where(lane == j, jnp.sum(
            g * jnp.maximum(s, 0.0), axis=1, keepdims=True), 0.0)
        # column j of the head weights, j traced: no dynamic lane slice
        wj = jnp.sum(jnp.where(lane == j, w, 0.0), axis=1, keepdims=True)
        ds = jnp.where(s > 0, g * wj, 0.0).astype(k.dtype)
        dq_s[j] += jnp.dot(ds, k, preferred_element_type=jnp.float32)
        return dk + lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32), dw

    dk, dw = lax.fori_loop(0, heads, head, (
        jnp.zeros(dk_ref.shape, jnp.float32), jnp.zeros(w.shape, jnp.float32)))
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dw_ref[...] += dw

    @pl.when(step == pl.num_programs(0) - 1)
    def _():
        dq_ref[...] = dq_s[...].astype(dq_ref.dtype)


def _call(kernel, grid, in_specs, out_specs, out_shape, scratch, args,
          interpret):
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*args)


def _plan(qi, ki):
    """(heads, grid, block specs) of both kernels, over a grid of key
    blocks: all heads' queries (J, R, D) and the head weights (R, J) whole,
    the key block, the (R, bk) tile."""
    r, j, d = qi.shape
    s = ki.shape[0]
    bk = _key_block(s)
    return j, (s // bk,), (pl.BlockSpec((j, r, d), lambda n: (0, 0, 0)),
                           pl.BlockSpec((r, j), lambda n: (0, 0)),
                           pl.BlockSpec((bk, d), lambda n: (n, 0)),
                           pl.BlockSpec((r, bk), lambda n: (0, n)))


def _fwd_call(qi, wi, ki, interpret):
    heads, grid, (qspec, wspec, kspec, tile) = _plan(qi, ki)
    return _call(functools.partial(_fwd_kernel, heads=heads), grid,
                 [qspec, wspec, kspec], tile,
                 jax.ShapeDtypeStruct((qi.shape[0], ki.shape[0]),
                                      jnp.float32), [],
                 (qi.transpose(1, 0, 2), wi, ki), interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def index_scores(qi, wi, ki, interpret=False):
    """qi (R, J, D), wi (R, J) float32, ki (S, D) -> (R, S) float32, for
    shapes ``supported`` accepts, in key blocks of the largest of 512, 256,
    128 that divides S (on the chip 512 beats 256 by a tenth and 128 by a
    third). Differentiable in qi, wi, ki."""
    return _fwd_call(qi, wi, ki, interpret)


def _fwd(qi, wi, ki, interpret):
    return _fwd_call(qi, wi, ki, interpret), (qi, wi, ki)


def _bwd(interpret, res, g):
    qi, wi, ki = res
    heads, grid, (qspec, wspec, kspec, tile) = _plan(qi, ki)
    qt = qi.transpose(1, 0, 2)
    dq, dw, dk = _call(
        functools.partial(_bwd_kernel, heads=heads), grid,
        [qspec, wspec, kspec, tile], (qspec, wspec, kspec),
        (jax.ShapeDtypeStruct(qt.shape, qi.dtype),
         jax.ShapeDtypeStruct(wi.shape, jnp.float32),
         jax.ShapeDtypeStruct(ki.shape, ki.dtype)),
        [pltpu.VMEM(qt.shape, jnp.float32)], (qt, wi, ki, g), interpret)
    return dq.transpose(1, 0, 2), dw.astype(wi.dtype), dk


index_scores.defvjp(_fwd, _bwd)


# ------------------------------------------------- which form a step traced
# ``note_call(form)`` counts one index-score call into the dict that
# ``counting_calls(into)`` holds open on this thread
_calls = CallForms()
FORMS, note_call, counting_calls = _calls.FORMS, _calls.note, _calls.counting
