"""Fused LSTM sequence kernel (Pallas TPU).

The TPU-native replacement for the reference's cuDNN fused RNN path
(deeplearning4j-cuda CudnnLSTMHelper.java:588 cudnnRNNForwardTraining,
:250 cudnnRNNBackwardData, :262 cudnnRNNBackwardWeights). Like cuDNN, it

- assumes the input-to-gate projection ``x @ W + b`` was done as ONE large
  MXU GEMM outside the time loop (the layer does this already),
- runs the whole time loop inside a single kernel launch: the TPU grid is
  executed sequentially, so VMEM scratch carries (h, c) across grid steps
  with zero HBM round-trips,
- saves a "reserve space" from the forward (post-activation gates, tanh(c)
  and c_prev streams) so the backward pass never recomputes the forward,
- has a hand-written backward kernel that walks the grid in reverse and
  emits per-step pre-activation gate gradients dz; the weight gradients
  are then big GEMMs outside the kernel (dW = x^T dz, dRW = h_prev^T dz)
  — exactly how cudnnRNNBackwardWeights batches its GEMMs.

Streams may be float32 or bfloat16 (the layer passes its compute dtype
through); all cell math and both carries run in float32 regardless — the
mixed-precision regime cuDNN uses for fp16 RNNs (fp16 streams, fp32 math).

Performance model (why the design looks like this): at training shapes the
sequence kernel is HBM-bandwidth-bound — per step it streams the (K,B,4H)
gate block plus the reserve-space writes — so the wins come from (a) bf16
streams halving traffic, (b) returning only the FINAL cell state (the full
cs sequence was a dead output: the layer uses hs + the last carry), and
(c) storing tanh(c)/c_prev from the forward so the backward neither
recomputes tanh nor materializes a shifted copy of cs. At small B*H the
loop is latency-bound instead and XLA's scan codegen beats Mosaic's, so
``fused_lstm_sequence`` routes the *forward* to an equivalent lax.scan
below a measured size threshold. The backward routes the same way
(``_scan_bwd`` mirrors the reverse kernel's math): the Pallas backward
wins at most validated shapes, but KERNELS_TPU.json carries two
measured bf16 losses — see exec/routing.py ``lstm_grad_route``.

Supported config (like cuDNN's CUDNN_LSTM mode): sigmoid gates, tanh cell
activation, no peepholes, no step masking. The layer falls back to the
pure-jnp `lax.scan` path otherwise (parity with CudnnLSTMHelper's
`supported` checks).

Gate order is IFOG to match the reference's LSTMParamInitializer layout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

f32 = jnp.float32

# VMEM working budget (v5e has 16 MiB/core; leave headroom for Mosaic's own
# temporaries). All K sizing and the supported() screen derive from this one
# number plus the actual per-pass stream footprints — see _pick_k.
_VMEM_BUDGET = 10 * 1024 * 1024

# Streams per (timestep, batch-row), in units of H elements, for each pass:
#   fwd inference: gate_in(4H read) + hs(H write)                      = 5H
#   fwd training:  + tanh_c(H) + c_prev(H) + gates(4H) reserve writes  = 11H
#   backward:      gates(4H) + tanh_c(H) + c_prev(H) + dhs(H) reads
#                  + dz(4H) write                                      = 11H
_ELEMS_INFER = 5
_ELEMS_TRAIN = 11
_ELEMS_BWD = 11

# Use the Pallas forward only when the per-step GEMM is wide enough to be
# bandwidth- rather than latency-bound; below this XLA's scan codegen wins
# (measured on v5e: (8,·,120) B*H=960 loses at ~0.6x, (16,·,128) B*H=2048
# is the crossover, (32,·,256)+ wins). The backward kernel wins everywhere.
_PALLAS_FWD_MIN_BH = 2048


def _resident_bytes(b, h, itemsize):
    """VMEM held for the whole kernel: the RW block + carries/scratch/h0/c0
    (scratch and carry math are always f32)."""
    return h * 4 * h * itemsize + 8 * b * h * 4


def _pick_k(t, b, h, itemsize, elems_h, resident=None):
    """Largest K dividing T whose double-buffered stream blocks plus the
    resident weight/scratch blocks fit the VMEM budget. Sizing from the
    TOTAL per-grid-step footprint (all blocked operands x2 for double
    buffering) — not just one stream — is what keeps Mosaic from
    oversubscribing VMEM at large B*H (the round-3 failure mode).
    ``resident`` overrides the single-layer weight/scratch footprint (the
    stacked kernel holds a 3x-wider weight block and twice the carries)."""
    if resident is None:
        resident = _resident_bytes(b, h, itemsize)
    # Prefer K=2: the sequentially-executed grid double-buffers the next
    # block behind the current one, so SMALL blocks overlap loads/stores
    # with compute best — measured on v5e at (256,64,256): K=2 144us,
    # K=4 163us, K=8 197us for the training forward, and end-to-end
    # charRNN (normalized by the same run's scan baseline to cancel pool
    # contention) 2.31x at K=2 vs 1.42x at K=4. Larger K only amortizes
    # grid overhead, which is not the bottleneck.
    for k in (2, 1):
        if t % k == 0 and 2 * k * b * elems_h * h * itemsize + resident \
                <= _VMEM_BUDGET:
            return k
    return 1


def supported(b, t, h, itemsize=4, interpret=False):
    """Shape screen for the compiled kernel (the interpreter has no tiling
    constraints): hidden size a multiple of the 8-row sublane tile, and the
    worst pass (backward) within the VMEM budget even at K=1 — otherwise
    Mosaic fails at compile time instead of falling back. Conservative on
    purpose: every shape it accepts compiles for v5e (swept to the budget's
    edge in PR 21, pinned at the charRNN shapes by
    tests/test_tpu_compile.py); Mosaic also takes some it rejects (H 12,
    100; H 1024 in bf16), which then run the scan."""
    if interpret:
        return True
    return (h % 8 == 0
            and 2 * b * _ELEMS_BWD * h * itemsize
            + _resident_bytes(b, h, itemsize) <= _VMEM_BUDGET)


def use_pallas_fwd(b, h, t=None, dtype=None):
    """Forward routing: Pallas when bandwidth-bound, lax.scan when the
    sequential small-GEMM chain is latency-bound. The decision lives in
    the shape-keyed routing table (exec/routing.py) — measured rows from
    KERNELS_TPU.json first, the ``B*H >= 2048`` crossover heuristic in
    between, pinnable via ``DL4JTPU_LSTM_FWD_ROUTE``. Callers that know
    T and dtype should pass them: two measured f32 shapes route to scan
    that the bare crossover heuristic would send to Pallas."""
    from deeplearning4j_tpu.exec.routing import lstm_fwd_route
    return lstm_fwd_route(b, h, t=t, dtype=dtype) == "pallas"


def _cell_math(z, c, H):
    """Post-GEMM cell math in f32. Activations run on two contiguous lane
    blocks (sigmoid over [i|f|o], tanh over g) instead of four per-gate
    slices. Returns (h, c, tanh(c), gates)."""
    sp = jax.nn.sigmoid(z[:, 0:3 * H])
    g = jnp.tanh(z[:, 3 * H:4 * H])
    i = sp[:, 0 * H:1 * H]
    f = sp[:, 1 * H:2 * H]
    o = sp[:, 2 * H:3 * H]
    c_new = f * c + i * g
    tc = jnp.tanh(c_new)
    h_new = o * tc
    gates = jnp.concatenate([sp, g], axis=-1)
    return h_new, c_new, tc, gates


def _gate_z(gate_in_k, h, rw):
    """z_t = gate_in_t + h_{t-1} @ RW with f32 accumulation. For bf16
    streams the carry is cast to the stream dtype so the MXU runs its
    native bf16 x bf16 -> f32 mode (casting RW up instead would materialize
    an (H,4H) f32 copy every step). Shared by the Pallas kernels (pass
    ``rw_ref[:]``) and the scan-routed forward, so the two paths cannot
    desynchronize numerically."""
    hd = h if rw.dtype == f32 else h.astype(rw.dtype)
    return gate_in_k.astype(f32) + jnp.dot(hd, rw,
                                           preferred_element_type=f32)


def _fwd_inference_kernel(K, gate_in_ref, rw_ref, h0_ref, c0_ref,
                          hs_ref, cT_ref, h_s, c_s):
    """Forward without reserve space (parity: cudnnRNNForwardInference vs
    ForwardTraining). ``K`` timesteps per grid step (statically unrolled)
    amortize per-step grid/pipelining overhead. Only hs and the final cell
    state leave the kernel."""
    t = pl.program_id(0)
    H = h_s.shape[-1]

    @pl.when(t == 0)
    def _():
        h_s[:] = h0_ref[:].astype(f32)
        c_s[:] = c0_ref[:].astype(f32)

    h, c = h_s[:], c_s[:]
    for k in range(K):
        z = _gate_z(gate_in_ref[k], h, rw_ref[:])
        h, c, _, _ = _cell_math(z, c, H)
        hs_ref[k] = h.astype(hs_ref.dtype)
    h_s[:] = h
    c_s[:] = c
    # last write wins == c_{T-1}
    cT_ref[:] = c.astype(cT_ref.dtype)


def _fwd_kernel(K, gate_in_ref, rw_ref, h0_ref, c0_ref,
                hs_ref, tc_ref, cprev_ref, gates_ref, cT_ref, h_s, c_s):
    """Training forward: one grid step = K timesteps (statically unrolled).
    Scratch (h_s, c_s) persists across the sequentially-executed TPU grid;
    the reserve space (tanh_c, c_prev, gates) feeds the backward kernel."""
    t = pl.program_id(0)
    H = h_s.shape[-1]

    @pl.when(t == 0)
    def _():
        h_s[:] = h0_ref[:].astype(f32)
        c_s[:] = c0_ref[:].astype(f32)

    h, c = h_s[:], c_s[:]
    for k in range(K):
        cprev_ref[k] = c.astype(cprev_ref.dtype)
        z = _gate_z(gate_in_ref[k], h, rw_ref[:])
        h, c, tc, gates = _cell_math(z, c, H)
        # one full-width gates store: per-gate slice stores are lane-aligned
        # only when H % 128 == 0; Mosaic rejects partial-lane writes otherwise
        gates_ref[k] = gates.astype(gates_ref.dtype)
        hs_ref[k] = h.astype(hs_ref.dtype)
        tc_ref[k] = tc.astype(tc_ref.dtype)
    h_s[:] = h
    c_s[:] = c
    cT_ref[:] = c.astype(cT_ref.dtype)


def _bwd_kernel(K, gates_ref, tc_ref, cprev_ref, rw_ref, dhs_ref, dcT_ref,
                dz_ref, dh0_ref, dc0_ref, dh_rec_s, dc_s):
    """Reverse-time grid step (index maps flip t), K timesteps per grid
    step walked in reverse inside the block. Carries the recurrent
    gradient dh_rec = dz_{t+1} @ RW^T and dc in scratch; dc starts from
    the final-cell-state cotangent."""
    t = pl.program_id(0)
    H = dh_rec_s.shape[-1]

    @pl.when(t == 0)
    def _():
        dh_rec_s[:] = jnp.zeros_like(dh_rec_s)
        dc_s[:] = dcT_ref[:].astype(f32)

    dh_rec = dh_rec_s[:]
    dc_carry = dc_s[:]
    for k in reversed(range(K)):
        i = gates_ref[k, :, 0 * H:1 * H].astype(f32)
        f = gates_ref[k, :, 1 * H:2 * H].astype(f32)
        o = gates_ref[k, :, 2 * H:3 * H].astype(f32)
        g = gates_ref[k, :, 3 * H:4 * H].astype(f32)
        tc = tc_ref[k].astype(f32)
        cp = cprev_ref[k].astype(f32)

        dh = dhs_ref[k].astype(f32) + dh_rec
        do = dh * tc
        dc = dc_carry + dh * o * (1.0 - tc * tc)
        di = dc * g
        dg = dc * i
        df = dc * cp

        dz = jnp.concatenate([di * i * (1.0 - i), df * f * (1.0 - f),
                              do * o * (1.0 - o), dg * (1.0 - g * g)],
                             axis=-1)
        dz_ref[k] = dz.astype(dz_ref.dtype)
        # dh_{t-1} recurrent contribution: dz_t @ RW^T (contract the 4H axis)
        dzd = dz if rw_ref.dtype == f32 else dz.astype(rw_ref.dtype)
        dh_rec = lax.dot_general(dzd, rw_ref[:], (((1,), (1,)), ((), ())),
                                 preferred_element_type=f32)
        dc_carry = dc * f
    dh_rec_s[:] = dh_rec
    dc_s[:] = dc_carry
    # final (t == T-1 in reverse order == timestep 0) carries are the
    # gradients w.r.t. h0/c0; writing every step is fine, last write wins.
    dh0_ref[:] = dh_rec
    dc0_ref[:] = dc_carry


def _fwd_call(gate_in, rw, h0, c0, *, interpret, save_reserve):
    T, B, G = gate_in.shape
    H = G // 4
    dt = gate_in.dtype
    isz = dt.itemsize if hasattr(dt, "itemsize") else jnp.dtype(dt).itemsize
    K = _pick_k(T, B, H, isz,
                _ELEMS_TRAIN if save_reserve else _ELEMS_INFER)
    step_b = lambda t: (t, 0, 0)
    fixed2 = lambda t: (0, 0)
    in_specs = [
        pl.BlockSpec((K, B, G), step_b, memory_space=pltpu.VMEM),
        pl.BlockSpec((H, G), fixed2, memory_space=pltpu.VMEM),
        pl.BlockSpec((B, H), fixed2, memory_space=pltpu.VMEM),
        pl.BlockSpec((B, H), fixed2, memory_space=pltpu.VMEM),
    ]
    state_spec = pl.BlockSpec((K, B, H), step_b, memory_space=pltpu.VMEM)
    fixed_spec = pl.BlockSpec((B, H), fixed2, memory_space=pltpu.VMEM)
    state_shape = jax.ShapeDtypeStruct((T, B, H), dt)
    fixed_shape = jax.ShapeDtypeStruct((B, H), dt)
    scratch = [pltpu.VMEM((B, H), f32), pltpu.VMEM((B, H), f32)]
    if save_reserve:
        return pl.pallas_call(
            functools.partial(_fwd_kernel, K),
            grid=(T // K,),
            in_specs=in_specs,
            out_specs=(state_spec, state_spec, state_spec,
                       pl.BlockSpec((K, B, G), step_b,
                                    memory_space=pltpu.VMEM),
                       fixed_spec),
            out_shape=(state_shape, state_shape, state_shape,
                       jax.ShapeDtypeStruct((T, B, G), dt), fixed_shape),
            scratch_shapes=scratch,
            interpret=interpret,
        )(gate_in, rw, h0, c0)              # hs, tc, cprev, gates, cT
    hs, cT = pl.pallas_call(
        functools.partial(_fwd_inference_kernel, K),
        grid=(T // K,),
        in_specs=in_specs,
        out_specs=(state_spec, fixed_spec),
        out_shape=(state_shape, fixed_shape),
        scratch_shapes=scratch,
        interpret=interpret,
    )(gate_in, rw, h0, c0)
    return hs, cT


def _bwd_call(gates, tc, cprev, rw, dhs, dcT, *, interpret):
    T, B, G = gates.shape
    H = G // 4
    dt = gates.dtype
    isz = jnp.dtype(dt).itemsize
    K = _pick_k(T, B, H, isz, _ELEMS_BWD)
    n_blocks = T // K
    rev_b = lambda t: (n_blocks - 1 - t, 0, 0)
    fixed2 = lambda t: (0, 0)
    dz, dh0, dc0 = pl.pallas_call(
        functools.partial(_bwd_kernel, K),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((K, B, G), rev_b, memory_space=pltpu.VMEM),
            pl.BlockSpec((K, B, H), rev_b, memory_space=pltpu.VMEM),
            pl.BlockSpec((K, B, H), rev_b, memory_space=pltpu.VMEM),
            pl.BlockSpec((H, G), fixed2, memory_space=pltpu.VMEM),
            pl.BlockSpec((K, B, H), rev_b, memory_space=pltpu.VMEM),
            pl.BlockSpec((B, H), fixed2, memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((K, B, G), rev_b, memory_space=pltpu.VMEM),
            pl.BlockSpec((B, H), fixed2, memory_space=pltpu.VMEM),
            pl.BlockSpec((B, H), fixed2, memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((T, B, G), dt),
            jax.ShapeDtypeStruct((B, H), f32),
            jax.ShapeDtypeStruct((B, H), f32),
        ),
        scratch_shapes=[pltpu.VMEM((B, H), f32), pltpu.VMEM((B, H), f32)],
        interpret=interpret,
    )(gates, tc, cprev, rw, dhs, dcT)
    return dz, dh0, dc0


# ------------------------------------------------------- scan-routed forward

def _scan_fwd(gate_in, rw, h0, c0, *, save_reserve):
    """lax.scan forward on the kernel's exact contract (f32 carries, stream-
    dtype outputs, same reserve space). Used below the Pallas routing
    threshold, where the sequential chain is latency-bound."""
    H = h0.shape[-1]
    dt = gate_in.dtype

    def step(carry, z_t):
        h, c = carry
        z = _gate_z(z_t, h, rw)
        h2, c2, tc, gates = _cell_math(z, c, H)
        if save_reserve:
            out = (h2.astype(dt), tc.astype(dt), c.astype(dt),
                   gates.astype(dt))
        else:
            out = h2.astype(dt)
        return (h2, c2), out

    (hT, cT), outs = lax.scan(step, (h0.astype(f32), c0.astype(f32)),
                              gate_in)
    if save_reserve:
        hs, tc, cprev, gates = outs
        return hs, tc, cprev, gates, cT.astype(dt)
    return outs, cT.astype(dt)


# ------------------------------------------------------ scan-routed backward

def _scan_bwd(gates, tc, cprev, rw, dhs, dcT):
    """Reverse-time lax.scan on the backward kernel's exact math (same
    f32 carries, same dz/dh0/dc0 contract as ``_bwd_call``). Used where
    the measured table says the reverse-grid kernel loses — the two
    validated bf16 losses are latency-bound small shapes, the same
    regime where the forward scans (see exec/routing.py)."""
    T, B, G = gates.shape
    H = G // 4

    def step(carry, inp):
        dh_rec, dc_carry = carry
        gates_t, tc_t, cp_t, dhs_t = inp
        i = gates_t[:, 0 * H:1 * H].astype(f32)
        f = gates_t[:, 1 * H:2 * H].astype(f32)
        o = gates_t[:, 2 * H:3 * H].astype(f32)
        g = gates_t[:, 3 * H:4 * H].astype(f32)
        tc_ = tc_t.astype(f32)
        cp = cp_t.astype(f32)

        dh = dhs_t.astype(f32) + dh_rec
        do = dh * tc_
        dc = dc_carry + dh * o * (1.0 - tc_ * tc_)
        di = dc * g
        dg = dc * i
        df = dc * cp

        dz = jnp.concatenate([di * i * (1.0 - i), df * f * (1.0 - f),
                              do * o * (1.0 - o), dg * (1.0 - g * g)],
                             axis=-1)
        dzd = dz if rw.dtype == f32 else dz.astype(rw.dtype)
        dh_rec = lax.dot_general(dzd, rw, (((1,), (1,)), ((), ())),
                                 preferred_element_type=f32)
        return (dh_rec, dc * f), dz.astype(gates.dtype)

    (dh0, dc0), dz = lax.scan(
        step, (jnp.zeros((B, H), f32), dcT.astype(f32)),
        (gates, tc, cprev, dhs), reverse=True)
    return dz, dh0, dc0


def use_pallas_bwd(b, h, t=None, dtype=None, interpret=False):
    """Backward routing: the reverse-grid Pallas kernel vs the reverse
    lax.scan above. Measurement-driven exactly like the forward
    (exec/routing.py ``lstm_grad_route`` — KERNELS_TPU.json
    ``grad_route``/``grad_speedup`` rows plus autotune), default
    pallas. Interpret mode skips the measured table (CPU tests must
    keep exercising the kernel) but still honors pins/env, so either
    side is forceable on any backend."""
    from deeplearning4j_tpu.exec.routing import lstm_grad_route
    if interpret:
        return lstm_grad_route(b, h) == "pallas"
    return lstm_grad_route(b, h, t=t, dtype=dtype,
                           backend=jax.default_backend()) == "pallas"


# ------------------------------------------------------------- public op

@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def fused_lstm_sequence(gate_in, rw, h0, c0, interpret=False):
    """Run a full LSTM over precomputed gate inputs.

    gate_in: (T, B, 4H) = x @ W + b, IFOG gate order, f32 or bf16.
    rw: (H, 4H) recurrent weights. h0/c0: (B, H) initial state.
    Returns (hs, c_last): per-step hidden states (T, B, H) and the final
    cell state (B, H). (The full cell-state sequence was a dead output —
    the layer only ever used the last step — so it is not materialized;
    this halves the inference kernel's write traffic.)
    """
    B, H = h0.shape
    if not interpret and not use_pallas_fwd(B, H, t=gate_in.shape[0],
                                            dtype=gate_in.dtype):
        return _scan_fwd(gate_in, rw, h0, c0, save_reserve=False)
    return _fwd_call(gate_in, rw, h0, c0, interpret=interpret,
                     save_reserve=False)


def _fused_fwd(gate_in, rw, h0, c0, interpret):
    B, H = h0.shape
    if not interpret and not use_pallas_fwd(B, H, t=gate_in.shape[0],
                                            dtype=gate_in.dtype):
        hs, tc, cprev, gates, cT = _scan_fwd(gate_in, rw, h0, c0,
                                             save_reserve=True)
    else:
        hs, tc, cprev, gates, cT = _fwd_call(gate_in, rw, h0, c0,
                                             interpret=interpret,
                                             save_reserve=True)
    return (hs, cT), (rw, h0, c0, hs, tc, cprev, gates)


def _fused_bwd(interpret, res, grads):
    rw, h0, c0, hs, tc, cprev, gates = res
    dhs, dcT = grads
    B, H = h0.shape
    if use_pallas_bwd(B, H, t=gates.shape[0], dtype=gates.dtype,
                      interpret=interpret):
        dz, dh0, dc0 = _bwd_call(gates, tc, cprev, rw,
                                 dhs.astype(gates.dtype),
                                 dcT.astype(gates.dtype),
                                 interpret=interpret)
    else:
        dz, dh0, dc0 = _scan_bwd(gates, tc, cprev, rw,
                                 dhs.astype(gates.dtype),
                                 dcT.astype(gates.dtype))
    # weight gradient = big batched GEMMs (cudnnRNNBackwardWeights parity);
    # h_prev is expressed as slices of hs (+ the h0 rank-1 term) instead of
    # materializing a shifted copy.
    drw = (jnp.einsum("tbh,tbg->hg", hs[:-1], dz[1:],
                      preferred_element_type=f32)
           + jnp.einsum("bh,bg->hg", h0.astype(f32), dz[0].astype(f32)))
    return (dz, drw.astype(rw.dtype),
            dh0.astype(h0.dtype), dc0.astype(c0.dtype))


fused_lstm_sequence.defvjp(_fused_fwd, _fused_bwd)


# --------------------------------------------------------------------------
# Stacked 2-layer fused LSTM (wavefront schedule)
#
# cuDNN's fused RNN takes numLayers and interleaves the layers' per-step
# GEMMs (CudnnLSTMHelper.java:588 passes the full descriptor); running two
# stacked LSTMs as two independent sequence kernels leaves the MXU idle
# between DEPENDENT small GEMMs (2T sequential dependency points). The
# wavefront schedule computes layer1 step t and layer2 step t-1 in the same
# iteration: both depend only on iteration t-1 state, so their GEMMs are
# independent and pipeline back-to-back — T+1 dependency points instead of
# 2T (measured ~1.3x forward at (256,64,256) bf16).
#
# Backward needs no new kernel: layer2's backward runs first (existing
# reverse kernel), the inter-layer gradient dh1 = dz2 @ W2^T is ONE big
# batched GEMM, then layer1's backward runs — the sequential structure of
# the backward is already two independent chains.
#
# Layer-2 indexing convention: the kernel emits layer-2 streams SHIFTED by
# one (position k holds step k-1; position 0 is discarded), and the final
# layer-2 step runs as a tiny jnp epilogue outside the kernel.
# --------------------------------------------------------------------------

_ELEMS2_TRAIN = 18   # gate_in1(4H) + hs1,o2(2H) + reserves 2x(4H+2H)
_ELEMS2_INFER = 5    # gate_in1(4H) + o2(H)


def supported2(b, t, h, itemsize=4, interpret=False):
    """Shape screen for the stacked pair: both single-layer passes must fit
    (the backward reuses them) plus the wavefront forward at K=1."""
    if interpret:
        return True
    return (supported(b, t, h, itemsize)
            and 2 * b * _ELEMS2_TRAIN * h * itemsize
            + _resident2_bytes(b, h, itemsize) <= _VMEM_BUDGET)


def _resident2_bytes(b, h, itemsize):
    """Stacked-kernel resident VMEM: the [RW1|W2|RW2] (H,12H) block plus
    doubled carries/scratch."""
    return h * 12 * h * itemsize + 10 * b * h * 4


def _fwd2_kernel(K, save_reserve, gate_in_ref, rww_ref, b2_ref,
                 h01_ref, c01_ref, h02_ref, c02_ref, *refs):
    """Wavefront training/inference forward. rww = [RW1 | W2 | RW2]
    (H, 12H) resident. Layer-2 streams shifted by one step (see module
    comment); the h2/c2 carry is masked off on the very first global
    iteration (there is no step -1)."""
    if save_reserve:
        (hs1_ref, o2_ref, tc1_ref, cp1_ref, g1_ref, tc2_ref, cp2_ref,
         g2_ref, h1T_ref, c1T_ref, h2p_ref, c2p_ref, h1_s, c1_s, h2_s,
         c2_s) = refs
    else:
        (o2_ref, h1T_ref, c1T_ref, h2p_ref, c2p_ref, h1_s, c1_s, h2_s,
         c2_s) = refs
    t = pl.program_id(0)
    H = h1_s.shape[-1]
    G = 4 * H

    @pl.when(t == 0)
    def _():
        h1_s[:] = h01_ref[:].astype(f32)
        c1_s[:] = c01_ref[:].astype(f32)
        h2_s[:] = h02_ref[:].astype(f32)
        c2_s[:] = c02_ref[:].astype(f32)

    h1, c1 = h1_s[:], c1_s[:]
    h2, c2 = h2_s[:], c2_s[:]
    dt_s = rww_ref.dtype
    for k in range(K):
        h1d = h1 if dt_s == f32 else h1.astype(dt_s)
        h2d = h2 if dt_s == f32 else h2.astype(dt_s)
        # two INDEPENDENT GEMMs: layer1 step t*K+k and layer2 step t*K+k-1
        zz = jnp.dot(h1d, rww_ref[:, :2 * G], preferred_element_type=f32)
        z2p = jnp.dot(h2d, rww_ref[:, 2 * G:], preferred_element_type=f32)
        z1 = gate_in_ref[k].astype(f32) + zz[:, :G]
        z2 = zz[:, G:] + b2_ref[:].astype(f32) + z2p

        if save_reserve:
            cp2_ref[k] = c2.astype(cp2_ref.dtype)   # c2 BEFORE the update
        h2n, c2n, tc2, gates2 = _cell_math(z2, c2, H)
        o2_ref[k] = h2n.astype(o2_ref.dtype)
        if save_reserve:
            tc2_ref[k] = tc2.astype(tc2_ref.dtype)
            g2_ref[k] = gates2.astype(g2_ref.dtype)
        if k == 0:
            # global step -1 does not exist: keep the initial carry on the
            # first grid step (the stores above land in discarded slot 0)
            live = (t > 0)
            h2 = jnp.where(live, h2n, h2)
            c2 = jnp.where(live, c2n, c2)
        else:
            h2, c2 = h2n, c2n

        if save_reserve:
            cp1_ref[k] = c1.astype(cp1_ref.dtype)
        h1, c1, tc1, gates1 = _cell_math(z1, c1, H)
        if save_reserve:
            hs1_ref[k] = h1.astype(hs1_ref.dtype)
            tc1_ref[k] = tc1.astype(tc1_ref.dtype)
            g1_ref[k] = gates1.astype(g1_ref.dtype)
    h1_s[:], c1_s[:] = h1, c1
    h2_s[:], c2_s[:] = h2, c2
    h1T_ref[:] = h1.astype(h1T_ref.dtype)
    c1T_ref[:] = c1.astype(c1T_ref.dtype)
    h2p_ref[:] = h2.astype(h2p_ref.dtype)      # layer2 state at step T-2
    c2p_ref[:] = c2.astype(c2p_ref.dtype)


def _fwd2_call(gate_in1, rww, b2, h01, c01, h02, c02, *, interpret,
               save_reserve):
    T, B, G = gate_in1.shape
    H = G // 4
    dt = gate_in1.dtype
    isz = jnp.dtype(dt).itemsize
    K = _pick_k(T, B, H, isz,
                _ELEMS2_TRAIN if save_reserve else _ELEMS2_INFER,
                resident=_resident2_bytes(B, H, isz))
    step_b = lambda t: (t, 0, 0)
    fixed2 = lambda t: (0, 0)
    state_spec = pl.BlockSpec((K, B, H), step_b, memory_space=pltpu.VMEM)
    gate_spec = pl.BlockSpec((K, B, G), step_b, memory_space=pltpu.VMEM)
    fixed_spec = pl.BlockSpec((B, H), fixed2, memory_space=pltpu.VMEM)
    state_shape = jax.ShapeDtypeStruct((T, B, H), dt)
    gate_shape = jax.ShapeDtypeStruct((T, B, G), dt)
    fixed_shape = jax.ShapeDtypeStruct((B, H), dt)
    in_specs = [
        gate_spec,
        pl.BlockSpec((H, 12 * H), fixed2, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, G), fixed2, memory_space=pltpu.VMEM),
        fixed_spec, fixed_spec, fixed_spec, fixed_spec,
    ]
    scratch = [pltpu.VMEM((B, H), f32) for _ in range(4)]
    if save_reserve:
        out_specs = (state_spec, state_spec, state_spec, state_spec,
                     gate_spec, state_spec, state_spec, gate_spec,
                     fixed_spec, fixed_spec, fixed_spec, fixed_spec)
        out_shape = (state_shape, state_shape, state_shape, state_shape,
                     gate_shape, state_shape, state_shape, gate_shape,
                     fixed_shape, fixed_shape, fixed_shape, fixed_shape)
    else:
        out_specs = (state_spec, fixed_spec, fixed_spec, fixed_spec,
                     fixed_spec)
        out_shape = (state_shape, fixed_shape, fixed_shape, fixed_shape,
                     fixed_shape)
    return pl.pallas_call(
        functools.partial(_fwd2_kernel, K, save_reserve),
        grid=(T // K,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(gate_in1, rww, b2.reshape(1, G), h01, c01, h02, c02)


def _l2_epilogue(h1T, h2p, c2p, w2, b2, rw2):
    """Layer-2 step T-1 (the wavefront lag), in f32 jnp."""
    H = h1T.shape[-1]
    h2d = h2p if rw2.dtype == f32 else h2p.astype(rw2.dtype)
    h1d = h1T if w2.dtype == f32 else h1T.astype(w2.dtype)
    z = (jnp.dot(h2d, rw2, preferred_element_type=f32)
         + jnp.dot(h1d, w2, preferred_element_type=f32)
         + b2.astype(f32))
    return _cell_math(z, c2p.astype(f32), H)   # h2T, c2T, tc, gates


def _stack_rww(rw1, w2, rw2):
    return jnp.concatenate([rw1, w2, rw2], axis=-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(9,))
def fused_lstm2_sequence(gate_in1, rw1, w2, b2, rw2, h01, c01, h02, c02,
                         interpret=False):
    """Two stacked LSTMs over precomputed layer-1 gate inputs (wavefront
    schedule; the cuDNN numLayers=2 fused-RNN equivalent).

    gate_in1: (T, B, 4H) = x @ W1 + b1. rw1/rw2: (H, 4H) recurrent
    weights; w2: (H, 4H) layer-2 input weights; b2: (4H,).
    Returns (hs2, h1T, c1T, c2T): layer-2 hidden sequence (T, B, H) plus
    the final states the carry API needs (h2T = hs2[-1]).
    """
    dt = gate_in1.dtype
    o2, h1T, c1T, h2p, c2p = _fwd2_call(
        gate_in1, _stack_rww(rw1, w2, rw2), b2, h01, c01, h02, c02,
        interpret=interpret, save_reserve=False)
    h2T, c2T, _, _ = _l2_epilogue(h1T, h2p, c2p, w2, b2, rw2)
    hs2 = jnp.concatenate([o2[1:], h2T[None].astype(dt)], axis=0)
    return hs2, h1T, c1T, c2T.astype(dt)


def _fused2_fwd(gate_in1, rw1, w2, b2, rw2, h01, c01, h02, c02, interpret):
    dt = gate_in1.dtype
    (hs1, o2, tc1, cp1, g1, tc2s, cp2s, g2s, h1T, c1T, h2p, c2p) = \
        _fwd2_call(gate_in1, _stack_rww(rw1, w2, rw2), b2, h01, c01, h02,
                   c02, interpret=interpret, save_reserve=True)
    h2T, c2T, tc_l, g_l = _l2_epilogue(h1T, h2p, c2p, w2, b2, rw2)
    hs2 = jnp.concatenate([o2[1:], h2T[None].astype(dt)], axis=0)
    # un-shift the layer-2 reserves (slot 0 is the discarded step -1)
    tc2 = jnp.concatenate([tc2s[1:], tc_l[None].astype(dt)], axis=0)
    cp2 = jnp.concatenate([cp2s[1:], c2p[None]], axis=0)
    g2 = jnp.concatenate([g2s[1:], g_l[None].astype(dt)], axis=0)
    res = (rw1, w2, rw2, h01, c01, h02, c02,
           hs1, tc1, cp1, g1, hs2, tc2, cp2, g2)
    return (hs2, h1T, c1T, c2T.astype(dt)), res


def _fused2_bwd(interpret, res, grads):
    (rw1, w2, rw2, h01, c01, h02, c02,
     hs1, tc1, cp1, g1, hs2, tc2, cp2, g2) = res
    dhs2, dh1T, dc1T, dc2T = grads
    dt = g1.dtype
    # layer-2 backward (existing reverse kernel)
    dz2, dh02, dc02 = _bwd_call(g2, tc2, cp2, rw2, dhs2.astype(dt),
                                dc2T.astype(dt), interpret=interpret)
    # inter-layer gradient: ONE big batched GEMM + the exposed-h1T term
    dh1 = jax.lax.dot_general(dz2, w2, (((2,), (1,)), ((), ())),
                              preferred_element_type=f32)
    dh1 = dh1.at[-1].add(dh1T.astype(f32))
    # layer-1 backward
    dz1, dh01, dc01 = _bwd_call(g1, tc1, cp1, rw1, dh1.astype(dt),
                                dc1T.astype(dt), interpret=interpret)
    # weight gradients: big batched GEMMs (h_prev as slices, no copies)
    drw1 = (jnp.einsum("tbh,tbg->hg", hs1[:-1], dz1[1:],
                       preferred_element_type=f32)
            + jnp.einsum("bh,bg->hg", h01.astype(f32), dz1[0].astype(f32)))
    dw2 = jnp.einsum("tbh,tbg->hg", hs1, dz2, preferred_element_type=f32)
    db2 = jnp.sum(dz2.astype(f32), axis=(0, 1))
    drw2 = (jnp.einsum("tbh,tbg->hg", hs2[:-1], dz2[1:],
                       preferred_element_type=f32)
            + jnp.einsum("bh,bg->hg", h02.astype(f32), dz2[0].astype(f32)))
    return (dz1, drw1.astype(rw1.dtype), dw2.astype(w2.dtype),
            db2.astype(dt), drw2.astype(rw2.dtype),
            dh01.astype(h01.dtype), dc01.astype(c01.dtype),
            dh02.astype(h02.dtype), dc02.astype(c02.dtype))


fused_lstm2_sequence.defvjp(_fused2_fwd, _fused2_bwd)
