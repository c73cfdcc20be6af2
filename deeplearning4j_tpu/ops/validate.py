"""On-hardware kernel validation — the ValidateCudnnLSTM pattern, on TPU.

The reference validates its accelerated kernels against the built-in path on
real hardware (deeplearning4j-cuda/src/test ValidateCudnnLSTM.java,
TestConvolution.java compare cuDNN vs pure-ND4J outputs/gradients). The CI
suite here runs the Pallas kernels only in interpreter mode on CPU, so this
module is the compiled-mode counterpart: it sweeps the ``supported()`` shape
envelope on the *current backend* (run it on the TPU chip), asserts
fused-vs-reference equivalence of outputs AND gradients, and times both
paths.

Run:  python -m deeplearning4j_tpu.ops.validate            # full sweep
      python -m deeplearning4j_tpu.ops.validate --quick    # small sweep
Emits one JSON line per case plus a summary line.
"""

from __future__ import annotations

import argparse
import functools
import json
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.ops import lstm_pallas
from deeplearning4j_tpu.ops.flash_attention import (_gqa_bwd, _gqa_fwd_call,
                                                    flash_attention,
                                                    gqa_flash_attention,
                                                    gqa_plan, gqa_supported,
                                                    supported as fa_supported)


# ---------------------------------------------------------------- references

def _lstm_scan_reference(gate_in, rw, h0, c0):
    """Pure lax.scan LSTM over precomputed gate inputs (the layer's built-in
    path, restated on the fused kernel's (gate_in, rw, h0, c0) contract:
    returns (hs, c_last))."""
    H = h0.shape[-1]

    def step(carry, z_t):
        h, c = carry
        z = z_t + h @ rw
        i = jax.nn.sigmoid(z[:, 0 * H:1 * H])
        f = jax.nn.sigmoid(z[:, 1 * H:2 * H])
        o = jax.nn.sigmoid(z[:, 2 * H:3 * H])
        g = jnp.tanh(z[:, 3 * H:4 * H])
        # TPU lowering returns f32 from a bf16 dot — pin the carry dtype
        c_new = (f * c + i * g).astype(c.dtype)
        h_new = (o * jnp.tanh(c_new)).astype(h.dtype)
        return (h_new, c_new), h_new

    (_, cT), hs = lax.scan(step, (h0, c0), gate_in)
    return hs, cT


def _attn_reference(q, k, v, causal):
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    s = jnp.einsum("btd,bsd->bts", q, k) * scale
    if causal:
        t = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("bts,bsd->btd", jax.nn.softmax(s, -1), v)


# ------------------------------------------------------------------- timing

def _time(fn, *args, **options):
    """Per-execution op time; see util/timing.py for why one dispatch of a
    short op cannot be timed by itself (launch cost exceeds the op)."""
    from deeplearning4j_tpu.util.timing import time_op
    return time_op(fn, *args, **options)


_MIN_MEASURABLE_S = 1e-7      # below the timer's resolution → time is noise


def _speedup(ref_s, ours_s):
    """Ratio, or None when either side is below measurable resolution —
    a near-zero denominator would fabricate million-x 'speedups'."""
    if ref_s < _MIN_MEASURABLE_S or ours_s < _MIN_MEASURABLE_S:
        return None
    return round(ref_s / ours_s, 2)


def _max_err(a, b):
    return float(jnp.max(jnp.abs(jnp.asarray(a) - jnp.asarray(b))))


# ---------------------------------------------------------------- LSTM sweep

def validate_lstm_case(b, t, h, dtype="float32", rtol=2e-3, atol=2e-4,
                       time_it=True):
    """Compare fused vs scan outputs and all gradients for one (B, T, H).

    Tolerances are backend-honest: on TPU both paths round MXU matmuls at
    bf16-multiply/f32-accumulate default precision with different blocking
    orders, so they agree to ~1e-3 relative, not 1e-5 (the exactness contract
    is pinned by the CPU interpreter tests in tests/test_ops_kernels.py; this
    sweep exists to catch Mosaic layout/compile bugs, which are O(1) errors).
    bf16 cases compare bf16-fused vs bf16-scan and widen tolerances by the
    bf16 epsilon ratio."""
    dt = jnp.dtype(dtype)
    assert lstm_pallas.supported(b, t, h, dt.itemsize), (b, t, h, dtype)
    if dt == jnp.bfloat16:
        rtol, atol = rtol * 16, atol * 16
    rs = np.random.RandomState(h + b + t)
    gate_in = jnp.asarray(rs.randn(t, b, 4 * h) * 0.4, dt)
    rw = jnp.asarray(rs.randn(h, 4 * h) / np.sqrt(h), dt)
    h0 = jnp.asarray(rs.randn(b, h) * 0.1, dt)
    c0 = jnp.asarray(rs.randn(b, h) * 0.1, dt)
    cot_h = jnp.asarray(rs.randn(t, b, h), jnp.float32)
    cot_c = jnp.asarray(rs.randn(b, h), jnp.float32)

    def loss_fused(gi, rw, h0, c0):
        hs, cT = lstm_pallas.fused_lstm_sequence(gi, rw, h0, c0)
        return (jnp.sum(hs.astype(jnp.float32) * cot_h)
                + jnp.sum(cT.astype(jnp.float32) * cot_c))

    def loss_ref(gi, rw, h0, c0):
        hs, cT = _lstm_scan_reference(gi, rw, h0, c0)
        return (jnp.sum(hs.astype(jnp.float32) * cot_h)
                + jnp.sum(cT.astype(jnp.float32) * cot_c))

    fwd_fused = jax.jit(lambda *a: lstm_pallas.fused_lstm_sequence(*a))
    fwd_ref = jax.jit(_lstm_scan_reference)
    g_fused = jax.jit(jax.grad(loss_fused, argnums=(0, 1, 2, 3)))
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2, 3)))

    hs_f, cT_f = fwd_fused(gate_in, rw, h0, c0)
    hs_r, cT_r = fwd_ref(gate_in, rw, h0, c0)
    errs = {"hs": _max_err(hs_f, hs_r), "cT": _max_err(cT_f, cT_r)}

    gf = g_fused(gate_in, rw, h0, c0)
    gr = g_ref(gate_in, rw, h0, c0)
    for name, a, b_ in zip(("dgate_in", "drw", "dh0", "dc0"), gf, gr):
        errs[name] = _max_err(a, b_)
        scale = float(jnp.max(jnp.abs(b_).astype(jnp.float32))) + 1.0
        assert errs[name] <= atol + rtol * scale, \
            f"LSTM B={b} T={t} H={h}: {name} err {errs[name]} (scale {scale})"
    assert errs["hs"] <= atol + rtol and errs["cT"] <= atol + rtol * 3, errs

    res = {"kernel": "fused_lstm", "B": b, "T": t, "H": h, "dtype": dtype,
           "fwd_route": ("pallas"
                         if lstm_pallas.use_pallas_fwd(b, h, t=t, dtype=dtype)
                         else "scan"),
           "max_err": round(max(errs.values()), 8)}
    if time_it:
        tf = _time(fwd_fused, gate_in, rw, h0, c0)
        tr = _time(fwd_ref, gate_in, rw, h0, c0)
        tgf = _time(g_fused, gate_in, rw, h0, c0)
        tgr = _time(g_ref, gate_in, rw, h0, c0)
        res.update(fwd_us=round(tf * 1e6, 1), fwd_scan_us=round(tr * 1e6, 1),
                   fwd_speedup=_speedup(tr, tf),
                   grad_us=round(tgf * 1e6, 1), grad_scan_us=round(tgr * 1e6, 1),
                   grad_speedup=_speedup(tgr, tgf))
    return res


# ------------------------------------------------------ stacked LSTM sweep

def _lstm2_scan_reference(gate_in1, rw1, w2, b2, rw2, h01, c01, h02, c02):
    """Two sequential scan layers on the stacked op's contract."""
    hs1, _ = _lstm_scan_reference(gate_in1, rw1, h01, c01)
    T, B, _ = hs1.shape
    gi2 = (hs1.reshape(T * B, -1) @ w2 + b2).reshape(T, B, -1)
    hs2, c2T = _lstm_scan_reference(gi2, rw2, h02, c02)
    return hs2, c2T


def validate_lstm2_case(b, t, h, dtype="float32", rtol=2e-3, atol=2e-4,
                        time_it=True):
    """Stacked wavefront kernel vs two sequential scan layers: layer-2
    outputs and every gradient (incl. layer-2 weights, which only the
    stacked op owns)."""
    from deeplearning4j_tpu.ops.lstm_pallas import (fused_lstm2_sequence,
                                                    supported2)
    dt = jnp.dtype(dtype)
    assert supported2(b, t, h, dt.itemsize), (b, t, h, dtype)
    if dt == jnp.bfloat16:
        rtol, atol = rtol * 16, atol * 16
    rs = np.random.RandomState(h + b + t + 1)
    gi = jnp.asarray(rs.randn(t, b, 4 * h) * 0.4, dt)
    rw1 = jnp.asarray(rs.randn(h, 4 * h) / np.sqrt(h), dt)
    w2 = jnp.asarray(rs.randn(h, 4 * h) / np.sqrt(h), dt)
    b2 = jnp.asarray(rs.randn(4 * h) * 0.1, dt)
    rw2 = jnp.asarray(rs.randn(h, 4 * h) / np.sqrt(h), dt)
    z = jnp.zeros((b, h), dt)
    cot = jnp.asarray(rs.randn(t, b, h), jnp.float32)

    def loss_fused(gi, rw1, w2, b2, rw2):
        hs2, _, _, _ = fused_lstm2_sequence(gi, rw1, w2, b2, rw2,
                                            z, z, z, z)
        return jnp.sum(hs2.astype(jnp.float32) * cot)

    def loss_ref(gi, rw1, w2, b2, rw2):
        hs2, _ = _lstm2_scan_reference(gi, rw1, w2, b2, rw2, z, z, z, z)
        return jnp.sum(hs2.astype(jnp.float32) * cot)

    f_fused = jax.jit(lambda *a: fused_lstm2_sequence(*a, z, z, z, z)[0])
    f_ref = jax.jit(lambda *a: _lstm2_scan_reference(*a, z, z, z, z)[0])
    g_fused = jax.jit(jax.grad(loss_fused, argnums=(0, 1, 2, 3, 4)))
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2, 3, 4)))

    args = (gi, rw1, w2, b2, rw2)
    errs = {"hs2": _max_err(f_fused(*args), f_ref(*args))}
    for name, a, b_ in zip(("dgi", "drw1", "dw2", "db2", "drw2"),
                           g_fused(*args), g_ref(*args)):
        errs[name] = _max_err(a, b_)
        scale = float(jnp.max(jnp.abs(b_).astype(jnp.float32))) + 1.0
        assert errs[name] <= atol + rtol * scale, \
            f"LSTM2 B={b} T={t} H={h} {dtype}: {name} err {errs[name]}"
    assert errs["hs2"] <= atol + rtol * 2, errs

    res = {"kernel": "fused_lstm2", "B": b, "T": t, "H": h, "dtype": dtype,
           "max_err": round(max(errs.values()), 8)}
    if time_it:
        tf = _time(f_fused, *args)
        tr = _time(f_ref, *args)
        tgf = _time(g_fused, *args)
        tgr = _time(g_ref, *args)
        res.update(fwd_us=round(tf * 1e6, 1), fwd_scan_us=round(tr * 1e6, 1),
                   fwd_speedup=_speedup(tr, tf),
                   grad_us=round(tgf * 1e6, 1),
                   grad_scan_us=round(tgr * 1e6, 1),
                   grad_speedup=_speedup(tgr, tgf))
    return res


LSTM2_SWEEP = [(32, 64, 256), (64, 64, 128), (128, 32, 256), (256, 64, 256)]
LSTM2_QUICK = [(32, 64, 256)]


# ----------------------------------------------------------- attention sweep

def validate_attention_case(bh, t, dh, causal, rtol=1e-2, atol=1e-3,
                            time_it=True):
    """rtol reflects default-precision MXU rounding under different blocking
    (see validate_lstm_case docstring); exactness is pinned by the CPU
    interpreter tests."""
    assert fa_supported(t, dh), (t, dh)
    rs = np.random.RandomState(t + dh)
    q, k, v = (jnp.asarray(rs.randn(bh, t, dh), jnp.float32) for _ in range(3))
    cot = jnp.asarray(rs.randn(bh, t, dh), jnp.float32)

    fa_fwd = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal))
    ref_fwd = jax.jit(lambda q, k, v: _attn_reference(q, k, v, causal))
    fa_g = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal) * cot),
        argnums=(0, 1, 2)))
    ref_g = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(_attn_reference(q, k, v, causal) * cot),
        argnums=(0, 1, 2)))

    o_f, o_r = fa_fwd(q, k, v), ref_fwd(q, k, v)
    errs = {"o": _max_err(o_f, o_r)}
    for name, a, b_ in zip("qkv", fa_g(q, k, v), ref_g(q, k, v)):
        errs["d" + name] = _max_err(a, b_)
        scale = float(jnp.max(jnp.abs(b_))) + 1.0
        assert errs["d" + name] <= atol + rtol * scale, \
            f"FA BH={bh} T={t} Dh={dh} causal={causal}: d{name} " \
            f"err {errs['d' + name]}"
    assert errs["o"] <= atol + rtol

    res = {"kernel": "flash_attention", "BH": bh, "T": t, "Dh": dh,
           "causal": causal, "max_err": round(max(errs.values()), 8)}
    if time_it:
        tf = _time(fa_fwd, q, k, v)
        tr = _time(ref_fwd, q, k, v)
        tgf = _time(fa_g, q, k, v)
        tgr = _time(ref_g, q, k, v)
        res.update(fwd_us=round(tf * 1e6, 1), fwd_ref_us=round(tr * 1e6, 1),
                   fwd_speedup=_speedup(tr, tf),
                   grad_us=round(tgf * 1e6, 1), grad_ref_us=round(tgr * 1e6, 1),
                   grad_speedup=_speedup(tgr, tgf))
    return res


def _masked_attention_by_rows(q, k, v, mask, rows, f32):
    """(output, head-mean weights) of grouped-query attention over the pairs
    ``mask`` (B, T, T) selects, by a masked softmax over ``rows`` query rows
    at a time, one chunk's scores alive at a time (the whole (B, H, T, T)
    score tensor does not fit the chip at the cells' shapes). ``f32``: the
    operands cast to float32; else the weights rounded to v's dtype, as the
    layer's plain path has them."""
    b, hq, t, dh = q.shape
    hkv = k.shape[1]
    cast = (lambda a: a.astype(jnp.float32)) if f32 else (lambda a: a)

    def chunk(start, q1, k1, v1, m1):
        cut = lambda a, axis: lax.dynamic_slice_in_dim(a, start, rows, axis)
        qc = cast(cut(q1, 1)).reshape(hkv, hq // hkv, rows, dh)
        s = jnp.einsum("kgqd,ksd->kgqs", qc, cast(k1),
                       preferred_element_type=jnp.float32) / math.sqrt(dh)
        p = jax.nn.softmax(jnp.where(
            (cut(m1, 0) != 0)[None, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("kgqs,ksd->kgqd", p if f32 else p.astype(v1.dtype),
                       cast(v1))
        return o.reshape(hq, rows, dh), p.mean(axis=(0, 1))

    def sequence(args):
        return lax.map(jax.checkpoint(lambda s: chunk(s, *args)),
                       jnp.arange(0, t, rows))

    o, pm = lax.map(sequence, (q, k, v, mask))
    return (o.transpose(0, 2, 1, 3, 4).reshape(b, hq, t, dh),
            pm.reshape(b, t, t))


def _gqa_pass_times(q, k, v, do, window, mask, interpret):
    """The tile the grouped-query kernels take at these shapes and the
    microseconds of each of their three passes: the forward call, and the
    dq and the dk/dv call of the backward rule on the forward's residuals
    (a pass whose outputs are dropped is dead code and does not run)."""
    b, hq, t, dh = q.shape
    m = () if mask is None else (mask,)
    fwd = jax.jit(lambda q, k, v, *m: _gqa_fwd_call(
        q, k, v, window, None, interpret, *m))
    bwd = lambda q, k, v, o, lse, do, *m: _gqa_bwd(
        window, None, interpret, (q, k, v, o, lse), do, *m)
    o, lse = fwd(q, k, v, *m)
    us = lambda fn, *a: round(_time(fn, *a, pilot_iters=8) * 1e6, 1)
    return {"plan": gqa_plan(t, hq, k.shape[1], dh, window,
                             itemsize=q.dtype.itemsize)._asdict(),
            "fwd_us": us(fwd, q, k, v, *m),
            "dq_us": us(jax.jit(lambda *a: bwd(*a)[0]),
                        q, k, v, o, lse, do, *m),
            "dkv_us": us(jax.jit(lambda *a: bwd(*a)[1:]),
                         q, k, v, o, lse, do, *m)}


def validate_gqa_attention_case(b, hq, hkv, t, dh, window,
                                dtype="bfloat16", rtol=2e-2, atol=2e-2,
                                time_it=True):
    """The grouped-query banded kernel against the layer's plain path (the
    masked softmax of ``banded_attention``, 256 query rows at a time),
    outputs and the three gradients, in the training path's dtype. bfloat16
    both sides: the tolerance is the rounding of p and of the outputs. With
    the times: the kernels' tile (``gqa_plan``) and each pass by itself."""
    assert gqa_supported(t, dh, hq, hkv), (t, dh, hq, hkv)
    dt = jnp.dtype(dtype)
    rs = np.random.RandomState(t + hq)
    q = jnp.asarray(rs.randn(b, hq, t, dh), dt)
    k, v = (jnp.asarray(rs.randn(b, hkv, t, dh), dt) for _ in range(2))
    cot = jnp.asarray(rs.randn(b, hq, t, dh), jnp.float32)

    def total(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * cot)

    def ref(q, k, v):
        i = jnp.arange(t)[:, None]
        j = jnp.arange(t)[None, :]
        ok = (i >= j) if window is None else (i >= j) & (i - j < window)
        mask = jnp.broadcast_to(ok.astype(jnp.int8), (b, t, t))
        return _masked_attention_by_rows(q, k, v, mask, min(256, t),
                                         f32=False)[0]

    fa = lambda q, k, v: gqa_flash_attention(q, k, v, window)
    fa_fwd, ref_fwd = jax.jit(fa), jax.jit(ref)
    fa_g = jax.jit(jax.grad(total(fa), argnums=(0, 1, 2)))
    ref_g = jax.jit(jax.grad(total(ref), argnums=(0, 1, 2)))
    errs = {"o": _max_err(fa_fwd(q, k, v).astype(jnp.float32),
                          ref_fwd(q, k, v).astype(jnp.float32))}
    assert errs["o"] <= atol + rtol, errs
    for name, a, b_ in zip("qkv", fa_g(q, k, v), ref_g(q, k, v)):
        a, b_ = a.astype(jnp.float32), b_.astype(jnp.float32)
        errs["d" + name] = _max_err(a, b_)
        scale = float(jnp.max(jnp.abs(b_))) + 1.0
        assert errs["d" + name] <= atol + rtol * scale, \
            f"GQA B={b} Hq={hq} Hkv={hkv} T={t} window={window}: " \
            f"d{name} err {errs['d' + name]} (scale {scale})"
    res = {"kernel": "gqa_flash_attention", "B": b, "Hq": hq, "Hkv": hkv,
           "T": t, "Dh": dh, "window": window, "dtype": dtype,
           "max_err": round(max(errs.values()), 6)}
    if time_it:
        res.update(_gqa_pass_times(q, k, v, cot.astype(dt), window, None,
                                   False))
        tr = _time(ref_fwd, q, k, v, pilot_iters=8)
        tgf = _time(fa_g, q, k, v, pilot_iters=8)
        tgr = _time(ref_g, q, k, v, pilot_iters=8)
        res.update(fwd_ref_us=round(tr * 1e6, 1),
                   fwd_speedup=_speedup(tr, res["fwd_us"] * 1e-6),
                   grad_us=round(tgf * 1e6, 1), grad_ref_us=round(tgr * 1e6, 1),
                   grad_speedup=_speedup(tgr, tgf))
    return res


def validate_expert_rounds_case(n, c, n_experts, top_k, width, count,
                                dtype="bfloat16", tol=2e-2, time_it=True):
    """The expert layer's overflow rounds (the two ``fori_loop``s of
    ``_expert_rounds``, which an even routing never enters) against a plain
    loop over the experts held with a mask, in float32 at ``highest``:
    the router is set so that every token picks every expert held, the
    worst the layer can see, so every later round runs. Output and the
    gradients of x and of the three stacked projections, by relative norm
    gap; ``pairs_dropped`` must read 0 and more than one round must run."""
    from deeplearning4j_tpu.nn.layers.decoder import ExpertLayer, route_top_k
    dt = jnp.dtype(dtype)
    layer = ExpertLayer(n_in=c, n_experts=n_experts, experts_per_token=top_k,
                        expert_width=width, routed_scale=2.5,
                        experts_held=(count, 0), weight_init="xavier")
    p = layer.init(jax.random.PRNGKey(n + count), jnp.float32)
    # positive tokens and one hot router column: expert 1 scores highest for
    # every token, the rest tie and top_k takes the lowest indices
    p["Wr"] = jnp.zeros_like(p["Wr"]).at[:, 1].set(1.0)
    p = {k: v.astype(dt) for k, v in p.items()}
    rs = np.random.RandomState(c)
    x = jnp.asarray(np.abs(rs.randn(n, c)) + 0.1, dt)
    cot = jnp.asarray(rs.randn(n, c), jnp.float32)
    rows, rounds = layer.round_rows(n)

    def ours(x, eg, eu, ed):
        y, seen = layer.routed(dict(p, Eg=eg, Eu=eu, Ed=ed), x)
        return jnp.sum(y * cot), (y, seen)

    def plain(x, eg, eu, ed):
        f32 = lambda a: a.astype(jnp.float32)
        hi = dict(precision=lax.Precision.HIGHEST)
        idx, w = route_top_k(x, p["Wr"], top_k, True, 2.5)
        y = jnp.zeros((n, c), jnp.float32)
        for e in range(count):
            pe = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
            h = jax.nn.silu(jnp.dot(f32(x), f32(eg[e]), **hi)) \
                * jnp.dot(f32(x), f32(eu[e]), **hi)
            y = y + pe[:, None] * jnp.dot(h, f32(ed[e]), **hi)
        return jnp.sum(y * cot), y

    args = (x, p["Eg"], p["Eu"], p["Ed"])
    ours_g = jax.jit(jax.value_and_grad(ours, argnums=(0, 1, 2, 3),
                                        has_aux=True))
    plain_g = jax.jit(jax.value_and_grad(plain, argnums=(0, 1, 2, 3),
                                         has_aux=True))
    (_, (got, seen)), g_got = ours_g(*args)
    (_, want), g_want = plain_g(*args)
    pairs, dropped = int(seen["pairs"]), int(seen["pairs_dropped"])
    assert rounds > 1 and pairs > rows, (pairs, rows, rounds)
    assert dropped == 0, f"{dropped} of {pairs} pairs dropped"

    def gap(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))

    gaps = {"y": gap(got, want)}
    for name, a, b in zip(("dx", "dEg", "dEu", "dEd"), g_got, g_want):
        gaps[name] = gap(a, b)
    for name, v in gaps.items():
        assert v <= tol, f"expert rounds N={n} held={count}: {name} gap {v}"
    res = {"kernel": "expert_rounds", "N": n, "C": c, "experts": n_experts,
           "top_k": top_k, "width": width, "held": count, "dtype": dtype,
           "pairs": pairs, "rows": rows,
           "rounds_run": -(-pairs // rows), "pairs_dropped": dropped,
           "gaps": {k: round(v, 6) for k, v in gaps.items()},
           "max_err": round(max(gaps.values()), 6)}
    if time_it:
        res.update(grad_us=round(_time(ours_g, *args) * 1e6, 1),
                   grad_ref_us=round(_time(plain_g, *args) * 1e6, 1))
    return res


def validate_selected_attention_case(b, hq, hkv, t, dh, j, di, top_k,
                                     dtype="bfloat16", rtol=2e-2, atol=2e-2,
                                     time_it=True):
    """Attention over a learned selection of keys at one layer's shapes: the
    selection (``selected_keys_mask``: exact counts, and the share of keys
    that differ from ``jax.lax.top_k`` over the same scores), the kernels
    under the mask (``gqa_selected_attention`` forward and its three
    gradients, ``gqa_head_mean_probs``) and the indexer's loss with its
    gradients (``index_loss``), each against a float32 loop over chunks of
    query rows at ``highest`` precision on the same inputs, and the time of
    each beside XLA's form of the masked attention in the same dtype. The
    index scores in both forms (the kernel of ops/index_scores.py where its
    screen accepts the shapes, and the einsum) on one chunk of rows against
    all T keys: the scores, and the chunk's KL with its three gradients,
    each against the plain form in float32, and the time of each pass."""
    from deeplearning4j_tpu.nn.layers.decoder import (
        index_loss, index_scores, index_scores_xla, selected_keys_mask)
    from deeplearning4j_tpu.ops.flash_attention import (
        gqa_head_mean_probs, gqa_selected_attention)
    from deeplearning4j_tpu.ops import index_scores as index_kernel
    from deeplearning4j_tpu import ops
    assert gqa_supported(t, dh, hq, hkv), (t, dh, hq, hkv)
    interp = ops.interpret_mode()       # off the chip: kernels interpreted
    dt = jnp.dtype(dtype)
    rs = np.random.RandomState(t + hq)
    q = jnp.asarray(rs.randn(b, hq, t, dh), dt)
    k, v = (jnp.asarray(rs.randn(b, hkv, t, dh), dt) for _ in range(2))
    qi = jnp.asarray(rs.randn(b, t, j, di), dt)
    ki = jnp.asarray(rs.randn(b, t, di), dt)
    wi = jnp.asarray(rs.randn(b, t, j) / math.sqrt(j * di), jnp.float32)
    cot = jnp.asarray(rs.randn(b, hq, t, dh), jnp.float32)
    rows = min(256, t)

    def chunks(fn, *full):
        """``fn(start, *full)`` over the chunks of query rows of every
        sequence, one chunk's scores alive at a time."""
        def one(args):
            return jax.lax.map(jax.checkpoint(lambda s: fn(s, *args)),
                               jnp.arange(0, t, rows))
        return jax.lax.map(one, full)

    def cut(a, start, axis):
        return jax.lax.dynamic_slice_in_dim(a, start, rows, axis)

    def loop(q, k, v, mask, f32=True):
        return _masked_attention_by_rows(q, k, v, mask, rows, f32)

    def kl_rows(scores, qc, wc, k1, mc, pc):
        """A chunk's KL from ``pc`` to the softmax of ``scores(qc, wc, k1)``
        over the keys ``mc`` selects."""
        sel = mc != 0
        logq = jax.nn.log_softmax(
            jnp.where(sel, scores(qc, wc, k1), -jnp.inf), axis=-1)
        return jnp.where(sel, jax.scipy.special.xlogy(pc, pc)
                         - pc * jnp.where(sel, logq, 0.0), 0.0).sum()

    def kl_loop(qi, wi, ki, mask, pm):
        def rows_of(start, q1, w1, k1, m1, p1):
            return kl_rows(index_scores_xla,
                           cut(q1, start, 0).astype(jnp.float32),
                           cut(w1, start, 0), k1.astype(jnp.float32),
                           cut(m1, start, 0), cut(p1, start, 0))
        return chunks(rows_of, qi, wi, ki, mask, pm).sum() / (b * t)

    def top_k_loop(qi, wi, ki):
        def rows_of(start, q1, w1, k1):
            sc = index_scores(cut(q1, start, 0), cut(w1, start, 0), k1)
            vis = jnp.arange(t)[None, :] <= start + jnp.arange(rows)[:, None]
            _, idx = jax.lax.top_k(jnp.where(vis, sc, -jnp.inf), top_k)
            return (jnp.zeros((rows, t), bool).at[
                jnp.arange(rows)[:, None], idx].set(True) & vis
            ).astype(jnp.int8)
        return chunks(rows_of, qi, wi, ki).reshape(b, t, t)

    # every array is an argument of the jitted calls: one closed over would
    # be baked into the executable as a constant (268 MB of mask at the
    # layer's shapes)
    select = jax.jit(lambda *a: selected_keys_mask(*a, top_k))
    mask = select(qi, wi, ki)
    want = b * sum(min(i + 1, top_k) for i in range(t))
    assert int(mask.sum(dtype=jnp.int32)) == want, "selection count"
    assert bool((mask.sum(axis=-1, dtype=jnp.int32)
                 == jnp.minimum(jnp.arange(t) + 1, top_k)).all())
    differ = float(jax.jit(lambda m, *a: (m != top_k_loop(*a)).sum())(
        mask, qi, wi, ki)) / want
    assert differ <= 1e-4, f"selection differs from top_k on {differ} of keys"

    def grad_of(attend):
        return jax.jit(jax.grad(
            lambda q, k, v, m, c: jnp.sum(
                attend(q, k, v, m).astype(jnp.float32) * c),
            argnums=(0, 1, 2)))

    with jax.default_matmul_precision("highest"):
        ref_fwd = jax.jit(loop)
        ref_g = grad_of(lambda q, k, v, m: loop(q, k, v, m)[0])
        o_ref, p_ref = ref_fwd(q, k, v, mask)
        g_ref = ref_g(q, k, v, mask, cot)
        kl_ref, kl_g_ref = jax.jit(jax.value_and_grad(
            kl_loop, argnums=(0, 1, 2)))(qi, wi, ki, mask, p_ref)
    fa_fwd = jax.jit(lambda q, k, v, m: gqa_selected_attention(
        q, k, v, m, None, interp))
    fa_g = grad_of(lambda q, k, v, m: gqa_selected_attention(
        q, k, v, m, None, interp)[0])
    probs = jax.jit(lambda q, k, lse, m: gqa_head_mean_probs(
        q, k, lse, m, None, interp))
    kl = jax.jit(jax.value_and_grad(index_loss, argnums=(0, 1, 2)))
    o, lse = fa_fwd(q, k, v, mask)
    errs = {"o": _max_err(o.astype(jnp.float32), o_ref),
            "p": _max_err(probs(q, k, lse, mask), p_ref) * top_k}
    assert errs["o"] <= atol + rtol and errs["p"] <= 20 * (atol + rtol), errs
    for name, a, b_ in zip("qkv", fa_g(q, k, v, mask, cot), g_ref):
        a, b_ = a.astype(jnp.float32), b_.astype(jnp.float32)
        errs["d" + name] = _max_err(a, b_)
        scale = float(jnp.max(jnp.abs(b_))) + 1.0
        assert errs["d" + name] <= atol + rtol * scale, \
            f"selected attention T={t}: d{name} err {errs['d' + name]} " \
            f"(scale {scale})"
    del g_ref, o_ref
    val, grads = kl(qi, wi, ki, mask, p_ref)
    errs["kl"] = abs(float(val) - float(kl_ref)) / abs(float(kl_ref))
    assert errs["kl"] <= rtol, errs
    for name, a, b_ in zip(("qi", "wi", "ki"), grads, kl_g_ref):
        a, b_ = a.astype(jnp.float32), b_.astype(jnp.float32)
        gap = float(jnp.linalg.norm(a - b_) / jnp.linalg.norm(b_))
        errs["d" + name] = gap
        assert gap <= 5 * rtol, f"index loss T={t}: d{name} norm gap {gap}"
    # the index scores by themselves: the last chunk of rows of sequence 0
    # against all T keys, both forms beside the plain form in float32
    irows = min(512, t)                 # the layer's chunk
    chunk = (qi[0, -irows:], wi[0, -irows:], ki[0], mask[0, -irows:],
             p_ref[0, -irows:])
    forms = {"xla": index_scores_xla}
    if index_kernel.supported(irows, j, di, t, dt.itemsize):
        forms["kernel"] = lambda q, w, k: index_kernel.index_scores(
            q, w, k, interp)
    f32 = lambda a: a.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        sc_ref = jax.jit(index_scores_xla)(f32(chunk[0]), chunk[1],
                                           f32(chunk[2]))
        ckl_ref = jax.jit(jax.grad(functools.partial(
            kl_rows, index_scores_xla), argnums=(0, 1, 2)))(
                f32(chunk[0]), chunk[1], f32(chunk[2]), *chunk[3:])
    index_fns = {}
    for form, fn in forms.items():
        fwd = jax.jit(fn)
        grad = jax.jit(jax.value_and_grad(functools.partial(kl_rows, fn),
                                          argnums=(0, 1, 2)))
        index_fns[form] = (fwd, grad)
        errs[f"index_{form}"] = _max_err(fwd(*chunk[:3]), sc_ref) \
            / float(jnp.max(jnp.abs(sc_ref)))
        assert errs[f"index_{form}"] <= rtol, errs
        for name, a, b_ in zip(("qi", "wi", "ki"), grad(*chunk)[1], ckl_ref):
            gap = float(jnp.linalg.norm(f32(a) - b_) / jnp.linalg.norm(b_))
            errs[f"index_{form}_d{name}"] = gap
            assert gap <= 5 * rtol, \
                f"index scores ({form}) T={t}: d{name} norm gap {gap}"
    res = {"kernel": "gqa_selected_attention", "B": b, "Hq": hq, "Hkv": hkv,
           "T": t, "Dh": dh, "index_heads": j, "index_dim": di,
           "top_k": top_k, "dtype": dtype, "keys_selected": want,
           "index_forms": sorted(forms), "index_rows": irows,
           "selection_differs_from_top_k": differ, "index_loss": float(val),
           "errs": {n: round(e, 6) for n, e in errs.items()},
           "max_err": round(max(errs["o"], errs["dq"], errs["dk"],
                                errs["dv"]), 6)}
    if time_it:
        xla = lambda q, k, v, m: loop(q, k, v, m, f32=False)[0]
        xla_fwd, xla_g = jax.jit(xla), grad_of(xla)
        us = lambda fn, *a: round(_time(fn, *a) * 1e6, 1)
        res.update(_gqa_pass_times(q, k, v, cot.astype(dt), None, mask,
                                   interp))
        res.update(select_us=us(select, qi, wi, ki),
                   grad_us=us(fa_g, q, k, v, mask, cot),
                   head_mean_us=us(probs, q, k, lse, mask),
                   index_loss_grad_us=us(kl, qi, wi, ki, mask, p_ref),
                   fwd_xla_us=us(xla_fwd, q, k, v, mask),
                   grad_xla_us=us(xla_g, q, k, v, mask, cot))
        # summed, so that the timing loop's one scalar needs every output
        # whole (it reads one element: a gradient it does not read is dead
        # code, and a slice of a product is a smaller product)
        whole = lambda fn: jax.jit(lambda *a: sum(
            jnp.sum(x.astype(jnp.float32))
            for x in jax.tree_util.tree_leaves(fn(*a))))
        for form, (fwd, grad) in index_fns.items():
            res[f"index_fwd_{form}_us"] = us(whole(fwd), *chunk[:3])
            res[f"index_kl_grad_{form}_us"] = us(whole(grad), *chunk)
    return res


def validate_ssd_scan_case(b, t, h, p, g, n, chunk, dtype="bfloat16", tol=3e-2,
                           time_it=True):
    """The state-space mixer's chunked scan (``nn/layers/ssm.py:ssd_scan``)
    at one layer's shapes against the recurrence itself, a float32 loop
    over the T positions (a state (H, P, N) a step, kept every ``chunk``
    positions for the gradient), on the same inputs: the output and the
    gradients of x, dt, a, B and C by relative norm gap, and the time of a
    forward-and-gradient pass of each. Steps are log-uniform in [0.001,
    0.1] and a in 1..16, as the layer starts them."""
    from deeplearning4j_tpu.nn.layers.ssm import ssd_scan
    dt_ = jnp.dtype(dtype)
    rs = np.random.RandomState(t + h)
    x = jnp.asarray(rs.randn(b, t, h, p), dt_)
    bm = jnp.asarray(rs.randn(b, t, g, n), dt_)
    cm = jnp.asarray(rs.randn(b, t, g, n) / np.sqrt(n), dt_)
    step = jnp.asarray(np.exp(rs.uniform(np.log(1e-3), np.log(1e-1),
                                         (b, t, h))), jnp.float32)
    a = -jnp.asarray(rs.uniform(1.0, 16.0, (h,)), jnp.float32)
    cot = jnp.asarray(rs.randn(b, t, h, p), jnp.float32)

    def ours(x, step, a, bm, cm):
        return jnp.sum(ssd_scan(x, step, a, bm, cm, chunk) * cot)

    def plain(x, step, a, bm, cm):
        f32 = lambda v: v.astype(jnp.float32)
        rep = h // g
        xs = (jnp.exp(step * a), step[..., None] * f32(x),
              jnp.repeat(f32(bm), rep, axis=2), jnp.repeat(f32(cm), rep, axis=2))
        xs = jax.tree_util.tree_map(
            lambda v: jnp.moveaxis(v, 1, 0).reshape(
                t // chunk, chunk, b, *v.shape[2:]), xs)

        def position(s, v):
            dec, dx, bb, cc = v
            s = dec[..., None, None] * s + dx[..., None] * bb[..., None, :]
            return s, (s * cc[..., None, :]).sum(axis=-1)

        _, y = jax.lax.scan(
            jax.checkpoint(lambda s, v: jax.lax.scan(position, s, v)),
            jnp.zeros((b, h, p, n), jnp.float32), xs)
        y = jnp.moveaxis(y.reshape(t, b, h, p), 0, 1)
        return jnp.sum(y * cot)

    args = (x, step, a, bm, cm)
    ours_g = jax.jit(jax.value_and_grad(ours, argnums=(0, 1, 2, 3, 4)))
    plain_g = jax.jit(jax.value_and_grad(plain, argnums=(0, 1, 2, 3, 4)))
    got, g_got = ours_g(*args)
    want, g_want = plain_g(*args)

    def gap(u, v):
        u, v = u.astype(jnp.float32), v.astype(jnp.float32)
        return float(jnp.linalg.norm(u - v) / (jnp.linalg.norm(v) + 1e-30))

    gaps = {"y": abs(float(got) - float(want)) / (abs(float(want)) + 1e-30)}
    for name, u, v in zip(("dx", "ddt", "da", "dB", "dC"), g_got, g_want):
        gaps[name] = gap(u, v)
    for name, v in gaps.items():
        assert v <= tol or name == "y", \
            f"ssd_scan T={t} H={h} chunk={chunk}: {name} gap {v}"
    res = {"kernel": "ssd_scan", "B": b, "T": t, "H": h, "P": p, "G": g,
           "N": n, "chunk": chunk, "dtype": dtype,
           "gaps": {k: round(v, 6) for k, v in gaps.items()},
           "max_err": round(max(v for k, v in gaps.items() if k != "y"), 6)}
    if time_it:
        res.update(grad_us=round(_time(ours_g, *args) * 1e6, 1),
                   grad_ref_us=round(_time(plain_g, *args) * 1e6, 1))
    return res


# (B, T, H, P, G, N, chunk): one chip's share of a Mamba-2 mixer of the
# benchmark's hybrid decoder at its step's 8192 positions, and a small case
SSD_SWEEP = [(1, 8192, 16, 64, 1, 128, 128), (2, 256, 4, 16, 2, 16, 32)]
SSD_QUICK = SSD_SWEEP[1:]

# (B, Hq, Hkv, T, Dh, index heads, index dim, top_k): one layer of the
# benchmark's decoder with a learned selection at its step's one sequence
# of 16,384 positions, and a small case
SELECTED_SWEEP = [(1, 32, 4, 16384, 128, 16, 64, 2048),
                  (2, 4, 2, 1024, 64, 2, 32, 256)]
SELECTED_QUICK = SELECTED_SWEEP[1:]

# (N, C, experts, top_k, width, held): one chip's share of the benchmark's
# sparse decoder at its step's 16,384 tokens (18 rounds), and a small case
EXPERT_SWEEP = [(16384, 3072, 256, 10, 1024, 8), (256, 64, 16, 3, 32, 4)]
EXPERT_QUICK = EXPERT_SWEEP[1:]

# (B, Hq, Hkv, T, Dh, window): group sizes 6 and 9, the band and the triangle;
# then the attention layers of the benchmark's cells: Laguna's full and
# sliding layers and Nemotron's one (Keye's is SELECTED_SWEEP's first)
GQA_SWEEP = [(1, 12, 2, 2048, 128, None), (1, 18, 2, 2048, 128, 512),
             (2, 6, 1, 1024, 64, 256), (1, 9, 1, 4096, 128, 512),
             (2, 12, 2, 8192, 128, None), (2, 18, 2, 8192, 128, 512),
             (1, 16, 1, 8192, 128, None)]
GQA_QUICK = GQA_SWEEP[:2]

LSTM_SWEEP = [
    # the supported() envelope edges: small/odd-ish H (8-aligned), big H
    (1, 4, 8), (4, 16, 8), (8, 16, 24), (4, 32, 56), (8, 32, 120),
    (16, 64, 128), (32, 64, 256), (32, 128, 256), (64, 32, 512),
]
LSTM_QUICK = [(4, 16, 8), (8, 32, 120), (32, 64, 256)]

ATTN_SWEEP = [
    (2, 16, 8), (4, 64, 32), (8, 128, 64), (8, 256, 64), (4, 512, 128),
    (2, 1024, 64),
]
ATTN_QUICK = [(2, 16, 8), (8, 128, 64)]


def run(quick=False, time_it=True):
    results = []
    failures = []
    skipped = []
    lstm_cases = LSTM_QUICK if quick else LSTM_SWEEP
    attn_cases = ATTN_QUICK if quick else ATTN_SWEEP
    for b, t, h in lstm_cases:
        for dtype in ("float32", "bfloat16"):
            try:
                r = validate_lstm_case(b, t, h, dtype, time_it=time_it)
                results.append(r)
                print(json.dumps(r))
            except Exception as e:  # noqa: BLE001 — report every failing shape
                failures.append({"kernel": "fused_lstm", "B": b, "T": t,
                                 "H": h, "dtype": dtype,
                                 "error": f"{type(e).__name__}: {e}"[:300]})
                print(json.dumps(failures[-1]))
    from deeplearning4j_tpu.ops.lstm_pallas import supported2 as _sup2
    for b, t, h in (LSTM2_QUICK if quick else LSTM2_SWEEP):
        for dtype in ("float32", "bfloat16"):
            if not _sup2(b, t, h, np.dtype(dtype).itemsize):
                # expected screen rejection, not a defect: the container
                # falls back to the per-layer kernels for this shape
                skipped.append({"kernel": "fused_lstm2", "B": b, "T": t,
                                "H": h, "dtype": dtype, "skipped":
                                "outside supported2() VMEM screen — "
                                "container falls back to per-layer kernels"})
                print(json.dumps(skipped[-1]))
                continue
            try:
                r = validate_lstm2_case(b, t, h, dtype, time_it=time_it)
                results.append(r)
                print(json.dumps(r))
            except Exception as e:  # noqa: BLE001
                failures.append({"kernel": "fused_lstm2", "B": b, "T": t,
                                 "H": h, "dtype": dtype,
                                 "error": f"{type(e).__name__}: {e}"[:300]})
                print(json.dumps(failures[-1]))
    for bh, t, dh in attn_cases:
        for causal in (False, True):
            try:
                r = validate_attention_case(bh, t, dh, causal, time_it=time_it)
                results.append(r)
                print(json.dumps(r))
            except Exception as e:  # noqa: BLE001
                failures.append({"kernel": "flash_attention", "BH": bh,
                                 "T": t, "Dh": dh, "causal": causal,
                                 "error": f"{type(e).__name__}: {e}"[:300]})
                print(json.dumps(failures[-1]))
    for case in (GQA_QUICK if quick else GQA_SWEEP):
        try:
            r = validate_gqa_attention_case(*case, time_it=time_it)
            results.append(r)
            print(json.dumps(r))
        except Exception as e:  # noqa: BLE001
            failures.append({"kernel": "gqa_flash_attention", "case": case,
                             "error": f"{type(e).__name__}: {e}"[:300]})
            print(json.dumps(failures[-1]))
    for case in (EXPERT_QUICK if quick else EXPERT_SWEEP):
        try:
            r = validate_expert_rounds_case(*case, time_it=time_it)
            results.append(r)
            print(json.dumps(r))
        except Exception as e:  # noqa: BLE001
            failures.append({"kernel": "expert_rounds", "case": case,
                             "error": f"{type(e).__name__}: {e}"[:300]})
            print(json.dumps(failures[-1]))
    for case in (SELECTED_QUICK if quick else SELECTED_SWEEP):
        try:
            r = validate_selected_attention_case(*case, time_it=time_it)
            results.append(r)
            print(json.dumps(r))
        except Exception as e:  # noqa: BLE001
            failures.append({"kernel": "gqa_selected_attention", "case": case,
                             "error": f"{type(e).__name__}: {e}"[:300]})
            print(json.dumps(failures[-1]))
    for case in (SSD_QUICK if quick else SSD_SWEEP):
        try:
            r = validate_ssd_scan_case(*case, time_it=time_it)
            results.append(r)
            print(json.dumps(r))
        except Exception as e:  # noqa: BLE001
            failures.append({"kernel": "ssd_scan", "case": case,
                             "error": f"{type(e).__name__}: {e}"[:300]})
            print(json.dumps(failures[-1]))
    summary = {"backend": jax.default_backend(),
               "device": jax.devices()[0].device_kind,
               "passed": len(results), "failed": len(failures),
               "skipped": len(skipped)}
    print(json.dumps(summary))
    return results, failures, skipped


if __name__ == "__main__":
    from deeplearning4j_tpu.util.compile_cache import setup_compile_cache
    setup_compile_cache()          # compiles dominate the sweep
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--no-time", action="store_true")
    ap.add_argument("--out", default=None,
                    help="write results+failures JSON to this path")
    a = ap.parse_args()
    results, failures, skipped = run(quick=a.quick, time_it=not a.no_time)
    if a.out:
        with open(a.out, "w") as f:
            json.dump({"results": results, "failures": failures,
                       "skipped": skipped,
                       "backend": jax.default_backend(),
                       "device": jax.devices()[0].device_kind,
                       "note": "Correctness (max_err vs the scan "
                       "reference) is the validation contract; "
                       "per-shape speedups are one sample."}, f, indent=1)
    raise SystemExit(1 if failures else 0)
