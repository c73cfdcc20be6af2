"""Per-backend kernel autotune harness.

KERNELS_TPU.json ships v5e numbers; any other backend (a different TPU
generation, CPU interpret runs) inherits routing decisions measured on
hardware it is not running on. This module closes that gap: on first
use per (kernel, shape, dtype) — gated behind ``DL4JTPU_AUTOTUNE=1`` so
CPU test runs never benchmark — it measures kernel-vs-reference for
BOTH phases on the actual backend and merges the rows into this
process's exec/routing.py measured tables, where they override the
shipped file.

The measurement contract matches bench_kernels exactly — rows use the
KERNELS_TPU.json ``results`` schema. ``tools/autotune.py`` sweeps shapes
offline and writes them to a table file the caller names, and
``routing.load_measurements_file(path)`` absorbs such a table exactly
like the shipped file. No table is ever read from a path the caller did
not name: a routing decision must not depend on a file git does not hold.

Timing: jitted closures per side, one warmup dispatch, then
min-over-iters of ``block_until_ready`` wall time (min is robust to
co-tenant noise; the same discipline bench.py uses).
"""

from __future__ import annotations

import json
import os
import tempfile
import time
from typing import Optional

_attempted = set()        # (kernel, shape_key) measurement already tried
_in_progress = False      # re-entrance guard: measuring calls the kernels,
                          # which ask routing, which must not re-enter here


def _metrics():
    from deeplearning4j_tpu.monitor.metrics import get_registry
    reg = get_registry()
    return (reg.counter("dl4jtpu_autotune_measurements_total",
                        "Kernel-vs-reference autotune measurements run "
                        "(first use per kernel/shape/dtype/backend).",
                        ("kernel",)),
            reg.gauge("dl4jtpu_autotune_table_rows",
                      "Rows in the autotune table last written."))


def backend_name() -> str:
    import jax
    return jax.default_backend()


def load_table(path: str) -> list:
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return json.load(f).get("results", [])


def _row_key(row) -> tuple:
    if row.get("kernel") == "flash_attention":
        return ("flash_attention", row.get("BH"), row.get("T"),
                row.get("Dh"), bool(row.get("causal")))
    return (row.get("kernel"), row.get("B"), row.get("T"), row.get("H"),
            row.get("dtype"))


def save_rows(rows, path: str) -> str:
    """Merge ``rows`` into the table at ``path`` (by shape identity, new
    rows win) with an atomic replace — concurrent processes lose an
    update at worst, never corrupt the file."""
    path = os.path.abspath(path)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    merged = {_row_key(r): r for r in load_table(path)}
    for r in rows:
        merged[_row_key(r)] = r
    out = sorted(merged.values(), key=lambda r: json.dumps(r, sort_keys=True))
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path), suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump({"backend": os.path.basename(path)
                       .removeprefix("autotune_").removesuffix(".json"),
                       "results": out}, f, indent=1)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    _metrics()[1].set(len(out))
    return path


# ------------------------------------------------------------- measurement

def _time_us(fn, args, iters: int) -> float:
    import jax
    out = fn(*args)                      # warmup: compile + first dispatch
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _speed(ref_us: float, ker_us: float) -> Optional[float]:
    if not ker_us:
        return None
    return round(ref_us / ker_us, 2)


def measure_fused_lstm(b: int, t: int, h: int, dtype: str = "float32",
                       iters: int = 3,
                       interpret: Optional[bool] = None) -> Optional[dict]:
    """Measure the fused-LSTM Pallas kernel against its lax.scan
    reference, forward AND backward, at one shape. Returns a
    KERNELS_TPU.json-schema row, or None when the compiled kernel does
    not support the shape (nothing to route)."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops import lstm_pallas as lp

    if interpret is None:
        interpret = backend_name() != "tpu"
    dt = jnp.dtype(dtype)
    if not lp.supported(b, t, h, dt.itemsize, interpret=interpret):
        return None
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    gate_in = jax.random.normal(ks[0], (t, b, 4 * h), dt)
    rw = jax.random.normal(ks[1], (h, 4 * h), dt) * 0.1
    h0 = jax.random.normal(ks[2], (b, h), dt)
    c0 = jax.random.normal(ks[3], (b, h), dt)

    fwd_p = jax.jit(lambda gi, rw, h0, c0: lp._fwd_call(
        gi, rw, h0, c0, interpret=interpret, save_reserve=True)[0])
    fwd_s = jax.jit(lambda gi, rw, h0, c0: lp._scan_fwd(
        gi, rw, h0, c0, save_reserve=True)[0])
    fwd_us = _time_us(fwd_p, (gate_in, rw, h0, c0), iters)
    fwd_scan_us = _time_us(fwd_s, (gate_in, rw, h0, c0), iters)

    # backward: same residuals both sides (the scan fwd emits the exact
    # reserve-space contract the kernels share)
    hs, tc, cprev, gates, _ = lp._scan_fwd(gate_in, rw, h0, c0,
                                           save_reserve=True)
    dhs = jax.random.normal(ks[4], (t, b, h), dt)
    dcT = jax.random.normal(ks[5], (b, h), dt)
    bwd_p = jax.jit(lambda g, tc, cp, rw, dhs, dcT: lp._bwd_call(
        g, tc, cp, rw, dhs, dcT, interpret=interpret)[0])
    bwd_s = jax.jit(lambda g, tc, cp, rw, dhs, dcT: lp._scan_bwd(
        g, tc, cp, rw, dhs, dcT)[0])
    grad_us = _time_us(bwd_p, (gates, tc, cprev, rw, dhs, dcT), iters)
    grad_scan_us = _time_us(bwd_s, (gates, tc, cprev, rw, dhs, dcT), iters)

    return {"kernel": "fused_lstm", "B": b, "T": t, "H": h,
            "dtype": str(dt),
            "fwd_us": round(fwd_us, 1), "fwd_scan_us": round(fwd_scan_us, 1),
            "fwd_speedup": _speed(fwd_scan_us, fwd_us),
            "grad_us": round(grad_us, 1),
            "grad_scan_us": round(grad_scan_us, 1),
            "grad_speedup": _speed(grad_scan_us, grad_us),
            "backend": backend_name(), "autotuned": True}


def measure_flash_attention(bh: int, t: int, dh: int, causal: bool = False,
                            iters: int = 3,
                            interpret: Optional[bool] = None) \
        -> Optional[dict]:
    """Measure the flash-attention kernel against the dense XLA
    softmax-attention reference, forward and grad."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops import flash_attention as fa

    if interpret is None:
        interpret = backend_name() != "tpu"
    if not fa.supported(t, dh):
        return None
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (bh, t, dh), jnp.float32)
    k = jax.random.normal(ks[1], (bh, t, dh), jnp.float32)
    v = jax.random.normal(ks[2], (bh, t, dh), jnp.float32)

    def dense(q, k, v):
        s = jnp.einsum("btd,bsd->bts", q, k) / (dh ** 0.5)
        if causal:
            tt = jnp.arange(t)
            s = jnp.where(tt[:, None] >= tt[None, :], s, -jnp.inf)
        return jnp.einsum("bts,bsd->btd", jax.nn.softmax(s, axis=-1), v)

    flash = lambda q, k, v: fa.flash_attention(q, k, v, causal, interpret)
    fwd_us = _time_us(jax.jit(flash), (q, k, v), iters)
    fwd_ref_us = _time_us(jax.jit(dense), (q, k, v), iters)
    g_fl = jax.jit(jax.grad(lambda q, k, v: flash(q, k, v).sum(),
                            argnums=(0, 1, 2)))
    g_de = jax.jit(jax.grad(lambda q, k, v: dense(q, k, v).sum(),
                            argnums=(0, 1, 2)))
    grad_us = _time_us(g_fl, (q, k, v), iters)
    grad_ref_us = _time_us(g_de, (q, k, v), iters)

    return {"kernel": "flash_attention", "BH": bh, "T": t, "Dh": dh,
            "causal": bool(causal),
            "fwd_us": round(fwd_us, 1), "fwd_ref_us": round(fwd_ref_us, 1),
            "fwd_speedup": _speed(fwd_ref_us, fwd_us),
            "grad_us": round(grad_us, 1),
            "grad_ref_us": round(grad_ref_us, 1),
            "grad_speedup": _speed(grad_ref_us, grad_us),
            "backend": backend_name(), "autotuned": True}


# --------------------------------------------------------- first-use hook

def ensure_measured(kernel: str, shape_key: tuple) -> Optional[str]:
    """Routing's first-use hook (DL4JTPU_AUTOTUNE=1): measure this shape
    on the actual backend, merge the row into this process's tables, and
    return the fresh route for the asked phase — or None when the shape was
    already attempted, is unsupported, or a measurement is running
    (re-entrance: the measurement itself calls the kernels)."""
    global _in_progress
    if _in_progress or (kernel, shape_key) in _attempted:
        return None
    _attempted.add((kernel, shape_key))
    from deeplearning4j_tpu.exec import routing
    _in_progress = True
    try:
        if kernel in ("fused_lstm_fwd", "fused_lstm_grad"):
            b, t, h, dtype = shape_key
            row = measure_fused_lstm(b, t, h, dtype)
            if row is None:
                return None
            routing.load_measurements([row], kernel="fused_lstm")
            table = (routing._MEASURED if kernel == "fused_lstm_fwd"
                     else routing._MEASURED_GRAD)
            route = table.get(("fused_lstm", b, t, h, str(dtype)))
        elif kernel == "flash_attention":
            bh, t, dh, causal, train = shape_key
            row = measure_flash_attention(bh, t, dh, causal)
            if row is None:
                return None
            routing.load_measurements([row], kernel="flash_attention")
            phases = ("fwd", "grad") if train else ("fwd",)
            hits = [routing._FLASH_MEASURED.get((ph, bh, t, dh,
                                                 bool(causal)))
                    for ph in phases]
            route = ("scan" if any(h == "scan" for h in hits)
                     else "pallas" if all(h == "pallas" for h in hits)
                     else None)
        else:
            return None
        _metrics()[0].labels(kernel=kernel).inc()
        return route
    finally:
        _in_progress = False


def sweep(path: str, lstm_shapes=(), flash_shapes=(), iters: int = 3,
          interpret: Optional[bool] = None) -> list:
    """Measure a batch of shapes and write them to the table at ``path``
    in one write (the tools/autotune.py CLI entry point). ``lstm_shapes``: iterable
    of (B, T, H, dtype); ``flash_shapes``: (BH, T, Dh, causal)."""
    rows = []
    for b, t, h, dtype in lstm_shapes:
        row = measure_fused_lstm(b, t, h, dtype, iters=iters,
                                 interpret=interpret)
        if row is not None:
            rows.append(row)
    for bh, t, dh, causal in flash_shapes:
        row = measure_flash_attention(bh, t, dh, causal, iters=iters,
                                      interpret=interpret)
        if row is not None:
            rows.append(row)
    if rows:
        save_rows(rows, path)
    return rows
