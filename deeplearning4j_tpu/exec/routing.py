"""Shape-keyed kernel-vs-reference routing (data-driven, overridable).

A hand-written kernel does not win everywhere: KERNELS_TPU.json
(bench_kernels, v5e) shows the fused-LSTM forward LOSING to XLA's scan
codegen at small ``B*H`` for BOTH dtypes (bf16 (4,16,8) runs at 0.1x,
(1,4,8) at 0.03x) and on two f32 shapes the old ``B*H >= 2048``
heuristic routed to Pallas anyway:

    (16, 64, 128, float32)  fwd 0.96x   — crossover shape, scan wins
    (32, 128, 256, float32) fwd 0.72x   — long-T f32: double-width
                                          streams, scan pipelines better

This module owns the routing decision per (backend, kernel, phase,
shape). The shipped measurement file (KERNELS_TPU.json at the repo
root) is absorbed wholesale at first use — every row with a measured
``fwd_speedup`` routes the forward to pallas iff it beat XLA, and every
row with a measured ``grad_route``/``grad_speedup`` routes the BACKWARD
the same way (the backward kernel wins at most validated shapes, but
two measured bf16 rows lose — (4,16,8) 0.24x, (8,32,120) 0.4x — so the
backward is measurement-routed exactly like the forward, with pallas as
the no-data default). Rows measured by the per-backend autotune harness
(exec/autotune.py) merge on top of the shipped file, so first-use
measurements on the actual backend override v5e numbers. The shipped
file is the only one read unasked: any other table reaches routing
through ``load_measurements_file(path)``, called by whoever names it.

Overrides, strongest first:

1. ``set_route(kernel, "pallas"|"scan"|None)`` — programmatic pin
   (per kernel: "fused_lstm", "fused_lstm_grad", "decode_attn",
   "flash_attn")
2. ``DL4JTPU_LSTM_FWD_ROUTE`` / ``DL4JTPU_LSTM_GRAD_ROUTE`` /
   ``DL4JTPU_DECODE_ATTN_ROUTE`` / ``DL4JTPU_FLASH_ATTN_ROUTE`` —
   environment pins
3. under a mesh of more than one device, 'scan' for every compiled
   kernel (``_mosaic_cannot_partition``: XLA will not auto-partition a
   Mosaic call)
4. measured per-shape table (exact (B, T, H, dtype) match, seeded from
   the shipped KERNELS_TPU.json via ``load_measurements`` plus any
   table the caller loaded by name)
5. heuristic: scan when ``B*H < 2048``; f32 additionally needs
   ``B*H > 2048`` and ``T < 128`` (both measured f32 losses above sit
   on those boundaries); otherwise pallas.  The backward defaults to
   pallas (it wins at every validated shape the heuristic covers).

The flash decode-step kernel (ops/flash_decode.py) routes through the
same table: ``decode_attn_route`` defaults to pallas wherever the
kernel supports the shape (the decode step is bandwidth-bound on the
KV cache at every capacity, and the kernel reads only ``pos+1`` of the
``C`` cached rows), with the same pin/env overrides for tests and
rollbacks.

The flash-attention training/inference forward (ops/flash_attention.py)
routes via ``flash_attn_route``: 'pallas' means the flash kernel,
'scan' means the dense XLA softmax-attention path (same vocabulary as
``decode_attn_route``). Training asks for BOTH phases — the custom-vjp
kernel commits forward and backward together, so a shape where the
measured backward loses stays dense even if the forward wins.
"""

import json
import os
from typing import Dict, Optional

# exact measured rows where the decision differs per shape. Seeded from
# the shipped KERNELS_TPU.json on first lookup (``load_measurements``
# absorbs every measured row — bf16 exactly like f32); the literal
# entries below keep the module meaningful without the file and remain
# human-auditable.
_MEASURED = {
    # (kernel, B, T, H, dtype) -> route        measured fwd speedup
    ("fused_lstm", 16, 64, 128, "float32"): "scan",     # 0.96x
    ("fused_lstm", 16, 64, 128, "bfloat16"): "pallas",  # 1.23x
    ("fused_lstm", 32, 128, 256, "float32"): "scan",    # 0.72x
    ("fused_lstm", 32, 128, 256, "bfloat16"): "pallas",  # 1.23x
    ("fused_lstm", 32, 64, 256, "float32"): "pallas",   # 1.19x
    ("fused_lstm", 64, 32, 512, "float32"): "pallas",   # 1.07x
}

# backward-phase table, same key schema. The two literal rows are the
# measured v5e LOSSES (every other validated shape wins — see the
# grad_speedup column of KERNELS_TPU.json); the default is pallas.
_MEASURED_GRAD = {
    ("fused_lstm", 4, 16, 8, "bfloat16"): "scan",     # 0.24x
    ("fused_lstm", 8, 32, 120, "bfloat16"): "scan",   # 0.40x
}

# flash-attention table: (phase, BH, T, Dh, causal) -> route. Seeded
# from the shipped file's flash_attention rows at first lookup.
_FLASH_MEASURED: Dict[tuple, str] = {}

# measured latency/bandwidth crossover (see ops/lstm_pallas.py docstring)
_MIN_BH = 2048

_forced: Dict[str, str] = {}      # kernel -> pinned route
_file_loaded = False


def set_route(kernel: str, route: Optional[str]) -> None:
    """Pin every ``kernel`` decision to ``route`` ('pallas'/'scan' — for
    ``decode_attn``/``flash_attn``, 'scan' means the dense reference
    path), or None to restore data-driven routing. Kernels:
    "fused_lstm" (forward), "fused_lstm_grad" (backward),
    "decode_attn", "flash_attn". Test/debug hook."""
    if route not in (None, "pallas", "scan"):
        raise ValueError(f"route must be pallas/scan/None, got {route!r}")
    if route is None:
        _forced.pop(kernel, None)
    else:
        _forced[kernel] = route


def _mosaic_cannot_partition() -> bool:
    """True while tracing a program the executor hands to the SPMD
    partitioner (a mesh of more than one device) with compiled kernels.
    XLA refuses such a program outright — "Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map"
    (compiled for v5e:2x2 with the fused-LSTM kernel inside a
    batch-sharded step, PR 21) — so under a mesh > 1 every kernel route is
    'scan' until a kernel is wrapped in a shard_map of its own. The
    interpreter lowers a kernel to plain XLA ops, which partition like
    any other, so interpret-mode callers are not held to this."""
    from deeplearning4j_tpu import ops
    from deeplearning4j_tpu.exec.executor import tracing_partitioned
    return tracing_partitioned() and not ops.interpret_mode()


def _grad_decision(row) -> Optional[str]:
    """A row's backward route: explicit ``grad_route`` wins, else the
    measured ``grad_speedup`` decides (pallas iff it beat the scan)."""
    gr = row.get("grad_route")
    if gr in ("pallas", "scan"):
        return gr
    gs = row.get("grad_speedup")
    if gs is None:
        return None
    return "pallas" if gs > 1 else "scan"


def load_measurements(results, kernel: str = "fused_lstm") -> int:
    """Merge bench rows (KERNELS_TPU.json ``results`` schema) into the
    tables: a row routes its forward to pallas iff its measured
    ``fwd_speedup`` > 1, and its backward by ``grad_route`` /
    ``grad_speedup`` the same way. Returns the number of rows absorbed
    (a row counts once even when it feeds both phases)."""
    n = 0
    for row in results:
        if row.get("kernel") != kernel:
            continue
        if kernel == "flash_attention":
            key = (row.get("BH"), row.get("T"), row.get("Dh"),
                   bool(row.get("causal")))
            hit = False
            if row.get("fwd_speedup") is not None:
                _FLASH_MEASURED[("fwd",) + key] = \
                    "pallas" if row["fwd_speedup"] > 1 else "scan"
                hit = True
            grad = _grad_decision(row)
            if grad is not None:
                _FLASH_MEASURED[("grad",) + key] = grad
                hit = True
            n += 1 if hit else 0
            continue
        key = (kernel, row.get("B"), row.get("T"), row.get("H"),
               row.get("dtype"))
        hit = False
        if row.get("fwd_speedup") is not None:
            _MEASURED[key] = "pallas" if row["fwd_speedup"] > 1 else "scan"
            hit = True
        grad = _grad_decision(row)
        if grad is not None:
            _MEASURED_GRAD[key] = grad
            hit = True
        n += 1 if hit else 0
    return n


def load_measurements_file(path: Optional[str] = None) -> int:
    """Absorb a KERNELS_TPU.json bench file (default: the one shipped at
    the repo root) for every kernel it measures. Idempotent; rows merge
    into the same table ``load_measurements`` feeds."""
    if path is None:
        here = os.path.dirname(os.path.abspath(__file__))
        path = os.path.join(os.path.dirname(os.path.dirname(here)),
                            "KERNELS_TPU.json")
    if not os.path.exists(path):
        return 0
    with open(path) as f:
        results = json.load(f).get("results", [])
    kernels = {r.get("kernel") for r in results} - {None}
    return sum(load_measurements(results, kernel=k) for k in sorted(kernels))


def _ensure_file_measurements() -> None:
    """Lazy one-shot load of the shipped measurement file — a tracked
    file, so a missing or malformed one is a defect and raises."""
    global _file_loaded
    if not _file_loaded:
        _file_loaded = True
        load_measurements_file()


def _reset_measurement_cache() -> None:
    """Forget the lazy-load latch (tests reload the shipped file)."""
    global _file_loaded
    _file_loaded = False


def _maybe_autotune(kernel: str, shape_key: tuple) -> Optional[str]:
    """First-use measurement hook: when DL4JTPU_AUTOTUNE is on and the
    tables have no row for this shape, measure kernel-vs-reference on
    the actual backend and return the fresh route (None when autotuning
    is off or the kernel does not support the shape). A measurement that
    fails raises: it ran the same kernel the route would have chosen."""
    if os.environ.get("DL4JTPU_AUTOTUNE", "").strip().lower() \
            not in ("1", "true", "on", "yes"):
        return None
    from deeplearning4j_tpu.exec import autotune
    return autotune.ensure_measured(kernel, shape_key)


def lstm_fwd_route(b: int, h: int, t: Optional[int] = None,
                   dtype: Optional[str] = None,
                   backend: Optional[str] = None) -> str:
    """Route the fused-LSTM forward for one shape: 'pallas' or 'scan'.

    ``backend`` other than TPU always scans (the kernel only compiles
    for Mosaic; CPU/interpret callers gate on that before asking)."""
    forced = _forced.get("fused_lstm")
    if forced is not None:
        return forced
    env = os.environ.get("DL4JTPU_LSTM_FWD_ROUTE", "").strip().lower()
    if env in ("pallas", "scan"):
        return env
    if _mosaic_cannot_partition():
        return "scan"
    if backend is not None and backend != "tpu":
        return "scan"
    if t is not None and dtype is not None:
        _ensure_file_measurements()
        hit = _MEASURED.get(("fused_lstm", b, t, h, str(dtype)))
        if hit is not None:
            return hit
        hit = _maybe_autotune("fused_lstm_fwd", (b, t, h, str(dtype)))
        if hit is not None:
            return hit
    if b * h < _MIN_BH:
        return "scan"
    if str(dtype) == "float32" and (b * h <= _MIN_BH
                                    or (t is not None and t >= 128)):
        return "scan"
    return "pallas"


def lstm_grad_route(b: int, h: int, t: Optional[int] = None,
                    dtype: Optional[str] = None,
                    backend: Optional[str] = None) -> str:
    """Route the fused-LSTM backward for one shape: 'pallas' (the
    reverse-grid kernel) or 'scan' (the equivalent reverse lax.scan,
    ops/lstm_pallas.py ``_scan_bwd``). Default is pallas — the backward
    kernel wins at every validated shape except the measured bf16
    losses in the table — with the same pin/env/measured precedence as
    the forward."""
    forced = _forced.get("fused_lstm_grad")
    if forced is not None:
        return forced
    env = os.environ.get("DL4JTPU_LSTM_GRAD_ROUTE", "").strip().lower()
    if env in ("pallas", "scan"):
        return env
    if _mosaic_cannot_partition():
        return "scan"
    if backend is not None and backend != "tpu":
        return "scan"
    if t is not None and dtype is not None:
        _ensure_file_measurements()
        hit = _MEASURED_GRAD.get(("fused_lstm", b, t, h, str(dtype)))
        if hit is not None:
            return hit
        hit = _maybe_autotune("fused_lstm_grad", (b, t, h, str(dtype)))
        if hit is not None:
            return hit
    return "pallas"


def flash_attn_route(bh: int, t: int, dh: int, causal: bool,
                     train: bool = False,
                     backend: Optional[str] = None,
                     min_t: int = 4096) -> str:
    """Route the flash-attention forward at the layer seam: 'pallas'
    (ops/flash_attention.py) or 'scan' (the dense XLA path).

    ``train=True`` commits the custom-vjp pair, so the decision needs
    BOTH phases to win: a measured 'scan' on either the fwd or grad row
    keeps the shape dense. Without measurements the seam falls back to
    the ``t >= min_t`` crossover (MIN_SEQ_FOR_AUTO_ROUTE, measured on
    v5e — the caller passes 0 in interpret mode so CPU tests exercise
    the kernel at any length)."""
    forced = _forced.get("flash_attn")
    if forced is not None:
        return forced
    env = os.environ.get("DL4JTPU_FLASH_ATTN_ROUTE", "").strip().lower()
    if env in ("pallas", "scan"):
        return env
    if _mosaic_cannot_partition():
        return "scan"
    if backend is not None and backend != "tpu":
        return "scan"
    if backend == "tpu":
        # measured rows only steer REAL compiled routing; interpret-mode
        # callers (backend=None) keep the deterministic min_t gate so the
        # CPU parity tests always exercise the kernel
        _ensure_file_measurements()
        key = (bh, t, dh, bool(causal))
        phases = ("fwd", "grad") if train else ("fwd",)
        hits = [_FLASH_MEASURED.get((ph,) + key) for ph in phases]
        if any(h == "scan" for h in hits):
            return "scan"
        if all(h == "pallas" for h in hits):
            return "pallas"
        hit = _maybe_autotune("flash_attention",
                              (bh, t, dh, bool(causal), bool(train)))
        if hit is not None:
            return hit
    return "pallas" if t >= min_t else "scan"


def decode_attn_route(c: Optional[int] = None, dh: Optional[int] = None,
                      backend: Optional[str] = None,
                      paged: bool = False) -> str:
    """Route the attention decode step: 'pallas' (flash decode-step
    kernel, ops/flash_decode.py) or 'scan' (the dense reference step —
    the path the bitwise-parity decode tests pin on CPU).

    ``paged=True`` asks for the block-table-gather variant
    (``flash_decode_step_paged``): same decision surface — the one
    ``decode_attn`` pin and ``DL4JTPU_DECODE_ATTN_ROUTE`` env apply to
    both, so a rollback or test pin flips the dense and paged engines
    together ('scan' means gather-then-dense-math there, the parity
    oracle).

    Default is pallas wherever the kernel supports the shape: the step
    is HBM-bound on the KV cache and the kernel stops reading at the
    cache position, so it wins by construction once the cache is larger
    than one block (the caller screens ``supported(c, dh)`` /
    ``supported_paged(block_size, dh, n_heads)`` first)."""
    forced = _forced.get("decode_attn")
    if forced is not None:
        return forced
    env = os.environ.get("DL4JTPU_DECODE_ATTN_ROUTE", "").strip().lower()
    if env in ("pallas", "scan"):
        return env
    if _mosaic_cannot_partition():
        return "scan"
    if backend is not None and backend != "tpu":
        return "scan"
    return "pallas"
