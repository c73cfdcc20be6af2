"""The compile ledger: what bringing programs into being cost, by phase.

JAX reports from the inside, through ``jax.monitoring``, every trace of a
jitted function to a jaxpr, every lowering of a jaxpr to a module, every
backend compile (which wraps the persistent cache's lookup, so a program
that loads is in it too) and what the cache answered. ``install()`` puts
one listener of each of the four kinds on those events, once, and turns
them into

- counters, always on: ``dl4jtpu_compile_stage_seconds_total{phase,stage}``
  with ``stage`` in ``trace``, ``lower``, ``backend``, ``cache_load``
  (the seconds of ``backend`` spent fetching and deserialising a cached
  executable), and ``dl4jtpu_compile_requests_total{phase,result}``, one a
  backend compile, with ``result`` in ``hit`` (loaded from the persistent
  cache), ``miss`` (the cache was asked, had none, XLA compiled) and
  ``uncached`` (XLA compiled and the cache was never asked);
- spans, only while the tracer is enabled: ``jit_trace``, ``jit_lower``,
  ``xla_compile`` and ``cache_load``, each a complete span in the tracer's
  ring with ``fun_name`` and ``phase`` (and ``result`` on the last two),
  written by ``Tracer.complete`` from JAX's own start and end.

``phase`` is what the program was doing, not a guess from a function's
name: a thread-local marker the entry points open (``with phase("fit")``):
``init``, ``fit``, ``register`` (the program registry's own lowering and
compile), ``output``, ``serve``; the innermost open marker wins, and with
none open an event lands in ``outside``.

A second of a thread is counted once. JAX reports the trace of ``matmul``
inside the trace of the ``step`` that calls it, and a lowering rule may
trace again: a stage that ends inside another open stage of its thread has
its seconds in that one already and adds none (its span is still written,
nested). So the stages of one phase add up to wall time of that thread.

The listeners run only when something is traced or compiled; a steady
training or serving loop never calls them.
"""

from __future__ import annotations

import threading
import time

from deeplearning4j_tpu.monitor.metrics import get_registry
from deeplearning4j_tpu.monitor.tracing import trace

__all__ = ["install", "phase", "current_phase", "mark", "since", "OUTSIDE"]

OUTSIDE = "outside"

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_HIT = "/jax/compilation_cache/cache_hits"
_LOADED = "/jax/compilation_cache/cache_retrieval_time_sec"

# a timed event of JAX -> (its ``stage`` label, its span's name)
_STAGES = {_TRACE: ("trace", "jit_trace"), _LOWER: ("lower", "jit_lower"),
           _BACKEND: ("backend", "xla_compile")}
_SECONDS = ("trace", "lower", "backend", "cache_load")
# by what a build's ``cache`` reads first: anything compiled, then loaded
_RESULTS = ("miss", "uncached", "hit")


class _Thread(threading.local):
    def __init__(self):
        self.phase = None
        self.depth = 0        # timed stages open on this thread
        self.asked = False    # the open backend compile asked the cache
        self.hit = False      # ... and the cache had it
        self.load = None      # (start, end) of that retrieval, time.time()
        self.totals = dict.fromkeys(_SECONDS + _RESULTS, 0.0)


_T = _Thread()
_install_lock = threading.Lock()
_installed = False


# ---------------------------------------------------------------- phase
class phase:
    """``with phase("fit"): ...``: what this thread is doing, for every
    compile event until the block ends (re-entrant; the innermost wins).
    Works with the tracer off: it is one thread-local attribute."""

    __slots__ = ("_name", "_prev")

    def __init__(self, name: str):
        self._name = name

    def __enter__(self):
        install()
        self._prev = _T.phase
        _T.phase = self._name
        return self

    def __exit__(self, *exc):
        _T.phase = self._prev
        return False


def current_phase() -> str:
    return _T.phase or OUTSIDE


# ------------------------------------------------------ a call's own part
def mark() -> dict:
    """This thread's running totals (seconds by stage, requests by result),
    to hand to :func:`since` after a call that may have built a program."""
    return dict(_T.totals)


def since(m: dict) -> dict:
    """What this thread added since ``m = mark()``: ``trace_seconds``,
    ``lower_seconds``, ``backend_seconds``, ``cache_load_seconds`` and
    ``cache``: ``miss`` if anything the cache was asked for compiled, else
    ``uncached`` if anything compiled unasked, else ``hit`` if anything
    loaded, else None (nothing reached the backend)."""
    now = _T.totals
    out = {f"{s}_seconds": now[s] - m[s] for s in _SECONDS}
    out["cache"] = next((r for r in _RESULTS if now[r] > m[r]), None)
    return out


# ------------------------------------------------------------ listeners
def _seconds(ph, stage, secs):
    secs = max(secs, 0.0)     # time.time() may step back under a span
    get_registry().counter(
        "dl4jtpu_compile_stage_seconds_total",
        "Seconds spent bringing programs into being, by what the program "
        "was doing (phase) and by stage: trace, lower, backend (XLA's "
        "compile or the persistent cache's load) and cache_load (the part "
        "of backend spent loading). A thread's second is counted once.",
        ("phase", "stage")).labels(phase=ph, stage=stage).inc(secs)
    _T.totals[stage] += secs


def _on_scalar(event, value, **kw):
    # JAX records a stage's start time as a scalar when it opens
    if event in _STAGES:
        _T.depth += 1


def _on_event(event, **kw):
    if event == _ASKED:
        _T.asked = True
    elif event == _HIT:
        _T.hit = True


def _on_duration(event, secs, **kw):
    # fires as the retrieval of a cached executable ends
    if event == _LOADED:
        end = time.time()
        _T.load = (end - secs, end)


def _on_span(event, start, end, **kw):
    stage = _STAGES.get(event)
    if stage is None:
        return
    t = _T
    # 0 where the listeners came while the stage was open
    t.depth = max(t.depth - 1, 0)
    outermost = t.depth == 0
    if not (outermost or event == _BACKEND or trace.enabled):
        # the common case, thousands a step program: the trace of a helper
        # inside the trace that calls it, its seconds counted there
        return
    label, name = stage
    ph = t.phase or OUTSIDE
    args = {"fun_name": kw.get("fun_name", ""), "phase": ph}
    if event == _BACKEND:
        result = "hit" if t.hit else "miss" if t.asked else "uncached"
        load, t.load = t.load, None    # the cache times a retrieval that hit
        t.asked = t.hit = False
        get_registry().counter(
            "dl4jtpu_compile_requests_total",
            "Backend compiles asked for, by phase and by how each ended: "
            "hit (loaded from the persistent cache), miss (the cache had "
            "none: XLA compiled), uncached (XLA compiled, cache not asked).",
            ("phase", "result")).labels(phase=ph, result=result).inc()
        t.totals[result] += 1
        args["result"] = result
        if load is not None:
            if outermost:
                # JAX times the retrieval on another clock than the stage
                # around it: never more than that stage
                _seconds(ph, "cache_load",
                         min(load[1] - load[0], end - start))
            if trace.enabled:
                trace.complete("cache_load", load[0], load[1], **args)
    if outermost:
        _seconds(ph, label, end - start)
    if trace.enabled:
        trace.complete(name, start, end, **args)


def install() -> None:
    """Register the four listeners on ``jax.monitoring``; the second and
    later calls do nothing. Every ``phase`` calls it, so the first entry
    point a process reaches turns the ledger on."""
    global _installed
    if _installed:
        return
    with _install_lock:
        if _installed:
            return
        from jax import monitoring
        monitoring.register_scalar_listener(_on_scalar)
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_time_span_listener(_on_span)
        _installed = True
