"""Low-overhead span tracing exported as Chrome trace-event JSON.

The per-step timeline half of the observability subsystem (fleet counters
are ``monitor/metrics.py``). Spans follow the Dapper model (Sigelman et
al., 2010): nestable named intervals recorded per thread, serialized as
``B``/``E`` (duration begin/end) events in the Chrome trace-event format
— load the exported file straight into Perfetto
(https://ui.perfetto.dev) or ``chrome://tracing`` and the ``train_step``
spans visually nest ``wait`` (with ``fetch``/``decode``/``stack``/``h2d``
inside it) and ``dispatch`` (with ``callback`` inside it); the serving
path shows ``enqueue``/``bucket``/``pad``/``device``/``readback``.

Two sinks, one set of spans. The ring buffer below is for the fleet merge
and is stamped with the wall clock. A ``jax.profiler`` capture has a clock
of its own (an event's ``start_ns`` counts from the session's start, not
from the unix epoch), so the ring's timestamps cannot be laid beside the
device's operations after the fact. While the tracer is enabled every span
therefore also enters a ``jax.profiler.TraceAnnotation`` of the same name
and arguments (``Tracer.step`` a ``StepTraceAnnotation`` carrying
``step_num``): under a capture with the host tracer at level 1
(``monitor/profiling.py``) the spans land in the xplane's host plane, on
the clock of the device's operations, and a device idle gap can be named
by the span the host was in. Outside a capture the annotation is inert.

Fleet tracing: timestamps are anchored to the unix epoch (wall clock) so
spans recorded by *different processes* — the router, each replica
subprocess — merge onto one timeline. A :class:`TraceContext` minted at
the router rides the ``x-trace-context`` HTTP header into every replica;
while a context is installed (thread-local), every span records its
``trace_id`` so a collected fleet document can be filtered to one
request's path end to end. ``monitor/collect.py`` pulls each process's
ring buffer over ``GET /trace`` and emits the single merged document.

Overhead discipline: tracing is OFF by default; a disabled tracer's
``span()`` returns one shared no-op context manager (no allocation, no
clock read). Enabled, argless spans are cached per name (no per-call
allocation); each span costs two ``perf_counter`` reads, two dict
appends into a bounded ring buffer (old events are dropped, the process
never grows without bound) and one profiler annotation. The bench's
``observability`` row pins the cost of both states.

Enable via code (``trace.enable()``) or environment::

    DL4JTPU_TRACE=1                 # collect; export manually
    DL4JTPU_TRACE=/tmp/step.json    # collect + auto-export at exit
"""

from __future__ import annotations

import atexit
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Optional

__all__ = [
    "Tracer", "trace", "get_tracer",
    "TraceContext", "set_context", "get_context", "trace_context",
]


# ------------------------------------------------------------- context
class TraceContext:
    """Dapper-style trace identity carried across process boundaries.

    ``trace_id`` names the whole request tree (the router mints it from
    the request id); ``parent`` names the span that caused this process
    to do work (e.g. the router attempt ``req-...#a1``). Serialized as
    the ``x-trace-context`` header: ``trace_id`` or ``trace_id;parent``.
    """

    __slots__ = ("trace_id", "parent")

    def __init__(self, trace_id: str, parent: str = ""):
        self.trace_id = trace_id
        self.parent = parent

    def child(self, parent: str) -> "TraceContext":
        return TraceContext(self.trace_id, parent)

    def to_header(self) -> str:
        return f"{self.trace_id};{self.parent}" if self.parent else self.trace_id

    @classmethod
    def from_header(cls, value) -> Optional["TraceContext"]:
        if not value:
            return None
        value = value.strip()
        if not value:
            return None
        trace_id, _, parent = value.partition(";")
        return cls(trace_id, parent)

    def __repr__(self):  # pragma: no cover - debug aid
        return f"TraceContext({self.trace_id!r}, parent={self.parent!r})"


_CTX = threading.local()


def set_context(ctx: Optional[TraceContext]) -> None:
    """Install ``ctx`` as this thread's current trace context."""
    _CTX.ctx = ctx


def get_context() -> Optional[TraceContext]:
    return getattr(_CTX, "ctx", None)


class _CtxScope:
    __slots__ = ("_ctx", "_prev")

    def __init__(self, ctx):
        self._ctx = ctx

    def __enter__(self):
        self._prev = getattr(_CTX, "ctx", None)
        _CTX.ctx = self._ctx
        return self._ctx

    def __exit__(self, *exc):
        _CTX.ctx = self._prev
        return False


def trace_context(ctx: Optional[TraceContext]) -> _CtxScope:
    """``with trace_context(ctx): ...`` — install for a scope, restoring
    the previous context on exit (re-entrant, per-thread)."""
    return _CtxScope(ctx)


# ---------------------------------------------------------------- spans
class _NullSpan:
    """Shared no-op context manager returned while tracing is disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


def _profiler_annotation(name, args, step):
    """The span's twin on the profiler's clock, or None in a process that
    has not imported jax (it can hold no capture either)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    cls = (jax.profiler.StepTraceAnnotation if step
           else jax.profiler.TraceAnnotation)
    return cls(name, **args) if args else cls(name)


class _Span:
    __slots__ = ("_tr", "_name", "_args", "_step")

    def __init__(self, tr, name, args, step=False):
        self._tr = tr
        self._name = name
        self._args = args
        self._step = step

    def __enter__(self):
        tr = self._tr
        ev = {"ph": "B", "name": self._name, "pid": tr._pid,
              "tid": threading.get_ident(),
              "ts": (tr._epoch + time.perf_counter()) * 1e6}
        args = self._args
        ctx = getattr(_CTX, "ctx", None)
        if ctx is not None:
            # never mutate self._args: argless spans are cached + shared
            args = dict(args) if args else {}
            args["trace_id"] = ctx.trace_id
            if ctx.parent:
                args["parent"] = ctx.parent
        if args:
            ev["args"] = args
        tr._events.append(ev)
        # argless spans are shared between threads, so the annotation an
        # enter opens lives on the thread's own stack, not on the span
        anno = _profiler_annotation(self._name, args, self._step)
        if anno is not None:
            anno.__enter__()
        try:
            _CTX.annos.append(anno)
        except AttributeError:
            _CTX.annos = [anno]
        return self

    def __exit__(self, *exc):
        anno = _CTX.annos.pop()
        if anno is not None:
            anno.__exit__(*exc)
        tr = self._tr
        tr._events.append(
            {"ph": "E", "name": self._name, "pid": tr._pid,
             "tid": threading.get_ident(),
             "ts": (tr._epoch + time.perf_counter()) * 1e6})
        return False


class Tracer:
    """Ring-buffered span recorder.

    ``capacity`` bounds memory: a deque(maxlen) of event dicts — at the
    default 200k events (~100k spans) a steady-state training loop keeps
    the most recent few thousand steps, which is what a stall
    investigation actually looks at.

    Timestamps are wall-clock microseconds (``time.time()`` anchored
    once, advanced by ``perf_counter`` so they stay monotonic within the
    process): every process shares the epoch, which is what lets
    ``monitor/collect.py`` merge ring buffers from N processes onto one
    Perfetto timeline."""

    def __init__(self, capacity: int = 200_000, enabled: bool = False):
        self._capacity = int(capacity)
        self._events = deque(maxlen=self._capacity)
        self._enabled = bool(enabled)
        self._pid = os.getpid()
        # wall-clock anchor: ts = (_epoch + perf_counter()) seconds
        self._epoch = time.time() - time.perf_counter()
        self._process_name = ""
        self._argless = {}

    @property
    def enabled(self) -> bool:
        return self._enabled

    def enable(self, on: bool = True) -> "Tracer":
        self._enabled = bool(on)
        return self

    def set_process_name(self, name: str) -> "Tracer":
        """Name this process's track in merged fleet traces (emitted as a
        Chrome ``process_name`` metadata event on export)."""
        self._process_name = str(name)
        return self

    @property
    def process_name(self) -> str:
        return self._process_name

    def clear(self) -> "Tracer":
        # rebind rather than .clear(): a concurrent span/instant append
        # lands harmlessly in the old deque instead of racing the wipe
        self._events = deque(maxlen=self._capacity)
        return self

    def span(self, name: str, **args):
        """``with trace.span("dispatch"): ...`` — nest freely; disabled
        tracing returns a shared no-op (near-zero cost)."""
        if not self._enabled:
            return _NULL_SPAN
        if not args:
            # argless spans (the hot-path kind) are immutable: cache one
            # instance per name instead of allocating per call
            s = self._argless.get(name)
            if s is None:
                s = self._argless[name] = _Span(self, name, None)
            return s
        return _Span(self, name, args)

    def step(self, name: str, step_num: int):
        """One iteration of a loop: a span that carries ``step_num`` and
        whose twin in a profiler capture is a ``StepTraceAnnotation``, so
        the device's operations group under the step that dispatched them.
        Positional, so that a disabled tracer's call allocates nothing."""
        if not self._enabled:
            return _NULL_SPAN
        return _Span(self, name, {"step_num": int(step_num)}, step=True)

    def instant(self, name: str, **args):
        """Point-in-time marker (Chrome ``i`` event)."""
        if not self._enabled:
            return
        ev = {"ph": "i", "name": name, "pid": self._pid,
              "tid": threading.get_ident(), "s": "t",
              "ts": (self._epoch + time.perf_counter()) * 1e6}
        ctx = getattr(_CTX, "ctx", None)
        if ctx is not None:
            args = dict(args) if args else {}
            args["trace_id"] = ctx.trace_id
        if args:
            ev["args"] = args
        self._events.append(ev)

    def complete(self, name: str, start: float, end: float, **args):
        """A span that is already over, given by its ``time.time()``
        seconds (Chrome ``X`` event): how the compile ledger writes the
        stages JAX timed itself (monitor/compile_ledger.py). The ring's
        clock agrees with ``time.time()`` when the tracer is made and
        parts from it by whatever the system does to the wall clock
        afterwards (NTP slews it by up to 0.5 ms a second, a step moves it
        whole). Both clocks are read here and the span is shifted by their
        difference, so it lies where the ring's own spans of this moment
        lie; what is left is an adjustment of the wall clock during the
        span itself, under 0.5 ms a second of span."""
        if not self._enabled:
            return
        shift = self._epoch + time.perf_counter() - time.time()
        ev = {"ph": "X", "name": name, "pid": self._pid,
              "tid": threading.get_ident(), "ts": (start + shift) * 1e6,
              "dur": max(end - start, 0.0) * 1e6}
        if args:
            ev["args"] = args
        self._events.append(ev)

    def events(self) -> list:
        return list(self._events)

    def export(self, path: Optional[str] = None) -> dict:
        """The Chrome trace-event document; written to ``path`` as JSON
        when given.

        Events are sorted by timestamp, and ``E`` events whose matching
        ``B`` fell off the ring (a wrap keeps the end of a span whose
        begin was dropped) are removed — an unbalanced ``E`` makes
        Perfetto close the *wrong* enclosing span, mis-nesting the whole
        track. A ``B`` without an ``E`` (span still open) is fine and is
        kept."""
        events = sorted(self._events, key=lambda e: e["ts"])
        kept, depth = [], {}
        for ev in events:
            ph = ev["ph"]
            if ph == "B":
                key = (ev["pid"], ev["tid"])
                depth[key] = depth.get(key, 0) + 1
            elif ph == "E":
                key = (ev["pid"], ev["tid"])
                d = depth.get(key, 0)
                if d <= 0:
                    continue  # orphan E: its B was dropped by the ring
                depth[key] = d - 1
            kept.append(ev)
        meta = []
        if self._process_name:
            meta.append({"ph": "M", "name": "process_name",
                         "pid": self._pid, "tid": 0,
                         "args": {"name": self._process_name}})
        doc = {"traceEvents": meta + kept, "displayTimeUnit": "ms"}
        if path:
            with open(path, "w") as f:
                json.dump(doc, f)
        return doc


# ---------------------------------------------------------------- default
# The process-wide tracer every instrumented path records into (the span
# analog of metrics.get_registry()).
trace = Tracer()


def get_tracer() -> Tracer:
    return trace


_env = os.environ.get("DL4JTPU_TRACE", "")
if _env and _env.lower() not in ("0", "false", "off", "no"):
    trace.enable(True)
    if os.sep in _env or _env.endswith(".json"):
        atexit.register(trace.export, _env)
