"""Device timing for ops shorter than a dispatch, and the fit loop's stage
clocks and counters.

JAX dispatch is asynchronous: a timing that does not wait for the result
measures the enqueue. On a directly attached chip ``block_until_ready``
waits and a scalar read costs microseconds, but launching a program still
costs tens of microseconds of host time — more than many single ops take.

``time_op`` therefore runs the op N times inside ONE jitted
``lax.fori_loop`` (iterations chained with a negligible 1e-30-scaled data
dependency so XLA cannot hoist the body), forces completion with a scalar
host read, and removes the fixed launch+read cost by differencing against
an N=1 run. N is chosen adaptively so the measured delta dominates jitter.
"""

from __future__ import annotations

import time
from collections import deque
from contextlib import contextmanager

import numpy as np

from deeplearning4j_tpu.monitor.tracing import trace


class PipelineTimer:
    """Per-stage accounting of one streamed fit/eval call: where the
    consumer loop's time went, what it cost in CPU, and what it moved.

    ``host_stall_frac()`` is the fraction of the call's wall time the host
    spent WAITING ON DATA instead of dispatching device work — the number
    that caps accelerator utilization once the compiled step is fast
    (un-pipelined input feeding, not FLOPs).

    Stage conventions used by ``_fit_stream``:

    - ``wait``  — consumer blocked in ``next()`` on the input stream. With
      the prefetch pipeline on, this is the ONLY stall the host sees (the
      fetch/decode/h2d work happens inside it or ahead of it).
    - ``fetch`` / ``decode`` / ``stack`` / ``h2d`` — sub-stage costs
      recorded by the stream/prefetcher; they are nested inside ``wait``
      so they are NOT summed into the stall when ``wait`` was recorded.
    - ``dispatch`` — handing a step (or a chunk of steps) to the device.
      Dispatch is asynchronous: this is enqueue time, and once the host has
      run as far ahead as the runtime lets it, mostly time blocked on the
      device's back-pressure. It is not the device's step time (that is
      ``time_op`` below, or a device trace).

    Beside the seconds (all per call, all in ``summary()``):

    - ``loop_cpu_sec`` — ``time.thread_time()`` of the loop's thread: what
      the loop *worked*. Wall minus CPU is time blocked, on data or on the
      device.
    - ``process_cpu_sec`` — ``time.process_time()`` over the loop: also the
      runtime's own threads (``device_put`` linearizes a batch there).
    - ``steps`` — train steps the loop dispatched.
    - ``bytes_staged`` — bytes of every array the ``DevicePrefetcher`` put
      on the device.
    - ``in_flight_max`` — the most dispatched program calls not yet
      complete, counted right after each dispatch (a call is one step on
      the ``train_step`` path, one chunk on ``fit_scan``): how far ahead of
      the device the host runs.

    ``host_stall_frac`` = wait/wall when ``wait`` was recorded, else
    (fetch+decode+h2d)/wall (the naive un-pipelined path executes those
    stages inline on the consumer thread)."""

    _STALL_FALLBACK = ("fetch", "decode", "h2d")

    def __init__(self):
        self.seconds = {}
        self.counts = {}
        self._t0 = None
        self.wall = 0.0
        self.loop_cpu = 0.0
        self.process_cpu = 0.0
        self.steps = 0
        self.bytes_staged = 0
        self._in_flight = deque()
        self.in_flight_max = 0

    def add(self, stage: str, sec: float):
        self.seconds[stage] = self.seconds.get(stage, 0.0) + sec
        self.counts[stage] = self.counts.get(stage, 0) + 1

    @contextmanager
    def stage(self, name: str):
        # every timed stage is also a trace span (no-op while tracing is
        # off), so the Perfetto timeline and the stage totals agree
        with trace.span(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.add(name, time.perf_counter() - t0)

    @contextmanager
    def dispatch(self, result):
        """The ``dispatch`` stage. ``result()`` is read after the block and
        gives the container's score: the loss array of the dispatched call.
        Calls whose array ``is_ready()`` are dropped before the dispatch,
        the new one joins after it, and what is left is in flight. Where a
        listener read the score inside the block (``get_score()`` leaves a
        float there), the host has waited for this call and so for every
        call before it: nothing is in flight."""
        q = self._in_flight
        while q and q[0].is_ready():
            q.popleft()
        with self.stage("dispatch"):
            yield
        loss = result()
        if hasattr(loss, "is_ready"):
            q.append(loss)
        else:
            q.clear()
        self.in_flight_max = max(self.in_flight_max, len(q))

    def start(self):
        self._t0 = (time.perf_counter(), time.thread_time(),
                    time.process_time())
        return self

    def stop(self):
        if self._t0 is not None:
            t0, c0, p0 = self._t0
            self.wall += time.perf_counter() - t0
            self.loop_cpu += time.thread_time() - c0
            self.process_cpu += time.process_time() - p0
            self._t0 = None
        self._in_flight.clear()
        return self

    def host_stall_frac(self):
        if not self.wall:
            return None
        if "wait" in self.seconds:
            stall = self.seconds["wait"]
        else:
            stall = sum(self.seconds.get(s, 0.0)
                        for s in self._STALL_FALLBACK)
        return min(1.0, stall / self.wall)

    def summary(self) -> dict:
        out = {"wall_sec": round(self.wall, 4),
               "host_stall_frac": self.host_stall_frac(),
               "loop_cpu_sec": round(self.loop_cpu, 4),
               "process_cpu_sec": round(self.process_cpu, 4),
               "steps": self.steps,
               "bytes_staged": self.bytes_staged,
               "in_flight_max": self.in_flight_max}
        if out["host_stall_frac"] is not None:
            out["host_stall_frac"] = round(out["host_stall_frac"], 4)
        for k in sorted(self.seconds):
            out[f"{k}_sec"] = round(self.seconds[k], 4)
        return out

    def publish(self, path: str):
        """Flow this timer's totals into the process-wide MetricsRegistry
        so they are scrapeable at ``/metrics``. ``path`` labels the
        pipeline ("fit" / "eval"). Counters accumulate across calls; the
        stall fraction and in-flight gauges hold the LAST call's value."""
        from deeplearning4j_tpu.monitor.metrics import get_registry
        reg = get_registry()
        fam = reg.counter(
            "dl4jtpu_pipeline_stage_seconds_total",
            "Cumulative input-pipeline stage seconds (see PipelineTimer "
            "stage conventions).", ("path", "stage"))
        for stage, sec in self.seconds.items():
            fam.labels(path=path, stage=stage).inc(sec)
        reg.counter(
            "dl4jtpu_pipeline_wall_seconds_total",
            "Cumulative wall seconds of streamed fit/eval epochs.",
            ("path",)).labels(path=path).inc(self.wall)
        cpu = reg.counter(
            "dl4jtpu_pipeline_cpu_seconds_total",
            "Cumulative CPU seconds over streamed fit/eval epochs: of the "
            "consumer loop's thread (scope=loop) and of the whole process "
            "(scope=process).", ("path", "scope"))
        cpu.labels(path=path, scope="loop").inc(self.loop_cpu)
        cpu.labels(path=path, scope="process").inc(self.process_cpu)
        reg.counter(
            "dl4jtpu_pipeline_steps_total",
            "Train steps dispatched by streamed fit epochs.",
            ("path",)).labels(path=path).inc(self.steps)
        reg.counter(
            "dl4jtpu_pipeline_bytes_staged_total",
            "Bytes the device prefetcher put on the device.",
            ("path",)).labels(path=path).inc(self.bytes_staged)
        reg.gauge(
            "dl4jtpu_pipeline_in_flight_max",
            "Most dispatched program calls not yet complete at once in "
            "the last epoch: how far the host ran ahead of the device.",
            ("path",)).labels(path=path).set(self.in_flight_max)
        frac = self.host_stall_frac()
        if frac is not None:
            reg.gauge(
                "dl4jtpu_pipeline_host_stall_frac",
                "Fraction of the last epoch's wall time the host spent "
                "blocked waiting on data.",
                ("path",)).labels(path=path).set(frac)
        return self


def host_sync(x) -> float:
    """Force completion of ``x`` by reading one scalar to the host."""
    import jax
    leaf = jax.tree_util.tree_leaves(x)[0]
    return float(np.asarray(jax.device_get(leaf)).ravel()[0])


def _chained_loop(fn, iters):
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def loop(*args):
        def body(_, carry):
            s, = carry
            out = fn(args[0] + s, *args[1:])
            leaf = jax.tree_util.tree_leaves(out)[0]
            return (jnp.asarray(leaf, jnp.float32).ravel()[0] * 1e-30,)
        return lax.fori_loop(0, iters, body, (jnp.float32(0),))[0]

    return loop


def _run(loop, args, repeats=3):
    best = float("inf")
    host_sync(loop(*args))                    # compile + warm
    for _ in range(repeats):
        t0 = time.perf_counter()
        host_sync(loop(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def time_op(fn, *args, target_s: float = 0.15, pilot_iters: int = 128,
            max_iters: int = 8192, repeats: int = 3) -> float:
    """Seconds per execution of ``fn(*args)`` on device.

    ``fn``'s first argument must be an array (it carries the chaining
    perturbation); its output may be any pytree of arrays.
    """
    t1 = _run(_chained_loop(fn, 1), args, repeats)
    n = pilot_iters
    tn = _run(_chained_loop(fn, n), args, repeats)
    delta = tn - t1
    if delta < target_s / 2:
        n2 = min(max_iters, max(n * 2, int(n * target_s / max(delta, 1e-3))))
        if n2 > n:
            n = n2
            tn = _run(_chained_loop(fn, n), args, repeats)
            delta = tn - t1
    return max(delta, 1e-9) / (n - 1)


def time_python_loop(step, n_steps: int, sync) -> float:
    """Seconds per step of a Python-level training loop with fixed-cost
    differencing: run ``step`` once + sync, then ``n_steps`` times + sync,
    return the per-step delta. ``step(i)`` must chain state internally;
    ``sync()`` must host-read something produced by the last step."""
    step(0)
    sync()                                     # warm / ensure compiled
    t0 = time.perf_counter()
    step(0)
    sync()
    t_one = time.perf_counter() - t0
    t0 = time.perf_counter()
    for i in range(n_steps):
        step(i)
    sync()
    t_n = time.perf_counter() - t0
    return max(t_n - t_one, 1e-9) / (n_steps - 1)
