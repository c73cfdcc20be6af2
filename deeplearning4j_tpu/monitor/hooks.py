"""Instrumentation glue between the containers and the registry.

``TrainMonitor`` caches one container's metric children so the per-step
record is pure attribute access + locked float adds — no family lookups
in the hot loop. Both containers (MultiLayerNetwork / ComputationGraph)
hold one lazily; ``record()`` is called once per ``_fit_batch`` and once
per ``fit_scan`` chunk.

Score is stored into its gauge as the RAW device scalar — the host read,
which waits for the step, happens at scrape time, never in the train loop
(the same deferred-sync discipline as ``get_score()``).
"""

from __future__ import annotations

from deeplearning4j_tpu.monitor.metrics import (
    DEFAULT_STEP_BUCKETS, get_registry)

__all__ = ["TrainMonitor"]


class TrainMonitor:
    """Cached metric children for one model container instance."""

    def __init__(self, model_kind: str):
        reg = get_registry()
        lab = {"model": model_kind}
        self.steps = reg.counter(
            "dl4jtpu_train_steps_total",
            "Train steps executed (fit_scan counts every scanned step).",
            ("model",)).labels(**lab)
        self.examples = reg.counter(
            "dl4jtpu_train_examples_total",
            "Examples consumed by train steps (examples/sec via rate()).",
            ("model",)).labels(**lab)
        self.score = reg.gauge(
            "dl4jtpu_train_score",
            "Loss of the most recent train step (device scalar, host-read "
            "lazily at scrape).", ("model",)).labels(**lab)
        self.compile_events = reg.counter(
            "dl4jtpu_train_compile_events_total",
            "Train calls that traced a new XLA program.",
            ("model",)).labels(**lab)
        self.compile_seconds = reg.counter(
            "dl4jtpu_train_compile_seconds_total",
            "Wall seconds of train calls that traced a new XLA program "
            "(compile dominates; includes that call's dispatch).",
            ("model",)).labels(**lab)
        hist = reg.histogram(
            "dl4jtpu_train_step_seconds",
            "Host-side dispatch seconds per train call (async on TPU: "
            "enqueue time; compile-bearing calls are excluded — they land "
            "in dl4jtpu_train_compile_seconds_total).",
            ("model", "path"), buckets=DEFAULT_STEP_BUCKETS)
        self._hist = {"batch": hist.labels(model=model_kind, path="batch"),
                      "scan": hist.labels(model=model_kind, path="scan")}

    def record(self, *, seconds: float, steps: int, examples: int,
               score, compiled: int, path: str) -> None:
        """One train call: ``steps`` steps over ``examples`` rows took
        ``seconds`` of host dispatch; ``compiled`` new programs traced."""
        self.steps.inc(steps)
        self.examples.inc(examples)
        self.score.set(score)
        if compiled:
            self.compile_events.inc(compiled)
            self.compile_seconds.inc(seconds)
        else:
            self._hist[path].observe(seconds)
