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


def _inc_wide(counter, seen, key, words):
    """Advance ``counter`` to a layer's running total kept as (low, high)
    uint32 words in its state; ``seen[key]`` is the total at the last call.
    A state set back to zero starts the sum again."""
    lo, hi = (int(w) for w in words)
    now = (hi << 32) | lo
    last = seen.get(key, 0)
    counter.inc(now - last if now >= last else now)
    seen[key] = now


class TrainMonitor:
    """Cached metric children for one model container instance."""

    def __init__(self, model_kind: str):
        reg = get_registry()
        lab = {"model": model_kind}
        self._kind = model_kind
        self._moe = None              # dl4jtpu_moe_* families, on first use
        self._sel = None              # dl4jtpu_sparse_attention_*, likewise
        self._ssm = None              # dl4jtpu_ssm_*, likewise
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

    def record_init(self, seconds: float, leaves: int) -> None:
        """One ``init()``: its wall seconds (the leaves' programs traced,
        compiled or loaded, and run) and the arrays it made: parameters,
        state and the updater's state."""
        reg, lab = get_registry(), {"model": self._kind}
        reg.counter(
            "dl4jtpu_init_seconds_total",
            "Wall seconds inside the containers' init().",
            ("model",)).labels(**lab).inc(seconds)
        reg.counter(
            "dl4jtpu_init_leaves_total",
            "Arrays init() made: parameters, state, the updater's state. "
            "Beside dl4jtpu_compile_requests_total{phase=\"init\"} it says "
            "how many programs a leaf costs.",
            ("model",)).labels(**lab).inc(leaves)

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

    def publish_expert_counters(self, layers, state, tokens=0) -> None:
        """At the end of a streamed fit call: what the expert layers counted
        inside the steps (their state, which the step returns), as
        ``dl4jtpu_moe_*`` labelled by layer. ``layers``: key -> layer,
        ``state``: the container's state under the same keys; ``tokens``:
        the tokens a layer saw in the last step (0: not known, and the
        rounds are not published). One host read of four scalars a layer;
        a model without expert layers reads nothing."""
        keys = [k for k in layers if state[k] and "pairs_total" in state[k]]
        if not keys:
            return
        import jax
        names = ("pairs_total", "pairs_dropped_total", "pairs", "load_max")
        if self._moe is None:
            reg = get_registry()
            lab = ("model", "layer")
            self._moe = {
                "pairs_total": reg.counter(
                    "dl4jtpu_moe_pairs_total",
                    "(token, expert) pairs routed to experts the layer "
                    "holds, over training steps.", lab),
                "pairs_dropped_total": reg.counter(
                    "dl4jtpu_moe_pairs_dropped_total",
                    "Such pairs that no round computed, counted by the "
                    "rounds that ran (a dropless layer keeps this at 0).",
                    lab),
                "load_max": reg.gauge(
                    "dl4jtpu_moe_expert_load_max",
                    "Pairs on the fullest held expert in the last step.",
                    lab),
                "load_mean": reg.gauge(
                    "dl4jtpu_moe_expert_load_mean",
                    "Pairs per held expert in the last step.", lab),
                "rounds_last": reg.gauge(
                    "dl4jtpu_moe_rounds_last",
                    "Rounds of the sorted-pair buffer that the last step's "
                    "routing needed: 1 where the first round held every "
                    "pair, above 1 where the step paid for later rounds.",
                    lab)}
            self._moe_seen = {}
        got = jax.device_get({k: {n: state[k][n] for n in names}
                              for k in keys})
        for k in keys:
            lab = {"model": self._kind, "layer": str(layers[k].name or k)}
            for name in ("pairs_total", "pairs_dropped_total"):
                now = int(got[k][name]) & 0xFFFFFFFF
                last = self._moe_seen.get((k, name), 0)
                self._moe[name].labels(**lab).inc((now - last) & 0xFFFFFFFF)
                self._moe_seen[(k, name)] = now
            self._moe["load_max"].labels(**lab).set(int(got[k]["load_max"]))
            self._moe["load_mean"].labels(**lab).set(
                int(got[k]["pairs"]) / layers[k].held[0])
            if tokens:
                rows, _ = layers[k].round_rows(tokens)
                self._moe["rounds_last"].labels(**lab).set(
                    max(1, -(-int(got[k]["pairs"]) // rows)))

    def publish_selection_counters(self, layers, state) -> None:
        """At the end of a streamed fit call: what the attention layers
        that select their keys counted inside the steps (their state), as
        ``dl4jtpu_sparse_attention_keys_{selected,visible}_total`` and
        ``dl4jtpu_index_loss`` labelled by layer. The totals are (low,
        high) uint32 words in the state and exact here. One host read a
        layer; a model without such layers reads nothing."""
        keys = [k for k in layers
                if state[k] and "keys_selected_total" in state[k]]
        if not keys:
            return
        import jax
        if self._sel is None:
            reg = get_registry()
            lab = ("model", "layer")
            self._sel = {
                "keys_selected_total": reg.counter(
                    "dl4jtpu_sparse_attention_keys_selected_total",
                    "(query, key) pairs the layer attended over training "
                    "steps: the keys its indexer selected.", lab),
                "keys_visible_total": reg.counter(
                    "dl4jtpu_sparse_attention_keys_visible_total",
                    "(query, key) pairs a causal layer without a selection "
                    "would have attended over the same steps.", lab),
                "index_loss": reg.gauge(
                    "dl4jtpu_index_loss",
                    "The indexer's own loss in the last step: the mean over "
                    "queries of the KL from the attention's head-mean "
                    "weights to the indexer's distribution on the selected "
                    "keys.", lab)}
            self._sel_seen = {}
        got = jax.device_get({k: state[k] for k in keys})
        for k in keys:
            lab = {"model": self._kind, "layer": str(layers[k].name or k)}
            for name in ("keys_selected_total", "keys_visible_total"):
                _inc_wide(self._sel[name].labels(**lab), self._sel_seen,
                          (k, name), got[k][name])
            self._sel["index_loss"].labels(**lab).set(
                float(got[k]["index_loss"]))

    def publish_ssm_counters(self, layers, state) -> None:
        """At the end of a streamed fit call: what the state-space mixers
        counted inside the steps (their state), as
        ``dl4jtpu_ssm_tokens_total`` and ``dl4jtpu_ssm_decay_mean`` labelled
        by layer. The total is (low, high) uint32 words in the state and
        exact here. One host read a layer; a model without such layers
        reads nothing."""
        keys = [k for k in layers if state[k] and "decay_mean" in state[k]]
        if not keys:
            return
        import jax
        if self._ssm is None:
            reg = get_registry()
            lab = ("model", "layer")
            self._ssm = {
                "tokens_total": reg.counter(
                    "dl4jtpu_ssm_tokens_total",
                    "Positions the mixer's recurrence ran over in training "
                    "steps.", lab),
                "decay_mean": reg.gauge(
                    "dl4jtpu_ssm_decay_mean",
                    "Mean over positions and heads of the state's decay "
                    "exp(delta a) in the last step: near 1 the state "
                    "remembers far back, near 0 it forgets at once.", lab)}
            self._ssm_seen = {}
        got = jax.device_get({k: state[k] for k in keys})
        for k in keys:
            lab = {"model": self._kind, "layer": str(layers[k].name or k)}
            _inc_wide(self._ssm["tokens_total"].labels(**lab),
                      self._ssm_seen, k, got[k]["tokens_total"])
            self._ssm["decay_mean"].labels(**lab).set(
                float(got[k]["decay_mean"]))
