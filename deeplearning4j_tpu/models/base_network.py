"""The training path both network containers share: everything between the
public ``fit()`` and the dispatch of a compiled step program.

TPU design: ONE jit-compiled pure train step per network — forward, loss,
``jax.grad`` backward, optax update, constraints — all fused by XLA into a
single device program (the reference runs a Java-side loop over layers with a
JNI call per op). Parameters/updater state are immutable pytrees; "mutation"
is rebinding, and buffers are donated so XLA updates in place.

A step program does not care whether its batch is an array or a list of
arrays: to ``jax.jit`` both are pytrees. So this module treats ``inputs``,
``labels``, ``masks`` and ``label_masks`` as opaque pytrees, and a container
(``MultiLayerNetwork``: a list of layers, trees that are lists by layer
index; ``ComputationGraph``: a DAG, trees that are dicts by node name)
supplies what differs by nature:

- ``_init_leaves(rng, dtype) -> (params, state)``: its layers' parameters
  and state, leaf by leaf, in its own tree form;
- ``_forward`` and ``_loss(params, state, inputs, labels, rng, masks,
  label_masks, carries=None) -> (loss, (new_state, new_carries))``, and
  ``_dp_loss``, which folds a pad mask into the label masks;
- ``_batch_parts(batch, asarray)``: a ``DataSet``, the container's own batch
  type or an ``(inputs, labels)`` pair as ``(inputs, labels, masks,
  label_masks)``, every array through ``asarray``;
- ``_layer(key)``: the layer whose parameters ``params[key]`` holds.
"""

from __future__ import annotations

import itertools
import json
import os
import time
from typing import Any, List

import numpy as np
import jax
import jax.numpy as jnp
import optax

from deeplearning4j_tpu.monitor import compile_ledger
from deeplearning4j_tpu.monitor.tracing import trace
from deeplearning4j_tpu.nn.layers.special import FrozenLayer
from deeplearning4j_tpu.nn.updaters import make_gradient_transform


def _dtype_of(name):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16, "float64": jnp.float64}[name]


def _by_key(tree):
    """A per-layer tree as ``{key: subtree}``: a list by layer index or a
    dict by node name."""
    return dict(enumerate(tree)) if isinstance(tree, list) else tree


def _like(tree, by_key):
    """``by_key`` back in the form of ``tree`` (see ``_by_key``)."""
    if isinstance(tree, list):
        return [by_key[i] for i in range(len(by_key))]
    return by_key


class BaseNetwork:
    _prog_ids = itertools.count()
    _prog_prefix = "net"    # a container's short name in the registry

    def __init__(self, conf):
        self.conf = conf
        self.params = None
        self.state = None
        self.opt_state = None
        self.listeners: List[Any] = []
        self.iteration = 0
        self.epoch = 0
        self._epoch_batch = 0         # batches consumed in the current epoch
                                      # (persisted in checkpoints → resume
                                      # restarts mid-epoch at the right batch)
        self._score = float("nan")
        self._last_input = None       # last fit batch (activation capture)
        self._rnn_carries = None      # stored state for rnn_time_step
        self._train_step_cache = {}
        self._scan_fit = None
        self._output_fn = None
        self._serving = None          # bucketed inference engine (lazy)
        self._transforms = None
        self._fused = None            # fused update plan (nn/fused_update.py)
        self._update_step = None      # standalone donated update program
        self._compile_count = 0       # train programs traced (see _note_compile)
        self._remat_kept = None       # remat='blocks': bytes kept, by name
        self._index_calls = None      # index-score calls traced, by form
        self._flight = None           # FlightRecorder (monitor/flight.py)
        self._train_mon = None        # lazy TrainMonitor (metric children)
        self._exec = None             # execution core (lazy; exec/executor.py)
        # per-instance caller id for the XLA program registry (/programs):
        # a rebuilt net gets fresh registry rows, never a stale hit
        self._prog_caller = f"{self._prog_prefix}{next(self._prog_ids)}"

    @property
    def _executor(self):
        """The execution core all compile sites build programs through
        (mesh placement, in/out shardings, donation — docs/SHARDING.md)."""
        if self._exec is None:
            from deeplearning4j_tpu.exec import get_executor
            self._exec = get_executor()
        return self._exec

    def init(self, rng=None):
        """Initialize parameters, state and the updater's state (parity:
        MultiLayerNetwork.init :541, ComputationGraph.init :370). Leaf by
        leaf, every ``jax.random`` and ``jnp`` call on a new shape a program
        of its own: the compile ledger counts them under ``init``, the
        wall seconds and the leaves made go to ``dl4jtpu_init_*``."""
        t0 = time.perf_counter()
        with compile_ledger.phase("init"), trace.span("init"):
            gc = self.conf.global_conf
            if rng is None:
                rng = jax.random.PRNGKey(gc.seed)
            self.params, self.state = self._init_leaves(
                rng, _dtype_of(gc.dtype))
            self._build_optimizer()
        self._mon.record_init(
            time.perf_counter() - t0,
            len(jax.tree_util.tree_leaves(
                (self.params, self.state, self.opt_state))))
        return self

    def _build_optimizer(self):
        from deeplearning4j_tpu.nn.fused_update import (build_fused_update,
                                                        fused_update_enabled)
        gc = self.conf.global_conf
        params = _by_key(self.params)
        transforms, group_keys = {}, {}
        for key, p in params.items():
            l = self._layer(key)
            if isinstance(l, FrozenLayer) or not p:
                transforms[key] = optax.set_to_zero()
                group_keys[key] = None
            else:
                upd = l.updater or gc.updater
                transforms[key] = make_gradient_transform(upd)
                group_keys[key] = json.dumps(upd.to_dict(), sort_keys=True)
        self._transforms = transforms
        self.opt_state = _like(self.params, {k: t.init(params[k])
                                             for k, t in transforms.items()})
        self._fused = None
        if fused_update_enabled():
            self._fused = build_fused_update(
                params, transforms, group_keys,
                {k: self._layer(k).apply_constraints for k in params})
        self._train_step_cache = {}   # force re-trace
        self._scan_fit = None
        self._output_fn = None
        self._serving = None
        self._update_step = None

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def attach_flight_recorder(self, recorder):
        """Attach (or detach, with None) a ``monitor.flight.FlightRecorder``.
        The train-step/fit_scan programs re-trace ONCE with the fused
        ``(L, 5)`` telemetry side-output (see monitor/flight.py); detached
        training stays byte-identical to today's path."""
        self._flight = recorder
        if recorder is not None:
            recorder.bind(self)
        self._train_step_cache = {}   # force re-trace with/without the
        self._scan_fit = None         # side-output
        return self

    def _compute_dtype(self, train):
        """The forward's compute dtype: the model's own ``compute_dtype``
        when configured, else the executor's train-precision policy (bf16
        compute, f32 accumulation — docs/TRAINING_PERF.md) on the fit path
        of f32 models. None means no cast. Read at trace time."""
        gc = self.conf.global_conf
        if gc.compute_dtype:
            return _dtype_of(gc.compute_dtype)
        if train:
            dt = self._executor.train_dtype
            if dt is not None and _dtype_of(gc.dtype) == jnp.float32:
                return dt
        return None

    def _normalize_grads(self, grads):
        from deeplearning4j_tpu.nn.updaters import normalize_layer_grad
        gc = self.conf.global_conf
        kind = gc.gradient_normalization
        if not kind or kind == "None":
            return grads
        thr = gc.gradient_normalization_threshold
        return _like(grads, {k: normalize_layer_grad(g, kind, thr)
                             for k, g in _by_key(grads).items()})

    # -------------------------------------------- data-parallel protocol
    # Uniform surface used by parallel.wrapper.ParallelWrapper so the wrapper
    # is model-agnostic (parity: reference ParallelWrapper.java:58 accepts any
    # Model): ``_dp_batch``, the container's ``_dp_loss``, ``_dp_apply_updates``.
    def _dp_batch(self, ds):
        """A batch as host arrays: ``(inputs, labels, masks, label_masks)``
        in the container's form (see ``_batch_parts``)."""
        return self._batch_parts(ds, np.asarray)

    @jax.named_scope("updater")
    def _dp_apply_updates(self, params, opt_state, grads, fused=None):
        """Normalize grads, run updaters, apply constraints. Default path:
        the fused flat program (nn/fused_update.py — bitwise-equal to the
        per-layer loop below, which remains as the parity oracle).
        Tensor-parallel callers pass ``fused=False``: raveling row- and
        column-sharded leaves into one vector would gather every shard (and
        trips a GSPMD mis-partition on mixed-axis concat) — the per-leaf
        loop keeps TP placement."""
        grads = self._normalize_grads(grads)
        if fused is None:
            fused = self._executor.model_size <= 1
        if fused and self._fused is not None:
            new_params, new_opt = self._fused.apply(
                _by_key(params), _by_key(opt_state), _by_key(grads))
            return _like(params, new_params), _like(params, new_opt)
        grads, opt_state = _by_key(grads), _by_key(opt_state)
        new_params, new_opt = {}, {}
        for key, p in _by_key(params).items():
            if not p:
                new_params[key], new_opt[key] = p, opt_state[key]
                continue
            u, o = self._transforms[key].update(grads[key], opt_state[key], p)
            p = optax.apply_updates(p, u)
            new_params[key] = self._layer(key).apply_constraints(p)
            new_opt[key] = o
        return _like(params, new_params), _like(params, new_opt)

    def _apply_updates_jitted(self):
        """The standalone grad→update→apply program: one compile per
        (model, updater), params + opt-state donated so XLA updates in
        place. External-gradient callers go through this instead of an
        eager per-leaf loop; it traces the same `_dp_apply_updates` math
        the train step embeds."""
        if self._update_step is None:
            def upd(params, opt_state, grads):
                self._note_compile()
                return self._dp_apply_updates(params, opt_state, grads)

            from deeplearning4j_tpu import exec as ex
            self._update_step = self._executor.jit(
                upd, in_specs=(ex.PARAMS, ex.OPT, ex.PARAMS),
                out_specs=(ex.PARAMS, ex.OPT), donate_argnums=(0, 1))
        return self._update_step

    def apply_external_updates(self, grads):
        """One updater step from externally-computed gradients via the
        donated fused-update program (registered as ``apply_updates`` in
        the /programs registry)."""
        step = self._apply_updates_jitted()
        c0, t0 = self._compile_count, time.perf_counter()
        self.params, self.opt_state = step(self.params, self.opt_state,
                                           grads)
        if self._compile_count > c0:
            self._executor.register_program(
                self._prog_caller, "apply_updates", step,
                (self.params, self.opt_state, grads),
                compile_seconds=time.perf_counter() - t0)
        return self

    def _note_compile(self):
        # called from inside jitted train-step bodies: runs only while jit
        # traces a NEW signature, i.e. exactly once per compiled program.
        # Program-registry introspection re-lowers the same body (exec/
        # programs.py) — that re-trace must not count as a fresh compile.
        from deeplearning4j_tpu.exec.programs import is_registering
        if is_registering():
            return
        self._compile_count += 1

    @property
    def _mon(self):
        if self._train_mon is None:
            from deeplearning4j_tpu.monitor.hooks import TrainMonitor
            self._train_mon = TrainMonitor(type(self).__name__)
        return self._train_mon

    # ----------------------------------------------------------- train step
    def _counting_index_calls(self):
        """Open around the trace of a step program's loss and gradient: the
        index-score calls it traces are counted by form, for the program's
        registry record."""
        from deeplearning4j_tpu.ops.index_scores import FORMS, counting_calls
        self._index_calls = dict.fromkeys(FORMS, 0)
        return counting_calls(self._index_calls)

    def _loss_for_grad(self):
        """The differentiated loss: jax.checkpoint-wrapped when remat is
        configured (recompute activations in the backward — faster AND
        smaller for HBM-bound conv models, see GlobalConf.remat); with
        ``'blocks'`` the checkpoints are inside a graph's ``_forward``."""
        from deeplearning4j_tpu.util.remat import remat_loss
        return remat_loss(self._loss, self.conf.global_conf.remat)

    def _make_train_step(self, with_carries=False):
        """The step program. ``with_carries``: the truncated-BPTT form,
        which takes the recurrent carries of the chunk before and returns
        this chunk's after the loss."""
        loss_fn = self._loss_for_grad()
        rec = self._flight           # captured at trace-build time: the
        # recorder-off program is byte-identical to the pre-flight path
        sample_k = rec.sample_every if rec is not None else 1

        def step(params, state, opt_state, inputs, labels, it, masks,
                 label_masks, carries=None):
            self._note_compile()
            rng = jax.random.fold_in(
                jax.random.PRNGKey(self.conf.global_conf.seed), it)
            with self._counting_index_calls():
                (loss, (new_state, new_carries)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, state, inputs, labels, rng,
                                           masks, label_masks, carries)
            new_params, new_opt = self._dp_apply_updates(params, opt_state, grads)
            out = (new_params, new_state, new_opt, loss)
            if with_carries:
                out = out + (new_carries,)
            if rec is None:
                return out
            from deeplearning4j_tpu.monitor import flight
            telem = flight.step_telemetry(
                flight.telemetry_triples(params, new_params, grads),
                it, sample_k)
            return out + (telem,)

        from deeplearning4j_tpu import exec as ex
        out_specs = (ex.PARAMS, ex.STATE, ex.OPT, ex.REPL)
        if with_carries:
            out_specs = out_specs + (ex.BATCH,)
        if rec is not None:
            out_specs = out_specs + (ex.AUX,)
        return self._executor.jit(
            step,
            in_specs=(ex.PARAMS, ex.STATE, ex.OPT, ex.BATCH, ex.BATCH,
                      ex.REPL, ex.BATCH, ex.BATCH, ex.BATCH),
            out_specs=out_specs,
            donate_argnums=(0, 1, 2))

    # ------------------------------------------------------------------- fit
    def fit_scan(self, xs, ys):
        """Device-resident training: run ``n`` train steps inside ONE
        compiled call (lax.scan over a leading step axis), eliminating
        per-step host dispatch — which dominates small-model training.

        ``xs``: (n_steps, batch, ...) features, ``ys``: (n_steps, batch, ...)
        labels, device-resident; for a graph, lists of such arrays (or
        single arrays for a single-input/-output graph). The reference has
        no equivalent (its fit loop dispatches per minibatch,
        MultiLayerNetwork.java:1204); this is the XLA-idiomatic fast path
        with identical per-step math."""
        with compile_ledger.phase("fit"):
            return self._fit_scan_impl(xs, ys)

    def _fit_scan_impl(self, xs, ys):
        if self.conf.backprop_type == "tbptt":
            raise ValueError(
                "fit_scan runs full-sequence backprop; a net configured for "
                "truncated BPTT must use fit() (the tbptt chunking path)")
        xs, ys = self._batch_parts((xs, ys), jnp.asarray)[:2]
        if self._scan_fit is None:
            loss_fn = self._loss_for_grad()
            rec = self._flight       # trace-build capture (see attach)
            sample_k = rec.sample_every if rec is not None else 1

            def inner(params, state, opt_state, xs, ys, it0):
                self._note_compile()

                def body(carry, inp):
                    params, state, opt_state, it = carry
                    x, y = inp
                    rng = jax.random.fold_in(
                        jax.random.PRNGKey(self.conf.global_conf.seed), it)
                    with self._counting_index_calls():
                        (loss, (new_state, _)), grads = jax.value_and_grad(
                            loss_fn, has_aux=True)(params, state, x, y, rng,
                                                   None, None)
                    new_params, opt_state = self._dp_apply_updates(
                        params, opt_state, grads)
                    if rec is None:
                        return (new_params, new_state, opt_state,
                                it + 1), loss
                    from deeplearning4j_tpu.monitor import flight
                    telem = flight.step_telemetry(
                        flight.telemetry_triples(params, new_params, grads),
                        it, sample_k)
                    return (new_params, new_state, opt_state, it + 1), \
                        (loss, telem)

                (p, s, o, _), out = jax.lax.scan(
                    body, (params, state, opt_state, it0), (xs, ys))
                if rec is None:
                    return p, s, o, out
                return p, s, o, out[0], out[1]

            from deeplearning4j_tpu import exec as ex
            out_specs = (ex.PARAMS, ex.STATE, ex.OPT, ex.REPL)
            if rec is not None:
                out_specs = out_specs + (ex.AUX,)
            self._scan_fit = self._executor.jit(
                inner,
                in_specs=(ex.PARAMS, ex.STATE, ex.OPT, ex.STEP_BATCH,
                          ex.STEP_BATCH, ex.REPL),
                out_specs=out_specs,
                donate_argnums=(0, 1, 2))
        c0, t0 = self._compile_count, time.perf_counter()
        m0 = compile_ledger.mark()
        out = self._scan_fit(
            self.params, self.state, self.opt_state, xs, ys,
            jnp.asarray(self.iteration, jnp.int32))
        self.params, self.state, self.opt_state, losses = out[:4]
        if self._flight is not None:
            self._flight.record_scan(self.iteration, out[4])
        # device ref for activation capture
        self._last_input = jax.tree_util.tree_map(lambda a: a[-1], xs)
        n_steps, batch = (int(d) for d in
                          jax.tree_util.tree_leaves(xs)[0].shape[:2])
        self.iteration += n_steps
        self._epoch_batch += n_steps
        self._score = losses[-1]
        self._mon.record(seconds=time.perf_counter() - t0, steps=n_steps,
                         examples=n_steps * batch, score=self._score,
                         compiled=self._compile_count - c0, path="scan")
        if self._compile_count > c0:
            # fresh XLA program: record its cost/memory analysis so /programs
            # and the bench MFU column read measured numbers, not estimates.
            # Lowering args are the donated call's OUTPUTS (same shapes).
            self._executor.register_program(
                self._prog_caller, f"fit_scan_k{n_steps}_b{batch}",
                self._scan_fit,
                (self.params, self.state, self.opt_state, xs, ys,
                 jnp.asarray(self.iteration, jnp.int32)),
                compile_seconds=time.perf_counter() - t0, scopes=True,
                remat_kept_bytes=self._remat_kept,
                index_scores_calls=self._index_calls,
                build=compile_ledger.since(m0))
        if self.listeners:
            with trace.span("callback"):
                for lst in self.listeners:
                    lst.iteration_done(self, self.iteration, self.epoch)
        return self

    def fit(self, data, labels=None, epochs=1, prefetch=None,
            checkpoint=None, resume_from=None):
        """fit(x, y) | fit(DataSet / MultiDataSet) | fit(iterator, epochs=N)
        (parity: MultiLayerNetwork.fit :1156, ComputationGraph.fit :863).

        Iterator batches are auto-chunked onto the device-resident scan
        path: runs of mask-free, same-shape batches are stacked and trained
        as ONE compiled multi-step call (``fit_scan``), so plain
        ``fit(iterator)`` gets the same dispatch amortization as callers
        who stage their data manually — per-minibatch host dispatch
        otherwise dominates small-model training. The per-step math and RNG streams are
        identical (both fold the iteration index into the seed); score
        listeners fire once per chunk instead of once per iteration.
        Masked, tBPTT, or shape-changing batches, and steps whose estimated
        work is heavy (util/chunking.py), fall back to single-step fits
        transparently.

        ``prefetch``: device-resident prefetch depth for the streamed path
        (see data/prefetcher.py) — staged work items are device_put ahead
        of consumption so the H2D transfer of chunk k+1 overlaps the step
        for chunk k. ``None`` uses the class default ``prefetch_depth``;
        ``0`` disables (naive path — same math, no overlap). Per-stage
        timing for the last epoch lands in ``self.last_pipeline_stats``.

        ``checkpoint``: crash-safe periodic saves for the duration of this
        call — a ``resilience.CheckpointListener``, or a directory path
        (defaults to save-every-epoch into it). ``resume_from``: a
        checkpoint zip or checkpoint directory (latest taken) — restores
        params/updater/iteration/epoch/epoch-position and continues the
        SAME run bitwise-identically: completed epochs are replayed
        through the iterator (reset + full consumption, so stateful
        shuffles land where the uninterrupted run left them) and the
        partial epoch skips the batches already trained. Requires
        resettable iterator data (docs/FAULT_TOLERANCE.md)."""
        from deeplearning4j_tpu.monitor.profiling import profile_scope

        # DL4JTPU_PROFILE=<dir> wraps the whole call in jax.profiler.trace
        # (docs/OBSERVABILITY.md); unset, this is a plain passthrough
        with profile_scope(), compile_ledger.phase("fit"):
            return self._fit_impl(data, labels, epochs, prefetch,
                                  checkpoint, resume_from)

    def _fit_impl(self, data, labels, epochs, prefetch, checkpoint,
                  resume_from):
        from deeplearning4j_tpu.data.dataset import DataSet, MultiDataSet

        ckpt = None
        if checkpoint is not None:
            from deeplearning4j_tpu.resilience.checkpoint import (
                CheckpointListener)
            ckpt = (checkpoint if isinstance(checkpoint, CheckpointListener)
                    else CheckpointListener(checkpoint, every_n_epochs=1))
            self.listeners.append(ckpt)
        try:
            if labels is not None or isinstance(data, (DataSet, MultiDataSet)):
                if resume_from is not None:
                    raise ValueError(
                        "resume_from needs resettable iterator data; a bare "
                        "array/DataSet fit has no epoch stream to replay")
                return self._fit_batch(data if labels is None
                                       else (data, labels))
            n_epochs, skip = epochs, 0
            if resume_from is not None:
                if not hasattr(data, "reset"):
                    raise ValueError(
                        "resume_from needs a resettable iterator (reset()) "
                        "to replay the stream to the crash position")
                skip = self._resume_training(resume_from, data)
                n_epochs = max(0, epochs - self.epoch)
            for k in range(n_epochs):
                if hasattr(data, "reset"):
                    data.reset()
                self._fit_stream(data, prefetch=prefetch,
                                 skip_batches=skip if k == 0 else 0)
                self.epoch += 1
                self._epoch_batch = 0
                for lst in self.listeners:
                    if hasattr(lst, "on_epoch_end"):
                        lst.on_epoch_end(self)
            return self
        finally:
            if ckpt is not None:
                self.listeners.remove(ckpt)

    def _resume_training(self, resume_from, data):
        """Restore from a checkpoint and wind the iterator forward to where
        the crashed run stood. Returns the number of batches to skip in the
        first (partial) epoch."""
        from deeplearning4j_tpu.resilience.checkpoint import latest_checkpoint
        from deeplearning4j_tpu.util.model_serializer import restore_into

        path = os.fspath(resume_from)
        if os.path.isdir(path):
            found = latest_checkpoint(path)
            if found is None:
                raise FileNotFoundError(
                    f"resume_from: no checkpoints in directory {path}")
            path = found
        restore_into(self, path)
        # replay completed epochs through the iterator: the uninterrupted
        # run did reset() (fit loop) + ONE iter() (_stream_chunks) + full
        # consumption per epoch — stateful iterators (advancing shuffle
        # RNGs, sampling) must see the identical call sequence to land in
        # the same state. NB `for _ in iter(data)` would call __iter__
        # twice (once explicitly, once by the for protocol) and de-sync a
        # reset-counting shuffle — drive next() by hand instead.
        for _ in range(self.epoch):
            data.reset()
            it = iter(data)
            while True:
                try:
                    next(it)
                except StopIteration:
                    break
        return self._epoch_batch

    # chunk cap: bounded host-side staging memory for the stacked block
    # (util/chunking.py sends a step of heavy estimated work singly)
    _CHUNK_MAX_STEPS = 64
    _CHUNK_MAX_BYTES = 256 << 20

    # device-resident prefetch depth for the streamed fit/eval path: work
    # items are device_put this many batches ahead of consumption so the
    # H2D copy of item k+1 overlaps the compiled step for item k
    # (data/prefetcher.py). 0 = naive path (same math, no overlap).
    prefetch_depth = 2
    # per-stage timing summary of the last streamed fit/eval epoch
    last_pipeline_stats = None

    def _resolve_device_pp(self, data):
        """Split a ``device_side`` pre-processor off the iterator chain:
        returns (dev_fn, host_pp). ``dev_fn`` is the jitted on-chip
        transform (raw — typically uint8 — batches travel host->device and
        the f32 cast/scale runs on chip, see data/normalizers.py);
        ``host_pp`` is the fallback when the transform is not expressible
        device-side (the iterator still emitted the batch raw)."""
        from deeplearning4j_tpu.data.iterators import resolve_pre_processor

        pp = resolve_pre_processor(data)
        dev_fn = host_pp = None
        if pp is not None and getattr(pp, "device_side", False):
            f = pp.as_device_transform()
            if f is not None:
                dev_fn = jax.jit(f)
            else:
                host_pp = pp      # device-side requested but not expressible
        return dev_fn, host_pp

    def _stream_chunks(self, data, host_pp, timer, skip_batches=0):
        """Host-side stage of the streamed fit pipeline: pull batches,
        stack runs of mask-free same-shape batches into scan chunks.
        Yields ``("chunk", (xs, ys))`` stacked host blocks (np arrays) or
        ``("batch", (inputs, labels, masks, label_masks))`` fallbacks, in
        base-iterator order — the chunk boundaries do not depend on
        prefetch depth, so the training math is bitwise-identical with
        prefetch on or off."""
        from deeplearning4j_tpu.util.chunking import (n_parameters,
                                                      steps_per_chunk)

        chunkable = self.conf.backprop_type != "tbptt"
        buf, shape = [], None
        n_params = n_parameters(self.params)

        def flush():
            nonlocal buf, shape
            out = None
            if len(buf) == 1:
                out = ("batch", buf[0])
            elif buf:
                with timer.stage("stack"):
                    out = ("chunk", jax.tree_util.tree_map(
                        lambda *steps: np.stack([np.asarray(a)
                                                 for a in steps]),
                        *[parts[:2] for parts in buf]))
            buf, shape = [], None
            return out

        it = iter(data)
        for _ in range(skip_batches):
            # resume path: these batches were already trained before the
            # crash — pull and drop them so the stream (and any iterator
            # RNG) advances exactly as it did in the uninterrupted run
            try:
                next(it)
            except StopIteration:
                return
        while True:
            t0 = time.perf_counter()
            try:
                with trace.span("fetch"):
                    batch = next(it)
            except StopIteration:
                break
            timer.add("fetch", time.perf_counter() - t0)
            parts = self._batch_parts(batch, lambda a: a)
            inputs, labels, masks, label_masks = parts
            if host_pp is not None:
                with timer.stage("decode"):
                    inputs = jax.tree_util.tree_map(
                        lambda f: host_pp.transform_features(np.asarray(f)),
                        inputs)
                    parts = (inputs,) + parts[1:]
            if not chunkable or masks is not None or label_masks is not None:
                out = flush()
                if out is not None:
                    yield out
                yield ("batch", parts)
                continue
            key = jax.tree_util.tree_map(np.shape, (inputs, labels))
            if shape is not None and key != shape:
                out = flush()
                if out is not None:
                    yield out
            shape = key
            buf.append(parts)
            if len(buf) >= steps_per_chunk(
                    jax.tree_util.tree_leaves(inputs),
                    jax.tree_util.tree_leaves(labels), n_params,
                    self._CHUNK_MAX_STEPS, self._CHUNK_MAX_BYTES):
                yield flush()
        out = flush()
        if out is not None:
            yield out

    def _stream_placement(self, item):
        """Where the step wants a ``_stream_chunks`` item, so that the
        prefetcher's copy lands there (split over the mesh's data axis
        when the step shards it) and not whole on the default device."""
        kind, payload = item
        return self._executor.batch_sharding(payload[:2],
                                             step_axis=kind == "chunk")

    def _fit_stream(self, data, prefetch=None, skip_batches=0):
        """One epoch over an iterator: host chunk assembly → device-resident
        prefetch → compiled steps. While the device executes chunk k, the
        prefetcher has already dispatched the H2D copy of chunk k+1 and the
        host is stacking chunk k+2 — the three pipeline stages overlap
        (the AsyncDataSetIterator adds a fourth: parallel decode).

        Per-stage timing lands in ``self.last_pipeline_stats``; its
        ``host_stall_frac`` is the fraction of epoch wall time the consumer
        loop spent blocked waiting on data."""
        from deeplearning4j_tpu.data.prefetcher import DevicePrefetcher
        from deeplearning4j_tpu.util.timing import PipelineTimer

        dev_fn, host_pp = self._resolve_device_pp(data)
        depth = self.prefetch_depth if prefetch is None else int(prefetch)
        timer = PipelineTimer()
        stream = self._stream_chunks(data, host_pp, timer,
                                     skip_batches=skip_batches)
        if depth > 0:
            stream = DevicePrefetcher(stream, depth=depth, timer=timer,
                                      device=self._stream_placement)
        it = iter(stream)
        it0 = self.iteration
        timer.start()
        while True:
            # one "train_step" span per consumer iteration: it nests the
            # wait (and the fetch/stack/h2d work inside it) + the dispatch
            with trace.step("train_step", self.iteration):
                with timer.stage("wait"):
                    try:
                        kind, payload = next(it)
                    except StopIteration:
                        break
                with timer.dispatch(lambda: self._score):
                    inputs, *rest = jax.tree_util.tree_map(jnp.asarray,
                                                           payload)
                    if dev_fn is not None:
                        # single batches must normalize too — the iterator
                        # intentionally emitted them raw for a device_side
                        # pp
                        inputs = jax.tree_util.tree_map(dev_fn, inputs)
                    if kind == "chunk":
                        self.fit_scan(inputs, *rest)
                    else:
                        self._fit_parts(inputs, *rest)
        timer.stop()
        timer.steps = self.iteration - it0
        self.last_pipeline_stats = timer.summary()
        timer.publish("fit")
        # an expert layer's tokens: the (batch, time) positions of a step
        shown = jax.tree_util.tree_leaves(self._last_input)
        layers = {k: self._layer(k) for k in _by_key(self.state)}
        self._mon.publish_expert_counters(
            layers, self.state,
            tokens=int(np.prod(shown[0].shape[:2])) if shown else 0)
        self._mon.publish_selection_counters(layers, self.state)
        self._mon.publish_ssm_counters(layers, self.state)

    def _fit_batch(self, batch):
        """One step on one batch: a ``DataSet``, the container's own batch
        type, or an ``(inputs, labels)`` pair."""
        return self._fit_parts(*self._batch_parts(batch, jnp.asarray))

    def _fit_parts(self, inputs, labels, masks, label_masks):
        self._last_input = inputs     # device ref for activation-capture
                                      # listeners (ConvolutionalIteration-
                                      # Listener)
        first = jax.tree_util.tree_leaves(inputs)[0]
        c0, t0 = self._compile_count, time.perf_counter()
        m0 = compile_ledger.mark()
        if self.conf.backprop_type == "tbptt" and first.ndim == 3:
            self._fit_tbptt(inputs, labels, masks, label_masks)
            self._last_fit_time = time.perf_counter() - t0
        else:
            key = (masks is not None, label_masks is not None)
            if key not in self._train_step_cache:
                self._train_step_cache[key] = self._make_train_step()
            step = self._train_step_cache[key]
            out = step(
                self.params, self.state, self.opt_state, inputs, labels,
                jnp.asarray(self.iteration, jnp.int32), masks, label_masks)
            self.params, self.state, self.opt_state, loss = out[:4]
            self._score = loss      # device scalar; host-read deferred to
                                    # get_score() (a read waits for the
                                    # step and stalls the dispatch queue)
            if self._flight is not None:
                self._flight.record(self.iteration, out[4])
            # taken before the registration below, whose second compile
            # is the record's own aot_seconds, not this call's
            self._last_fit_time = time.perf_counter() - t0
            if self._compile_count > c0:
                # fresh XLA program: expose its cost/memory analysis via the
                # registry (/programs). Donated inputs → lower with outputs.
                self._executor.register_program(
                    self._prog_caller, f"train_step_b{int(first.shape[0])}",
                    step,
                    (self.params, self.state, self.opt_state, inputs, labels,
                     jnp.asarray(self.iteration, jnp.int32), masks,
                     label_masks),
                    compile_seconds=self._last_fit_time, scopes=True,
                    remat_kept_bytes=self._remat_kept,
                    index_scores_calls=self._index_calls,
                    build=compile_ledger.since(m0))
        self.iteration += 1
        self._epoch_batch += 1
        self._mon.record(seconds=self._last_fit_time, steps=1,
                         examples=int(first.shape[0]), score=self._score,
                         compiled=self._compile_count - c0, path="batch")
        if self.listeners:
            with trace.span("callback"):
                for lst in self.listeners:
                    lst.iteration_done(self, self.iteration, self.epoch)
        return self

    def _fit_tbptt(self, inputs, labels, masks, label_masks):
        """Truncated BPTT: slice time into tbptt_fwd_length chunks, carrying
        RNN state across chunks (parity: MultiLayerNetwork.doTruncatedBPTT
        :1219, ComputationGraph.java:1617-1629). Truncation is structural:
        each chunk's step differentiates only through its own forward — the
        carried state enters as a plain (non-differentiated) argument, so
        no stop_gradient is needed."""
        T = jax.tree_util.tree_leaves(inputs)[0].shape[1]
        L = self.conf.tbptt_fwd_length
        if "tbptt" not in self._train_step_cache:
            self._train_step_cache["tbptt"] = self._make_train_step(
                with_carries=True)
        step = self._train_step_cache["tbptt"]
        # no carry yet, layer by layer
        carries = _like(self.params, dict.fromkeys(_by_key(self.params)))
        losses = []
        telem = None
        for start in range(0, T, L):
            sl = slice(start, start + L)

            def seq(a):
                return a[:, sl] if a.ndim == 3 else a

            def mask(m):
                return m[:, sl] if m.ndim >= 2 else m

            out = step(
                self.params, self.state, self.opt_state,
                jax.tree_util.tree_map(seq, inputs),
                jax.tree_util.tree_map(seq, labels),
                jnp.asarray(self.iteration, jnp.int32),
                jax.tree_util.tree_map(mask, masks),
                jax.tree_util.tree_map(mask, label_masks), carries)
            self.params, self.state, self.opt_state, loss, carries = out[:5]
            if self._flight is not None:
                telem = out[5]      # every chunk shares the iteration —
                                    # the LAST chunk's stats are the record
            losses.append(loss)
        self._score = jnp.mean(jnp.stack(losses))   # device-side mean
        if self._flight is not None and telem is not None:
            self._flight.record(self.iteration, telem)

    def get_score(self):
        self._score = float(self._score)   # cache: one host read (a sync),
        return self._score                 # not one per call

    # ------------------------------------------------------------- utilities
    def num_params(self):
        return sum(int(np.prod(a.shape)) for a in
                   jax.tree_util.tree_leaves(self.params))

    # persistence shortcut (full impl in util/model_serializer.py)
    def save(self, path, save_updater=True):
        from deeplearning4j_tpu.util.model_serializer import write_model
        write_model(self, path, save_updater)
