"""MultiLayerNetwork — the sequential network container.

Parity surface: reference nn/multilayer/MultiLayerNetwork.java (3,156 LoC):
``init`` (:541), ``fit`` (:1156), ``output`` (:1947), ``score``,
``computeGradientAndScore`` (:2206), truncated BPTT (:1219),
``rnnTimeStep`` (:2209 stored-state path), plus the Solver/updater loop
(optimize/Solver.java, BaseOptimizer.java:171).

TPU design: ONE jit-compiled pure train step per network — forward, loss,
``jax.grad`` backward, optax update, constraints — all fused by XLA into a
single device program (the reference runs a Java-side loop over layers with a
JNI call per op). Parameters/updater state are immutable pytrees; "mutation"
is rebinding, and buffers are donated so XLA updates in place.
"""

from __future__ import annotations

import itertools
import time
from typing import Optional, List, Any, Dict

import numpy as np
import jax
import jax.numpy as jnp
import optax

from deeplearning4j_tpu.monitor.tracing import trace
from deeplearning4j_tpu.nn.conf.configuration import MultiLayerConfiguration
from deeplearning4j_tpu.nn.updaters import make_gradient_transform
from deeplearning4j_tpu.nn.layers.special import FrozenLayer


def _dtype_of(name):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16, "float64": jnp.float64}[name]


from deeplearning4j_tpu.util.scopes import layer_scope
from deeplearning4j_tpu.util.dtypes import (cast_floats as _cast_floats,
                                             restore_dtypes as _restore_dtypes)


class MultiLayerNetwork:
    _prog_ids = itertools.count()

    def __init__(self, conf: MultiLayerConfiguration):
        conf.finalize()
        if conf.global_conf.remat == "blocks":
            # util/remat.py: blocks are runs of graph nodes named
            # '<block>.<node>'; here the mode would do nothing, silently
            raise ValueError(
                "remat='blocks' replays blocks of a ComputationGraph whose "
                "nodes are named '<block>.<node>'; a list of layers has "
                "none (use True, 'full' or 'save_convs')")
        self.conf = conf
        self.layers = conf.layers
        self.params: Optional[List[Dict]] = None
        self.state: Optional[List[Dict]] = None
        self.opt_state: Optional[List[Any]] = None
        self.listeners: List[Any] = []
        self.iteration = 0
        self.epoch = 0
        self._epoch_batch = 0         # batches consumed in the current epoch
                                      # (persisted in checkpoints → resume
                                      # restarts mid-epoch at the right batch)
        self._score = float("nan")
        self._last_input = None       # last fit batch (activation capture)
        self._rnn_carries = None      # stored state for rnn_time_step
        self._train_step = None
        self._train_step_seq = None
        self._scan_fit = None
        self._output_fn = None
        self._serving = None          # bucketed inference engine (lazy)
        self._transforms = None
        self._fused = None            # fused update plan (nn/fused_update.py)
        self._update_step = None      # standalone donated update program
        self._compile_count = 0       # train programs traced (see _note_compile)
        self._flight = None           # FlightRecorder (monitor/flight.py)
        self._train_mon = None        # lazy TrainMonitor (metric children)
        self._exec = None             # execution core (lazy; exec/executor.py)
        # per-instance caller id for the XLA program registry (/programs):
        # a rebuilt net gets fresh registry rows, never a stale hit
        self._prog_caller = f"mln{next(MultiLayerNetwork._prog_ids)}"

    @property
    def _executor(self):
        """The execution core all compile sites build programs through
        (mesh placement, in/out shardings, donation — docs/SHARDING.md)."""
        if self._exec is None:
            from deeplearning4j_tpu.exec import get_executor
            self._exec = get_executor()
        return self._exec

    # ------------------------------------------------------------------ init
    def init(self, rng=None):
        """Initialize parameters (parity: MultiLayerNetwork.init :541)."""
        gc = self.conf.global_conf
        dtype = _dtype_of(gc.dtype)
        if rng is None:
            rng = jax.random.PRNGKey(gc.seed)
        keys = jax.random.split(rng, max(len(self.layers), 1))
        self.params = [l.init(k, dtype) for l, k in zip(self.layers, keys)]
        self.state = [l.init_state(dtype) for l in self.layers]
        self._build_optimizer()
        return self

    def _build_optimizer(self):
        import json
        from deeplearning4j_tpu.nn.fused_update import (build_fused_update,
                                                        fused_update_enabled)
        gc = self.conf.global_conf
        self._transforms = []
        group_keys = {}
        for i, (l, p) in enumerate(zip(self.layers, self.params)):
            upd = l.updater or gc.updater
            if isinstance(l, FrozenLayer) or not p:
                self._transforms.append(optax.set_to_zero())
                group_keys[i] = None
            else:
                self._transforms.append(make_gradient_transform(upd))
                group_keys[i] = json.dumps(upd.to_dict(), sort_keys=True)
        self.opt_state = [t.init(p) for t, p in zip(self._transforms, self.params)]
        self._fused = None
        if fused_update_enabled():
            self._fused = build_fused_update(
                dict(enumerate(self.params)),
                dict(enumerate(self._transforms)), group_keys,
                {i: l.apply_constraints
                 for i, l in enumerate(self.layers)})
        self._train_step = None  # force re-trace
        self._scan_fit = None
        self._output_fn = None
        self._serving = None
        self._update_step = None

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def add_listeners(self, *listeners):
        self.listeners.extend(listeners)
        return self

    def attach_flight_recorder(self, recorder):
        """Attach (or detach, with None) a ``monitor.flight.FlightRecorder``.
        The train-step/fit_scan programs re-trace ONCE with the fused
        ``(L, 5)`` telemetry side-output (see monitor/flight.py); detached
        training stays byte-identical to today's path."""
        self._flight = recorder
        if recorder is not None:
            recorder.bind(self)
        self._train_step = None       # force re-trace with/without the
        self._scan_fit = None         # side-output
        return self

    # ----------------------------------------------------------- forward core
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

    def _forward(self, params, state, x, *, train, rng, mask=None, carries=None,
                 upto=None):
        """Pure forward through layers [0, upto). Returns (act, new_states,
        new_carries)."""
        gc = self.conf.global_conf
        cdt = self._compute_dtype(train)
        if cdt is not None:
            if jnp.issubdtype(x.dtype, jnp.floating):
                x = x.astype(cdt)       # token ids stay integers
            params = _cast_floats(params, cdt)
        n = len(self.layers) if upto is None else upto
        new_states = list(state)
        new_carries = list(carries) if carries is not None else None
        i = 0
        while i < n:
            l = self.layers[i]
            lrng = None if rng is None else jax.random.fold_in(rng, i)
            # consecutive stacked LSTMs fuse into ONE wavefront kernel (the
            # cuDNN numLayers=2 schedule — see ops/lstm_pallas.py); the
            # stateful-carry path (rnn_time_step) stays per-layer
            if (new_carries is None and i + 1 < n and x.ndim == 3):
                from deeplearning4j_tpu.nn.layers.rnn import (
                    lstm_pair_fusable, apply_lstm_pair)
                if lstm_pair_fusable(l, self.layers[i + 1], params[i],
                                     params[i + 1], x, mask):
                    with layer_scope(l.name or f"layer{i}", l):
                        x = apply_lstm_pair(l, self.layers[i + 1],
                                            params[i], params[i + 1], x,
                                            train=train, rng=lrng)
                    i += 2
                    continue
            p_i = params[i]
            with layer_scope(l.name or f"layer{i}", l):
                if train and l.weight_noise is not None and lrng is not None:
                    p_i = l.weight_noise.apply(
                        p_i, jax.random.fold_in(lrng, 0x5eed))
                if new_carries is not None and hasattr(l, "apply_with_carry"):
                    x, c = l.apply_with_carry(p_i, x, new_carries[i],
                                              mask=mask)
                    new_carries[i] = c
                else:
                    x, st = l.apply(p_i, x, state[i], train=train, rng=lrng,
                                    mask=mask)
                    new_states[i] = st if st is not None else state[i]
            if x.ndim == 2:
                mask = None  # sequence collapsed to per-example
            i += 1
        if cdt is not None:
            # keep persistent layer state (e.g. BN running stats) at its
            # storage dtype so dtypes are stable across steps
            new_states = _restore_dtypes(new_states, list(state))
        return x, new_states, new_carries

    def _loss(self, params, state, x, y, rng, mask_f, mask_l, carries=None):
        gc = self.conf.global_conf
        out_layer = self.layers[-1]
        if not hasattr(out_layer, "compute_score"):
            raise ValueError(
                f"Last layer {type(out_layer).__name__} has no loss; use an "
                "OutputLayer/LossLayer variant")
        with jax.named_scope("forward"):
            act, new_states, new_carries = self._forward(
                params, state, x, train=True, rng=rng, mask=mask_f,
                carries=carries, upto=len(self.layers) - 1)
        with jax.named_scope("loss"):
            lrng = (None if rng is None
                    else jax.random.fold_in(rng, len(self.layers) - 1))
            p_out = params[-1]
            if out_layer.weight_noise is not None and lrng is not None:
                p_out = out_layer.weight_noise.apply(
                    p_out, jax.random.fold_in(lrng, 0x5eed))
            loss = out_layer.compute_score(p_out, act, y, mask_l,
                                           train=True, rng=lrng)
            reg = 0.0
            for l, p in zip(self.layers, params):
                reg = reg + l.reg_loss(p)
            loss = loss + reg
            if self._compute_dtype(True) is not None:
                loss = loss.astype(jnp.float32)
        return loss, (new_states, new_carries)

    def _normalize_grads(self, grads):
        from deeplearning4j_tpu.nn.updaters import normalize_layer_grad
        gc = self.conf.global_conf
        kind = gc.gradient_normalization
        if not kind or kind == "None":
            return grads
        thr = gc.gradient_normalization_threshold
        return [normalize_layer_grad(g, kind, thr) for g in grads]

    # -------------------------------------------- data-parallel protocol
    # Uniform surface used by parallel.wrapper.ParallelWrapper so the wrapper
    # is model-agnostic (parity: reference ParallelWrapper.java:58 accepts any
    # Model). ComputationGraph implements the same three methods.
    def _dp_batch(self, ds):
        """DataSet → canonical (x, y, features_mask, labels_mask)."""
        return (np.asarray(ds.features), np.asarray(ds.labels),
                None if ds.features_mask is None else np.asarray(ds.features_mask),
                None if ds.labels_mask is None else np.asarray(ds.labels_mask))

    def _dp_loss(self, params, state, x, y, rng, pad_mask=None, mf=None,
                 ml=None):
        """Loss with optional per-example zero-weighting of padded rows,
        combined with the DataSet's own masks. pad_mask: (B,) float,
        1=real row / 0=pad. Returns (loss, new_state)."""
        if pad_mask is not None:
            pm = (jnp.broadcast_to(pad_mask[:, None], y.shape[:2])
                  if y.ndim == 3 else pad_mask)
            ml = pm if ml is None else ml * pm
        loss, (new_state, _) = self._loss(params, state, x, y, rng, mf, ml)
        return loss, new_state

    @jax.named_scope("updater")
    def _dp_apply_updates(self, params, opt_state, grads, fused=None):
        """Normalize grads, run updaters, apply constraints. Default path:
        the fused flat program (nn/fused_update.py — bitwise-equal to the
        per-layer loop below, which remains as the DL4JTPU_FUSED_UPDATE=0
        fallback and the parity oracle). Tensor-parallel callers pass
        ``fused=False``: raveling row- and column-sharded leaves into one
        vector would gather every shard (and trips a GSPMD mis-partition
        on mixed-axis concat) — the per-leaf loop keeps TP placement."""
        grads = self._normalize_grads(grads)
        if fused is None:
            fused = self._executor.model_size <= 1
        if fused and self._fused is not None:
            n = len(params)
            pd, od = self._fused.apply(dict(enumerate(params)),
                                       dict(enumerate(opt_state)),
                                       dict(enumerate(grads)))
            return [pd[i] for i in range(n)], [od[i] for i in range(n)]
        new_params, new_opt = [], []
        for i, (l, t) in enumerate(zip(self.layers, self._transforms)):
            if not params[i]:
                new_params.append(params[i])
                new_opt.append(opt_state[i])
                continue
            u, o = t.update(grads[i], opt_state[i], params[i])
            p = optax.apply_updates(params[i], u)
            new_params.append(l.apply_constraints(p))
            new_opt.append(o)
        return new_params, new_opt

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
    def _loss_for_grad(self):
        """The differentiated loss: jax.checkpoint-wrapped when remat is
        configured (recompute activations in the backward — faster AND
        smaller for HBM-bound conv models, see GlobalConf.remat)."""
        from deeplearning4j_tpu.util.remat import remat_loss
        return remat_loss(self._loss, self.conf.global_conf.remat)

    def _make_train_step(self, with_masks, with_carries):
        loss_fn = self._loss_for_grad()
        rec = self._flight           # captured at trace-build time: the
        # recorder-off program is byte-identical to the pre-flight path
        sample_k = rec.sample_every if rec is not None else 1

        def step(params, state, opt_state, x, y, it, mask_f, mask_l, carries):
            self._note_compile()
            rng = jax.random.fold_in(
                jax.random.PRNGKey(self.conf.global_conf.seed), it)
            (loss, (new_state, new_carries)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, state, x, y, rng,
                                       mask_f, mask_l, carries)
            new_params, new_opt = self._dp_apply_updates(params, opt_state, grads)
            if rec is None:
                return new_params, new_state, new_opt, loss, new_carries
            from deeplearning4j_tpu.monitor import flight
            telem = flight.step_telemetry(
                flight.telemetry_triples(params, new_params, grads),
                it, sample_k)
            return new_params, new_state, new_opt, loss, new_carries, telem

        from deeplearning4j_tpu import exec as ex
        out_specs = (ex.PARAMS, ex.STATE, ex.OPT, ex.REPL, ex.BATCH)
        if rec is not None:
            out_specs = out_specs + (ex.AUX,)
        return self._executor.jit(
            step,
            in_specs=(ex.PARAMS, ex.STATE, ex.OPT, ex.BATCH, ex.BATCH,
                      ex.REPL, ex.BATCH, ex.BATCH, ex.BATCH),
            out_specs=out_specs,
            donate_argnums=(0, 1, 2))

    def _get_train_step(self, with_masks, with_carries):
        key = (with_masks, with_carries)
        if self._train_step is None:
            self._train_step = {}
        if key not in self._train_step:
            self._train_step[key] = self._make_train_step(*key)
        return self._train_step[key]

    # ------------------------------------------------------------------- fit
    def fit_scan(self, xs, ys):
        """Device-resident training: run ``xs.shape[0]`` train steps inside
        ONE compiled call (lax.scan over a leading step axis), eliminating
        per-step host dispatch — which dominates small-model training.

        ``xs``: (n_steps, batch, ...) features, ``ys``: (n_steps, batch, ...)
        labels, both device-resident. The reference has no equivalent (its
        fit loop dispatches per minibatch, MultiLayerNetwork.java:1204); this
        is the XLA-idiomatic fast path with identical per-step math."""
        if self.conf.backprop_type == "tbptt":
            raise ValueError(
                "fit_scan runs full-sequence backprop; a net configured for "
                "truncated BPTT must use fit() (the tbptt chunking path)")
        xs, ys = jnp.asarray(xs), jnp.asarray(ys)
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
                    (loss, (new_state, _)), grads = jax.value_and_grad(
                        loss_fn, has_aux=True)(params, state, x, y, rng,
                                               None, None, None)
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
        if self._flight is not None:
            (self.params, self.state, self.opt_state, losses,
             telems) = self._scan_fit(
                self.params, self.state, self.opt_state, xs, ys,
                jnp.asarray(self.iteration, jnp.int32))
            self._flight.record_scan(self.iteration, telems)
        else:
            self.params, self.state, self.opt_state, losses = self._scan_fit(
                self.params, self.state, self.opt_state, xs, ys,
                jnp.asarray(self.iteration, jnp.int32))
        self._last_input = xs[-1]     # device ref for activation capture
        self.iteration += int(xs.shape[0])
        self._epoch_batch += int(xs.shape[0])
        self._score = losses[-1]
        self._mon.record(seconds=time.perf_counter() - t0,
                         steps=int(xs.shape[0]),
                         examples=int(xs.shape[0]) * int(xs.shape[1]),
                         score=self._score,
                         compiled=self._compile_count - c0, path="scan")
        if self._compile_count > c0:
            # fresh XLA program: record its cost/memory analysis so /programs
            # and the bench MFU column read measured numbers, not estimates.
            # Lowering args are the donated call's OUTPUTS (same shapes).
            self._executor.register_program(
                self._prog_caller,
                f"fit_scan_k{int(xs.shape[0])}_b{int(xs.shape[1])}",
                self._scan_fit,
                (self.params, self.state, self.opt_state, xs, ys,
                 jnp.asarray(self.iteration, jnp.int32)),
                compile_seconds=time.perf_counter() - t0, scopes=True)
        if self.listeners:
            with trace.span("callback"):
                for lst in self.listeners:
                    lst.iteration_done(self, self.iteration, self.epoch)
        return self

    def fit(self, data, labels=None, epochs=1, prefetch=None,
            checkpoint=None, resume_from=None):
        """fit(x, y) | fit(DataSet) | fit(iterator, epochs=N)
        (parity: MultiLayerNetwork.fit :1156).

        Iterator batches are auto-chunked onto the device-resident scan
        path: runs of mask-free, same-shape batches are stacked and trained
        as ONE compiled multi-step call (``fit_scan``), so plain
        ``fit(iterator)`` gets the same dispatch amortization as callers
        who stage their data manually — per-minibatch host dispatch
        otherwise dominates small-model training. The per-step math and RNG streams are
        identical (both fold the iteration index into the seed); score
        listeners fire once per chunk instead of once per iteration.
        Masked, tBPTT, or shape-changing batches fall back to single-step
        fits transparently.

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
        with profile_scope():
            return self._fit_impl(data, labels, epochs, prefetch,
                                  checkpoint, resume_from)

    def _fit_impl(self, data, labels, epochs, prefetch, checkpoint,
                  resume_from):
        from deeplearning4j_tpu.data.dataset import DataSet

        ckpt = None
        if checkpoint is not None:
            from deeplearning4j_tpu.resilience.checkpoint import (
                CheckpointListener)
            ckpt = (checkpoint if isinstance(checkpoint, CheckpointListener)
                    else CheckpointListener(checkpoint, every_n_epochs=1))
            self.listeners.append(ckpt)
        try:
            if labels is not None or isinstance(data, DataSet):
                if resume_from is not None:
                    raise ValueError(
                        "resume_from needs resettable iterator data; a bare "
                        "array/DataSet fit has no epoch stream to replay")
                return self._fit_batch(data if labels is None
                                       else DataSet(data, labels))
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
        import os as _os
        from deeplearning4j_tpu.resilience.checkpoint import latest_checkpoint
        from deeplearning4j_tpu.util.model_serializer import restore_into

        path = _os.fspath(resume_from)
        if _os.path.isdir(path):
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
    _CHUNK_MAX_STEPS = 64
    _CHUNK_MAX_BYTES = 256 << 20

    def _chunk_len(self, ds):
        """util/chunking.py: bounded by steps and by staged bytes; one where
        the step's estimated work is heavy."""
        from deeplearning4j_tpu.util.chunking import (n_parameters,
                                                      steps_per_chunk)
        return steps_per_chunk([ds.features], [ds.labels],
                               n_parameters(self.params),
                               self._CHUNK_MAX_STEPS, self._CHUNK_MAX_BYTES)

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
        ``("batch", DataSet)`` fallbacks, in base-iterator order — the
        chunk boundaries do not depend on prefetch depth, so the training
        math is bitwise-identical with prefetch on or off."""
        from deeplearning4j_tpu.data.dataset import DataSet

        chunkable = self.conf.backprop_type != "tbptt"
        buf, shape = [], None

        def flush():
            nonlocal buf, shape
            out = None
            if len(buf) == 1:
                out = ("batch", buf[0])
            elif buf:
                with timer.stage("stack"):
                    out = ("chunk", (
                        np.stack([np.asarray(d.features) for d in buf]),
                        np.stack([np.asarray(d.labels) for d in buf])))
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
            ds = batch if isinstance(batch, DataSet) else DataSet(*batch)
            if host_pp is not None:
                with timer.stage("decode"):
                    ds = host_pp.pre_process(ds)
            if (not chunkable or ds.features_mask is not None
                    or ds.labels_mask is not None):
                out = flush()
                if out is not None:
                    yield out
                yield ("batch", ds)
                continue
            key = (ds.features.shape, ds.labels.shape)
            if shape is not None and key != shape:
                out = flush()
                if out is not None:
                    yield out
            shape = key
            buf.append(ds)
            if len(buf) >= self._chunk_len(ds):
                yield flush()
        out = flush()
        if out is not None:
            yield out

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
                    if kind == "chunk":
                        xs, ys = payload
                        xs = jnp.asarray(xs)
                        if dev_fn is not None:
                            xs = dev_fn(xs)
                        self.fit_scan(xs, ys)
                    else:
                        # the fallback path must normalize too — the
                        # iterator intentionally emitted this batch raw
                        # for a device_side pp
                        self._fit_batch(self._apply_dev_pp(payload, dev_fn))
        timer.stop()
        timer.steps = self.iteration - it0
        self.last_pipeline_stats = timer.summary()
        timer.publish("fit")
        self._mon.publish_expert_counters(
            {i: l for i, l in enumerate(self.layers)}, self.state)

    def _stream_placement(self, item):
        """Where the step wants a ``_stream_chunks`` item, so that the
        prefetcher's copy lands there (split over the mesh's data axis
        when the step shards it) and not whole on the default device."""
        kind, payload = item
        if kind == "chunk":
            return self._executor.batch_sharding(payload, step_axis=True)
        return self._executor.batch_sharding(
            (payload.features, payload.labels))

    @staticmethod
    def _apply_dev_pp(ds, dev_fn):
        if dev_fn is None:
            return ds
        from deeplearning4j_tpu.data.dataset import DataSet
        return DataSet(dev_fn(jnp.asarray(ds.features)),
                       ds.labels, ds.features_mask, ds.labels_mask)

    def _fit_batch(self, ds):
        gc = self.conf.global_conf
        x = jnp.asarray(ds.features)
        y = jnp.asarray(ds.labels)
        mf = None if ds.features_mask is None else jnp.asarray(ds.features_mask)
        ml = None if ds.labels_mask is None else jnp.asarray(ds.labels_mask)
        self._last_input = x          # device ref for activation-capture
        c0 = self._compile_count      # listeners (ConvolutionalIteration-
        t0 = time.perf_counter()      # Listener)
        if self.conf.backprop_type == "tbptt" and x.ndim == 3:
            self._fit_tbptt(x, y, mf, ml)
        else:
            step = self._get_train_step(mf is not None or ml is not None, False)
            out = step(
                self.params, self.state, self.opt_state, x, y,
                jnp.asarray(self.iteration, jnp.int32), mf, ml, None)
            self.params, self.state, self.opt_state, loss = out[:4]
            self._score = loss      # device scalar; host-read deferred to
                                    # get_score() (a read waits for the
                                    # step and stalls the dispatch queue)
            if self._flight is not None:
                self._flight.record(self.iteration, out[5])
        self._last_fit_time = time.perf_counter() - t0
        self.iteration += 1
        self._epoch_batch += 1
        self._mon.record(seconds=self._last_fit_time, steps=1,
                         examples=int(x.shape[0]), score=self._score,
                         compiled=self._compile_count - c0, path="batch")
        if self.listeners:
            with trace.span("callback"):
                for lst in self.listeners:
                    lst.iteration_done(self, self.iteration, self.epoch)
        return self

    # -------------------------------------------------------------- pretrain
    def pretrain(self, data, epochs: int = 1, lr: float = 0.01):
        """Greedy unsupervised layerwise pretraining of RBM/AutoEncoder/VAE
        layers (parity: MultiLayerNetwork.pretrain :1172 — called before
        supervised fit). ``data``: iterator of DataSets (features used)."""
        from deeplearning4j_tpu.nn.layers.pretrain import get_pretrain_step
        from deeplearning4j_tpu.data.dataset import DataSet

        # a plain generator would be exhausted after the first (layer, epoch)
        # pass — materialize anything we can't reset()
        if not isinstance(data, DataSet) and not hasattr(data, "reset"):
            data = list(data)
        for i, layer in enumerate(self.layers):
            step = get_pretrain_step(layer)
            if step is None:
                continue
            jit_step = jax.jit(step)

            def featurize(x):
                act, _, _ = self._forward(self.params, self.state,
                                          jnp.asarray(x), train=False,
                                          rng=None, upto=i)
                return act

            feat_fn = jax.jit(featurize)
            for ep in range(epochs):
                if hasattr(data, "reset"):
                    data.reset()
                for j, ds in enumerate(data if not isinstance(data, DataSet)
                                       else [data]):
                    if not isinstance(ds, DataSet):
                        ds = DataSet(*ds)
                    x = feat_fn(ds.features)
                    if x.ndim > 2:
                        x = x.reshape(x.shape[0], -1)
                    rng = jax.random.fold_in(
                        jax.random.PRNGKey(self.conf.global_conf.seed),
                        i * 100003 + ep * 1009 + j)
                    self.params[i], loss = jit_step(self.params[i], x, rng,
                                                    jnp.asarray(lr))
                    self._score = loss
        return self

    def _fit_tbptt(self, x, y, mf, ml):
        """Truncated BPTT: slice time into tbptt_fwd_length chunks, carrying
        RNN state across chunks (parity: MultiLayerNetwork.doTruncatedBPTT
        :1219). Truncation is structural: each chunk's step differentiates
        only through its own forward — the carried state enters as a plain
        argument, so no stop_gradient is needed."""
        T = x.shape[1]
        L = self.conf.tbptt_fwd_length
        carries = [None] * len(self.layers)
        step = self._get_train_step(mf is not None or ml is not None, True)
        losses = []
        telem = None
        for start in range(0, T, L):
            xs = x[:, start:start + L]
            ys = y[:, start:start + L] if y.ndim == 3 else y
            mfs = None if mf is None else mf[:, start:start + L]
            mls = None if ml is None else ml[:, start:start + L]
            out = step(
                self.params, self.state, self.opt_state, xs, ys,
                jnp.asarray(self.iteration, jnp.int32), mfs, mls, carries)
            self.params, self.state, self.opt_state, loss, carries = out[:5]
            if self._flight is not None:
                telem = out[5]      # every chunk shares the iteration —
                                    # the LAST chunk's stats are the record
            losses.append(loss)
        self._score = jnp.mean(jnp.stack(losses))   # device-side mean
        if self._flight is not None and telem is not None:
            self._flight.record(self.iteration, telem)

    # ------------------------------------------------------------- inference
    def serving_engine(self, **kw):
        """The shape-bucketed inference engine for this net (lazy, shared by
        ``output``/``evaluate``; see serving/engine.py). Keyword args are
        honored on first construction only."""
        if self._serving is None:
            from deeplearning4j_tpu.serving.engine import InferenceEngine
            self._serving = InferenceEngine(self, **kw)
        return self._serving

    def output(self, x, train=False, mask=None, bucketed=True):
        """Forward pass to network output (parity: output :1947).

        Default fast path is shape-BUCKETED: the batch is zero-padded up to
        a power-of-two bucket so ⌈log2(max_batch)⌉+1 compiled programs cover
        every request size (each fresh compile is seconds to minutes on a TPU
        attachments), with pad rows sliced off after the device call —
        numerically identical because inference computes every output row
        from its own input row alone. ``bucketed=False`` forces the legacy
        exact-shape program (one compile per distinct batch size)."""
        x = jnp.asarray(x)
        if bucketed:
            return self.serving_engine().predict(
                x, None if mask is None else jnp.asarray(mask))
        if self._output_fn is None:
            def fwd(params, state, x, mask):
                act, _, _ = self._forward(params, state, x, train=False,
                                          rng=None, mask=mask)
                return act
            from deeplearning4j_tpu import exec as ex
            self._output_fn = self._executor.jit(
                fwd, in_specs=(ex.PARAMS, ex.STATE, ex.BATCH, ex.BATCH),
                out_specs=(ex.BATCH,))
        return self._output_fn(self.params, self.state, x,
                               None if mask is None else jnp.asarray(mask))

    def feed_forward(self, x, train=False):
        """All layer activations (parity: feedForward :852)."""
        x = jnp.asarray(x)
        acts = [x]
        state = self.state
        for i, l in enumerate(self.layers):
            x, st = l.apply(self.params[i], x, state[i], train=train, rng=None)
            acts.append(x)
        return acts

    def score(self, ds=None, x=None, y=None):
        """Loss on a dataset (parity: MultiLayerNetwork.score)."""
        if ds is not None:
            x, y = ds.features, ds.labels
            mf = ds.features_mask
            ml = ds.labels_mask
        else:
            mf = ml = None
        loss, _ = self._loss(self.params, self.state, jnp.asarray(x),
                             jnp.asarray(y), None,
                             None if mf is None else jnp.asarray(mf),
                             None if ml is None else jnp.asarray(ml))
        return float(loss)

    def get_score(self):
        self._score = float(self._score)   # cache: one host read (a sync),
        return self._score                 # not one per call

    # ------------------------------------------------------------------ rnn
    def rnn_time_step(self, x):
        """Stateful single/multi-step inference (parity: rnnTimeStep :2362 in
        ComputationGraph / MultiLayerNetwork.java:2209)."""
        x = jnp.asarray(x)
        if x.ndim == 2:
            x = x[:, None, :]
        if self._rnn_carries is None:
            self._rnn_carries = [None] * len(self.layers)
        act, _, self._rnn_carries = self._forward(
            self.params, self.state, x, train=False, rng=None,
            carries=self._rnn_carries)
        return act

    def rnn_clear_previous_state(self):
        self._rnn_carries = None

    # --------------------------------------------------- incremental decode
    def init_decode_state(self, batch: int, max_len: int = 256, kv=None):
        """Per-layer decode state for ``batch`` concurrent streams of up to
        ``max_len`` tokens (serving/decode.py keeps this tree resident on
        device). Recurrent layers contribute their (h, c) carry; attention
        a fixed-capacity KV cache; stateless layers None. ``kv`` — a
        ``{"num_blocks": N, "block_size": bs}`` dict — switches attention
        to the shared block-pool layout (serving/kv/) instead of dense
        per-slot strips."""
        gc = self.conf.global_conf
        dt = _dtype_of(gc.compute_dtype or gc.dtype)
        if kv is not None:
            return [l.init_paged_decode_state(p, batch, max_len,
                                              kv["num_blocks"],
                                              kv["block_size"], dt)
                    for l, p in zip(self.layers, self.params)]
        return [l.init_decode_state(p, batch, max_len, dt)
                for l, p in zip(self.layers, self.params)]

    def decode_step(self, params, state, dstate, x_t, pos,
                    block_tables=None):
        """Pure one-token step through the stack: ``x_t`` (B, 1, F) input
        slice, ``pos`` (B,) int32 per-stream position. Returns
        ``(y, new_dstate)`` — bitwise-equal to position ``pos`` of a full
        teacher-forced ``_forward`` on the same prefix (the compute-dtype
        cast mirrors ``_forward`` exactly so bf16 nets stay bit-identical).
        ``block_tables`` (B, max_blocks) routes attention through the
        paged-KV path; the dense path is byte-identical without it."""
        gc = self.conf.global_conf
        if gc.compute_dtype:
            cdt = _dtype_of(gc.compute_dtype)
            x_t = x_t.astype(cdt)
            params = _cast_floats(params, cdt)
        x = x_t
        new_d = list(dstate)
        for i, l in enumerate(self.layers):
            st = state[i] if state else None
            if block_tables is None:
                x, new_d[i] = l.decode_step(params[i], dstate[i], x, pos,
                                            state=st)
            else:
                x, new_d[i] = l.decode_step_paged(params[i], dstate[i], x,
                                                  pos, block_tables,
                                                  state=st)
        return x, new_d

    def prefill_chunk(self, params, state, dstate, x, start, n,
                      block_tables=None, carry_stack=False):
        """Advance a prefill chunk through the stack: ``x`` (B, K, F)
        activations for positions ``start .. start+K-1`` per stream, ``n``
        (B,) valid rows (see Layer.prefill_chunk). Same compute-dtype
        handling as ``decode_step``. ``carry_stack=True`` additionally
        returns a per-layer list of carry snapshot stacks (None where the
        layer keeps no carry) for speculative rewind (serving/spec/)."""
        gc = self.conf.global_conf
        if gc.compute_dtype:
            cdt = _dtype_of(gc.compute_dtype)
            x = x.astype(cdt)
            params = _cast_floats(params, cdt)
        new_d = list(dstate)
        stacks = [None] * len(self.layers)
        for i, l in enumerate(self.layers):
            st = state[i] if state else None
            if carry_stack:
                x, new_d[i], stacks[i] = l.prefill_chunk(
                    params[i], dstate[i], x, start, n, state=st,
                    block_tables=block_tables, carry_stack=True)
            else:
                x, new_d[i] = l.prefill_chunk(params[i], dstate[i], x,
                                              start, n, state=st,
                                              block_tables=block_tables)
        return (x, new_d, stacks) if carry_stack else (x, new_d)

    def tree_chunk(self, params, state, dstate, x, pos0, tree, n,
                   block_tables=None):
        """Score a speculation token tree through the stack: ``x``
        (B, N, F) node activations in ``tree`` (TreeSpec) order, node n
        at stream position ``pos0 + tree.depth[n]`` attending only to
        its root-path (Layer.tree_chunk). Same compute-dtype handling as
        ``decode_step``. Returns ``(y, stacks, kv_windows)`` — per-layer
        node-indexed carry snapshot stacks and uncommitted attention K/V
        windows; ``dstate`` itself is NOT advanced (the verify program
        rewinds carries from the stacks and commits the accepted path
        via ``tree_commit``)."""
        gc = self.conf.global_conf
        if gc.compute_dtype:
            cdt = _dtype_of(gc.compute_dtype)
            x = x.astype(cdt)
            params = _cast_floats(params, cdt)
        stacks = [None] * len(self.layers)
        wins = [None] * len(self.layers)
        for i, l in enumerate(self.layers):
            st = state[i] if state else None
            x, _, stacks[i], wins[i] = l.tree_chunk(
                params[i], dstate[i], x, pos0, tree, n, state=st,
                block_tables=block_tables)
        return x, stacks, wins

    def tree_commit(self, dstate, kv_windows, path, pos0, commit_n,
                    block_tables=None):
        """Write the accepted root-path's positional KV into the decode
        state (Layer.tree_commit); layers without a KV window pass
        through untouched."""
        new_d = list(dstate)
        for i, l in enumerate(self.layers):
            if kv_windows[i] is not None:
                new_d[i] = l.tree_commit(None, dstate[i], kv_windows[i],
                                         path, pos0, commit_n,
                                         block_tables=block_tables)
        return new_d

    # ------------------------------------------------------------- evaluate
    def _eval_stream(self, data, eval_fn):
        """Shared bucketed+pipelined evaluation core: dispatch runs one
        batch ahead of the host read, so the device executes batch k+1
        while ``eval_fn`` consumes batch k (the serving engine's
        predict_stream does the in-flight bookkeeping). ``eval_fn`` gets
        (labels, host_output, labels_mask) per batch.

        Mirrors the fit path's input handling: features are staged onto
        the device ahead of the engine (H2D overlaps the previous batch's
        forward) and a ``device_side`` pre-processor on the iterator chain
        runs on chip here too — a net trained with an on-chip normalizer
        evaluates through the same transform (train/eval parity)."""
        from deeplearning4j_tpu.data.dataset import DataSet
        from deeplearning4j_tpu.data.prefetcher import DevicePrefetcher
        from deeplearning4j_tpu.util.timing import PipelineTimer

        dev_fn, host_pp = self._resolve_device_pp(data)
        eng = self.serving_engine()
        metas = []
        timer = PipelineTimer()

        def feats():
            for ds in data:
                if not isinstance(ds, DataSet):
                    ds = DataSet(*ds)
                if host_pp is not None:
                    ds = host_pp.pre_process(ds)
                metas.append((ds.labels, ds.labels_mask))
                yield ds.features

        staged = DevicePrefetcher(feats(), depth=max(1, self.prefetch_depth),
                                  transform=dev_fn, timer=timer,
                                  device=self._executor.batch_sharding)
        # predict_stream lags ≥1 batch behind feats(), so metas[i] is
        # always populated before output i arrives
        timer.start()
        for i, out in enumerate(eng.predict_stream(staged)):
            labels, lm = metas[i]
            eval_fn(np.asarray(labels), out,
                    None if lm is None else np.asarray(lm))
        timer.stop()
        self.last_pipeline_stats = timer.summary()
        timer.publish("eval")

    def evaluate(self, data, labels=None):
        """Classification evaluation (parity: MultiLayerNetwork.evaluate),
        batches dispatched through the bucketed engine with the host read
        pipelined one batch behind the device."""
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        from deeplearning4j_tpu.data.dataset import DataSet
        ev = Evaluation()
        if labels is not None:
            data = [DataSet(data, labels)]
        elif isinstance(data, DataSet):
            data = [data]
        elif hasattr(data, "reset"):
            data.reset()
        self._eval_stream(data, ev.eval)
        return ev

    def evaluate_regression(self, data):
        from deeplearning4j_tpu.eval.evaluation import RegressionEvaluation
        from deeplearning4j_tpu.data.dataset import DataSet
        ev = RegressionEvaluation()
        if isinstance(data, DataSet):
            data = [data]
        elif hasattr(data, "reset"):
            data.reset()
        self._eval_stream(data,
                          lambda y, out, _lm: ev.eval(y, out))
        return ev

    # ------------------------------------------------------------- utilities
    def num_params(self):
        return sum(int(np.prod(a.shape)) for a in
                   jax.tree_util.tree_leaves(self.params))

    def summary(self):
        lines = ["=" * 70,
                 f"{'Layer':<30}{'Type':<25}{'Params':>12}", "=" * 70]
        for i, (l, p) in enumerate(zip(self.layers, self.params)):
            n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(p))
            name = l.name or f"layer_{i}"
            lines.append(f"{name:<30}{type(l).__name__:<25}{n:>12,}")
        lines.append("=" * 70)
        lines.append(f"Total params: {self.num_params():,}")
        return "\n".join(lines)

    def clone(self):
        import copy as _copy
        net = MultiLayerNetwork(MultiLayerConfiguration.from_json(self.conf.to_json()))
        if self.params is not None:
            net.params = jax.tree_util.tree_map(lambda a: a, self.params)
            net.state = jax.tree_util.tree_map(lambda a: a, self.state)
            net._build_optimizer()
        return net

    # persistence shortcuts (full impl in util/model_serializer.py)
    def save(self, path, save_updater=True):
        from deeplearning4j_tpu.util.model_serializer import write_model
        write_model(self, path, save_updater)

    @staticmethod
    def load(path, load_updater=True):
        from deeplearning4j_tpu.util.model_serializer import restore_multi_layer_network
        return restore_multi_layer_network(path, load_updater)
