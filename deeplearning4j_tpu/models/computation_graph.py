"""ComputationGraph — the DAG network container.

Parity surface: reference nn/graph/ComputationGraph.java (3,363 LoC):
``init`` + topo sort (:370/:394), ``fit`` (:863/:988), forward over
topologicalOrder, ``calcBackpropGradients`` (:1629 — here jax.grad),
multi-input/multi-output ``output`` (:1532), ``rnnTimeStep`` (:2362).

TPU design mirrors MultiLayerNetwork: one jit'd pure train step; the DAG is
unrolled along the precomputed topological order at trace time so XLA fuses
the whole graph.
"""

from __future__ import annotations

import itertools
import time
from typing import Optional, Dict, Any, List

import numpy as np
import jax
import jax.numpy as jnp
import optax

from deeplearning4j_tpu.monitor.tracing import trace
from deeplearning4j_tpu.nn.conf.graph_conf import ComputationGraphConfiguration
from deeplearning4j_tpu.nn.updaters import make_gradient_transform
from deeplearning4j_tpu.nn.layers.special import FrozenLayer


def _dtype_of(name):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16, "float64": jnp.float64}[name]


from deeplearning4j_tpu.util.scopes import layer_scope
from deeplearning4j_tpu.util.remat import (BLOCK_KEPT, block_checkpoint,
                                           counting_kept, remat_segments)
from deeplearning4j_tpu.util.dtypes import (cast_floats as _cast_floats,
                                             restore_dtypes as _restore_dtypes)


class ComputationGraph:
    _prog_ids = itertools.count()

    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        self.params: Optional[Dict[str, Dict]] = None
        self.state: Optional[Dict[str, Dict]] = None
        self.opt_state: Optional[Dict[str, Any]] = None
        self.listeners: List[Any] = []
        self.iteration = 0
        self.epoch = 0
        self._epoch_batch = 0         # batches consumed in the current epoch
                                      # (persisted in checkpoints → resume
                                      # restarts mid-epoch at the right batch)
        self._score = float("nan")
        self._last_input = None       # last fit batch (activation capture)
        self._rnn_carries = None      # rnnTimeStep stateMap
        self._train_step_cache = {}
        self._scan_fit = None
        self._output_fn = None
        self._serving = None          # bucketed inference engine (lazy)
        self._transforms = None
        self._fused = None            # fused update plan (nn/fused_update.py)
        self._update_step = None      # standalone donated update program
        self._compile_count = 0       # train programs traced (see _note_compile)
        self._remat_kept = None       # remat='blocks': bytes kept, by name
        self._flight = None           # FlightRecorder (monitor/flight.py)
        self._train_mon = None        # lazy TrainMonitor (metric children)
        self._exec = None             # execution core (lazy; exec/executor.py)
        # per-instance caller id for the XLA program registry (/programs):
        # a rebuilt graph gets fresh registry rows, never a stale hit
        self._prog_caller = f"cg{next(ComputationGraph._prog_ids)}"

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
        gc = self.conf.global_conf
        dtype = _dtype_of(gc.dtype)
        if rng is None:
            rng = jax.random.PRNGKey(gc.seed)
        self.params, self.state = {}, {}
        layer_nodes = [n for n in self.conf.topological_order
                       if self.conf.nodes[n].kind == "layer"]
        keys = jax.random.split(rng, max(len(layer_nodes), 1))
        for name, k in zip(layer_nodes, keys):
            l = self.conf.nodes[name].layer
            self.params[name] = l.init(k, dtype)
            self.state[name] = l.init_state(dtype)
        self._build_optimizer()
        return self

    def _build_optimizer(self):
        import json
        from deeplearning4j_tpu.nn.fused_update import (build_fused_update,
                                                        fused_update_enabled)
        gc = self.conf.global_conf
        self._transforms = {}
        group_keys = {}
        for name, p in self.params.items():
            l = self.conf.nodes[name].layer
            if isinstance(l, FrozenLayer) or not p:
                self._transforms[name] = optax.set_to_zero()
                group_keys[name] = None
            else:
                upd = l.updater or gc.updater
                self._transforms[name] = make_gradient_transform(upd)
                group_keys[name] = json.dumps(upd.to_dict(), sort_keys=True)
        self.opt_state = {n: t.init(self.params[n])
                          for n, t in self._transforms.items()}
        self._fused = None
        if fused_update_enabled():
            self._fused = build_fused_update(
                self.params, self._transforms, group_keys,
                {n: self.conf.nodes[n].layer.apply_constraints
                 for n in self.params})
        self._train_step_cache = {}
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

    def _forward(self, params, state, inputs: List, *, train, rng, masks=None,
                 carries=None):
        """Forward along topo order. Returns (activations dict, new_state,
        new_carries). ``carries``: dict layer-name → recurrent carry (the
        reference's rnnTimeStep stateMap, ComputationGraph.java:2362); when
        given, recurrent layers resume from it and the updated map is
        returned (None entries mean zero initial state)."""
        acts: Dict[str, Any] = {}
        new_state = dict(state)
        new_carries = dict(carries) if carries is not None else None
        cdt = self._compute_dtype(train)
        # with a block as the replay unit the float32 parameters go into
        # each checkpoint and are cast there, so that the compute-dtype
        # copies live only while their block runs
        by_block = (train and carries is None
                    and self.conf.global_conf.remat == "blocks")
        if cdt is not None and not by_block:
            params = _cast_floats(params, cdt)
        for i, n in enumerate(self.conf.network_inputs):
            x = inputs[i]
            if cdt is not None and jnp.issubdtype(x.dtype, jnp.floating):
                x = x.astype(cdt)       # token ids stay integers
            acts[n] = x

        def run(names, params, state, acts, new_state):
            """Apply the nodes ``names`` in order; fills ``acts`` and
            ``new_state``."""
            for name in names:
                idx = order_index[name]
                node = self.conf.nodes[name]
                if node.kind == "input":
                    continue
                ins = [acts[i] for i in node.inputs]
                if node.kind == "vertex":
                    v = node.vertex
                    with layer_scope(name, v):
                        if getattr(v, "mask_input", None) is not None:
                            # mask-aware vertex (LastTimeStepVertex): the
                            # named network input's (B, T) mask locates true
                            # last steps
                            m = masks.get(v.mask_input) if masks else None
                            acts[name] = v.apply(ins, mask=m)
                        else:
                            acts[name] = v.apply(ins)
                    continue
                lrng = None if rng is None else jax.random.fold_in(rng, idx)
                mask = None
                if masks and node.inputs and node.inputs[0] in masks:
                    mask = masks[node.inputs[0]]
                p_n = params.get(name, {})
                if by_block and cdt is not None:
                    p_n = _cast_floats(p_n, cdt)
                with layer_scope(name, node.layer):
                    if (train and node.layer.weight_noise is not None
                            and lrng is not None):
                        p_n = node.layer.weight_noise.apply(
                            p_n, jax.random.fold_in(lrng, 0x5eed))
                    if (new_carries is not None
                            and hasattr(node.layer, "apply_with_carry")):
                        y, c = node.layer.apply_with_carry(
                            p_n, ins[0], new_carries.get(name), mask=mask)
                        new_carries[name] = c
                    else:
                        y, st = node.layer.apply(p_n, ins[0],
                                                 state.get(name), train=train,
                                                 rng=lrng, mask=mask)
                        if st is not None:
                            new_state[name] = st
                acts[name] = y

        order = self.conf.topological_order
        order_index = {n: i for i, n in enumerate(order)}
        if not by_block:
            run(order, params, state, acts, new_state)
        else:
            # bytes the blocks keep beside their inputs, by name, of the
            # step being traced: the program's registry record carries them
            kept = self._remat_kept = dict.fromkeys(BLOCK_KEPT, 0)
            for names, outs in remat_segments(self.conf):
                if outs is None:
                    run(names, params, state, acts, new_state)
                    continue
                needs = [i for n in names for i in self.conf.nodes[n].inputs
                         if i not in names]

                def block(p, s, a, names=names, outs=outs):
                    a, ns = dict(a), {}
                    run(names, p, s, a, ns)
                    return {o: a[o] for o in outs}, ns

                with counting_kept(kept):
                    got, ns = block_checkpoint(block)(
                        {n: params[n] for n in names if n in params},
                        {n: state[n] for n in names if n in state},
                        {i: acts[i] for i in needs})
                acts.update(got)
                new_state.update(ns)
        if cdt is not None:
            # persistent state (BN stats) keeps its storage dtype
            new_state = {
                k: _restore_dtypes(v, state[k])
                if k in state and state[k] is not None else v
                for k, v in new_state.items()}
        return acts, new_state, new_carries

    def _loss(self, params, state, inputs, labels, rng, masks=None,
              label_masks=None, carries=None):
        """Aux return is ``new_state`` normally; when ``carries`` is given
        (tBPTT chunked training) it is ``(new_state, new_carries)``."""
        with jax.named_scope("forward"):
            acts, new_state, new_carries = self._forward(
                params, state, inputs, train=True, rng=rng, masks=masks,
                carries=carries)
        with jax.named_scope("loss"):
            total = 0.0
            for oi, out_name in enumerate(self.conf.network_outputs):
                node = self.conf.nodes[out_name]
                if (node.kind != "layer"
                        or not hasattr(node.layer, "compute_score")):
                    raise ValueError(
                        f"Output '{out_name}' is not a loss-bearing layer")
                pre_act_input = acts[node.inputs[0]]
                lrng = (None if rng is None
                        else jax.random.fold_in(rng, 10000 + oi))
                lm = None if not label_masks else label_masks[oi]
                p_out = params.get(out_name, {})
                if node.layer.weight_noise is not None and lrng is not None:
                    p_out = node.layer.weight_noise.apply(
                        p_out, jax.random.fold_in(lrng, 0x5eed))
                total = total + node.layer.compute_score(
                    p_out, pre_act_input, labels[oi], lm,
                    train=True, rng=lrng)
            for name, p in params.items():
                total = total + self.conf.nodes[name].layer.reg_loss(p)
            if self._compute_dtype(True) is not None:
                total = total.astype(jnp.float32)
        if carries is not None:
            return total, (new_state, new_carries)
        return total, new_state

    def _normalize_grads(self, grads):
        from deeplearning4j_tpu.nn.updaters import normalize_layer_grad
        gc = self.conf.global_conf
        kind = gc.gradient_normalization
        if not kind or kind == "None":
            return grads
        thr = gc.gradient_normalization_threshold
        return {n: normalize_layer_grad(g, kind, thr) for n, g in grads.items()}

    # -------------------------------------------- data-parallel protocol
    # Same three-method surface as MultiLayerNetwork so ParallelWrapper is
    # model-agnostic (parity: ParallelWrapper.java:58 takes any Model).
    def _dp_batch(self, ds):
        """DataSet/MultiDataSet → (inputs list, labels list, masks dict|None,
        label_masks list|None)."""
        from deeplearning4j_tpu.data.dataset import DataSet
        if isinstance(ds, DataSet):
            ds = ds.to_multi()
        masks = None
        if ds.features_masks and any(m is not None for m in ds.features_masks):
            masks = {n: np.asarray(m) for n, m in
                     zip(self.conf.network_inputs, ds.features_masks)
                     if m is not None}
        label_masks = None
        if ds.labels_masks and any(m is not None for m in ds.labels_masks):
            label_masks = [None if m is None else np.asarray(m)
                           for m in ds.labels_masks]
        return ([np.asarray(f) for f in ds.features],
                [np.asarray(l) for l in ds.labels], masks, label_masks)

    def _dp_loss(self, params, state, inputs, labels, rng, pad_mask=None,
                 masks=None, label_masks=None):
        if pad_mask is not None:
            pms = [jnp.broadcast_to(pad_mask[:, None], y.shape[:2])
                   if y.ndim == 3 else pad_mask for y in labels]
            if label_masks is None:
                label_masks = pms
            else:
                label_masks = [pm if m is None else m * pm
                               for m, pm in zip(label_masks, pms)]
        return self._loss(params, state, inputs, labels, rng, masks,
                          label_masks)

    @jax.named_scope("updater")
    def _dp_apply_updates(self, params, opt_state, grads, fused=None):
        """Fused flat update by default (nn/fused_update.py — bitwise-equal
        to the per-node loop below, kept as the DL4JTPU_FUSED_UPDATE=0
        fallback and parity oracle). Tensor-parallel callers pass
        ``fused=False``: raveling row- and column-sharded leaves into one
        vector would gather every shard (and trips a GSPMD mis-partition
        on mixed-axis concat) — the per-node loop keeps TP placement."""
        grads = self._normalize_grads(grads)
        if fused is None:
            fused = self._executor.model_size <= 1
        if fused and self._fused is not None:
            return self._fused.apply(params, opt_state, grads)
        new_params, new_opt = {}, {}
        for name, p in params.items():
            if not p:
                new_params[name], new_opt[name] = p, opt_state[name]
                continue
            u, o = self._transforms[name].update(grads[name], opt_state[name], p)
            np_ = optax.apply_updates(p, u)
            np_ = self.conf.nodes[name].layer.apply_constraints(np_)
            new_params[name], new_opt[name] = np_, o
        return new_params, new_opt

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
        """jax.checkpoint-wrapped loss when remat is configured (see
        GlobalConf.remat / MultiLayerNetwork._loss_for_grad); with
        ``'blocks'`` the checkpoints are inside ``_forward``."""
        from deeplearning4j_tpu.util.remat import remat_loss
        return remat_loss(self._loss, self.conf.global_conf.remat)

    def _make_train_step(self):
        loss_fn = self._loss_for_grad()
        rec = self._flight           # captured at trace-build time: the
        # recorder-off program is byte-identical to the pre-flight path
        sample_k = rec.sample_every if rec is not None else 1

        def step(params, state, opt_state, inputs, labels, it, masks, label_masks):
            self._note_compile()
            rng = jax.random.fold_in(
                jax.random.PRNGKey(self.conf.global_conf.seed), it)
            (loss, new_state), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, state, inputs, labels, rng,
                                       masks, label_masks)
            new_params, new_opt = self._dp_apply_updates(params, opt_state, grads)
            if rec is None:
                return new_params, new_state, new_opt, loss
            from deeplearning4j_tpu.monitor import flight
            telem = flight.step_telemetry(
                flight.telemetry_triples(params, new_params, grads),
                it, sample_k)
            return new_params, new_state, new_opt, loss, telem

        from deeplearning4j_tpu import exec as ex
        out_specs = (ex.PARAMS, ex.STATE, ex.OPT, ex.REPL)
        if rec is not None:
            out_specs = out_specs + (ex.AUX,)
        return self._executor.jit(
            step,
            in_specs=(ex.PARAMS, ex.STATE, ex.OPT, ex.BATCH, ex.BATCH,
                      ex.REPL, ex.BATCH, ex.BATCH),
            out_specs=out_specs,
            donate_argnums=(0, 1, 2))

    # ------------------------------------------------------------------- fit
    def fit_scan(self, inputs_steps, labels_steps):
        """Device-resident training: ``n`` train steps in ONE compiled call
        via lax.scan over a leading step axis (see
        MultiLayerNetwork.fit_scan). ``inputs_steps``/``labels_steps``:
        lists of arrays shaped (n_steps, batch, ...) — or single arrays for
        single-input/-output graphs."""
        if getattr(self.conf, "backprop_type", "standard") == "tbptt":
            raise ValueError(
                "fit_scan runs full-sequence backprop; a graph configured "
                "for truncated BPTT must use fit() (the tbptt chunking path)")
        if not isinstance(inputs_steps, (list, tuple)):
            inputs_steps = [inputs_steps]
        if not isinstance(labels_steps, (list, tuple)):
            labels_steps = [labels_steps]
        inputs_steps = [jnp.asarray(a) for a in inputs_steps]
        labels_steps = [jnp.asarray(a) for a in labels_steps]
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
                    (loss, new_state), grads = jax.value_and_grad(
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
        if self._flight is not None:
            (self.params, self.state, self.opt_state, losses,
             telems) = self._scan_fit(
                self.params, self.state, self.opt_state, inputs_steps,
                labels_steps, jnp.asarray(self.iteration, jnp.int32))
            self._flight.record_scan(self.iteration, telems)
        else:
            self.params, self.state, self.opt_state, losses = self._scan_fit(
                self.params, self.state, self.opt_state, inputs_steps,
                labels_steps, jnp.asarray(self.iteration, jnp.int32))
        self._last_input = [a[-1] for a in inputs_steps]  # activation capture
        n_steps = int(inputs_steps[0].shape[0])
        self.iteration += n_steps
        self._epoch_batch += n_steps
        self._score = losses[-1]
        self._mon.record(seconds=time.perf_counter() - t0, steps=n_steps,
                         examples=n_steps * int(inputs_steps[0].shape[1]),
                         score=self._score,
                         compiled=self._compile_count - c0, path="scan")
        if self._compile_count > c0:
            # fresh XLA program: record its cost/memory analysis so /programs
            # and the bench MFU column read measured numbers, not estimates.
            # Lowering args are the donated call's OUTPUTS (same shapes).
            self._executor.register_program(
                self._prog_caller,
                f"fit_scan_k{n_steps}_b{int(inputs_steps[0].shape[1])}",
                self._scan_fit,
                (self.params, self.state, self.opt_state, inputs_steps,
                 labels_steps, jnp.asarray(self.iteration, jnp.int32)),
                compile_seconds=time.perf_counter() - t0, scopes=True,
                remat_kept_bytes=self._remat_kept)
        if self.listeners:
            with trace.span("callback"):
                for lst in self.listeners:
                    lst.iteration_done(self, self.iteration, self.epoch)
        return self

    def fit(self, data, labels=None, epochs=1, prefetch=None,
            checkpoint=None, resume_from=None):
        """fit(inputs, labels) | fit(MultiDataSet/DataSet) | fit(iterator).

        ``prefetch``: device-resident prefetch depth for the streamed path
        (see data/prefetcher.py and MultiLayerNetwork.fit); ``None`` uses
        the class default ``prefetch_depth``, ``0`` disables. Per-stage
        timing lands in ``self.last_pipeline_stats``.

        ``checkpoint`` / ``resume_from``: crash-safe periodic saves and
        bitwise-identical continuation — same contract as
        MultiLayerNetwork.fit (docs/FAULT_TOLERANCE.md)."""
        from deeplearning4j_tpu.monitor.profiling import profile_scope

        # DL4JTPU_PROFILE=<dir> wraps the whole call in jax.profiler.trace
        # (docs/OBSERVABILITY.md); unset, this is a plain passthrough
        with profile_scope():
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
            direct = (labels is not None
                      or isinstance(data, (DataSet, MultiDataSet)))
            if direct:
                if resume_from is not None:
                    raise ValueError(
                        "resume_from needs resettable iterator data; a bare "
                        "array/DataSet fit has no epoch stream to replay")
                if labels is not None:
                    return self._fit_batch(MultiDataSet(
                        features=[data] if not isinstance(data, (list, tuple))
                        else list(data),
                        labels=[labels] if not isinstance(labels, (list, tuple))
                        else list(labels)))
                if isinstance(data, DataSet):
                    return self._fit_batch(data.to_multi())
                return self._fit_batch(data)
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
        """See MultiLayerNetwork._resume_training — restore + wind the
        iterator to the crash position; returns batches to skip in the
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
        # one reset() + ONE iter() + full consumption per completed epoch —
        # the exact call sequence the uninterrupted fit made (a bare
        # `for _ in iter(data)` would invoke __iter__ twice and de-sync
        # reset-counting shuffles; see MultiLayerNetwork._resume_training)
        for _ in range(self.epoch):
            data.reset()
            it = iter(data)
            while True:
                try:
                    next(it)
                except StopIteration:
                    break
        return self._epoch_batch

    # chunk caps — see MultiLayerNetwork._fit_stream (same design: runs of
    # mask-free same-shape batches stack onto the device-resident scan path;
    # util/chunking.py sends a step of heavy estimated work singly)
    _CHUNK_MAX_STEPS = 64
    _CHUNK_MAX_BYTES = 256 << 20

    # see MultiLayerNetwork: device-resident prefetch depth for the
    # streamed fit/eval path, and the last epoch's per-stage timing
    prefetch_depth = 2
    last_pipeline_stats = None

    def _resolve_device_pp(self, data):
        """(dev_fn, host_pp) — see MultiLayerNetwork._resolve_device_pp;
        a device_side processor with no device transform falls back to
        host application."""
        from deeplearning4j_tpu.data.iterators import resolve_pre_processor

        pp = resolve_pre_processor(data)
        dev_fn = host_pp = None
        if pp is not None and getattr(pp, "device_side", False):
            f = pp.as_device_transform()
            if f is not None:
                dev_fn = jax.jit(f)
            else:
                host_pp = pp
        return dev_fn, host_pp

    def _stream_chunks(self, data, host_pp, timer, skip_batches=0):
        """Host-side chunk assembly (see MultiLayerNetwork._stream_chunks):
        yields ``("chunk", (xs_list, ys_list))`` stacked host blocks or
        ``("batch", MultiDataSet)`` fallbacks, in base order — chunk
        boundaries do not depend on prefetch depth, so the training math
        is bitwise-identical with prefetch on or off."""
        from deeplearning4j_tpu.data.dataset import DataSet, MultiDataSet

        from deeplearning4j_tpu.util.chunking import (n_parameters,
                                                      steps_per_chunk)

        chunkable = (getattr(self.conf, "backprop_type", "standard")
                     != "tbptt")
        buf, shape = [], None
        n_params = n_parameters(self.params)

        def flush():
            nonlocal buf, shape
            out = None
            if len(buf) == 1:
                out = ("batch", buf[0])
            elif buf:
                with timer.stage("stack"):
                    xs = [np.stack([np.asarray(m.features[i]) for m in buf])
                          for i in range(len(buf[0].features))]
                    ys = [np.stack([np.asarray(m.labels[i]) for m in buf])
                          for i in range(len(buf[0].labels))]
                    out = ("chunk", (xs, ys))
            buf, shape = [], None
            return out

        it = iter(data)
        for _ in range(skip_batches):
            # resume path: already trained before the crash — pull and drop
            # so the stream (and any iterator RNG) advances identically
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
            if isinstance(batch, DataSet):
                batch = batch.to_multi()
            elif not isinstance(batch, MultiDataSet):
                batch = MultiDataSet(features=[batch[0]], labels=[batch[1]])
            if host_pp is not None:
                with timer.stage("decode"):
                    batch = MultiDataSet(
                        features=[host_pp.transform_features(np.asarray(f))
                                  for f in batch.features],
                        labels=batch.labels,
                        features_masks=batch.features_masks,
                        labels_masks=batch.labels_masks)
            has_mask = (
                (batch.features_masks
                 and any(m is not None for m in batch.features_masks))
                or (batch.labels_masks
                    and any(m is not None for m in batch.labels_masks)))
            if not chunkable or has_mask:
                out = flush()
                if out is not None:
                    yield out
                yield ("batch", batch)
                continue
            key = (tuple(np.asarray(f).shape for f in batch.features),
                   tuple(np.asarray(l).shape for l in batch.labels))
            if shape is not None and key != shape:
                out = flush()
                if out is not None:
                    yield out
            shape = key
            buf.append(batch)
            if len(buf) >= steps_per_chunk(
                    batch.features, batch.labels, n_params,
                    self._CHUNK_MAX_STEPS, self._CHUNK_MAX_BYTES):
                yield flush()
        out = flush()
        if out is not None:
            yield out

    def _stream_placement(self, item):
        """Where the step wants a ``_stream_chunks`` item (see
        MultiLayerNetwork._stream_placement)."""
        kind, payload = item
        if kind == "chunk":
            return self._executor.batch_sharding(payload, step_axis=True)
        return self._executor.batch_sharding(
            (payload.features, payload.labels))

    def _fit_stream(self, data, prefetch=None, skip_batches=0):
        """One epoch: host chunk assembly → device-resident prefetch →
        compiled steps (see MultiLayerNetwork._fit_stream for the overlap
        model and stall accounting)."""
        from deeplearning4j_tpu.data.dataset import MultiDataSet
        from deeplearning4j_tpu.data.prefetcher import DevicePrefetcher
        from deeplearning4j_tpu.util.timing import PipelineTimer

        dev_fn, host_pp = self._resolve_device_pp(data)

        def dev_mds(m):
            if dev_fn is None:
                return m
            return MultiDataSet(
                features=[dev_fn(jnp.asarray(ff)) for ff in m.features],
                labels=m.labels, features_masks=m.features_masks,
                labels_masks=m.labels_masks)

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
            # one "train_step" span per consumer iteration (nests the wait
            # and the dispatch — see MultiLayerNetwork._fit_stream)
            with trace.step("train_step", self.iteration):
                with timer.stage("wait"):
                    try:
                        kind, payload = next(it)
                    except StopIteration:
                        break
                with timer.dispatch(lambda: self._score):
                    if kind == "chunk":
                        xs, ys = payload
                        xs = [jnp.asarray(a) for a in xs]
                        if dev_fn is not None:
                            xs = [dev_fn(a) for a in xs]
                        self.fit_scan(xs, ys)
                    else:
                        # fallback batches must be normalized too (the
                        # iterator emitted them raw for a device_side
                        # processor)
                        self._fit_batch(dev_mds(payload))
        timer.stop()
        timer.steps = self.iteration - it0
        self.last_pipeline_stats = timer.summary()
        timer.publish("fit")
        self._mon.publish_expert_counters(
            {n: self.conf.nodes[n].layer for n in self.state}, self.state)

    def _fit_batch(self, mds):
        inputs = [jnp.asarray(f) for f in mds.features]
        labels = [jnp.asarray(l) for l in mds.labels]
        self._last_input = inputs     # device ref for activation capture
        c0, t0 = self._compile_count, time.perf_counter()
        masks = None
        if mds.features_masks and any(m is not None for m in mds.features_masks):
            masks = {n: jnp.asarray(m) for n, m in
                     zip(self.conf.network_inputs, mds.features_masks)
                     if m is not None}
        label_masks = None
        if mds.labels_masks and any(m is not None for m in mds.labels_masks):
            label_masks = [None if m is None else jnp.asarray(m)
                           for m in mds.labels_masks]
        if (getattr(self.conf, "backprop_type", "standard") == "tbptt"
                and inputs[0].ndim == 3):
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
            self._score = loss  # device scalar; host-read deferred to
                                # get_score() (a read waits for the step)
            if self._flight is not None:
                self._flight.record(self.iteration, out[4])
            # taken before the registration below, whose second compile
            # is the record's own aot_seconds, not this call's
            self._last_fit_time = time.perf_counter() - t0
            if self._compile_count > c0:
                # fresh XLA program: expose its cost/memory analysis via the
                # registry (/programs). Donated inputs → lower with outputs.
                self._executor.register_program(
                    self._prog_caller,
                    f"train_step_b{int(inputs[0].shape[0])}",
                    step,
                    (self.params, self.state, self.opt_state, inputs, labels,
                     jnp.asarray(self.iteration, jnp.int32), masks,
                     label_masks),
                    compile_seconds=self._last_fit_time, scopes=True,
                    remat_kept_bytes=self._remat_kept)
        self.iteration += 1
        self._epoch_batch += 1
        self._mon.record(seconds=self._last_fit_time, steps=1,
                         examples=int(inputs[0].shape[0]), score=self._score,
                         compiled=self._compile_count - c0, path="batch")
        if self.listeners:
            with trace.span("callback"):
                for lst in self.listeners:
                    lst.iteration_done(self, self.iteration, self.epoch)
        return self

    # ---------------------------------------------------------------- tbptt
    def _make_tbptt_step(self):
        rec = self._flight
        sample_k = rec.sample_every if rec is not None else 1

        def step(params, state, opt_state, inputs, labels, it, masks,
                 label_masks, carries):
            self._note_compile()
            rng = jax.random.fold_in(
                jax.random.PRNGKey(self.conf.global_conf.seed), it)
            (loss, (new_state, new_carries)), grads = jax.value_and_grad(
                self._loss, has_aux=True)(params, state, inputs, labels, rng,
                                          masks, label_masks, carries)
            new_params, new_opt = self._dp_apply_updates(params, opt_state,
                                                         grads)
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

    def _fit_tbptt(self, inputs, labels, masks, label_masks):
        """Truncated BPTT over the graph: slice time into tbptt_fwd_length
        chunks, carrying recurrent state across chunks (parity:
        ComputationGraph.java:1617-1629 doTruncatedBPTT). Truncation is
        structural: each chunk's step differentiates only through its own
        forward — the carried state enters as a plain (non-differentiated)
        argument, so no stop_gradient is needed."""
        T = inputs[0].shape[1]
        L = self.conf.tbptt_fwd_length
        if "tbptt" not in self._train_step_cache:
            self._train_step_cache["tbptt"] = self._make_tbptt_step()
        step = self._train_step_cache["tbptt"]
        carries = {}
        losses = []
        telem = None
        for start in range(0, T, L):
            sl = slice(start, start + L)
            ins = [x[:, sl] if x.ndim == 3 else x for x in inputs]
            lbs = [y[:, sl] if y.ndim == 3 else y for y in labels]
            mks = None if masks is None else {
                n: (m[:, sl] if m.ndim >= 2 else m) for n, m in masks.items()}
            lms = None if label_masks is None else [
                None if m is None else (m[:, sl] if m.ndim >= 2 else m)
                for m in label_masks]
            out = step(
                self.params, self.state, self.opt_state, ins, lbs,
                jnp.asarray(self.iteration, jnp.int32), mks, lms, carries)
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
        """The shape-bucketed inference engine for this graph (lazy, shared
        by ``output``/``evaluate``; see serving/engine.py)."""
        if self._serving is None:
            from deeplearning4j_tpu.serving.engine import InferenceEngine
            self._serving = InferenceEngine(self, **kw)
        return self._serving

    def output(self, *inputs, train=False, bucketed=True):
        """Multi-output inference (parity: ComputationGraph.output :1532).

        Default fast path is shape-bucketed (see
        MultiLayerNetwork.output): every input is padded to the same
        power-of-two batch bucket and pad rows are sliced off the outputs,
        so a handful of compiled programs serve every request size.
        ``bucketed=False`` forces the exact-shape program."""
        inputs = [jnp.asarray(x) for x in inputs]
        if bucketed:
            outs = self.serving_engine().predict(list(inputs))
            return outs
        if self._output_fn is None:
            def fwd(params, state, inputs):
                acts, _, _ = self._forward(params, state, inputs, train=False,
                                           rng=None)
                return [acts[n] for n in self.conf.network_outputs]
            from deeplearning4j_tpu import exec as ex
            self._output_fn = self._executor.jit(
                fwd, in_specs=(ex.PARAMS, ex.STATE, ex.BATCH),
                out_specs=(ex.BATCH,))
        outs = self._output_fn(self.params, self.state, inputs)
        return outs[0] if len(outs) == 1 else outs

    def score(self, mds=None, inputs=None, labels=None):
        from deeplearning4j_tpu.data.dataset import DataSet
        if mds is not None:
            if isinstance(mds, DataSet):
                mds = mds.to_multi()
            inputs, labels = mds.features, mds.labels
        loss, _ = self._loss(self.params, self.state,
                             [jnp.asarray(x) for x in inputs],
                             [jnp.asarray(y) for y in labels], None)
        return float(loss)

    def get_score(self):
        self._score = float(self._score)   # cache: one host read (a sync),
        return self._score                 # not one per call

    # ------------------------------------------------- external gradients
    def backprop_external(self, inputs, epsilons):
        """Parameter gradients from externally-supplied dL/d(output)
        epsilons (parity: ComputationGraph.calcBackpropGradients(
        externalEpsilons), used when this graph's outputs feed an external
        computation — e.g. featurized transfer-learning workflows).
        ``epsilons``: one array per network output, shaped like it.
        Returns (grads, new_state) — grads include the l1/l2 regularization
        term (this framework applies regularization in the loss, so an
        external-epsilon step must add its gradient explicitly to match
        fit())."""
        inputs = [jnp.asarray(x) for x in inputs] \
            if isinstance(inputs, (list, tuple)) else [jnp.asarray(inputs)]
        epsilons = [jnp.asarray(e) for e in epsilons] \
            if isinstance(epsilons, (list, tuple)) else [jnp.asarray(epsilons)]

        # iteration-seeded PRNG like fit(): dropout/weight-noise behave the
        # same on the external-epsilon path as in ordinary training
        rng = jax.random.fold_in(
            jax.random.PRNGKey(self.conf.global_conf.seed), self.iteration)

        def outs(params):
            acts, new_state, _ = self._forward(params, self.state, inputs,
                                               train=True, rng=rng)
            return [acts[n] for n in self.conf.network_outputs], new_state

        _, vjp, new_state = jax.vjp(outs, self.params, has_aux=True)
        (grads,) = vjp(epsilons)

        def reg(params):
            return sum((self.conf.nodes[n].layer.reg_loss(p)
                        for n, p in params.items()), jnp.float32(0))

        reg_grads = jax.grad(reg)(self.params)
        grads = jax.tree_util.tree_map(jnp.add, grads, reg_grads)
        return grads, new_state

    def _apply_updates_jitted(self):
        """The standalone grad→update→apply program: one compile per
        (model, updater), params + opt-state donated so XLA updates in
        place. Traces the same `_dp_apply_updates` math the train step
        embeds (fused flat path by default)."""
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

    def fit_external(self, inputs, epsilons):
        """One updater step driven by external epsilons (the training half
        of the externalEpsilons contract). Updates params, updater state and
        layer state (e.g. batchnorm running stats) like fit(). The update
        runs through the standalone donated program, not an eager loop."""
        grads, new_state = self.backprop_external(inputs, epsilons)
        self.apply_external_updates(grads)
        self.state = new_state
        self.iteration += 1
        return self

    # ------------------------------------------------------------------ rnn
    def rnn_time_step(self, *inputs):
        """Stateful streaming inference: feed one (or a few) timesteps,
        recurrent layers resume from the stored state map (parity:
        ComputationGraph.rnnTimeStep :2362). 2-D inputs are treated as a
        single timestep (B, F) → (B, 1, F)."""
        inputs = [jnp.asarray(x) for x in inputs]
        inputs = [x[:, None, :] if x.ndim == 2 else x for x in inputs]
        if self._rnn_carries is None:
            self._rnn_carries = {}
        acts, _, self._rnn_carries = self._forward(
            self.params, self.state, inputs, train=False, rng=None,
            carries=self._rnn_carries)
        outs = [acts[n] for n in self.conf.network_outputs]
        return outs[0] if len(outs) == 1 else outs

    def rnn_clear_previous_state(self):
        """Parity: ComputationGraph.rnnClearPreviousState."""
        self._rnn_carries = None

    # --------------------------------------------------- incremental decode
    def init_decode_state(self, batch: int, max_len: int = 256, kv=None):
        """Decode state keyed by layer-node name (see
        MultiLayerNetwork.init_decode_state; serving/decode.py holds this
        tree resident on device across token steps). ``kv`` switches
        attention nodes to the shared block-pool layout (serving/kv/)."""
        gc = self.conf.global_conf
        dt = _dtype_of(gc.compute_dtype or gc.dtype)
        out = {}
        for name in self.conf.topological_order:
            node = self.conf.nodes[name]
            if node.kind == "layer":
                if kv is not None:
                    out[name] = node.layer.init_paged_decode_state(
                        self.params.get(name, {}), batch, max_len,
                        kv["num_blocks"], kv["block_size"], dt)
                else:
                    out[name] = node.layer.init_decode_state(
                        self.params.get(name, {}), batch, max_len, dt)
        return out

    def decode_step(self, params, state, dstate, x_t, pos,
                    block_tables=None):
        """Pure one-token step along the topo order (single-input,
        single-path graphs; vertices like residual adds work on the
        (B, 1, F) slices unchanged). Bitwise contract and compute-dtype
        handling match MultiLayerNetwork.decode_step; ``block_tables``
        routes attention nodes through the paged-KV path."""
        if len(self.conf.network_inputs) != 1:
            raise ValueError(
                "incremental decode supports single-input graphs; got "
                f"inputs {self.conf.network_inputs}")
        gc = self.conf.global_conf
        if gc.compute_dtype:
            cdt = _dtype_of(gc.compute_dtype)
            x_t = x_t.astype(cdt)
            params = _cast_floats(params, cdt)
        acts = {self.conf.network_inputs[0]: x_t}
        new_d = dict(dstate)
        for name in self.conf.topological_order:
            node = self.conf.nodes[name]
            if node.kind == "input":
                continue
            ins = [acts[i] for i in node.inputs]
            if node.kind == "vertex":
                acts[name] = node.vertex.apply(ins)
                continue
            st = state.get(name) if state else None
            if block_tables is None:
                y, nd = node.layer.decode_step(
                    params.get(name, {}), dstate.get(name), ins[0], pos,
                    state=st)
            else:
                y, nd = node.layer.decode_step_paged(
                    params.get(name, {}), dstate.get(name), ins[0], pos,
                    block_tables, state=st)
            new_d[name] = nd
            acts[name] = y
        outs = [acts[n] for n in self.conf.network_outputs]
        return (outs[0] if len(outs) == 1 else outs), new_d

    def prefill_chunk(self, params, state, dstate, x, start, n,
                      block_tables=None, carry_stack=False):
        """Advance a prefill chunk along the topo order: ``x`` (B, K, F)
        chunk activations, ``n`` (B,) valid rows (Layer.prefill_chunk).
        Vertices apply to the (B, K, F) chunk slices unchanged.
        ``carry_stack=True`` additionally returns a name-keyed dict of
        carry snapshot stacks (None where the layer keeps no carry) for
        speculative rewind (serving/spec/)."""
        if len(self.conf.network_inputs) != 1:
            raise ValueError(
                "incremental decode supports single-input graphs; got "
                f"inputs {self.conf.network_inputs}")
        gc = self.conf.global_conf
        if gc.compute_dtype:
            cdt = _dtype_of(gc.compute_dtype)
            x = x.astype(cdt)
            params = _cast_floats(params, cdt)
        acts = {self.conf.network_inputs[0]: x}
        new_d = dict(dstate)
        stacks = {}
        for name in self.conf.topological_order:
            node = self.conf.nodes[name]
            if node.kind == "input":
                continue
            ins = [acts[i] for i in node.inputs]
            if node.kind == "vertex":
                acts[name] = node.vertex.apply(ins)
                continue
            st = state.get(name) if state else None
            if carry_stack:
                y, nd, stacks[name] = node.layer.prefill_chunk(
                    params.get(name, {}), dstate.get(name), ins[0], start,
                    n, state=st, block_tables=block_tables,
                    carry_stack=True)
            else:
                y, nd = node.layer.prefill_chunk(
                    params.get(name, {}), dstate.get(name), ins[0], start,
                    n, state=st, block_tables=block_tables)
            new_d[name] = nd
            acts[name] = y
        outs = [acts[n] for n in self.conf.network_outputs]
        out = outs[0] if len(outs) == 1 else outs
        return (out, new_d, stacks) if carry_stack else (out, new_d)

    def tree_chunk(self, params, state, dstate, x, pos0, tree, n,
                   block_tables=None):
        """Score a speculation token tree along the topo order (see
        MultiLayerNetwork.tree_chunk): ``x`` (B, N, F) node activations,
        vertices apply to the (B, N, F) slices unchanged. Returns
        ``(y, stacks, kv_windows)`` keyed by layer-node name; ``dstate``
        is NOT advanced — the verify program rewinds carries from the
        stacks and commits the accepted path via ``tree_commit``."""
        if len(self.conf.network_inputs) != 1:
            raise ValueError(
                "incremental decode supports single-input graphs; got "
                f"inputs {self.conf.network_inputs}")
        gc = self.conf.global_conf
        if gc.compute_dtype:
            cdt = _dtype_of(gc.compute_dtype)
            x = x.astype(cdt)
            params = _cast_floats(params, cdt)
        acts = {self.conf.network_inputs[0]: x}
        stacks, wins = {}, {}
        for name in self.conf.topological_order:
            node = self.conf.nodes[name]
            if node.kind == "input":
                continue
            ins = [acts[i] for i in node.inputs]
            if node.kind == "vertex":
                acts[name] = node.vertex.apply(ins)
                continue
            st = state.get(name) if state else None
            y, _, stacks[name], wins[name] = node.layer.tree_chunk(
                params.get(name, {}), dstate.get(name), ins[0], pos0,
                tree, n, state=st, block_tables=block_tables)
            acts[name] = y
        outs = [acts[n] for n in self.conf.network_outputs]
        return (outs[0] if len(outs) == 1 else outs), stacks, wins

    def tree_commit(self, dstate, kv_windows, path, pos0, commit_n,
                    block_tables=None):
        """Write the accepted root-path's positional KV into the decode
        state (Layer.tree_commit); nodes without a KV window pass
        through untouched."""
        new_d = dict(dstate)
        for name, win in kv_windows.items():
            if win is not None:
                new_d[name] = self.conf.nodes[name].layer.tree_commit(
                    None, dstate.get(name), win, path, pos0, commit_n,
                    block_tables=block_tables)
        return new_d

    def evaluate(self, data):
        """First-output classification eval, dispatched through the
        bucketed engine with the host read pipelined one batch behind the
        device (see MultiLayerNetwork._eval_stream). Features are staged
        on device ahead of the engine and a ``device_side`` pre-processor
        on the iterator chain runs on chip here too — train/eval parity."""
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        from deeplearning4j_tpu.data.dataset import DataSet, MultiDataSet
        from deeplearning4j_tpu.data.prefetcher import DevicePrefetcher
        from deeplearning4j_tpu.util.timing import PipelineTimer

        ev = Evaluation()
        dev_fn, host_pp = self._resolve_device_pp(data)
        if isinstance(data, (DataSet, MultiDataSet)):
            data = [data]
        elif hasattr(data, "reset"):
            data.reset()
        eng = self.serving_engine()
        labels = []
        timer = PipelineTimer()

        def feats():
            for ds in data:
                if isinstance(ds, DataSet):
                    ds = ds.to_multi()
                if host_pp is not None:
                    ds = MultiDataSet(
                        features=[host_pp.transform_features(np.asarray(f))
                                  for f in ds.features],
                        labels=ds.labels, features_masks=ds.features_masks,
                        labels_masks=ds.labels_masks)
                labels.append(ds.labels[0])
                yield list(ds.features)    # the prefetcher places them

        dev_tx = (None if dev_fn is None
                  else (lambda fs: [dev_fn(f) for f in fs]))
        staged = DevicePrefetcher(feats(), depth=max(1, self.prefetch_depth),
                                  transform=dev_tx, timer=timer,
                                  device=self._executor.batch_sharding)
        timer.start()
        for i, out in enumerate(eng.predict_stream(staged)):
            if isinstance(out, list):
                out = out[0]
            ev.eval(np.asarray(labels[i]), out)
        timer.stop()
        self.last_pipeline_stats = timer.summary()
        timer.publish("eval")
        return ev

    # ------------------------------------------------------------- utilities
    def num_params(self):
        return sum(int(np.prod(a.shape)) for a in
                   jax.tree_util.tree_leaves(self.params))

    def summary(self):
        lines = ["=" * 78,
                 f"{'Vertex':<28}{'Type':<26}{'Inputs':<14}{'Params':>10}",
                 "=" * 78]
        for name in self.conf.topological_order:
            node = self.conf.nodes[name]
            if node.kind == "input":
                lines.append(f"{name:<28}{'(input)':<26}{'':<14}{0:>10}")
                continue
            tname = (type(node.layer).__name__ if node.kind == "layer"
                     else type(node.vertex).__name__)
            n = 0
            if node.kind == "layer" and self.params and name in self.params:
                n = sum(int(np.prod(a.shape)) for a in
                        jax.tree_util.tree_leaves(self.params[name]))
            ins = ",".join(node.inputs)[:13]
            lines.append(f"{name:<28}{tname:<26}{ins:<14}{n:>10,}")
        lines.append("=" * 78)
        lines.append(f"Total params: {self.num_params():,}")
        return "\n".join(lines)

    def save(self, path, save_updater=True):
        from deeplearning4j_tpu.util.model_serializer import write_model
        write_model(self, path, save_updater)

    @staticmethod
    def load(path, load_updater=True):
        from deeplearning4j_tpu.util.model_serializer import restore_computation_graph
        return restore_computation_graph(path, load_updater)
