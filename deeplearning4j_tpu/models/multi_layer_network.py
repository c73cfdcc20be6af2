"""MultiLayerNetwork — the sequential network container.

Parity surface: reference nn/multilayer/MultiLayerNetwork.java (3,156 LoC):
``init`` (:541), ``fit`` (:1156), ``output`` (:1947), ``score``,
``computeGradientAndScore`` (:2206), truncated BPTT (:1219),
``rnnTimeStep`` (:2209 stored-state path), plus the Solver/updater loop
(optimize/Solver.java, BaseOptimizer.java:171).

The training path (``fit`` down to the dispatch of the compiled step)
is ``models/base_network.py``; this file holds what a list of layers
differs in: the forward, the loss, a batch's form, inference and decode.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from deeplearning4j_tpu.models.base_network import BaseNetwork, _dtype_of
from deeplearning4j_tpu.monitor import compile_ledger
from deeplearning4j_tpu.nn.conf.configuration import MultiLayerConfiguration
from deeplearning4j_tpu.util.scopes import layer_scope
from deeplearning4j_tpu.util.dtypes import (cast_floats as _cast_floats,
                                             restore_dtypes as _restore_dtypes)


class MultiLayerNetwork(BaseNetwork):
    _prog_prefix = "mln"

    def __init__(self, conf: MultiLayerConfiguration):
        conf.finalize()
        if conf.global_conf.remat == "blocks":
            # util/remat.py: blocks are runs of graph nodes named
            # '<block>.<node>'; here the mode would do nothing, silently
            raise ValueError(
                "remat='blocks' replays blocks of a ComputationGraph whose "
                "nodes are named '<block>.<node>'; a list of layers has "
                "none (use True, 'full' or 'save_convs')")
        super().__init__(conf)
        self.layers = conf.layers   # params, state, opt_state: lists by index

    # ------------------------------------------------------------------ init
    def _init_leaves(self, rng, dtype):
        keys = jax.random.split(rng, max(len(self.layers), 1))
        return ([l.init(k, dtype) for l, k in zip(self.layers, keys)],
                [l.init_state(dtype) for l in self.layers])

    def _layer(self, key):
        return self.layers[key]

    def add_listeners(self, *listeners):
        self.listeners.extend(listeners)
        return self

    # ----------------------------------------------------------- forward core
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
            for l, p, st in zip(self.layers, params, new_states):
                reg = reg + (l.reg_loss(p) + l.loss_term(st))
            loss = loss + reg
            if self._compute_dtype(True) is not None:
                loss = loss.astype(jnp.float32)
        return loss, (new_states, new_carries)

    def _batch_parts(self, batch, asarray):
        """DataSet or ``(x, y)`` → canonical (x, y, features_mask,
        labels_mask)."""
        from deeplearning4j_tpu.data.dataset import DataSet
        ds = batch if isinstance(batch, DataSet) else DataSet(*batch)
        return (asarray(ds.features), asarray(ds.labels),
                None if ds.features_mask is None else asarray(ds.features_mask),
                None if ds.labels_mask is None else asarray(ds.labels_mask))

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
        with compile_ledger.phase("output"):
            return self._output_fn(
                self.params, self.state, x,
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

    @staticmethod
    def load(path, load_updater=True):
        from deeplearning4j_tpu.util.model_serializer import restore_multi_layer_network
        return restore_multi_layer_network(path, load_updater)
