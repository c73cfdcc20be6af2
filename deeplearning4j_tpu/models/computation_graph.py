"""ComputationGraph — the DAG network container.

Parity surface: reference nn/graph/ComputationGraph.java (3,363 LoC):
``init`` + topo sort (:370/:394), ``fit`` (:863/:988), forward over
topologicalOrder, ``calcBackpropGradients`` (:1629 — here jax.grad),
multi-input/multi-output ``output`` (:1532), ``rnnTimeStep`` (:2362).

TPU design: the DAG is unrolled along the precomputed topological order at
trace time so XLA fuses the whole graph into the one jit'd train step. The
training path (``fit`` down to the dispatch of the compiled step) is
``models/base_network.py``; this file holds what a DAG differs in: the
forward, the loss, a batch's form, inference and decode.
"""

from __future__ import annotations

from typing import Dict, Any, List

import numpy as np
import jax
import jax.numpy as jnp

from deeplearning4j_tpu.models.base_network import BaseNetwork, _dtype_of
from deeplearning4j_tpu.monitor import compile_ledger
from deeplearning4j_tpu.util.scopes import layer_scope
from deeplearning4j_tpu.util.remat import (BLOCK_KEPT, block_checkpoint,
                                           counting_kept, remat_segments)
from deeplearning4j_tpu.util.dtypes import (cast_floats as _cast_floats,
                                             restore_dtypes as _restore_dtypes)


def _listed(x):
    """One array of a single-input/-output graph, or several, as a list."""
    return list(x) if isinstance(x, (list, tuple)) else [x]


class ComputationGraph(BaseNetwork):
    """``params``, ``state`` and ``opt_state`` are dicts by layer-node name;
    ``conf`` is a ``ComputationGraphConfiguration`` (nn/conf/graph_conf.py)."""
    _prog_prefix = "cg"

    # ------------------------------------------------------------------ init
    def _init_leaves(self, rng, dtype):
        params, state = {}, {}
        layer_nodes = [n for n in self.conf.topological_order
                       if self.conf.nodes[n].kind == "layer"]
        keys = jax.random.split(rng, max(len(layer_nodes), 1))
        for name, k in zip(layer_nodes, keys):
            l = self.conf.nodes[name].layer
            params[name] = l.init(k, dtype)
            state[name] = l.init_state(dtype)
        return params, state

    def _layer(self, key):
        return self.conf.nodes[key].layer

    # ----------------------------------------------------------- forward core
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
            kept_names = self.conf.global_conf.remat_keep or BLOCK_KEPT
            kept = dict.fromkeys(BLOCK_KEPT, 0)
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
                    got, ns = block_checkpoint(block, kept_names)(
                        {n: params[n] for n in names if n in params},
                        {n: state[n] for n in names if n in state},
                        {i: acts[i] for i in needs})
                acts.update(got)
                new_state.update(ns)
            # a name the policy leaves out is replayed: it keeps 0 bytes
            self._remat_kept = {n: v if n in kept_names else 0
                                for n, v in kept.items()}
        if cdt is not None:
            # persistent state (BN stats) keeps its storage dtype
            new_state = {
                k: _restore_dtypes(v, state[k])
                if k in state and state[k] is not None else v
                for k, v in new_state.items()}
        return acts, new_state, new_carries

    def _loss(self, params, state, inputs, labels, rng, masks=None,
              label_masks=None, carries=None):
        """The summed loss of every output and ``(new_state, new_carries)``
        (``new_carries`` is None unless ``carries`` is given: tBPTT's
        chunked training)."""
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
                with_state = getattr(node.layer, "score_and_state", None)
                if with_state is None:
                    score = node.layer.compute_score(
                        p_out, pre_act_input, labels[oi], lm,
                        train=True, rng=lrng)
                else:
                    # an output layer that counts inside its score (a head
                    # with a multi-token-prediction term) returns its state
                    score, new_state[out_name] = with_state(
                        p_out, pre_act_input, labels[oi], lm,
                        train=True, rng=lrng)
                total = total + score
            for name, p in params.items():
                layer = self.conf.nodes[name].layer
                total = total + (layer.reg_loss(p)
                                 + layer.loss_term(new_state.get(name)))
            if self._compute_dtype(True) is not None:
                total = total.astype(jnp.float32)
        return total, (new_state, new_carries)

    def _batch_parts(self, batch, asarray):
        """DataSet / MultiDataSet / ``(inputs, labels)`` → (inputs list,
        labels list, masks dict|None by network input, label_masks
        list|None)."""
        from deeplearning4j_tpu.data.dataset import DataSet, MultiDataSet
        if isinstance(batch, DataSet):
            batch = batch.to_multi()
        elif not isinstance(batch, MultiDataSet):
            batch = MultiDataSet(features=_listed(batch[0]),
                                 labels=_listed(batch[1]))
        masks = None
        if batch.features_masks and any(m is not None
                                        for m in batch.features_masks):
            masks = {n: asarray(m) for n, m in
                     zip(self.conf.network_inputs, batch.features_masks)
                     if m is not None}
        label_masks = None
        if batch.labels_masks and any(m is not None
                                      for m in batch.labels_masks):
            label_masks = [None if m is None else asarray(m)
                           for m in batch.labels_masks]
        return ([asarray(f) for f in batch.features],
                [asarray(l) for l in batch.labels], masks, label_masks)

    def _dp_loss(self, params, state, inputs, labels, rng, pad_mask=None,
                 masks=None, label_masks=None):
        """Loss with optional per-example zero-weighting of padded rows,
        combined with the batch's own label masks. pad_mask: (B,) float,
        1=real row / 0=pad. Returns (loss, new_state)."""
        if pad_mask is not None:
            pms = [jnp.broadcast_to(pad_mask[:, None], y.shape[:2])
                   if y.ndim == 3 else pad_mask for y in labels]
            if label_masks is None:
                label_masks = pms
            else:
                label_masks = [pm if m is None else m * pm
                               for m, pm in zip(label_masks, pms)]
        loss, (new_state, _) = self._loss(params, state, inputs, labels, rng,
                                          masks, label_masks)
        return loss, new_state

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
        with compile_ledger.phase("output"):
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

    def fit_external(self, inputs, epsilons):
        """One updater step driven by external epsilons (the training half
        of the externalEpsilons contract). Updates params, updater state and
        layer state (e.g. batchnorm running stats) like fit(). The update
        runs through the standalone donated program, not an eager loop."""
        with compile_ledger.phase("fit"):
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

    @staticmethod
    def load(path, load_updater=True):
        from deeplearning4j_tpu.util.model_serializer import restore_computation_graph
        return restore_computation_graph(path, load_updater)
