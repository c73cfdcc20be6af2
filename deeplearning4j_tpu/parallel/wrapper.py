"""Data-parallel training over a device mesh.

Parity surface: reference ParallelWrapper (deeplearning4j-scaleout-
parallelwrapper/.../ParallelWrapper.java:58 — N replicas, synchronous param
averaging every ``averagingFrequency`` iterations :251-371, or async
threshold-encoded gradient sharing via EncodedGradientsAccumulator) and the
Spark ParameterAveragingTrainingMaster / SharedTrainingMaster stacks
(SURVEY.md §2 #19/#22/#23). Like the reference (which takes any ``Model``),
this wrapper accepts either container — MultiLayerNetwork or
ComputationGraph — through the uniform ``_dp_batch`` / ``_dp_loss`` /
``_dp_apply_updates`` protocol both implement.

TPU-native design: there are no worker threads, no parameter server, no
gradient quantization — one jit'd SPMD train step over a
``jax.sharding.Mesh``:

- params/opt-state: replicated (NamedSharding(P()))
- batch: sharded along the mesh 'data' axis (P('data'))
- XLA inserts the gradient all-reduce over ICI automatically from the
  sharding annotations (the scaling-book recipe). This is mathematically the
  reference's averaging with frequency=1 and supersedes its Aeron gradient-
  sharing path (SURVEY.md §5 maps all three mechanisms to psum).

``averaging_frequency > 1`` reproduces the reference's divergent-replica
semantics: each device takes k independent local steps on its own params
(shard_map + lax.scan over microbatches), then params AND updater state are
pmean-averaged (parity: averageUpdatersState ParallelWrapper.java:339).

Uneven batches are padded to a device multiple by duplicating rows, but the
pad rows carry a zero loss-weight (a per-example mask through the model's
mask-aware losses), so gradients equal the unpadded batch exactly — no
double-counting.

Multi-host: the same code scales over DCN by initializing
``jax.distributed`` (see deeplearning4j_tpu.parallel.distributed) — the mesh
then spans all hosts' devices and the collectives ride ICI within a pod and
DCN across pods. No NCCL/Aeron equivalent is needed.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, List

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from deeplearning4j_tpu.data.dataset import DataSet, MultiDataSet


def default_mesh(n_devices: Optional[int] = None, axis: str = "data") -> Mesh:
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))


class ParallelWrapper:
    """Data-parallel trainer wrapping a MultiLayerNetwork or ComputationGraph.

    Usage (parity: ParallelWrapper.Builder):
        pw = ParallelWrapper(net, workers=8, averaging_frequency=1)
        pw.fit(iterator)

    workers = number of mesh devices (defaults to all).
    averaging_frequency=1 → per-step gradient allreduce (recommended on TPU);
    >1 → reference-style local steps + periodic param/updater averaging.
    """

    def __init__(self, model, workers: Optional[int] = None,
                 averaging_frequency: int = 1, prefetch_buffer: int = 2,
                 mesh: Optional[Mesh] = None, model_axis: str = "model"):
        """``mesh`` may be 1-D ``('data',)`` (pure DP, the reference's
        capability bar) or 2-D ``('data', 'model')`` — a TPU-idiomatic
        extension: parameter output dims are sharded over the model axis
        (tensor parallelism) while the batch shards over data; XLA/GSPMD
        inserts the TP collectives. The reference has no TP (SURVEY §2
        parallelism inventory)."""
        self.model = model
        self.mesh = mesh if mesh is not None else default_mesh(workers)
        if "data" not in self.mesh.axis_names:
            raise ValueError(
                f"ParallelWrapper mesh needs a 'data' axis, got "
                f"{self.mesh.axis_names}")
        self.n_devices = self.mesh.shape["data"]   # batch shards over data
        if len(self.mesh.axis_names) > 1 and model_axis not in self.mesh.axis_names:
            # a multi-axis mesh whose extra axis doesn't match would silently
            # run pure DP with duplicate compute on the second axis
            raise ValueError(
                f"mesh has axes {self.mesh.axis_names} but model_axis="
                f"{model_axis!r} matches none of them")
        self.model_axis = model_axis if model_axis in self.mesh.axis_names \
            else None
        if self.model_axis is not None and averaging_frequency != 1:
            raise ValueError(
                "tensor parallelism (2-D mesh) requires "
                "averaging_frequency=1 (per-step sync)")
        self.averaging_frequency = max(1, int(averaging_frequency))
        self.prefetch_buffer = prefetch_buffer
        self._step_fn = None
        self._scan_fn = None

    # ------------------------------------------------------------------ build
    def _param_sharding(self, leaf, path=""):
        """TP placement for one weight leaf. The Megatron pairing rule
        (column-parallel Q/K/V & up-projections, row-parallel Wo/ff2/down,
        replicated 1-D vectors) lives in ``exec.param_spec`` — the same
        rule the execution core applies when its mesh has a model axis, so
        the wrapper and the default path can never disagree on placement."""
        if self.model_axis is None:
            return NamedSharding(self.mesh, P())
        from deeplearning4j_tpu.exec import param_spec
        return NamedSharding(self.mesh, param_spec(
            path, leaf, self.mesh.shape[self.model_axis],
            axis=self.model_axis))

    def _replicated(self, tree):
        """Place params: replicated (pure DP) or TP-sharded (2-D mesh)."""
        if self.model_axis is None:
            return jax.device_put(tree, NamedSharding(self.mesh, P()))

        def place(path, a):
            return jax.device_put(
                a, self._param_sharding(a, jax.tree_util.keystr(path)))
        return jax.tree_util.tree_map_with_path(place, tree)

    def _grad_update(self, params, state, opt_state, x, y, rng,
                     pad_mask=None, mf=None, ml=None):
        """The single train-step math shared by every DP path (per-step and
        scan, sync and averaging): grad of ``_dp_loss`` → ``_dp_apply_updates``.
        RNG derivation stays with each caller (the sync paths fold the
        iteration; the averaging paths additionally fold the device index so
        divergent replicas draw independent dropout masks)."""
        (loss, new_state), grads = jax.value_and_grad(
            self.model._dp_loss, has_aux=True)(params, state, x, y, rng,
                                               pad_mask, mf, ml)
        # TP meshes take the per-leaf path (see _dp_apply_updates: the
        # fused flat program would gather every TP shard)
        new_params, new_opt = self.model._dp_apply_updates(
            params, opt_state, grads,
            fused=None if self.model_axis is None else False)
        return new_params, new_state, new_opt, loss

    def _fold_iteration(self, it):
        return jax.random.fold_in(
            jax.random.PRNGKey(self.model.conf.global_conf.seed), it)

    def _build_sync_step(self):
        """averaging_frequency == 1: jit with sharding annotations; XLA emits
        the ICI all-reduce in backward."""
        mesh = self.mesh
        repl = NamedSharding(mesh, P())
        data_sh = NamedSharding(mesh, P("data"))

        def step(params, state, opt_state, x, y, it, pad_mask, mf, ml):
            return self._grad_update(params, state, opt_state, x, y,
                                     self._fold_iteration(it), pad_mask, mf, ml)

        if self.model_axis is not None:
            # TP x DP: params/opt were committed TP-sharded by _replicated
            # and the batch is committed data-sharded in fit(); jit follows
            # the committed input shardings and GSPMD inserts both the DP
            # gradient all-reduce and the TP collectives.
            return jax.jit(step, donate_argnums=(0, 1, 2))
        return jax.jit(
            step,
            in_shardings=(repl, repl, repl, data_sh, data_sh, None, data_sh,
                          data_sh, data_sh),
            out_shardings=(repl, repl, repl, repl),
            donate_argnums=(0, 1, 2))

    def _build_averaging_step(self):
        """averaging_frequency == k > 1: each device scans k local updates on
        its own divergent params, then params+opt state are pmean'd
        (parity: ParallelWrapper averaging + averageUpdatersState)."""
        mesh = self.mesh

        @partial(shard_map, mesh=mesh,
                 in_specs=(P(), P(), P(), P(None, "data"), P(None, "data"),
                           P(None, "data"), P()),
                 out_specs=(P(), P(), P(), P()),
                 check_vma=False)
        def step(params, state, opt_state, xs, ys, pad_masks, it):
            # xs leaves: (k, local_batch, ...) — microbatch axis leading,
            # batch axis sharded over 'data'
            def body(carry, inp):
                params, state, opt_state, j = carry
                x, y, pm = inp
                rng = jax.random.fold_in(self._fold_iteration(it + j),
                                         jax.lax.axis_index("data"))
                p, s, o, loss = self._grad_update(params, state, opt_state,
                                                  x, y, rng, pm)
                return (p, s, o, j + 1), loss

            (params, state, opt_state, _), losses = jax.lax.scan(
                body, (params, state, opt_state, 0), (xs, ys, pad_masks))
            # average divergent replicas (params + updater state + bn stats)
            params = jax.lax.pmean(params, "data")
            state = jax.lax.pmean(state, "data")
            opt_state = jax.lax.pmean(opt_state, "data")
            return params, state, opt_state, jax.lax.pmean(losses.mean(), "data")

        return jax.jit(step, donate_argnums=(0, 1, 2))

    def _build_sync_scan(self):
        """Device-resident multi-step sync DP: lax.scan over a leading step
        axis INSIDE the sharded jit. One dispatch trains ``n_steps``
        minibatches; XLA still inserts the per-step ICI gradient all-reduce
        from the sharding annotations. This is the DP analogue of the
        containers' ``fit_scan`` — per-step host dispatch is paid once per
        call instead of once per minibatch."""
        mesh = self.mesh
        repl = NamedSharding(mesh, P())
        step_data = NamedSharding(mesh, P(None, "data"))

        def inner(params, state, opt_state, xs, ys, it0):
            def body(carry, inp):
                params, state, opt_state, it = carry
                x, y = inp
                p, s, o, loss = self._grad_update(
                    params, state, opt_state, x, y, self._fold_iteration(it))
                return (p, s, o, it + 1), loss

            (p, s, o, _), losses = jax.lax.scan(
                body, (params, state, opt_state, it0), (xs, ys))
            return p, s, o, losses

        if self.model_axis is not None:
            # TP x DP: follow the committed input shardings (params TP-sharded
            # by _replicated, batches data-sharded by fit_scan).
            return jax.jit(inner, donate_argnums=(0, 1, 2))
        return jax.jit(
            inner,
            in_shardings=(repl, repl, repl, step_data, step_data, None),
            out_shardings=(repl, repl, repl, repl),
            donate_argnums=(0, 1, 2))

    def _build_averaging_scan(self):
        """Device-resident averaging-frequency DP: outer scan over rounds,
        inner scan over the k local (divergent-replica) steps of each round,
        params+updater state pmean'd at every round boundary — the
        reference's averaging semantics (ParallelWrapper.java:251-371) with
        all rounds in one compiled call."""
        mesh = self.mesh

        @partial(shard_map, mesh=mesh,
                 in_specs=(P(), P(), P(), P(None, None, "data"),
                           P(None, None, "data"), P()),
                 out_specs=(P(), P(), P(), P()),
                 check_vma=False)
        def step(params, state, opt_state, xs, ys, it0):
            # xs leaves: (rounds, k, local_batch, ...)
            def round_body(carry, inp):
                params, state, opt_state, it = carry
                xs_k, ys_k = inp

                def body(carry2, inp2):
                    params, state, opt_state, it = carry2
                    x, y = inp2
                    rng = jax.random.fold_in(self._fold_iteration(it),
                                             jax.lax.axis_index("data"))
                    p, s, o, loss = self._grad_update(params, state,
                                                      opt_state, x, y, rng)
                    return (p, s, o, it + 1), loss

                (params, state, opt_state, it), losses = jax.lax.scan(
                    body, (params, state, opt_state, it), (xs_k, ys_k))
                params = jax.lax.pmean(params, "data")
                state = jax.lax.pmean(state, "data")
                opt_state = jax.lax.pmean(opt_state, "data")
                return (params, state, opt_state, it), losses.mean()

            (params, state, opt_state, _), losses = jax.lax.scan(
                round_body, (params, state, opt_state, it0), (xs, ys))
            return params, state, opt_state, jax.lax.pmean(losses, "data")

        return jax.jit(step, donate_argnums=(0, 1, 2))

    def fit_scan(self, xs, ys):
        """Train ``xs.shape[0]`` minibatches in ONE compiled sharded call.

        ``xs``: (n_steps, batch, ...) features, ``ys``: (n_steps, batch, ...)
        labels; ``batch`` must divide evenly over the mesh's data axis.
        averaging_frequency=1 runs per-step gradient all-reduce;
        k>1 requires n_steps % k == 0 and averages params/updater state every
        k local steps (reference averaging semantics). Masked datasets go
        through ``fit`` (the per-step path handles masks exactly)."""
        model = self.model
        if getattr(model.conf, "backprop_type", "standard") == "tbptt":
            raise ValueError(
                "fit_scan runs full-sequence backprop; a net configured for "
                "truncated BPTT must use fit() (the tbptt chunking path)")
        if model.params is None:
            model.init()
        xs = jax.tree_util.tree_map(jnp.asarray, xs)
        ys = jax.tree_util.tree_map(jnp.asarray, ys)
        lead = jax.tree_util.tree_leaves(xs)[0]
        n_steps, batch = lead.shape[0], lead.shape[1]
        for leaf in jax.tree_util.tree_leaves((xs, ys)):
            if leaf.shape[:2] != (n_steps, batch):
                raise ValueError(
                    f"fit_scan leaves must share (n_steps, batch)="
                    f"{(n_steps, batch)}; got {leaf.shape[:2]}")
        if batch % self.n_devices != 0:
            raise ValueError(
                f"fit_scan batch {batch} must divide over {self.n_devices} "
                "devices; pad the batch or use fit() (which pads exactly)")
        model.params = self._replicated(model.params)
        model.state = self._replicated(model.state)
        model.opt_state = self._replicated(model.opt_state)
        if self.averaging_frequency == 1:
            if self._scan_fn is None:
                self._scan_fn = self._build_sync_scan()
            if self.model_axis is not None:
                sh = NamedSharding(self.mesh, P(None, "data"))
                xs = jax.tree_util.tree_map(
                    lambda a: jax.device_put(a, sh), xs)
                ys = jax.tree_util.tree_map(
                    lambda a: jax.device_put(a, sh), ys)
        else:
            k = self.averaging_frequency
            if n_steps % k != 0:
                raise ValueError(
                    f"n_steps={n_steps} must be a multiple of "
                    f"averaging_frequency={k} on the fit_scan path")
            reshape = lambda a: a.reshape((n_steps // k, k) + a.shape[1:])
            xs = jax.tree_util.tree_map(reshape, xs)
            ys = jax.tree_util.tree_map(reshape, ys)
            if self._scan_fn is None:
                self._scan_fn = self._build_averaging_scan()
        model.params, model.state, model.opt_state, losses = self._scan_fn(
            model.params, model.state, model.opt_state, xs, ys,
            jnp.asarray(model.iteration, jnp.int32))
        model.iteration += n_steps
        model._score = losses[-1]
        for lst in model.listeners:
            lst.iteration_done(model, model.iteration, model.epoch)
        return model

    # -------------------------------------------------------------------- fit
    def fit(self, data, epochs=1):
        """Train over the mesh. ``data``: iterator of DataSets (or list)."""
        model = self.model
        if model.params is None:
            model.init()
        model.params = self._replicated(model.params)
        model.state = self._replicated(model.state)
        model.opt_state = self._replicated(model.opt_state)

        if self.averaging_frequency == 1:
            if self._step_fn is None:
                self._step_fn = self._build_sync_step()
            data_sh = NamedSharding(self.mesh, P("data"))

            # device-side normalizer: raw (e.g. uint8) batches over the
            # host->device link, transform on chip (data/normalizers.py)
            from deeplearning4j_tpu.data.iterators import \
                resolve_pre_processor
            pp = resolve_pre_processor(data)
            dev_fn = host_pp = None
            if pp is not None and getattr(pp, "device_side", False):
                f = pp.as_device_transform()
                if f is not None:
                    dev_fn = jax.jit(f)
                else:
                    host_pp = pp   # device-side requested, not expressible

            def fit_one(ds):
                x, y, pad_mask, mf, ml = self._prepare(ds)
                if dev_fn is not None:
                    x = jax.tree_util.tree_map(
                        lambda a: dev_fn(jnp.asarray(a)), x)
                if self.model_axis is not None:
                    x, y, pad_mask, mf, ml = jax.tree_util.tree_map(
                        lambda a: jax.device_put(jnp.asarray(a), data_sh),
                        (x, y, pad_mask, mf, ml))
                model.params, model.state, model.opt_state, loss = \
                    self._step_fn(model.params, model.state, model.opt_state,
                                  x, y, jnp.asarray(model.iteration, jnp.int32),
                                  pad_mask, mf, ml)
                model._score = loss
                model.iteration += 1
                for lst in model.listeners:
                    lst.iteration_done(model, model.iteration, model.epoch)

            # auto-chunk runs of scan-able batches onto the device-resident
            # sharded multi-step path (same design as
            # BaseNetwork._fit_stream: one compiled call per chunk
            # instead of one host dispatch per minibatch)
            chunkable = (getattr(model.conf, "backprop_type", "standard")
                         != "tbptt")
            for _ in range(epochs):
                if hasattr(data, "reset"):
                    data.reset()
                buf, shape = [], None

                def flush():
                    nonlocal buf, shape
                    if not buf:
                        return
                    if len(buf) == 1:
                        fit_one(buf[0])
                    else:
                        # _dp_batch returns numpy VIEWS of the DataSet
                        # arrays — re-deriving them here costs nothing and
                        # keeps the buffer to just the DataSets
                        views = [model._dp_batch(d)[:2] for d in buf]
                        xs = jax.tree_util.tree_map(
                            lambda *a: np.stack(a), *[v[0] for v in views])
                        ys = jax.tree_util.tree_map(
                            lambda *a: np.stack(a), *[v[1] for v in views])
                        if dev_fn is not None:
                            xs = jax.tree_util.tree_map(
                                lambda a: dev_fn(jnp.asarray(a)), xs)
                        self.fit_scan(xs, ys)
                    buf, shape = [], None

                for ds in data:
                    dsn = ds if isinstance(ds, (DataSet, MultiDataSet)) \
                        else DataSet(*ds)
                    if host_pp is not None:
                        dsn = host_pp.pre_process(dsn)
                    x, y, mf, ml = model._dp_batch(dsn)
                    b = jax.tree_util.tree_leaves(x)[0].shape[0]
                    if (not chunkable or mf is not None or ml is not None
                            or b % self.n_devices != 0):
                        flush()
                        fit_one(dsn)
                        continue
                    key = tuple(a.shape for a in
                                jax.tree_util.tree_leaves((x, y)))
                    if shape is not None and key != shape:
                        flush()
                    shape = key
                    buf.append(dsn)
                    per = sum(a.nbytes for a in
                              jax.tree_util.tree_leaves((x, y)))
                    if len(buf) >= max(1, min(64, (256 << 20) //
                                              max(1, per))):
                        flush()
                flush()
                model.epoch += 1
        else:
            if self._step_fn is None:
                self._step_fn = self._build_averaging_step()
            k = self.averaging_frequency
            for _ in range(epochs):
                if hasattr(data, "reset"):
                    data.reset()
                micro = []
                for ds in data:
                    micro.append(ds)
                    if len(micro) == k:
                        self._fit_avg_chunk(micro)
                        micro = []
                if micro:
                    self._fit_avg_chunk(micro)
                model.epoch += 1
        return model

    def _prepare(self, ds):
        """DataSet → numpy (x, y, pad_mask, mf, ml) padded to a device
        multiple; pad rows get zero loss-weight. The DataSet's own masks are
        carried through (combined with the pad mask inside ``_dp_loss``)."""
        if not isinstance(ds, (DataSet, MultiDataSet)):
            ds = DataSet(*ds)
        x, y, mf, ml = self.model._dp_batch(ds)
        b = jax.tree_util.tree_leaves(x)[0].shape[0]
        pad_mask = np.ones((b,), np.float32)
        if b % self.n_devices != 0:
            pad = self.n_devices - (b % self.n_devices)
            x = jax.tree_util.tree_map(self._pad_rows, x)
            y = jax.tree_util.tree_map(self._pad_rows, y)
            mf = jax.tree_util.tree_map(self._pad_rows, mf)
            ml = jax.tree_util.tree_map(self._pad_rows, ml)
            pad_mask = np.concatenate([pad_mask, np.zeros((pad,), np.float32)])
        return x, y, pad_mask, mf, ml

    def _fit_avg_chunk(self, micro: List):
        model = self.model
        # microbatches may differ in size (last batch of an epoch): pad each
        # to the chunk max by wrapping (zero loss-weight), then to a device
        # multiple
        prepared = [self._prepare(ds) for ds in micro]
        if any(p[3] is not None or p[4] is not None for p in prepared):
            raise NotImplementedError(
                "averaging_frequency > 1 does not support per-example masks; "
                "use averaging_frequency=1 (sync gradient allreduce), which "
                "handles masked data exactly")
        max_b = max(jax.tree_util.tree_leaves(p[0])[0].shape[0]
                    for p in prepared)

        def widen(arr, m):
            arr = np.asarray(arr)
            b = arr.shape[0]
            if b >= m:
                return arr
            idx = np.arange(m - b) % b  # wrap rows; mask zero-weights them
            return np.concatenate([arr, arr[idx]])

        xs, ys, pms = [], [], []
        for x, y, pm, _, _ in prepared:
            b = pm.shape[0]
            if b < max_b:
                x = jax.tree_util.tree_map(lambda a: widen(a, max_b), x)
                y = jax.tree_util.tree_map(lambda a: widen(a, max_b), y)
                pm = np.concatenate([pm, np.zeros((max_b - b,), np.float32)])
            xs.append(x)
            ys.append(y)
            pms.append(pm)
        xs = jax.tree_util.tree_map(lambda *a: np.stack(a), *xs)
        ys = jax.tree_util.tree_map(lambda *a: np.stack(a), *ys)
        pms = np.stack(pms)
        model.params, model.state, model.opt_state, loss = self._step_fn(
            model.params, model.state, model.opt_state, xs, ys, pms,
            jnp.asarray(model.iteration, jnp.int32))
        model._score = loss
        model.iteration += len(micro)
        for lst in model.listeners:
            lst.iteration_done(model, model.iteration, model.epoch)

    def _pad_rows(self, arr):
        n = self.n_devices
        arr = np.asarray(arr)
        b = arr.shape[0]
        if b % n == 0:
            return arr
        pad = n - (b % n)
        idx = np.arange(pad) % b  # wrap rows; pad_mask zero-weights them
        return np.concatenate([arr, arr[idx]])
