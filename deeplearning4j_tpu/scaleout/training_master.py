"""TrainingMaster SPI + implementations.

Parity: reference spark/api/TrainingMaster.java (SPI),
spark/impl/paramavg/ParameterAveragingTrainingMaster.java:62 (sync param
averaging with averagingFrequency/batchSizePerWorker/aggregationDepth),
spark/dl4j-spark-parameterserver training/SharedTrainingMaster.java:55
(threshold-encoded async gradient sharing over Aeron), and
spark/api/stats/SparkTrainingStats (timings).

TPU design: both masters compile ONE sharded train step over the device
mesh. ParameterAveraging maps to local steps + pmean every
``averaging_frequency`` iterations (ParallelWrapper's averaging step — the
math the Spark master computed with treeAggregate; ``aggregation_depth`` is
obsolete because XLA's all-reduce is already a tree/ring over ICI).
SharedTraining maps to per-step threshold-encoded updates exchanged through
EncodedGradientsAccumulator (parallel/compression.py) — semantics parity
for the reference's quantized path; on real pods dense psum is faster and
is what ParameterAveraging(frequency=1) emits.
"""

from __future__ import annotations

import time
from typing import Optional, List, Dict

import numpy as np
import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper, default_mesh
from deeplearning4j_tpu.parallel.compression import EncodedGradientsAccumulator


class TrainingStats:
    """Per-phase wall-clock stats (parity: spark/api/stats/SparkTrainingStats
    + StatsCalculationHelper). Keys are phase names; values lists of ms."""

    def __init__(self):
        self.timings: Dict[str, List[float]] = {}

    def time(self, key):
        stats = self

        class _Ctx:
            def __enter__(self):
                self.t0 = time.perf_counter()
                return self

            def __exit__(self, *a):
                stats.timings.setdefault(key, []).append(
                    (time.perf_counter() - self.t0) * 1e3)

        return _Ctx()

    def summary(self) -> str:
        lines = []
        for k, v in sorted(self.timings.items()):
            lines.append(f"{k}: n={len(v)} total={sum(v):.1f}ms "
                         f"mean={np.mean(v):.2f}ms")
        return "\n".join(lines)


class TrainingMaster:
    """SPI (parity: spark/api/TrainingMaster.java). Implementations define
    how a dataset is partitioned over the mesh and how replicas are kept in
    sync."""

    def __init__(self):
        self.stats: Optional[TrainingStats] = None

    def set_collect_training_stats(self, flag: bool):
        self.stats = TrainingStats() if flag else None
        return self

    def get_training_stats(self) -> Optional[TrainingStats]:
        return self.stats

    def execute_training(self, net, data):
        raise NotImplementedError


class ParameterAveragingTrainingMaster(TrainingMaster):
    """Synchronous parameter averaging (parity:
    ParameterAveragingTrainingMaster.java:62; builder knobs
    batchSizePerWorker :, averagingFrequency, repartitioning). Runs the
    mesh-sharded train step; with frequency=1 this is a per-step dense
    gradient all-reduce (strictly better than the reference's average-
    after-k semantics and its own frequency=1 case); with frequency=k the
    replicas diverge k local steps then params+updater state are pmean'd —
    bit-for-bit the reference's semantics."""

    def __init__(self, batch_size_per_worker: int = 16,
                 averaging_frequency: int = 1,
                 workers: Optional[int] = None,
                 mesh=None, repartition_data: bool = True):
        super().__init__()
        self.batch_size_per_worker = batch_size_per_worker
        self.averaging_frequency = averaging_frequency
        self.workers = workers
        self.mesh = mesh
        self.repartition_data = repartition_data
        self._pw: Optional[ParallelWrapper] = None

    def _wrapper(self, net):
        if self._pw is None or self._pw.model is not net:
            self._pw = ParallelWrapper(
                net, workers=self.workers, mesh=self.mesh,
                averaging_frequency=self.averaging_frequency)
        return self._pw

    def execute_training(self, net, data):
        pw = self._wrapper(net)
        if self.repartition_data and self.batch_size_per_worker:
            # one step consumes batch_size_per_worker × workers examples
            # (each mesh device = one Spark-executor-equivalent)
            from deeplearning4j_tpu.scaleout.cluster import repartition
            if self.stats is not None:
                with self.stats.time("repartition"):
                    data = repartition(
                        list(data),
                        self.batch_size_per_worker * pw.n_devices)
            else:
                data = repartition(
                    list(data), self.batch_size_per_worker * pw.n_devices)
        if self.stats is not None:
            with self.stats.time("fit"):
                pw.fit(data)
        else:
            pw.fit(data)
        return net


class SharedTrainingMaster(TrainingMaster):
    """Gradient-sharing with threshold encoding (parity:
    SharedTrainingMaster.java:55 + WiredEncodingHandler.java:96). Each
    worker computes its own gradient, threshold-encodes it
    (|g| >= threshold → sign*threshold sparse message, residual carried),
    broadcasts the message, and applies everyone's sparse updates locally —
    the Strom-2015 scheme the reference ships over Aeron UDP.

    The exchange here is the in-process EncodedGradientsAccumulator (device
    math identical to the wire path; SURVEY.md §5 maps Aeron to collectives
    — sync exchange replaces async staleness by design, documented
    equivalence). Workers are logical (round-robin over minibatches), so
    semantics can be validated on one chip or a CPU mesh."""

    def __init__(self, threshold: float = 1e-3, min_threshold: float = 1e-5,
                 threshold_step: float = 1e-5, shake_frequency: int = 0,
                 workers: int = 2, batch_size_per_worker: int = 16,
                 learning_rate: Optional[float] = None, mesh=None,
                 capacity_fraction: float = 0.05):
        """``mesh``: when given, workers are REAL mesh devices and the whole
        encode→exchange→apply cycle runs as one compiled shard_map program
        (threshold messages summed with lax.psum over ICI) instead of the
        host-side logical-replica loop — see execute_training_collective."""
        super().__init__()
        self.threshold = threshold
        self.min_threshold = min_threshold
        self.threshold_step = threshold_step
        self.shake_frequency = shake_frequency
        self.workers = workers
        self.batch_size_per_worker = batch_size_per_worker
        self.learning_rate = learning_rate
        self.mesh = mesh
        self.capacity_fraction = capacity_fraction
        self._net = None
        self._acc: Optional[EncodedGradientsAccumulator] = None
        self._grad_fn = None
        self._collective_fn = None
        self._residuals = None
        self._thresholds = None
        self._unravel = None
        self._n_params = None

    def _setup(self, net):
        self._net = net
        flat, unravel = ravel_pytree(net.params)
        self._n_params = flat.shape[0]
        self._unravel = unravel
        self._acc = EncodedGradientsAccumulator(
            self.workers, self._n_params, threshold=self.threshold,
            min_threshold=self.min_threshold,
            threshold_step=self.threshold_step,
            shake_frequency=self.shake_frequency)

        def grad(vec, state, x, y, lr):
            loss, g = jax.value_and_grad(
                lambda v: net._loss(unravel(v), state, x, y, None,
                                    None, None)[0])(vec)
            # the reference encodes the post-updater UPDATE, not the raw
            # gradient (SharedTrainingWrapper applies the updater first;
            # EncodingHandler thresholds update magnitudes) — so scale by
            # the learning rate before encoding.
            return loss, lr * g

        self._grad_fn = jax.jit(grad)

    # ------------------------------------------------- collective exchange
    def _build_collective_epoch(self, net, n, unravel, capacity):
        """The Strom-2015 cycle as ONE shard_map program: per device —
        local grad on its batch shard, residual add, threshold encode,
        psum the sparse messages (≡ every worker applying every peer's
        message exactly once), apply, adapt threshold. Replicas stay
        bit-identical because each applies the same summed message; the
        residual and threshold remain per-worker state, as in the
        reference's per-executor EncodingHandler."""
        from functools import partial as _partial
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from deeplearning4j_tpu.parallel.compression import (
            adapt_threshold_jnp, threshold_encode, threshold_decode)
        mesh = self.mesh
        step = jnp.float32(self.threshold_step)
        min_thr = jnp.float32(self.min_threshold)

        # residual/threshold are PER-DEVICE state (the reference keeps one
        # EncodingHandler per executor): leading device axis, sharded in and
        # out, persisted across execute_training calls by the caller
        @_partial(shard_map, mesh=mesh,
                  in_specs=(P(), P("data"), P("data"), P(None, "data"),
                            P(None, "data"), P()),
                  out_specs=(P(), P("data"), P("data"), P()),
                  check_vma=False)
        def epoch(vec, residual, threshold, xs, ys, lr):
            residual = residual[0]          # (1, n) shard → (n,)
            threshold = threshold[0]

            def body(carry, inp):
                vec, residual, threshold = carry
                x, y = inp
                loss, g = jax.value_and_grad(
                    lambda v: net._loss(unravel(v), net.state, x, y, None,
                                        None, None)[0])(vec)
                u = lr * g + residual
                idx, vals, count = threshold_encode(u, threshold, capacity)
                msg = threshold_decode(idx, vals, n)
                residual = u - msg
                vec = vec - jax.lax.psum(msg, "data")
                # EncodingHandler._adapt via the shared policy (per
                # worker, as per executor in the reference)
                threshold = adapt_threshold_jnp(
                    threshold, count, capacity, step=step,
                    min_threshold=min_thr)
                return (vec, residual, threshold), loss
            (vec, residual, threshold), losses = jax.lax.scan(
                body, (vec, residual, threshold), (xs, ys))
            return (vec, residual[None], threshold[None],
                    jax.lax.pmean(losses.mean(), "data"))

        return jax.jit(epoch)

    def execute_training_collective(self, net, data):
        """Mesh path: stack the (already per-worker-sized) minibatches into
        (S, B_global, ...) with B_global sharded over the mesh and run the
        whole exchange compiled (no host round trips)."""
        flat, unravel = ravel_pytree(net.params)
        n = int(flat.shape[0])
        n_dev_state = self.mesh.devices.size
        capacity = max(1, min(n, int(n * self.capacity_fraction)))
        if self._collective_fn is None or self._net is not net:
            self._net = net
            self._collective_fn = self._build_collective_epoch(
                net, n, unravel, capacity)
            self._unravel = unravel
            # per-device Strom state, carried ACROSS execute_training calls
            # (epoch boundaries must not drop accumulated sub-threshold mass)
            self._residuals = jnp.zeros((n_dev_state, n), jnp.float32)
            self._thresholds = jnp.full((n_dev_state,), self.threshold,
                                        jnp.float32)
        lr = self.learning_rate
        if lr is None:
            upd = net.conf.global_conf.updater
            lr = getattr(upd, "learning_rate", 0.01)
        n_dev = self.mesh.devices.size
        batches = [ds if isinstance(ds, DataSet) else DataSet(*ds)
                   for ds in data]
        from deeplearning4j_tpu.scaleout.cluster import repartition
        batches = repartition(batches, self.batch_size_per_worker * n_dev)
        # drop a trailing ragged batch (shard_map needs equal shards)
        full = [b for b in batches
                if b.features.shape[0] == self.batch_size_per_worker * n_dev]
        if not full:
            raise ValueError(
                f"not enough data for one global batch of "
                f"{self.batch_size_per_worker * n_dev}")
        xs = jnp.asarray(np.stack([b.features for b in full]))
        ys = jnp.asarray(np.stack([b.labels for b in full]))
        vec, self._residuals, self._thresholds, loss = self._collective_fn(
            flat, self._residuals, self._thresholds, xs, ys,
            jnp.float32(lr))
        self.threshold = float(jnp.mean(self._thresholds))  # summary only
        net.params = self._unravel(vec)
        net.iteration += len(full)
        net._score = loss
        return net

    def execute_training(self, net, data):
        """Round-robins minibatches over per-worker model replicas; each
        worker computes its gradient on ITS replica, broadcasts the encoded
        update, and applies every pending update (its own + peers') to its
        replica exactly once — SharedTrainingWrapper.run semantics. Replicas
        stay in sync because the exchange is synchronous (SURVEY.md §5:
        async Aeron staleness intentionally not reproduced).

        With a ``mesh``, routes to execute_training_collective (the
        compiled shard_map exchange — the production path)."""
        if self.mesh is not None:
            return self.execute_training_collective(net, data)
        if self._acc is None or self._net is not net:
            self._setup(net)
        lr = self.learning_rate
        if lr is None:
            upd = net.conf.global_conf.updater
            lr = getattr(upd, "learning_rate", 0.01)
        if self.batch_size_per_worker:
            from deeplearning4j_tpu.scaleout.cluster import repartition
            data = repartition(list(data), self.batch_size_per_worker)
        vec0, _ = ravel_pytree(net.params)
        replicas = [vec0] * self.workers
        w = 0
        losses = []
        for ds in data:
            if not isinstance(ds, DataSet):
                ds = DataSet(*ds)
            x, y = jnp.asarray(ds.features), jnp.asarray(ds.labels)
            loss, u = self._grad_fn(replicas[w], net.state, x, y, lr)
            losses.append(float(loss))
            self._acc.store_update(w, u)
            # drain this worker's queue: every message lands exactly once
            # per replica
            replicas[w] = replicas[w] - self._acc.apply_update(w)
            w = (w + 1) % self.workers
            net.iteration += 1
        # flush remaining queued updates so all replicas converge, then
        # average (they are near-identical; averaging is the reference's
        # final transfer of the best model back to the source)
        for w2 in range(self.workers):
            replicas[w2] = replicas[w2] - self._acc.apply_update(w2)
        vec = sum(replicas) / self.workers
        net.params = self._unravel(vec)
        net._score = float(np.mean(losses)) if losses else float("nan")
        return net
