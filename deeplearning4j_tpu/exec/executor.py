"""The execution core: every XLA program in the repo compiles here.

``Executor.jit`` is the one wrapper the four compile sites use — the
train-step / ``fit_scan`` programs in both model containers, the
bucketed serving forward, and the continuous-batching decode step. A
compile site declares WHAT each argument is (``"params"``, ``"repl"``,
``"batch"``, ``"step_batch"``, ``"slots"``) and the executor owns HOW
that maps onto the mesh:

- params / updater state / model state: replicated on a pure-DP mesh,
  Megatron TP placement (``param_spec``) when the ``model`` axis > 1 —
  updater-state leaves co-shard with the param whose shape they mirror;
- batch-like args: sharded over ``data`` when the leading rows divide
  the axis AND each shard keeps at least ``min_rows_per_shard`` rows
  (sharding 4-row batches buys nothing and costs collectives — the
  threshold is the measured crossover knob, see docs/SHARDING.md);
  otherwise the call runs the exact single-device program it runs
  today. The decision is a pure function of the argument shapes, so a
  given shape always maps to the same compiled program and the
  trace-count accounting the tests pin (`_note_compile`/`_note_trace`)
  is unchanged;
- ``slots`` args (decode state trees): per-sequence rows — useful to
  shard at 1 row/shard, so they get their own threshold, and KV-cache
  leaves additionally TP-shard their feature dim when ``model`` > 1.

On a 1-device mesh ``Executor.jit`` RETURNS ``jax.jit(fn, ...)``
itself — not a wrapper — so the single-device path is byte-identical
to the pre-executor code and compiles zero new programs.
"""

import os
import threading
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.exec.mesh import (DATA_AXIS, MODEL_AXIS,
                                          default_mesh)

# argument/output spec vocabulary
PARAMS = "params"          # weight tree: replicated or Megatron TP
STATE = "state"            # model state (BN stats): replicated
OPT = "opt"                # updater state: co-sharded with params
REPL = "repl"              # replicate (scalars, loss)
BATCH = "batch"            # shard dim 0 over 'data' (x, y, masks)
STEP_BATCH = "step_batch"  # shard dim 1 over 'data' ((steps, batch, ...))
SLOTS = "slots"            # decode state: dim 0 = slot rows, KV dims TP
AUX = "aux"                # small replicated side-outputs (telemetry):
                           # never donated, never sharded — a fused
                           # (L, C) stats array rides the step program
                           # without perturbing its main-output layout

# set while the body of a mesh program is being traced: what it traces
# goes to the SPMD partitioner, which exec/routing.py needs to know
_PARTITIONED = threading.local()


def tracing_partitioned() -> bool:
    """True inside the traced body of a program :meth:`Executor.jit`
    compiles with mesh shardings (mesh > 1), whoever triggers the trace —
    the step's first call or a registry relower."""
    return getattr(_PARTITIONED, "on", False)


_ROW_TOKENS = ("Wo", "ff2", "down")
_COL_TOKENS = ("Wq", "Wk", "Wv", "ff1", "up")


def param_spec(path: str, leaf, model_size: int,
               axis: str = MODEL_AXIS) -> P:
    """Megatron TP placement for one weight leaf (the GSPMD annotation;
    XLA inserts the collectives, correctness never depends on it):
    column-parallel (shard the output/last dim) for Q/K/V, FFN
    up-projections and generic kernels; row-parallel (shard the
    input/first dim) for the pair's second half — ``Wo``/``ff2``/
    ``down`` by name or a wide->narrow shape; 1-D vectors replicate."""
    nd = getattr(leaf, "ndim", 0)
    if model_size <= 1 or nd < 2:
        return P()
    row_name = any(t in path for t in _ROW_TOKENS)
    row_shape = leaf.shape[0] > leaf.shape[-1]
    if (row_name or (row_shape
                     and not any(t in path for t in _COL_TOKENS))) \
            and leaf.shape[0] % model_size == 0 \
            and leaf.shape[0] >= model_size:
        return P(*([axis] + [None] * (nd - 1)))
    if leaf.shape[-1] % model_size == 0 and leaf.shape[-1] >= model_size:
        return P(*([None] * (nd - 1) + [axis]))
    return P()


def _slot_spec(leaf, data_ok: bool, model_size: int) -> P:
    """Decode-state leaf: slot rows over 'data', and (KV caches — any
    leaf with a wide trailing feature dim) the last dim over 'model'."""
    nd = getattr(leaf, "ndim", 0)
    lead = DATA_AXIS if (data_ok and nd >= 1) else None
    if (model_size > 1 and nd >= 2
            and leaf.shape[-1] % model_size == 0
            and leaf.shape[-1] >= model_size):
        return P(*([lead] + [None] * (nd - 2) + [MODEL_AXIS]))
    if nd == 0:
        return P()
    return P(*([lead] + [None] * (nd - 1)))


def _row_counts(tree, axis: int) -> set:
    """The distinct sizes along ``axis`` over a tree's array leaves."""
    return {leaf.shape[axis] for leaf in jax.tree_util.tree_leaves(tree)
            if getattr(leaf, "ndim", 0) > axis}


class Executor:
    """One mesh + one policy for turning step functions into programs."""

    def __init__(self, mesh: Optional[Mesh] = None, *,
                 min_rows_per_shard: Optional[int] = None,
                 min_slots_per_shard: Optional[int] = None,
                 precision: Optional[str] = None,
                 train_precision: Optional[str] = None):
        self.mesh = default_mesh() if mesh is None else mesh
        self.data_size = (self.mesh.shape[DATA_AXIS]
                          if DATA_AXIS in self.mesh.axis_names else 1)
        self.model_size = (self.mesh.shape[MODEL_AXIS]
                           if MODEL_AXIS in self.mesh.axis_names else 1)
        env = os.environ.get("DL4JTPU_MIN_ROWS_PER_SHARD")
        self.min_rows = int(env) if min_rows_per_shard is None and env \
            else (16 if min_rows_per_shard is None
                  else int(min_rows_per_shard))
        self.min_slots = 2 if min_slots_per_shard is None \
            else int(min_slots_per_shard)
        # declarative serving precision: every engine built against this
        # executor (bucketed forward, decode step, replica --checkpoint
        # loads) inherits it without per-caller code (docs/QUANTIZATION.md)
        from deeplearning4j_tpu.quant import resolve_precision
        self.precision = resolve_precision(
            precision if precision is not None
            else os.environ.get("DL4JTPU_PRECISION"))
        # declarative TRAINING precision: 'bf16' casts activations+params
        # to bfloat16 in the fit-path forward of every f32 model built
        # against this executor (loss and updater math stay f32 — the MXU
        # accumulates bf16 matmuls in f32, docs/TRAINING_PERF.md). Read at
        # trace time: containers rebuilt against a new executor pick it up.
        tp = (train_precision if train_precision is not None
              else os.environ.get("DL4JTPU_TRAIN_PRECISION")) or "f32"
        tp = tp.strip().lower()
        if tp not in ("f32", "float32", "bf16", "bfloat16"):
            raise ValueError(
                f"train_precision must be 'f32' or 'bf16', got {tp!r}")
        self.train_precision = "bf16" if tp in ("bf16", "bfloat16") else "f32"
        try:
            from deeplearning4j_tpu.monitor.metrics import get_registry
            get_registry().gauge(
                "dl4jtpu_train_precision_bf16",
                "1 when the executor's training-precision policy is bf16"
            ).set(1.0 if self.train_precision == "bf16" else 0.0)
        except Exception:
            pass

    @property
    def train_dtype(self):
        """The compute dtype the train-precision policy imposes on the fit
        path (None = storage dtype, i.e. no cast)."""
        import jax.numpy as jnp
        return jnp.bfloat16 if self.train_precision == "bf16" else None

    def prepare_params(self, tree, precision: Optional[str] = None):
        """Apply the serving-precision policy to a weight tree: per-channel
        weight-only quantization for 'int8'/'fp8', the identity (same
        objects, bitwise f32 path) for 'f32'. Engines call this once at
        load/swap time — never per request."""
        from deeplearning4j_tpu.quant import quantize_tree
        p = precision if precision is not None else self.precision
        return quantize_tree(tree, p)

    # ------------------------------------------------------------- shardings
    @property
    def is_single(self) -> bool:
        return self.mesh.size == 1

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def _named(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def param_shardings(self, tree):
        """Per-leaf NamedSharding tree for a weight pytree (replicated
        unless the mesh has a model axis)."""
        if self.model_size <= 1:
            return self.replicated()

        def place(path, leaf):
            return self._named(param_spec(jax.tree_util.keystr(path), leaf,
                                          self.model_size))
        return jax.tree_util.tree_map_with_path(place, tree)

    def put_params(self, tree):
        """Commit a weight tree to its mesh placement (used by
        ParallelWrapper and TP setups before the first step)."""
        if self.model_size <= 1:
            return jax.device_put(tree, self.replicated())
        return jax.tree_util.tree_map_with_path(
            lambda p, a: jax.device_put(
                a, self._named(param_spec(jax.tree_util.keystr(p), a,
                                          self.model_size))), tree)

    def _state_shardings(self, tree, params):
        """Updater/model state co-sharded with params: a leaf whose shape
        matches a TP-sharded weight (momentum/velocity mirror their
        param) takes that weight's spec; everything else replicates."""
        if self.model_size <= 1:
            return self.replicated()
        by_shape = {}
        def note(path, leaf):
            sp = param_spec(jax.tree_util.keystr(path), leaf,
                            self.model_size)
            by_shape.setdefault(getattr(leaf, "shape", None), sp)
        jax.tree_util.tree_map_with_path(note, params)
        return jax.tree_util.tree_map(
            lambda leaf: self._named(
                by_shape.get(getattr(leaf, "shape", None), P())), tree)

    def shardable_rows(self, n: int, *, min_rows: Optional[int] = None) \
            -> bool:
        mr = self.min_rows if min_rows is None else min_rows
        return (self.data_size > 1 and n % self.data_size == 0
                and n // self.data_size >= mr)

    def batch_sharding(self, tree, *, step_axis: bool = False):
        """Where :meth:`jit` will want a batch-like argument, known ahead
        of the call (the input prefetcher stages batches there, so a
        batch never lands whole on the default device to be resharded):
        the ``data``-axis sharding a BATCH — or with ``step_axis`` a
        (steps, batch, ...) STEP_BATCH — spec resolves to when the rows
        of ``tree``'s leaves shard, else None for the default device,
        where the single-device program runs."""
        rows = _row_counts(tree, 1 if step_axis else 0)
        if len(rows) == 1 and self.shardable_rows(next(iter(rows))):
            return self._named(P(None, DATA_AXIS) if step_axis
                               else P(DATA_AXIS))
        return self.replicated() if self.model_size > 1 else None

    # ------------------------------------------------------------------ jit
    def jit(self, fn, *, in_specs: Optional[Sequence] = None,
            out_specs: Optional[Sequence] = None, donate_argnums=(),
            static_argnums=()):
        """Compile ``fn`` against the mesh. ``in_specs``/``out_specs``
        name one spec per positional argument / output (see module
        docstring); each spec is applied as a pytree prefix, so an
        argument may be any tree (a list of graph inputs, an optional
        mask, a decode-state tree, None).

        mesh.size == 1 → returns ``jax.jit`` directly (the special case
        the trace-count tests pin: zero wrapper, zero new programs).
        """
        if self.is_single or in_specs is None:
            return jax.jit(fn, donate_argnums=donate_argnums,
                           static_argnums=static_argnums)
        if static_argnums:
            raise ValueError("static_argnums is only supported on the "
                             "single-device path")
        in_specs = tuple(in_specs)
        cache = {}
        placement = {}      # shard_data -> in_shardings of a mesh program

        def _rows(args):
            """Leading batch rows seen by the data-sharded args; None when
            absent or inconsistent (→ replicate)."""
            dims = set()
            for spec, a in zip(in_specs, args):
                if spec not in (BATCH, STEP_BATCH, SLOTS):
                    continue
                dims |= _row_counts(a, 1 if spec == STEP_BATCH else 0)
            if len(dims) != 1:
                return None
            return next(iter(dims))

        def _build(shard_data, args):
            if not shard_data and self.model_size <= 1:
                # exact single-device program (today's path, on the
                # default device); GSPMD never sees it
                return jax.jit(fn, donate_argnums=donate_argnums)
            params_args = [a for s, a in zip(in_specs, args)
                           if s == PARAMS]
            params_tree = params_args[0] if params_args else None

            def resolve(spec, arg):
                if spec == PARAMS:
                    return self.param_shardings(arg)
                if spec == OPT:
                    return self._state_shardings(arg, params_tree)
                if spec == BATCH:
                    return self._named(P(DATA_AXIS)) if shard_data \
                        else self.replicated()
                if spec == STEP_BATCH:
                    return self._named(P(None, DATA_AXIS)) if shard_data \
                        else self.replicated()
                if spec == SLOTS:
                    return jax.tree_util.tree_map(
                        lambda leaf: self._named(_slot_spec(
                            leaf, shard_data, self.model_size)), arg)
                return self.replicated()

            in_sh = placement[shard_data] = tuple(
                resolve(s, a) for s, a in zip(in_specs, args))
            out_sh = None
            if out_specs is not None:
                # outputs resolve against the input trees they mirror
                # (a step's new params/state/opt/dstate have the same
                # structure as the input they update)
                by_spec = {}
                for s, a in zip(in_specs, args):
                    by_spec.setdefault(s, a)
                resolved = [resolve(s, by_spec.get(s)) for s in out_specs]
                # single-output functions take the sharding directly
                # (a 1-tuple would claim a tuple-shaped output pytree)
                out_sh = resolved[0] if len(resolved) == 1 \
                    else tuple(resolved)
            def partitioned(*a):
                prev = tracing_partitioned()
                _PARTITIONED.on = True
                try:
                    return fn(*a)
                finally:
                    _PARTITIONED.on = prev

            return jax.jit(partitioned, in_shardings=in_sh,
                           out_shardings=out_sh,
                           donate_argnums=donate_argnums)

        slot_specs = any(s == SLOTS for s in in_specs)
        min_rows = self.min_slots if slot_specs else None

        def wrapped(*args):
            rows = _rows(args)
            shard = rows is not None and self.shardable_rows(
                rows, min_rows=min_rows)
            jf = cache.get(shard)
            if jf is None:
                jf = cache[shard] = _build(shard, args)
            in_sh = placement.get(shard)
            if in_sh is not None:
                # commit every argument to its mesh placement BEFORE the
                # call: an array's aval carries the mesh it lives on, so
                # host batches and fresh (single-device) params would
                # trace one program and the step's own mesh-resident
                # outputs a second, identical one
                args = jax.device_put(args, in_sh)
            return jf(*args)

        wrapped._dl4jtpu_exec_wrapper = True   # introspection for tests
        wrapped._exec_cache = cache
        return wrapped

    # ------------------------------------------------------------- programs
    @property
    def programs(self):
        """The process-wide compiled-program registry (``exec.programs``):
        every compile site records cost/memory analysis of the programs
        it built through this executor — ``GET /programs`` and the
        ``dl4jtpu_program_*`` gauges read from here."""
        from deeplearning4j_tpu.exec.programs import get_programs
        return get_programs()

    def register_program(self, caller, key, fn, args, compile_seconds=None,
                         scopes=False, remat_kept_bytes=None,
                         index_scores_calls=None, build=None):
        """Record a program built by :meth:`jit` (single-device ``jax.jit``
        results and mesh wrappers both work); see
        ``programs.ProgramRegistry.record``."""
        return self.programs.record(caller, key, fn, args,
                                    compile_seconds=compile_seconds,
                                    scopes=scopes,
                                    remat_kept_bytes=remat_kept_bytes,
                                    index_scores_calls=index_scores_calls,
                                    build=build)


# ------------------------------------------------------- process default
_default_executor: Optional[Executor] = None


def get_executor() -> Executor:
    global _default_executor
    if _default_executor is None:
        _default_executor = Executor()
    return _default_executor


def set_executor(ex: Optional[Executor]) -> None:
    global _default_executor
    _default_executor = ex


def _invalidate_default() -> None:
    global _default_executor
    _default_executor = None
