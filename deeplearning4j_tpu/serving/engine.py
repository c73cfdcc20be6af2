"""Shape-bucketed inference execution.

The naive path jits one forward per EXACT batch shape, so a traffic mix of
request sizes pays one fresh XLA compile per distinct size — seconds to
minutes per program (util/compile_cache.py). The engine instead pads every batch up to a small power-of-two ladder of bucket sizes:
⌈log2(max_batch)⌉+1 compiled programs cover every request size from 1 to
max_batch, and anything larger is chunked through the top bucket.

Padding is numerics-neutral for inference: ``output()`` runs train=False, so
every op the containers emit (dense/conv matmuls, pooling, BN with running
stats, per-row softmax, per-example LSTM scan) computes row i of the output
from row i of the input alone — pad rows are dead weight that is sliced off
after the device call, and the engine's test suite pins the bucketed result
bitwise-equal to the exact-shape forward. (Train-mode batch statistics WOULD
couple rows; the engine is inference-only for exactly that reason.)

``warmup()`` pre-executes the ladder through the persistent compilation
cache (util/compile_cache.py), so a fresh server process — whose in-process
jit cache starts empty — serves its first request with ~0 compile time.

Trace accounting: the traced python body increments ``trace_count`` exactly
once per new XLA program signature, giving tests and /stats an exact
compiled-program count with no XLA internals involved.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Any, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from deeplearning4j_tpu.monitor import compile_ledger, get_registry, trace
from deeplearning4j_tpu.quant import (dequantize_tree, record_weight_bytes,
                                      resolve_precision, tree_bytes)
from deeplearning4j_tpu.resilience.errors import WeightSwapError


def _tree_signature(tree):
    """Flattened ``{path: (shape, dtype)}`` of a pytree — the swap
    compatibility key. Same path convention as util/model_serializer."""
    sig = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        arr = leaf if hasattr(leaf, "shape") else np.asarray(leaf)
        sig[key] = (tuple(arr.shape), str(arr.dtype))
    return sig


def validate_swap(current, candidate, what: str = "params") -> None:
    """Reject a hot-swap candidate whose pytree does not match the live
    weights array-for-array (path set, shapes, dtypes). Raising HERE — before
    any engine state is touched — is what makes a rejected swap a no-op; a
    mismatch that slipped through would either retrace a fresh XLA program
    (shape/dtype change) or crash a device call mid-request."""
    _validate_sig(_tree_signature(current), _tree_signature(candidate), what)


def _validate_sig(cur, new, what: str = "params") -> None:
    """Signature-level half of ``validate_swap``: quantizing engines keep
    the ORIGINAL f32 signature and validate swap candidates against it
    (candidates always arrive in f32 — quantization happens after the
    gate, so the quantized shapes/dtypes match the live program's and the
    jit cache still hits)."""
    problems = []
    for key in sorted(set(cur) - set(new)):
        problems.append(f"missing array {key!r}")
    for key in sorted(set(new) - set(cur)):
        problems.append(f"unexpected array {key!r}")
    for key in sorted(set(cur) & set(new)):
        if cur[key] != new[key]:
            problems.append(
                f"{key!r} expected {cur[key][0]}/{cur[key][1]}, "
                f"got {new[key][0]}/{new[key][1]}")
    if problems:
        raise WeightSwapError(
            f"candidate {what} incompatible with live weights", problems)


def bucket_for(n: int, max_batch: int, min_bucket: int = 1,
               ladder: Optional[Sequence[int]] = None) -> int:
    """Smallest rung ≥ n. Default rungs are the power-of-two ladder; an
    explicit ``ladder`` (sorted ascending, topped by max_batch — the
    autotuned ladders ``autotune_ladder`` produces) overrides it."""
    if n < 1:
        raise ValueError(f"batch size must be ≥ 1, got {n}")
    if ladder:
        for b in ladder:
            if b >= n:
                return b
        return ladder[-1]
    b = max(min_bucket, 1)
    while b < n:
        b <<= 1
    return min(b, max_batch)


def bucket_ladder(max_batch: int, min_bucket: int = 1) -> List[int]:
    """The full ladder [min_bucket, 2·min_bucket, ..., max_batch]."""
    out = []
    b = max(min_bucket, 1)
    while b < max_batch:
        out.append(b)
        b <<= 1
    out.append(max_batch)
    return out


def autotune_ladder(counts, max_batch: int, max_rungs: Optional[int] = None,
                    min_bucket: int = 1) -> List[int]:
    """Choose bucket rungs from MEASURED traffic instead of blind powers
    of two.

    ``counts`` maps observed batch size -> request count (the engine's
    per-size histogram). Candidate rungs are the observed sizes plus the
    pow2 rungs; a DP picks at most ``max_rungs`` of them (default: the
    pow2 ladder's length) minimizing total padding rows, with
    ``max_batch`` always kept as the top rung so oversize chunking still
    works. The pow2 ladder itself is a feasible choice, so the optimum
    NEVER pads more than pow2 does, with never more rungs (= compiled
    programs) — the two acceptance bars the bench row asserts.
    """
    pow2 = bucket_ladder(max_batch, min_bucket)
    K = int(max_rungs) if max_rungs else len(pow2)
    lo = max(min_bucket, 1)
    # sizes above max_batch arrive pre-chunked (the dispatch recursion
    # re-buckets tails), below min_bucket they pad up to it
    sizes = {}
    for s, c in dict(counts).items():
        s = min(max(int(s), lo), max_batch)
        sizes[s] = sizes.get(s, 0) + int(c)
    if not sizes:
        return pow2
    cand = sorted(set(sizes) | set(pow2) | {max_batch})
    cand = [c for c in cand if lo <= c <= max_batch]

    def seg_cost(i: int, j: int) -> float:
        """Pad rows when sizes in (cand[i], cand[j]] all round to cand[j]."""
        lo_v = cand[i] if i >= 0 else 0
        r = cand[j]
        return float(sum(c * (r - s) for s, c in sizes.items()
                         if lo_v < s <= r))

    p = len(cand)
    INF = float("inf")
    dp = [[INF] * (K + 1) for _ in range(p)]
    back = [[None] * (K + 1) for _ in range(p)]
    for j in range(p):
        dp[j][1] = seg_cost(-1, j)
        for k in range(2, K + 1):
            for i in range(j):
                if dp[i][k - 1] == INF:
                    continue
                v = dp[i][k - 1] + seg_cost(i, j)
                if v < dp[j][k]:
                    dp[j][k] = v
                    back[j][k] = i
    top = p - 1                              # cand[top] == max_batch
    best_k = min(range(1, K + 1), key=lambda k: (dp[top][k], k))
    rungs, j, k = [cand[top]], top, best_k
    while k > 1 and back[j][k] is not None:
        j = back[j][k]
        k -= 1
        rungs.append(cand[j])
    return sorted(rungs)


def prune_ladder(ladder: Sequence[int], counts, rung_costs) -> List[int]:
    """Drop rungs whose measured one-time compile cost exceeds the padding
    run-time they save on the observed traffic.

    ``rung_costs`` maps rung -> {"compile_s", "run_s"} as recorded by
    ``warmup()``. A rung saves (next_rung - rung) pad rows per request it
    absorbs; valued at the rung's measured per-row run time, if that
    saving is worth less wall-clock than the rung's compile, the rung is
    merged upward. The top rung is never dropped. This trades pad-waste
    back for compiles, so it is opt-in (``autotune(prune=True)``)."""
    ladder = sorted(ladder)
    sizes = {int(s): int(c) for s, c in dict(counts).items()}
    changed = True
    while changed and len(ladder) > 1:
        changed = False
        for idx in range(len(ladder) - 1):
            r, nxt = ladder[idx], ladder[idx + 1]
            cost = rung_costs.get(r, {})
            compile_s, run_s = cost.get("compile_s"), cost.get("run_s")
            if compile_s is None or run_s is None or run_s <= 0:
                continue
            lo = ladder[idx - 1] if idx > 0 else 0
            absorbed = sum(c for s, c in sizes.items() if lo < s <= r)
            extra_run_s = absorbed * (nxt - r) * (run_s / max(r, 1))
            if extra_run_s < compile_s:
                ladder.pop(idx)
                changed = True
                break
    return ladder


class InferenceEngine:
    """Bucketed inference over a model container.

    ``model`` is a MultiLayerNetwork or ComputationGraph (anything with
    ``params``/``state``/``_forward`` and the container conf surface).
    Parameters are read from the model at call time, so the engine stays
    valid across further ``fit()`` calls — only the program structure is
    cached, never the weights.

    ``swap_weights`` hot-swaps the serving weights for a same-shape pytree
    (the online-learning deploy path, docs/ONLINE_LEARNING.md): after the
    first swap the engine serves its own pinned ``(params, state)`` pair
    instead of reading the model, so a trainer mutating the model can no
    longer affect serving. Identical shapes/dtypes mean the jit cache hits —
    a swap performs ZERO new XLA compiles by construction (the regression
    tests pin ``trace_count`` across swaps).
    """

    _ids = itertools.count()

    def __init__(self, model, max_batch: int = 1024, min_bucket: int = 1,
                 precision: Optional[str] = None):
        from deeplearning4j_tpu import exec as ex
        self.model = model
        self.max_batch = int(max_batch)
        self.min_bucket = int(min_bucket)
        self._traced_keys = set()
        self._fwd = None
        # AOT-restored executables by (bucket, has_mask) — consulted by
        # _dispatch before the traced path (exec/aot.py; filled by
        # ``warmup(aot=...)``). Restores never touch trace_count.
        self._aot: dict = {}
        self._lock = threading.Lock()
        self._live = None          # (params, state) after the first swap
        self._version = 0
        self._is_graph = hasattr(model.conf, "network_inputs")
        self.warmup_seconds: Optional[float] = None
        # measurement-driven ladder state: per-size traffic histogram
        # (fed by live dispatches, read by ``autotune``), per-rung
        # compile/run costs (recorded by ``warmup``), and the active
        # ladder (None = the pow2 default)
        self.ladder: Optional[List[int]] = None
        self.rung_costs: dict = {}
        self._size_counts: dict = {}
        self._in_warmup = False
        # serving precision: explicit arg > the executor's declarative
        # policy (Executor(precision=...) / DL4JTPU_PRECISION). For
        # int8/fp8 the engine pins the quantized weights at construction
        # and keeps the f32 signature for swap validation — candidates
        # arrive in f32 and are quantized AFTER the gate, so the
        # quantized shapes/dtypes never change and swaps stay
        # zero-new-compiles (docs/QUANTIZATION.md).
        execu = getattr(model, "_executor", None) or ex.get_executor()
        self.precision = (resolve_precision(precision)
                          if precision is not None else execu.precision)
        self._raw_sig = None
        if self.precision != "f32":
            self._raw_sig = _tree_signature(model.params)
            qp = execu.prepare_params(model.params, self.precision)
            st = jax.tree_util.tree_map(jnp.asarray, model.state)
            self._live = (qp, st)
        # registry-backed counters: /stats and /metrics read the SAME cells
        self.id = f"engine{next(InferenceEngine._ids)}"
        reg = get_registry()
        lab = {"engine": self.id}
        self._m_compiled = reg.counter(
            "dl4jtpu_serving_compiled_programs_total",
            "XLA programs traced by the inference engine (one per bucket "
            "shape signature).", ("engine",)).labels(**lab)
        self._m_rows = reg.counter(
            "dl4jtpu_serving_batch_rows_total",
            "Real (un-padded) rows executed through bucketed device calls.",
            ("engine",)).labels(**lab)
        self._m_pad_rows = reg.counter(
            "dl4jtpu_serving_pad_rows_total",
            "Padding rows added to round batches up to bucket sizes "
            "(pad-waste = pad / (pad + rows)).", ("engine",)).labels(**lab)
        self._m_version = reg.gauge(
            "dl4jtpu_model_version",
            "Version of the weights currently serving (0 = the model's "
            "initial weights; bumped by every hot swap).",
            ("engine",)).labels(**lab)
        self._m_swaps = reg.counter(
            "dl4jtpu_model_swaps_total",
            "Weight hot-swaps applied with zero new XLA compiles.",
            ("engine",)).labels(**lab)
        self._m_rungs = reg.gauge(
            "dl4jtpu_serving_bucket_rungs",
            "Rungs in the active bucket ladder (= compiled programs the "
            "ladder needs; drops when autotune merges rungs).",
            ("engine",)).labels(**lab)
        self._m_version.set(0.0)
        self._m_rungs.set(float(len(bucket_ladder(self.max_batch,
                                                  self.min_bucket))))
        if self.precision != "f32":
            record_weight_bytes(self.id, self.precision,
                                tree_bytes(self._live[0]))

    @property
    def trace_count(self) -> int:
        """Compiled-program count (reads the registry counter — the single
        source of truth shared with ``/metrics``)."""
        return int(self._m_compiled.value)

    @property
    def model_version(self) -> int:
        return self._version

    def _weights(self):
        """The live (params, state) pair: the engine's own swapped weights
        once a swap happened, the model's otherwise. Read under the lock so
        a concurrent swap can never tear params against state."""
        with self._lock:
            if self._live is not None:
                return self._live
        return self.model.params, self.model.state

    def swap_weights(self, params, state=None, version: Optional[int] = None):
        """Atomically replace the serving weights with a same-shape pytree.

        The candidate is validated (path set, shapes, dtypes) BEFORE any
        state changes — a mismatch raises ``WeightSwapError`` and leaves the
        engine untouched. In-flight ``predict`` calls already captured their
        weight references and finish on the old weights; subsequent
        dispatches see the new pair. Same shapes/dtypes → the cached jitted
        forward is reused, so a swap costs zero new XLA compiles. Returns
        the new model version (``version`` or previous + 1).

        Under int8/fp8 precision the candidate still arrives in f32 (the
        trainer/checkpoint format): it is validated against the ORIGINAL
        f32 signature, then quantized — same quantized shapes/dtypes as
        the live tree, so the zero-new-compiles invariant holds."""
        cur_p, cur_s = self._weights()
        if self._raw_sig is not None:
            _validate_sig(self._raw_sig, _tree_signature(params), "params")
        else:
            validate_swap(cur_p, params, "params")
        if state is not None:
            validate_swap(cur_s, state, "state")
        # device-resident once, at swap time — numpy trees fresh from a
        # checkpoint zip would otherwise pay a host→device copy per request
        params = jax.tree_util.tree_map(jnp.asarray, params)
        if self.precision != "f32":
            from deeplearning4j_tpu import exec as ex
            execu = getattr(self.model, "_executor", None) \
                or ex.get_executor()
            params = execu.prepare_params(params, self.precision)
            record_weight_bytes(self.id, self.precision, tree_bytes(params))
        state = (cur_s if state is None
                 else jax.tree_util.tree_map(jnp.asarray, state))
        with self._lock:
            self._live = (params, state)
            self._version = (int(version) if version is not None
                             else self._version + 1)
            v = self._version
        self._m_version.set(float(v))
        self._m_swaps.inc()
        return v

    # ------------------------------------------------------------- forward
    def _forward_fn(self):
        if self._fwd is not None:
            return self._fwd
        model = self.model

        # dequant-on-the-fly INSIDE the traced body: XLA fuses the
        # codes→f32 scale-multiply into the consuming matmuls, so the
        # weights live in HBM at int8/fp8 width and widen in registers.
        # On the f32 path ``dequantize_tree`` is the identity on every
        # leaf — the emitted program is byte-identical to before.
        if self._is_graph:
            def fwd(params, state, inputs, mask):
                self._note_trace(inputs, mask)
                params = dequantize_tree(params)
                acts, _, _ = model._forward(params, state, inputs,
                                            train=False, rng=None)
                return [acts[n] for n in model.conf.network_outputs]
        else:
            def fwd(params, state, inputs, mask):
                self._note_trace(inputs, mask)
                params = dequantize_tree(params)
                act, _, _ = model._forward(params, state, inputs[0],
                                           train=False, rng=None, mask=mask)
                return [act]

        from deeplearning4j_tpu import exec as ex
        execu = getattr(model, "_executor", None) or ex.get_executor()
        self._fwd = execu.jit(
            fwd, in_specs=(ex.PARAMS, ex.STATE, ex.BATCH, ex.BATCH),
            out_specs=(ex.BATCH,))
        return self._fwd

    def _note_trace(self, inputs, mask):
        # runs only while jit traces a NEW (shape, dtype, mask-presence)
        # signature — i.e. exactly once per compiled program. Registration
        # relowers the same body; that trace must not count twice.
        from deeplearning4j_tpu.exec.programs import is_registering
        if is_registering():
            return
        key = (tuple((tuple(x.shape), str(x.dtype)) for x in inputs),
               None if mask is None else (tuple(mask.shape), str(mask.dtype)))
        self._m_compiled.inc()
        self._traced_keys.add(key)

    # ------------------------------------------------------------- padding
    @staticmethod
    def _pad_rows(a, b: int):
        n = a.shape[0]
        if n == b:
            return a
        widths = [(0, b - n)] + [(0, 0)] * (a.ndim - 1)
        if isinstance(a, np.ndarray):
            return np.pad(a, widths)
        return jnp.pad(a, widths)

    def _dispatch(self, inputs: Sequence, mask=None, phases=None) -> List:
        """One bucketed device call: pad → run → slice. Returns the list of
        output device arrays (async — not yet host-read). Batches larger
        than ``max_batch`` are chunked through the top bucket.

        ``phases``: optional dict the call ACCUMULATES wall seconds into
        under ``bucket``/``pad``/``device`` keys — the per-batch phase
        attribution the micro-batcher's wide-event records carry
        (docs/OBSERVABILITY.md "Request lifecycle")."""
        n = inputs[0].shape[0]
        if n > self.max_batch:
            # each chunk recurses through THIS method, so the tail chunk
            # (n % max_batch rows) re-buckets via bucket_for(tail) instead
            # of padding to the full top bucket — its saved pad rows simply
            # never hit the pad-waste counter below
            pieces = [self._dispatch(
                [x[i:i + self.max_batch] for x in inputs],
                None if mask is None else mask[i:i + self.max_batch],
                phases=phases)
                for i in range(0, n, self.max_batch)]
            return [jnp.concatenate([p[j] for p in pieces])
                    for j in range(len(pieces[0]))]
        if not self._in_warmup:
            self._size_counts[n] = self._size_counts.get(n, 0) + 1
        tp = time.perf_counter()
        with trace.span("bucket", n=n):
            b = bucket_for(n, self.max_batch, self.min_bucket, self.ladder)
        if phases is not None:
            t = time.perf_counter()
            phases["bucket"] = phases.get("bucket", 0.0) + (t - tp)
            tp = t
        with trace.span("pad", bucket=b):
            padded = [self._pad_rows(x, b) for x in inputs]
            mask_p = None if mask is None else self._pad_rows(mask, b)
        if phases is not None:
            t = time.perf_counter()
            phases["pad"] = phases.get("pad", 0.0) + (t - tp)
            tp = t
        with trace.span("device", bucket=b), compile_ledger.phase("serve"):
            params, state = self._weights()
            prog = self._aot.get((b, mask_p is not None))
            if prog is not None:
                try:
                    outs = prog(params, state, padded, mask_p)
                except Exception:
                    # the restored executable was serialized under
                    # different shapes/dtypes than this call (e.g. a mask
                    # length the artifact never saw): drop the entry and
                    # retrace — correctness beats the fast path
                    self._aot.pop((b, mask_p is not None), None)
                    prog = None
            if prog is None:
                c0 = self.trace_count
                t0 = time.perf_counter()
                outs = self._forward_fn()(params, state, padded, mask_p)
        if prog is None and self.trace_count > c0:
            # a fresh program was traced: register its cost/memory analysis
            # (the relower hits the compile cache; guarded, off-hot-path)
            from deeplearning4j_tpu.exec.programs import get_programs
            key = f"b{b}" if mask_p is None else f"b{b}_mask"
            get_programs().record(
                self.id, key, self._fwd, (params, state, padded, mask_p),
                compile_seconds=time.perf_counter() - t0)
        if phases is not None:
            t = time.perf_counter()
            phases["device"] = phases.get("device", 0.0) + (t - tp)
        self._m_rows.inc(n)
        self._m_pad_rows.inc(b - n)
        return [o[:n] for o in outs]

    # ----------------------------------------------------------- public API
    def predict(self, x, mask=None, phases=None):
        """Bucketed forward. ``x``: one batch array, or a list of input
        arrays for multi-input graphs; returns device array(s) shaped like
        the model's own ``output()`` (slicing already applied). The call is
        async — reading the result to the host is the caller's sync point.
        ``phases``: optional dict accumulating bucket/pad/device wall
        seconds (see ``_dispatch``)."""
        single = not isinstance(x, (list, tuple))
        inputs = [jnp.asarray(x)] if single else [jnp.asarray(a) for a in x]
        if mask is not None:
            mask = jnp.asarray(mask)
        outs = self._dispatch(inputs, mask, phases=phases)
        if self._is_graph:
            return outs[0] if len(outs) == 1 else outs
        return outs[0]

    def predict_host(self, x, mask=None, phases=None):
        """``predict`` + host read; returns np.ndarray (or list of them).
        With ``phases``, the host read lands under ``readback``."""
        out = self.predict(x, mask, phases=phases)
        t0 = time.perf_counter() if phases is not None else 0.0
        with trace.span("readback"):
            if isinstance(out, list):
                out = [np.asarray(o) for o in out]
            else:
                out = np.asarray(out)
        if phases is not None:
            phases["readback"] = (phases.get("readback", 0.0)
                                  + (time.perf_counter() - t0))
        return out

    def predict_stream(self, batches, depth: int = 2):
        """Pipelined inference over an iterable of batches: keeps up to
        ``depth`` dispatches in flight so the device executes batch k+1
        while the host reads batch k's result (the role AsyncDataSetIterator
        prefetch plays on the input side). Yields host np arrays — one per
        input batch, in order; multi-output graphs yield lists."""
        pending = deque()

        def read(out):
            if isinstance(out, list) and self._is_graph and len(out) > 1:
                return [np.asarray(o) for o in out]
            o = out[0] if isinstance(out, list) else out
            return np.asarray(o)

        for x in batches:
            pending.append(self.predict(x))
            while len(pending) >= max(depth, 1):
                yield read(pending.popleft())
        while pending:
            yield read(pending.popleft())

    # -------------------------------------------------------------- warmup
    def _aot_key(self, b: int, shapes, dtype,
                 mask_len: Optional[int] = None) -> str:
        """Artifact key of one ladder rung: bucket + per-example shapes +
        dtype (+ mask length for the mask-carrying variant)."""
        s = ";".join("x".join(str(d) for d in tuple(shp)) for shp in shapes)
        kind = "graph" if self._is_graph else "mln"
        key = f"engine:{kind}:b{b}:{s}:{np.dtype(dtype).name}"
        return key if mask_len is None else f"{key}:mask{mask_len}"

    def warmup(self, example_shape, dtype=np.float32, max_batch=None,
               with_mask_len: Optional[int] = None,
               aot: Optional[str] = None):
        """Pre-compile the bucket ladder through the persistent compilation
        cache so the first real request pays ~0 compile time.

        ``example_shape``: per-example feature shape (no batch dim), or a
        list of shapes for multi-input graphs. ``max_batch`` caps the ladder
        (default: the engine's max_batch). ``with_mask_len``: also compile
        the mask-carrying variants for (B, T=with_mask_len) masks.

        ``aot``: path to an AOT artifact (exec/aot.py). Rungs found there
        are deserialized in milliseconds instead of retraced — trace_count
        stays 0 for them, restores count in ``dl4jtpu_aot_restores_total``.
        Any miss (absent file, env/model mismatch, unknown rung) falls back
        to trace-and-save: the rung compiles as usual and the fresh
        executable is merged back into the artifact.

        Each rung is dispatched twice with the second run timed separately,
        so ``rung_costs[b] = {"compile_s", "run_s"}`` records what the rung
        actually cost — the measurements ``autotune(prune=True)`` uses to
        merge rungs not worth their compile. Returns the bucket sizes
        compiled (the ACTIVE ladder — autotuned if one was applied)."""
        from deeplearning4j_tpu.util.compile_cache import setup_compile_cache
        setup_compile_cache()
        shapes = (example_shape if isinstance(example_shape, list)
                  else [example_shape])
        shapes = [tuple(s) for s in shapes]
        cap = min(max_batch or self.max_batch, self.max_batch)
        ladder = [b for b in (self.ladder
                              or bucket_ladder(cap, self.min_bucket))
                  if b <= cap]
        bundle = None
        added = 0
        if aot is not None:
            from deeplearning4j_tpu.exec import aot as aot_mod
            p, s = self._weights()
            sig = aot_mod.model_signature(p, s)
            bundle, _reason = aot_mod.open_bundle(aot, sig, self.precision)
            if bundle is None:
                bundle = aot_mod.AotBundle(sig, self.precision)
        t0 = time.perf_counter()
        self._in_warmup = True    # warmup traffic must not skew autotune
        try:
            for b in ladder:
                zeros = [jnp.zeros((b,) + s, dtype) for s in shapes]
                key = self._aot_key(b, shapes, dtype)
                if bundle is not None and (b, False) not in self._aot:
                    prog = bundle.restore(key, engine=self.id)
                    if prog is not None:
                        self._aot[(b, False)] = prog
                ta = time.perf_counter()
                jax.block_until_ready(self._dispatch(zeros))
                tb = time.perf_counter()
                jax.block_until_ready(self._dispatch(zeros))
                tc = time.perf_counter()
                self.rung_costs[b] = {
                    "compile_s": max((tb - ta) - (tc - tb), 0.0),
                    "run_s": tc - tb}
                if bundle is not None and (b, False) not in self._aot:
                    from deeplearning4j_tpu.exec import aot as aot_mod
                    params, state = self._weights()
                    bundle.add_compiled(key, aot_mod.export_compiled(
                        self._forward_fn(), (params, state, zeros, None)))
                    added += 1
                if with_mask_len is not None and not self._is_graph:
                    m = jnp.ones((b, with_mask_len), dtype)
                    mkey = self._aot_key(b, shapes, dtype, with_mask_len)
                    if bundle is not None and (b, True) not in self._aot:
                        prog = bundle.restore(mkey, engine=self.id)
                        if prog is not None:
                            self._aot[(b, True)] = prog
                    jax.block_until_ready(self._dispatch(zeros, m))
                    if bundle is not None and (b, True) not in self._aot:
                        from deeplearning4j_tpu.exec import aot as aot_mod
                        params, state = self._weights()
                        bundle.add_compiled(mkey, aot_mod.export_compiled(
                            self._forward_fn(), (params, state, zeros, m)))
                        added += 1
        finally:
            self._in_warmup = False
        self.warmup_seconds = time.perf_counter() - t0
        if bundle is not None and added:
            bundle.save(aot)
        return ladder

    def autotune(self, max_rungs: Optional[int] = None, apply: bool = True,
                 prune: bool = False, counts: Optional[dict] = None,
                 ) -> List[int]:
        """Re-derive the bucket ladder from the traffic this engine has
        actually served (the per-size histogram ``_dispatch`` records).

        The DP (``autotune_ladder``) never pads more than pow2 and never
        uses more rungs; ``prune=True`` additionally merges rungs whose
        measured compile cost (from ``warmup``'s rung_costs) exceeds the
        run-time their padding saves. ``apply=False`` just returns the
        proposal. ``counts`` substitutes an external size histogram (e.g.
        another engine's measured traffic) for this engine's own. Call
        after a representative traffic window; already-compiled pow2
        programs stay cached, so switching ladders mid-run only ever ADDS
        at most len(new ladder) compiles."""
        counts = dict(self._size_counts if counts is None else counts)
        ladder = autotune_ladder(counts, self.max_batch, max_rungs,
                                 self.min_bucket)
        if prune and self.rung_costs:
            ladder = prune_ladder(ladder, counts, self.rung_costs)
        if apply:
            self.ladder = ladder
            self._m_rungs.set(float(len(ladder)))
        return ladder

    # --------------------------------------------------------------- stats
    def stats(self) -> dict:
        from deeplearning4j_tpu.util.compile_cache import cache_stats
        rows = self._m_rows.value
        pad = self._m_pad_rows.value
        return {"id": self.id,
                "max_batch": self.max_batch,
                "bucket_ladder": (list(self.ladder) if self.ladder
                                  else bucket_ladder(self.max_batch,
                                                     self.min_bucket)),
                "ladder_autotuned": self.ladder is not None,
                "rung_costs": {int(k): dict(v)
                               for k, v in self.rung_costs.items()},
                "precision": self.precision,
                "weight_bytes": tree_bytes(self._weights()[0]),
                "model_version": self._version,
                "compiled_programs": self.trace_count,
                "rows": int(rows),
                "pad_rows": int(pad),
                "pad_waste_frac": (pad / (pad + rows)) if rows else 0.0,
                "warmup_seconds": self.warmup_seconds,
                "compile_cache": cache_stats()}
