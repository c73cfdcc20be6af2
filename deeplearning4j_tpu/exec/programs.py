"""Registry of compiled XLA programs with cost/memory introspection.

Every compile site in the system — the bucketed serving engine, the
continuous-batching decode engine, and both model containers — registers
the program it just traced here, keyed by ``(caller, key)`` (e.g.
``("engine0", "b32")`` or ``("mln0", "fit_scan_k64_b128")``). At
registration the program is re-lowered and AOT-compiled to read XLA's
own ``cost_analysis()`` (flops, bytes accessed) and
``memory_analysis()`` (device footprint); the persistent compile cache
(``util/compile_cache``) makes the second compile of an already-compiled
signature cheap.

What this buys:

- ``dl4jtpu_program_{flops,bytes,memory_bytes,compile_seconds}`` gauges
  labelled ``{caller,key}`` — MFU is now derivable from /metrics alone.
- ``GET /programs`` on the inference server: the live program table.
- ``op_scopes``, a step program's table from instruction name to the
  named scopes it was traced under (util/scopes.py): what turns a device
  trace's compiler-made operation names into layer and phase.
- ``remat_kept_bytes``, a step program's count under ``remat="blocks"`` of
  the bytes its blocks keep for the backward pass beside their inputs, by
  name (util/remat.py), and the ``dl4jtpu_remat_kept_bytes`` gauge
  labelled ``{caller,key,name}``: a name that reads 0 is replayed.
- ``index_scores_calls``, a step program's count of the index-score calls
  it traced by the form each took (nn/layers/decoder.py:index_scores:
  ``kernel``, the Pallas kernel of ops/index_scores.py, or ``xla``, the
  einsum), and the ``dl4jtpu_index_scores_calls`` gauge labelled
  ``{caller,key,form}``.
- ``hyper_connection_calls``, likewise the hyper-connection passes (the
  maps, a mix-in, a mix-out: nn/layers/latent.py:hyper_kernels) by form,
  ``kernel`` (ops/hyper_connection.py) or ``xla``, and the
  ``dl4jtpu_hyper_connection_calls`` gauge.
- ``latent_attention_calls``, likewise the latent-attention calls
  (nn/layers/latent.py:LatentAttention) by the form their attention took,
  ``kernel`` (the grouped-query flash kernels) or ``xla``
  (``banded_attention``), and the ``dl4jtpu_latent_attention_calls``
  gauge. The three are ``CALL_FIELDS``.
- ``trace_seconds``, ``lower_seconds``, ``backend_seconds``,
  ``cache_load_seconds`` and ``cache`` (``hit`` | ``miss`` | ``uncached``)
  of a step program's record: what the call that built it spent tracing,
  lowering and in the backend, how much of that loading from the
  persistent cache, and whether it loaded or compiled (the compile ledger,
  monitor/compile_ledger.py). The registration's own second pass is not in
  them: it is ``aot_seconds``, and the ledger's ``register`` phase.
- ``bench.py`` MFU rows read flops from here instead of re-deriving them
  with a private lowering helper.

Re-lowering re-traces the python callable, which would double-count the
callers' compile accounting (``_note_compile`` / ``_m_compiled.inc()``
run inside traced bodies). Those sites consult :func:`is_registering`
and skip their increment while a registration lowering is in flight.
"""

from __future__ import annotations

import re
import threading
import time
from typing import Optional

__all__ = ["ProgramRegistry", "get_programs", "is_registering",
           "hlo_instructions"]


_REGISTERING = threading.local()

# what a step program's record counts of the calls of an op that routes
# between a kernel and XLA's form, by the form each took: the record's
# field -> (its gauge, the gauge's help)
CALL_FIELDS = {
    "index_scores_calls": (
        "dl4jtpu_index_scores_calls",
        "index-score calls a step program traced, by the form each took "
        "(kernel: Pallas, tile by tile in VMEM; xla: einsum)"),
    "hyper_connection_calls": (
        "dl4jtpu_hyper_connection_calls",
        "hyper-connection passes a step program traced, by the form each "
        "took (kernel: Pallas, the state read once a pass; xla: XLA's "
        "float32 form)"),
    "latent_attention_calls": (
        "dl4jtpu_latent_attention_calls",
        "latent-attention calls a step program traced, by the form their "
        "attention took (kernel: the grouped-query flash kernels; xla: "
        "banded_attention)")}

# how a step program came to be (``record``'s ``build``), where nobody says
_NO_BUILD = dict.fromkeys(("trace_seconds", "lower_seconds", "backend_seconds",
                           "cache_load_seconds", "cache"))


def is_registering() -> bool:
    """True while this thread is re-lowering a program for registration —
    compile-accounting side effects inside traced bodies must no-op."""
    return getattr(_REGISTERING, "on", False)


class _Registering:
    __slots__ = ()

    def __enter__(self):
        _REGISTERING.on = True
        return self

    def __exit__(self, *exc):
        _REGISTERING.on = False
        return False


def _lowerable(fn):
    """The object carrying ``.lower``: a plain ``jax.jit`` result, or one
    of the jitted entries inside a mesh ``Executor.jit`` wrapper."""
    if hasattr(fn, "lower"):
        return fn
    cache = getattr(fn, "_exec_cache", None)
    if cache:
        return next(iter(cache.values()))
    return None


_COMPUTATION = re.compile(r"^(ENTRY )?%([^\s(]+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%(\S+) = (.*)$")
_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')
_FUSED = re.compile(r"\bcalls=%([^\s,)}]+)")
_CALLED = re.compile(
    r"\b(?:body|condition|to_apply|true_computation|false_computation)"
    r"=%([^\s,)}]+)|\bbranch_computations=\{([^}]*)\}")


def hlo_instructions(text: str) -> list:
    """``(name, opcode, op_name)`` of every instruction that runs as a
    step of its own in the compiled HLO ``text``: those of the entry
    computation and of the bodies it calls (``while``, ``call``,
    ``conditional``), transitively. A fusion is one instruction, named by
    its own metadata, or, where the compiler gave the fusion none, by the
    instruction nearest its root that has some; what is fused into it is
    not listed. ``op_name`` is ``""`` where the compiler left none."""
    comps, entry, cur = {}, None, None      # computation -> its raw lines
    for line in text.splitlines():
        if line[:1] in ("%", "E"):
            m = _COMPUTATION.match(line)
            if m:
                cur = comps.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
        elif cur is not None:
            if line[:1] == "}":
                cur = None
            else:
                cur.append(line)

    def named(comp):
        """The last ``op_name`` of a computation, nearest its root."""
        for line in reversed(comps.get(comp, ())):
            m = _OP_NAME.search(line)
            if m:
                return m.group(1)
        return ""

    out, todo, seen = [], [entry], set()
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        for line in comps[comp]:
            m = _INSTRUCTION.match(line)
            if not m:
                continue
            rest = m.group(2)
            op = _OPCODE.search(rest)
            op = op.group(1) if op else ""
            op_name = _OP_NAME.search(rest)
            op_name = op_name.group(1) if op_name else ""
            if op == "fusion" and not op_name:
                fused = _FUSED.search(rest)
                op_name = named(fused.group(1)) if fused else ""
            elif op in ("while", "call", "conditional"):
                for one, many in _CALLED.findall(rest):
                    todo += [one] if one else [
                        c.strip().lstrip("%") for c in many.split(",")]
            out.append((m.group(1), op, op_name))
    return out


def _analyze(jitted, args, scopes=False) -> dict:
    """Record fields read off the AOT-compiled program; an analysis XLA
    does not offer comes back None. ``mosaic_calls`` counts the Mosaic
    (Pallas TPU) custom calls in the compiled program: 0 means no
    hand-written kernel made it in — the interpreter lowers a kernel to
    plain XLA ops, and so counts 0. ``all_reduces`` counts the all-reduce
    collectives the partitioner put in: 0 on one device, the gradient
    reduction of a data-parallel step on a mesh. ``op_scopes``, where
    ``scopes`` asks for it, is ``{instruction name: op_name}`` of
    :func:`hlo_instructions`: the named scopes (util/scopes.py) of the
    program that was loaded, which may be an older commit's entry of the
    persistent cache (the cache key leaves names out), so the table
    always matches what runs.
    ``memory_bytes`` counts a donated argument once: ``memory_analysis``
    lists it among the arguments and again among the outputs it aliases."""
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    aot_seconds = time.perf_counter() - t0
    text = compiled.as_text()
    out = {"flops": None, "bytes": None, "memory_bytes": None,
           "aot_seconds": aot_seconds,
           "mosaic_calls": text.count('custom_call_target="tpu_custom_call"'),
           "all_reduces": len(re.findall(r"\ball-reduce(?:-start)?\(",
                                         text)),
           "op_scopes": ({n: o for n, _, o in hlo_instructions(text)}
                         if scopes else None)}
    try:
        an = compiled.cost_analysis()
        if isinstance(an, (list, tuple)):
            an = an[0] if an else {}
        if an:
            f = an.get("flops")
            out["flops"] = float(f) if f is not None else None
            b = an.get("bytes accessed")
            out["bytes"] = float(b) if b is not None else None
    except Exception:
        pass
    try:
        mem = compiled.memory_analysis()
        sizes = [getattr(mem, attr, None) for attr in (
            "temp_size_in_bytes", "argument_size_in_bytes",
            "output_size_in_bytes", "generated_code_size_in_bytes")]
        if any(v is not None for v in sizes):
            out["memory_bytes"] = float(
                sum(v for v in sizes if v is not None)
                - (getattr(mem, "alias_size_in_bytes", None) or 0))
    except Exception:
        pass
    return out


class ProgramRegistry:
    """Process-wide table of registered programs (thread-safe)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._programs = {}        # (caller, key) -> record dict
        self._gauges = None
        self._kept = None          # the remat_kept_bytes gauge family
        self._calls = None         # the *_calls gauge families, by field

    def _metric(self, record):
        if self._gauges is None:
            from deeplearning4j_tpu.monitor import get_registry
            reg = get_registry()
            self._gauges = {
                "flops": reg.gauge(
                    "dl4jtpu_program_flops",
                    "XLA cost_analysis flops of the registered program",
                    labelnames=("caller", "key")),
                "bytes": reg.gauge(
                    "dl4jtpu_program_bytes",
                    "XLA cost_analysis bytes accessed",
                    labelnames=("caller", "key")),
                "memory_bytes": reg.gauge(
                    "dl4jtpu_program_memory_bytes",
                    "XLA memory_analysis device footprint "
                    "(args + outputs + temps + code)",
                    labelnames=("caller", "key")),
                "compile_seconds": reg.gauge(
                    "dl4jtpu_program_compile_seconds",
                    "wall seconds of the compile-bearing call that "
                    "produced the program (AOT relower time if unmeasured)",
                    labelnames=("caller", "key")),
            }
            self._kept = reg.gauge(
                "dl4jtpu_remat_kept_bytes",
                "bytes a step program's blocks keep for the backward pass "
                "beside their inputs under remat='blocks', by name",
                labelnames=("caller", "key", "name"))
            self._calls = {
                field: reg.gauge(name, help_,
                                 labelnames=("caller", "key", "form"))
                for field, (name, help_) in CALL_FIELDS.items()}
        lbl = {"caller": record["caller"], "key": record["key"]}
        for field, fam in self._gauges.items():
            v = record.get(field)
            if v is not None:
                fam.labels(**lbl).set(v)
        for name, v in (record.get("remat_kept_bytes") or {}).items():
            self._kept.labels(name=name, **lbl).set(v)
        for field, fam in self._calls.items():
            for form, v in (record.get(field) or {}).items():
                fam.labels(form=form, **lbl).set(v)

    def record(self, caller: str, key: str, fn, args,
               compile_seconds: Optional[float] = None,
               scopes: bool = False,
               remat_kept_bytes: Optional[dict] = None,
               kernel_calls: Optional[dict] = None,
               build: Optional[dict] = None) -> Optional[dict]:
        """Register program ``(caller, key)``; re-registration of a known
        key is a no-op (returns the existing record). Analysis failures
        degrade to a record with None fields rather than raising into
        the caller's hot path. ``scopes`` keeps the record's ``op_scopes``
        table (the containers' step programs ask for it; a serving bucket
        has no reader for one). ``remat_kept_bytes``: what the caller
        counted while it traced the program (a graph's step under
        ``remat="blocks"``), kept as the record's field of that name;
        ``kernel_calls`` likewise, {field of ``CALL_FIELDS``: {form:
        count}}: a field no call of which was traced reads None.
        ``build``: how the program came to be, for the call that built it
        (``monitor/compile_ledger.since``: ``trace_seconds``,
        ``lower_seconds``, ``backend_seconds``, ``cache_load_seconds`` and
        ``cache``: ``hit`` | ``miss`` | ``uncached``); the containers' step
        programs bring it, and a record without it keeps the five None."""
        caller, key = str(caller), str(key)
        with self._lock:
            existing = self._programs.get((caller, key))
        if existing is not None:
            return existing
        jitted = _lowerable(fn)
        if jitted is None:
            return None
        fields = {"flops": None, "bytes": None, "memory_bytes": None,
                  "aot_seconds": None, "mosaic_calls": None,
                  "all_reduces": None, "op_scopes": None}
        from deeplearning4j_tpu.monitor import compile_ledger
        try:
            # the second lowering and compile are the registry's own cost
            with _Registering(), compile_ledger.phase("register"):
                fields = _analyze(jitted, args, scopes)
        except Exception:
            pass
        calls = kernel_calls or {}
        record = {
            "caller": caller,
            "key": key,
            **fields,
            "compile_seconds": (compile_seconds if compile_seconds is not None
                                else fields["aot_seconds"]),
            **(build or _NO_BUILD),
            "remat_kept_bytes": (dict(remat_kept_bytes)
                                 if remat_kept_bytes is not None else None),
            **{field: dict(calls[field])
               if any((calls.get(field) or {}).values()) else None
               for field in CALL_FIELDS},
        }
        with self._lock:
            # lost a race: keep the first registration
            existing = self._programs.setdefault((caller, key), record)
        if existing is record:
            try:
                self._metric(record)
            except Exception:
                pass
        return existing

    def get(self, caller: str, key: str) -> Optional[dict]:
        with self._lock:
            return self._programs.get((str(caller), str(key)))

    def last(self, caller: str) -> Optional[dict]:
        """Most recently registered program of ``caller``."""
        caller = str(caller)
        with self._lock:
            out = None
            for (c, _), rec in self._programs.items():
                if c == caller:
                    out = rec
            return out

    def entries(self) -> list:
        """Every record without its ``op_scopes`` table (thousands of
        rows a step program; ``get``/``last`` return the record with
        it)."""
        with self._lock:
            return [{k: v for k, v in rec.items() if k != "op_scopes"}
                    for rec in self._programs.values()]

    def clear(self) -> None:
        with self._lock:
            self._programs.clear()


_programs = ProgramRegistry()


def get_programs() -> ProgramRegistry:
    """The process-wide program registry (analog of
    ``monitor.get_registry()``)."""
    return _programs
