"""AOT program artifacts: serialize compiled XLA executables next to the
checkpoint so a fresh replica restores them in milliseconds instead of
paying a full retrace.

A cold replica's dominant start-up cost is tracing + XLA-compiling its hot
programs (the bucketed ladder rungs, the decode step, the spec
draft/verify pair, the paged-KV side programs) — seconds per program
even on CPU. The persistent compile cache (util/compile_cache.py)
removes the XLA backend compile but still pays the full python trace per
program; this module removes BOTH by shipping the serialized executables
themselves
(``jax.experimental.serialize_executable``) in a versioned zip artifact
written with the atomic ``model_serializer`` discipline.

Validity model: a serialized executable bakes in argument shapes/dtypes,
donation, and backend-specific generated code. The bundle is therefore
keyed on (backend, jaxlib version, model signature, precision) at the
artifact level — any mismatch rejects the WHOLE bundle — and each program
inside is keyed by a caller-chosen string encoding its rung/shape
(``engine:mln:b8:...``, ``decode:step:S4:...``). The model signature
hashes shapes/dtypes only (weights are runtime arguments), so a newer
checkpoint of the same architecture reuses the artifact unchanged.

Every miss falls back to trace-and-save: callers trace as before, export
the fresh program, and merge it into the artifact. Restores count in
``dl4jtpu_aot_restores_total`` — never in the engines' compile counters —
so the existing compiled-program pins survive, and "zero new compiles
after restore" is directly observable as ``trace_count == 0``.
"""

from __future__ import annotations

import hashlib
import io
import json
import os
import pickle
import time
import zipfile
from typing import Any, Dict, Optional, Tuple

__all__ = ["AotBundle", "open_bundle", "export_compiled", "companion_path",
           "model_signature", "MISS_REASONS"]

# v2: every program carries the ids of the devices it was compiled for
FORMAT = "deeplearning4j_tpu/aot-bundle/v2"

#: every reason ``dl4jtpu_aot_misses_total`` can carry — the artifact-level
#: gates first (whole bundle rejected), then per-program misses
MISS_REASONS = ("no_artifact", "corrupt", "format", "backend", "jaxlib",
                "model_sig", "precision", "key", "devices")

_metrics = None


def _aot_metrics():
    global _metrics
    if _metrics is None:
        from deeplearning4j_tpu.monitor import get_registry
        reg = get_registry()
        _metrics = {
            "restores": reg.counter(
                "dl4jtpu_aot_restores_total",
                "Compiled programs deserialized from an AOT artifact "
                "instead of being retraced (counted separately from the "
                "engines' compile counters).", ("engine",)),
            "misses": reg.counter(
                "dl4jtpu_aot_misses_total",
                "AOT artifact lookups that fell back to trace-and-save, "
                "by reason (no_artifact/corrupt/format/backend/jaxlib/"
                "model_sig/precision/key/devices).", ("reason",)),
            "seconds": reg.histogram(
                "dl4jtpu_aot_restore_seconds",
                "Wall seconds to deserialize one compiled program from "
                "the artifact.", ("engine",)),
        }
    return _metrics


def note_miss(reason: str) -> None:
    if reason not in MISS_REASONS:
        reason = "corrupt"
    _aot_metrics()["misses"].labels(reason=reason).inc()


def _env_fingerprint() -> Dict[str, str]:
    import jax
    try:
        import jaxlib
        jl = getattr(jaxlib, "__version__", None)
        if jl is None:
            from jaxlib import version as _jlv
            jl = getattr(_jlv, "__version__", "unknown")
    except Exception:
        jl = "unknown"
    return {"backend": jax.default_backend(), "jaxlib": str(jl),
            "jax": jax.__version__}


def model_signature(*trees) -> str:
    """Hash of the shapes/dtypes of the given pytrees (weights are runtime
    arguments to the serialized programs, so VALUES are irrelevant — a
    later checkpoint of the same architecture keeps the same signature,
    while any architectural change rejects the bundle)."""
    from deeplearning4j_tpu.serving.engine import _tree_signature
    sig = [sorted(_tree_signature(t).items()) for t in trees]
    blob = json.dumps(sig, sort_keys=True).encode()
    return hashlib.blake2b(blob, digest_size=16).hexdigest()


def companion_path(checkpoint_path) -> str:
    """The artifact path riding next to a checkpoint: ``model.zip`` →
    ``model.aot.zip`` (rotated and pinned together by CheckpointManager)."""
    p = os.fspath(checkpoint_path)
    return (p[:-len(".zip")] if p.endswith(".zip") else p) + ".aot.zip"


def export_compiled(jitted, args):
    """AOT-compile ``jitted`` (a ``jax.jit`` result or mesh ``Executor.jit``
    wrapper) at the shapes of ``args`` for serialization. Runs under the
    registration guard so the relowered python body does not double-count
    the caller's compile accounting; the persistent compile cache makes
    the XLA half of this relower cheap.

    On the CPU backend the relower runs with the persistent cache
    DISABLED: a CPU executable loaded from the compilation cache cannot
    be re-serialized (``deserialize_and_load`` of such a payload fails
    with ``Symbols not found``), so a cache HIT here would poison the
    artifact. The cache object is a process singleton that ignores
    config changes after first use, so the dir change alone is not
    enough — the singleton is reset around the compile (and re-armed
    after, so the ambient cache keeps working for everything else)."""
    import jax

    from deeplearning4j_tpu.exec.programs import _Registering, _lowerable
    low = _lowerable(jitted)
    if low is None:
        raise TypeError(f"object has no lowerable jit entry: {jitted!r}")
    with _Registering():
        if jax.default_backend() != "cpu":
            return low.lower(*args).compile()
        try:
            from jax._src import compilation_cache as _cc
        except Exception:
            _cc = None
        prev = jax.config.jax_compilation_cache_dir
        try:
            jax.config.update("jax_compilation_cache_dir", None)
            if _cc is not None:
                _cc.reset_cache()
            # a non-empty compiler_options dict (the value IS the
            # default, so the program is unchanged) bypasses the
            # memoized executable of an earlier call at these shapes —
            # that executable may itself have been loaded from the cache
            return low.lower(*args).compile(
                compiler_options={"xla_cpu_enable_fast_math": False})
        finally:
            jax.config.update("jax_compilation_cache_dir", prev)
            if _cc is not None:
                _cc.reset_cache()


class AotBundle:
    """A set of serialized executables sharing one validity envelope.

    ``programs`` maps caller-chosen key strings to pickled
    ``serialize_executable`` triples plus their device ids. ``save`` merges with any compatible
    bundle already on disk (two engines warming against the same artifact
    union their programs) and writes atomically.
    """

    def __init__(self, model_sig: str, precision: str,
                 env: Optional[Dict[str, str]] = None):
        env = env or _env_fingerprint()
        self.backend = env["backend"]
        self.jaxlib = env["jaxlib"]
        self.jax = env.get("jax", "unknown")
        self.model_sig = str(model_sig)
        self.precision = str(precision)
        self._programs: Dict[str, bytes] = {}

    # ------------------------------------------------------------ programs
    def keys(self):
        return set(self._programs)

    def __contains__(self, key: str) -> bool:
        return key in self._programs

    def __len__(self) -> int:
        return len(self._programs)

    def add_compiled(self, key: str, compiled) -> None:
        """Serialize one compiled executable under ``key`` (replacing any
        previous entry), with the ids of the devices it runs on: a
        program is restored onto those devices, not onto every local one
        (a one-device program loaded across a four-chip host would expect
        four shards of every argument)."""
        from jax.experimental import serialize_executable as se
        payload, in_tree, out_tree = se.serialize(compiled)
        device_ids = [d.id for d in
                      compiled.runtime_executable().local_devices()]
        self._programs[str(key)] = pickle.dumps(
            (payload, in_tree, out_tree, device_ids),
            protocol=pickle.HIGHEST_PROTOCOL)

    def restore(self, key: str, engine: str = ""):
        """Deserialize-and-load the program under ``key`` onto the devices
        it was compiled for; None on a key miss, a device this process
        does not have, or a torn entry (all counted, never raised — the
        caller falls back to trace-and-save). Anything else a restore
        raises is a defect and propagates."""
        import jax
        from jax.experimental import serialize_executable as se
        blob = self._programs.get(str(key))
        if blob is None:
            note_miss("key")
            return None
        t0 = time.perf_counter()
        try:
            payload, in_tree, out_tree, device_ids = pickle.loads(blob)
        except (pickle.UnpicklingError, EOFError, ValueError, TypeError,
                AttributeError, ImportError, IndexError):
            note_miss("corrupt")       # not a program entry of this format
            return None
        local = {d.id: d for d in jax.local_devices()}
        if any(i not in local for i in device_ids):
            note_miss("devices")
            return None
        try:
            compiled = se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[local[i] for i in device_ids])
        except (pickle.UnpicklingError, EOFError,
                jax.errors.JaxRuntimeError):
            note_miss("corrupt")       # torn or stale executable payload
            return None
        m = _aot_metrics()
        m["restores"].labels(engine=engine or "unknown").inc()
        m["seconds"].labels(engine=engine or "unknown").observe(
            time.perf_counter() - t0)
        return compiled

    # ----------------------------------------------------------------- io
    def _meta(self) -> dict:
        return {"format": FORMAT, "backend": self.backend,
                "jaxlib": self.jaxlib, "jax": self.jax,
                "model_sig": self.model_sig, "precision": self.precision,
                "programs": sorted(self._programs)}

    def compatible(self, other: "AotBundle") -> bool:
        return (self.backend == other.backend
                and self.jaxlib == other.jaxlib
                and self.model_sig == other.model_sig
                and self.precision == other.precision)

    def save(self, path) -> str:
        """Atomic merge-save: union with a compatible bundle already at
        ``path`` (an incompatible one is overwritten — it could never be
        restored in this process anyway), then temp + fsync + rename, the
        model_serializer discipline."""
        path = os.fspath(path)
        try:
            prev = AotBundle.load(path)
        except Exception:
            prev = None
        merged = dict(self._programs)
        if prev is not None and self.compatible(prev):
            for k, v in prev._programs.items():
                merged.setdefault(k, v)
        self._programs = merged

        buf = io.BytesIO()
        with zipfile.ZipFile(buf, "w", zipfile.ZIP_STORED) as z:
            z.writestr("meta.json", json.dumps(self._meta(), indent=1))
            for i, key in enumerate(sorted(self._programs)):
                z.writestr(f"programs/{i:04d}.bin", self._programs[key])
        d = os.path.dirname(path) or "."
        os.makedirs(d, exist_ok=True)
        tmp = os.path.join(d, f".{os.path.basename(path)}.tmp.{os.getpid()}")
        try:
            with open(tmp, "wb") as f:
                f.write(buf.getvalue())
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        finally:
            if os.path.exists(tmp):
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
        try:
            fd = os.open(d, os.O_RDONLY)
            try:
                os.fsync(fd)
            finally:
                os.close(fd)
        except OSError:
            pass
        return path

    @classmethod
    def load(cls, path) -> "AotBundle":
        """Read a bundle from disk (raises on absence/corruption/unknown
        format — ``open_bundle`` is the non-raising, metric-counting
        entry)."""
        with zipfile.ZipFile(os.fspath(path), "r") as z:
            meta = json.loads(z.read("meta.json"))
            if meta.get("format") != FORMAT:
                raise ValueError(
                    f"unknown artifact format {meta.get('format')!r}")
            b = cls(meta["model_sig"], meta["precision"],
                    env={"backend": meta["backend"],
                         "jaxlib": meta["jaxlib"],
                         "jax": meta.get("jax", "unknown")})
            for i, key in enumerate(meta["programs"]):
                b._programs[key] = z.read(f"programs/{i:04d}.bin")
        return b


def open_bundle(path, model_sig: str, precision: str,
                ) -> Tuple[Optional[AotBundle], Optional[str]]:
    """Open + validate an artifact against this process's environment and
    the caller's model. Returns ``(bundle, None)`` when every artifact-level
    gate passes, else ``(None, reason)`` with the miss counted — a stale
    program is NEVER deserialized; the caller falls back to trace-and-save.
    """
    if not path or not os.path.exists(os.fspath(path)):
        note_miss("no_artifact")
        return None, "no_artifact"
    try:
        b = AotBundle.load(path)
    except ValueError:
        note_miss("format")
        return None, "format"
    except Exception:
        note_miss("corrupt")
        return None, "corrupt"
    env = _env_fingerprint()
    reason = None
    if b.backend != env["backend"]:
        reason = "backend"
    elif b.jaxlib != env["jaxlib"]:
        reason = "jaxlib"
    elif b.model_sig != str(model_sig):
        reason = "model_sig"
    elif b.precision != str(precision):
        reason = "precision"
    if reason is not None:
        note_miss(reason)
        return None, reason
    return b, None
