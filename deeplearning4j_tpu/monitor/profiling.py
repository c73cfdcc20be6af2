"""On-demand device profiling around live traffic and training.

Two entry points over ``jax.profiler``:

- ``POST /admin/profile {"seconds": S, "dir": D}`` on the inference
  server calls :func:`start_profile`, which starts ``jax.profiler`` and
  stops it from a timer thread ``S`` seconds later — live traffic keeps
  flowing and lands inside the captured trace. One session at a time per
  process; a second request while one is running is rejected.
- ``DL4JTPU_PROFILE=/dir python train.py`` wraps the whole ``fit()``
  call via :func:`profile_scope` in both model containers.

Both capture with the host tracer at level 1 and the Python tracer off,
and enable the program's span tracer for the capture's duration (its
state is restored after): the spans of ``monitor/tracing.py`` then sit in
the xplane's host plane beside the device's operations, on one clock.
Level 1 does not silence the runtime: on a TPU the host-side linearize of
a large float image batch writes some 10^6 ``Transpose`` events a batch at
level 1 as at the default (ResNet50 at batch 256: a 928 MB trace of a 4 s
window, 129 s of ``stop_trace``, the device 42 % idle; PERF.md §6). The
device's own operations and their named scopes need no host tracer at all
(``perfbench/tools/trace_host.py --host-tracer 0``).

Everything degrades to a no-op (with the reason reported) when the
installed jax has no usable profiler — the serving path must never 500
because profiling is unavailable.
"""

from __future__ import annotations

import os
import threading
import time
from contextlib import contextmanager

from deeplearning4j_tpu.monitor.tracing import trace

__all__ = ["start_profile", "profile_status", "profile_scope",
           "PROFILE_ENV"]

PROFILE_ENV = "DL4JTPU_PROFILE"

_lock = threading.Lock()
_active = None        # {"dir", "seconds", "started_at"} while running


def _options():
    import jax
    o = jax.profiler.ProfileOptions()
    o.host_tracer_level = 1
    o.python_tracer_level = 0
    return o


def profile_status() -> dict:
    with _lock:
        if _active is None:
            return {"profiling": False}
        return {"profiling": True, **_active}


def start_profile(log_dir: str, seconds: float = 5.0) -> dict:
    """Start a timed ``jax.profiler`` capture into ``log_dir``.

    Returns the session descriptor immediately (the stop runs on a
    daemon timer thread). Raises ``RuntimeError`` if a session is
    already running or the profiler cannot start."""
    seconds = float(seconds)
    if not (0.0 < seconds <= 600.0):
        raise ValueError(f"seconds must be in (0, 600], got {seconds}")
    if not log_dir:
        raise ValueError("dir is required")
    global _active
    with _lock:
        if _active is not None:
            raise RuntimeError("a profiling session is already running")
        _active = {"dir": str(log_dir), "seconds": seconds,
                   "started_at": time.time()}
    was_tracing = trace.enabled
    try:
        import jax
        os.makedirs(log_dir, exist_ok=True)
        jax.profiler.start_trace(str(log_dir), profiler_options=_options())
    except Exception as e:
        with _lock:
            _active = None
        raise RuntimeError(f"profiler unavailable: {e}")
    trace.enable(True)

    def _stop():
        global _active
        time.sleep(seconds)
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception:
            pass
        trace.enable(was_tracing)
        with _lock:
            _active = None

    threading.Thread(target=_stop, name="profile-stop", daemon=True).start()
    return {"profiling": str(log_dir), "seconds": seconds}


@contextmanager
def profile_scope(env: str = PROFILE_ENV):
    """Wrap a block in ``jax.profiler.trace(dir)`` when ``$DL4JTPU_PROFILE``
    names a directory, with the span tracer on inside it; a plain
    pass-through otherwise (including when the profiler itself is
    unusable)."""
    log_dir = os.environ.get(env, "").strip()
    if not log_dir:
        yield
        return
    try:
        import jax
        os.makedirs(log_dir, exist_ok=True)
        cm = jax.profiler.trace(log_dir, profiler_options=_options())
    except Exception:
        yield
        return
    was_tracing = trace.enabled
    with cm:
        trace.enable(True)
        try:
            yield
        finally:
            trace.enable(was_tracing)
