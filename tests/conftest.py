"""Test config: force CPU with 8 virtual devices so sharding/multi-chip tests
run anywhere, fast and deterministically (parity with the reference's
`local[N]` Spark test masters — SURVEY.md §4 'distributed tests without a
real cluster'). Tests never claim an accelerator: the chip is reached only
by sending ``chip_smoke.py`` through the builder's chip tool.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

import jax
import pytest

# In-memory XLA executable memo, shared across the whole suite run. The
# suite compiles the same tiny programs hundreds of times — every
# DecodeEngine/InferenceEngine builds fresh closures, so jax's jaxpr-level
# jit cache never hits, but the lowered HLO is identical. Memoizing
# compile_or_get_cached on jax's own content-addressed cache key returns
# the already-loaded executable for a repeat compile. This deliberately
# does NOT use the persistent disk cache: on this jaxlib's CPU backend,
# deserializing a cached executable whose twin is already loaded in the
# same process corrupts the heap (the same symbol-registry defect that
# makes cache-loaded CPU executables unserializable — see
# exec/aot.py::export_compiled), and one pytest process re-compiling a
# program it already holds is exactly that case. Compile accounting is
# unaffected: every counter in the tree counts python-level TRACES, which
# still happen per fresh closure. The memo key includes the current
# jax_compilation_cache_dir so tests that point the config at their own
# ``cache_dir=`` (AOT cold-start arms) keep their compile isolation;
# pytest_runtest_teardown pins the dir back off afterwards so a leaked
# dir can never feed disk-cached executables to a later test.
_COMPILE_MEMO = {}


def _install_compile_memo():
    import threading

    from jax._src import compilation_cache as _cc
    from jax._src import compiler as _compiler

    orig = _compiler.compile_or_get_cached
    lock = threading.Lock()

    def memoized(backend, computation, devices, compile_options,
                 host_callbacks, *a, **kw):
        if getattr(backend, "platform", None) != "cpu" or host_callbacks:
            return orig(backend, computation, devices, compile_options,
                        host_callbacks, *a, **kw)
        try:
            key = (_cc.get_cache_key(computation, devices, compile_options,
                                     backend),
                   jax.config.jax_compilation_cache_dir)
        except Exception:
            return orig(backend, computation, devices, compile_options,
                        host_callbacks, *a, **kw)
        with lock:
            hit = _COMPILE_MEMO.get(key)
        if hit is not None:
            return hit
        exe = orig(backend, computation, devices, compile_options,
                   host_callbacks, *a, **kw)
        with lock:
            return _COMPILE_MEMO.setdefault(key, exe)

    _compiler.compile_or_get_cached = memoized


try:
    if not os.environ.get("DL4JTPU_TEST_NO_COMPILE_CACHE"):
        _install_compile_memo()
except Exception:
    pass


def pytest_runtest_teardown(item, nextitem):
    try:
        import jax as _jax
        if _jax.config.jax_compilation_cache_dir is not None:
            _jax.config.update("jax_compilation_cache_dir", None)
    except Exception:
        pass


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "mesh8: needs 8 devices (the forced host-device count above; "
        "skipped automatically when the process sees fewer)")


def pytest_runtest_setup(item):
    if item.get_closest_marker("mesh8") is not None:
        import jax as _jax
        if len(_jax.devices()) < 8:
            pytest.skip("needs 8 devices "
                        "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


@pytest.fixture
def mesh8():
    """Subprocess environment with 8 virtual CPU devices. The host-device
    flag only takes effect before jax initializes, so tests that need a
    DIFFERENT device count than this process (or a clean jax) must spawn a
    child with this env rather than mutate XLA_FLAGS in place."""
    from deeplearning4j_tpu.exec import host_device_env
    return host_device_env(8)
