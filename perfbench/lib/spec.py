"""BENCHMARK.json and the files it names. Everything that belongs to one
configuration, one traffic mix, one job kind, one per-layer metric or one
reader is a file of its own, found by name; nothing about a cell is
written into the harness."""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def load_benchmark(root=ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"perfbench: no {what} named {name!r}")


def cell(bench, workload, bench_dir=BENCH_DIR, rehearse=False):
    """(cell entry, configuration entry, traffic parameters, limits); with
    ``rehearse`` the limits of the float32 CPU rehearsal."""
    w = by_name(bench["workloads"], workload, "workload")
    c = by_name(bench["configs"], w["config"], "configuration")
    traffic = json.loads(
        (Path(bench_dir) / "traffic" / f"{w['traffic']}.json").read_text())
    lim = Path(bench_dir) / "limits" / f"{workload}.json"
    key = "rehearsal_limits" if rehearse else "limits"
    limits = json.loads(lim.read_text()).get(key, {}) if lim.exists() else {}
    return w, c, traffic, limits


def applies(metric, workload) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_module(kind, name, bench_dir=BENCH_DIR):
    """``<kind>/<name>.py`` under the benchmark's directory, as a module."""
    path = Path(bench_dir) / kind / f"{name}.py"
    if not path.exists():
        raise SystemExit(f"perfbench: no {kind[:-1]} {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_file(name, bench_dir=BENCH_DIR) -> dict:
    """A per-layer metric's own file: its reader and the reader's
    arguments, beside a copy of what BENCHMARK.json says of it."""
    return json.loads(
        (Path(bench_dir) / "metrics" / f"{name}.json").read_text())


def peaks(device_kind, bench_dir=BENCH_DIR) -> dict:
    table = json.loads((Path(bench_dir) / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise SystemExit(f"perfbench: no peaks for device kind "
                         f"{device_kind!r}; add it to peaks.json with its "
                         "source")
    return table["devices"][device_kind]


def enable_compile_cache(root=ROOT) -> str:
    """JAX's persistent compile cache at ``JAX_COMPILATION_CACHE_DIR`` if
    that is set, else at the fixed ``.jax_cache`` of the checkout (the path
    is part of the cache's key)."""
    import os
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache
