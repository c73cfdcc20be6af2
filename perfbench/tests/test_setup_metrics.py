"""The six metrics of set-up by part (PR 38) and their reader, without a
chip: ``readers/registry_counter_sum.py`` on a hand-made registry, and the
metric files against their BENCHMARK.json entries."""

import json
import os

import pytest

from perfbench.lib import spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SIX = ("init_s", "init_programs_built", "setup_trace_lower_s",
       "setup_xla_compile_s", "setup_cache_load_s", "setup_cache_misses")
SECONDS = "dl4jtpu_compile_stage_seconds_total"
REQUESTS = "dl4jtpu_compile_requests_total"


@pytest.fixture
def registry(monkeypatch):
    """A registry of the program's own kind, filled by hand, in the place
    of the process's."""
    from deeplearning4j_tpu.monitor import metrics
    reg = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "get_registry", lambda: reg)
    sec = reg.counter(SECONDS, "", ("phase", "stage"))
    for (phase, stage), v in {
            ("init", "trace"): 1.0, ("init", "lower"): 2.0,
            ("init", "backend"): 10.0, ("init", "cache_load"): 4.0,
            ("fit", "trace"): 20.0, ("fit", "lower"): 5.0,
            ("fit", "backend"): 30.0, ("fit", "cache_load"): 25.0,
            ("register", "trace"): 0.5, ("register", "backend"): 1.5,
            ("outside", "trace"): 100.0, ("outside", "lower"): 100.0,
            ("outside", "backend"): 100.0, ("outside", "cache_load"): 50.0,
            ("serve", "backend"): 1000.0}.items():
        sec.labels(phase=phase, stage=stage).inc(v)
    req = reg.counter(REQUESTS, "", ("phase", "result"))
    for (phase, result), v in {
            ("init", "hit"): 40, ("init", "miss"): 7, ("fit", "hit"): 1,
            ("fit", "uncached"): 2, ("register", "miss"): 1,
            ("outside", "miss"): 9, ("outside", "hit"): 30}.items():
        req.labels(phase=phase, result=result).inc(v)
    init = reg.counter("dl4jtpu_init_seconds_total", "", ("model",))
    init.labels(model="ComputationGraph").inc(12.5)
    init.labels(model="MultiLayerNetwork").inc(0.5)
    return reg


def _read(name):
    mf = spec.metric_file(name)
    return spec.load_module("readers", mf["reader"]).read(
        {}, {}, {}, mf.get("args", {}))


@pytest.mark.parametrize("name, want", [
    ("init_s", 13.0),                          # both containers
    ("init_programs_built", 47.0),             # every result under init
    ("setup_trace_lower_s", 28.5),             # init + fit + register
    ("setup_xla_compile_s", 41.5 - 29.0),      # backend less cache_load
    ("setup_cache_load_s", 29.0),
    ("setup_cache_misses", 10.0)])             # miss + uncached, not hit
def test_reader_sums_the_labelled_children(registry, name, want):
    assert _read(name) == pytest.approx(want)


def test_reader_none_where_the_family_is_absent(monkeypatch):
    from deeplearning4j_tpu.monitor import metrics
    empty = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "get_registry", lambda: empty)
    assert [_read(n) for n in SIX] == [None] * 6
    # the family there and no child among the labels: nothing, not absent
    empty.counter(REQUESTS, "", ("phase", "result")).labels(
        phase="outside", result="hit").inc()
    assert _read("setup_cache_misses") == 0.0
    assert _read("init_programs_built") == 0.0


def test_the_six_files_say_what_benchmark_json_says():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    assert [m["name"] for m in bench["per_layer"]][-6:] == list(SIX)
    for name in SIX:
        m, f = entries[name], spec.metric_file(name)
        assert {k: f[k] for k in m} == m
        assert m["layer"] == "execution core" and m["moves"] == "setup_s"
        assert m["better"] == "lower" and m["workloads"] == cells[:4]
        assert f["reader"] == "registry_counter_sum"
