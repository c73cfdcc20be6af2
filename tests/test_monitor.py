"""Observability subsystem: metrics registry, span tracer, and their wiring.

The load-bearing claims pinned here:
- the registry is exact under concurrent writers (8 threads of increments
  lose nothing — Counter holds a lock, not a hope);
- histogram buckets use Prometheus ``le`` (≤) semantics and the rendered
  text exposition round-trips through an independent parser: cumulative
  buckets are monotone and the ``+Inf`` bucket equals ``_count``;
- the tracer emits balanced, correctly NESTED begin/end events and valid
  Chrome trace JSON; disabled, it returns a shared no-op span and records
  nothing;
- a streamed ``fit`` under tracing produces ``train_step`` spans that
  nest the ``wait``/``step`` (and ``fetch``/``h2d``) children — the
  acceptance shape for a Perfetto timeline;
- ``GET /metrics`` serves the request-latency histogram and queue-depth
  gauge in valid exposition text, ``GET /healthz`` answers, and ``/stats``
  agrees with ``/metrics`` because both read the same registry cells;
- training is bitwise-identical with monitoring on vs off.
"""

import json
import re
import threading
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.monitor import (
    MetricsRegistry, Tracer, get_registry, set_metrics_enabled, trace)
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.updaters import Adam, Sgd
from deeplearning4j_tpu.serving import InferenceServer


@pytest.fixture(autouse=True)
def _restore_observability():
    """Every test leaves the process-wide registry/tracer as it found them."""
    reg = get_registry()
    prev_enabled = reg.enabled
    prev_trace = trace.enabled
    yield
    reg.enabled = prev_enabled
    trace.enable(prev_trace)
    trace.clear()


def _mlp(seed=42):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-2))
            .weight_init("xavier").list()
            .layer(DenseLayer(n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(4))
            .build())
    return MultiLayerNetwork(conf).init()


def _toy_data(n_batches=6, batch=8, seed=0):
    rs = np.random.RandomState(seed)
    out = []
    for _ in range(n_batches):
        x = rs.rand(batch, 4).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, size=batch)]
        out.append(DataSet(x, y))
    return out


# A parser independent of the renderer: Prometheus text exposition lines.
_LINE = re.compile(r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? (\S+)$')


def _parse_exposition(text):
    """{series_with_labels: float} plus {name: TYPE} from a /metrics body."""
    series, types = {}, {}
    for line in text.strip().splitlines():
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            types[name] = kind
            continue
        if line.startswith("#"):
            continue
        m = _LINE.match(line)
        assert m, f"unparseable exposition line: {line!r}"
        series[m.group(1) + (m.group(2) or "")] = float(m.group(3))
    return series, types


# ------------------------------------------------------------- registry core

def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("jobs_total", "jobs", ("kind",))
    c.labels(kind="a").inc()
    c.labels(kind="a").inc(2.5)
    c.labels(kind="b").inc()
    assert c.labels(kind="a").value == 3.5
    assert c.labels(kind="b").value == 1.0
    with pytest.raises(ValueError):
        c.labels(kind="a").inc(-1)
    with pytest.raises(ValueError):
        c.labels(wrong="a")
    g = reg.gauge("depth")
    g.set(7)
    g.inc(3)
    g.dec()
    assert g.value == 9.0


def test_registry_rejects_kind_and_label_mismatch():
    reg = MetricsRegistry()
    reg.counter("x_total", "x", ("a",))
    with pytest.raises(ValueError):
        reg.gauge("x_total")
    with pytest.raises(ValueError):
        reg.counter("x_total", "x", ("b",))


def test_histogram_bucket_boundaries_le_semantics():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "l", buckets=(1.0, 2.0, 5.0))
    for v in (1.0, 2.5, 7.0):     # 1.0 lands IN the le=1 bucket (≤, not <)
        h.observe(v)
    assert h.cumulative() == [(1.0, 1), (2.0, 1), (5.0, 2),
                              (float("inf"), 3)]
    assert h.count == 3 and h.sum == pytest.approx(10.5)


def test_histogram_percentile():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "l", buckets=(0.01, 0.1, 1.0))
    assert h.percentile(0.5) is None          # nothing observed yet
    for _ in range(100):
        h.observe(0.05)                        # all in the (0.01, 0.1] bucket
    p50 = h.percentile(0.5)
    assert 0.01 < p50 <= 0.1
    h.observe(50.0)                            # beyond the last finite bound
    assert h.percentile(1.0) == 1.0            # saturates at that bound


def test_registry_thread_safety_exact_totals():
    reg = MetricsRegistry()
    c = reg.counter("hits_total")
    h = reg.histogram("obs", buckets=(0.5,))
    n_threads, n_incs = 8, 5000

    def worker():
        for _ in range(n_incs):
            c.inc()
            h.observe(0.25)

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * n_incs
    assert h.count == n_threads * n_incs
    assert h.cumulative()[-1] == (float("inf"), n_threads * n_incs)


def test_enabled_flag_gates_recording():
    reg = MetricsRegistry()
    c = reg.counter("n_total")
    h = reg.histogram("h", buckets=(1.0,))
    reg.enabled = False
    c.inc()
    h.observe(0.5)
    assert c.value == 0 and h.count == 0
    reg.enabled = True
    c.inc()
    assert c.value == 1


def test_function_gauge_reads_live():
    reg = MetricsRegistry()
    box = {"v": 3}
    g = reg.gauge("live").set_function(lambda: box["v"])
    assert g.value == 3.0
    box["v"] = 11
    assert g.value == 11.0
    assert 'live 11.0' in reg.render()


def test_prometheus_exposition_round_trip():
    reg = MetricsRegistry()
    reg.counter("req_total", "requests", ("path",)).labels(
        path="/a").inc(4)
    reg.gauge("depth", "queue depth").set(2)
    h = reg.histogram("lat_seconds", "latency", ("path",), buckets=(0.1, 1.0))
    h.labels(path="/a").observe(0.05)
    h.labels(path="/a").observe(0.5)
    h.labels(path="/a").observe(5.0)
    series, types = _parse_exposition(reg.render())
    assert types == {"req_total": "counter", "depth": "gauge",
                     "lat_seconds": "histogram"}
    assert series['req_total{path="/a"}'] == 4.0
    assert series["depth"] == 2.0
    # cumulative buckets are monotone and +Inf equals _count
    b1 = series['lat_seconds_bucket{path="/a",le="0.1"}']
    b2 = series['lat_seconds_bucket{path="/a",le="1.0"}']
    binf = series['lat_seconds_bucket{path="/a",le="+Inf"}']
    assert (b1, b2, binf) == (1.0, 2.0, 3.0)
    assert series['lat_seconds_count{path="/a"}'] == 3.0
    assert series['lat_seconds_sum{path="/a"}'] == pytest.approx(5.55)


def test_snapshot_flat_dict():
    reg = MetricsRegistry()
    reg.counter("a_total").inc(2)
    reg.histogram("h", buckets=(1.0,)).observe(0.5)
    snap = reg.snapshot()
    assert snap["a_total"] == 2.0
    assert snap["h_count"] == 1 and snap["h_sum"] == 0.5
    assert reg.snapshot(kinds=("counter",)) == {"a_total": 2.0}


# ----------------------------------------------------------------- tracer

def _span_pairs(events):
    """Match B/E per tid by stack discipline; returns [(B, E), ...] and
    asserts balance + proper nesting (an E always closes the open B)."""
    stacks, pairs = {}, []
    for ev in sorted(events, key=lambda e: e["ts"]):
        if ev["ph"] == "B":
            stacks.setdefault(ev["tid"], []).append(ev)
        elif ev["ph"] == "E":
            top = stacks[ev["tid"]].pop()
            assert top["name"] == ev["name"], "interleaved, not nested"
            pairs.append((top, ev))
    assert all(not s for s in stacks.values()), "unbalanced B/E"
    return pairs


def test_tracer_nested_spans_and_export(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("outer", n=1):
        with tr.span("inner"):
            pass
        with tr.span("inner"):
            pass
    tr.instant("marker")
    pairs = _span_pairs([e for e in tr.events() if e["ph"] in "BE"])
    names = sorted(b["name"] for b, _ in pairs)
    assert names == ["inner", "inner", "outer"]
    outer = next(b for b, _ in pairs if b["name"] == "outer")
    outer_end = next(e for b, e in pairs if b["name"] == "outer")
    for b, e in pairs:
        if b["name"] == "inner":
            assert outer["ts"] <= b["ts"] and e["ts"] <= outer_end["ts"]
    assert outer["args"] == {"n": 1}

    path = tmp_path / "trace.json"
    doc = tr.export(str(path))
    loaded = json.loads(path.read_text())   # valid JSON on disk
    assert loaded["traceEvents"] == doc["traceEvents"]
    ts = [e["ts"] for e in loaded["traceEvents"]]
    assert ts == sorted(ts)
    assert any(e["ph"] == "i" and e["name"] == "marker"
               for e in loaded["traceEvents"])


def test_tracer_disabled_is_noop():
    tr = Tracer(enabled=False)
    s1 = tr.span("a")
    s2 = tr.span("b", k=1)
    assert s1 is s2                       # the shared null span: no alloc
    with s1:
        pass
    tr.instant("x")
    assert tr.events() == []


def test_tracer_ring_buffer_bounds_memory():
    tr = Tracer(capacity=10, enabled=True)
    for i in range(50):
        with tr.span(f"s{i}"):
            pass
    evs = tr.events()
    assert len(evs) == 10
    assert evs[-1]["name"] == "s49"       # newest kept, oldest dropped


# ------------------------------------------------------- training integration

def test_streamed_fit_trace_nests_step_spans():
    net = _mlp()
    trace.enable(True)
    trace.clear()
    try:
        net.fit(_toy_data(n_batches=6))
    finally:
        trace.enable(False)
    events = [e for e in trace.events() if e["ph"] in "BE"]
    pairs = _span_pairs(events)
    by_name = {}
    for b, e in pairs:
        by_name.setdefault(b["name"], []).append((b, e))
    for required in ("train_step", "wait", "dispatch", "fetch", "h2d"):
        assert required in by_name, f"missing span {required!r}"
    # every dispatch span sits inside some train_step span
    for sb, se in by_name["dispatch"]:
        assert any(tb["ts"] <= sb["ts"] and se["ts"] <= te["ts"]
                   for tb, te in by_name["train_step"]
                   if tb["tid"] == sb["tid"]), \
            "dispatch not nested in train_step"


def test_train_metrics_recorded_and_pipeline_published():
    reg = get_registry()
    steps_fam = reg.counter("dl4jtpu_train_steps_total",
                            labelnames=("model",))
    before = steps_fam.labels(model="MultiLayerNetwork").value
    net = _mlp()
    net.fit(_toy_data(n_batches=6))
    after = steps_fam.labels(model="MultiLayerNetwork").value
    assert after - before == 6            # every scanned step is counted
    ex_fam = reg.get("dl4jtpu_train_examples_total")
    assert ex_fam is not None
    stage = reg.get("dl4jtpu_pipeline_stage_seconds_total")
    assert stage is not None
    assert stage.labels(path="fit", stage="dispatch").value > 0
    frac = reg.get("dl4jtpu_pipeline_host_stall_frac")
    assert 0.0 <= frac.labels(path="fit").value <= 1.0
    # the registry snapshot renders cleanly with everything above in it
    assert "dl4jtpu_train_steps_total" in reg.render()


def test_training_bitwise_identical_monitored_or_not():
    data = _toy_data(n_batches=4)
    set_metrics_enabled(True)
    trace.enable(True)
    try:
        net_on = _mlp(seed=7)
        net_on.fit(data)
    finally:
        trace.enable(False)
    set_metrics_enabled(False)
    try:
        net_off = _mlp(seed=7)
        net_off.fit(data)
    finally:
        set_metrics_enabled(True)
    for a, b in zip(net_on.params, net_off.params):
        for k in a:
            assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), \
                f"monitoring changed the training math at {k}"


# ------------------------------------------------------------ serving surface

def test_metrics_and_healthz_endpoints():
    net = _mlp()
    srv = InferenceServer(net, port=0, max_latency_ms=1.0).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        # traffic first, so the latency histogram has observations
        rs = np.random.RandomState(3)
        for n in (1, 5, 8):
            out = srv.batcher.predict(rs.rand(n, 4).astype(np.float32))
            assert out.shape == (n, 3)

        with urllib.request.urlopen(base + "/healthz", timeout=5) as r:
            assert r.status == 200
            assert json.loads(r.read()) == {"status": "ok"}

        with urllib.request.urlopen(base + "/metrics", timeout=5) as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/plain")
            body = r.read().decode()
        series, types = _parse_exposition(body)
        assert types["dl4jtpu_serving_request_latency_seconds"] == "histogram"
        assert types["dl4jtpu_serving_queue_depth"] == "gauge"
        bid = srv.batcher.id
        assert series[f'dl4jtpu_serving_request_latency_seconds_count'
                      f'{{batcher="{bid}"}}'] == 3.0
        assert series[f'dl4jtpu_serving_queue_depth{{batcher="{bid}"}}'] == 0.0
        assert series[f'dl4jtpu_serving_requests_total{{batcher="{bid}"}}'] \
            == 3.0
        # the /healthz hit above landed in the HTTP counter by scrape time
        assert series['dl4jtpu_http_requests_total{path="/healthz"}'] >= 1.0
    finally:
        srv.stop()


def test_stats_and_metrics_read_the_same_cells():
    net = _mlp()
    srv = InferenceServer(net, port=0, max_latency_ms=1.0).start()
    try:
        rs = np.random.RandomState(4)
        for n in (2, 3, 9, 1):
            srv.batcher.predict(rs.rand(n, 4).astype(np.float32))
        st = srv.stats()
        series, _ = _parse_exposition(get_registry().render())
        bid, eid = st["batcher"]["id"], st["engine"]["id"]
        assert st["batcher"]["requests"] == series[
            f'dl4jtpu_serving_requests_total{{batcher="{bid}"}}']
        assert st["batcher"]["rows"] == series[
            f'dl4jtpu_serving_rows_total{{batcher="{bid}"}}'] == 15
        assert st["batcher"]["device_calls"] == series[
            f'dl4jtpu_serving_device_calls_total{{batcher="{bid}"}}']
        assert st["engine"]["compiled_programs"] == series[
            f'dl4jtpu_serving_compiled_programs_total{{engine="{eid}"}}']
        assert st["engine"]["rows"] == series[
            f'dl4jtpu_serving_batch_rows_total{{engine="{eid}"}}']
        assert 0.0 <= st["engine"]["pad_waste_frac"] < 1.0
        assert st["batcher"]["latency_p50_ms"] > 0
    finally:
        srv.stop()


# --------------------------------------------------------------- listeners

def test_score_listener_logs_without_stdout(capsys):
    from deeplearning4j_tpu.optimize.listeners import ScoreIterationListener

    class _M:
        def get_score(self):
            return 0.5

    lst = ScoreIterationListener(1)
    lst.iteration_done(_M(), 10, 0)
    assert capsys.readouterr().out == ""   # logger only, no bare print


def test_performance_listener_registry_sink():
    from deeplearning4j_tpu.optimize.listeners import PerformanceListener

    class _M:
        _last_input = np.zeros((16, 4), np.float32)
        _last_fit_time = 0.002

        def get_score(self):
            return 0.25

    reg = MetricsRegistry()
    lst = PerformanceListener(frequency=10, registry=reg)
    lst.iteration_done(_M(), 10, 0)        # arms the window
    lst.iteration_done(_M(), 20, 0)        # reports
    batches = reg.get("dl4jtpu_listener_batches_per_sec").value
    samples = reg.get("dl4jtpu_listener_samples_per_sec").value
    assert batches > 0
    assert samples == pytest.approx(batches * 16)
