"""The fit path accounts for itself (util/scopes.py, monitor/tracing.py,
util/timing.py, monitor/profiling.py): names inside the step programs, the
program's spans in a ``jax.profiler`` capture, counters at the fit loop's
boundaries. All on the CPU; the chip's own tables are in PERF.md §5."""

import contextlib
import glob
import os
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.exec.programs import (_lowerable, get_programs,
                                              hlo_instructions)
from deeplearning4j_tpu.models.computation_graph import ComputationGraph
from deeplearning4j_tpu.monitor import profiling
from deeplearning4j_tpu.monitor.tracing import trace
from deeplearning4j_tpu.nn.conf.graph_conf import ElementWiseVertex
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers import (
    ActivationLayer, BatchNormalization, ConvolutionLayer, DenseLayer,
    GlobalPoolingLayer, OutputLayer)
from deeplearning4j_tpu.nn.updaters import Nesterovs

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from perfbench.lib import scopes  # noqa: E402  the path rule under test

PHASES = {"forward", "recompute", "backward", "loss", "updater"}
# what the issue counts as doing no work
IDLE_OPS = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")


# --------------------------------------------------------------- the nets

def _builder():
    return (NeuralNetConfiguration.builder().seed(11)
            .updater(Nesterovs(1e-2, momentum=0.9)).weight_init("relu")
            .l2(1e-4).remat("save_convs"))


def _resnet_graph():
    """Stem and two bottlenecks (the first projects its shortcut), as
    zoo/resnet.py builds them, at 8 channels."""
    g = (_builder().graph_builder().add_inputs("input")
         .set_input_types(InputType.convolutional(8, 8, 3)))

    def conv_bn(name, inp, n_out, k, pad=0, act=True):
        g.add_layer(f"{name}_conv", ConvolutionLayer(
            n_out=n_out, kernel_size=k, padding=pad, has_bias=False,
            activation="identity"), inp)
        g.add_layer(f"{name}_bn", BatchNormalization(
            activation="relu" if act else "identity"), f"{name}_conv")
        return f"{name}_bn"

    def bottleneck(name, inp, f, project):
        x = conv_bn(f"{name}_a", inp, f, 1)
        x = conv_bn(f"{name}_b", x, f, 3, pad=1)
        x = conv_bn(f"{name}_c", x, 4 * f, 1, act=False)
        sc = conv_bn(f"{name}_sc", inp, 4 * f, 1, act=False) \
            if project else inp
        g.add_vertex(f"{name}_add", ElementWiseVertex(op="add"), x, sc)
        g.add_layer(f"{name}_out", ActivationLayer(activation="relu"),
                    f"{name}_add")
        return f"{name}_out"

    x = conv_bn("stem", "input", 8, 3, pad=1)
    x = bottleneck("res2/0", x, 8, True)       # a "/" the scope replaces
    x = bottleneck("res2_1", x, 8, False)
    g.add_layer("avgpool", GlobalPoolingLayer(pooling_type="avg"), x)
    g.add_layer("fc", OutputLayer(n_out=5, activation="softmax",
                                  loss="mcxent", n_in=32), "avgpool")
    g.set_outputs("fc")
    return ComputationGraph(g.build()).init()


def _conv_mln():
    conf = (_builder().list()
            .layer(ConvolutionLayer(n_out=4, kernel_size=3,
                                    activation="identity"))
            .layer(BatchNormalization(activation="relu"))
            .layer(DenseLayer(n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=5, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.convolutional(8, 8, 3)).build())
    return MultiLayerNetwork(conf).init()


def _batches(n=3, b=4):
    rs = np.random.RandomState(0)
    return [DataSet(rs.rand(b, 8, 8, 3).astype(np.float32),
                    np.eye(5, dtype=np.float32)[rs.randint(0, 5, b)])
            for _ in range(n)]


def _train(kind, net, data):
    """Three steps through ``kind``'s program; returns it lowered."""
    if kind == "fit_scan":
        xs = np.stack([d.features for d in data])
        ys = np.stack([d.labels for d in data])
        net.fit_scan(xs, ys)
        fn = net._scan_fit
        args = (net.params, net.state, net.opt_state,
                [jnp.asarray(xs)], [jnp.asarray(ys)],
                jnp.asarray(0, jnp.int32))
    else:
        for d in data:
            net.fit(d)
        x, y = net._batch_parts(data[0], jnp.asarray)[:2]
        fn = net._train_step_cache[(False, False)]
        args = (net.params, net.state, net.opt_state, x, y,
                jnp.asarray(0, jnp.int32), None, None)
    return _lowerable(fn).lower(*args)


_MAKE = {"graph": _resnet_graph, "mln": _conv_mln, "fit_scan": _resnet_graph}


def _leaves(net):
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(
        (net.params, net.state, net.opt_state))]


@contextlib.contextmanager
def _scopes_off(monkeypatch):
    """The containers as the parent commit traced them: no named scope."""
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    for cls in (ComputationGraph, MultiLayerNetwork):
        monkeypatch.setattr(cls, "_dp_apply_updates",
                            cls._dp_apply_updates.__wrapped__)
    yield
    monkeypatch.undo()


# ------------------------------------------------- A. names in the program

@pytest.mark.parametrize("kind", ["graph", "mln", "fit_scan"])
def test_step_program_classifies_and_is_bitwise_the_unscoped_one(
        kind, monkeypatch):
    data = _batches()
    scoped = _MAKE[kind]()
    text = _train(kind, scoped, data).compile().as_text()
    rows = hlo_instructions(text)
    # the CPU compiler's copies of donated parameters and the
    # weight-gradient convolutions it rewrites carry no op_name at all:
    # no scope the program opens could name them, so they do not count
    # (the chip's unscoped share of device time is in PERF.md §5)
    work = [(n, o, scopes.classify(o)) for n, op, o in rows
            if op not in IDLE_OPS
            and not (op in ("copy", "convolution") and not o)]
    phases = {ph for _, _, (ph, _, _) in work}
    assert PHASES <= phases, f"missing {PHASES - phases}"
    # what else stays unscoped is a fusion the compiler made and left
    # nameless, or the scan's own loop
    unscoped = [o for _, o, (ph, _, _) in work if ph == "unscoped"]
    assert all(not o or "/while" in o for o in unscoped), unscoped
    assert len(unscoped) < 0.10 * len(work), (len(unscoped), len(work))
    work = [(n, c) for n, _, c in work]
    # layers are named <name>:<Class>, a "/" in the name replaced
    layers = {layer for _, (_, layer, _) in work if layer}
    if kind == "mln":
        assert "layer1:BatchNormalization" in layers
    else:
        assert "res2_0_a_bn:BatchNormalization" in layers
        # the add is fused into its consumer, and a fusion has one name
        assert "/res2_0_add:ElementWiseVertex/" in text
        assert not any("/" in layer for layer in layers)

    with _scopes_off(monkeypatch):
        plain = _MAKE[kind]()
        # as lowered: the compile-cache key leaves names out, so the test
        # suite's compile memo (conftest.py) hands this program the scoped
        # executable, names and all
        plain_text = _train(kind, plain, data).as_text(debug_info=True)
    assert "rematted_computation" in plain_text
    for ours in ("/forward/", "/loss/", "/updater/", ":BatchNormalization"):
        assert ours not in plain_text
    for a, b in zip(_leaves(scoped), _leaves(plain)):
        assert a.tobytes() == b.tobytes()


def test_registry_keeps_scopes_aot_seconds_and_counts_donation_once():
    net = _resnet_graph()
    net.fit(_batches(1)[0])
    rec = get_programs().last(net._prog_caller)
    assert rec["key"] == "train_step_b4"
    assert rec["aot_seconds"] > 0 and rec["compile_seconds"] > 0
    table = rec["op_scopes"]
    assert any(scopes.classify(o)[0] == "updater" for o in table.values())
    assert any(scopes.classify(o) == ("recompute",
                                      "stem_bn:BatchNormalization",
                                      "BatchNormalization")
               for o in table.values())
    listed = [e for e in get_programs().entries()
              if e["caller"] == net._prog_caller]
    assert listed and all("op_scopes" not in e for e in listed)
    # parameters, momentum and statistics are donated: arguments and the
    # outputs that alias them are one buffer, and are counted once
    state = sum(a.nbytes for a in jax.tree_util.tree_leaves(
        (net.params, net.state, net.opt_state)))
    fn = _lowerable(net._train_step_cache[(False, False)])
    d = _batches(1)[0]
    mem = fn.lower(net.params, net.state, net.opt_state,
                   [jnp.asarray(d.features)], [jnp.asarray(d.labels)],
                   jnp.asarray(0, jnp.int32), None, None
                   ).compile().memory_analysis()
    assert mem.alias_size_in_bytes >= 0.9 * state
    assert rec["memory_bytes"] == (
        mem.temp_size_in_bytes + mem.argument_size_in_bytes
        + mem.output_size_in_bytes + mem.generated_code_size_in_bytes
        - mem.alias_size_in_bytes)


@pytest.mark.parametrize("chunked", [False, True])
@pytest.mark.parametrize("kind", ["mln", "graph"])
def test_streamed_fit_leaves_its_program_in_the_registry(kind, chunked):
    """Whichever container, whichever program the chunk rule picks: what
    ``step_program_hbm_share`` and ``train_compile_s`` read is there."""
    net = _MAKE[kind]()
    if not chunked:
        net._CHUNK_MAX_BYTES = 1
    net.fit(iter(_batches(3)))
    rec = get_programs().last(net._prog_caller)
    assert rec["key"] == ("fit_scan_k3_b4" if chunked else "train_step_b4")
    assert rec["memory_bytes"] > 0 and rec["aot_seconds"] > 0
    assert any(scopes.classify(o)[0] == "updater"
               for o in rec["op_scopes"].values())


@pytest.mark.parametrize("kind", ["mln", "graph"])
def test_a_mixed_stream_is_cut_the_same_way_under_both_containers(kind):
    """A run of one shape, a shape change, a masked batch, a last one alone:
    ``_stream_chunks`` is one function, and a batch's form does not show in
    where it cuts."""
    from deeplearning4j_tpu.util.timing import PipelineTimer
    masked = _batches(1)[0]
    masked.labels_mask = np.ones(4, np.float32)
    data = _batches(3) + _batches(2, b=2) + [masked] + _batches(1)
    net = _MAKE[kind]()
    got = [(k, [len(a) for a in jax.tree_util.tree_leaves(payload[:2])])
           for k, payload in net._stream_chunks(iter(data), None,
                                                PipelineTimer())]
    assert got == [("chunk", [3, 3]), ("chunk", [2, 2]), ("batch", [4, 4]),
                   ("batch", [4, 4])]


# ------------------------------ B. the spans on the profiler's clock

def _host_events(log_dir):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                {k: v for k, v in ev.stats}))
    return out


@pytest.mark.parametrize("tracer_on", [True, False])
def test_fit_spans_land_in_a_level1_profile(tracer_on, tmp_path):
    net = _conv_mln()
    net._CHUNK_MAX_BYTES = 1               # one train_step a batch
    net.fit(_batches(1)[0])                # compile outside the capture
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 1
    options.python_tracer_level = 0
    was = trace.enabled
    trace.enable(tracer_on)
    try:
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            net.fit(iter(_batches(3)))
            jax.block_until_ready(net.params)
        finally:
            jax.profiler.stop_trace()
    finally:
        trace.enable(was)
    events = _host_events(str(tmp_path))
    steps = [e for e in events if e[0] == "train_step"]
    if not tracer_on:
        assert not steps
        assert not [e for e in events if e[0] in ("wait", "dispatch")]
        return

    def inside(step, name):
        return [e for e in events if e[0] == name
                and step[1] <= e[1] and e[2] <= step[2]]

    stepped = [s for s in steps if inside(s, "dispatch")]
    assert len(stepped) == 3
    nums = [s[3]["step_num"] for s in stepped]
    assert nums == list(range(nums[0], nums[0] + 3))
    for s in stepped:
        assert len(inside(s, "wait")) == 1
        assert len(inside(s, "dispatch")) == 1
    # the loop's last turn finds the stream at its end: a wait, no step
    assert len(steps) == 4 and not inside(steps[-1], "dispatch")
    h2d = [e for e in events if e[0] == "h2d"]
    waits = [e for e in events if e[0] == "wait"]
    assert h2d and all(any(w[1] <= h[1] and h[2] <= w[2] for w in waits)
                       for h in h2d)


def test_profile_scope_restores_the_tracer(tmp_path, monkeypatch):
    monkeypatch.setenv(profiling.PROFILE_ENV, str(tmp_path))
    for before in (False, True):
        trace.enable(before)
        try:
            with profiling.profile_scope():
                assert trace.enabled
            assert trace.enabled is before
            with pytest.raises(RuntimeError):
                with profiling.profile_scope():
                    raise RuntimeError("fit failed")
            assert trace.enabled is before
        finally:
            trace.enable(False)
    assert glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))


# --------------------------------- C. counters at the loop's boundaries

@pytest.mark.parametrize("kind", ["mln", "graph"])
def test_pipeline_stats_count_steps_bytes_and_flight(kind):
    net = _MAKE[kind]()
    net._CHUNK_MAX_BYTES = 1
    data = _batches(5)
    net.fit(iter(data))
    st = net.last_pipeline_stats
    assert st["steps"] == 5
    assert st["bytes_staged"] == sum(
        d.features.nbytes + d.labels.nbytes for d in data)
    assert 0 <= st["loop_cpu_sec"] <= st["wall_sec"]
    assert st["process_cpu_sec"] >= st["loop_cpu_sec"]
    assert 1 <= st["in_flight_max"] <= 5
    assert st["wait_sec"] >= 0 and st["wall_sec"] > 0
    assert st["dispatch_sec"] >= 0 and "step_sec" not in st
    # a chunked call is one dispatch of several steps
    net._CHUNK_MAX_BYTES = 256 << 20
    net.fit(iter(data))
    st = net.last_pipeline_stats
    assert st["steps"] == 5 and st["in_flight_max"] == 1


@pytest.mark.parametrize("chunked", [False, True])
@pytest.mark.parametrize("kind", ["mln", "graph"])
def test_fit_iterator_with_score_listeners_counts_flight(kind, chunked):
    """A listener's ``get_score()`` leaves a float where the loss array
    was; the loop's in-flight count has to live with that."""
    from deeplearning4j_tpu.optimize.listeners import (
        CollectScoresIterationListener, ScoreIterationListener)
    net = _MAKE[kind]()
    if not chunked:
        net._CHUNK_MAX_BYTES = 1
    data = _batches(6)
    every, second = CollectScoresIterationListener(1), \
        ScoreIterationListener(2)
    # read at every second step: the step between stays in flight (a
    # chunk is one call of six steps, and six is a second step)
    net.set_listeners(second)
    net.fit(iter(data))
    st = net.last_pipeline_stats
    assert st["steps"] == 6
    assert st["in_flight_max"] == (0 if chunked else 1)
    # read at every step (or chunk): the host waits for each
    net.set_listeners(every, second)
    net.fit(iter(data))
    st = net.last_pipeline_stats
    assert st["steps"] == 6 and st["in_flight_max"] == 0
    assert len(every.scores) == (1 if chunked else 6)
    assert all(np.isfinite(s) for _, s in every.scores)
