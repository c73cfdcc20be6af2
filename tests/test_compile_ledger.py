"""The compile ledger (monitor/compile_ledger.py): set-up accounts for itself.

Claims pinned here, each as a difference of the process-wide counters
around the call that should move them (other tests share the registry):
- a fresh MultiLayerNetwork and a fresh ComputationGraph count their
  programs and seconds under ``init`` after ``init()`` and under ``fit``
  after the first ``fit``; a second ``fit`` of the same shapes adds nothing;
- a ``jax.jit`` called under no marker lands in ``outside``;
- a trace inside a trace adds its seconds once;
- with a cache directory the first process reads ``miss`` and the second
  ``hit`` with ``cache_load`` seconds above zero;
- the registration's own pass lands in ``register``, not in ``fit``;
- the step program's record carries the five build fields;
- with the tracer on the ring holds ``init`` and the stage spans inside the
  wall-clock interval of the call that caused them, with it off none;
- installing the listeners twice counts once.
"""

import itertools
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.exec.programs import get_programs
from deeplearning4j_tpu.models import ComputationGraph
from deeplearning4j_tpu.monitor import compile_ledger, get_registry, trace
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.updaters import Sgd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SECONDS = "dl4jtpu_compile_stage_seconds_total"
REQUESTS = "dl4jtpu_compile_requests_total"
STAGES = ("trace", "lower", "backend")
F, C, B = 6, 3, 8
# a hidden width no net of this process had: its programs are new to JAX's
# in-memory caches, so they reach the backend
_WIDTHS = itertools.count(131, 2)


def _builder():
    return (NeuralNetConfiguration.builder().seed(7).updater(Sgd(0.1))
            .weight_init("xavier"))


def _mln():
    return MultiLayerNetwork(
        _builder().list()
        .layer(DenseLayer(n_out=next(_WIDTHS), activation="tanh"))
        .layer(OutputLayer(n_out=C, activation="softmax", loss="mcxent"))
        .set_input_type(InputType.feed_forward(F)).build())


def _cg():
    g = (_builder().graph_builder().add_inputs("in")
         .set_input_types(InputType.feed_forward(F)))
    g.add_layer("a", DenseLayer(n_out=next(_WIDTHS), activation="tanh"),
                "in")
    g.add_layer("out", OutputLayer(n_out=C, activation="softmax",
                                   loss="mcxent"), "a")
    return ComputationGraph(g.set_outputs("out").build())


NETS = {"MultiLayerNetwork": _mln, "ComputationGraph": _cg}


def _data(steps=None):
    rs = np.random.RandomState(0)
    lead = (B,) if steps is None else (steps, B)
    x = rs.randn(*lead, F).astype(np.float32)
    y = np.eye(C, dtype=np.float32)[rs.randint(0, C, lead)]
    return x, y


def _snap():
    """{(family, label values): value} of the ledger's families and the
    two ``init`` counters."""
    out = {}
    for name in (SECONDS, REQUESTS, "dl4jtpu_init_seconds_total",
                 "dl4jtpu_init_leaves_total"):
        fam = get_registry().get(name)
        for values, child in (fam.children() if fam else ()):
            out[(name,) + values] = child.value
    return out


def _grew(before, family, *labels):
    """What the children of ``family`` whose label values start with
    ``labels`` added since ``before``."""
    now = _snap()
    return sum(v - before.get(k, 0.0) for k, v in now.items()
               if k[0] == family and k[1:1 + len(labels)] == labels)


@pytest.fixture
def tracer():
    trace.clear().enable(True)
    yield trace
    trace.enable(False).clear()


@pytest.mark.parametrize("kind", sorted(NETS))
def test_init_counts_under_init(kind):
    net = NETS[kind]()
    before, t0 = _snap(), time.perf_counter()
    net.init()
    wall = time.perf_counter() - t0
    assert _grew(before, REQUESTS, "init") >= 1
    staged = sum(_grew(before, SECONDS, "init", s) for s in STAGES)
    assert 0 < staged <= wall
    assert _grew(before, SECONDS, "fit") == 0
    assert _grew(before, REQUESTS, "fit") == 0
    secs = _grew(before, "dl4jtpu_init_seconds_total", kind)
    assert staged <= secs <= wall
    leaves = len(jax.tree_util.tree_leaves(
        (net.params, net.state, net.opt_state)))
    assert _grew(before, "dl4jtpu_init_leaves_total", kind) == leaves > 0


@pytest.mark.parametrize("kind", sorted(NETS))
def test_first_fit_counts_under_fit_and_a_second_adds_nothing(kind):
    net = NETS[kind]().init()
    x, y = _data()
    before = _snap()
    net.fit(x, y)
    # the step's program and nothing else of fit's: the registration's
    # compile is the ``register`` phase's
    assert _grew(before, REQUESTS, "fit") >= 1
    assert all(_grew(before, SECONDS, "fit", s) > 0 for s in STAGES)
    assert _grew(before, SECONDS, "init") == 0
    before = _snap()
    net.fit(x, y)
    for phase in ("init", "fit", "register"):
        assert _grew(before, SECONDS, phase) == 0
        assert _grew(before, REQUESTS, phase) == 0


def test_unmarked_jit_lands_in_outside():
    compile_ledger.install()
    assert compile_ledger.current_phase() == "outside"
    before = _snap()
    jax.jit(lambda a: a * 3 + 1)(jnp.ones(5)).block_until_ready()
    assert _grew(before, REQUESTS, "outside") >= 1
    assert all(_grew(before, SECONDS, "outside", s) > 0 for s in STAGES)
    for phase in ("init", "fit", "register", "output", "serve"):
        assert _grew(before, REQUESTS, phase) == 0


def test_nested_trace_adds_its_seconds_once(tracer):
    @jax.jit
    def ledger_inner(a):
        time.sleep(0.05)          # runs while the function is traced
        return a * 2

    @jax.jit
    def ledger_outer(a):
        return ledger_inner(a) + 1

    before = _snap()
    with compile_ledger.phase("nested_case"):
        ledger_outer(jnp.ones(3)).block_until_ready()
    spans = {e["args"]["fun_name"]: e["dur"] / 1e6 for e in trace.events()
             if e["name"] == "jit_trace"
             and e["args"]["phase"] == "nested_case"}
    inner, outer = spans["ledger_inner"], spans["ledger_outer"]
    assert 0.05 <= inner <= outer
    counted = _grew(before, SECONDS, "nested_case", "trace")
    # the outer span's own duration, not the two added up
    assert counted == pytest.approx(outer, abs=1e-4)


_CACHE_CHILD = """
import json, sys
import jax, jax.numpy as jnp
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from deeplearning4j_tpu.monitor import compile_ledger, get_registry
with compile_ledger.phase("cache_case"):
    jax.jit(lambda a: jnp.tanh(a @ a.T).sum())(jnp.ones((16, 16))
                                               ).block_until_ready()
out = {}
for name in ("dl4jtpu_compile_requests_total",
             "dl4jtpu_compile_stage_seconds_total"):
    for (phase, label), child in get_registry().get(name).children():
        if phase == "cache_case":
            out[label] = out.get(label, 0.0) + child.value
print(json.dumps(out))
"""


def test_cache_miss_in_the_first_process_hit_in_the_second(tmp_path):
    """Two processes, as a cold and a warm run are (and because this
    jaxlib's CPU backend may not deserialise an executable whose twin the
    process still holds: conftest.py)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    runs = []
    for _ in range(2):
        p = subprocess.run([sys.executable, "-c", _CACHE_CHILD,
                            str(tmp_path)], env=env, capture_output=True,
                           text=True, timeout=120)
        assert p.returncode == 0, p.stderr[-2000:]
        runs.append(json.loads(p.stdout.strip().splitlines()[-1]))
    cold, warm = runs
    assert cold.get("miss", 0) >= 1 and not cold.get("hit")
    assert not cold.get("cache_load")
    assert warm.get("hit", 0) >= 1 and not warm.get("miss")
    assert 0 < warm["cache_load"] <= warm["backend"]


def test_registration_lands_in_register_not_in_fit():
    before = _snap()
    fn = jax.jit(lambda a: jnp.sin(a).sum())      # never called: the
    rec = get_programs().record(                  # registry builds it
        "ledger_case", f"k{time.time_ns()}", fn, (jnp.ones((4, 4)),))
    assert rec["aot_seconds"] > 0
    assert _grew(before, REQUESTS, "register") == 1
    assert all(_grew(before, SECONDS, "register", s) > 0 for s in STAGES)
    staged = sum(_grew(before, SECONDS, "register", s) for s in STAGES)
    assert staged <= rec["aot_seconds"]
    assert _grew(before, REQUESTS, "fit") == 0
    assert _grew(before, SECONDS, "fit") == 0
    # a record nobody handed a build keeps the five fields, empty
    assert rec["cache"] is None and rec["trace_seconds"] is None


@pytest.mark.parametrize("path", ["train_step", "fit_scan"])
def test_step_record_says_how_it_came_to_be(path):
    net = _mln().init()
    before = _snap()
    if path == "train_step":
        net.fit(*_data())
    else:
        net.fit_scan(*_data(steps=2))
    rec = get_programs().last(net._prog_caller)
    assert rec["key"].startswith(path)
    for stage in STAGES:
        assert rec[f"{stage}_seconds"] == pytest.approx(
            _grew(before, SECONDS, "fit", stage), abs=1e-9)
        assert rec[f"{stage}_seconds"] > 0
    assert rec["cache_load_seconds"] >= 0
    assert rec["cache"] in ("hit", "miss", "uncached")
    built = sum(rec[f"{s}_seconds"] for s in STAGES)
    assert built <= rec["compile_seconds"]
    # GET /programs serves entries(): the fields are there, small
    shown = [e for e in get_programs().entries()
             if e["caller"] == net._prog_caller][-1]
    assert shown["cache"] == rec["cache"] and "op_scopes" not in shown


def test_output_and_serving_programs_have_their_own_phases():
    net = _mln().init()
    x, _ = _data()
    before = _snap()
    net.output(x, bucketed=False)
    assert _grew(before, REQUESTS, "output") >= 1
    before = _snap()
    net.output(x)                    # the bucketed engine's program
    assert _grew(before, REQUESTS, "serve") >= 1
    assert _grew(before, REQUESTS, "output") == 0


def test_tracer_on_ring_holds_init_and_stage_spans_inside_the_call(tracer):
    net = _cg()
    t0 = time.time()
    net.init()
    t1 = time.time()
    events = trace.events()
    assert [e["ph"] for e in events if e["name"] == "init"] == ["B", "E"]
    stages = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in stages} >= {"jit_trace", "jit_lower",
                                           "xla_compile"}
    slack = 0.05                     # the ring's clock against time.time()
    for e in stages:
        assert e["args"]["phase"] == "init" and e["args"]["fun_name"]
        assert t0 - slack <= e["ts"] / 1e6
        assert (e["ts"] + e["dur"]) / 1e6 <= t1 + slack
    assert all("result" in e["args"] for e in stages
               if e["name"] == "xla_compile")
    # the document Perfetto loads keeps them
    kept = trace.export()["traceEvents"]
    assert sum(e["ph"] == "X" for e in kept) == len(stages)


def test_tracer_off_ring_holds_none():
    trace.enable(False).clear()
    before = _snap()
    _mln().init()
    assert _grew(before, REQUESTS, "init") >= 1      # counters are always on
    assert trace.events() == []


def test_installing_twice_counts_once():
    compile_ledger.install()
    compile_ledger.install()
    from jax._src import monitoring      # the public module has no getters
    for listeners, mine in (
            (monitoring.get_event_time_span_listeners(),
             compile_ledger._on_span),
            (monitoring.get_event_duration_listeners(),
             compile_ledger._on_duration),
            (monitoring.get_event_listeners(), compile_ledger._on_event),
            (monitoring.get_scalar_listeners(), compile_ledger._on_scalar)):
        assert listeners.count(mine) == 1
    x = jnp.ones(2)
    before = _snap()
    jax.jit(lambda a: a - 7)(x).block_until_ready()
    assert _grew(before, REQUESTS, "outside") == 1


def test_phase_is_per_thread_and_the_innermost_wins():
    seen = {}
    with compile_ledger.phase("fit"):
        with compile_ledger.phase("register"):
            seen["inner"] = compile_ledger.current_phase()
        seen["restored"] = compile_ledger.current_phase()
        t = threading.Thread(target=lambda: seen.__setitem__(
            "thread", compile_ledger.current_phase()))
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    seen["after"] = compile_ledger.current_phase()
    assert seen == {"inner": "register", "restored": "fit",
                    "thread": "outside", "after": "outside"}
