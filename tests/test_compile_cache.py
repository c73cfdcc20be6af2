"""Where the persistent compile cache lives, what may steer kernel routing,
and where a streamed batch lands on a multi-device mesh.

Claims pinned here:
- ``JAX_COMPILATION_CACHE_DIR`` set → ``setup_compile_cache()`` returns it
  and leaves ``jax.config.jax_compilation_cache_dir`` equal to it, whatever
  ``cache_dir=`` says; unset → the fixed ``<checkout>/.jax_cache``; an
  explicit ``cache_dir=`` survives later no-argument calls (the engines'
  warmups make them);
- a routing table lying in the cache directory — an ignored path — is never
  read: only the tracked KERNELS_TPU.json, or a file the caller names;
- on a 4-device mesh the input prefetcher hands ``fit`` a batch that is
  already split over the 4 devices (not staged whole on the first).
"""

import json
import os

import numpy as np
import pytest

import jax

from deeplearning4j_tpu.exec import routing
from deeplearning4j_tpu.util import compile_cache as cc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """Restore the process's cache settings (conftest's teardown resets
    only the directory)."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


class TestCacheDirectory:
    def test_env_dir_wins_over_everything(self, tmp_path, monkeypatch,
                                          cache_config):
        env_dir = str(tmp_path / "from_env")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert cc.setup_compile_cache() == env_dir
        assert jax.config.jax_compilation_cache_dir == env_dir
        # the code names no other directory while the environment names one
        assert cc.setup_compile_cache(str(tmp_path / "arg")) == env_dir
        assert jax.config.jax_compilation_cache_dir == env_dir
        assert cc.cache_stats()["dir"] == env_dir

    def test_unset_env_is_the_fixed_checkout_path(self, monkeypatch,
                                                  cache_config):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(ROOT, ".jax_cache")
        assert cc.setup_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert cc.setup_compile_cache() == want      # idempotent

    def test_explicit_dir_survives_no_arg_calls(self, tmp_path, monkeypatch,
                                                cache_config):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        arm = str(tmp_path / "arm")
        assert cc.setup_compile_cache(cache_dir=arm) == arm
        # what an engine's warmup() does inside an isolated arm
        assert cc.setup_compile_cache() == arm
        assert jax.config.jax_compilation_cache_dir == arm


def test_routing_ignores_a_table_in_the_cache_dir(tmp_path, monkeypatch,
                                                  cache_config):
    """A leftover autotune table next to the compile cache used to be
    merged over the shipped one at first lookup — a routing decision taken
    from a file git does not hold."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("DL4JTPU_LSTM_FWD_ROUTE", raising=False)
    cache = cc.setup_compile_cache(cache_dir=str(tmp_path))
    row = {"kernel": "fused_lstm", "B": 3, "T": 5, "H": 7,
           "dtype": "float32", "fwd_speedup": 9.0, "grad_speedup": 0.1}
    for name in ("autotune_cpu.json", "autotune_tpu.json"):
        with open(os.path.join(cache, name), "w") as f:
            json.dump({"results": [row]}, f)
    saved = (dict(routing._MEASURED), dict(routing._MEASURED_GRAD),
             routing._file_loaded)
    try:
        routing._reset_measurement_cache()
        # the heuristic's answers (tiny B*H scans; the backward defaults
        # to the kernel), not the planted row's
        assert routing.lstm_fwd_route(3, 7, t=5, dtype="float32") == "scan"
        assert routing.lstm_grad_route(3, 7, t=5,
                                       dtype="float32") == "pallas"
        assert ("fused_lstm", 3, 5, 7, "float32") not in routing._MEASURED
    finally:
        routing._MEASURED.clear(), routing._MEASURED.update(saved[0])
        routing._MEASURED_GRAD.clear()
        routing._MEASURED_GRAD.update(saved[1])
        routing._file_loaded = saved[2]


def _small_net(container, **global_conf):
    """Dense 5 -> 8 -> 3 as a list of layers or as a graph."""
    from deeplearning4j_tpu import (ComputationGraph, MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.updaters import Sgd

    b = NeuralNetConfiguration.builder().seed(0).updater(Sgd(0.1))
    for name, value in global_conf.items():
        b = getattr(b, name)(value)
    if container == "mln":
        conf = (b.list()
                .layer(DenseLayer(n_out=8, activation="relu"))
                .layer(OutputLayer(n_out=3, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(5)).build())
        return MultiLayerNetwork(conf).init()
    conf = (b.graph_builder().add_inputs("in")
            .add_layer("d", DenseLayer(n_out=8, activation="relu"), "in")
            .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                          loss="mcxent"), "d")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(5)).build())
    return ComputationGraph(conf).init()


@pytest.mark.parametrize("container", ["mln", "graph"])
def test_remat_blocks_is_a_graphs_mode(container):
    """Blocks are runs of graph nodes; a list of layers has none and says
    so, where the mode would do nothing in silence."""
    if container == "mln":
        with pytest.raises(ValueError, match="a list of layers has"):
            _small_net(container, remat="blocks")
        return
    from deeplearning4j_tpu.data.dataset import DataSet
    net = _small_net(container, remat="blocks")
    net.fit(DataSet(np.zeros((4, 5), np.float32),
                    np.eye(3, dtype=np.float32)[[0, 1, 2, 0]]))
    assert np.isfinite(net.get_score())


@pytest.mark.parametrize("container", ["mln", "graph"])
def test_streamed_fit_batch_is_split_over_four_devices(container):
    """``fit(iterator)`` on a 4-device mesh: what the prefetcher hands the
    step already has one shard per device along the batch axis."""
    from deeplearning4j_tpu.data.iterators import ExistingDataSetIterator
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.exec import Executor, build_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    B, steps = 64, 4
    net = _small_net(container)
    net._exec = Executor(mesh=build_mesh(jax.devices()[:4]))

    rs = np.random.RandomState(0)
    batches = [DataSet(rs.rand(B, 5).astype(np.float32),
                       np.eye(3, dtype=np.float32)[rs.randint(0, 3, B)])
               for _ in range(steps)]
    seen = []
    real_fit_scan = net.fit_scan

    def spy(xs, ys):
        seen.append((xs, ys))
        return real_fit_scan(xs, ys)

    net.fit_scan = spy
    net.fit(ExistingDataSetIterator(batches))
    assert seen, "the streamed path never reached fit_scan"
    for xs, ys in seen:
        for a in jax.tree_util.tree_leaves((xs, ys)):
            assert len(a.sharding.device_set) == 4, a.sharding
            shards = a.addressable_shards
            assert {s.device for s in shards} == set(jax.devices()[:4])
            # (steps, batch, ...) chunks split along the batch axis
            assert all(s.data.shape[1] == B // 4 for s in shards)
    # and the step itself traced once: its arguments always arrive
    # committed to the mesh
    assert net._compile_count == 1
