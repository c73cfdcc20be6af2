"""Execution core (exec/): mesh policy, sharded parity, trace counts,
kernel routing.

The load-bearing claims pinned here:
- the sharding decision is a pure function of argument shapes (same shape
  -> same compiled program), with the measured min-rows-per-shard
  threshold keeping small batches on the exact single-device program;
- on a 1-device mesh ``Executor.jit`` IS ``jax.jit`` — no wrapper, zero
  new XLA programs vs the pre-executor code;
- sharded d=8 execution (the conftest-forced host devices) matches d=1
  within pinned tolerance for fit / predict / decode — f32 reductions
  reorder across shard boundaries, so the pin is a tolerance, not
  bitwise (measured max abs diff ~3e-8 on a conv forward);
- the fused-LSTM forward routes per measured shape (KERNELS_TPU.json),
  overridably.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu import exec as ex
from deeplearning4j_tpu.exec.executor import Executor, param_spec
from deeplearning4j_tpu.exec.mesh import _mesh_from_env
from deeplearning4j_tpu.exec import routing
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers import (DenseLayer, OutputLayer, LSTM,
                                          RnnOutputLayer)
from deeplearning4j_tpu.nn.updaters import Adam
from deeplearning4j_tpu.data.dataset import DataSet

V = 13


def _mln(seed=42):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-2))
            .weight_init("xavier").list()
            .layer(DenseLayer(n_in=6, n_out=8, activation="relu"))
            .layer(OutputLayer(n_in=8, n_out=3, activation="softmax",
                               loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _lstm_net(seed=7):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-2))
            .weight_init("xavier").list()
            .layer(LSTM(n_out=16, activation="tanh"))
            .layer(RnnOutputLayer(n_out=V, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(InputType.recurrent(V))
            .build())
    return MultiLayerNetwork(conf).init()


def _single_exec():
    return Executor(ex.build_mesh(jax.devices()[:1]))


def _batch(b, f=6, c=3, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.randn(b, f).astype(np.float32)
    y = np.eye(c, dtype=np.float32)[rs.randint(0, c, b)]
    return x, y


# ---------------------------------------------------------------- mesh
class TestMesh:
    @pytest.mark.mesh8
    def test_default_mesh_is_pure_dp_over_all_devices(self):
        mesh = ex.default_mesh()
        assert mesh.shape[ex.DATA_AXIS] == len(jax.devices())
        assert mesh.shape[ex.MODEL_AXIS] == 1

    @pytest.mark.mesh8
    def test_env_spec_parses(self):
        assert _mesh_from_env("off").size == 1
        m = _mesh_from_env("data=4,model=2")
        assert m.shape[ex.DATA_AXIS] == 4 and m.shape[ex.MODEL_AXIS] == 2
        m = _mesh_from_env("model=2")   # data absorbs the rest
        assert m.shape[ex.MODEL_AXIS] == 2
        assert m.size == len(jax.devices())
        with pytest.raises(ValueError):
            _mesh_from_env("data=999")

    def test_model_parallel_must_divide(self):
        with pytest.raises(ValueError):
            ex.build_mesh(jax.devices()[:1], model_parallel=3)

    def test_host_device_env_composes_flag(self):
        env = ex.host_device_env(4, base={"XLA_FLAGS":
                                          "--foo "
                                          "--xla_force_host_platform_device_count=2"})
        assert "--xla_force_host_platform_device_count=4" in env["XLA_FLAGS"]
        assert "device_count=2" not in env["XLA_FLAGS"]
        assert "--foo" in env["XLA_FLAGS"]
        assert env["JAX_PLATFORMS"] == "cpu"

    def test_mesh8_fixture_is_a_subprocess_env(self, mesh8):
        assert "--xla_force_host_platform_device_count=8" in mesh8["XLA_FLAGS"]

    def test_mesh_gauges_published(self):
        from deeplearning4j_tpu.monitor.metrics import get_registry
        ex.default_mesh()
        text = get_registry().render()
        assert "dl4jtpu_mesh_devices" in text
        assert 'dl4jtpu_mesh_axis_size{axis="data"}' in text


# -------------------------------------------------------------- policy
class TestShardingPolicy:
    @pytest.mark.mesh8
    def test_min_rows_threshold(self):
        e = Executor(ex.build_mesh())          # 8 devices, pure DP
        assert e.shardable_rows(128)           # 16 rows/shard
        assert e.shardable_rows(8 * 16)
        assert not e.shardable_rows(64)        # 8/shard < 16
        assert not e.shardable_rows(127)       # not divisible
        assert e.shardable_rows(8, min_rows=1)

    def test_single_device_never_shards(self):
        e = _single_exec()
        assert not e.shardable_rows(1 << 20)

    def test_param_spec_megatron_rules(self):
        w_col = jnp.zeros((8, 32))     # generic kernel: shard output dim
        assert param_spec("['Wq']", w_col, 2) == P(None, "model")
        w_row = jnp.zeros((32, 8))     # wide->narrow: row-parallel
        assert param_spec("['ff2']['W']", w_row, 2) == P("model", None)
        assert param_spec("['dense']['W']", w_row, 2) == P("model", None)
        bias = jnp.zeros((32,))
        assert param_spec("['b']", bias, 2) == P()
        odd = jnp.zeros((3, 5))        # nothing divides: replicate
        assert param_spec("['W']", odd, 2) == P()
        assert param_spec("['Wq']", w_col, 1) == P()

    @pytest.mark.mesh8
    def test_opt_state_co_shards_with_params(self):
        e = Executor(ex.build_mesh(model_parallel=2))
        params = {"dense": {"W": jnp.zeros((32, 8)), "b": jnp.zeros((8,))}}
        opt = {"m": {"W": jnp.zeros((32, 8)), "b": jnp.zeros((8,))}}
        sh = e._state_shardings(opt, params)
        assert sh["m"]["W"].spec == P("model", None)
        assert sh["m"]["b"].spec == P()


# ----------------------------------------------------- single-device path
class TestSingleDevicePath:
    def test_jit_is_plain_jax_jit(self):
        e = _single_exec()
        f = e.jit(lambda x: x + 1, in_specs=(ex.BATCH,),
                  out_specs=(ex.BATCH,))
        assert not hasattr(f, "_dl4jtpu_exec_wrapper")
        assert hasattr(f, "lower")     # a real jax.jit object

    def test_train_step_compiles_once_per_shape(self):
        net = _mln()
        net._exec = _single_exec()
        x, y = _batch(32)
        net.fit(DataSet(x, y))
        net.fit(DataSet(x, y))
        assert net._compile_count == 1
        step = net._train_step_cache[(False, False)]
        assert not hasattr(step, "_dl4jtpu_exec_wrapper")

    @pytest.mark.mesh8
    def test_small_batches_stay_on_replicated_program(self):
        net = _mln()
        assert net._executor.mesh.size == len(jax.devices())
        x, y = _batch(32)              # 4 rows/shard < 16: replicated
        net.fit(DataSet(x, y))
        net.fit(DataSet(x, y))
        assert net._compile_count == 1
        step = net._train_step_cache[(False, False)]
        assert step._dl4jtpu_exec_wrapper
        assert set(step._exec_cache) == {False}

    @pytest.mark.mesh8
    def test_large_batch_adds_exactly_one_sharded_program(self):
        net = _mln()
        xs, ys = _batch(32)
        net.fit(DataSet(xs, ys))
        xl, yl = _batch(128)
        net.fit(DataSet(xl, yl))
        net.fit(DataSet(xl, yl))
        step = net._train_step_cache[(False, False)]
        assert set(step._exec_cache) == {False, True}
        assert net._compile_count == 2


# ------------------------------------------------------- sharded parity
@pytest.mark.mesh8
class TestShardedParity:
    """d=8 (conftest's forced host devices) vs d=1, pinned tolerance:
    f32 reductions reorder across shard boundaries, so 'parity' is a
    numeric pin, not bitwise equality."""

    FIT_RTOL, FIT_ATOL = 1e-4, 1e-6
    FWD_RTOL, FWD_ATOL = 1e-5, 1e-6

    def test_fit_matches_single_device(self):
        b = 128                        # 16 rows/shard: sharded path
        net1, net8 = _mln(), _mln()
        net1._exec = _single_exec()
        for i in range(3):
            x, y = _batch(b, seed=i)
            net1.fit(DataSet(x, y))
            net8.fit(DataSet(x, y))
        step = net8._train_step_cache[(False, False)]
        assert set(step._exec_cache) == {True}
        for p1, p8 in zip(net1.params, net8.params):
            for k in p1:
                np.testing.assert_allclose(
                    np.asarray(p1[k]), np.asarray(p8[k]),
                    rtol=self.FIT_RTOL, atol=self.FIT_ATOL, err_msg=k)
        np.testing.assert_allclose(net1.get_score(), net8.get_score(),
                                   rtol=self.FIT_RTOL, atol=self.FIT_ATOL)

    def test_fit_scan_matches_single_device(self):
        k, b = 3, 128
        rs = np.random.RandomState(0)
        xs = rs.randn(k, b, 6).astype(np.float32)
        ys = np.eye(3, dtype=np.float32)[rs.randint(0, 3, (k, b))]
        net1, net8 = _mln(), _mln()
        net1._exec = _single_exec()
        net1.fit_scan(xs, ys)
        net8.fit_scan(xs, ys)
        for p1, p8 in zip(net1.params, net8.params):
            for key in p1:
                np.testing.assert_allclose(
                    np.asarray(p1[key]), np.asarray(p8[key]),
                    rtol=self.FIT_RTOL, atol=self.FIT_ATOL, err_msg=key)

    def test_predict_matches_single_device(self):
        net1, net8 = _mln(), _mln()
        net1._exec = _single_exec()
        x, _ = _batch(128)
        y1 = np.asarray(net1.output(x))            # bucketed serving path
        y8 = np.asarray(net8.output(x))
        np.testing.assert_allclose(y1, y8, rtol=self.FWD_RTOL,
                                   atol=self.FWD_ATOL)
        # the sharded engine really took the sharded program
        eng = net8.serving_engine()
        assert set(eng._fwd._exec_cache) == {True}

    def test_decode_matches_single_device(self):
        from deeplearning4j_tpu.serving import DecodeEngine
        prompt = [3, 1, 4, 1, 5]
        outs = []
        for make_exec in (_single_exec, None):
            net = _lstm_net()
            if make_exec is not None:
                net._exec = make_exec()
            eng = DecodeEngine(net, slots=16, max_len=32).start()
            try:
                r = eng.generate(prompt, max_new_tokens=8, temperature=0.0)
            finally:
                eng.stop()
            outs.append(list(r["tokens"]))
        assert outs[0] == outs[1]


# -------------------------------------------------------------- routing
class TestRouting:
    def test_measured_table_hits(self):
        assert routing.lstm_fwd_route(16, 128, t=64,
                                      dtype="float32") == "scan"
        assert routing.lstm_fwd_route(16, 128, t=64,
                                      dtype="bfloat16") == "pallas"
        assert routing.lstm_fwd_route(32, 256, t=128,
                                      dtype="float32") == "scan"
        assert routing.lstm_fwd_route(32, 256, t=64,
                                      dtype="float32") == "pallas"

    def test_heuristic_between_measured_shapes(self):
        assert routing.lstm_fwd_route(4, 16) == "scan"       # latency-bound
        assert routing.lstm_fwd_route(256, 256) == "pallas"  # bandwidth-bound
        # f32 long-T falls back to scan even above the B*H crossover
        assert routing.lstm_fwd_route(64, 64, t=256,
                                      dtype="float32") == "scan"

    def test_non_tpu_backend_scans(self):
        assert routing.lstm_fwd_route(256, 256, backend="cpu") == "scan"

    def test_set_route_pin_wins(self):
        routing.set_route("fused_lstm", "scan")
        try:
            assert routing.lstm_fwd_route(256, 256) == "scan"
        finally:
            routing.set_route("fused_lstm", None)
        with pytest.raises(ValueError):
            routing.set_route("fused_lstm", "nope")

    def test_env_pin(self, monkeypatch):
        monkeypatch.setenv("DL4JTPU_LSTM_FWD_ROUTE", "pallas")
        assert routing.lstm_fwd_route(1, 1) == "pallas"

    def test_load_measurements_merges_bench_rows(self):
        n = routing.load_measurements([
            {"kernel": "fused_lstm", "B": 2, "T": 2, "H": 2,
             "dtype": "float32", "fwd_speedup": 1.5},
            {"kernel": "other", "B": 2, "T": 2, "H": 2,
             "dtype": "float32", "fwd_speedup": 9.0},
            {"kernel": "fused_lstm", "B": 2, "T": 2, "H": 2,
             "dtype": "float32"},
        ])
        assert n == 1
        try:
            assert routing.lstm_fwd_route(2, 2, t=2,
                                          dtype="float32") == "pallas"
        finally:
            routing._MEASURED.pop(("fused_lstm", 2, 2, 2, "float32"))


class TestMeasurementFileRouting:
    """Regression over the SHIPPED KERNELS_TPU.json: every measured
    fused-LSTM row — bf16 exactly like f32 — routes pallas iff its
    measured forward speedup beat XLA. Guards the bf16 small-shape
    losses (0.03x-0.4x) that the pre-measurement heuristic got wrong."""

    def _rows(self):
        import json
        import os
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "KERNELS_TPU.json")) as f:
            return [r for r in json.load(f)["results"]
                    if r.get("kernel") == "fused_lstm"
                    and r.get("fwd_speedup") is not None]

    def test_every_measured_row_routes_by_its_speedup(self):
        rows = self._rows()
        assert len(rows) >= 10            # the file really shipped data
        n = routing.load_measurements_file()
        assert n >= len(rows)
        for r in rows:
            want = "pallas" if r["fwd_speedup"] > 1 else "scan"
            got = routing.lstm_fwd_route(r["B"], r["H"], t=r["T"],
                                         dtype=r["dtype"])
            assert got == want, (r, got)

    def test_bf16_small_shapes_route_scan(self):
        routing.load_measurements_file()
        # the three bf16 rows that LOSE hardest (0.03x, 0.1x, 0.31x)
        assert routing.lstm_fwd_route(1, 8, t=4, dtype="bfloat16") == "scan"
        assert routing.lstm_fwd_route(4, 8, t=16, dtype="bfloat16") == "scan"
        assert routing.lstm_fwd_route(8, 24, t=16, dtype="bfloat16") == "scan"
        # and the bf16 rows that WIN route pallas
        assert routing.lstm_fwd_route(16, 128, t=64,
                                      dtype="bfloat16") == "pallas"
        assert routing.lstm_fwd_route(32, 256, t=128,
                                      dtype="bfloat16") == "pallas"

    def test_file_load_is_idempotent(self):
        a = routing.load_measurements_file()
        b = routing.load_measurements_file()
        assert a == b >= 1
