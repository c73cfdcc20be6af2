"""Training-perf seams (ISSUE 11): fused optimizer update, train-precision
policy, flash-attention training route, grad-phase measurement routing,
and the autotune persist→consult roundtrip.

The load-bearing claims pinned here:
- the fused grad→update→apply program (nn/fused_update.py) is BITWISE
  equal to the per-leaf optax chain it replaces — for SGD/Nesterov/Adam,
  with elementwise clipping and iteration-indexed LR schedules, for both
  params and opt state, over multiple steps;
- ``apply_external_updates`` compiles exactly ONE program per (model,
  updater), registers it in the /programs registry, and donates params +
  opt state (old buffers die, new outputs reuse them);
- the bf16 train-precision policy keeps stored params f32, pins the loss
  trajectory within tolerance of f32, composes with remat='selective',
  and leaves inference untouched;
- the attention layer seam routes the TRAINING forward through the same
  decision as inference (train=True asks for both phases) and the flash
  kernel's gradients match the dense path at pinned tolerance;
- every KERNELS_TPU.json row with grad data routes the backward by its
  measurement (the fwd-only version of this regression lives in
  tests/test_exec.py); the scan backward is numerically equal to the
  Pallas backward it stands in for;
- a persisted autotune table is consulted for at least one fwd and one
  grad route after a cache reset;
- tensor-parallel callers bypass the fused path (raveling row- and
  column-sharded leaves would gather every shard).
"""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration, ops
from deeplearning4j_tpu import exec as ex
from deeplearning4j_tpu.exec import routing
from deeplearning4j_tpu.nn import fused_update as fu
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.updaters import Adam, Nesterovs, Schedule, Sgd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _leaves(tree):
    return jax.tree_util.tree_leaves(tree)


def _assert_bitwise(a, b, what=""):
    la, lb = _leaves(a), _leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype, what
        assert (np.asarray(x) == np.asarray(y)).all(), what


# --------------------------------------------------- standalone fused update

class TestFusedUpdateParity:
    """build_fused_update vs the per-member optax loop, bitwise."""

    def _params(self):
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        return {
            "l0": {"W": jax.random.normal(ks[0], (6, 8)),
                   "b": jnp.zeros((8,))},
            "l1": {"W": jax.random.normal(ks[1], (8, 8)),
                   "b": jax.random.normal(ks[2], (8,))},
            "l2": {"W": jax.random.normal(ks[3], (8, 3)),
                   "b": jax.random.normal(ks[4], (3,))},
        }

    def _grads(self, params, step):
        return jax.tree_util.tree_map(
            lambda p: jnp.cos(p * (step + 1)) * 0.7, params)

    @pytest.mark.parametrize("make_tx", [
        lambda: optax.sgd(0.05),
        lambda: Nesterovs(0.05).to_optax(),
        lambda: Adam(1e-2, schedule=Schedule(
            kind="exponential", initial=1e-2, decay_rate=0.95)).to_optax(),
        # elementwise clipping composes into the flat program
        lambda: optax.chain(optax.clip(0.5),
                            optax.add_decayed_weights(1e-4),
                            Adam(1e-2).to_optax()),
    ], ids=["sgd", "nesterov", "adam-exp-schedule", "clip-wd-adam"])
    def test_bitwise_over_steps(self, make_tx):
        params = self._params()
        transforms = {k: make_tx() for k in params}
        group_keys = {k: "same" for k in params}
        fused = fu.build_fused_update(params, transforms, group_keys)
        assert fused.fused_keys, "expected the group to actually fuse"

        ref_p = dict(params)
        ref_o = {k: transforms[k].init(ref_p[k]) for k in params}
        fus_p = dict(params)
        fus_o = {k: transforms[k].init(fus_p[k]) for k in params}
        for step in range(3):
            grads = self._grads(ref_p, step)
            for k in params:
                u, o = transforms[k].update(grads[k], ref_o[k], ref_p[k])
                ref_p[k] = optax.apply_updates(ref_p[k], u)
                ref_o[k] = o
            fus_p, fus_o = fused.apply(fus_p, fus_o, grads)
            _assert_bitwise(fus_p, ref_p, f"params step {step}")
            _assert_bitwise(fus_o, ref_o, f"opt state step {step}")

    def test_global_norm_clip_falls_back(self):
        # clip_by_global_norm reduces ACROSS leaves — concatenating members
        # would change its norm, so such groups must not fuse
        params = self._params()
        transforms = {k: optax.chain(optax.clip_by_global_norm(1.0),
                                     optax.sgd(0.1)) for k in params}
        fused = fu.build_fused_update(params, transforms,
                                      {k: None for k in params})
        assert not fused.fused_keys
        grads = self._grads(params, 0)
        ref = {k: optax.apply_updates(
            params[k], transforms[k].update(
                grads[k], transforms[k].init(params[k]), params[k])[0])
            for k in params}
        got, _ = fused.apply(params,
                             {k: transforms[k].init(params[k])
                              for k in params}, grads)
        _assert_bitwise(got, ref)


def _mlp(updater, n_in=6, hidden=8, n_out=3, seed=42, **conf_kw):
    b = (NeuralNetConfiguration.builder().seed(seed).updater(updater)
         .weight_init("xavier"))
    for k, v in conf_kw.items():
        b = getattr(b, k)(v)
    conf = (b.list()
            .layer(DenseLayer(n_in=n_in, n_out=hidden, activation="relu"))
            .layer(OutputLayer(n_in=hidden, n_out=n_out,
                               activation="softmax", loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _xy(n=16, n_in=6, n_out=3, seed=0):
    rs = np.random.RandomState(seed)
    x = jnp.asarray(rs.randn(n, n_in).astype(np.float32))
    y = jnp.asarray(np.eye(n_out, dtype=np.float32)[
        rs.randint(0, n_out, size=n)])
    return x, y


class TestFusedUpdateInContainers:
    def test_model_fit_bitwise_vs_per_leaf(self):
        x, y = _xy()
        nets = []
        try:
            for flag in (True, False):
                fu.set_fused_update(flag)
                net = _mlp(Adam(1e-2, schedule=Schedule(
                    kind="exponential", initial=1e-2, decay_rate=0.9)))
                for _ in range(3):
                    net.fit(np.asarray(x), np.asarray(y))
                nets.append(net)
        finally:
            fu.set_fused_update(None)
        _assert_bitwise(nets[0].params, nets[1].params, "params")
        _assert_bitwise(nets[0].opt_state, nets[1].opt_state, "opt state")

    def test_external_updates_compile_once_and_register(self):
        net = _mlp(Sgd(0.1))
        grads = [jax.tree_util.tree_map(jnp.ones_like, p)
                 for p in net.params]
        c0 = net._compile_count
        net.apply_external_updates(grads)
        assert net._compile_count == c0 + 1
        ent = ex.get_programs().get(net._prog_caller, "apply_updates")
        assert ent is not None
        # second step with fresh grads: same program, no new compile
        grads2 = [jax.tree_util.tree_map(lambda g: g * 0.5, p)
                  for p in net.params]
        net.apply_external_updates(grads2)
        assert net._compile_count == c0 + 1

    def test_external_updates_donate_buffers(self):
        net = _mlp(Sgd(0.1))
        grads = [jax.tree_util.tree_map(jnp.zeros_like, p)
                 for p in net.params]
        net.apply_external_updates(grads)      # compile with donation
        old_params, old_opt = net.params, net.opt_state
        # device-commit so the inputs are real device buffers
        jax.block_until_ready(old_params)
        net.apply_external_updates(grads)
        donated = [l for l in _leaves((old_params, old_opt))
                   if hasattr(l, "is_deleted") and l.is_deleted()]
        assert donated, "donated inputs should be consumed (buffers dead)"

    def test_tensor_parallel_gate_uses_per_leaf_path(self):
        # TP callers pass fused=False / model_size>1 executors skip the
        # fused path: raveling row- and column-sharded leaves would gather
        # every shard. The per-leaf result must still be identical.
        net = _mlp(Adam(1e-2))
        grads = [jax.tree_util.tree_map(jnp.ones_like, p)
                 for p in net.params]
        p_fused, o_fused = net._dp_apply_updates(net.params, net.opt_state,
                                                 grads)
        calls = []
        orig_apply = net._fused.apply
        net._fused.apply = lambda *a: (calls.append(1), orig_apply(*a))[1]
        try:
            net._exec = SimpleNamespace(model_size=2)
            p_leaf, o_leaf = net._dp_apply_updates(net.params, net.opt_state,
                                                   grads)
        finally:
            net._exec = None
            net._fused.apply = orig_apply
        assert not calls, "model_size>1 must not take the fused path"
        _assert_bitwise(p_fused, p_leaf)
        _assert_bitwise(o_fused, o_leaf)


# ------------------------------------------------------ train precision bf16

class TestTrainPrecisionPolicy:
    def _fit(self, train_precision, remat=False, steps=3):
        old = ex.get_executor()
        try:
            ex.set_executor(ex.Executor(train_precision=train_precision))
            kw = {"remat": "selective"} if remat else {}
            net = _mlp(Adam(1e-2), **kw)
            x, y = _xy()
            for _ in range(steps):
                net.fit(np.asarray(x), np.asarray(y))
            out = net.output(np.asarray(x))
            return net, float(net.get_score()), np.asarray(out)
        finally:
            ex.set_executor(old)

    def test_params_stay_f32_and_loss_pinned(self):
        net32, s32, out32 = self._fit("f32")
        net16, s16, out16 = self._fit("bf16")
        for leaf in _leaves(net16.params):
            assert leaf.dtype == jnp.float32
        # pinned trajectory tolerance: measured delta ~4e-4 after 3 steps
        assert abs(s32 - s16) <= 2e-2
        # inference is NOT under the policy: both outputs are f32 and close
        assert out16.dtype == np.float32
        np.testing.assert_allclose(out16, out32, atol=5e-2)

    def test_composes_with_selective_remat(self):
        _, s_plain, _ = self._fit("bf16")
        _, s_remat, _ = self._fit("bf16", remat=True)
        # remat replays the SAME bf16 forward — identical math, same score
        assert s_plain == pytest.approx(s_remat, abs=1e-6)

    def test_env_var_resolution(self, monkeypatch):
        monkeypatch.setenv("DL4JTPU_TRAIN_PRECISION", "bf16")
        assert ex.Executor().train_precision == "bf16"
        monkeypatch.setenv("DL4JTPU_TRAIN_PRECISION", "f32")
        assert ex.Executor().train_dtype is None
        with pytest.raises(ValueError):
            ex.Executor(train_precision="fp16")


# ------------------------------------------- flash-attention training route

class TestFlashTrainingRoute:
    def _qkv(self, B=2, T=16, H=2, Dh=8):
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        shape = (B, T, H, Dh)
        return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)

    def test_training_forward_asks_with_train_true(self, monkeypatch):
        from deeplearning4j_tpu.nn.layers.attention import (
            scaled_dot_product_attention)
        seen = []

        def spy(bh, t, dh, causal, train=False, backend=None, min_t=4096):
            seen.append({"train": train, "backend": backend, "min_t": min_t})
            return "pallas"
        monkeypatch.setattr(routing, "flash_attn_route", spy)
        q, k, v = self._qkv()
        try:
            ops.set_helpers_enabled(True, interpret=True)
            scaled_dot_product_attention(q, k, v, causal=True, train=True)
            scaled_dot_product_attention(q, k, v, causal=True, train=False)
        finally:
            ops.set_helpers_enabled(None)
        assert [s["train"] for s in seen] == [True, False]
        # interpret mode: deterministic gate (min_t=0), no backend screen —
        # the SAME decision for the training and inference forward
        assert all(s["min_t"] == 0 and s["backend"] is None for s in seen)

    def test_flash_vs_dense_gradient_parity(self):
        from deeplearning4j_tpu.nn.layers.attention import (
            scaled_dot_product_attention)
        q, k, v = self._qkv()

        def loss(q, k, v, causal):
            o = scaled_dot_product_attention(q, k, v, causal=causal,
                                             train=True)
            return (o * jnp.cos(o)).sum()

        for causal in (False, True):
            try:
                ops.set_helpers_enabled(True, interpret=True)
                routing.set_route("flash_attn", "pallas")
                f_val, f_grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(
                    q, k, v, causal)
                routing.set_route("flash_attn", "scan")
                d_val, d_grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(
                    q, k, v, causal)
            finally:
                routing.set_route("flash_attn", None)
                ops.set_helpers_enabled(None)
            # pinned seam tolerance: the kernel accumulates the softmax
            # streaming-style, so parity is a tolerance, not bitwise
            assert abs(float(f_val) - float(d_val)) <= 1e-4
            for fg, dg in zip(f_grads, d_grads):
                np.testing.assert_allclose(np.asarray(fg), np.asarray(dg),
                                           atol=2e-4, rtol=1e-3)


# ----------------------------------------------------- grad-phase routing

@pytest.fixture
def clean_routing():
    m = dict(routing._MEASURED)
    mg = dict(routing._MEASURED_GRAD)
    fm = dict(routing._FLASH_MEASURED)
    loaded = routing._file_loaded
    yield
    routing._MEASURED.clear(), routing._MEASURED.update(m)
    routing._MEASURED_GRAD.clear(), routing._MEASURED_GRAD.update(mg)
    routing._FLASH_MEASURED.clear(), routing._FLASH_MEASURED.update(fm)
    routing._file_loaded = loaded


class TestGradRouteRegression:
    """Every shipped row with grad data routes the backward by it —
    the grad-phase twin of tests/test_exec.py TestMeasurementFileRouting."""

    def _rows(self, kernel):
        with open(os.path.join(ROOT, "KERNELS_TPU.json")) as f:
            return [r for r in json.load(f)["results"]
                    if r.get("kernel") == kernel
                    and (r.get("grad_route") in ("pallas", "scan")
                         or r.get("grad_speedup") is not None)]

    def test_every_lstm_grad_row_routes_by_measurement(self, clean_routing):
        rows = self._rows("fused_lstm")
        assert len(rows) >= 10             # the file really ships grad data
        routing.load_measurements_file()
        for r in rows:
            want = r.get("grad_route") or (
                "pallas" if r["grad_speedup"] > 1 else "scan")
            got = routing.lstm_grad_route(r["B"], r["H"], t=r["T"],
                                          dtype=r["dtype"])
            assert got == want, (r, got)

    def test_every_flash_grad_row_gates_training_route(self, clean_routing):
        rows = self._rows("flash_attention")
        assert len(rows) >= 5
        routing.load_measurements_file()
        for r in rows:
            key = (r["BH"], r["T"], r["Dh"], bool(r.get("causal")))
            grad = r.get("grad_route") or (
                "pallas" if r["grad_speedup"] > 1 else "scan")
            got = routing.flash_attn_route(*key, train=True, backend="tpu")
            if grad == "scan":
                # a losing backward keeps the TRAINING shape dense even
                # when the forward wins
                assert got == "scan", (r, got)
            else:
                fwd = routing._FLASH_MEASURED.get(("fwd",) + key)
                if fwd == "pallas":
                    assert got == "pallas", (r, got)

    def test_scan_bwd_matches_pallas_bwd(self):
        # the scan backward is the routed stand-in for the Pallas backward:
        # same residual contract, numerically equal gradients
        from deeplearning4j_tpu.ops import lstm_pallas as lp
        b, t, h = 2, 8, 16
        ks = jax.random.split(jax.random.PRNGKey(1), 6)
        gate_in = jax.random.normal(ks[0], (t, b, 4 * h))
        rw = jax.random.normal(ks[1], (h, 4 * h)) * 0.1
        h0 = jax.random.normal(ks[2], (b, h))
        c0 = jax.random.normal(ks[3], (b, h))
        hs, tc, cprev, gates, _ = lp._scan_fwd(gate_in, rw, h0, c0,
                                               save_reserve=True)
        dhs = jax.random.normal(ks[4], (t, b, h))
        dcT = jax.random.normal(ks[5], (b, h))
        out_p = lp._bwd_call(gates, tc, cprev, rw, dhs, dcT, interpret=True)
        out_s = lp._scan_bwd(gates, tc, cprev, rw, dhs, dcT)
        for a, b_ in zip(out_p, out_s):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=1e-5, rtol=1e-5)


class TestAutotuneRoundtrip:
    def test_persisted_table_consulted_for_fwd_and_grad(
            self, tmp_path, monkeypatch, clean_routing):
        from deeplearning4j_tpu.exec import autotune
        # shapes chosen to exist in NO shipped table, with the fwd winning
        # and the grad losing — so each phase's answer can only come from
        # the persisted autotune rows
        row = {"kernel": "fused_lstm", "B": 3, "T": 5, "H": 7,
               "dtype": "float32", "fwd_speedup": 1.5, "grad_speedup": 0.5,
               "backend": "cpu", "autotuned": True}
        flash = {"kernel": "flash_attention", "BH": 3, "T": 40, "Dh": 24,
                 "causal": False, "fwd_speedup": 2.0, "grad_speedup": 0.5,
                 "backend": "cpu", "autotuned": True}
        path = autotune.save_rows([row, flash],
                                  str(tmp_path / "autotune_cpu.json"))
        # a table reaches routing only through the caller that names it
        routing._reset_measurement_cache()
        assert routing.lstm_fwd_route(3, 7, t=5, dtype="float32") == "scan"
        assert routing.load_measurements_file(path) == 2
        # heuristic alone would say scan (B*H tiny) — pallas proves the
        # persisted fwd row was consulted
        assert routing.lstm_fwd_route(3, 7, t=5, dtype="float32") == "pallas"
        # grad default is pallas — scan proves the grad row was consulted
        assert routing.lstm_grad_route(3, 7, t=5, dtype="float32") == "scan"
        # training flash route: measured losing grad keeps the shape dense
        assert routing.flash_attn_route(3, 40, 24, False, train=True,
                                        backend="tpu") == "scan"
        assert routing.flash_attn_route(3, 40, 24, False, train=False,
                                        backend="tpu") == "pallas"

    def test_save_rows_merges_by_shape(self, tmp_path, monkeypatch):
        from deeplearning4j_tpu.exec import autotune
        path = str(tmp_path / "table.json")
        r1 = {"kernel": "fused_lstm", "B": 1, "T": 2, "H": 3,
              "dtype": "float32", "fwd_speedup": 0.5}
        autotune.save_rows([r1], path)
        r2 = dict(r1, fwd_speedup=2.0)
        autotune.save_rows([r2, {"kernel": "fused_lstm", "B": 9, "T": 9,
                                 "H": 9, "dtype": "float32",
                                 "fwd_speedup": 1.1}], path)
        rows = autotune.load_table(path)
        assert len(rows) == 2
        mine = [r for r in rows if r["B"] == 1]
        assert mine[0]["fwd_speedup"] == 2.0
