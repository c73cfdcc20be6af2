"""The fused passes over the residual state of hyper-connections
(ops/hyper_connection.py) against XLA's form of the same layers, on the CPU
with the kernels interpreted: each pass's output and every cotangent (the
state, the sublayer's output, the maps, phi, the scales, the bias); the
rule that routes a pass to them or leaves it to XLA; and the count of the
forms a decoder's step takes."""

import contextlib
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu import ops
from deeplearning4j_tpu.exec import build_mesh, executor, set_default_mesh
from deeplearning4j_tpu.nn.conf.graph_conf import HyperIn, HyperOut
from deeplearning4j_tpu.nn.layers import HyperConnection
from deeplearning4j_tpu.ops import hyper_connection as kernel
from perfbench.lib import arch, spec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N = 4                       # streams
WIDTH = 2 * N + N * N       # the maps of a token


@contextlib.contextmanager
def _helpers(on):
    prev = ops.set_helpers_enabled(on, interpret=on)
    try:
        yield
    finally:
        ops.set_helpers_enabled(prev[0], interpret=prev[1])


def _rand(shape, seed, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       dtype)


def _maps(b, t, seed):
    """Maps as ``HyperConnection`` leaves them: H_pre in (0, 1), H_post in
    (0, 2), H_res positive, rows near 1."""
    u = np.random.RandomState(seed).rand(b, t, WIDTH)
    u[..., N:2 * N] *= 2
    u[..., 2 * N:] /= N / 2
    return jnp.asarray(u, jnp.float32)


def _pass(which, b, t, c, dtype):
    """(function of its differentiable arguments, the arguments, their
    names) of one pass at (B, T, C)."""
    x = _rand((b, t, N * c), 1, dtype)
    if which == "maps":
        layer = HyperConnection(n_in=N * c, streams=N)
        p = layer.init(jax.random.PRNGKey(0))
        # the scales and bias away from their start, so that every map
        # depends on them
        p = {"phi": p["phi"].astype(dtype), "alpha": jnp.asarray(
            [0.7, -0.4, 0.3], dtype), "bias": _rand((WIDTH,), 2, dtype, 0.1)}
        return (lambda p, x: layer.apply(p, x)[0]), (p, x), ("params", "x")
    maps = _maps(b, t, 3)
    if which == "mix_in":
        return (lambda m, x: HyperIn(streams=N).apply([m, x])), (maps, x), \
            ("maps", "x")
    f = _rand((b, t, c), 4, dtype)
    return (lambda f, m, x: HyperOut(streams=N).apply([f, m, x])), \
        (f, maps, x), ("f", "maps", "x")


def _value_and_grads(fn, args, cot):
    def loss(*a):
        return jnp.sum(fn(*a).astype(jnp.float32) * cot)
    return jax.value_and_grad(loss, tuple(range(len(args))))(*args)


# (B, T, C): every token in one block; 600 tokens in blocks of 256 or 512
# rows, the last one partial
SHAPES = {"one_block": (2, 24, 128), "partial_block": (1, 600, 256)}
# the largest gap over the largest value, by leaf: float32 on both sides;
# bfloat16 state, where the XLA form rounds the normalised state before phi
# and the kernel does not
TOL = {"float32": 1e-5, "bfloat16": 3e-2}


@pytest.mark.parametrize("dtype", sorted(TOL))
@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("which", ["maps", "mix_in", "mix_out"])
def test_a_pass_and_every_cotangent_against_the_xla_form(which, shape,
                                                         dtype):
    b, t, c = SHAPES[shape]
    fn, args, names = _pass(which, b, t, c, jnp.dtype(dtype))
    with jax.default_matmul_precision("highest"):
        with _helpers(False):
            out = fn(*args)
            want = _value_and_grads(fn, args, _rand(out.shape, 5))
        calls = {}
        with _helpers(True), kernel.counting_calls(calls):
            got_out = fn(*args)
            got = _value_and_grads(fn, args, _rand(out.shape, 5))
            text = str(jax.make_jaxpr(fn)(*args))
    assert "pallas_call" in text and calls == {"kernel": 3}
    # the state's dtype stays; the maps are float32
    assert got_out.dtype == out.dtype == (
        jnp.float32 if which == "maps" else jnp.dtype(dtype))
    tol = TOL[dtype]
    pairs = [("out", got_out, out), ("loss", got[0], want[0])] + [
        (f"d{names[i]}/{jax.tree_util.keystr(k)}", u, v)
        for i, (g, w) in enumerate(zip(got[1], want[1]))
        for (k, u), v in zip(jax.tree_util.tree_leaves_with_path(g),
                             jax.tree_util.tree_leaves(w))]
    for name, u, v in pairs:
        assert u.dtype == v.dtype, name
        u, v = np.asarray(u, np.float32), np.asarray(v, np.float32)
        scale = max(float(np.abs(v).max()), 1e-12)
        assert float(np.abs(u - v).max()) / scale < tol, name


# how each case sets the rule: helpers, a partitioned trace, the stream width
ROUTES = {
    "routed": (True, False, 128, "kernel"),
    "helpers_off": (False, False, 128, "xla"),
    "partitioned_trace": (True, True, 128, "xla"),
    "stream_not_lane_aligned": (True, False, 96, "xla"),
}


@pytest.mark.parametrize("case", sorted(ROUTES))
def test_the_rule_routes_each_pass(case, monkeypatch):
    helpers, partitioned, c, form = ROUTES[case]
    if partitioned:
        monkeypatch.setattr(executor._PARTITIONED, "on", True, raising=False)
    calls = {}
    with _helpers(helpers), kernel.counting_calls(calls):
        texts = [str(jax.make_jaxpr(fn)(*args)) for fn, args, _ in (
            _pass(w, 1, 16, c, jnp.float32)
            for w in ("maps", "mix_in", "mix_out"))]
    assert calls == {form: 3}
    assert all(("pallas_call" in s) == (form == "kernel") for s in texts)
    assert kernel.supported(c, N) == (c % 128 == 0)


# the passes a decoder's step traces with every kernel on: the latent stack's
# 12 hyper-connections (5 layers and the module, 2 sublayers each) x the
# maps, the mix-in and the mix-out, all on the kernels at a stream width
# they take; none in the other families
STEP_PASSES = {"xing4.0-29b-a4b.fit-s4k-b1": 36,
               "laguna-s-2.1.fit-s8k-b2": 0,
               "keye-vl-2.0-30b-a3b.fit-s16k-b1": 0,
               "nemotron-3-super-120b-a12b.fit-s8k-b1": 0,
               "deepseek-v2-lite.fit-s32k-b1": 0}


@pytest.mark.parametrize("cell", sorted(STEP_PASSES))
def test_the_forms_a_decoder_step_takes(cell):
    _, conf, traffic, _ = spec.cell(spec.load_benchmark(), cell)
    c = arch.load_config(os.path.join(ROOT, conf["file"]), rehearse=True)
    if "hc_mult" in c:
        r = c["rehearsal"]
        c["rehearsal"] = dict(r, model=dict(r["model"], hidden_size=128))
    set_default_mesh(build_mesh(jax.devices()[:1]))     # one chip's step
    try:
        with _helpers(True):
            net = spec.load_module("jobs", traffic["job"]).build_net(c)
            ids = jnp.zeros((traffic["rehearsal_batch"],
                             traffic["rehearsal_seq"]), jnp.int32)
            net._make_train_step().lower(
                net.params, net.state, net.opt_state, [ids], [ids],
                jnp.asarray(0, jnp.int32), None, None)
    finally:
        set_default_mesh(None)
    assert net._kernel_calls["hyper_connection_calls"] == {
        "kernel": STEP_PASSES[cell], "xla": 0}
