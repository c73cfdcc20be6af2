"""The dropless expert layer (nn/layers/decoder.py:ExpertLayer) and its
experts over a mesh axis (parallel/expert_parallel.py). Oracle: every token
through each of its chosen experts, one at a time."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

from deeplearning4j_tpu.nn.layers import ExpertLayer
from deeplearning4j_tpu.nn.layers.decoder import route_top_k, swiglu
from deeplearning4j_tpu.parallel.expert_parallel import (
    shard_expert_params, expert_parallel_apply)

D, W, E, K = 8, 16, 8, 3


def _layer(**kw):
    return ExpertLayer(n_in=D, n_experts=E, experts_per_token=K,
                       expert_width=W, shared_width=W, routed_scale=2.5, **kw)


def _dense_oracle(layer, p, x2):
    idx, w = route_top_k(x2, p["Wr"], K, True, 2.5)
    y = swiglu(x2, p["Sg"], p["Su"], p["Sd"])
    for e in range(p["Eg"].shape[0]):
        we = jnp.where(idx == e, w, 0.0).sum(-1)
        y = y + we[:, None] * swiglu(x2, p["Eg"][e], p["Eu"][e], p["Ed"][e])
    return y


def _x(n, seed=1):
    return jnp.asarray(np.random.RandomState(seed).randn(n, D), jnp.float32)


def test_routing_equals_dense_oracle():
    layer = _layer()
    p = layer.init(jax.random.PRNGKey(0))
    x = _x(32)
    y, _ = layer.apply(p, x[None])
    np.testing.assert_allclose(np.asarray(y[0]),
                               np.asarray(_dense_oracle(layer, p, x)),
                               rtol=1e-4, atol=1e-5)


def test_nothing_dropped_under_skew():
    """Every token on the same three experts of the four held: they take
    all the pairs, past the first round's buffer, and none is left out."""
    layer = _layer(experts_held=(4, 0))
    p = layer.init(jax.random.PRNGKey(2))
    p["Wr"] = jnp.zeros_like(p["Wr"]).at[:, :K].set(1.0)
    x = jnp.abs(_x(64, 2)) + 0.1         # positive: experts 0..K-1 win
    rows, rounds = layer.round_rows(64)
    assert rounds > 1 and rows < 64 * K
    y, seen = layer.routed(p, x)
    assert int(seen["pairs"]) == 64 * K and int(seen["pairs_dropped"]) == 0
    assert int(seen["load_max"]) == 64
    want = _dense_oracle(layer, p, x) - swiglu(x, p["Sg"], p["Su"], p["Sd"])
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    # and the gradient flows through the later rounds
    g = jax.grad(lambda p: layer.routed(p, x)[0].sum())(p)
    gw = jax.grad(lambda p: (_dense_oracle(layer, p, x)
                             - swiglu(x, p["Sg"], p["Su"], p["Sd"])).sum())(p)
    for k in ("Eg", "Eu", "Ed"):
        np.testing.assert_allclose(np.asarray(g[k]), np.asarray(gw[k]),
                                   rtol=1e-3, atol=1e-4, err_msg=k)


# the second form too: relu^2 experts inside a latent behind a sigmoid
# router, whose stacked leaves are E1, E2
@pytest.mark.parametrize("form,leaf", [
    ({}, "Eg"),
    ({"expert_form": "relu2", "score": "sigmoid", "latent_width": 4}, "E1")])
def test_sharded_over_expert_axis_equals_unsharded(form, leaf):
    mesh = Mesh(np.array(jax.devices()[:4]), ("expert",))
    layer = _layer(**form)
    p = layer.init(jax.random.PRNGKey(3))
    x = _x(32, 3)
    want, _ = layer.apply(p, x[None])
    sharded = shard_expert_params(p, mesh)
    assert len(sharded[leaf].sharding.device_set) == 4
    y, seen = jax.jit(lambda p, x: expert_parallel_apply(layer, p, x, mesh))(
        sharded, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want[0]),
                               rtol=1e-4, atol=1e-5)
    assert int(seen["pairs"]) == 32 * K and int(seen["pairs_dropped"]) == 0


def test_trainable_end_to_end():
    layer = _layer()
    p = layer.init(jax.random.PRNGKey(4))
    x = _x(64, 4)
    target = jnp.tanh(x)

    def loss(p):
        y, _ = layer.apply(p, x[None])
        return jnp.mean((y[0] - target) ** 2)

    step = jax.jit(lambda p: jax.tree_util.tree_map(
        lambda a, g: a - 0.05 * g, p, jax.grad(loss)(p)))
    l0 = float(loss(p))
    for _ in range(40):
        p = step(p)
    assert float(loss(p)) < 0.7 * l0
    assert float(jnp.abs(jax.grad(loss)(p)["Wr"]).sum()) > 0
