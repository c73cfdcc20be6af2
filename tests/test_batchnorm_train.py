"""Train-mode BatchNormalization is one op with its own backward
(``batch_norm_train`` in nn/layers/conv.py): statistics summed in float32
at least, kept for the backward pass, and the canonical backward. The formula
it replaced (``x.mean()`` then ``x.var()`` in the activation's dtype, autodiff
for the backward) lives on here as the oracle."""

import contextlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax import lax

from deeplearning4j_tpu.nn.activations import get_activation
from deeplearning4j_tpu.nn.layers import BatchNormalization
from deeplearning4j_tpu.util.gradient_check import _x64

SHAPES = {"conv4d": (4, 5, 6, 8), "recurrent3d": (4, 7, 8), "dense2d": (16, 8)}
# relative to the largest entry of the oracle's value
TOL = {"float32": 1e-4, "float64": 1e-9}
BF16_EPS = 2.0 ** -8


def _oracle(layer, params, x, state):
    """``BatchNormalization.apply(train=True)`` as it was before the op."""
    axes = tuple(range(x.ndim - 1))
    mean = x.mean(axis=axes)
    var = x.var(axis=axes)
    new_state = {
        "mean": layer.decay * state["mean"] + (1 - layer.decay) * mean,
        "var": layer.decay * state["var"] + (1 - layer.decay) * var,
    }
    xn = (x - mean) * lax.rsqrt(var + layer.eps)
    if not layer.lock_gamma_beta:
        xn = xn * params["gamma"] + params["beta"]
    return get_activation(layer.activation or "identity")(xn), new_state


def _inputs(shape, dtype, lock):
    rs = np.random.RandomState(3)
    c = shape[-1]
    x = jnp.asarray(0.7 + 1.5 * rs.randn(*shape), dtype)
    w = jnp.asarray(rs.randn(*shape), dtype)          # the cotangent
    params = {} if lock else {
        "gamma": jnp.asarray(1 + 0.3 * rs.randn(c), dtype),
        "beta": jnp.asarray(0.2 * rs.randn(c), dtype)}
    state = {"mean": jnp.asarray(0.1 * rs.randn(c), jnp.float32),
             "var": jnp.asarray(1 + 0.1 * rs.rand(c), jnp.float32)}
    return x, w, params, state


def _value_and_grads(fn, layer, params, x, w, state):
    def loss(params, x):
        y, new_state = fn(layer, params, x, state)
        return (y.astype(w.dtype) * w).sum(), (y, new_state)
    (_, (y, new_state)), (gp, gx) = jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)(params, x)
    return y, new_state, gx, gp


def _apply(layer, params, x, state):
    return layer.apply(params, x, state, train=True)


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(np.abs(want).max(), 1e-30)
    assert np.abs(got - want).max() <= tol * scale, \
        (np.abs(got - want).max() / scale, tol)


@pytest.mark.parametrize("activation", ["identity", "relu"])
@pytest.mark.parametrize("lock", [False, True], ids=["affine", "locked"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "float64"])
@pytest.mark.parametrize("rank", list(SHAPES))
def test_train_op_equals_the_two_pass_autodiff_formula(rank, dtype, lock,
                                                       activation):
    layer = BatchNormalization(n_in=SHAPES[rank][-1], activation=activation,
                               lock_gamma_beta=lock)
    with _x64() if dtype == "float64" else contextlib.nullcontext():
        x, w, params, state = _inputs(SHAPES[rank], jnp.dtype(dtype), lock)
        got = _value_and_grads(_apply, layer, params, x, w, state)
        assert got[0].dtype == x.dtype and got[2].dtype == x.dtype
        assert all(got[3][k].dtype == x.dtype for k in params)
        # the state's contract: float32, or wider with the activations
        assert all(v.dtype == jnp.promote_types(x.dtype, jnp.float32)
                   for v in got[1].values())
        if dtype != "bfloat16":
            want = _value_and_grads(_oracle, layer, params, x, w, state)
            _compare(got, want, TOL[dtype], TOL[dtype])
            return
    # bfloat16: the oracle in float32 on the same bfloat16 numbers; the op
    # rounds its outputs once, and its statistics not at all
    up = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: a.astype(jnp.float32), t)
    want = _value_and_grads(_oracle, layer, up(params), up(x), up(w), state)
    if activation == "relu":
        # an output within rounding of 0 may fall on either side
        live = np.abs(np.asarray(want[0])) > 4 * BF16_EPS
        got = (got[0], got[1], jnp.where(live, got[2], want[2]), got[3])
    _compare(got, want, 2 * BF16_EPS, 1e-5)


def _compare(got, want, tol, state_tol):
    y, new_state, gx, gp = got
    y0, new_state0, gx0, gp0 = want
    _close(y, y0, tol)
    _close(gx, gx0, tol)
    assert set(gp) == set(gp0)
    for k in gp:
        _close(gp[k], gp0[k], tol)
    for k in ("mean", "var"):
        _close(new_state[k], new_state0[k], state_tol)


@pytest.mark.parametrize("dtype,offset,tol", [("float32", 100.0, 1e-3),
                                              ("bfloat16", 10.0, 1e-2)])
def test_variance_survives_a_mean_many_deviations_out(dtype, offset, tol):
    """float32 takes two passes and keeps the variance at a hundred
    deviations; bfloat16 takes one pass in float32, and at ten (beyond, its
    own step is no finer than the deviations) that pass loses nothing."""
    rs = np.random.RandomState(5)
    layer = BatchNormalization(n_in=16, decay=0.0)    # new_state = the batch's
    step = jax.jit(lambda a: layer.apply(layer.init(None), a,
                                         layer.init_state(), train=True))
    x = jnp.asarray(offset + rs.randn(32, 14, 14, 16), dtype)
    y, new_state = step(x)
    true = np.asarray(x, np.float64).var(axis=(0, 1, 2))
    var = np.asarray(new_state["var"], np.float64)
    assert (np.abs(var - true) <= tol * true).all(), np.abs(var / true - 1)
    # rounding may take the mean of squares under mean^2: never negative
    y, new_state = step(jnp.full_like(x, 100.0))
    assert (np.asarray(new_state["var"]) >= 0).all()
    assert np.isfinite(np.asarray(y, np.float32)).all()
