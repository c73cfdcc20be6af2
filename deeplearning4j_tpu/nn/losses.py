"""Loss functions.

Parity surface: the reference's ``ILossFunction`` set (nd4j lossfunctions,
selected in output-layer configs, e.g. reference
deeplearning4j-nn/.../conf/layers/OutputLayer.java and
LossFunctions.LossFunction enum). Every loss takes ``(labels, preoutput,
activation_fn, mask)`` and returns a per-example score plus supports autodiff;
the reference's hand-written ``computeGradient`` is unnecessary under jax.

All losses reduce with mean-over-batch, sum-over-output-dims — matching the
reference's score convention (BaseOptimizer divides by minibatch size,
optimize/solvers/BaseOptimizer.java:314 path).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.activations import get_activation

_EPS = 1e-7


def _apply_mask(per_elem, mask):
    """Broadcast a per-timestep/per-example mask over a per-element loss."""
    if mask is None:
        return per_elem, None
    while mask.ndim < per_elem.ndim:
        mask = mask[..., None]
    return per_elem * mask, mask


def _reduce(per_elem, mask):
    """Sum over feature dims, mean over examples (mask-aware)."""
    per_ex = per_elem.reshape(per_elem.shape[0], -1).sum(axis=-1)
    if mask is not None:
        # mean over unmasked examples/timesteps
        denom = jnp.maximum(mask.reshape(mask.shape[0], -1).max(axis=-1).sum(), 1.0)
        # For RNN losses (B, T, C) the mask sums timesteps; handled upstream by
        # flattening time into batch before calling the loss.
        return per_ex.sum() / denom
    return per_ex.mean()


def l2(labels, preout, activation="identity", mask=None):
    # reference L2 = per-example SUM of squared errors
    out = get_activation(activation)(preout)
    per = (labels - out) ** 2
    per, m = _apply_mask(per, mask)
    return _reduce(per, mask)


def mse(labels, preout, activation="identity", mask=None):
    # reference MSE = L2 / nOut (LossMSE extends LossL2 with /nOut scaling)
    n_out = preout.shape[-1]
    return l2(labels, preout, activation, mask) / n_out


def l1(labels, preout, activation="identity", mask=None):
    out = get_activation(activation)(preout)
    per = jnp.abs(labels - out)
    per, m = _apply_mask(per, mask)
    return _reduce(per, mask)


def mae(labels, preout, activation="identity", mask=None):
    # reference MAE = L1 / nOut
    return l1(labels, preout, activation, mask) / preout.shape[-1]


def mcxent(labels, preout, activation="softmax", mask=None):
    """Multi-class cross entropy. With softmax activation, computed fused as
    log_softmax for numerical stability (XLA fuses this into one kernel)."""
    act_name = activation if isinstance(activation, str) else "softmax"
    if str(act_name).lower() == "softmax":
        logp = jax.nn.log_softmax(preout, axis=-1)
    else:
        out = get_activation(activation)(preout)
        logp = jnp.log(jnp.clip(out, _EPS, 1.0))
    per = -labels * logp
    per, _ = _apply_mask(per, mask)
    return _reduce(per, mask)


def sparse_mcxent(labels, preout, mask=None):
    """Multi-class cross entropy over integer labels: ``labels`` (N,) class
    ids, ``preout`` (N, C) logits; the same number ``mcxent`` gives for the
    one-hot form of the labels, without the (N, C) label array. The log
    softmax is taken in float32."""
    per, _ = _apply_mask(_neg_logp_at(preout, labels), mask)
    return _reduce(per, mask)


def _neg_logp_at(preout, labels):
    """-log softmax(preout)[label] per row, (N, 1), taken in float32."""
    logp = jax.nn.log_softmax(preout.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32),
                                axis=1)


# logits of rows x classes beyond this many elements are never held whole
_CHUNKED_LOGITS = 1 << 26
_LOGIT_ROWS = 2048


def sparse_mcxent_from_features(labels, x, w, b=None, mask=None):
    """``sparse_mcxent(labels, x @ w + b, mask)``; where the (N, C) logits
    would be large (a language model's head) they are made, reduced and
    dropped ``_LOGIT_ROWS`` rows at a time, each chunk a ``jax.checkpoint``
    of its own, so that neither pass holds them whole."""
    n, c = x.shape[0], w.shape[-1]

    def logits(xc):
        z = xc @ w
        return z if b is None else z + b

    if n * c < _CHUNKED_LOGITS or n % _LOGIT_ROWS:
        return sparse_mcxent(labels, logits(x), mask)

    rows = jax.checkpoint(lambda args: _neg_logp_at(logits(args[0]), args[1]))
    per = jax.lax.map(rows, (x.reshape(-1, _LOGIT_ROWS, x.shape[-1]),
                             labels.reshape(-1, _LOGIT_ROWS))).reshape(n, 1)
    per, _ = _apply_mask(per, mask)
    return _reduce(per, mask)


def is_class_ids(labels) -> bool:
    """Integer labels are class ids; floating ones a distribution."""
    return jnp.issubdtype(labels.dtype, jnp.integer)


def negativeloglikelihood(labels, preout, activation="softmax", mask=None):
    return mcxent(labels, preout, activation, mask)


def xent(labels, preout, activation="sigmoid", mask=None):
    """Binary cross entropy. With sigmoid activation uses the logits-stable
    form."""
    if str(activation).lower() == "sigmoid":
        # stable: max(x,0) - x*z + log(1+exp(-|x|))
        x = preout
        per = jnp.maximum(x, 0) - x * labels + jnp.log1p(jnp.exp(-jnp.abs(x)))
    else:
        out = jnp.clip(get_activation(activation)(preout), _EPS, 1 - _EPS)
        per = -(labels * jnp.log(out) + (1 - labels) * jnp.log(1 - out))
    per, _ = _apply_mask(per, mask)
    return _reduce(per, mask)


def hinge(labels, preout, activation="identity", mask=None):
    out = get_activation(activation)(preout)
    per = jnp.maximum(0.0, 1.0 - labels * out)
    per, _ = _apply_mask(per, mask)
    return _reduce(per, mask)


def squared_hinge(labels, preout, activation="identity", mask=None):
    out = get_activation(activation)(preout)
    per = jnp.maximum(0.0, 1.0 - labels * out) ** 2
    per, _ = _apply_mask(per, mask)
    return _reduce(per, mask)


def kl_divergence(labels, preout, activation="softmax", mask=None):
    out = jnp.clip(get_activation(activation)(preout), _EPS, 1.0)
    lab = jnp.clip(labels, _EPS, 1.0)
    per = lab * (jnp.log(lab) - jnp.log(out))
    per, _ = _apply_mask(per, mask)
    return _reduce(per, mask)


def poisson(labels, preout, activation="identity", mask=None):
    out = get_activation(activation)(preout)
    per = out - labels * jnp.log(jnp.clip(out, _EPS, None))
    per, _ = _apply_mask(per, mask)
    return _reduce(per, mask)


def mape(labels, preout, activation="identity", mask=None):
    out = get_activation(activation)(preout)
    per = 100.0 * jnp.abs((labels - out) / jnp.clip(jnp.abs(labels), _EPS, None))
    per, _ = _apply_mask(per, mask)
    return _reduce(per, mask)


def msle(labels, preout, activation="identity", mask=None):
    out = get_activation(activation)(preout)
    per = (jnp.log1p(jnp.clip(out, 0, None)) - jnp.log1p(jnp.clip(labels, 0, None))) ** 2
    per, _ = _apply_mask(per, mask)
    return _reduce(per, mask)


def cosine_proximity(labels, preout, activation="identity", mask=None):
    out = get_activation(activation)(preout)
    ln = jnp.linalg.norm(labels, axis=-1, keepdims=True)
    on = jnp.linalg.norm(out, axis=-1, keepdims=True)
    cos = (labels * out) / jnp.clip(ln * on, _EPS, None)
    per = -cos
    per, _ = _apply_mask(per, mask)
    return _reduce(per, mask)


def wasserstein(labels, preout, activation="identity", mask=None):
    out = get_activation(activation)(preout)
    per = labels * out
    per, _ = _apply_mask(per, mask)
    return _reduce(per, mask)


LOSSES = {
    "mse": mse,
    "l1": l1,
    "l2": l2,
    "mae": mae,
    "mcxent": mcxent,
    "negativeloglikelihood": negativeloglikelihood,
    "xent": xent,
    "hinge": hinge,
    "squaredhinge": squared_hinge,
    "kldivergence": kl_divergence,
    "kl_divergence": kl_divergence,
    "poisson": poisson,
    "meanabsolutepercentageerror": mape,
    "mape": mape,
    "meansquaredlogarithmicerror": msle,
    "msle": msle,
    "cosineproximity": cosine_proximity,
    "cosine_proximity": cosine_proximity,
    "wasserstein": wasserstein,
}


def get_loss(name):
    if callable(name):
        return name
    key = str(name).lower().replace("_", "")
    key2 = str(name).lower()
    if key in LOSSES:
        return LOSSES[key]
    if key2 in LOSSES:
        return LOSSES[key2]
    raise ValueError(f"Unknown loss '{name}'. Available: {sorted(set(LOSSES))}")
