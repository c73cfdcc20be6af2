"""Numeric gradient checking.

Parity surface: reference gradientcheck/GradientCheckUtil.java:57 — the
correctness backbone of the test suite (13 gradient-check suites,
SURVEY.md §4). Compares ``jax.grad`` analytic gradients against central
finite differences parameter-by-parameter.

Checks run in float64 on CPU (jax.enable_x64 inside) because finite
differences at eps=1e-6 drown in float32 rounding — same reason the
reference forces DOUBLE data type in its gradient-check tests.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp


def _x64():
    """Scope float64 to the check (central differences at eps~1e-6 cancel
    catastrophically in float32; the reference similarly forces
    DataBuffer.Type.DOUBLE in its gradient-check suites). A process-global
    ``jax.config.update`` would leak x64 defaults into every test imported
    after this module — the context manager keeps it local."""
    return jax.enable_x64(True)


def gradient_check_fn(loss_fn, params, eps=1e-6, max_rel_error=1e-3,
                      min_abs_error=1e-8, max_checks_per_array=25, seed=0,
                      verbose=False):
    """Check d loss_fn / d params via central differences (in scoped x64).

    loss_fn: params_pytree -> scalar. Must be pure.
    Returns (n_failures, n_checked, max_rel_err_seen).
    """
    with _x64():
        return _gradient_check_fn_x64(loss_fn, params, eps, max_rel_error,
                                      min_abs_error, max_checks_per_array,
                                      seed, verbose)


def _gradient_check_fn_x64(loss_fn, params, eps, max_rel_error,
                           min_abs_error, max_checks_per_array, seed,
                           verbose):
    # upcast float params HERE, inside the x64 scope — callers can pass f32
    # pytrees without caring about the x64 state of their own context
    params = jax.tree_util.tree_map(
        lambda a: (jnp.asarray(a, jnp.float64)
                   if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)
                   else jnp.asarray(a)), params)
    for leaf in jax.tree_util.tree_leaves(params):
        if jnp.issubdtype(leaf.dtype, jnp.floating) and \
                leaf.dtype != jnp.float64:
            # x64 must actually be enabled here or the whole check silently
            # runs at f32 against its own design (parity:
            # GradientCheckUtil.java:57 forces DOUBLE)
            raise RuntimeError(
                f"gradient check requires f64 but got {leaf.dtype}; "
                "is jax.enable_x64 active?")
    loss_fn = jax.jit(loss_fn)  # compile once; FD loop then runs fast
    grads = jax.jit(jax.grad(loss_fn))(params)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    gleaves = jax.tree_util.tree_flatten(grads)[0]
    rng = np.random.RandomState(seed)

    failures = 0
    checked = 0
    worst = 0.0
    for li, (leaf, gleaf) in enumerate(zip(leaves, gleaves)):
        arr = np.array(leaf, np.float64)  # copy: jax buffers are read-only
        ganalytic = np.asarray(gleaf, np.float64)
        n = arr.size
        idxs = (np.arange(n) if n <= max_checks_per_array
                else rng.choice(n, max_checks_per_array, replace=False))
        for i in idxs:
            orig = arr.flat[i]
            arr.flat[i] = orig + eps
            leaves2 = list(leaves)
            leaves2[li] = jnp.asarray(arr, leaf.dtype)
            plus = float(loss_fn(jax.tree_util.tree_unflatten(treedef, leaves2)))
            arr.flat[i] = orig - eps
            leaves2[li] = jnp.asarray(arr, leaf.dtype)
            minus = float(loss_fn(jax.tree_util.tree_unflatten(treedef, leaves2)))
            arr.flat[i] = orig
            numeric = (plus - minus) / (2 * eps)
            analytic = ganalytic.flat[i]
            denom = abs(numeric) + abs(analytic)
            abs_err = abs(numeric - analytic)
            rel = abs_err / denom if denom > 0 else 0.0
            checked += 1
            if rel > max_rel_error and abs_err > min_abs_error:
                failures += 1
                if verbose:
                    print(f"  leaf {li} idx {i}: analytic={analytic:.3e} "
                          f"numeric={numeric:.3e} rel={rel:.3e}")
            worst = max(worst, rel if abs_err > min_abs_error else 0.0)
    return failures, checked, worst


def gradient_check_network(net, x, y, eps=1e-5, max_rel_error=1e-3,
                           min_abs_error=1e-7, max_checks_per_array=20,
                           verbose=False):
    """Gradient-check a MultiLayerNetwork's full loss (incl. l1/l2) wrt all
    params (parity: GradientCheckUtil.checkGradients)."""
    with _x64():
        x = jnp.asarray(x, jnp.float64) if x.dtype != np.int32 \
            else jnp.asarray(x)
        y = jnp.asarray(y, jnp.float64)
        params64 = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float64), net.params)

        def loss_fn(params):
            loss, _ = net._loss(params, net.state, x, y, None, None, None)
            return loss

        return _gradient_check_fn_x64(loss_fn, params64, eps, max_rel_error,
                                      min_abs_error, max_checks_per_array,
                                      0, verbose)
