"""The plain reference: the configuration's convolutional net, its loss,
gradient and Nesterov update in straightforward float32 ``jax.numpy`` /
``lax`` at the highest matmul precision. It imports nothing of the program
and is given nothing the program made: weights come from
``perfbench/lib/weights.py``, batches from the job's own pool.

Memory: each ``block`` of the node list is one ``jax.checkpoint``, so only
block boundaries are kept for the backward pass and a float32 step at the
timed batch fits beside nothing else on the chip.

``precision`` below float32 is the control of ``correct``. ``bfloat16`` is
what the program itself does: every tensor between layers in bfloat16,
products accumulated in float32. ``fp8`` is the step below it: every tensor
between layers, and every kernel going into a product, rounded to
float8_e4m3 (scaled per tensor to its range), every cotangent coming back
to float8_e5m2, products still accumulated in float32. ``int8`` is the
same with 255 even levels per tensor, forward and back: the other type the
chip multiplies faster than bfloat16.
``fault`` plants one of the faults a training step can have.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
from jax import lax

from perfbench.lib import arch

FAULTS = (None, "half_batch")


def _scaled_round(x, dtype, top):
    """x rounded to an 8-bit float type, scaled per tensor to its range."""
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)).astype(jnp.float32), 1e-30)
    q = (x.astype(jnp.float32) * scale).astype(dtype)
    return (q.astype(jnp.float32) / scale).astype(x.dtype)


def _int8_round(x):
    """x rounded to 255 levels, symmetric, scaled per tensor."""
    scale = 127.0 / jnp.maximum(jnp.max(jnp.abs(x)).astype(jnp.float32),
                                1e-30)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) * scale), -127.0, 127.0)
    return (q / scale).astype(x.dtype)


@jax.custom_vjp
def _int8_both(x):
    return _int8_round(x)


_int8_both.defvjp(lambda x: (_int8_round(x), None),
                  lambda _, g: (_int8_round(g),))


@jax.custom_vjp
def _fp8_round(x):
    """float8_e4m3 going forward, float8_e5m2 for the cotangent coming
    back: the usual pair of fp8 training."""
    return _scaled_round(x, jnp.float8_e4m3fn, 448.0)


_fp8_round.defvjp(
    lambda x: (_fp8_round(x), None),
    lambda _, g: (_scaled_round(g, jnp.float8_e5m2, 57344.0),))


ROUNDERS = {"fp8": _fp8_round, "int8": _int8_both}


def _act(name, x):
    if name == "relu":
        return jnp.maximum(x, 0)
    if name == "identity":
        return x
    raise ValueError(name)


def _blocks(nodes):
    out = []
    for n in nodes:
        if out and out[-1][0]["block"] == n["block"]:
            out[-1].append(n)
        else:
            out.append([n])
    return out


def _apply_node(cfg, n, acts, params, state, new_state, rng, precision,
                labels):
    y = _node(cfg, n, acts, params, state, new_state, rng, precision, labels)
    if precision in ROUNDERS and n["op"] != "output":
        y = ROUNDERS[precision](y)
    return y


def _node(cfg, n, acts, params, state, new_state, rng, precision, labels):
    cdt = jnp.float32 if precision == "float32" else jnp.bfloat16
    x = acts[n["in"][0]]
    op = n["op"]

    def product_inputs(a, w):
        return (a, ROUNDERS[precision](w)) if precision in ROUNDERS \
            else (a, w)

    if op == "conv":
        a, w = product_inputs(x, params[n["key"]]["W"].astype(cdt))
        y = lax.conv_general_dilated(
            a, w, (n["s"], n["s"]), [(n["p"], n["p"])] * 2,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if n.get("bias"):
            y = y + params[n["key"]]["b"].astype(cdt)
        return _act(n["act"], y)
    if op == "bn":
        axes = tuple(range(x.ndim - 1))
        mean = jnp.mean(x, axes)
        var = jnp.var(x, axes)
        d = cfg["bn_decay"]
        st = state[n["key"]]
        new_state[n["key"]] = {
            "mean": d * st["mean"] + (1 - d) * mean.astype(jnp.float32),
            "var": d * st["var"] + (1 - d) * var.astype(jnp.float32)}
        p = params[n["key"]]
        y = (x - mean) * lax.rsqrt(var + cfg["bn_eps"])
        y = y * p["gamma"].astype(cdt) + p["beta"].astype(cdt)
        return _act(n["act"], y)
    if op == "maxpool":
        return lax.reduce_window(
            x, -jnp.inf, lax.max, (1, n["k"], n["k"], 1),
            (1, n["s"], n["s"], 1),
            ((0, 0), (n["p"], n["p"]), (n["p"], n["p"]), (0, 0)))
    if op == "add":
        return x + acts[n["in"][1]]
    if op == "relu":
        return jnp.maximum(x, 0)
    if op == "gap":
        return jnp.mean(x, (1, 2))
    if op in ("dense", "output"):
        x = x.reshape(x.shape[0], -1)
        drop = n.get("dropout")
        if drop:
            # the program's own key, which the configuration file states
            k = jax.random.fold_in(rng, n["rng_index"])
            keep = 1.0 - drop
            m = jax.random.bernoulli(k, keep, x.shape)
            x = jnp.where(m, x / keep, jnp.zeros((), x.dtype))
        a, w = product_inputs(x, params[n["key"]]["W"].astype(cdt))
        y = a @ w
        if n.get("bias", True):
            y = y + params[n["key"]]["b"].astype(cdt)
        if op == "dense":
            return _act(n["act"], y)
        logp = jax.nn.log_softmax(y, axis=-1)
        return jnp.mean(jnp.sum(-labels.astype(y.dtype) * logp, axis=-1))
    raise ValueError(op)


def loss_fn(cfg, params, state, x, labels, rng, precision="float32"):
    """Mean cross entropy of the softmax output plus 0.5 * l2 * |W|^2 over
    kernels; returns (loss, new BatchNorm state)."""
    cdt = jnp.float32 if precision == "float32" else jnp.bfloat16
    acts = {"input": x.astype(cdt)}
    new_state = {}
    for block in _blocks(cfg["nodes"]):
        keys = [n["key"] for n in block]
        needs = sorted({str(i) for n in block for i in n["in"]
                        if i not in keys})
        by_str = {str(k): k for k in acts}

        def run(ext, bparams, bstate, block=block, by_str=by_str):
            local = {by_str[s]: v for s, v in ext.items()}
            ns = {}
            for n in block:
                local[n["key"]] = _apply_node(cfg, n, local, bparams, bstate,
                                              ns, rng, precision, labels)
            return local[block[-1]["key"]], ns

        bparams = {n["key"]: params[n["key"]] for n in block
                   if n["key"] in params}
        bstate = {n["key"]: state[n["key"]] for n in block
                  if n["key"] in state}
        y, ns = jax.checkpoint(run)(
            {s: acts[by_str[s]] for s in needs}, bparams, bstate)
        acts[block[-1]["key"]] = y
        new_state.update(ns)
    loss = acts[cfg["nodes"][-1]["key"]].astype(jnp.float32)
    l2 = cfg.get("l2", 0.0)
    if l2:
        reg = sum(jnp.sum(p["W"].astype(jnp.float32) ** 2)
                  for p in params.values() if "W" in p)
        loss = loss + 0.5 * l2 * reg
    return loss, new_state


def make_step(cfg, precision="float32", fault=None):
    """One training step, jitted: (params, state, trace, x, labels, it) ->
    (params, state, trace, loss). Nesterov momentum as the
    configuration states it: trace = g + m * trace; w -= lr * (g + m * trace).
    The dropout key is fold_in(PRNGKey(seed), it)."""
    if fault not in FAULTS:
        raise ValueError(fault)
    lr = cfg["updater"]["learning_rate"]
    mom = cfg["updater"]["momentum"]

    def step(params, state, trace, x, labels, it, seed):
        if fault == "half_batch":
            x, labels = x[: x.shape[0] // 2], labels[: labels.shape[0] // 2]
        rng = jax.random.fold_in(jax.random.PRNGKey(seed), it)
        (loss, new_state), grads = jax.value_and_grad(
            functools.partial(loss_fn, cfg, precision=precision),
            has_aux=True)(params, state, x, labels, rng)
        new_trace = jax.tree_util.tree_map(lambda g, t: g + mom * t,
                                           grads, trace)
        new_params = jax.tree_util.tree_map(
            lambda p, g, t: p - lr * (g + mom * t), params, grads, new_trace)
        return new_params, new_state, new_trace, loss

    return jax.jit(step, donate_argnums=(0, 1, 2))


def leaf_norms(tree, leaves):
    """Euclidean norm of every (key, leaf) of ``leaves`` in ``tree``, as
    one float32 vector in that order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        tree[k][name].astype(jnp.float32)))) for k, name in leaves])


def run_steps(cfg, weights, state, batches, seed, *, precision="float32",
              fault=None, trace_after=1):
    """Drive ``len(batches)`` steps from ``weights``. Returns per-step
    losses and, per parameter leaf in ``arch.param_leaves`` order, the norm
    of the momentum trace after ``trace_after`` steps (after one step that
    is the first gradient as the optimizer gets it), the norm of the
    parameters' change after the last step, and the same for BatchNorm's
    statistics. ``weights`` and ``state`` are consumed."""
    pleaves = [(k, n) for k, n, _, _ in arch.param_leaves(cfg)]
    sleaves = [(k, n) for k, n, _ in arch.state_leaves(cfg)]
    step = make_step(cfg, precision, fault)
    # whole trees under one jit each: leaf by leaf, every shape of every
    # small operation compiles anew in every run (13 s of a 20 s reference)
    tmap = jax.tree_util.tree_map
    diff = jax.jit(lambda a, b: tmap(jnp.subtract, a, b))
    copy = jax.jit(lambda t: tmap(jnp.copy, t))
    pnorms = jax.jit(lambda t: leaf_norms(t, pleaves))
    snorms = jax.jit(lambda t: leaf_norms(t, sleaves))
    p0, s0 = copy(weights), copy(state)
    params, trace = weights, jax.jit(
        lambda t: tmap(jnp.zeros_like, t))(weights)
    out = {"losses": [], "trace_norms": None, "step_seconds": []}
    with jax.default_matmul_precision("highest"):
        for i, (x, y) in enumerate(batches):
            t = time.perf_counter()
            params, state, trace, loss = step(
                params, state, trace, x, y, jnp.asarray(i, jnp.int32),
                jnp.asarray(int(seed), jnp.uint32))
            out["losses"].append(float(loss))
            # the first holds tracing, lowering and the cache load
            out["step_seconds"].append(time.perf_counter() - t)
            if i + 1 == trace_after:
                out["trace_norms"] = jax.device_get(pnorms(trace))
    out["delta_norms"] = jax.device_get(pnorms(diff(params, p0)))
    out["state_delta_norms"] = (jax.device_get(
        snorms(diff(state, s0))) if sleaves else [])
    return out
