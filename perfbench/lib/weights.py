"""Weights made by the benchmark from --seed, on the device, in one jitted
call, float32 (the type the program keeps its parameters in). The program
and the plain reference are both given these; neither makes its own."""

from __future__ import annotations

import math

from perfbench.lib import arch


def jax_seed(seed: int) -> int:
    """--seed folded into what a 32-bit PRNG key takes."""
    return int(seed) % (2 ** 31 - 1)


def make_weights(cfg, seed: int):
    """{key: {leaf: array}} for every parameter leaf of the configuration:
    He-normal kernels (std sqrt(2 / fan_in), the zoo's ``relu`` rule),
    zero biases and beta, unit gamma."""
    import jax
    import jax.numpy as jnp
    leaves = arch.param_leaves(cfg)

    def build(key):
        out = {}
        for i, (k, name, shape, kind) in enumerate(leaves):
            if kind == "weight":
                fan_in = math.prod(shape[:-1])
                v = jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32) * math.sqrt(2.0 / fan_in)
            elif kind == "one":
                v = jnp.ones(shape, jnp.float32)
            else:
                v = jnp.zeros(shape, jnp.float32)
            out.setdefault(k, {})[name] = v
        return out

    return jax.jit(build)(jax.random.PRNGKey(jax_seed(seed)))


def initial_state(cfg):
    """BatchNorm's running statistics before the first step."""
    import jax
    import jax.numpy as jnp

    def build():
        out = {}
        for k, name, shape in arch.state_leaves(cfg):
            out.setdefault(k, {})[name] = (
                jnp.zeros if name == "mean" else jnp.ones)(shape, jnp.float32)
        return out

    return jax.jit(build)()
