"""Pipeline parallelism: GPipe-style microbatch schedule over a mesh axis.

The reference has no pipeline parallelism (SURVEY §2 parallelism inventory —
its capability bar is DP only); this is a TPU-idiomatic extension completing
the dp/tp/sp/pp set. Design (the scaling-book recipe):

- the model is S *uniform* stages (same pytree structure per stage); stage
  parameters are stacked on a leading axis and sharded over the mesh's
  'pipe' axis, so each device holds exactly one stage;
- a batch is split into M microbatches; the schedule runs M + S - 1 ticks
  inside ONE compiled ``lax.scan``. Each tick, every device applies its
  stage to its current activation and hands the result to the next device
  with ``lax.ppermute`` (compute overlaps the ICI transfer);
- the whole schedule is differentiable — shard_map/ppermute have transpose
  rules — so ``jax.grad`` of a loss over ``pipeline_forward`` yields the
  stacked per-stage parameter gradients and one optimizer step updates all
  stages in place (the GPipe synchronous update, no weight staleness).

Uniform stages are the standard PP regime (transformer blocks); arbitrary
heterogeneous stacks should use DP/TP instead.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map


def stack_stage_params(per_stage_params):
    """[stage0_tree, stage1_tree, ...] → one tree with leading stage axis."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_stage_params)


def shard_stages(stacked, mesh: Mesh, axis: str = "pipe"):
    """Place the stacked stage params with the stage axis over ``axis``."""
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(
            a, NamedSharding(mesh, P(*( [axis] + [None] * (a.ndim - 1))))),
        stacked)


def pipeline_forward(stage_fn: Callable, stacked_params, x_microbatches,
                     mesh: Mesh, axis: str = "pipe"):
    """Run the pipelined forward.

    stage_fn(params, x) -> y with y.shape == x.shape (uniform stages).
    stacked_params: pytree, leaves (S, ...), stage axis sharded over ``axis``.
    x_microbatches: (M, mb, F) — microbatch axis leading, replicated.
    Returns (M, mb, F): the last stage's output per microbatch.
    """
    S = mesh.shape[axis]
    n_stages = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if n_stages != S:
        raise ValueError(
            f"{n_stages} stages but the '{axis}' mesh axis has {S} devices "
            "— each device holds exactly one stage")
    M = x_microbatches.shape[0]
    T = M + S - 1

    @partial(shard_map, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(),
             check_vma=False)
    def run(params, xs):
        my_params = jax.tree_util.tree_map(lambda a: a[0], params)
        s = lax.axis_index(axis)
        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t; later stages take the handoff
            mb_idx = jnp.clip(t, 0, M - 1)
            x_in = jnp.where(s == 0, xs[mb_idx], buf)
            y = stage_fn(my_params, x_in)
            # the last stage's tick t result is microbatch t - (S-1)
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            take = (s == S - 1) & (t >= S - 1)
            outs = outs.at[out_idx].set(
                jnp.where(take, y, outs[out_idx]))
            buf = lax.ppermute(y, axis, perm)
            return (buf, outs), jnp.float32(0)

        buf0 = jnp.zeros_like(xs[0])
        outs0 = jnp.zeros_like(xs)
        (buf, outs), _ = lax.scan(tick, (buf0, outs0), jnp.arange(T))
        # only the last device holds real outputs; broadcast to all
        outs = lax.psum(jnp.where(s == S - 1, outs, jnp.zeros_like(outs)),
                        axis)
        return outs

    return run(stacked_params, x_microbatches)


def split_microbatches(x, num_microbatches: int):
    """(B, ...) → (M, B/M, ...)."""
    B = x.shape[0]
    if B % num_microbatches:
        raise ValueError(f"batch {B} not divisible into "
                         f"{num_microbatches} microbatches")
    return x.reshape((num_microbatches, B // num_microbatches) + x.shape[1:])


class PipelineParallel:
    """Minimal GPipe trainer over uniform stages.

    stage_fn(stage_params, x) -> y (same shape); loss_fn(y, targets) ->
    scalar mean loss. One jitted train step runs schedule + backward +
    SGD update for all stages.
    """

    def __init__(self, stage_fn, loss_fn, per_stage_params, mesh: Mesh,
                 axis: str = "pipe", learning_rate: float = 1e-2,
                 num_microbatches: int = None):
        self.stage_fn = stage_fn
        self.loss_fn = loss_fn
        self.mesh = mesh
        self.axis = axis
        self.lr = learning_rate
        self.num_microbatches = num_microbatches or mesh.shape[axis]
        if len(per_stage_params) != mesh.shape[axis]:
            raise ValueError(
                f"{len(per_stage_params)} stages but the '{axis}' mesh axis "
                f"has {mesh.shape[axis]} devices")
        self.params = shard_stages(stack_stage_params(per_stage_params),
                                   mesh, axis)
        self._step = None

    def _build(self):
        stage_fn, loss_fn = self.stage_fn, self.loss_fn
        mesh, axis, lr = self.mesh, self.axis, self.lr

        def loss(params, xs, ys):
            outs = pipeline_forward(stage_fn, params, xs, mesh, axis)
            return loss_fn(outs.reshape((-1,) + outs.shape[2:]),
                           ys.reshape((-1,) + ys.shape[2:]))

        def step(params, xs, ys):
            l, g = jax.value_and_grad(loss)(params, xs, ys)
            params = jax.tree_util.tree_map(lambda p, gg: p - lr * gg,
                                            params, g)
            return params, l

        return jax.jit(step, donate_argnums=(0,))

    def fit_batch(self, x, y):
        xs = split_microbatches(jnp.asarray(x), self.num_microbatches)
        ys = split_microbatches(jnp.asarray(y), self.num_microbatches)
        if self._step is None:
            self._step = self._build()
        self.params, loss = self._step(self.params, xs, ys)
        return loss

    def forward(self, x):
        xs = split_microbatches(jnp.asarray(x), self.num_microbatches)
        outs = pipeline_forward(self.stage_fn, self.params, xs, self.mesh,
                                self.axis)
        return outs.reshape((-1,) + outs.shape[2:])
