"""Expert parallelism: ``ExpertLayer``'s experts divided over a mesh axis.

The reference has no mixture of experts (SURVEY §2 inventory). The layer
(``nn/layers/decoder.py``) is written for a chip that holds a share of the
experts: it routes over all of them, is told which it holds, and computes
their part of the result for the tokens routed to them, dropping nothing.
Here every device of the ``expert`` axis is such a chip: under ``shard_map``
each holds ``n_experts / devices`` consecutive experts, sees every token,
computes its part, and the parts are summed over the axis (``psum``); the
shared expert, which every device would compute alike, is added once. No
capacity, no dispatch tensors, no auxiliary loss.

The exchange is the all-gather of tokens the replicated input implies plus
the ``psum``; an all-to-all that sends each device only its own tokens is
the next step for a mesh where that traffic matters (ROADMAP, Reach).
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

EXPERT_LEAVES = ("Eg", "Eu", "Ed", "E1", "E2")


def shard_expert_params(params, mesh: Mesh, axis: str = "expert"):
    """Expert-major leaves shard their leading (expert) dim over ``axis``;
    the router and the shared expert are replicated."""
    def place(name, a):
        spec = P(axis) if name in EXPERT_LEAVES else P()
        return jax.device_put(a, NamedSharding(mesh, spec))
    return {k: place(k, v) for k, v in params.items()}


def expert_parallel_apply(layer, params, x2, mesh: Mesh,
                          axis: str = "expert"):
    """``layer`` (an ``ExpertLayer`` that holds all its experts) applied to
    tokens x2 (N, C) with its experts divided over ``axis``. Returns
    (y (N, C), counters summed over the axis; ``load_max`` the largest)."""
    import dataclasses

    n_dev = mesh.shape[axis]
    if layer.held != (layer.n_experts, 0) or layer.n_experts % n_dev:
        raise ValueError("expert_parallel_apply divides a whole layer's "
                         f"{layer.n_experts} experts over {n_dev} devices")
    per = layer.n_experts // n_dev
    share = dataclasses.replace(layer, experts_held=(per, 0))

    def part(p, x):
        first = jax.lax.axis_index(axis) * per
        y, seen = share.routed(p, x, first=first)
        return (jax.lax.psum(y, axis),
                {"pairs": jax.lax.psum(seen["pairs"], axis),
                 "pairs_dropped": jax.lax.psum(seen["pairs_dropped"], axis),
                 "load_max": jax.lax.pmax(seen["load_max"], axis)})

    specs = {k: (P(axis) if k in EXPERT_LEAVES else P()) for k in params}
    y, seen = jax.shard_map(part, mesh=mesh, in_specs=(specs, P()),
                            out_specs=(P(), P()), check_vma=False)(params, x2)
    if layer.shared_width:
        y = y + layer.shared(params, x2)
    return y.astype(x2.dtype), seen
