"""Fused optimizer update: one flat program instead of per-leaf tree_maps.

The per-leaf updater path runs one optax ``update``/``apply_updates`` chain
per layer, which lowers to hundreds of tiny elementwise XLA ops on real
models — each a separate fusion with its own launch and layout overhead.
Here every group of layers that shares an updater config and dtype is
raveled into ONE flat vector, the optax transform runs once over it, and
the results are sliced back into the per-layer pytrees. Because every
shipped updater (nn/updaters.py) plus ``optax.clip`` /
``add_decayed_weights`` is purely elementwise, the fused math is
**bitwise identical** to the per-leaf path — concatenation commutes with
elementwise ops. Cross-leaf reductions (``clip_by_global_norm``) would
not commute; callers mark those members non-fusable via a ``None`` group
key and they keep the legacy per-member math.

The stored opt-state layout is untouched: states stay per-layer (so
checkpoints, the model serializer, and the executor's co-sharding specs
all see the exact structures they saw before) and are flattened/rebuilt
*inside* the traced update via slot-walking:

- the "template" is ``transform.init`` evaluated on the flat vector
  (``jax.eval_shape`` — no compute). Its leaves enumerate the state
  slots in DFS order: a leaf shaped ``(total,)`` is a *param slot* (mu,
  nu, trace, ...), anything else is a *scalar slot* (count, ...).
- each member's stored state flattens in the SAME slot order, with each
  param slot contributing that member's k_i param leaves contiguously
  (DFS keeps embedded param subtrees contiguous). So a single cursor
  walk converts per-member states <-> the flat state exactly.
- scalar slots (step counts) are taken from the first member: within a
  group every member is created by the same ``init`` and stepped by the
  same calls, so the counts are equal by construction.

``FusedUpdate.apply`` is pure — it is traced inside the existing train
steps AND inside the standalone donated update program the model
containers register (see ``_apply_updates_jitted``).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np
import optax

_OVERRIDE: Optional[bool] = None

# A member this large is updated by itself. Fusing exists to spare the
# launches of hundreds of tiny elementwise ops; a layer of 8M parameters is
# no tiny op, and raveling it into the group's flat vector copies its
# parameters, gradients and optimizer moments once more (a decoder's 100M
# layers would not fit beside themselves). The math is the same either way.
FUSE_MAX_MEMBER_SIZE = 1 << 23


def fused_update_enabled() -> bool:
    """Fused updates are on; ``set_fused_update(False)`` forces the per-leaf
    path. Read at optimizer-build time — call ``_build_optimizer()`` after
    toggling."""
    return _OVERRIDE is not False


def set_fused_update(flag: Optional[bool]) -> None:
    """Process-wide override (None restores the default). Used by the bench
    fused-vs-per-leaf sub-row and tests; rebuild optimizers after."""
    global _OVERRIDE
    _OVERRIDE = flag


def _metrics():
    from deeplearning4j_tpu.monitor.metrics import get_registry
    reg = get_registry()
    return reg.gauge(
        "dl4jtpu_train_fused_groups",
        "Fused updater groups in the most recently built optimizer "
        "(0 = per-leaf path)")


@dataclass
class _Group:
    """Members fused into one flat transform (same updater config+dtype)."""
    transform: Any                       # optax GradientTransformation
    members: List[Any]                   # item keys, in build order
    dtype: Any


@dataclass
class FusedUpdate:
    """Grouped update plan for one model's (params, opt_state, grads).

    ``apply`` takes/returns dicts keyed like the build-time dicts; the
    containers adapt their list/dict layouts around it.
    """
    groups: List[_Group]
    fallback: List[Any]                  # keys updated with per-member math
    passthrough: List[Any]               # empty-params keys (copied as-is)
    transforms: Dict[Any, Any]
    constraints: Dict[Any, Callable]

    @property
    def fused_keys(self) -> List[Any]:
        return [k for g in self.groups for k in g.members]

    def apply(self, params: Dict, opt_state: Dict, grads: Dict
              ) -> Tuple[Dict, Dict]:
        new_params: Dict[Any, Any] = {}
        new_opt: Dict[Any, Any] = {}
        for k in self.passthrough:
            new_params[k], new_opt[k] = params[k], opt_state[k]
        for k in self.fallback:
            u, o = self.transforms[k].update(grads[k], opt_state[k],
                                             params[k])
            p = optax.apply_updates(params[k], u)
            new_params[k] = self.constraints[k](p)
            new_opt[k] = o
        for g in self.groups:
            self._apply_group(g, params, opt_state, grads,
                              new_params, new_opt)
        return new_params, new_opt

    # ------------------------------------------------------------ fused core
    def _apply_group(self, g, params, opt_state, grads, new_params, new_opt):
        # ravel every member's param/grad leaves into one flat vector
        metas = []            # (key, treedef, [(shape, dtype), ...])
        pf_parts, gf_parts = [], []
        for k in g.members:
            leaves, treedef = jtu.tree_flatten(params[k])
            gleaves = jtu.tree_flatten(grads[k])[0]
            metas.append((k, treedef, [(l.shape, l.dtype) for l in leaves]))
            pf_parts += [l.ravel() for l in leaves]
            gf_parts += [gl.ravel() for gl in gleaves]
        pf = jnp.concatenate(pf_parts) if len(pf_parts) > 1 else pf_parts[0]
        gf = jnp.concatenate(gf_parts) if len(gf_parts) > 1 else gf_parts[0]
        total = pf.size

        # slot-walk the stored per-member states into the flat state
        tmpl_leaves, tmpl_def = jtu.tree_flatten(
            jax.eval_shape(g.transform.init, jax.ShapeDtypeStruct(
                pf.shape, pf.dtype)))
        mstates = [jtu.tree_flatten(opt_state[k]) for k in g.members]
        cursors = [0] * len(g.members)
        flat_state_leaves = []
        for t in tmpl_leaves:
            if tuple(t.shape) == (int(total),):
                parts = []
                for mi, (_, _, shapes) in enumerate(metas):
                    kk = len(shapes)
                    run = mstates[mi][0][cursors[mi]:cursors[mi] + kk]
                    cursors[mi] += kk
                    parts += [r.ravel() for r in run]
                flat_state_leaves.append(
                    jnp.concatenate(parts) if len(parts) > 1 else parts[0])
            else:
                # scalar slot (e.g. step count): equal across members
                flat_state_leaves.append(mstates[0][0][cursors[0]])
                for mi in range(len(g.members)):
                    cursors[mi] += 1
        flat_state = jtu.tree_unflatten(tmpl_def, flat_state_leaves)

        # one update over the whole group
        u, new_flat = g.transform.update(gf, flat_state, pf)
        new_pf = optax.apply_updates(pf, u)

        # slice params back out and re-apply per-layer constraints
        off = 0
        for k, treedef, shapes in metas:
            lvs = []
            for shp, _dt in shapes:
                n = int(np.prod(shp)) if shp else 1
                lvs.append(new_pf[off:off + n].reshape(shp))
                off += n
            p = jtu.tree_unflatten(treedef, lvs)
            new_params[k] = self.constraints[k](p)

        # slot-walk the new flat state back into per-member states
        new_flat_leaves = jtu.tree_flatten(new_flat)[0]
        member_leaves: List[List[Any]] = [[] for _ in g.members]
        for t, s in zip(tmpl_leaves, new_flat_leaves):
            if tuple(t.shape) == (int(total),):
                off = 0
                for mi, (_, _, shapes) in enumerate(metas):
                    for shp, _dt in shapes:
                        n = int(np.prod(shp)) if shp else 1
                        member_leaves[mi].append(
                            s[off:off + n].reshape(shp))
                        off += n
            else:
                for mi in range(len(g.members)):
                    member_leaves[mi].append(s)
        for mi, (k, _, _) in enumerate(metas):
            new_opt[k] = jtu.tree_unflatten(mstates[mi][1],
                                            member_leaves[mi])


def _identity(p):
    return p


def build_fused_update(params: Dict, transforms: Dict,
                       group_keys: Dict, constraints: Optional[Dict] = None
                       ) -> FusedUpdate:
    """Group items by (group key, dtype) into a :class:`FusedUpdate`.

    ``params`` / ``transforms`` / ``group_keys`` are dicts over the same
    keys. ``group_keys[k]`` is any hashable describing the updater config
    (the containers use the updater's sorted-JSON dict) — members fuse
    only when BOTH the key and every param leaf's dtype match. ``None``
    marks a member non-fusable (frozen layers, cross-leaf clipping), as
    does a size of ``FUSE_MAX_MEMBER_SIZE`` parameters or more; empty param
    trees pass through untouched.
    """
    constraints = constraints or {}
    groups: Dict[Tuple, _Group] = {}
    fallback: List[Any] = []
    passthrough: List[Any] = []
    for k, p in params.items():
        leaves = jtu.tree_leaves(p)
        if not leaves:
            passthrough.append(k)
            continue
        gk = group_keys.get(k)
        dtypes = {l.dtype for l in leaves}
        if (gk is None or len(dtypes) != 1
                or sum(l.size for l in leaves) >= FUSE_MAX_MEMBER_SIZE):
            fallback.append(k)
            continue
        bucket = (gk, next(iter(dtypes)))
        if bucket not in groups:
            groups[bucket] = _Group(transform=transforms[k], members=[],
                                    dtype=bucket[1])
        groups[bucket].members.append(k)
    fu = FusedUpdate(groups=list(groups.values()), fallback=fallback,
                     passthrough=passthrough, transforms=dict(transforms),
                     constraints={k: constraints.get(k, _identity)
                                  for k in params})
    try:
        _metrics().set(len(fu.groups))
    except Exception:
        pass
    return fu
