"""Stable names inside the step programs of both network containers.

``jax.named_scope`` puts a name on the stack that every operation traced
under it carries into the compiled HLO as ``op_name``, through autodiff and
``jax.checkpoint``: ``jit(step)/jvp(forward)/bn1:BatchNormalization/div``
(forward), ``.../transpose(jvp(...))/checkpoint/forward/bn1:.../mul``
(backward), ``.../checkpoint/rematted_computation/forward/bn1:.../sqrt``
(recomputation), ``jit(step)/updater/sub``. Phase and layer follow from the
path by a rule (perfbench/lib/scopes.py reads it; the table of a compiled
program is ``ProgramRegistry``'s ``op_scopes``). Scopes are metadata only:
the arithmetic, the donation and the compile-cache key do not change.

The step's three phases are the plain scopes ``forward``, ``loss`` and
``updater``, opened by the containers; a layer's or vertex's scope is
``<name>:<Class>``.
"""

from __future__ import annotations

import jax


def layer_scope(name, layer):
    """``jax.named_scope("<name>:<LayerClass>")`` around one layer's or
    vertex's ``apply``. The path rule splits on ``/``, so a ``/`` in the
    name is replaced."""
    return jax.named_scope(
        f"{str(name).replace('/', '_')}:{type(layer).__name__}")
