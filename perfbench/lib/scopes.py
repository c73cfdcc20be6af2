"""From the compiler's operation names to the program's own: phase, layer
and layer kind of a device event.

The program opens ``jax.named_scope`` around each phase of a step
(``forward``, ``loss``, ``updater``) and around each layer's ``apply``
(``<name>:<Class>``); the compiled HLO carries the path as ``op_name``,
and the program's registry keeps ``op_scopes``, the table from instruction
name to ``op_name`` of the program that was loaded
(``deeplearning4j_tpu/exec/programs.py``). A device event of the profiler
is named by its instruction's text, so ``lib.trace.short_name`` of it is the
table's key. This module holds the rule and the reduction; it imports
nothing of the program.

The rule, on the path split at ``/`` with autodiff's wrappers taken off
each part (``jvp(forward)`` is ``forward``):

- under ``updater``: phase ``updater``;
- else under ``rematted_computation``: ``recompute`` (what
  ``jax.checkpoint`` runs again in the backward pass);
- else inside a ``transpose(...)``: ``backward``;
- else under ``loss``: ``loss``; under ``forward``: ``forward``;
- anything else is ``unscoped``, and is reported, never dropped.

The layer is the first part after ``forward`` that has a ``:``, its kind
the class after the last ``:``; an operation of a phase with no layer
around it (the cast of the parameters, the l2 terms, the updater) has the
kind ``-``.
"""

from __future__ import annotations

import re

from perfbench.lib.trace import CONTAINER, short_name

PHASES = ("forward", "recompute", "backward", "loss", "updater", "unscoped")
_WRAPPED = re.compile(r"^(?:(?:jvp|transpose|vmap|custom_jvp|custom_vjp)\()+"
                      r"(.*?)\)+$")


def classify(op_name):
    """``(phase, layer, kind)`` of one ``op_name`` path."""
    parts = (op_name or "").split("/")
    transposed = any(p.startswith("transpose(") for p in parts)
    parts = [m.group(1) if (m := _WRAPPED.match(p)) else p for p in parts]
    layer = kind = None
    if "forward" in parts:
        for p in parts[parts.index("forward") + 1:]:
            if ":" in p:
                layer, kind = p, p.rsplit(":", 1)[1]
                break
    if "updater" in parts:
        phase = "updater"
    elif "rematted_computation" in parts:
        phase = "recompute"
    elif transposed:
        phase = "backward"
    elif "loss" in parts:
        phase = "loss"
    elif "forward" in parts:
        phase = "forward"
    else:
        return "unscoped", None, None
    return phase, layer, kind or "-"


def split(ops, op_scopes):
    """Device seconds of ``ops`` (``[(start_ns, end_ns, event name)]``, as
    ``lib.trace.Chip.ops``) by phase, by kind and by (phase, kind), joined
    to ``op_scopes`` by instruction name. An event the table does not know,
    or knows without a scope, counts as ``unscoped``; an event that only
    holds others (the scan's ``while``) is theirs and is left out."""
    both, total = {}, 0.0
    for s, e, text in ops:
        if CONTAINER.match(text):
            continue
        phase, _, kind = classify(op_scopes.get(short_name(text)))
        sec = (e - s) / 1e9
        total += sec
        key = (phase, kind or "-")
        both[key] = both.get(key, 0.0) + sec

    def summed(i):
        out = {}
        for key, sec in both.items():
            out[key[i]] = out.get(key[i], 0.0) + sec
        return out

    by_phase = summed(0)
    return {"total_s": total, "by_phase": by_phase, "by_kind": summed(1),
            "by_phase_kind": both,
            "unscoped_share": (by_phase.get("unscoped", 0.0) / total
                               if total else 0.0)}
