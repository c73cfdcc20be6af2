"""Shared backward-rematerialization dispatch for the network containers.

See GlobalConf.remat (nn/conf/configuration.py) for the modes.
"""

from __future__ import annotations

import jax

_MODES = (False, True, "full", "save_convs", "selective")


def check_remat_mode(mode):
    """Fail fast on an invalid mode (builder/zoo entry points call this so
    a typo surfaces at configuration time, not at the first train step)."""
    if mode not in _MODES:
        raise ValueError(
            f"unknown remat mode {mode!r} "
            "(False | True | 'full' | 'save_convs' | 'selective')")
    return mode


def remat_loss(loss_fn, mode):
    """``loss_fn`` wrapped per the configured remat ``mode``:
    False → unchanged; True/'full' → jax.checkpoint;
    'save_convs'/'selective' → checkpoint saving only named values: conv
    outputs (ConvolutionLayer tags them "conv_out") and BatchNorm's batch
    mean and inverse deviation (``batch_norm_train`` tags them "bn_stats":
    a few KB a layer that spare the replay a reduction over activations)."""
    if not mode:
        return loss_fn
    if mode in (True, "full"):
        return jax.checkpoint(loss_fn)
    if mode in ("save_convs", "selective"):
        return jax.checkpoint(
            loss_fn,
            policy=jax.checkpoint_policies.save_only_these_names(
                "conv_out", "bn_stats"))
    check_remat_mode(mode)                     # raises; not a known mode
    raise AssertionError("unreachable")
