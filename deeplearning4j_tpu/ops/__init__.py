"""Accelerated op helpers — the TPU-native equivalent of the reference's
cuDNN helper seam.

Parity: deeplearning4j-cuda loads drop-in "Helper" kernels by reflection
(reference nn/layers/convolution/ConvolutionLayer.java:74-84,
CudnnLSTMHelper.java:588, SURVEY.md §2 #18). Here the same seam is a module
switch: every hot layer has a *reference* path (pure jax.numpy, always
correct, differentiable by autodiff) and an *accelerated* path (hand-written
Pallas TPU kernels with custom VJPs). The accelerated path is used when

- the platform is TPU (or helpers are force-enabled for interpret-mode
  tests), and
- the call shape/config is supported by the kernel (otherwise the layer
  silently falls back, exactly like the cuDNN helpers return null and the
  built-in path runs).

Equivalence tests (tests/test_ops_kernels.py) compare the two paths'
outputs AND gradients — the ValidateCudnnLSTM / TestConvolution pattern
from deeplearning4j-cuda/src/test (SURVEY.md §4).
"""

from __future__ import annotations

import os
from typing import Optional

_FORCED: Optional[bool] = None      # set_helpers_enabled override
_INTERPRET: bool = False            # run Pallas kernels in interpreter mode


def _on_tpu() -> bool:
    try:
        import jax
        return jax.default_backend() == "tpu"
    except Exception:
        return False


def set_helpers_enabled(flag: Optional[bool], *, interpret: bool = False):
    """Force the accelerated path on/off (None = auto: on iff TPU).
    ``interpret=True`` runs kernels through the Pallas interpreter so the
    accelerated path can be exercised on CPU (tests). Returns the
    ``(flag, interpret)`` it replaced, for a caller that puts it back."""
    global _FORCED, _INTERPRET
    prev = (_FORCED, _INTERPRET)
    _FORCED = flag
    _INTERPRET = interpret
    return prev


def helpers_enabled() -> bool:
    if os.environ.get("DL4J_TPU_DISABLE_HELPERS", "").lower() in ("1", "true", "yes", "on"):
        return False
    if _FORCED is not None:
        return _FORCED
    return _on_tpu()


def interpret_mode() -> bool:
    return _INTERPRET


from deeplearning4j_tpu.ops.lstm_pallas import (fused_lstm_sequence,  # noqa: E402
                                                fused_lstm2_sequence)
from deeplearning4j_tpu.ops.flash_attention import flash_attention  # noqa: E402
from deeplearning4j_tpu.ops.flash_decode import (flash_decode_step,  # noqa: E402
                                                 flash_decode_step_paged)

__all__ = [
    "helpers_enabled", "set_helpers_enabled", "interpret_mode",
    "fused_lstm_sequence", "fused_lstm2_sequence", "flash_attention",
    "flash_decode_step", "flash_decode_step_paged",
]
