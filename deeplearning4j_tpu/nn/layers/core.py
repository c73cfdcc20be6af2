"""Core feed-forward layers.

Parity: reference nn/conf/layers/DenseLayer.java, OutputLayer.java,
LossLayer.java, ActivationLayer.java, DropoutLayer.java, EmbeddingLayer.java,
ElementWiseMultiplicationLayer + nn/layers/feedforward/** impls. Param keys
match the reference ("W", "b") for import compatibility
(nn/params/DefaultParamInitializer.java).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers.base import Layer, register_layer, require_dims
from deeplearning4j_tpu.nn.activations import get_activation
from deeplearning4j_tpu.nn.losses import (get_loss, is_class_ids,
                                          sparse_mcxent_from_features)
from deeplearning4j_tpu.nn.weights import init_weights
from deeplearning4j_tpu.nn.conf.inputs import InputType


@register_layer
@dataclass
class DenseLayer(Layer):
    """Fully connected layer: y = act(x @ W + b). On 3d (B,T,C) input the
    matmul is applied per timestep — one big (B*T, C) GEMM on the MXU
    (the reference inserts an RnnToFeedForwardPreProcessor instead)."""
    n_in: int = 0
    n_out: int = 0
    has_bias: bool = True

    def set_n_in(self, input_type):
        if self.n_in == 0:
            self.n_in = input_type.flat_size() if input_type.kind != "rnn" \
                else input_type.size

    def output_type(self, input_type):
        if input_type.kind == "rnn":
            return InputType.recurrent(self.n_out, input_type.timeseries_length)
        return InputType.feed_forward(self.n_out)

    def init(self, rng, dtype=jnp.float32):
        require_dims(self, n_in=self.n_in, n_out=self.n_out)
        p = {"W": init_weights(rng, (self.n_in, self.n_out),
                               self.weight_init or "xavier", self.dist, dtype)}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init or 0.0, dtype)
        return p

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        if x.ndim >= 4 or (x.ndim == 3 and x.shape[-1] != self.n_in):
            x = x.reshape(x.shape[0], -1)  # implicit CNN→FF flatten
        y = x @ params["W"]
        if self.has_bias:
            y = y + params["b"]
        return get_activation(self.activation or "identity")(y), state


@register_layer
@dataclass
class OutputLayer(DenseLayer):
    """Dense + loss head (parity: nn/conf/layers/OutputLayer.java). The
    container calls ``compute_score`` with labels during training."""
    loss: str = "mcxent"

    def compute_score(self, params, x, labels, mask=None, *, train=False, rng=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        if x.ndim >= 4 or (x.ndim == 3 and x.shape[-1] != self.n_in):
            x = x.reshape(x.shape[0], -1)
        if is_class_ids(labels):
            # integer labels are class ids, (B,) or (B, T): never one-hot
            if (str(self.loss).lower() not in ("mcxent",
                                               "negativeloglikelihood")
                    or str(self.activation or "softmax").lower() != "softmax"):
                raise ValueError(
                    "integer labels need loss 'mcxent' over a softmax; got "
                    f"loss {self.loss!r}, activation {self.activation!r}")
            return sparse_mcxent_from_features(
                labels.reshape(-1), x.reshape(-1, x.shape[-1]), params["W"],
                params["b"] if self.has_bias else None,
                None if mask is None else mask.reshape(-1))
        pre = x @ params["W"]
        if self.has_bias:
            pre = pre + params["b"]
        if pre.ndim == 3:  # (B,T,C) time-distributed loss
            B, T, C = pre.shape
            pre = pre.reshape(B * T, C)
            labels = labels.reshape(B * T, -1)
            if mask is not None:
                mask = mask.reshape(B * T)
        return get_loss(self.loss)(labels, pre, self.activation or "softmax", mask)


@register_layer
@dataclass
class LossLayer(Layer):
    """Loss-only head, no params (parity: nn/conf/layers/LossLayer.java)."""
    loss: str = "mcxent"

    def has_params(self):
        return False

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        return get_activation(self.activation or "identity")(x), state

    def compute_score(self, params, x, labels, mask=None, *, train=False, rng=None):
        return get_loss(self.loss)(labels, x, self.activation or "identity", mask)


@register_layer
@dataclass
class ActivationLayer(Layer):
    def has_params(self):
        return False

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        return get_activation(self.activation or "relu")(x), state


@register_layer
@dataclass
class DropoutLayer(Layer):
    def has_params(self):
        return False

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        return self.maybe_dropout(x, train=train, rng=rng), state


@register_layer
@dataclass
class FlattenLayer(Layer):
    """Flatten all non-batch dims to (B, N). Needed for Keras-import parity
    where a Flatten precedes a Dense over a SEQUENCE input — our DenseLayer
    is time-distributed on (B, T, C), not flattening (for CNN inputs it
    flattens natively, core.py:30)."""

    def has_params(self):
        return False

    def output_type(self, input_type):
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        if input_type.kind == "rnn":
            t = input_type.timeseries_length
            if t is None or t <= 0:
                raise ValueError(
                    "FlattenLayer over a sequence input needs a static "
                    "timeseries length (flat width = size * T)")
            return InputType.feed_forward(input_type.size * t)
        return InputType.feed_forward(input_type.flat_size())

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        return x.reshape(x.shape[0], -1), state


@register_layer
@dataclass
class ReshapeLayer(Layer):
    """Reshape activations to ``target_shape`` (excluding the batch dim).
    Parity role: the reference's ReshapeVertex / KerasReshape
    (modelimport/keras/layers/core/KerasReshape.java) as a sequential layer.
    Rank decides the output kind: 1 → feed-forward, 2 → recurrent (T, C),
    3 → convolutional (H, W, C) — this build's native layouts. One ``-1``
    wildcard dim is resolved from the input's flat size (Keras Reshape
    semantics)."""
    target_shape: tuple = ()

    def __post_init__(self):
        self.target_shape = tuple(int(d) for d in self.target_shape)
        if sum(1 for d in self.target_shape if d == -1) > 1:
            raise ValueError(
                f"Reshape target {self.target_shape} has more than one -1")

    def has_params(self):
        return False

    def _resolved(self, flat: int) -> tuple:
        s = self.target_shape
        if -1 not in s:
            return s
        known = 1
        for d in s:
            if d != -1:
                known *= d
        if known <= 0 or flat % known != 0:
            raise ValueError(
                f"Cannot infer -1 in reshape target {s} from flat size {flat}")
        return tuple(flat // known if d == -1 else d for d in s)

    def output_type(self, input_type):
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        s = self.target_shape
        if -1 in s:
            flat = (input_type.size * input_type.timeseries_length
                    if input_type.kind == "rnn"
                    and input_type.timeseries_length > 0
                    else input_type.flat_size())
            s = self._resolved(flat)
        if len(s) == 1:
            return InputType.feed_forward(s[0])
        if len(s) == 2:
            return InputType.recurrent(s[1], s[0])
        if len(s) == 3:
            return InputType.convolutional(s[0], s[1], s[2])
        raise ValueError(f"Unsupported reshape target {s}")

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        return x.reshape((x.shape[0],) + self.target_shape), state


@register_layer
@dataclass
class EmbeddingLayer(Layer):
    """Index → vector lookup (parity: nn/conf/layers/EmbeddingLayer.java).
    Input: (B,) or (B,1) int indices. A gather, not a one-hot matmul —
    XLA lowers this to a dynamic-slice, cheap on TPU."""
    n_in: int = 0   # vocab size
    n_out: int = 0
    has_bias: bool = True

    def set_n_in(self, input_type):
        if self.n_in == 0:
            self.n_in = input_type.flat_size()

    def output_type(self, input_type):
        return InputType.feed_forward(self.n_out)

    def init(self, rng, dtype=jnp.float32):
        p = {"W": init_weights(rng, (self.n_in, self.n_out),
                               self.weight_init or "xavier", self.dist, dtype)}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init or 0.0, dtype)
        return p

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        idx = x.astype(jnp.int32)
        if idx.ndim == 2 and idx.shape[-1] == 1:
            idx = idx[:, 0]
        y = params["W"][idx]
        if self.has_bias:
            y = y + params["b"]
        return get_activation(self.activation or "identity")(y), state


@register_layer
@dataclass
class EmbeddingSequenceLayer(Layer):
    """Sequence of indices → sequence of vectors: (B,T) → (B,T,E)."""
    n_in: int = 0
    n_out: int = 0
    has_bias: bool = False

    def set_n_in(self, input_type):
        if self.n_in == 0:
            self.n_in = input_type.size or input_type.flat_size()

    def output_type(self, input_type):
        t = input_type.timeseries_length if input_type.kind == "rnn" else -1
        return InputType.recurrent(self.n_out, t)

    def init(self, rng, dtype=jnp.float32):
        p = {"W": init_weights(rng, (self.n_in, self.n_out),
                               self.weight_init or "xavier", self.dist, dtype)}
        if self.has_bias:
            p["b"] = jnp.zeros((self.n_out,), dtype)
        return p

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        idx = x.astype(jnp.int32)
        if idx.ndim == 3 and idx.shape[-1] == 1:
            idx = idx[..., 0]
        y = params["W"][idx]
        if self.has_bias:
            y = y + params["b"]
        return get_activation(self.activation or "identity")(y), state


@register_layer
@dataclass
class PReLULayer(Layer):
    """Learned leaky-relu slope (parity: nn/conf/layers/PReLULayer later refs;
    alpha shared per-feature)."""
    n_in: int = 0

    def set_n_in(self, input_type):
        if self.n_in == 0:
            self.n_in = input_type.flat_size()

    def init(self, rng, dtype=jnp.float32):
        return {"alpha": jnp.zeros((self.n_in,), dtype)}

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        a = params["alpha"]
        shape = [1] * (x.ndim - 1) + [a.shape[0]]
        a = a.reshape(shape)
        return jnp.where(x >= 0, x, a * x), state


@register_layer
@dataclass
class ElementWiseMultiplicationLayer(Layer):
    """y = act(x * w + b), elementwise learned scaling
    (parity: nn/conf/layers/misc/ElementWiseMultiplicationLayer)."""
    n_in: int = 0
    n_out: int = 0

    def set_n_in(self, input_type):
        if self.n_in == 0:
            self.n_in = input_type.flat_size()
        self.n_out = self.n_in

    def output_type(self, input_type):
        return InputType.feed_forward(self.n_out or self.n_in)

    def init(self, rng, dtype=jnp.float32):
        return {"W": jnp.ones((self.n_in,), dtype),
                "b": jnp.zeros((self.n_in,), dtype)}

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        y = x * params["W"] + params["b"]
        return get_activation(self.activation or "identity")(y), state
