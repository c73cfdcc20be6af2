"""Convolutional / pooling / normalization layers.

Parity: reference nn/conf/layers/ConvolutionLayer.java:1-566,
SubsamplingLayer.java, Upsampling*.java, ZeroPaddingLayer.java,
BatchNormalization.java, LocalResponseNormalization.java and their
nn/layers/convolution|normalization impls, plus the cuDNN helper seam
(deeplearning4j-cuda CudnnConvolutionHelper.java etc.).

TPU design: internal layout is NHWC with HWIO kernels — the layout XLA tiles
best onto the MXU; convs lower to ``lax.conv_general_dilated`` (one fused XLA
conv per layer, replacing the reference's im2col+GEMM pipeline,
ConvolutionLayer.java:279 preOutput). There is no algo-selection/workspace
machinery to port: XLA owns scheduling and memory.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from deeplearning4j_tpu.nn.layers.base import Layer, register_layer, as_pair, require_dims
from deeplearning4j_tpu.nn.activations import get_activation
from deeplearning4j_tpu.nn.weights import init_weights
from deeplearning4j_tpu.nn.conf.inputs import InputType, conv_output_size


def _padding_config(mode, kernel, stride, padding, dilation):
    """lax padding config for ConvolutionMode parity ('same'|'truncate')."""
    if mode == "same":
        return "SAME"
    return [(p, p) for p in padding]


@register_layer
@dataclass
class ConvolutionLayer(Layer):
    """2D convolution. Input/weights: NHWC / HWIO."""
    n_in: int = 0                  # input channels
    n_out: int = 0                 # output channels
    kernel_size: Tuple[int, int] = (3, 3)
    stride: Tuple[int, int] = (1, 1)
    padding: Tuple[int, int] = (0, 0)
    dilation: Tuple[int, int] = (1, 1)
    convolution_mode: str = "truncate"   # 'truncate' | 'same'
    has_bias: bool = True

    def __post_init__(self):
        self.kernel_size = as_pair(self.kernel_size)
        self.stride = as_pair(self.stride)
        self.padding = as_pair(self.padding)
        self.dilation = as_pair(self.dilation)

    def set_n_in(self, input_type):
        if self.n_in == 0:
            self.n_in = input_type.channels

    def output_type(self, input_type):
        h = conv_output_size(input_type.height, self.kernel_size[0], self.stride[0],
                             self.padding[0], self.dilation[0], self.convolution_mode)
        w = conv_output_size(input_type.width, self.kernel_size[1], self.stride[1],
                             self.padding[1], self.dilation[1], self.convolution_mode)
        return InputType.convolutional(h, w, self.n_out)

    def init(self, rng, dtype=jnp.float32):
        require_dims(self, n_in=self.n_in, n_out=self.n_out)
        kh, kw = self.kernel_size
        p = {"W": init_weights(rng, (kh, kw, self.n_in, self.n_out),
                               self.weight_init or "xavier", self.dist, dtype)}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init or 0.0, dtype)
        return p

    def _conv(self, x, w):
        y = lax.conv_general_dilated(
            x, w, window_strides=self.stride,
            padding=_padding_config(self.convolution_mode, self.kernel_size,
                                    self.stride, self.padding, self.dilation),
            rhs_dilation=self.dilation,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        # named for selective rematerialization (GlobalConf.remat =
        # 'save_convs', alias 'selective': keep conv outputs, recompute
        # BN/activations); identity outside a remat context
        return checkpoint_name(y, "conv_out")

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        y = self._conv(x, params["W"])
        if self.has_bias:
            y = y + params["b"]
        return get_activation(self.activation or "identity")(y), state


@register_layer
@dataclass
class Convolution1DLayer(Layer):
    """1D (temporal) convolution over (B, T, C)
    (parity: nn/conf/layers/Convolution1DLayer.java)."""
    n_in: int = 0
    n_out: int = 0
    kernel_size: int = 3
    stride: int = 1
    padding: int = 0
    dilation: int = 1
    convolution_mode: str = "truncate"
    has_bias: bool = True

    def set_n_in(self, input_type):
        if self.n_in == 0:
            self.n_in = input_type.size

    def output_type(self, input_type):
        t = input_type.timeseries_length
        if t > 0:
            t = conv_output_size(t, self.kernel_size, self.stride, self.padding,
                                 self.dilation, self.convolution_mode)
        return InputType.recurrent(self.n_out, t)

    def init(self, rng, dtype=jnp.float32):
        p = {"W": init_weights(rng, (self.kernel_size, self.n_in, self.n_out),
                               self.weight_init or "xavier", self.dist, dtype)}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init or 0.0, dtype)
        return p

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        pad = "SAME" if self.convolution_mode == "same" else [(self.padding, self.padding)]
        y = lax.conv_general_dilated(
            x, params["W"], window_strides=(self.stride,), padding=pad,
            rhs_dilation=(self.dilation,),
            dimension_numbers=("NWC", "WIO", "NWC"))
        if self.has_bias:
            y = y + params["b"]
        return get_activation(self.activation or "identity")(y), state


@register_layer
@dataclass
class SeparableConvolution2D(ConvolutionLayer):
    """Depthwise + pointwise conv
    (parity: nn/conf/layers/SeparableConvolution2D.java)."""
    depth_multiplier: int = 1

    def init(self, rng, dtype=jnp.float32):
        kh, kw = self.kernel_size
        r1, r2 = jax.random.split(rng)
        p = {"dW": init_weights(r1, (kh, kw, 1, self.n_in * self.depth_multiplier),
                                self.weight_init or "xavier", self.dist, dtype,
                                fan_in=kh * kw, fan_out=kh * kw * self.depth_multiplier),
             "pW": init_weights(r2, (1, 1, self.n_in * self.depth_multiplier, self.n_out),
                                self.weight_init or "xavier", self.dist, dtype)}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init or 0.0, dtype)
        return p

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        pad = _padding_config(self.convolution_mode, self.kernel_size, self.stride,
                              self.padding, self.dilation)
        y = lax.conv_general_dilated(
            x, params["dW"], window_strides=self.stride, padding=pad,
            rhs_dilation=self.dilation, feature_group_count=self.n_in,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        y = lax.conv_general_dilated(
            y, params["pW"], window_strides=(1, 1), padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if self.has_bias:
            y = y + params["b"]
        return get_activation(self.activation or "identity")(y), state


@register_layer
@dataclass
class DepthwiseConvolution2D(ConvolutionLayer):
    depth_multiplier: int = 1

    def output_type(self, input_type):
        ot = super().output_type(input_type)
        return InputType.convolutional(ot.height, ot.width,
                                       self.n_in * self.depth_multiplier)

    def init(self, rng, dtype=jnp.float32):
        kh, kw = self.kernel_size
        p = {"W": init_weights(rng, (kh, kw, 1, self.n_in * self.depth_multiplier),
                               self.weight_init or "xavier", self.dist, dtype,
                               fan_in=kh * kw, fan_out=kh * kw * self.depth_multiplier)}
        if self.has_bias:
            p["b"] = jnp.full((self.n_in * self.depth_multiplier,),
                              self.bias_init or 0.0, dtype)
        return p

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        pad = _padding_config(self.convolution_mode, self.kernel_size, self.stride,
                              self.padding, self.dilation)
        y = lax.conv_general_dilated(
            x, params["W"], window_strides=self.stride, padding=pad,
            rhs_dilation=self.dilation, feature_group_count=self.n_in,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if self.has_bias:
            y = y + params["b"]
        return get_activation(self.activation or "identity")(y), state


@register_layer
@dataclass
class Deconvolution2D(ConvolutionLayer):
    """Transposed convolution (parity: nn/conf/layers/Deconvolution2D)."""

    def output_type(self, input_type):
        kh, kw = self.kernel_size
        sh, sw = self.stride
        if self.convolution_mode == "same":
            h, w = input_type.height * sh, input_type.width * sw
        else:
            h = sh * (input_type.height - 1) + kh - 2 * self.padding[0]
            w = sw * (input_type.width - 1) + kw - 2 * self.padding[1]
        return InputType.convolutional(h, w, self.n_out)

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        if self.convolution_mode == "same":
            pad = "SAME"
        else:
            kh, kw = self.kernel_size
            ph, pw = self.padding
            pad = [(kh - 1 - ph, kh - 1 - ph), (kw - 1 - pw, kw - 1 - pw)]
        y = lax.conv_transpose(x, params["W"], strides=self.stride, padding=pad,
                               dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if self.has_bias:
            y = y + params["b"]
        return get_activation(self.activation or "identity")(y), state


@register_layer
@dataclass
class SubsamplingLayer(Layer):
    """Pooling (parity: nn/conf/layers/SubsamplingLayer.java; cuDNN seam
    CudnnSubsamplingHelper). Lowered to ``lax.reduce_window``."""
    pooling_type: str = "max"       # 'max' | 'avg' | 'pnorm' | 'sum'
    kernel_size: Tuple[int, int] = (2, 2)
    stride: Tuple[int, int] = (2, 2)
    padding: Tuple[int, int] = (0, 0)
    convolution_mode: str = "truncate"
    pnorm: int = 2
    # avg divisor at padded edges: True = kernel size (reference/dl4j
    # semantics), False = only real positions (Keras/TF semantics — set by
    # the Keras importer so imported AveragePooling matches Keras output)
    avg_count_includes_padding: bool = True

    def __post_init__(self):
        self.kernel_size = as_pair(self.kernel_size)
        self.stride = as_pair(self.stride)
        self.padding = as_pair(self.padding)

    def has_params(self):
        return False

    def output_type(self, input_type):
        h = conv_output_size(input_type.height, self.kernel_size[0], self.stride[0],
                             self.padding[0], 1, self.convolution_mode)
        w = conv_output_size(input_type.width, self.kernel_size[1], self.stride[1],
                             self.padding[1], 1, self.convolution_mode)
        return InputType.convolutional(h, w, input_type.channels)

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        kh, kw = self.kernel_size
        sh, sw = self.stride
        dims = (1, kh, kw, 1)
        strides = (1, sh, sw, 1)
        if self.convolution_mode == "same":
            pad = "SAME"
        else:
            ph, pw = self.padding
            pad = ((0, 0), (ph, ph), (pw, pw), (0, 0))
        if self.pooling_type == "max":
            y = lax.reduce_window(x, -jnp.inf, lax.max, dims, strides, pad)
        elif self.pooling_type in ("avg", "sum"):
            y = lax.reduce_window(x, 0.0, lax.add, dims, strides, pad)
            if self.pooling_type == "avg":
                if self.avg_count_includes_padding:
                    y = y / (kh * kw)
                else:
                    ones = jnp.ones_like(x)
                    cnt = lax.reduce_window(ones, 0.0, lax.add, dims,
                                            strides, pad)
                    y = y / cnt
        elif self.pooling_type == "pnorm":
            p = float(self.pnorm)
            y = lax.reduce_window(jnp.abs(x) ** p, 0.0, lax.add, dims, strides, pad)
            y = y ** (1.0 / p)
        else:
            raise ValueError(self.pooling_type)
        return y, state


@register_layer
@dataclass
class Subsampling1DLayer(Layer):
    pooling_type: str = "max"
    kernel_size: int = 2
    stride: int = 2
    padding: int = 0
    convolution_mode: str = "truncate"
    avg_count_includes_padding: bool = True   # False = Keras/TF semantics

    def has_params(self):
        return False

    def output_type(self, input_type):
        t = input_type.timeseries_length
        if t > 0:
            t = conv_output_size(t, self.kernel_size, self.stride, self.padding,
                                 1, self.convolution_mode)
        return InputType.recurrent(input_type.size, t)

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        dims, strides = (1, self.kernel_size, 1), (1, self.stride, 1)
        pad = "SAME" if self.convolution_mode == "same" else \
            ((0, 0), (self.padding, self.padding), (0, 0))
        if self.pooling_type == "max":
            y = lax.reduce_window(x, -jnp.inf, lax.max, dims, strides, pad)
        else:
            y = lax.reduce_window(x, 0.0, lax.add, dims, strides, pad)
            if self.pooling_type == "avg":
                if self.avg_count_includes_padding:
                    y = y / self.kernel_size
                else:
                    cnt = lax.reduce_window(jnp.ones_like(x), 0.0, lax.add,
                                            dims, strides, pad)
                    y = y / cnt
        return y, state


@register_layer
@dataclass
class Upsampling2D(Layer):
    size: Tuple[int, int] = (2, 2)

    def __post_init__(self):
        self.size = as_pair(self.size)

    def has_params(self):
        return False

    def output_type(self, input_type):
        return InputType.convolutional(input_type.height * self.size[0],
                                       input_type.width * self.size[1],
                                       input_type.channels)

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        y = jnp.repeat(jnp.repeat(x, self.size[0], axis=1), self.size[1], axis=2)
        return y, state


@register_layer
@dataclass
class Upsampling1D(Layer):
    size: int = 2

    def has_params(self):
        return False

    def output_type(self, input_type):
        t = input_type.timeseries_length
        return InputType.recurrent(input_type.size, t * self.size if t > 0 else t)

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        return jnp.repeat(x, self.size, axis=1), state


@register_layer
@dataclass
class ZeroPaddingLayer(Layer):
    padding: Tuple[int, int, int, int] = (0, 0, 0, 0)  # top,bottom,left,right

    def __post_init__(self):
        p = self.padding
        if isinstance(p, int):
            p = (p, p, p, p)
        elif len(p) == 2:
            p = (p[0], p[0], p[1], p[1])
        self.padding = tuple(p)

    def has_params(self):
        return False

    def output_type(self, input_type):
        t, b, l, r = self.padding
        return InputType.convolutional(input_type.height + t + b,
                                       input_type.width + l + r,
                                       input_type.channels)

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        t, b, l, r = self.padding
        return jnp.pad(x, ((0, 0), (t, b), (l, r), (0, 0))), state


@register_layer
@dataclass
class ZeroPadding1DLayer(Layer):
    padding: Tuple[int, int] = (0, 0)

    def __post_init__(self):
        self.padding = as_pair(self.padding)

    def has_params(self):
        return False

    def output_type(self, input_type):
        t = input_type.timeseries_length
        extra = self.padding[0] + self.padding[1]
        return InputType.recurrent(input_type.size, t + extra if t > 0 else t)

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        l, r = self.padding
        return jnp.pad(x, ((0, 0), (l, r), (0, 0))), state


@register_layer
@dataclass
class Cropping2D(Layer):
    cropping: Tuple[int, int, int, int] = (0, 0, 0, 0)

    def __post_init__(self):
        c = self.cropping
        if isinstance(c, int):
            c = (c, c, c, c)
        elif len(c) == 2:
            c = (c[0], c[0], c[1], c[1])
        self.cropping = tuple(c)

    def has_params(self):
        return False

    def output_type(self, input_type):
        t, b, l, r = self.cropping
        return InputType.convolutional(input_type.height - t - b,
                                       input_type.width - l - r,
                                       input_type.channels)

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        t, b, l, r = self.cropping
        H, W = x.shape[1], x.shape[2]
        return x[:, t:H - b if b else H, l:W - r if r else W, :], state


def _bn_train_fwd(x, gamma, beta, eps):
    acc = jnp.promote_types(x.dtype, jnp.float32)
    axes = tuple(range(x.ndim - 1))
    n = x.size // x.shape[-1]
    xf = x.astype(acc)
    mean = xf.sum(axis=axes) / n
    if acc == x.dtype:
        # sums no wider than the activations: a second pass, about the
        # mean, or mean^2 would cancel the variance's low bits away
        var = jnp.square(xf - mean).sum(axis=axes) / n
    else:
        # half-precision activations, float32 sums: both sums from one read
        # of x, neither waiting for the other, so XLA makes them one
        # multi-output reduction, in the epilogue of the convolution whose
        # output x is. What cancels is far under x's own rounding
        var = jnp.maximum((xf * xf).sum(axis=axes) / n - mean * mean, 0)
    # named for selective rematerialization: a few KB a layer that save the
    # replay a reduction over the whole activation
    mean = checkpoint_name(mean, "bn_stats")
    rstd = checkpoint_name(lax.rsqrt(var + eps), "bn_stats")
    scale = rstd if gamma is None else rstd * gamma.astype(acc)
    y = (xf - mean) * scale
    if beta is not None:
        y = y + beta.astype(acc)
    return (y.astype(x.dtype), mean, var), (x, gamma, mean, rstd)


def _bn_train_bwd(eps, res, cts):
    x, gamma, mean, rstd = res
    acc = mean.dtype
    axes = tuple(range(x.ndim - 1))
    n = x.size // x.shape[-1]
    dy = cts[0].astype(acc)
    xhat = (x.astype(acc) - mean) * rstd
    # one read of dy and x for both sums, one elementwise pass for dx
    db = dy.sum(axis=axes)
    dg = (dy * xhat).sum(axis=axes)
    scale = rstd if gamma is None else rstd * gamma.astype(acc)
    dx = (scale * (dy - db / n - xhat * (dg / n))).astype(x.dtype)
    if gamma is None:
        return dx, None, None
    return dx, dg.astype(gamma.dtype), db.astype(gamma.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def batch_norm_train(x, gamma, beta, eps):
    """Train-mode batch normalisation over every axis but the last as one
    op with its own backward (parity: CudnnBatchNormalizationHelper, which
    keeps the batch mean and inverse deviation of the forward pass for the
    backward one). Returns ``(y, mean, var)``: ``y`` in ``x.dtype``, the
    statistics in ``max(float32, x.dtype)``, ``var`` biased. ``gamma`` and
    ``beta`` are of one dtype, or both None (locked at 1 and 0). ``mean``
    and ``var`` are for the running statistics and carry no gradient, as
    the reference updates globalMean/globalVar outside backprop."""
    return _bn_train_fwd(x, gamma, beta, eps)[0]


batch_norm_train.defvjp(_bn_train_fwd, _bn_train_bwd)


@register_layer
@dataclass
class BatchNormalization(Layer):
    """Batch norm with running stats carried as functional state
    (parity: nn/conf/layers/BatchNormalization.java + cuDNN seam
    CudnnBatchNormalizationHelper = ``batch_norm_train`` above; running
    stats = the reference's globalMean/globalVar params, here non-trainable
    state updated in the train step and returned — no mutation)."""
    n_in: int = 0
    decay: float = 0.9
    eps: float = 1e-5
    lock_gamma_beta: bool = False

    def set_n_in(self, input_type):
        if self.n_in == 0:
            self.n_in = input_type.channels if input_type.kind == "cnn" \
                else input_type.flat_size() if input_type.kind != "rnn" \
                else input_type.size

    def init(self, rng, dtype=jnp.float32):
        require_dims(self, n_in=self.n_in)
        if self.lock_gamma_beta:
            return {}
        return {"gamma": jnp.ones((self.n_in,), dtype),
                "beta": jnp.zeros((self.n_in,), dtype)}

    def init_state(self, dtype=jnp.float32):
        return {"mean": jnp.zeros((self.n_in,), dtype),
                "var": jnp.ones((self.n_in,), dtype)}

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        act = get_activation(self.activation or "identity")
        if train:
            xn, mean, var = batch_norm_train(
                x, params.get("gamma"), params.get("beta"), self.eps)
            return act(xn), {
                "mean": self.decay * state["mean"] + (1 - self.decay) * mean,
                "var": self.decay * state["var"] + (1 - self.decay) * var,
            }
        # running stats are stored f32 (dtype-stable state contract);
        # cast to the activation dtype or a bf16 forward would promote
        # to f32 and crash the next conv on mixed dtypes
        mean = state["mean"].astype(x.dtype)
        var = state["var"].astype(x.dtype)
        xn = (x - mean) * lax.rsqrt(var + self.eps)
        if not self.lock_gamma_beta:
            xn = xn * params["gamma"] + params["beta"]
        return act(xn), state


@register_layer
@dataclass
class LocalResponseNormalization(Layer):
    """LRN across channels (parity: nn/conf/layers/
    LocalResponseNormalization.java; cuDNN seam CudnnLocalResponseNormalizationHelper).
    Implemented as an avg-pool over the channel axis — one fused XLA window op."""
    k: float = 2.0
    alpha: float = 1e-4
    beta: float = 0.75
    n: int = 5

    def has_params(self):
        return False

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        half = self.n // 2
        sq = x ** 2
        win = lax.reduce_window(sq, 0.0, lax.add, (1, 1, 1, self.n), (1, 1, 1, 1),
                                ((0, 0), (0, 0), (0, 0), (half, half)))
        denom = (self.k + self.alpha * win) ** self.beta
        return x / denom, state


@register_layer
@dataclass
class SpaceToDepthLayer(Layer):
    block_size: int = 2

    def has_params(self):
        return False

    def output_type(self, input_type):
        b = self.block_size
        return InputType.convolutional(input_type.height // b, input_type.width // b,
                                       input_type.channels * b * b)

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        B, H, W, C = x.shape
        b = self.block_size
        y = x.reshape(B, H // b, b, W // b, b, C)
        y = y.transpose(0, 1, 3, 2, 4, 5).reshape(B, H // b, W // b, b * b * C)
        return y, state


@register_layer
@dataclass
class SpaceToBatchLayer(Layer):
    block_size: Tuple[int, int] = (2, 2)
    padding: Tuple[int, int] = (0, 0)

    def __post_init__(self):
        self.block_size = as_pair(self.block_size)

    def has_params(self):
        return False

    def output_type(self, input_type):
        bh, bw = self.block_size
        return InputType.convolutional(input_type.height // bh,
                                       input_type.width // bw, input_type.channels)

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        B, H, W, C = x.shape
        bh, bw = self.block_size
        y = x.reshape(B, H // bh, bh, W // bw, bw, C)
        y = y.transpose(2, 4, 0, 1, 3, 5).reshape(B * bh * bw, H // bh, W // bw, C)
        return y, state
