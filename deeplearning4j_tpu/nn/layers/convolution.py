"""Convolution layer family — NHWC / HWIO, lowered to XLA convolutions.

Parity targets (semantics, not code):
- ConvolutionLayer       <- DL4J nn/conf/layers/ConvolutionLayer.java; impl
  nn/layers/convolution/ConvolutionLayer.java (im2col+gemm at :208-224, cuDNN
  helper at :75-85). Here the conv IS one XLA op that tiles directly onto the
  MXU — no im2col materialization, no helper fallback needed.
- SubsamplingLayer       <- nn/conf/layers/SubsamplingLayer.java (MAX/AVG/PNORM)
- Upsampling2D, ZeroPaddingLayer, SpaceToDepth, SpaceToBatch, Cropping2D
- Deconvolution2D, SeparableConvolution2D, DepthwiseConvolution2D
- GlobalPoolingLayer     <- nn/conf/layers/GlobalPoolingLayer.java (MAX/AVG/SUM/PNORM,
  works on CNN and RNN input, mask-aware for RNN)
- CnnLossLayer           <- nn/conf/layers/CnnLossLayer.java

ConvolutionMode parity (nn/conf/ConvolutionMode.java): Same -> XLA SAME
padding; Truncate -> VALID (silently truncates); Strict -> VALID + static
shape check at config time.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.activations import get_activation
from deeplearning4j_tpu.nn.conf.base import InputType, Kind, LayerConf, register_layer
from deeplearning4j_tpu.nn.initializers import get_initializer
from deeplearning4j_tpu.nn.losses import get_loss


def _pair(v) -> Tuple[int, int]:
    if isinstance(v, (tuple, list)):
        return (int(v[0]), int(v[1]))
    return (int(v), int(v))


def _conv_out_dim(size, k, s, d, mode) -> int:
    eff_k = (k - 1) * d + 1
    if mode == "same":
        return -(-size // s)
    out = (size - eff_k) // s + 1
    if mode == "strict" and (size - eff_k) % s != 0:
        raise ValueError(
            f"ConvolutionMode.Strict: input size {size} with kernel {k}, "
            f"stride {s}, dilation {d} does not tile exactly")
    return out


def _padding(mode):
    return "SAME" if mode == "same" else "VALID"


@register_layer
@dataclasses.dataclass(frozen=True)
class ConvolutionLayer(LayerConf):
    n_out: int = 0                       # output channels
    kernel: Tuple[int, int] = (3, 3)
    stride: Tuple[int, int] = (1, 1)
    dilation: Tuple[int, int] = (1, 1)
    convolution_mode: str = "truncate"   # same | truncate | strict
    activation: str = "identity"
    weight_init: str = "relu"
    bias_init: float = 0.0
    has_bias: bool = True
    n_in: Optional[int] = None

    def output_type(self, input_type: InputType) -> InputType:
        h, w, c = input_type.shape
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride)
        dh, dw = _pair(self.dilation)
        oh = _conv_out_dim(h, kh, sh, dh, self.convolution_mode)
        ow = _conv_out_dim(w, kw, sw, dw, self.convolution_mode)
        return InputType.convolutional(oh, ow, self.n_out)

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        c_in = self.n_in or input_type.shape[2]
        kh, kw = _pair(self.kernel)
        fan_in = c_in * kh * kw
        fan_out = self.n_out * kh * kw
        w_init = get_initializer(self.weight_init)
        params = {"W": w_init(key, (kh, kw, c_in, self.n_out), fan_in, fan_out, dtype)}
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return params, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        with jax.named_scope("conv"):
            # No preferred_element_type here (or in the other conv variants):
            # JAX's conv transpose rule rejects the mixed-dtype cotangent it
            # produces under bf16 compute, and the TPU MXU accumulates bf16
            # convolutions in f32 regardless — the f32-accumulation invariant
            # holds without requesting it.
            x = self.maybe_dropout_input(x, train, rng)
            y = lax.conv_general_dilated(
                x, params["W"],
                window_strides=_pair(self.stride),
                padding=_padding(self.convolution_mode),
                rhs_dilation=_pair(self.dilation),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
            if self.has_bias:
                y = y + params["b"]
            return get_activation(self.activation)(y), state


@register_layer
@dataclasses.dataclass(frozen=True)
class Deconvolution2D(ConvolutionLayer):
    """Transposed convolution (DL4J nn/conf/layers/Deconvolution2D.java)."""

    def output_type(self, input_type: InputType) -> InputType:
        h, w, c = input_type.shape
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride)
        dh, dw = _pair(self.dilation)
        ekh, ekw = (kh - 1) * dh + 1, (kw - 1) * dw + 1
        if self.convolution_mode == "same":
            oh, ow = h * sh, w * sw
        else:
            oh, ow = (h - 1) * sh + ekh, (w - 1) * sw + ekw
        return InputType.convolutional(oh, ow, self.n_out)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        with jax.named_scope("conv"):
            x = self.maybe_dropout_input(x, train, rng)
            y = lax.conv_transpose(
                x, params["W"],
                strides=_pair(self.stride),
                padding=_padding(self.convolution_mode),
                rhs_dilation=_pair(self.dilation),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
            if self.has_bias:
                y = y + params["b"]
            return get_activation(self.activation)(y), state


@register_layer
@dataclasses.dataclass(frozen=True)
class DepthwiseConvolution2D(LayerConf):
    """Per-channel convolution (DL4J DepthwiseConvolution2D); XLA
    feature_group_count — TPU lowers this natively."""
    depth_multiplier: int = 1
    kernel: Tuple[int, int] = (3, 3)
    stride: Tuple[int, int] = (1, 1)
    dilation: Tuple[int, int] = (1, 1)
    convolution_mode: str = "truncate"
    activation: str = "identity"
    weight_init: str = "relu"
    has_bias: bool = True

    def output_type(self, input_type: InputType) -> InputType:
        h, w, c = input_type.shape
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride)
        dh, dw = _pair(self.dilation)
        oh = _conv_out_dim(h, kh, sh, dh, self.convolution_mode)
        ow = _conv_out_dim(w, kw, sw, dw, self.convolution_mode)
        return InputType.convolutional(oh, ow, c * self.depth_multiplier)

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        c_in = input_type.shape[2]
        kh, kw = _pair(self.kernel)
        w_init = get_initializer(self.weight_init)
        fan_in = kh * kw
        params = {"W": w_init(key, (kh, kw, 1, c_in * self.depth_multiplier),
                              fan_in, fan_in * self.depth_multiplier, dtype)}
        if self.has_bias:
            params["b"] = jnp.zeros((c_in * self.depth_multiplier,), dtype)
        return params, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        with jax.named_scope("conv"):
            x = self.maybe_dropout_input(x, train, rng)
            c_in = x.shape[-1]
            y = lax.conv_general_dilated(
                x, params["W"],
                window_strides=_pair(self.stride),
                padding=_padding(self.convolution_mode),
                rhs_dilation=_pair(self.dilation),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=c_in,
            )
            if self.has_bias:
                y = y + params["b"]
            return get_activation(self.activation)(y), state


@register_layer
@dataclasses.dataclass(frozen=True)
class SeparableConvolution2D(LayerConf):
    """Depthwise + pointwise (DL4J SeparableConvolution2D)."""
    n_out: int = 0
    depth_multiplier: int = 1
    kernel: Tuple[int, int] = (3, 3)
    stride: Tuple[int, int] = (1, 1)
    dilation: Tuple[int, int] = (1, 1)
    convolution_mode: str = "truncate"
    activation: str = "identity"
    weight_init: str = "relu"
    has_bias: bool = True

    def output_type(self, input_type: InputType) -> InputType:
        h, w, c = input_type.shape
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride)
        dh, dw = _pair(self.dilation)
        oh = _conv_out_dim(h, kh, sh, dh, self.convolution_mode)
        ow = _conv_out_dim(w, kw, sw, dw, self.convolution_mode)
        return InputType.convolutional(oh, ow, self.n_out)

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        c_in = input_type.shape[2]
        kh, kw = _pair(self.kernel)
        k1, k2 = jax.random.split(key)
        w_init = get_initializer(self.weight_init)
        mid = c_in * self.depth_multiplier
        params = {
            "dW": w_init(k1, (kh, kw, 1, mid), kh * kw, kh * kw, dtype),
            "pW": w_init(k2, (1, 1, mid, self.n_out), mid, self.n_out, dtype),
        }
        if self.has_bias:
            params["b"] = jnp.zeros((self.n_out,), dtype)
        return params, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        with jax.named_scope("conv"):
            x = self.maybe_dropout_input(x, train, rng)
            c_in = x.shape[-1]
            y = lax.conv_general_dilated(
                x, params["dW"], window_strides=_pair(self.stride),
                padding=_padding(self.convolution_mode),
                rhs_dilation=_pair(self.dilation),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=c_in,
            )
            y = lax.conv_general_dilated(
                y, params["pW"], window_strides=(1, 1), padding="VALID",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
            if self.has_bias:
                y = y + params["b"]
            return get_activation(self.activation)(y), state


@register_layer
@dataclasses.dataclass(frozen=True)
class SubsamplingLayer(LayerConf):
    """2D pooling (DL4J SubsamplingLayer; impl
    nn/layers/convolution/subsampling/SubsamplingLayer.java, cuDNN helper
    CudnnSubsamplingHelper). XLA reduce_window replaces both paths."""
    kernel: Tuple[int, int] = (2, 2)
    stride: Tuple[int, int] = (2, 2)
    pooling_type: str = "max"            # max | avg | sum | pnorm
    convolution_mode: str = "truncate"
    pnorm: int = 2

    def output_type(self, input_type: InputType) -> InputType:
        h, w, c = input_type.shape
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride)
        oh = _conv_out_dim(h, kh, sh, 1, self.convolution_mode)
        ow = _conv_out_dim(w, kw, sw, 1, self.convolution_mode)
        return InputType.convolutional(oh, ow, c)

    def has_params(self):
        return False

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        with jax.named_scope("pool"):
            kh, kw = _pair(self.kernel)
            sh, sw = _pair(self.stride)
            dims = (1, kh, kw, 1)
            strides = (1, sh, sw, 1)
            pad = _padding(self.convolution_mode)
            pt = self.pooling_type.lower()
            if pt == "max":
                y = lax.reduce_window(x, -jnp.inf, lax.max, dims, strides, pad)
            elif pt == "sum":
                y = lax.reduce_window(x, 0.0, lax.add, dims, strides, pad)
            elif pt == "avg":
                s = lax.reduce_window(x, 0.0, lax.add, dims, strides, pad)
                ones = jnp.ones_like(x)
                cnt = lax.reduce_window(ones, 0.0, lax.add, dims, strides, pad)
                y = s / cnt
            elif pt == "pnorm":
                p = float(self.pnorm)
                s = lax.reduce_window(jnp.abs(x) ** p, 0.0, lax.add, dims, strides, pad)
                y = s ** (1.0 / p)
            else:
                raise ValueError(f"Unknown pooling type {self.pooling_type}")
            return y, state


@register_layer
@dataclasses.dataclass(frozen=True)
class GlobalPoolingLayer(LayerConf):
    """Global pooling over spatial or time dims (DL4J GlobalPoolingLayer).
    Mask-aware for RNN input, mirroring MaskedReductionUtil."""
    pooling_type: str = "max"            # max | avg | sum | pnorm
    pnorm: int = 2
    collapse_dimensions: bool = True

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.feed_forward(input_type.features)

    def has_params(self):
        return False

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        with jax.named_scope("pool"):
            if x.ndim == 4:       # (B,H,W,C)
                axes = (1, 2)
            elif x.ndim == 3:     # (B,T,F)
                axes = (1,)
            else:
                raise ValueError(f"GlobalPooling expects 3d/4d input, got {x.shape}")
            pt = self.pooling_type.lower()
            if mask is not None and x.ndim == 3:
                m = mask[..., None].astype(x.dtype)
                if pt == "max":
                    y = jnp.max(jnp.where(m > 0, x, -jnp.inf), axis=1)
                elif pt == "sum":
                    y = jnp.sum(x * m, axis=1)
                elif pt == "avg":
                    y = jnp.sum(x * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1.0)
                elif pt == "pnorm":
                    p = float(self.pnorm)
                    y = jnp.sum((jnp.abs(x) * m) ** p, axis=1) ** (1.0 / p)
                else:
                    raise ValueError(self.pooling_type)
                return y, state
            if pt == "max":
                y = jnp.max(x, axis=axes)
            elif pt == "sum":
                y = jnp.sum(x, axis=axes)
            elif pt == "avg":
                y = jnp.mean(x, axis=axes)
            elif pt == "pnorm":
                p = float(self.pnorm)
                y = jnp.sum(jnp.abs(x) ** p, axis=axes) ** (1.0 / p)
            else:
                raise ValueError(self.pooling_type)
            return y, state


@register_layer
@dataclasses.dataclass(frozen=True)
class Upsampling2D(LayerConf):
    size: Tuple[int, int] = (2, 2)

    def output_type(self, input_type: InputType) -> InputType:
        h, w, c = input_type.shape
        sh, sw = _pair(self.size)
        return InputType.convolutional(h * sh, w * sw, c)

    def has_params(self):
        return False

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        sh, sw = _pair(self.size)
        return jnp.repeat(jnp.repeat(x, sh, axis=1), sw, axis=2), state


@register_layer
@dataclasses.dataclass(frozen=True)
class ZeroPaddingLayer(LayerConf):
    padding: Tuple[int, int, int, int] = (0, 0, 0, 0)   # top,bottom,left,right

    def output_type(self, input_type: InputType) -> InputType:
        h, w, c = input_type.shape
        t, b, l, r = self.padding
        return InputType.convolutional(h + t + b, w + l + r, c)

    def has_params(self):
        return False

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        with jax.named_scope("layout"):
            t, b, l, r = self.padding
            return jnp.pad(x, ((0, 0), (t, b), (l, r), (0, 0))), state


@register_layer
@dataclasses.dataclass(frozen=True)
class Cropping2D(LayerConf):
    cropping: Tuple[int, int, int, int] = (0, 0, 0, 0)  # top,bottom,left,right

    def output_type(self, input_type: InputType) -> InputType:
        h, w, c = input_type.shape
        t, b, l, r = self.cropping
        return InputType.convolutional(h - t - b, w - l - r, c)

    def has_params(self):
        return False

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        t, b, l, r = self.cropping
        h, w = x.shape[1], x.shape[2]
        return x[:, t:h - b if b else h, l:w - r if r else w, :], state


@register_layer
@dataclasses.dataclass(frozen=True)
class SpaceToDepthLayer(LayerConf):
    block_size: int = 2

    def output_type(self, input_type: InputType) -> InputType:
        h, w, c = input_type.shape
        bs = self.block_size
        return InputType.convolutional(h // bs, w // bs, c * bs * bs)

    def has_params(self):
        return False

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        with jax.named_scope("layout"):
            b, h, w, c = x.shape
            bs = self.block_size
            x = x.reshape(b, h // bs, bs, w // bs, bs, c)
            x = x.transpose(0, 1, 3, 2, 4, 5)
            return x.reshape(b, h // bs, w // bs, bs * bs * c), state


@register_layer
@dataclasses.dataclass(frozen=True)
class SpaceToBatchLayer(LayerConf):
    block_size: Tuple[int, int] = (2, 2)

    def output_type(self, input_type: InputType) -> InputType:
        h, w, c = input_type.shape
        bh, bw = _pair(self.block_size)
        return InputType.convolutional(h // bh, w // bw, c)

    def has_params(self):
        return False

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        b, h, w, c = x.shape
        bh, bw = _pair(self.block_size)
        x = x.reshape(b, h // bh, bh, w // bw, bw, c)
        x = x.transpose(2, 4, 0, 1, 3, 5)
        return x.reshape(b * bh * bw, h // bh, w // bw, c), state


@register_layer
@dataclasses.dataclass(frozen=True)
class Convolution1DLayer(LayerConf):
    """1D convolution over (B, T, C) (DL4J Convolution1DLayer)."""
    n_out: int = 0
    kernel: int = 3
    stride: int = 1
    dilation: int = 1
    convolution_mode: str = "same"
    activation: str = "identity"
    weight_init: str = "relu"
    has_bias: bool = True

    def output_type(self, input_type: InputType) -> InputType:
        t, c = input_type.shape
        ot = _conv_out_dim(t, self.kernel, self.stride, self.dilation,
                           self.convolution_mode)
        return InputType(Kind.RNN, (ot, self.n_out))

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        c_in = input_type.shape[1]
        fan_in = c_in * self.kernel
        w_init = get_initializer(self.weight_init)
        params = {"W": w_init(key, (self.kernel, c_in, self.n_out), fan_in,
                              self.n_out * self.kernel, dtype)}
        if self.has_bias:
            params["b"] = jnp.zeros((self.n_out,), dtype)
        return params, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        with jax.named_scope("conv"):
            x = self.maybe_dropout_input(x, train, rng)
            y = lax.conv_general_dilated(
                x, params["W"], window_strides=(self.stride,),
                padding=_padding(self.convolution_mode),
                rhs_dilation=(self.dilation,),
                dimension_numbers=("NWC", "WIO", "NWC"),
            )
            if self.has_bias:
                y = y + params["b"]
            return get_activation(self.activation)(y), state


@register_layer
@dataclasses.dataclass(frozen=True)
class Subsampling1DLayer(LayerConf):
    kernel: int = 2
    stride: int = 2
    pooling_type: str = "max"
    convolution_mode: str = "truncate"

    def output_type(self, input_type: InputType) -> InputType:
        t, c = input_type.shape
        ot = _conv_out_dim(t, self.kernel, self.stride, 1, self.convolution_mode)
        return InputType(Kind.RNN, (ot, c))

    def has_params(self):
        return False

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        with jax.named_scope("pool"):
            dims, strides = (1, self.kernel, 1), (1, self.stride, 1)
            pad = _padding(self.convolution_mode)
            if self.pooling_type == "max":
                y = lax.reduce_window(x, -jnp.inf, lax.max, dims, strides, pad)
            else:
                s = lax.reduce_window(x, 0.0, lax.add, dims, strides, pad)
                cnt = lax.reduce_window(jnp.ones_like(x), 0.0, lax.add, dims, strides, pad)
                y = s / cnt
            return y, state


@register_layer
@dataclasses.dataclass(frozen=True)
class CnnLossLayer(LayerConf):
    """Per-pixel loss head for dense prediction (DL4J CnnLossLayer)."""
    activation: str = "softmax"
    loss: str = "mcxent"

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def has_params(self):
        return False

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return get_activation(self.activation)(x), state

    def score(self, params, x, labels, *, train=False, rng=None, mask=None):
        b = x.shape[0]
        z = x.reshape(b, -1, x.shape[-1])
        lab = labels.reshape(b, -1, labels.shape[-1])
        loss_fn = get_loss(self.loss)
        per_pix_mask = None
        if mask is not None:
            per_pix_mask = mask.reshape(b, -1)
        return loss_fn(lab, z, self.activation, mask=per_pix_mask)


@register_layer
@dataclasses.dataclass(frozen=True)
class Cropping1D(LayerConf):
    """Crop timesteps off a (B, T, C) sequence (DL4J
    nn/conf/layers/convolutional/Cropping1D.java)."""
    cropping: Tuple[int, int] = (0, 0)      # (head, tail)

    def output_type(self, input_type: InputType) -> InputType:
        t, c = input_type.shape
        a, b = self.cropping
        return InputType(Kind.RNN, (t - a - b, c))

    def has_params(self):
        return False

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        a, b = self.cropping
        T = x.shape[1]
        return x[:, a:T - b if b else T, :], state


@register_layer
@dataclasses.dataclass(frozen=True)
class Upsampling1D(LayerConf):
    """Repeat each timestep `size` times (DL4J Upsampling1D.java)."""
    size: int = 2

    def output_type(self, input_type: InputType) -> InputType:
        t, c = input_type.shape
        return InputType(Kind.RNN, (t * int(self.size), c))

    def has_params(self):
        return False

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return jnp.repeat(x, int(self.size), axis=1), state


@register_layer
@dataclasses.dataclass(frozen=True)
class ZeroPadding1DLayer(LayerConf):
    """Zero-pad the time axis of a (B, T, C) sequence (DL4J
    ZeroPadding1DLayer.java)."""
    padding: Tuple[int, int] = (0, 0)       # (head, tail)

    def output_type(self, input_type: InputType) -> InputType:
        t, c = input_type.shape
        a, b = self.padding
        return InputType(Kind.RNN, (t + a + b, c))

    def has_params(self):
        return False

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        a, b = self.padding
        return jnp.pad(x, ((0, 0), (a, b), (0, 0))), state


@register_layer
@dataclasses.dataclass(frozen=True)
class LocallyConnected1D(LayerConf):
    """1D convolution with UNTIED weights — a distinct kernel per output
    position (DL4J nn/conf/layers/LocallyConnected1D.java, a SameDiff
    layer in the reference; here one einsum over extracted patches, which
    XLA maps onto the MXU as a batched matmul).

    W: (ot, k*c_in, n_out); b: (ot, n_out) — matching Keras
    LocallyConnected1D's storage so import is a verbatim copy."""
    n_out: int = 0
    kernel: int = 3
    stride: int = 1
    convolution_mode: str = "truncate"
    activation: str = "identity"
    weight_init: str = "xavier"
    has_bias: bool = True

    def _out_len(self, t: int) -> int:
        return _conv_out_dim(t, self.kernel, self.stride, 1,
                             "truncate" if self.convolution_mode != "strict"
                             else "strict")

    def output_type(self, input_type: InputType) -> InputType:
        t, c = input_type.shape
        return InputType(Kind.RNN, (self._out_len(t), self.n_out))

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        t, c = input_type.shape
        ot = self._out_len(t)
        fan_in = self.kernel * c
        w_init = get_initializer(self.weight_init)
        params = {"W": w_init(key, (ot, self.kernel * c, self.n_out),
                              fan_in, self.n_out, dtype)}
        if self.has_bias:
            params["b"] = jnp.zeros((ot, self.n_out), dtype)
        return params, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        ot = params["W"].shape[0]
        # patches[b, o, k*c] for output position o
        idx = (jnp.arange(ot)[:, None] * self.stride
               + jnp.arange(self.kernel)[None, :])        # (ot, k)
        patches = x[:, idx, :]                            # (B, ot, k, C)
        patches = patches.reshape(x.shape[0], ot, -1)     # (B, ot, k*C)
        y = jnp.einsum("bok,okn->bon", patches, params["W"])
        if self.has_bias:
            y = y + params["b"]
        return get_activation(self.activation)(y), state


@register_layer
@dataclasses.dataclass(frozen=True)
class LocallyConnected2D(LayerConf):
    """2D convolution with untied weights (DL4J LocallyConnected2D.java).
    W: (oh*ow, kh*kw*c_in, n_out); b: (oh, ow, n_out) — Keras
    LocallyConnected2D storage, verbatim import."""
    n_out: int = 0
    kernel: Tuple[int, int] = (3, 3)
    stride: Tuple[int, int] = (1, 1)
    convolution_mode: str = "truncate"
    activation: str = "identity"
    weight_init: str = "xavier"
    has_bias: bool = True

    def _out_hw(self, h: int, w: int) -> Tuple[int, int]:
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride)
        mode = "strict" if self.convolution_mode == "strict" else "truncate"
        return (_conv_out_dim(h, kh, sh, 1, mode),
                _conv_out_dim(w, kw, sw, 1, mode))

    def output_type(self, input_type: InputType) -> InputType:
        h, w, c = input_type.shape
        oh, ow = self._out_hw(h, w)
        return InputType.convolutional(oh, ow, self.n_out)

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        h, w, c = input_type.shape
        oh, ow = self._out_hw(h, w)
        kh, kw = _pair(self.kernel)
        fan_in = kh * kw * c
        w_init = get_initializer(self.weight_init)
        params = {"W": w_init(key, (oh * ow, fan_in, self.n_out),
                              fan_in, self.n_out, dtype)}
        if self.has_bias:
            params["b"] = jnp.zeros((oh, ow, self.n_out), dtype)
        return params, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        B, H, W, C = x.shape
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride)
        oh, ow = self._out_hw(H, W)
        iy = (jnp.arange(oh)[:, None] * sh
              + jnp.arange(kh)[None, :])                  # (oh, kh)
        ix = (jnp.arange(ow)[:, None] * sw
              + jnp.arange(kw)[None, :])                  # (ow, kw)
        # (B, oh, kh, ow, kw, C) -> (B, oh, ow, kh, kw, C)
        patches = x[:, iy[:, :, None, None], ix[None, None, :, :], :]
        patches = patches.transpose(0, 1, 3, 2, 4, 5).reshape(
            B, oh * ow, kh * kw * C)
        y = jnp.einsum("bok,okn->bon", patches, params["W"])
        y = y.reshape(B, oh, ow, self.n_out)
        if self.has_bias:
            y = y + params["b"]
        return get_activation(self.activation)(y), state
