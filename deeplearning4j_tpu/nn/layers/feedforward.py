"""Feed-forward layer family.

Parity targets (config semantics, not code):
- DenseLayer        <- DL4J nn/conf/layers/DenseLayer.java + nn/layers/feedforward/dense/
- EmbeddingLayer    <- nn/conf/layers/EmbeddingLayer.java (one-hot index -> row lookup)
- ActivationLayer   <- nn/conf/layers/ActivationLayer.java
- DropoutLayer      <- nn/conf/layers/DropoutLayer.java
- OutputLayer       <- nn/conf/layers/OutputLayer.java (dense + loss head)
- LossLayer         <- nn/conf/layers/LossLayer.java (loss head, no params)
- AutoEncoder       <- nn/conf/layers/AutoEncoder.java (denoising AE pretrain layer)

All matmuls are (B, in) @ (in, out) — MXU-shaped; dtype follows the network's
compute dtype (bf16 on TPU by default, fp32 for parity runs).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.activations import get_activation
from deeplearning4j_tpu.nn.conf.base import InputType, Kind, LayerConf, register_layer
from deeplearning4j_tpu.nn.initializers import get_initializer
from deeplearning4j_tpu.nn.losses import get_loss


@register_layer
@dataclasses.dataclass(frozen=True)
class DenseLayer(LayerConf):
    n_out: int = 0
    n_in: Optional[int] = None          # inferred from input when None
    activation: str = "identity"
    weight_init: str = "xavier"
    bias_init: float = 0.0
    has_bias: bool = True

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.feed_forward(self.n_out)

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        n_in = self.n_in or input_type.features
        w_init = get_initializer(self.weight_init)
        params = {"W": w_init(key, (n_in, self.n_out), n_in, self.n_out, dtype)}
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return params, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        with jax.named_scope("dense"):
            x = self.maybe_dropout_input(x, train, rng)
            y = x @ params["W"]
            if self.has_bias:
                y = y + params["b"]
            return get_activation(self.activation)(y), state


@register_layer
@dataclasses.dataclass(frozen=True)
class ElementWiseMultiplicationLayer(LayerConf):
    """out = activation(x * w + b) with a learnable per-feature weight
    vector w and bias b; input and output size are equal
    (DL4J nn/conf/layers/misc/ElementWiseMultiplicationLayer.java, impl
    nn/layers/feedforward/elementwise/ElementWiseMultiplicationLayer.java,
    params ElementWiseParamInitializer — W is a length-nOut vector)."""
    n_out: int = 0                      # == n_in; inferred when 0
    n_in: Optional[int] = None
    activation: str = "identity"
    weight_init: str = "xavier"
    bias_init: float = 0.0

    def output_type(self, input_type: InputType) -> InputType:
        n = self.n_out or input_type.features
        if self.n_in and self.n_in != n:
            raise ValueError("ElementWiseMultiplicationLayer requires "
                             f"n_in == n_out, got {self.n_in} vs {n}")
        return InputType.feed_forward(n)

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        n = self.n_out or input_type.features
        if input_type.features != n:
            raise ValueError("ElementWiseMultiplicationLayer requires "
                             f"n_in == n_out, got {input_type.features} "
                             f"vs {n}")
        w_init = get_initializer(self.weight_init)
        params = {"W": w_init(key, (n,), n, n, dtype),
                  "b": jnp.full((n,), self.bias_init, dtype)}
        return params, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        return get_activation(self.activation)(
            x * params["W"] + params["b"]), state


@register_layer
@dataclasses.dataclass(frozen=True)
class EmbeddingLayer(LayerConf):
    """Index -> embedding row. Input: (B,) or (B,1) integer indices.
    DL4J's EmbeddingLayer is mathematically a one-hot matmul; on TPU we use a
    gather (jnp.take) which XLA lowers to a dynamic-slice — no dense one-hot."""
    n_out: int = 0
    n_in: Optional[int] = None          # vocab size; must be set or inferred
    activation: str = "identity"
    weight_init: str = "xavier"
    has_bias: bool = False

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.feed_forward(self.n_out)

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        n_in = self.n_in or input_type.features
        w_init = get_initializer(self.weight_init)
        params = {"W": w_init(key, (n_in, self.n_out), n_in, self.n_out, dtype)}
        if self.has_bias:
            params["b"] = jnp.zeros((self.n_out,), dtype)
        return params, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        with jax.named_scope("embed"):
            idx = x.astype(jnp.int32)
            if idx.ndim == 2 and idx.shape[-1] == 1:
                idx = idx[..., 0]
            y = jnp.take(params["W"], idx, axis=0)
            if self.has_bias:
                y = y + params["b"]
            return get_activation(self.activation)(y), state


@register_layer
@dataclasses.dataclass(frozen=True)
class ActivationLayer(LayerConf):
    """Standalone activation (DL4J ActivationLayer). `alpha` parameterizes
    leaky/elu-style activations (DL4J ActivationLReLU alpha, default 0.01)."""
    activation: str = "relu"
    alpha: Optional[float] = None

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def has_params(self):
        return False

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        with jax.named_scope("act"):
            fn = get_activation(self.activation)
            if self.alpha is not None:
                return fn(x, self.alpha), state
            return fn(x), state


@register_layer
@dataclasses.dataclass(frozen=True)
class DropoutLayer(LayerConf):
    """Standalone dropout layer (DL4J DropoutLayer). `dropout` is the drop
    probability; inverted scaling at train time, identity at inference."""
    dropout: float = 0.5

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def has_params(self):
        return False

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return self.maybe_dropout_input(x, train, rng), state


@register_layer
@dataclasses.dataclass(frozen=True)
class OutputLayer(LayerConf):
    """Dense + loss head (DL4J OutputLayer: BaseOutputLayer.computeScore).

    `apply` returns post-activation predictions; `score` computes the loss on
    pre-activation output — autodiff differentiates through both."""
    n_out: int = 0
    n_in: Optional[int] = None
    activation: str = "softmax"
    loss: str = "mcxent"
    weight_init: str = "xavier"
    bias_init: float = 0.0
    has_bias: bool = True

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.feed_forward(self.n_out)

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        n_in = self.n_in or input_type.features
        w_init = get_initializer(self.weight_init)
        params = {"W": w_init(key, (n_in, self.n_out), n_in, self.n_out, dtype)}
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return params, {}

    def preout(self, params, x, train=False, rng=None):
        x = self.maybe_dropout_input(x, train, rng)
        y = x @ params["W"]
        if self.has_bias:
            y = y + params["b"]
        return y

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        with jax.named_scope("dense"):
            return get_activation(self.activation)(self.preout(params, x, train, rng)), state

    def score(self, params, x, labels, *, train=False, rng=None, mask=None):
        with jax.named_scope("head/loss"):
            z = self.preout(params, x, train, rng)
            return get_loss(self.loss)(labels, z, self.activation, mask=mask)


@register_layer
@dataclasses.dataclass(frozen=True)
class CenterLossOutputLayer(LayerConf):
    """Softmax head + center loss (DL4J nn/layers/training/
    CenterLossOutputLayer.java): loss = primary + lambda/2 * ||f - c_y||^2,
    pulling each class's features toward a learned per-class center.

    Design deviation, documented: DL4J updates centers by a non-gradient
    EMA c_y <- (1-alpha) c_y + alpha f. Here centers are ordinary params —
    the gradient of the center term w.r.t. c_y is lambda*(c_y - f), so SGD
    performs the same pull with alpha = lr * lambda (DL4J's own
    gradientCheck mode treats centers exactly this way)."""
    n_out: int = 0
    n_in: Optional[int] = None
    activation: str = "softmax"
    loss: str = "mcxent"
    alpha: float = 0.05             # kept for DL4J config parity
    lambda_: float = 2e-4           # center-loss weight (DL4J lambda)
    weight_init: str = "xavier"
    bias_init: float = 0.0
    has_bias: bool = True

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.feed_forward(self.n_out)

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        n_in = self.n_in or input_type.features
        w_init = get_initializer(self.weight_init)
        params = {"W": w_init(key, (n_in, self.n_out), n_in, self.n_out,
                              dtype),
                  "cL": jnp.zeros((self.n_out, n_in), dtype)}   # class centers
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return params, {}

    def preout(self, params, x, train=False, rng=None):
        x = self.maybe_dropout_input(x, train, rng)
        y = x @ params["W"]
        if self.has_bias:
            y = y + params["b"]
        return y

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return get_activation(self.activation)(
            self.preout(params, x, train, rng)), state

    def score(self, params, x, labels, *, train=False, rng=None, mask=None):
        z = self.preout(params, x, train, rng)
        primary = get_loss(self.loss)(labels, z, self.activation, mask=mask)
        c_y = labels @ params["cL"]                  # (B, n_in) via one-hot
        center = 0.5 * self.lambda_ * jnp.mean(
            jnp.sum((x - c_y) ** 2, axis=-1))
        return primary + center


@register_layer
@dataclasses.dataclass(frozen=True)
class LossLayer(LayerConf):
    """Parameter-free loss head (DL4J LossLayer)."""
    activation: str = "identity"
    loss: str = "mse"

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def has_params(self):
        return False

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return get_activation(self.activation)(x), state

    def score(self, params, x, labels, *, train=False, rng=None, mask=None):
        return get_loss(self.loss)(labels, x, self.activation, mask=mask)


@register_layer
@dataclasses.dataclass(frozen=True)
class AutoEncoder(LayerConf):
    """Denoising autoencoder pretrain layer (DL4J nn/conf/layers/AutoEncoder.java,
    impl nn/layers/feedforward/autoencoder/AutoEncoder.java).

    Forward (as a stacked layer) = encoder only. `pretrain_score` corrupts the
    input, encodes, decodes with tied-shape decoder params and scores the
    reconstruction — used by the layerwise-pretraining path
    (MultiLayerNetwork.fit pretrain branch, MultiLayerNetwork.java:1344-1346).
    """
    n_out: int = 0
    n_in: Optional[int] = None
    activation: str = "sigmoid"
    loss: str = "mse"
    corruption_level: float = 0.3
    weight_init: str = "xavier"

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.feed_forward(self.n_out)

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        n_in = self.n_in or input_type.features
        k1, k2 = jax.random.split(key)
        w_init = get_initializer(self.weight_init)
        params = {
            "W": w_init(k1, (n_in, self.n_out), n_in, self.n_out, dtype),
            "b": jnp.zeros((self.n_out,), dtype),
            # decoder bias; decoder weight is tied (W^T), as in DL4J
            "vb": jnp.zeros((n_in,), dtype),
        }
        return params, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        act = get_activation(self.activation)
        return act(x @ params["W"] + params["b"]), state

    def pretrain_score(self, params, x, rng):
        act = get_activation(self.activation)
        if self.corruption_level > 0 and rng is not None:
            keep = jax.random.bernoulli(rng, 1.0 - self.corruption_level, x.shape)
            x_in = jnp.where(keep, x, 0.0)
        else:
            x_in = x
        h = act(x_in @ params["W"] + params["b"])
        recon_pre = h @ params["W"].T + params["vb"]
        return get_loss(self.loss)(x, recon_pre, self.activation)


@register_layer
@dataclasses.dataclass(frozen=True)
class RepeatVector(LayerConf):
    """Repeat a (B, C) vector n times into a (B, n, C) sequence (DL4J
    nn/conf/layers/misc/RepeatVector.java; Keras RepeatVector)."""
    n: int = 1

    def output_type(self, input_type: InputType) -> InputType:
        return InputType(Kind.RNN, (int(self.n), input_type.shape[0]))

    def has_params(self):
        return False

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return jnp.repeat(x[:, None, :], int(self.n), axis=1), state


@register_layer
@dataclasses.dataclass(frozen=True)
class PermuteLayer(LayerConf):
    """Permute the non-batch axes (the layer form of DL4J's
    keras/preprocessors/PermutePreprocessor.java; Keras Permute). `dims`
    uses Keras' 1-indexed convention: Permute((2, 1)) swaps the first two
    non-batch axes."""
    dims: Tuple[int, ...] = (1,)

    def output_type(self, input_type: InputType) -> InputType:
        shape = tuple(input_type.shape[d - 1] for d in self.dims)
        if len(shape) == len(input_type.shape):
            return InputType(input_type.kind, shape)
        return input_type

    def has_params(self):
        return False

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        perm = (0,) + tuple(int(d) for d in self.dims)
        return jnp.transpose(x, perm), state


@register_layer
@dataclasses.dataclass(frozen=True)
class ReshapeLayer(LayerConf):
    """Reshape the non-batch axes (the layer form of DL4J's
    ReshapePreprocessor, used by modelimport KerasReshape.java; Keras
    Reshape). target: non-batch shape; kind is inferred from its rank
    (1 -> FF, 2 -> (T, C) sequence, 3 -> (H, W, C) image)."""
    target: Tuple[int, ...] = ()    # one dim may be -1 (inferred, as Keras)

    def _resolve(self, in_shape) -> Tuple[int, ...]:
        import numpy as _np
        total = int(_np.prod(in_shape))
        tgt = [int(d) for d in self.target]
        if tgt.count(-1) > 1:
            raise ValueError(f"Reshape: at most one -1 in {self.target}")
        if -1 in tgt:
            rest = int(_np.prod([d for d in tgt if d != -1]))
            if rest <= 0 or total % rest:
                raise ValueError(
                    f"Reshape: cannot infer -1 reshaping {in_shape} "
                    f"into {self.target}")
            tgt[tgt.index(-1)] = total // rest
        if int(_np.prod(tgt)) != total:
            raise ValueError(
                f"Reshape: cannot reshape {tuple(in_shape)} (size {total}) "
                f"into {self.target}")
        return tuple(tgt)

    def output_type(self, input_type: InputType) -> InputType:
        shape = self._resolve(input_type.shape)
        kind = {1: Kind.FF, 2: Kind.RNN, 3: Kind.CNN}.get(len(shape))
        if kind is None:
            raise ValueError(f"Reshape: unsupported rank {len(shape)}")
        return InputType(kind, shape)

    def has_params(self):
        return False

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return x.reshape((x.shape[0],) + self._resolve(x.shape[1:])), state
