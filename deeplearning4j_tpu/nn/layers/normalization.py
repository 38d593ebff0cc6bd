"""Normalization layers.

- BatchNormalization <- DL4J nn/conf/layers/BatchNormalization.java; impl
  nn/layers/normalization/BatchNormalization.java (cuDNN helper
  CudnnBatchNormalizationHelper). Running statistics live in the layer
  *state* pytree (the analog of DL4J's global mean/var params updated with
  `decay`). What a training step costs in passes over the activation, as
  XLA:TPU compiles it (PERF.md, PR 25): forward ONE read for both batch
  statistics (`_batch_moments`; a pass of its own, because its shift is a
  sample of the producer's output and so cannot ride in the producer's
  fusion), the normalize+scale+shift chain fused into the consumer (the
  next convolution reads `x` and the two vectors);
  backward the sums over `dy` and `x` as siblings on one level, fused with
  their neighbours, and a `dx` that waits on those vectors alone. No
  reduction over the activation waits on another of its direction.
  Inference reads the running state and reduces nothing.
- LocalResponseNormalization <- nn/conf/layers/LocalResponseNormalization.java
  (cuDNN helper CudnnLocalResponseNormalizationHelper) — AlexNet-era
  cross-channel LRN.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.conf.base import InputType, LayerConf, register_layer


# jitted as `jnp.mean` and `jnp.var` are: a network's batch norms then share
# one traced and lowered body a shape. Inline, the 53 of ResNet-50 made the
# step's lowering 20 % longer (3.4 s of the benchmark's set-up, PR 25).
@functools.partial(jax.jit, static_argnames=("axes", "stat_t"))
def _batch_moments(x, axes, stat_t):
    """Per-channel mean and biased variance from ONE traversal of `x`.

    `sum(x - k)` and `sum((x - k)**2)`, accumulated in `stat_t`, are sibling
    reductions over the same operand, and the gradient of both reaches `x`
    through per-channel vectors alone, so in neither direction does a
    reduction over the activation wait on another. `k` is the batch's first
    position: a sample of the data costs no pass and keeps
    `E[d*d] - E[d]**2` from cancelling when |mean| >> std (the running mean
    would not: it is zero at step one). The variance does not depend on `k`,
    so stopping its gradient is exact.
    """
    # sliced BEFORE the conversion: sliced after it, XLA:TPU has the
    # producing convolution write a `stat_t` copy of the whole activation
    k = lax.stop_gradient(x[(0,) * len(axes)].astype(stat_t))
    d = x.astype(stat_t) - k
    n = math.prod(x.shape[a] for a in axes)
    m1 = jnp.sum(d, axis=axes) / n
    m2 = jnp.sum(d * d, axis=axes) / n
    return k + m1, jnp.maximum(m2 - m1 * m1, 0.0)


@register_layer
@dataclasses.dataclass(frozen=True)
class BatchNormalization(LayerConf):
    epsilon: float = 1e-5
    decay: float = 0.9          # running-stat EMA decay (DL4J `decay`)
    gamma_init: float = 1.0
    beta_init: float = 0.0
    lock_gamma_beta: bool = False   # DL4J lockGammaBeta: fixed scale/shift

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        c = input_type.features
        params = {}
        if not self.lock_gamma_beta:
            params = {"gamma": jnp.full((c,), self.gamma_init, dtype),
                      "beta": jnp.full((c,), self.beta_init, dtype)}
        state = {"mean": jnp.zeros((c,), jnp.float32),
                 "var": jnp.ones((c,), jnp.float32)}
        return params, state

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        with jax.named_scope("bn"):
            axes = tuple(range(x.ndim - 1))    # all but channel/feature dim
            stat_t = jnp.promote_types(jnp.float32, x.dtype)
            if train:
                mean, var = _batch_moments(x, axes, stat_t)
                new_state = {
                    "mean": self.decay * state["mean"] + (1.0 - self.decay) * mean,
                    "var": self.decay * state["var"] + (1.0 - self.decay) * var,
                }
            else:
                mean, var = state["mean"], state["var"]
                new_state = state
            inv = lax.rsqrt(var + self.epsilon)
            y = (x - mean.astype(x.dtype)) * inv.astype(x.dtype)
            if not self.lock_gamma_beta:
                y = y * params["gamma"] + params["beta"]
            else:
                y = y * self.gamma_init + self.beta_init
            return y, new_state


@register_layer
@dataclasses.dataclass(frozen=True)
class LocalResponseNormalization(LayerConf):
    """Cross-channel LRN: y = x / (k + alpha*sum(x^2 over n channels))^beta."""
    k: float = 2.0
    n: int = 5
    alpha: float = 1e-4
    beta: float = 0.75

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def has_params(self):
        return False

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        sq = x * x
        half = self.n // 2
        # sum over a window of `n` adjacent channels (NHWC last axis)
        summed = lax.reduce_window(
            sq, 0.0, lax.add,
            window_dimensions=(1, 1, 1, self.n),
            window_strides=(1, 1, 1, 1),
            padding=((0, 0), (0, 0), (0, 0), (half, self.n - 1 - half)),
        )
        return x / (self.k + self.alpha * summed) ** self.beta, state
