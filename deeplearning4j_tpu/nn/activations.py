"""Activation functions.

Capability parity with DL4J's IActivation implementations (consumed from
nd4j-api; enumerated in deeplearning4j-nn layer configs via `Activation`).
Here each activation is a pure jnp function resolved by name through a
registry — XLA fuses these into adjacent matmuls, so there is no per-activation
kernel object like DL4J's IActivation classes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_EPS = 1e-12


def identity(x):
    return x


def relu(x):
    return jax.nn.relu(x)


def relu2(x):
    """``relu(x)^2`` (the Nemotron-H family's ``mlp_hidden_act``)."""
    return jnp.square(jax.nn.relu(x))


def relu6(x):
    return jnp.clip(x, 0.0, 6.0)


def leakyrelu(x, alpha: float = 0.01):
    return jnp.where(x >= 0, x, alpha * x)


def elu(x, alpha: float = 1.0):
    return jax.nn.elu(x, alpha)


def selu(x):
    return jax.nn.selu(x)


def gelu(x):
    return jax.nn.gelu(x)


def sigmoid(x):
    return jax.nn.sigmoid(x)


def hardsigmoid(x):
    return jnp.clip(0.2 * x + 0.5, 0.0, 1.0)


def tanh_(x):
    return jnp.tanh(x)


def hardtanh(x):
    return jnp.clip(x, -1.0, 1.0)


def rationaltanh(x):
    # DL4J ActivationRationalTanh: 1.7159 * tanh_approx(2x/3)
    ax = jnp.abs(2.0 * x / 3.0)
    approx = jnp.sign(x) * (1.0 - 1.0 / (1.0 + ax + ax * ax + 1.41645 * ax**4))
    return 1.7159 * approx


def rectifiedtanh(x):
    return jnp.maximum(0.0, jnp.tanh(x))

def softmax(x):
    return jax.nn.softmax(x, axis=-1)


def softplus(x):
    return jax.nn.softplus(x)


def softsign(x):
    return jax.nn.soft_sign(x)


def swish(x):
    return jax.nn.swish(x)


def mish(x):
    return x * jnp.tanh(jax.nn.softplus(x))


def cube(x):
    return x * x * x


def thresholdedrelu(x, theta: float = 1.0):
    return jnp.where(x > theta, x, 0.0)


ACTIVATIONS = {
    "identity": identity,
    "linear": identity,
    "relu": relu,
    "relu2": relu2,
    "relu6": relu6,
    "leakyrelu": leakyrelu,
    "elu": elu,
    "selu": selu,
    "gelu": gelu,
    "sigmoid": sigmoid,
    "hardsigmoid": hardsigmoid,
    "tanh": tanh_,
    "hardtanh": hardtanh,
    "rationaltanh": rationaltanh,
    "rectifiedtanh": rectifiedtanh,
    "softmax": softmax,
    "softplus": softplus,
    "softsign": softsign,
    "swish": swish,
    "mish": mish,
    "cube": cube,
    "thresholdedrelu": thresholdedrelu,
}


def get_activation(name_or_fn):
    """Resolve an activation by name (case-insensitive) or pass through a callable."""
    if callable(name_or_fn):
        return name_or_fn
    key = str(name_or_fn).lower()
    if key not in ACTIVATIONS:
        raise ValueError(f"Unknown activation '{name_or_fn}'. Known: {sorted(ACTIVATIONS)}")
    return ACTIVATIONS[key]
