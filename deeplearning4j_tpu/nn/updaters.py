"""Updaters (optimizers) and learning-rate schedules.

Capability parity with DL4J's IUpdater configs applied by
nn/updater/BaseMultiLayerUpdater.java:208-223 and the ISchedule family.
Realized as optax gradient transformations — the optimizer state is a pytree
(the analog of DL4J's flat updaterState view, ModelSerializer.java:109-125),
serialized alongside params in checkpoints.

Supports DL4J's per-layer updater overrides: `resolve_updater` builds one
transformation per layer via optax.multi_transform when layer configs override
the global updater (DL4J: Layer config `.updater(...)`).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import optax


# ---------------------------------------------------------------- schedules
@dataclasses.dataclass(frozen=True)
class Schedule:
    """Base LR schedule config (DL4J ISchedule). `to_optax()` yields an
    optax schedule fn: step -> lr."""

    def to_optax(self):
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class FixedSchedule(Schedule):
    value: float

    def to_optax(self):
        return optax.constant_schedule(self.value)


@dataclasses.dataclass(frozen=True)
class StepSchedule(Schedule):
    """DL4J StepSchedule: lr * decay^floor(iter/step)."""
    initial: float
    decay_rate: float
    step: int

    def to_optax(self):
        return lambda count: self.initial * (self.decay_rate ** (count // self.step))


@dataclasses.dataclass(frozen=True)
class ExponentialSchedule(Schedule):
    """DL4J ExponentialSchedule: lr * gamma^iter."""
    initial: float
    gamma: float

    def to_optax(self):
        return lambda count: self.initial * (self.gamma ** count)


@dataclasses.dataclass(frozen=True)
class InverseSchedule(Schedule):
    """DL4J InverseSchedule: lr / (1 + gamma*iter)^power."""
    initial: float
    gamma: float
    power: float = 1.0

    def to_optax(self):
        return lambda count: self.initial / (1.0 + self.gamma * count) ** self.power


@dataclasses.dataclass(frozen=True)
class PolySchedule(Schedule):
    """DL4J PolySchedule: lr * (1 - iter/maxIter)^power."""
    initial: float
    power: float
    max_iter: int

    def to_optax(self):
        return optax.polynomial_schedule(
            init_value=self.initial, end_value=0.0, power=self.power,
            transition_steps=self.max_iter)


@dataclasses.dataclass(frozen=True)
class SigmoidSchedule(Schedule):
    """DL4J SigmoidSchedule: lr / (1 + exp(-gamma*(iter-stepSize)))."""
    initial: float
    gamma: float
    step_size: int

    def to_optax(self):
        import jax.numpy as jnp
        return lambda count: self.initial / (1.0 + jnp.exp(-self.gamma * (count - self.step_size)))


@dataclasses.dataclass(frozen=True)
class WarmupCosineSchedule(Schedule):
    """TPU-native addition: linear warmup + cosine decay (no DL4J analog;
    standard for large-batch pod training)."""
    peak: float
    warmup_steps: int
    total_steps: int
    end_value: float = 0.0

    def to_optax(self):
        return optax.warmup_cosine_decay_schedule(
            init_value=0.0, peak_value=self.peak, warmup_steps=self.warmup_steps,
            decay_steps=self.total_steps, end_value=self.end_value)


# ---------------------------------------------------------------- updaters
@dataclasses.dataclass(frozen=True)
class Updater:
    """Base updater config (DL4J IUpdater)."""
    learning_rate: float = 1e-3
    schedule: Optional[Schedule] = None

    def _lr(self):
        if self.schedule is not None:
            return self.schedule.to_optax()
        return self.learning_rate

    def to_optax(self) -> optax.GradientTransformation:
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class Sgd(Updater):
    def to_optax(self):
        return optax.sgd(self._lr())


@dataclasses.dataclass(frozen=True)
class Nesterovs(Updater):
    learning_rate: float = 0.1
    momentum: float = 0.9

    def to_optax(self):
        return optax.sgd(self._lr(), momentum=self.momentum, nesterov=True)


@dataclasses.dataclass(frozen=True)
class Momentum(Updater):
    learning_rate: float = 0.1
    momentum: float = 0.9

    def to_optax(self):
        return optax.sgd(self._lr(), momentum=self.momentum, nesterov=False)


@dataclasses.dataclass(frozen=True)
class Adam(Updater):
    learning_rate: float = 1e-3
    beta1: float = 0.9
    beta2: float = 0.999
    epsilon: float = 1e-8

    def to_optax(self):
        return optax.adam(self._lr(), b1=self.beta1, b2=self.beta2, eps=self.epsilon)


@dataclasses.dataclass(frozen=True)
class AdamW(Updater):
    learning_rate: float = 1e-3
    beta1: float = 0.9
    beta2: float = 0.999
    epsilon: float = 1e-8
    weight_decay: float = 1e-2
    #: decay only the leaves of two or more dimensions (the LM recipe:
    #: matrices, not gains, biases or per-head scalars)
    decay_matrices_only: bool = False

    def to_optax(self):
        mask = None
        if self.decay_matrices_only:
            mask = lambda params: jax.tree_util.tree_map(
                lambda a: a.ndim >= 2, params)
        return optax.adamw(self._lr(), b1=self.beta1, b2=self.beta2,
                           eps=self.epsilon, weight_decay=self.weight_decay,
                           mask=mask)


@dataclasses.dataclass(frozen=True)
class AMSGrad(Updater):
    learning_rate: float = 1e-3
    beta1: float = 0.9
    beta2: float = 0.999
    epsilon: float = 1e-8

    def to_optax(self):
        return optax.amsgrad(self._lr(), b1=self.beta1, b2=self.beta2, eps=self.epsilon)


@dataclasses.dataclass(frozen=True)
class Nadam(Updater):
    learning_rate: float = 1e-3
    beta1: float = 0.9
    beta2: float = 0.999
    epsilon: float = 1e-8

    def to_optax(self):
        return optax.nadam(self._lr(), b1=self.beta1, b2=self.beta2, eps=self.epsilon)


@dataclasses.dataclass(frozen=True)
class AdaMax(Updater):
    learning_rate: float = 2e-3
    beta1: float = 0.9
    beta2: float = 0.999
    epsilon: float = 1e-8

    def to_optax(self):
        return optax.adamax(self._lr(), b1=self.beta1, b2=self.beta2, eps=self.epsilon)


@dataclasses.dataclass(frozen=True)
class AdaGrad(Updater):
    learning_rate: float = 1e-1
    epsilon: float = 1e-6

    def to_optax(self):
        return optax.adagrad(self._lr(), eps=self.epsilon)


@dataclasses.dataclass(frozen=True)
class AdaDelta(Updater):
    """DL4J AdaDelta carries no learning rate — the update magnitude is
    the RMS(dx)/RMS(g) ratio itself (nd4j AdaDeltaUpdater applies the
    raw delta), i.e. an effective LR of 1.0. optax >= 0.2 defaults
    ``adadelta(learning_rate=None)`` which crashes inside
    ``scale_by_learning_rate``; pin the DL4J semantics explicitly."""
    learning_rate: float = 1.0
    rho: float = 0.95
    epsilon: float = 1e-6

    def to_optax(self):
        return optax.adadelta(self._lr(), rho=self.rho, eps=self.epsilon)


@dataclasses.dataclass(frozen=True)
class RmsProp(Updater):
    learning_rate: float = 1e-1
    decay: float = 0.95
    epsilon: float = 1e-8

    def to_optax(self):
        return optax.rmsprop(self._lr(), decay=self.decay, eps=self.epsilon)


@dataclasses.dataclass(frozen=True)
class NoOp(Updater):
    """Frozen params (DL4J NoOp updater, used by FrozenLayer)."""

    def to_optax(self):
        return optax.set_to_zero()


@dataclasses.dataclass(frozen=True)
class Lars(Updater):
    """TPU-native addition: layer-wise adaptive rate scaling for large-batch
    pod-scale data parallelism (no DL4J analog)."""
    learning_rate: float = 1.0
    momentum: float = 0.9
    weight_decay: float = 0.0

    def to_optax(self):
        return optax.lars(self._lr(), weight_decay=self.weight_decay,
                          momentum=self.momentum)


UPDATERS = {
    "sgd": Sgd,
    "nesterovs": Nesterovs,
    "momentum": Momentum,
    "adam": Adam,
    "adamw": AdamW,
    "amsgrad": AMSGrad,
    "nadam": Nadam,
    "adamax": AdaMax,
    "adagrad": AdaGrad,
    "adadelta": AdaDelta,
    "rmsprop": RmsProp,
    "noop": NoOp,
    "lars": Lars,
}


def get_updater(spec: Any) -> Updater:
    """Resolve an updater from an Updater instance, name, or (name, lr)."""
    if isinstance(spec, Updater):
        return spec
    if isinstance(spec, str):
        return UPDATERS[spec.lower()]()
    if isinstance(spec, (tuple, list)) and len(spec) == 2:
        return UPDATERS[str(spec[0]).lower()](learning_rate=float(spec[1]))
    raise ValueError(f"Cannot resolve updater from {spec!r}")


def build_optimizer(updater: Any, grad_clip_norm: Optional[float] = None,
                    grad_clip_value: Optional[float] = None) -> optax.GradientTransformation:
    """Build the final optax chain, including DL4J GradientNormalization
    equivalents (ClipL2PerParamType ~ clip_by_global_norm; ClipElementWise ~
    clip)."""
    tx = get_updater(updater).to_optax()
    chain = []
    if grad_clip_value is not None:
        chain.append(optax.clip(grad_clip_value))
    if grad_clip_norm is not None:
        chain.append(optax.clip_by_global_norm(grad_clip_norm))
    chain.append(tx)
    return optax.chain(*chain) if len(chain) > 1 else tx


def apply_update(tx, grads, opt_state, params, plan=None):
    """One optimizer update, ``(new_params, new_opt_state, updates)``:
    what every compiled train step of both containers does with its
    gradients, under the scope ``opt/update`` (a trace tells the
    optimizer's device ops by it). ``plan`` pins the updates to the
    gradients' layout."""
    with jax.named_scope("opt/update"):
        updates, new_opt = tx.update(grads, opt_state, params)
        if plan is not None:
            updates = plan.constrain_grads(updates)
        return optax.apply_updates(params, updates), new_opt, updates
