"""Graph vertices — parameter-free DAG combinators.

Parity target: DL4J nn/conf/graph/ (14 vertex types) + impls in
nn/graph/vertex/impl/: Merge, ElementWise(Add/Sub/Mul/Max/Avg), Subset,
Stack, Unstack, Reshape, Scale, Shift, L2Normalize, L2 (pairwise distance),
LastTimeStep, DuplicateToTimeSeries, ReverseTimeSeries, Preprocessor.

Each vertex is a frozen dataclass with `output_type(*input_types)` and
`apply(*inputs)` — pure functions XLA fuses into the surrounding graph.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.base import InputType, Kind, register_layer


@dataclasses.dataclass(frozen=True)
class GraphVertexConf:
    def output_type(self, *input_types: InputType) -> InputType:
        raise NotImplementedError

    def apply(self, *inputs):
        raise NotImplementedError

    def has_params(self) -> bool:
        return False


@register_layer
@dataclasses.dataclass(frozen=True)
class MergeVertex(GraphVertexConf):
    """Concatenate along the feature axis (DL4J MergeVertex)."""

    def output_type(self, *input_types: InputType) -> InputType:
        k = input_types[0].kind
        if k == Kind.FF:
            return InputType.feed_forward(sum(t.shape[0] for t in input_types))
        if k == Kind.RNN:
            t0 = input_types[0].shape[0]
            return InputType(Kind.RNN, (t0, sum(t.shape[1] for t in input_types)))
        if k == Kind.CNN:
            h, w, _ = input_types[0].shape
            return InputType.convolutional(h, w, sum(t.shape[2] for t in input_types))
        raise ValueError(k)

    def apply(self, *inputs):
        with jax.named_scope("merge"):
            return jnp.concatenate(inputs, axis=-1)


@register_layer
@dataclasses.dataclass(frozen=True)
class ElementWiseVertex(GraphVertexConf):
    """Pointwise combine (DL4J ElementWiseVertex): add|subtract|product|max|average."""
    op: str = "add"

    def output_type(self, *input_types: InputType) -> InputType:
        return input_types[0]

    def apply(self, *inputs):
        with jax.named_scope("merge"):
            op = self.op.lower()
            if op == "add":
                out = inputs[0]
                for x in inputs[1:]:
                    out = out + x
                return out
            if op == "subtract":
                return inputs[0] - inputs[1]
            if op in ("product", "mul"):
                out = inputs[0]
                for x in inputs[1:]:
                    out = out * x
                return out
            if op == "max":
                out = inputs[0]
                for x in inputs[1:]:
                    out = jnp.maximum(out, x)
                return out
            if op in ("average", "avg"):
                return sum(inputs) / float(len(inputs))
            raise ValueError(f"Unknown ElementWise op {self.op}")


@register_layer
@dataclasses.dataclass(frozen=True)
class SubsetVertex(GraphVertexConf):
    """Feature-range slice [from_idx, to_idx] inclusive (DL4J SubsetVertex)."""
    from_idx: int = 0
    to_idx: int = 0

    def output_type(self, *input_types: InputType) -> InputType:
        n = self.to_idx - self.from_idx + 1
        t = input_types[0]
        if t.kind == Kind.FF:
            return InputType.feed_forward(n)
        if t.kind == Kind.RNN:
            return InputType(Kind.RNN, (t.shape[0], n))
        if t.kind == Kind.CNN:
            return InputType.convolutional(t.shape[0], t.shape[1], n)
        raise ValueError(t.kind)

    def apply(self, *inputs):
        return inputs[0][..., self.from_idx:self.to_idx + 1]


@register_layer
@dataclasses.dataclass(frozen=True)
class StackVertex(GraphVertexConf):
    """Stack along batch dim (DL4J StackVertex)."""

    def output_type(self, *input_types: InputType) -> InputType:
        return input_types[0]

    def apply(self, *inputs):
        return jnp.concatenate(inputs, axis=0)


@register_layer
@dataclasses.dataclass(frozen=True)
class UnstackVertex(GraphVertexConf):
    """Take batch slice `from_idx` of `stack_size` (DL4J UnstackVertex)."""
    from_idx: int = 0
    stack_size: int = 1

    def output_type(self, *input_types: InputType) -> InputType:
        return input_types[0]

    def apply(self, *inputs):
        x = inputs[0]
        n = x.shape[0] // self.stack_size
        return x[self.from_idx * n:(self.from_idx + 1) * n]


@register_layer
@dataclasses.dataclass(frozen=True)
class ReshapeVertex(GraphVertexConf):
    """Reshape (batch-preserving) (DL4J ReshapeVertex). new_shape excludes batch."""
    new_shape: Tuple[int, ...] = ()
    kind: str = "ff"

    def output_type(self, *input_types: InputType) -> InputType:
        return InputType(Kind(self.kind), tuple(self.new_shape))

    def apply(self, *inputs):
        x = inputs[0]
        return x.reshape((x.shape[0],) + tuple(self.new_shape))


@register_layer
@dataclasses.dataclass(frozen=True)
class ScaleVertex(GraphVertexConf):
    scale: float = 1.0

    def output_type(self, *input_types: InputType) -> InputType:
        return input_types[0]

    def apply(self, *inputs):
        return inputs[0] * self.scale


@register_layer
@dataclasses.dataclass(frozen=True)
class ShiftVertex(GraphVertexConf):
    shift: float = 0.0

    def output_type(self, *input_types: InputType) -> InputType:
        return input_types[0]

    def apply(self, *inputs):
        return inputs[0] + self.shift


@register_layer
@dataclasses.dataclass(frozen=True)
class L2NormalizeVertex(GraphVertexConf):
    eps: float = 1e-8

    def output_type(self, *input_types: InputType) -> InputType:
        return input_types[0]

    def apply(self, *inputs):
        x = inputs[0]
        norm = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))
        return x / jnp.maximum(norm, self.eps)


@register_layer
@dataclasses.dataclass(frozen=True)
class L2Vertex(GraphVertexConf):
    """Pairwise L2 distance between two inputs (DL4J L2Vertex)."""
    eps: float = 1e-8

    def output_type(self, *input_types: InputType) -> InputType:
        return InputType.feed_forward(1)

    def apply(self, *inputs):
        a, b = inputs
        d = a.reshape(a.shape[0], -1) - b.reshape(b.shape[0], -1)
        return jnp.sqrt(jnp.sum(d * d, axis=-1, keepdims=True) + self.eps)


@register_layer
@dataclasses.dataclass(frozen=True)
class LastTimeStepVertex(GraphVertexConf):
    """(B,T,F) -> (B,F) last step (DL4J LastTimeStepVertex); mask-aware
    variant lives in the LastTimeStep layer wrapper."""

    def output_type(self, *input_types: InputType) -> InputType:
        return InputType.feed_forward(input_types[0].shape[1])

    def apply(self, *inputs):
        return inputs[0][:, -1, :]


@register_layer
@dataclasses.dataclass(frozen=True)
class DuplicateToTimeSeriesVertex(GraphVertexConf):
    """(B,F) -> (B,T,F) by repetition; T taken from a reference input
    (DL4J DuplicateToTimeSeriesVertex)."""

    def output_type(self, *input_types: InputType) -> InputType:
        ff, ref = input_types
        return InputType(Kind.RNN, (ref.shape[0], ff.shape[0]))

    def apply(self, *inputs):
        x, ref = inputs
        return jnp.broadcast_to(x[:, None, :], (x.shape[0], ref.shape[1], x.shape[1]))


@register_layer
@dataclasses.dataclass(frozen=True)
class ReverseTimeSeriesVertex(GraphVertexConf):
    def output_type(self, *input_types: InputType) -> InputType:
        return input_types[0]

    def apply(self, *inputs):
        return jnp.flip(inputs[0], axis=1)


@register_layer
@dataclasses.dataclass(frozen=True)
class ShiftTimeSeriesVertex(GraphVertexConf):
    """A sequence ``steps`` positions ahead of its input: ``y[:, t] =
    x[:, t + steps]`` on (B, T) ids or (B, T, F), zeros where the input
    has run out (the last ``steps`` positions, which the consumer's label
    mask leaves out). What a multi-token-prediction branch embeds: the
    token after the one the trunk saw."""
    steps: int = 1

    def output_type(self, *input_types: InputType) -> InputType:
        return input_types[0]

    def apply(self, *inputs):
        with jax.named_scope("shift"):
            x = inputs[0]
            pad = [(0, 0)] * x.ndim
            pad[1] = (0, self.steps)
            return jnp.pad(x[:, self.steps:], pad)


@register_layer
@dataclasses.dataclass(frozen=True)
class TimeSliceVertex(GraphVertexConf):
    """The first ``steps`` time steps of a sequence: (B, T, F) ->
    (B, steps, F) (ids (B, T) likewise), the mask with it. What hands a
    head the half of a stream it scores: the first L of a block-diffusion
    LM's ``[noisy ; clean]`` stream of 2L rows, so that the head over the
    clean half, which nothing reads, is never made."""
    steps: int = 0

    def output_type(self, *input_types: InputType) -> InputType:
        t = input_types[0]
        if t.kind != Kind.RNN or not 0 < self.steps <= t.shape[0]:
            raise ValueError(f"TimeSliceVertex [0, {self.steps}) of {t}")
        return InputType(Kind.RNN, (self.steps,) + tuple(t.shape[1:]))

    def apply(self, *inputs):
        with jax.named_scope("layout"):
            return jax.lax.slice_in_dim(inputs[0], 0, self.steps, axis=1)


@register_layer
@dataclasses.dataclass(frozen=True)
class StreamsInVertex(GraphVertexConf):
    """One residual stream made ``n_streams``: (B, T, C) -> (B, T,
    n_streams x C), every stream a copy (hyper-connections' way in, behind
    the embedding; `nn/layers/hyper_connection.py`)."""
    n_streams: int = 4

    def output_type(self, *input_types: InputType) -> InputType:
        t = input_types[0]
        if t.kind != Kind.RNN:
            raise ValueError(f"StreamsInVertex expects a sequence, got {t}")
        return InputType(Kind.RNN, (t.shape[0], self.n_streams * t.shape[1]))

    def apply(self, *inputs):
        with jax.named_scope("mhc/io"):
            return jnp.tile(inputs[0], (1, 1, self.n_streams))


@register_layer
@dataclasses.dataclass(frozen=True)
class StreamsOutVertex(GraphVertexConf):
    """``n_streams`` residual streams made one: (B, T, n_streams x C) ->
    (B, T, C), their sum accumulated in float32 (hyper-connections' way
    out, before the final norm)."""
    n_streams: int = 4

    def output_type(self, *input_types: InputType) -> InputType:
        t = input_types[0]
        if t.kind != Kind.RNN or t.shape[1] % self.n_streams:
            raise ValueError(f"StreamsOutVertex: {t} is not "
                             f"{self.n_streams} streams of a sequence")
        return InputType(Kind.RNN, (t.shape[0], t.shape[1] // self.n_streams))

    def apply(self, *inputs):
        with jax.named_scope("mhc/io"):
            x = inputs[0]
            b, t, _ = x.shape
            y = jnp.sum(x.reshape(b, t, self.n_streams, -1), axis=2,
                        dtype=jnp.promote_types(jnp.float32, x.dtype))
            return y.astype(x.dtype)


@register_layer
@dataclasses.dataclass(frozen=True)
class PoolHelperVertex(GraphVertexConf):
    """Strip the first spatial row and column of a CNN activation
    (DL4J nn/conf/graph/PoolHelperVertex.java + impl
    nn/graph/vertex/impl/PoolHelperVertex.java) — compensates the
    off-by-one pooling of Caffe-imported GoogLeNet-style models."""

    def output_type(self, *input_types: InputType) -> InputType:
        t = input_types[0]
        if t.kind != Kind.CNN:
            raise ValueError("PoolHelperVertex expects CNN input, got "
                             f"{t.kind}")
        h, w, c = t.shape
        return InputType.convolutional(h - 1, w - 1, c)

    def apply(self, *inputs):
        return inputs[0][:, 1:, 1:, :]     # NHWC: drop first row + column
