"""Network-level configuration: sequential and DAG configs + fluent builders.

Parity targets:
- NeuralNetConfiguration.Builder -> ListBuilder
  (DL4J NeuralNetConfiguration.java:584 builder, :744 list()) — global
  defaults (seed, updater, weight init, activation, l1/l2) applied to layers
  that don't override them.
- MultiLayerConfiguration with toJson/fromJson
  (MultiLayerConfiguration.java:120,138) — JSON round-trip is the wire format
  for model replication and the checkpoint config entry.
- ComputationGraphConfiguration.GraphBuilder
  (ComputationGraphConfiguration.java; graph vertices in nn/conf/graph/).
- BackpropType.TruncatedBPTT with fwd/bwd lengths
  (MultiLayerNetwork.java:1315-1317).

TPU-native additions (no DL4J analog): `dtype`/`compute_dtype` for bf16
mixed-precision on the MXU.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Optional, Tuple

from deeplearning4j_tpu.nn.conf.base import (
    InputType, LayerConf, layer_from_dict, layer_to_dict,
)
from deeplearning4j_tpu.nn.updaters import Sgd, get_updater


@dataclasses.dataclass(frozen=True)
class MultiLayerConfiguration:
    layers: Tuple[LayerConf, ...] = ()
    input_type: Optional[InputType] = None
    seed: int = 0
    updater: Any = dataclasses.field(default_factory=lambda: Sgd(1e-2))
    backprop_type: str = "standard"       # standard | tbptt
    tbptt_fwd_length: int = 20
    tbptt_back_length: int = 20
    dtype: str = "float32"                # parameter dtype
    compute_dtype: Optional[str] = None   # activation dtype (None = dtype)
    grad_clip_norm: Optional[float] = None
    grad_clip_value: Optional[float] = None
    # rematerialize per-layer activations in the backward pass
    # (jax.checkpoint): trades recompute FLOPs for HBM — the TPU lever
    # for deep nets / long sequences that don't fit otherwise
    gradient_checkpointing: bool = False

    # ---- serde ----------------------------------------------------------
    def to_dict(self) -> dict:
        return {
            "format": "deeplearning4j_tpu.MultiLayerConfiguration.v1",
            "layers": [layer_to_dict(l) for l in self.layers],
            "input_type": None if self.input_type is None else self.input_type.to_dict(),
            "seed": self.seed,
            "updater": layer_to_dict(get_updater(self.updater)),
            "backprop_type": self.backprop_type,
            "tbptt_fwd_length": self.tbptt_fwd_length,
            "tbptt_back_length": self.tbptt_back_length,
            "dtype": self.dtype,
            "compute_dtype": self.compute_dtype,
            "grad_clip_norm": self.grad_clip_norm,
            "grad_clip_value": self.grad_clip_value,
            "gradient_checkpointing": self.gradient_checkpointing,
        }

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2)

    def to_yaml(self) -> str:
        """YAML form (DL4J MultiLayerConfiguration.toYaml)."""
        import yaml
        return yaml.safe_dump(self.to_dict(), sort_keys=False)

    @staticmethod
    def from_yaml(s: str) -> "MultiLayerConfiguration":
        import yaml
        return MultiLayerConfiguration.from_dict(yaml.safe_load(s))

    @staticmethod
    def from_dict(d: dict) -> "MultiLayerConfiguration":
        return MultiLayerConfiguration(
            layers=tuple(layer_from_dict(l) for l in d["layers"]),
            input_type=None if d.get("input_type") is None
            else InputType.from_dict(d["input_type"]),
            seed=d.get("seed", 0),
            updater=layer_from_dict(d["updater"]) if isinstance(d.get("updater"), dict)
            else d.get("updater", Sgd(1e-2)),
            backprop_type=d.get("backprop_type", "standard"),
            tbptt_fwd_length=d.get("tbptt_fwd_length", 20),
            tbptt_back_length=d.get("tbptt_back_length", 20),
            dtype=d.get("dtype", "float32"),
            compute_dtype=d.get("compute_dtype"),
            grad_clip_norm=d.get("grad_clip_norm"),
            grad_clip_value=d.get("grad_clip_value"),
            gradient_checkpointing=d.get("gradient_checkpointing", False),
        )

    @staticmethod
    def from_json(s: str) -> "MultiLayerConfiguration":
        return MultiLayerConfiguration.from_dict(json.loads(s))


class NeuralNetConfiguration:
    """Fluent builder entry point, mirroring DL4J usage:

        conf = (NeuralNetConfiguration.Builder()
                .seed(12345).updater(Adam(1e-3))
                .list()
                .layer(DenseLayer(n_out=128, activation="relu"))
                .layer(OutputLayer(n_out=10))
                .set_input_type(InputType.feed_forward(784))
                .build())
    """

    class Builder:
        def __init__(self):
            self._seed = 0
            self._updater: Any = Sgd(1e-2)
            self._l1 = 0.0
            self._l2 = 0.0
            self._dtype = "float32"
            self._compute_dtype: Optional[str] = None
            self._grad_clip_norm: Optional[float] = None
            self._grad_clip_value: Optional[float] = None
            self._gradient_checkpointing = False
            self._weight_init: Optional[str] = None
            self._activation: Optional[str] = None
            self._dropout: Optional[float] = None

        def seed(self, s: int):
            self._seed = int(s)
            return self

        def updater(self, u):
            self._updater = u
            return self

        def l1(self, v: float):
            self._l1 = float(v)
            return self

        def l2(self, v: float):
            self._l2 = float(v)
            return self

        def weight_init(self, w: str):
            self._weight_init = w
            return self

        def activation(self, a: str):
            self._activation = a
            return self

        def dropout(self, d: float):
            self._dropout = float(d)
            return self

        def dtype(self, d: str):
            self._dtype = d
            return self

        def compute_dtype(self, d: str):
            self._compute_dtype = d
            return self

        def grad_clip_norm(self, v: float):
            self._grad_clip_norm = float(v)
            return self

        def grad_clip_value(self, v: float):
            self._grad_clip_value = float(v)
            return self

        def gradient_checkpointing(self, on: bool = True):
            self._gradient_checkpointing = bool(on)
            return self

        def list(self) -> "ListBuilder":
            return ListBuilder(self)

        def graph_builder(self) -> "GraphBuilder":
            return GraphBuilder(self)

    def _apply_defaults(builder: "NeuralNetConfiguration.Builder",
                        layer: LayerConf) -> LayerConf:
        raise NotImplementedError


def _apply_global_defaults(b: "NeuralNetConfiguration.Builder",
                           layer: LayerConf) -> LayerConf:
    """Fill layer fields from global builder defaults when the layer left
    them at their dataclass defaults (DL4J's 'global config' semantics)."""
    updates: Dict[str, Any] = {}
    fields = {f.name: f for f in dataclasses.fields(layer)}
    if b._l1 and "l1" in fields and layer.l1 == 0.0:
        updates["l1"] = b._l1
    if b._l2 and "l2" in fields and layer.l2 == 0.0:
        updates["l2"] = b._l2
    if b._dropout is not None and layer.dropout == 0.0:
        updates["dropout"] = b._dropout
    if b._weight_init is not None and "weight_init" in fields:
        f = fields["weight_init"]
        if getattr(layer, "weight_init") == f.default:
            updates["weight_init"] = b._weight_init
    if b._activation is not None and "activation" in fields:
        f = fields["activation"]
        if getattr(layer, "activation") == f.default:
            updates["activation"] = b._activation
    return dataclasses.replace(layer, **updates) if updates else layer


class ListBuilder:
    """DL4J NeuralNetConfiguration.ListBuilder analog."""

    def __init__(self, parent: "NeuralNetConfiguration.Builder"):
        self._parent = parent
        self._layers: List[LayerConf] = []
        self._input_type: Optional[InputType] = None
        self._backprop_type = "standard"
        self._tbptt_fwd = 20
        self._tbptt_back = 20

    def layer(self, l: LayerConf):
        self._layers.append(_apply_global_defaults(self._parent, l))
        return self

    def set_input_type(self, t: InputType):
        self._input_type = t
        return self

    def backprop_type(self, t: str, fwd_length: int = 20, back_length: int = 20):
        if t == "tbptt" and back_length != fwd_length:
            # DL4J allows tBPTTBackwardLength < forward; this framework chunks
            # by one length (gradients truncate at chunk boundaries). Refuse
            # rather than silently ignoring the shorter backward window.
            raise NotImplementedError(
                "tbptt_back_length != tbptt_fwd_length is not supported; "
                "use equal lengths (gradient truncation happens at chunk "
                "boundaries of fwd_length)")
        self._backprop_type = t
        self._tbptt_fwd = fwd_length
        self._tbptt_back = back_length
        return self

    def tbptt(self, fwd_length: int, back_length: Optional[int] = None):
        return self.backprop_type("tbptt", fwd_length, back_length or fwd_length)

    def build(self) -> MultiLayerConfiguration:
        p = self._parent
        return MultiLayerConfiguration(
            layers=tuple(self._layers),
            input_type=self._input_type,
            seed=p._seed,
            updater=p._updater,
            backprop_type=self._backprop_type,
            tbptt_fwd_length=self._tbptt_fwd,
            tbptt_back_length=self._tbptt_back,
            dtype=p._dtype,
            compute_dtype=p._compute_dtype,
            grad_clip_norm=p._grad_clip_norm,
            grad_clip_value=p._grad_clip_value,
            gradient_checkpointing=p._gradient_checkpointing,
        )


# ------------------------------------------------------------------- graph
@dataclasses.dataclass(frozen=True)
class VertexDef:
    """One node in the DAG: either a LayerConf or a GraphVertex op.
    ``params_of`` names another layer vertex whose parameters this one
    reads instead of holding its own (parameter sharing: a tied embedding,
    a second head over the first one's matrix): ONE leaf in the net's
    params and optimizer state, its gradient the sum over every use.
    ``scope`` is a `jax.named_scope` put on every op of the vertex (and,
    for an output vertex, of its loss), so that a device trace can tell a
    branch of the graph apart."""
    vertex: Any                      # LayerConf | GraphVertexConf
    inputs: Tuple[str, ...]
    params_of: Optional[str] = None
    scope: Optional[str] = None

    def to_dict(self) -> dict:
        d = {"vertex": layer_to_dict(self.vertex), "inputs": list(self.inputs)}
        if self.params_of is not None:
            d["params_of"] = self.params_of
        if self.scope is not None:
            d["scope"] = self.scope
        return d


@dataclasses.dataclass(frozen=True)
class ComputationGraphConfiguration:
    """DAG config (DL4J ComputationGraphConfiguration). Vertices keyed by
    name; topological order computed at build time (ComputationGraph.java:152,401)."""
    vertices: Dict[str, VertexDef] = dataclasses.field(default_factory=dict)
    network_inputs: Tuple[str, ...] = ()
    network_outputs: Tuple[str, ...] = ()
    input_types: Tuple[InputType, ...] = ()
    seed: int = 0
    updater: Any = dataclasses.field(default_factory=lambda: Sgd(1e-2))
    backprop_type: str = "standard"
    tbptt_fwd_length: int = 20
    tbptt_back_length: int = 20
    dtype: str = "float32"
    compute_dtype: Optional[str] = None
    grad_clip_norm: Optional[float] = None
    grad_clip_value: Optional[float] = None
    gradient_checkpointing: bool = False   # remat per-vertex activations
    # the weight of each output's loss in the score, in network_outputs'
    # order; () = every output at 1 (DL4J sums them unweighted)
    output_weights: Tuple[float, ...] = ()

    def topological_order(self) -> List[str]:
        order: List[str] = []
        seen = set(self.network_inputs)
        pending = dict(self.vertices)
        while pending:
            progressed = False
            for name, vd in list(pending.items()):
                if all(i in seen for i in vd.inputs):
                    order.append(name)
                    seen.add(name)
                    del pending[name]
                    progressed = True
            if not progressed:
                raise ValueError(f"Graph has a cycle or missing inputs: {list(pending)}")
        return order

    def to_dict(self) -> dict:
        return {
            "format": "deeplearning4j_tpu.ComputationGraphConfiguration.v1",
            "vertices": {name: vd.to_dict()
                         for name, vd in self.vertices.items()},
            "network_inputs": list(self.network_inputs),
            "network_outputs": list(self.network_outputs),
            "input_types": [t.to_dict() for t in self.input_types],
            "seed": self.seed,
            "updater": layer_to_dict(get_updater(self.updater)),
            "backprop_type": self.backprop_type,
            "tbptt_fwd_length": self.tbptt_fwd_length,
            "tbptt_back_length": self.tbptt_back_length,
            "dtype": self.dtype,
            "compute_dtype": self.compute_dtype,
            "grad_clip_norm": self.grad_clip_norm,
            "grad_clip_value": self.grad_clip_value,
            "gradient_checkpointing": self.gradient_checkpointing,
            **({"output_weights": list(self.output_weights)}
               if self.output_weights else {}),
        }

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2)

    def to_yaml(self) -> str:
        """YAML form (DL4J ComputationGraphConfiguration.toYaml)."""
        import yaml
        return yaml.safe_dump(self.to_dict(), sort_keys=False)

    @staticmethod
    def from_yaml(s: str) -> "ComputationGraphConfiguration":
        import yaml
        return ComputationGraphConfiguration.from_dict(yaml.safe_load(s))

    @staticmethod
    def from_dict(d: dict) -> "ComputationGraphConfiguration":
        return ComputationGraphConfiguration(
            vertices={
                name: VertexDef(layer_from_dict(vd["vertex"]),
                                tuple(vd["inputs"]), vd.get("params_of"),
                                vd.get("scope"))
                for name, vd in d["vertices"].items()
            },
            network_inputs=tuple(d["network_inputs"]),
            network_outputs=tuple(d["network_outputs"]),
            input_types=tuple(InputType.from_dict(t) for t in d.get("input_types", [])),
            seed=d.get("seed", 0),
            updater=layer_from_dict(d["updater"]) if isinstance(d.get("updater"), dict)
            else d.get("updater", Sgd(1e-2)),
            backprop_type=d.get("backprop_type", "standard"),
            tbptt_fwd_length=d.get("tbptt_fwd_length", 20),
            tbptt_back_length=d.get("tbptt_back_length", 20),
            dtype=d.get("dtype", "float32"),
            compute_dtype=d.get("compute_dtype"),
            grad_clip_norm=d.get("grad_clip_norm"),
            grad_clip_value=d.get("grad_clip_value"),
            gradient_checkpointing=d.get("gradient_checkpointing", False),
            output_weights=tuple(d.get("output_weights", ())),
        )

    @staticmethod
    def from_json(s: str) -> "ComputationGraphConfiguration":
        return ComputationGraphConfiguration.from_dict(json.loads(s))


class GraphBuilder:
    """DL4J ComputationGraphConfiguration.GraphBuilder analog."""

    def __init__(self, parent: Optional["NeuralNetConfiguration.Builder"] = None):
        self._parent = parent or NeuralNetConfiguration.Builder()
        self._vertices: Dict[str, VertexDef] = {}
        self._inputs: Tuple[str, ...] = ()
        self._outputs: Tuple[str, ...] = ()
        self._output_weights: Tuple[float, ...] = ()
        self._input_types: Tuple[InputType, ...] = ()
        self._backprop_type = "standard"
        self._tbptt_fwd = 20
        self._tbptt_back = 20

    def add_inputs(self, *names: str):
        self._inputs = tuple(names)
        return self

    def set_input_types(self, *types: InputType):
        self._input_types = tuple(types)
        return self

    def add_layer(self, name: str, layer: LayerConf, *inputs: str,
                  params_of: Optional[str] = None,
                  scope: Optional[str] = None):
        """``params_of``: the layer vertex whose parameters this one reads
        (it holds none of its own); ``scope``: a `jax.named_scope` on the
        vertex's ops (`VertexDef`)."""
        self._vertices[name] = VertexDef(
            _apply_global_defaults(self._parent, layer), tuple(inputs),
            params_of, scope)
        return self

    def add_vertex(self, name: str, vertex, *inputs: str,
                   scope: Optional[str] = None):
        self._vertices[name] = VertexDef(vertex, tuple(inputs), None, scope)
        return self

    def set_outputs(self, *names: str):
        self._outputs = tuple(names)
        return self

    def set_output_weights(self, *weights: float):
        """The weight of each output's loss in the score, in the order of
        `set_outputs` (default: each at 1)."""
        self._output_weights = tuple(float(w) for w in weights)
        return self

    def backprop_type(self, t: str, fwd_length: int = 20, back_length: int = 20):
        self._backprop_type = t
        self._tbptt_fwd = fwd_length
        self._tbptt_back = back_length
        return self

    def build(self) -> ComputationGraphConfiguration:
        p = self._parent
        if self._output_weights and \
                len(self._output_weights) != len(self._outputs):
            raise ValueError(f"{len(self._output_weights)} output weights "
                             f"for {len(self._outputs)} outputs")
        for name, vd in self._vertices.items():
            owner = self._vertices.get(vd.params_of)
            if vd.params_of is not None and (
                    owner is None or owner.params_of is not None
                    or not isinstance(owner.vertex, LayerConf)):
                raise ValueError(
                    f"vertex '{name}' shares the parameters of "
                    f"'{vd.params_of}', which is not a layer vertex "
                    "holding its own")
        return ComputationGraphConfiguration(
            vertices=dict(self._vertices),
            network_inputs=self._inputs,
            network_outputs=self._outputs,
            input_types=self._input_types,
            seed=p._seed,
            updater=p._updater,
            backprop_type=self._backprop_type,
            tbptt_fwd_length=self._tbptt_fwd,
            tbptt_back_length=self._tbptt_back,
            dtype=p._dtype,
            compute_dtype=p._compute_dtype,
            grad_clip_norm=p._grad_clip_norm,
            grad_clip_value=p._grad_clip_value,
            gradient_checkpointing=p._gradient_checkpointing,
            output_weights=self._output_weights,
        )
