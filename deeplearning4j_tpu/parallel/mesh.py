"""Device-mesh construction and canonical shardings.

The analog of DL4J's device bookkeeping (`Nd4j.getAffinityManager()` thread
pinning, `ParallelWrapper.java:123-141`) — on TPU, placement is declarative:
a `jax.sharding.Mesh` over the chip topology, `NamedSharding`s instead of
thread-to-device affinity. ICI topology awareness comes from mesh axis order
(XLA maps the trailing mesh axes to the closest chips).

Axis conventions used throughout:
  "data"  — data parallelism (batch dim; DL4J worker index)
  "model" — tensor parallelism (feature/head dims; absent in DL4J)
  "seq"   — sequence/context parallelism (time dim; absent in DL4J)
  "stage" — pipeline parallelism (layer-stack dim; absent in DL4J)
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
STAGE_AXIS = "stage"


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Declarative mesh spec: how many devices along each logical axis.

    `data=-1` means "all remaining devices". Mirrors the role of
    ParallelWrapper's `workers(n)` builder knob (ParallelWrapper.java:59-74)
    plus the model/seq/stage axes DL4J has no equivalent for.
    """
    data: int = -1
    model: int = 1
    seq: int = 1
    stage: int = 1

    def resolve(self, n_devices: int) -> Tuple[int, int, int, int]:
        d, m, s, p = self.data, self.model, self.seq, self.stage
        if d == -1:
            if n_devices % (m * s * p):
                raise ValueError(
                    f"{n_devices} devices not divisible by "
                    f"model*seq*stage={m * s * p}")
            d = n_devices // (m * s * p)
        if d * m * s * p != n_devices:
            raise ValueError(
                f"mesh {d}x{p}x{s}x{m} != available devices {n_devices}")
        return d, m, s, p


def build_mesh(config: Optional[MeshConfig] = None,
               devices: Optional[Sequence] = None) -> Mesh:
    """Build a (data, stage, seq, model) mesh over the given (default: all)
    devices.

    Axis order puts "model" and "seq" innermost so tensor/sequence
    collectives ride the fastest ICI links; "stage" sits next to "data"
    because its traffic is point-to-point ring permutes (scaling-book
    recipe: closest chips get the highest-traffic axis)."""
    config = config or MeshConfig()
    devices = list(devices if devices is not None else jax.devices())
    d, m, s, p = config.resolve(len(devices))
    arr = np.asarray(devices).reshape(d, p, s, m)
    return Mesh(arr, (DATA_AXIS, STAGE_AXIS, SEQ_AXIS, MODEL_AXIS))


def data_sharding(mesh: Mesh) -> NamedSharding:
    """Batch-dim sharding for input/label arrays."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully-replicated sharding (params in pure data parallelism)."""
    return NamedSharding(mesh, P())


def stacked_sharding(mesh: Mesh) -> NamedSharding:
    """Leading-axis sharding for per-replica stacked pytrees (AVERAGING
    mode keeps one parameter copy per data-parallel worker)."""
    return NamedSharding(mesh, P(DATA_AXIS))
