"""The system under test for ``resnet50-imagenet``: the zoo's ResNet50 as a
``ComputationGraph``, trained through ``fit()``. Everything the benchmark
takes from the program for this configuration is here: how to build the
network from the configuration file, how to hand it the seeded weights, and
where its optimizer keeps the momentum."""
from __future__ import annotations

import dataclasses
import time

import jax


def build(cfg: dict, params: dict):
    """A ``ComputationGraph`` at the configuration's sizes holding the
    benchmark's seeded float32 weights (same names, same shapes)."""
    from deeplearning4j_tpu.models import ResNet50
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.updaters import Nesterovs
    assert cfg["updater"] == "nesterov"
    size = cfg["image_size"]
    conf = dataclasses.replace(
        ResNet50(num_classes=cfg["num_classes"],
                 input_shape=(size, size, cfg["channels"])).conf(),
        compute_dtype=cfg["compute_dtype"],
        updater=Nesterovs(cfg["learning_rate"], momentum=cfg["momentum"]))
    net = ComputationGraph(conf).init()
    shapes = lambda t: jax.tree_util.tree_map(
        lambda a: (a.shape, str(a.dtype)), t)
    # layers without parameters keep the empty entry init() gave them
    if shapes({k: v for k, v in net.params.items() if v}) != shapes(params):
        raise SystemExit("benchmark: the zoo's ResNet50 and the "
                         "configuration file disagree on the parameters")
    net.params = {k: params.get(k, v) for k, v in net.params.items()}
    return net


def trained(tree):
    """The entries of a program tree that hold parameters."""
    return {k: v for k, v in tree.items() if v}


def momentum(net):
    """The optimizer's momentum trace, a tree shaped like the params."""
    found = []

    def walk(node):
        if hasattr(node, "trace"):
            found.append(node.trace)
        elif isinstance(node, (tuple, list)):
            for child in node:
                walk(child)

    walk(net.opt_state)
    (trace,) = found
    return trace


def feed(batches, plan=None):
    """The data iterator a DL4J user hands to ``fit()``: host uint8
    batches (any iterable of ``(pixels, one-hot labels)``, a generator for
    a feed that ends by the clock) behind the async prefetch, with the
    [0,1] pixel scaler attached (``fit()`` applies it on the device)."""
    from deeplearning4j_tpu.data.async_iterator import AsyncDataSetIterator
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterator import DataSetIterator
    from deeplearning4j_tpu.data.normalization import (
        ImagePreProcessingScaler)

    class HostBatches(DataSetIterator):
        def __iter__(self):
            return (self._pp(DataSet(x, y)) for x, y in batches)

    source = HostBatches().set_pre_processor(ImagePreProcessingScaler())
    device = None if plan is None else plan.batch_sharding()
    return AsyncDataSetIterator(source, device=device)


def make_plan(kind):
    if kind is None:
        return None
    assert kind == "data"
    from deeplearning4j_tpu.parallel.plan import ShardingPlan
    return ShardingPlan(data=-1)


def stamp_listener():
    """A listener that keeps ``(time.monotonic(), loss)`` of every
    optimizer step as ``fit()`` reports it. The scan path reports the steps
    of a chunk together, once the chunk's losses are on the host, so the
    last stamp of a chunk is when the chunk was done."""
    from deeplearning4j_tpu.train.listeners import TrainingListener

    class Stamps(TrainingListener):
        def __init__(self):
            self.rows = []

        def iteration_done(self, model, iteration, epoch, score,
                           etl_ms=0.0, batch_size=0):
            self.rows.append((time.monotonic(), float(score)))

    return Stamps()


def fit_seconds_by_category():
    """The goodput ledger's running totals (``train_time_seconds_total``):
    seconds of ``fit()`` wall time by category since the process began."""
    from deeplearning4j_tpu.monitor import metrics
    family = metrics.counter("train_time_seconds_total", "",
                             labels=("category",))
    return {c: family.value(category=c)
            for c in ("step_compute", "data_wait", "host_sync", "compile",
                      "checkpoint", "eval_gate", "resume_replay", "other")}


STEP_PROGRAM = "jit_kstep"      # the scan-of-K program's name in a trace
