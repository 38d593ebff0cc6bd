"""The chunked fit path and its feed as entered spans (nn/multilayer.py
`_run_scan_pipeline`, data/async_iterator.py `_prefetch_pump`): the span
tree of every chunked loop of both containers, the zero-cost contract of
the new call sites, the spans on the profiler's host plane, the goodput
ledger's stall detector on the scan path, and the compile spans from
`jax.monitoring`."""
import logging
import re
import time

import numpy as np
import pytest

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.data.async_iterator import AsyncDataSetIterator
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterator import (
    ArrayDataSetIterator, DataSetIterator,
)
from deeplearning4j_tpu.monitor import flight, goodput, trace
from deeplearning4j_tpu.nn.conf.base import InputType
from deeplearning4j_tpu.nn.conf.network import (
    GraphBuilder, NeuralNetConfiguration,
)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updaters import Sgd

#: the harness takes host events of the profile by this pattern
#: (benchmark/lib/xplane.py `_SPAN`)
_SPAN = re.compile(r"^[a-z_0-9]+/[a-z_0-9/]+$")
FIT_TREE = ("train/chunk", "train/etl", "etl/queue_wait", "train/dispatch",
            "train/stage", "train/launch", "train/chunk_sync",
            "train/loss_fetch", "train/listeners")
FEED_TREE = ("etl/source_next", "etl/stage", "etl/queue_put")
PARENT = {"train/chunk": "train/epoch", "train/etl": "train/chunk",
          "etl/queue_wait": "train/etl", "train/dispatch": "train/chunk",
          "train/stage": "train/dispatch", "train/launch": "train/dispatch",
          "train/chunk_sync": "train/chunk",
          "train/loss_fetch": "train/chunk_sync",
          "train/listeners": "train/chunk_sync"}
CONTAINERS = [("mln", "scan"), ("mln", "accum"),
              ("graph", "scan"), ("graph", "accum")]


@pytest.fixture(autouse=True)
def _clean_telemetry():
    def reset():
        monitor.REGISTRY.reset()
        monitor.disable_tracing()
        monitor.clear_trace()
        goodput.disable_goodput()
        flight.disable_flight()
        flight.clear()
    reset()
    yield
    reset()


def _net(container):
    base = NeuralNetConfiguration.Builder().seed(7).updater(Sgd(0.1))
    if container == "mln":
        conf = (base.list()
                .layer(DenseLayer(n_out=8, activation="tanh"))
                .layer(OutputLayer(n_out=3, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(5)).build())
        return MultiLayerNetwork(conf).init()
    g = (GraphBuilder(base).add_inputs("in")
         .set_input_types(InputType.feed_forward(5)))
    g.add_layer("d", DenseLayer(n_out=8, activation="tanh"), "in")
    g.add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                   loss="mcxent"), "d")
    g.set_outputs("out")
    return ComputationGraph(g.build()).init()


def _data(n=70, batch=10):
    rs = np.random.RandomState(0)
    X = rs.randn(n, 5).astype("float32")
    Y = np.eye(3, dtype="float32")[rs.randint(0, 3, n)]
    return X, Y, batch


def _fit(container, path, source=None, epochs=1, K=3):
    """A chunked fit() of K batches a chunk over 7 batches of 10 (two
    whole chunks and a ragged tail of one) behind the async prefetch."""
    if source is None:
        X, Y, batch = _data()
        source = ArrayDataSetIterator(X, Y, batch_size=batch)
    net = _net(container)
    kw = {"scan_steps": K} if path == "scan" else {"accumulate_steps": K}
    # the graph container takes the user's own async iterator (as the
    # benchmark's feed does); the sequential one wraps by itself
    net.fit(AsyncDataSetIterator(source, device_put=False)
            if container == "graph" else source, epochs=epochs, **kw)
    return net


def _spans():
    return [e for e in monitor.trace_events() if e.get("ph") == "X"]


def _inside(child, parent, eps=1.0):
    return (child["tid"] == parent["tid"]
            and parent["ts"] - eps <= child["ts"]
            and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"] + eps)


# ------------------------------------------------------------ (a) the tree
@pytest.mark.parametrize("container,path", CONTAINERS)
def test_chunked_fit_emits_the_span_tree(container, path):
    monitor.enable_tracing()
    _fit(container, path, epochs=2)
    events = _spans()
    by = {n: [e for e in events if e["name"] == n]
          for n in FIT_TREE + FEED_TREE + ("train/epoch",)}
    (fit_tid,) = {e["tid"] for e in by["train/epoch"]}
    # names and nesting on the fit() thread: every span of the tree lies
    # inside one span of its parent's name, on the same thread
    for name in FIT_TREE:
        assert by[name], name
        for e in by[name]:
            assert e["tid"] == fit_tid, name
            assert sum(_inside(e, p) for p in by[PARENT[name]]) == 1, name
    # the feed's spans are on prefetch threads, none on the fit() thread
    names = monitor.thread_names()
    for name in FEED_TREE:
        assert by[name] and all(
            names[e["tid"]].startswith("etl-prefetch-")
            for e in by[name]), name
    # chunk numbers run 0..n-1 over the epochs of the one fit(): 3 chunks
    # an epoch (3 + 3 + 1 batches); the turn that pulls nothing and drains
    # carries the number the next chunk gets
    dispatched = [e["args"]["chunk"] for e in by["train/dispatch"]]
    assert dispatched == list(range(6))
    assert [e["args"]["chunk"] for e in by["train/chunk_sync"]] == \
        dispatched
    turns = [(e["args"]["chunk"], e["args"]["batches"],
              e["args"]["examples"]) for e in by["train/chunk"]]
    assert turns == [(0, 3, 30), (1, 3, 30), (2, 1, 10), (3, 0, 0),
                     (3, 3, 30), (4, 3, 30), (5, 1, 10), (6, 0, 0)]
    steps = [e["args"]["steps"] for e in by["train/chunk"]]
    per_chunk = [3, 3, 1] if path == "scan" else [1, 1, 1]
    assert steps == ([0] + per_chunk) * 2        # synced one turn late
    assert [e["args"]["steps"] for e in by["train/listeners"]] == \
        per_chunk * 2
    assert [e["args"]["batches"] for e in by["train/etl"]] == \
        [3, 3, 1, 0] * 2
    # every wait for a batch is matched, by seq, by the feed-thread spans
    # that made the batch; the last wait of an epoch meets the end mark
    for epoch in by["train/epoch"]:
        waits = [e for e in by["etl/queue_wait"] if _inside(e, epoch)]
        assert [e["args"]["seq"] for e in waits] == list(range(8))
        (feed_tid,) = {e["tid"] for e in by["etl/queue_put"]
                       if epoch["ts"] <= e["ts"]
                       <= epoch["ts"] + epoch["dur"]}
        for name in FEED_TREE:
            made = sorted(e["args"]["seq"] for e in by[name]
                          if e["tid"] == feed_tid
                          and e["ts"] + e["dur"] >= epoch["ts"]
                          and e["ts"] <= epoch["ts"] + epoch["dur"])
            assert made[:7] == list(range(7)), name
    # nothing reaches the buffer through add_span on this path: an entered
    # span and the same extent added afterwards would show twice
    assert len(by["train/etl"]) == len(by["train/chunk"])


# ------------------------------------------------------- (b) zero cost
@pytest.mark.parametrize("container,path", CONTAINERS)
def test_call_sites_get_the_null_span_when_off(container, path,
                                                monkeypatch):
    handed = []
    real = trace.span

    def spy(name, *a, **kw):
        sp = real(name, *a, **kw)
        handed.append((name, sp))
        return sp

    monkeypatch.setattr(monitor, "span", spy)
    _fit(container, path)
    seen = {n for n, _ in handed}
    assert set(FIT_TREE + FEED_TREE) <= seen
    assert all(sp is trace._NULL for _, sp in handed)
    assert monitor.trace_events() == []


@pytest.mark.parametrize("container,path", CONTAINERS)
def test_a_launched_chunks_inputs_are_not_held_to_the_next_turn(
        container, path, monkeypatch):
    """The loop keeps no reference to a chunk's stacked device inputs
    once the chunk is launched: held to the next turn's stage() they cost
    a whole chunk of device memory at the peak (736 MiB in
    `resnet50-fit-1chip`: 12.73 GB where the parent commit of PR 24 read
    12.00), exactly while the next chunk's inputs are allocated."""
    import jax
    K, batch = 3, 10
    cls = MultiLayerNetwork if container == "mln" else ComputationGraph
    real = cls._stage_stacked
    alive = []

    def spy(self, group):
        # stacked inputs of EARLIER chunks still alive as stage() begins
        alive.append(sum(a.shape[:2] == (K, batch)
                         for a in jax.live_arrays()))
        return real(self, group)

    monkeypatch.setattr(cls, "_stage_stacked", spy)
    _fit(container, path, K=K)
    assert len(alive) >= 2 and not any(alive), alive


# ------------------------------------------- (c) on the profiler's plane
def test_every_span_is_on_the_profilers_host_plane(tmp_path):
    import glob

    import jax
    monitor.enable_tracing(jax_annotations=True)
    _fit("mln", "scan")                     # compile outside the session
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _fit("mln", "scan")
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    host = {e.name for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events}
    ours = {n for n in host if n.startswith(("train/", "etl/"))}
    # all but the feed's healthy wait for room in the queue, which is
    # about a chunk long and must not be taken for what the device waited
    # on: that one is in the program's buffer only
    assert ours == set(FIT_TREE + FEED_TREE + ("train/epoch",)) \
        - {"etl/queue_put"}
    assert any(e["name"] == "etl/queue_put" for e in _spans())
    assert all(_SPAN.match(n) for n in ours)    # bare names, no attrs


# ------------------------------------------ (d) the detector on the scan path
class _SleepsOnce(DataSetIterator):
    """Batches of 10 from arrays; the `stall_at`-th pull sleeps."""

    def __init__(self, X, Y, stall_at, stall_s):
        self.X, self.Y = X, Y
        self.stall_at, self.stall_s = stall_at, stall_s

    def __iter__(self):
        for i in range(0, len(self.X), 10):
            if i // 10 == self.stall_at:
                time.sleep(self.stall_s)
            yield self._pp(DataSet(self.X[i:i + 10], self.Y[i:i + 10]))


@pytest.mark.parametrize("container", ["mln", "graph"])
def test_stall_detector_arms_on_the_scan_path(container, caplog, tmp_path):
    # a floor of half a second: on a loaded test box a sub-millisecond
    # chunk can hiccup by tens of ms, and only the 1 s sleep may trip
    goodput.enable_goodput(warmup_steps=8, anomaly_min_s=0.5)
    flight.enable_flight(dump_dir=str(tmp_path))
    X, Y, _ = _data(n=600)                       # 60 batches, 20 chunks
    t0 = time.perf_counter()
    with caplog.at_level(logging.WARNING, logger="deeplearning4j_tpu"):
        _fit(container, "scan", source=_SleepsOnce(X, Y, 45, 1.0))
    wall = time.perf_counter() - t0
    s = goodput.last_session()
    assert s["steps"] == 60 and s["anomalies"] == 1
    assert s["categories"]["data_wait"] >= 0.9
    # the categories still partition the wall clock, leaves only
    assert sum(s["categories"].values()) == pytest.approx(
        s["wall_s"], abs=1e-5)      # eight values rounded to 1e-6
    assert s["wall_s"] <= wall
    assert monitor.REGISTRY.collect("train_goodput_pct").value() > 0.0
    (doc,) = [d for d in flight.postmortems()
              if d["reason"] == "step_time_anomaly"]
    meta = doc["meta"]
    assert meta["dominant_category"] == "data_wait"
    assert meta["leaf_span"] == "etl/source_next"
    assert meta["leaf_thread"].startswith("etl-prefetch-")
    assert meta["leaf_excess_s"] >= 0.9
    (line,) = [r.getMessage() for r in caplog.records
               if "fit() stalled" in r.getMessage()]
    assert "data_wait" in line and "etl/source_next" in line \
        and meta["leaf_thread"] in line and "chunk 15" in line


def test_stall_under_no_span_is_named_so(caplog):
    """A stall of the fit() thread that no leaf span covers (fake clock,
    the ledger driven by hand): the category is `other`, the leaf
    `_no_span_` on the fit() thread."""
    import threading
    flight.enable_flight()

    class Clock:
        t = 0.0

        def __call__(self):
            return self.t

    led = goodput.GoodputLedger(clock=Clock(), warmup_steps=4)
    s = led.fit_begin()

    def turn(t0, t1, chunk):
        led.on_span("train/loss_fetch", t1 - 0.8, t1 - 0.1, {})
        led.on_span("train/listeners", t1 - 0.1, t1, {"steps": 10})
        led.on_span("train/chunk", t0, t1, {"chunk": chunk, "steps": 10})

    for i in range(8):
        turn(float(i), i + 1.0, i)
    with caplog.at_level(logging.WARNING, logger="deeplearning4j_tpu"):
        turn(8.0, 14.0, 8)
    assert s.anomalies == 1 and s.steps == 90
    meta = flight.postmortems()[-1]["meta"]
    assert meta["dominant_category"] == "other"
    assert meta["leaf_span"] == "_no_span_"
    assert meta["leaf_thread"] == threading.current_thread().name
    assert meta["leaf_excess_s"] == pytest.approx(5.0)
    assert any("_no_span_" in r.getMessage() for r in caplog.records)
    # listener time has no category: it is in `other`, not step_compute
    led.clock.t = 14.0
    out = led.fit_end(s)
    assert out["categories"]["step_compute"] == pytest.approx(0.7 * 9)
    assert out["categories"]["other"] == pytest.approx(14.0 - 0.7 * 9)


def test_a_pull_counts_only_what_its_queue_waits_left():
    """`etl/queue_wait` inside `train/etl` (entered or added afterwards)
    is data_wait once, not twice; outside any pull it counts alone."""
    led = goodput.GoodputLedger(clock=lambda: 10.0)
    s = led.fit_begin()
    s.t0 = 0.0
    led.on_span("etl/queue_wait", 0.0, 1.0, {})
    led.on_span("etl/queue_wait", 1.5, 2.0, {})
    led.on_span("train/etl", 0.0, 3.0, {})          # 1.5 s left over
    led.on_span("etl/queue_wait", 4.0, 4.25, {})    # no pull around it
    led.on_span("etl/queue_wait", 5.0, 6.0, {})
    led.on_span("train/resume_replay", 5.0, 7.0, {})
    out = led.fit_end(s)
    assert out["categories"]["data_wait"] == pytest.approx(4.25)
    assert out["categories"]["resume_replay"] == pytest.approx(1.0)
    assert sum(out["categories"].values()) == pytest.approx(10.0)


def test_stall_history_does_not_grow_with_the_pumps():
    """Every epoch's pump is a new `etl-prefetch-N` thread: the history a
    stall is measured against is by span name, so a long fit() keeps one
    deque a leaf, and queue waits under no pull span are not kept."""
    import threading
    led = goodput.GoodputLedger(clock=lambda: 0.0, warmup_steps=2)
    s = led.fit_begin()
    for i in range(6):
        pump = threading.Thread(
            target=led.on_span, name=f"etl-prefetch-{100 + i}",
            args=("etl/stage", i + 0.0, i + 0.1, {}))
        pump.start()
        pump.join()
        led.on_span("etl/queue_wait", i + 0.0, i + 0.2, {})
        led.on_span("train/chunk", i + 0.0, i + 1.0,
                    {"chunk": i, "steps": 10})
    assert set(s.leaf_hist) == {"etl/stage", "etl/queue_wait", "_no_span_"}
    assert list(s.leaf_hist["etl/stage"]) == pytest.approx([0.1] * 6)
    assert s.waits == [] and s.anomalies == 0


# ----------------------------------------------------- compiles as spans
def test_compiles_are_spans_from_jax_monitoring():
    import jax
    import jax.numpy as jnp

    def fresh(x):
        return jnp.tanh(x) * 3.0 + 1.0

    monitor.enable_tracing()
    monitor.enable_tracing()                    # registers once
    t0 = time.perf_counter()
    jax.jit(fresh)(jnp.ones((3, 5))).block_until_ready()
    t1 = time.perf_counter()
    spans = {n: [e for e in _spans() if e["name"] == n
                 and "fresh" in str(e["args"].get("fun_name"))]
             for n in ("xla/trace", "xla/lower", "xla/backend_compile")}
    for name, found in spans.items():
        assert len(found) == 1, name            # once, not once a listener
        e = found[0]
        assert t0 * 1e6 - 1 <= e["ts"] and \
            e["ts"] + e["dur"] <= t1 * 1e6 + 1
    assert spans["xla/trace"][0]["ts"] <= spans["xla/lower"][0]["ts"] \
        <= spans["xla/backend_compile"][0]["ts"]
    # off again: the listener returns at once
    monitor.disable_tracing()
    before = len(monitor.trace_events())
    jax.jit(lambda x: x - 7.0)(jnp.ones(3)).block_until_ready()
    assert len(monitor.trace_events()) == before
