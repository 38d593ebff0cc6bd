"""Telemetry subsystem tests — monitor/ (metrics registry + trace
spans), the UIServer /metrics route and error handling, and the
cross-subsystem instrumentation (fit loops, resilience, transport,
inference, PerformanceListener)."""
import json
import os
import subprocess
import sys
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.monitor.metrics import MetricsRegistry
from deeplearning4j_tpu.nn.conf.base import InputType
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updaters import Sgd

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Each test sees a fresh global registry and a disabled, empty
    tracer (and leaves them that way for the rest of the suite)."""
    monitor.REGISTRY.reset()
    monitor.disable_tracing()
    monitor.clear_trace()
    yield
    monitor.REGISTRY.reset()
    monitor.disable_tracing()
    monitor.clear_trace()


def _small_net(seed=7):
    conf = (NeuralNetConfiguration.Builder().seed(seed).updater(Sgd(0.1))
            .list()
            .layer(DenseLayer(n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(5)).build())
    return MultiLayerNetwork(conf).init()


def _blobs(n=48, d=5, k=3, seed=0):
    rs = np.random.RandomState(seed)
    X = rs.randn(n, d).astype("float32")
    Y = np.eye(k, dtype="float32")[rs.randint(0, k, n)]
    return X, Y


# ------------------------------------------------------------- registry
def test_counter_concurrent_increments_are_exact():
    reg = MetricsRegistry()
    c = reg.counter("hits_total", "hits", labels=("worker",))
    n_threads, per_thread = 8, 5000

    def work(i):
        for _ in range(per_thread):
            c.inc(worker=i % 2)

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value(worker=0) + c.value(worker=1) == n_threads * per_thread
    assert c.value(worker=0) == n_threads // 2 * per_thread


def test_histogram_concurrent_observes_are_exact():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "lat", buckets=(0.5,))
    threads = [threading.Thread(
        target=lambda: [h.observe(0.25) for _ in range(2000)])
        for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = h.snapshot()
    assert snap["count"] == 12000
    assert snap["buckets"]["0.5"] == 12000
    assert snap["sum"] == pytest.approx(3000.0)


def test_counter_rejects_decrease_and_gauge_allows_it():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.counter("c_total", "c").inc(-1)
    g = reg.gauge("g", "g")
    g.set(5)
    g.dec(2)
    assert g.value() == 3


def test_registry_rejects_kind_and_label_mismatch():
    reg = MetricsRegistry()
    reg.counter("x_total", "x", labels=("a",))
    with pytest.raises(ValueError):
        reg.gauge("x_total", "x")
    with pytest.raises(ValueError):
        reg.counter("x_total", "x", labels=("b",))
    with pytest.raises(ValueError):                   # wrong label names
        reg.counter("x_total", "x", labels=("a",)).inc(b=1)


def test_histogram_bucket_edges_inclusive_upper():
    reg = MetricsRegistry()
    h = reg.histogram("h", "h", buckets=(1.0, 2.0, 5.0))
    for v in (0.5, 1.0, 1.5, 2.0, 5.0, 7.0):
        h.observe(v)
    snap = h.snapshot()
    # `le` bounds are inclusive: 1.0 lands in le=1, 2.0 in le=2, 5.0 in
    # le=5; 7.0 only in +Inf; counts are cumulative
    assert snap["buckets"] == {"1": 2, "2": 4, "5": 5, "+Inf": 6}
    assert snap["count"] == 6
    assert snap["sum"] == pytest.approx(17.0)


def test_histogram_buckets_mismatch_rejected():
    reg = MetricsRegistry()
    reg.histogram("hb", "h", buckets=(1.0, 2.0))
    # same buckets (any order / explicit +Inf) re-resolve fine
    assert reg.histogram("hb", "h", buckets=(2.0, 1.0, float("inf"))) \
        is reg.histogram("hb", "h", buckets=(1.0, 2.0))
    with pytest.raises(ValueError):   # silently mismatched edges would
        reg.histogram("hb", "h", buckets=(1.0, 3.0))


def test_histogram_explicit_inf_bucket_and_empty_rejected():
    reg = MetricsRegistry()
    h = reg.histogram("h2", "h", buckets=(1.0, float("inf")))
    h.observe(0.5)
    h.observe(9.0)
    assert h.snapshot()["buckets"] == {"1": 1, "+Inf": 2}
    with pytest.raises(ValueError):
        reg.histogram("h3", "h", buckets=())


def test_prometheus_text_golden():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "Total requests", labels=("method",))
    c.inc(3, method="get")
    c.inc(1.5, method="post")
    reg.gauge("queue_depth", "Depth").set(2)
    h = reg.histogram("latency_seconds", "Latency", buckets=(0.1, 1.0))
    for v in (0.0625, 0.5, 5.0):      # binary-exact values: sum is exact
        h.observe(v)
    expected = (
        "# HELP latency_seconds Latency\n"
        "# TYPE latency_seconds histogram\n"
        'latency_seconds_bucket{le="0.1"} 1\n'
        'latency_seconds_bucket{le="1"} 2\n'
        'latency_seconds_bucket{le="+Inf"} 3\n'
        "latency_seconds_sum 5.5625\n"
        "latency_seconds_count 3\n"
        "# HELP queue_depth Depth\n"
        "# TYPE queue_depth gauge\n"
        "queue_depth 2\n"
        "# HELP requests_total Total requests\n"
        "# TYPE requests_total counter\n"
        'requests_total{method="get"} 3\n'
        'requests_total{method="post"} 1.5\n'
    )
    assert reg.prometheus_text() == expected


def test_prometheus_label_escaping():
    reg = MetricsRegistry()
    reg.counter("e_total", "e", labels=("p",)).inc(p='a"b\\c\nd')
    line = [ln for ln in reg.prometheus_text().splitlines()
            if ln.startswith("e_total{")][0]
    assert line == 'e_total{p="a\\"b\\\\c\\nd"} 1'


def test_dump_and_summary_shapes():
    reg = MetricsRegistry()
    reg.counter("a_total", "a").inc(2)
    reg.histogram("b_seconds", "b", buckets=(1.0,)).observe(0.5)
    d = reg.dump()
    assert d["a_total"]["type"] == "counter"
    assert d["a_total"]["series"][0] == {"labels": {}, "value": 2.0}
    assert d["b_seconds"]["series"][0]["buckets"] == {"1": 1, "+Inf": 1}
    s = reg.summary()
    assert s["a_total"] == 2.0
    assert s["b_seconds"]["count"] == 1
    json.dumps(s)                     # summary must be JSON-serializable


# -------------------------------------------------------------- tracing
def test_span_is_noop_while_disabled():
    s1 = monitor.span("x", a=1)
    s2 = monitor.span("y")
    assert s1 is s2                   # shared null object: zero allocation
    with s1:
        pass
    monitor.add_span("z", 0.0, 1.0)
    monitor.instant("i")
    assert monitor.trace_events() == []


def test_trace_spans_nest_and_threads_are_distinct(tmp_path):
    monitor.enable_tracing()
    with monitor.span("parent", phase="outer"):
        with monitor.span("child"):
            pass

    def worker():
        with monitor.span("worker_span"):
            pass

    t = threading.Thread(target=worker, name="trace-worker")
    t.start()
    t.join()
    monitor.instant("mark", step=3)
    path = str(tmp_path / "trace.json")
    n = monitor.save_trace(path)
    assert n == 4
    assert monitor.trace_events() == []           # save drains by default
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    spans = {e["name"]: e for e in events if e.get("ph") == "X"}
    parent, child = spans["parent"], spans["child"]
    assert parent["args"] == {"phase": "outer"}
    assert parent["tid"] == child["tid"]
    eps = 1.0
    assert parent["ts"] - eps <= child["ts"]
    assert child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + eps
    assert spans["worker_span"]["tid"] != parent["tid"]
    assert len({e["tid"] for e in events if e.get("ph") == "X"}) == 2
    names = {e["args"]["name"] for e in events
             if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert "trace-worker" in names
    marks = [e for e in events if e.get("ph") == "i"]
    assert marks and marks[0]["name"] == "mark" \
        and marks[0]["args"] == {"step": 3}


# ------------------------------------------------- fit instrumentation
def test_fit_records_metrics_and_nested_trace(tmp_path):
    monitor.enable_tracing()
    X, Y = _blobs()
    net = _small_net()
    net.fit((X, Y), epochs=2, batch_size=16, scan_steps=1)
    reg = monitor.REGISTRY
    assert reg.collect("train_iterations_total").value() == 6
    assert reg.collect("train_examples_total").value() == 96
    assert np.isfinite(reg.collect("train_score").value())
    assert reg.collect("train_step_seconds").snapshot()["count"] == 6
    assert reg.collect("train_host_sync_seconds").snapshot()["count"] == 6
    # prefetch wrap is on by default: ETL series must be present too
    assert reg.collect("etl_batches_prefetched_total").value() == 6
    assert reg.collect("etl_fetch_wait_seconds").snapshot()["count"] >= 6

    path = str(tmp_path / "fit_trace.json")
    monitor.save_trace(path)
    with open(path) as f:
        events = [e for e in json.load(f)["traceEvents"]
                  if e.get("ph") == "X"]
    epochs = [e for e in events if e["name"] == "train/epoch"]
    steps = [e for e in events if e["name"] == "train/step"]
    etls = [e for e in events if e["name"] == "train/etl"]
    stages = [e for e in events if e["name"] == "etl/stage"]
    assert len(epochs) == 2 and len(steps) == 6 and len(etls) == 6
    eps = 1.0
    for s in steps:                   # every step nests inside an epoch
        assert any(ep["tid"] == s["tid"]
                   and ep["ts"] - eps <= s["ts"]
                   and s["ts"] + s["dur"] <= ep["ts"] + ep["dur"] + eps
                   for ep in epochs)
    # prefetch staging runs on its own thread track
    assert stages and stages[0]["tid"] != steps[0]["tid"]


def test_fit_scan_path_records_iterations():
    X, Y = _blobs()
    net = _small_net()
    net.fit((X, Y), epochs=1, batch_size=16, scan_steps=3)
    reg = monitor.REGISTRY
    assert reg.collect("train_iterations_total").value() == 3


def test_performance_listener_consistent_and_feeds_registry():
    from deeplearning4j_tpu.train.listeners import PerformanceListener
    X, Y = _blobs()
    net = _small_net()
    lst = PerformanceListener(frequency=1, report=False)
    net.set_listeners(lst)
    net.fit((X, Y), epochs=1, batch_size=16, scan_steps=1)
    assert lst.history
    for rec in lst.history:
        assert rec["examples_per_sec"] == rec["samples_per_sec"]
        assert "etl_ms" in rec
    reg = monitor.REGISTRY
    assert reg.collect("train_examples_per_sec").value() > 0
    assert reg.collect("train_batches_per_sec").value() > 0
    assert reg.collect("train_etl_seconds").snapshot()["count"] \
        == len(lst.history)


# ------------------------------------------------ resilience integration
def test_resilience_nan_skip_increments_counter(tmp_path):
    from deeplearning4j_tpu.data.iterator import ArrayDataSetIterator
    from deeplearning4j_tpu.train.resilience import ResilientTrainer
    from deeplearning4j_tpu.util.faults import FaultInjector
    X, Y = _blobs()
    net = _small_net()
    report = ResilientTrainer(
        net, str(tmp_path / "ck"), save_every_n_iterations=100,
        injector=FaultInjector(nan_at=[1]),
    ).fit(ArrayDataSetIterator(X, Y, batch_size=16), epochs=1)
    assert report.skipped_steps == 1
    reg = monitor.REGISTRY
    assert reg.collect("resilience_steps_skipped_total").value() == 1
    assert reg.collect("resilience_checkpoints_written_total").value() >= 1
    assert reg.collect("resilience_checkpoint_save_seconds"
                       ).snapshot()["count"] >= 1
    assert reg.collect("train_iterations_total").value() \
        == report.applied_steps


# ------------------------------------------------- transport integration
def test_transport_metrics_bytes_and_messages():
    from deeplearning4j_tpu.parallel.transport import SocketTransport
    base = 30530 + os.getpid() % 200
    msg = (np.arange(3, dtype=np.int32), np.ones(3, np.int8), 1.0)
    with SocketTransport(0, 2, base_port=base) as t0, \
            SocketTransport(1, 2, base_port=base) as t1:
        t0.broadcast(0, msg)
        t1.broadcast(1, msg)
        t0.recv(1, timeout=30)
        t1.recv(1, timeout=30)
        reg = monitor.REGISTRY
        sent = reg.collect("transport_bytes_sent_total")
        rcvd = reg.collect("transport_bytes_received_total")
        assert sent.value(rank=0) == t0.bytes_sent > 0
        # the wire is lossless: rank 1's inbound bytes == rank 0's out
        assert rcvd.value(rank=1) == sent.value(rank=0)
        msgs = reg.collect("transport_messages_sent_total")
        assert msgs.value(rank=0) == 1 and msgs.value(rank=1) == 1
        assert reg.collect("transport_send_seconds"
                           ).snapshot(rank=0)["count"] == 1
        assert reg.collect("transport_recv_wait_seconds"
                           ).snapshot(rank=0)["count"] == 1
        assert reg.collect("transport_connects_total").value(rank=0) == 1


# ------------------------------------------------- inference integration
def test_inference_metrics_latency_and_batches():
    from deeplearning4j_tpu.parallel.inference import (
        InferenceMode, ParallelInference,
    )
    net = _small_net()
    x = np.random.RandomState(3).randn(4, 5).astype("float32")
    with ParallelInference(net, mode=InferenceMode.BATCHED,
                           max_batch_size=8) as pi:
        y = pi.output(x)
    assert y.shape == (4, 3)
    reg = monitor.REGISTRY
    assert reg.collect("inference_requests_total").value() == 1
    assert reg.collect("inference_request_seconds"
                       ).snapshot()["count"] == 1
    bsnap = reg.collect("inference_batch_size").snapshot()
    assert bsnap["count"] == 1 and bsnap["sum"] == 4


# ------------------------------------------------------ /metrics route
def _http_error(url, data=None):
    try:
        urllib.request.urlopen(url, data=data, timeout=10)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode())
    raise AssertionError(f"expected an HTTP error from {url}")


def test_ui_server_serves_prometheus_metrics():
    from deeplearning4j_tpu.ui.server import UIServer
    monitor.counter("scrape_probe_total", "probe").inc(7)
    monitor.histogram("scrape_lat_seconds", "probe",
                      buckets=(0.5,)).observe(0.1)
    server = UIServer(port=0)
    try:
        resp = urllib.request.urlopen(server.url + "metrics", timeout=10)
        body = resp.read().decode()
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("text/plain")
        assert "scrape_probe_total 7" in body
        assert 'scrape_lat_seconds_bucket{le="0.5"} 1' in body
        assert "# TYPE scrape_probe_total counter" in body
    finally:
        server.stop()


def test_ui_server_clean_errors_not_500():
    from deeplearning4j_tpu.ui.server import UIServer
    server = UIServer(port=0)
    try:
        code, body = _http_error(server.url + "train/data?sid=nope&after=0")
        assert code == 404 and "unknown session" in body["error"]
        code, body = _http_error(server.url + "train/data?sid=x&after=zzz")
        assert code == 400 and "after" in body["error"]
        # well-formed JSON that is not an object must 400, not 500
        code, body = _http_error(server.url + "remoteReceive",
                                 data=b"[1, 2, 3]")
        assert code == 400 and "bad body" in body["error"]
        code, body = _http_error(server.url + "tsne/post/s",
                                 data=b"not json at all")
        assert code == 400 and "bad body" in body["error"]
        code, body = _http_error(server.url + "no/such/route")
        assert code == 404
    finally:
        server.stop()


# ------------------------------------------------------------ CI smoke
@pytest.mark.slow
def test_telemetry_smoke_tool(tmp_path):
    out = str(tmp_path / "trace.json")
    r = subprocess.run(
        [sys.executable, os.path.join("tools", "telemetry_smoke.py"),
         "--trace-out", out],
        cwd=_REPO, capture_output=True, text=True, timeout=420,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    summary = json.loads(r.stdout)
    assert summary["ok"] and summary["metric_families"] >= 12
    assert os.path.exists(out)
