"""Cross-process request tracing + flight recorder (PR 13).

Covers: W3C-traceparent context parse/mint/bind, automatic trace_id
attachment to spans, the zero-cost-when-disabled guard (the acceptance
contract: with tracing and the flight recorder off, the request path's
span sites allocate nothing), the flight recorder ring/trip lifecycle,
histogram trace_id exemplars, per-layer propagation (batcher, HTTP
server, decode scheduler, supervisor wedge postmortems), trace_report
merging, and — as the slow acceptance test — ONE trace_id spanning the
real CLI fleet (router + 2 subprocess replicas) merged into one valid
Perfetto document.
"""
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.monitor import flight
from deeplearning4j_tpu.monitor import trace as trace_mod

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "tools"))
import trace_report  # noqa: E402  (tools/ is not a package)


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Every test starts and ends with tracing + flight disabled and
    empty buffers — the library default other suites rely on."""
    monitor.disable_tracing()
    monitor.clear_trace()
    flight.disable_flight()
    flight.clear()
    yield
    monitor.disable_tracing()
    monitor.clear_trace()
    flight.disable_flight()
    flight.clear()


def _net(seed=0):
    from deeplearning4j_tpu.nn.conf.base import InputType
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.updaters import Adam
    conf = (NeuralNetConfiguration.Builder().seed(seed).updater(Adam(1e-2))
            .list()
            .layer(DenseLayer(n_out=8, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(6)).build())
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    return MultiLayerNetwork(conf).init()


# ------------------------------------------------------------ the context
def test_traceparent_roundtrip():
    ctx = monitor.mint_context()
    assert len(ctx.trace_id) == 32 and len(ctx.span_id) == 16
    parsed = monitor.parse_traceparent(ctx.header())
    assert parsed.trace_id == ctx.trace_id
    assert parsed.span_id == ctx.span_id
    child = parsed.child()
    assert child.trace_id == ctx.trace_id
    assert child.parent_id == ctx.span_id
    assert child.span_id != ctx.span_id


@pytest.mark.parametrize("bad", [
    None, "", "garbage", "00-short-beef-01", "00-" + "g" * 32 + "-" +
    "a" * 16 + "-01", "00-" + "0" * 32 + "-" + "a" * 16 + "-01",
    "00-" + "a" * 32 + "-" + "0" * 16 + "-01",
    "00-" + "a" * 31 + "-" + "b" * 16 + "-01",
    # int(x, 16) would accept these; strict hex must not
    "00-" + "a" * 29 + "_bb" + "-" + "b" * 16 + "-01",
    "00-" + "a" * 32 + "-" + " " + "b" * 15 + "-01",
])
def test_traceparent_rejects_malformed(bad):
    assert monitor.parse_traceparent(bad) is None


def test_span_attaches_bound_context():
    monitor.enable_tracing()
    ctx = monitor.mint_context()
    with monitor.bind_context(ctx):
        with monitor.span("a", k=1):
            pass
        assert monitor.current_context() is ctx
    assert monitor.current_context() is None
    with monitor.span("b"):          # outside any binding: no trace_id
        pass
    monitor.add_span("c", 0.0, 1.0, ctx=ctx)          # explicit override
    evs = {e["name"]: e for e in monitor.trace_events()}
    assert evs["a"]["args"]["trace_id"] == ctx.trace_id
    assert evs["a"]["args"]["k"] == 1
    assert "trace_id" not in evs["b"].get("args", {})
    assert evs["c"]["args"]["trace_id"] == ctx.trace_id


def test_disabled_request_path_allocates_nothing():
    """The acceptance guard: tracing + flight disabled means the span
    sites hand out the ONE shared null object, the ingress returns
    None, and nothing is recorded anywhere."""
    assert monitor.span("x", model="m") is monitor.span("y", n=3)
    assert flight.request_context("00-" + "a" * 32 + "-" + "b" * 16
                                  + "-01", "server") is None
    assert flight.begin(monitor.mint_context(), "predict") is None
    flight.note("deadbeef", "event")            # no-op, no error
    flight.finish(None, "ok")
    with monitor.bind_context(None):
        assert monitor.current_context() is None
    assert monitor.trace_events() == []
    assert flight.snapshot()["records"] == []


def test_request_context_minted_vs_adopted():
    flight.enable_flight()
    minted = flight.request_context(None, "router")
    assert minted is not None and minted.parent_id is None
    adopted = flight.request_context(minted.header(), "server")
    assert adopted.trace_id == minted.trace_id
    assert adopted.parent_id == minted.span_id
    # malformed header -> fresh mint, never a crash
    fresh = flight.request_context("not-a-header", "server")
    assert fresh is not None and fresh.parent_id is None


# ------------------------------------------------------- flight recorder
def test_flight_ring_and_multi_layer_notes(tmp_path):
    flight.enable_flight(capacity=4)
    ctx = monitor.mint_context()
    router_rec = flight.begin(ctx, "route", model="m", cls="batch")
    server_rec = flight.begin(ctx, "predict", model="m")
    # a note by context lands in EVERY open record of the request
    flight.note(ctx, "dispatch", wait_ms=1.5)
    flight.finish(server_rec, "ok", code=200)
    flight.finish(router_rec, "ok", code=200)
    snap = flight.snapshot()
    assert len(snap["records"]) == 2
    for rec in snap["records"]:
        assert rec["trace_id"] == ctx.trace_id
        assert rec["events"][0]["event"] == "dispatch"
        assert rec["outcome"] == "ok" and rec["duration_ms"] >= 0
    # the ring is bounded at capacity
    for _ in range(10):
        flight.finish(flight.begin(monitor.mint_context(), "predict"),
                      "ok")
    assert len(flight.snapshot()["records"]) == 4
    # open records are bounded too, evicting the OLDEST — never the
    # record just opened
    flight.clear()
    handles = [flight.begin(monitor.mint_context(), "predict")
               for _ in range(6)]
    live_ids = {rec["trace_id"] for rec in flight.snapshot()["live"]}
    assert live_ids == {h["trace_id"] for h in handles[-4:]}


def test_flight_trip_dumps_postmortem_with_cooldown(tmp_path):
    flight.enable_flight(capacity=8, dump_dir=str(tmp_path))
    rec = flight.begin(monitor.mint_context(), "route", model="m")
    flight.note(rec["trace_id"], "shed", cls="batch")
    flight.finish(rec, "shed_429", code=429)
    path = flight.trip("replica_wedged", replica="r-1", generation=3)
    assert path is not None and os.path.isfile(path)
    doc = json.load(open(path))
    assert doc["reason"] == "replica_wedged"
    assert doc["meta"] == {"replica": "r-1", "generation": 3}
    assert any(r["outcome"] == "shed_429" and
               r["events"][0]["event"] == "shed"
               for r in doc["records"])
    # cooldown: an immediate second trip for the SAME reason is absorbed
    assert flight.trip("replica_wedged", replica="r-1") is None
    # ... but a different reason dumps
    assert flight.trip("breaker_open", replica="r-0") is not None
    assert len(flight.postmortems()) == 2


def test_histogram_exemplars():
    h = monitor.histogram("test_exemplar_seconds", "x", labels=("m",))
    h.observe(0.007, m="a")                       # no exemplar: fine
    h.observe(0.3, exemplar="trace-slow", m="a")
    h.observe(0.004, exemplar="trace-fast", m="a")
    ex = h.exemplars(m="a")
    assert ex["0.5"] == {"value": 0.3, "trace_id": "trace-slow"}
    assert ex["0.005"] == {"value": 0.004, "trace_id": "trace-fast"}
    series = monitor.dump()["test_exemplar_seconds"]["series"][0]
    assert series["exemplars"]["0.5"]["trace_id"] == "trace-slow"
    # exemplars never leak into the classic text exposition
    assert "trace-slow" not in monitor.prometheus_text()


# ------------------------------------------------------- batcher + server
def test_batcher_propagates_request_context():
    from deeplearning4j_tpu.serving.batcher import ShapeBucketedBatcher
    monitor.enable_tracing()
    flight.enable_flight()
    ctx = monitor.mint_context()
    fr = flight.begin(ctx, "predict", model="bt")
    b = ShapeBucketedBatcher(lambda x: x * 2.0, input_shape=(4,),
                             buckets=(1, 8), name="bt")
    try:
        with monitor.bind_context(ctx):
            y = b.predict(np.ones((2, 4), "float32"))
        assert y.shape == (2, 4)
    finally:
        b.shutdown()
    flight.finish(fr, "ok", code=200)
    evs = [e for e in monitor.trace_events() if e.get("ph") == "X"
           and (e.get("args") or {}).get("trace_id") == ctx.trace_id]
    names = {e["name"] for e in evs}
    assert "serving/queue_wait" in names
    assert "serving/batch" in names
    rec = flight.snapshot()["records"][-1]
    ev_names = [e["event"] for e in rec["events"]]
    assert "dispatch" in ev_names
    # no warm(): the live request paid the bucket compile — the flight
    # timeline must say so
    assert "bucket_compile" in ev_names


def test_server_http_propagation_and_debug_endpoint():
    from deeplearning4j_tpu.serving.registry import ModelRegistry
    from deeplearning4j_tpu.serving.server import ModelServer
    monitor.enable_tracing()
    flight.enable_flight()
    registry = ModelRegistry()
    registry.deploy("m", _net(), buckets=(1, 8))
    server = ModelServer(registry, port=0)
    try:
        client_tid = "ab" * 16
        body = json.dumps({"inputs": [[0.1] * 6]}).encode()
        r = urllib.request.urlopen(urllib.request.Request(
            server.url + "/v1/models/m/predict", data=body,
            headers={"Content-Type": "application/json",
                     "traceparent": f"00-{client_tid}-{'cd' * 8}-01"}),
            timeout=30)
        r.read()
        assert r.status == 200
        # the response names the trace; the adopted id is the client's
        assert r.headers.get("X-Trace-Id") == client_tid
        # a request WITHOUT a header gets a server-minted id
        r2 = urllib.request.urlopen(urllib.request.Request(
            server.url + "/v1/models/m/predict", data=body,
            headers={"Content-Type": "application/json"}), timeout=30)
        r2.read()
        minted = r2.headers.get("X-Trace-Id")
        assert minted and minted != client_tid
        # replica-side spans carry the client's trace id
        evs = [e for e in monitor.trace_events() if e.get("ph") == "X"
               and (e.get("args") or {}).get("trace_id") == client_tid]
        assert {"serving/request", "serving/batch",
                "serving/queue_wait"} <= {e["name"] for e in evs}
        # the debug endpoint exposes the finished record + exemplars
        doc = json.loads(urllib.request.urlopen(
            server.url + "/v1/debug/flight", timeout=10).read())
        recs = {rec["trace_id"]: rec for rec in doc["records"]}
        assert client_tid in recs and minted in recs
        assert recs[client_tid]["outcome"] == "ok"
        assert recs[client_tid]["code"] == 200
        assert "serving_request_seconds" in doc["exemplars"]
    finally:
        server.drain(timeout=5)


# --------------------------------------------------------- decode stream
class _FakeCache:
    def __init__(self, slots):
        self.slots = slots
        self.seq_lens = np.zeros((slots,), np.int32)
        self._active = set()

    def admit(self, n):
        for s in range(self.slots):
            if s not in self._active:
                self._active.add(s)
                self.seq_lens[s] = n
                return s
        return None

    def active_slots(self):
        return sorted(self._active)

    def ensure_page(self, s):
        return True

    def release(self, s):
        self._active.discard(s)

    def register_prefix(self, slot, tokens):
        pass


class _FakeEngine:
    max_context = 128
    prefill_chunk_tokens = 0          # chunking off: one-shot prefill
    spec_enabled = False              # no speculative draft engine

    def __init__(self, slots=2):
        self.cache = _FakeCache(slots)
        self.closed = False

    def draft_prefill_origin(self, slot):
        return None

    def draft_prefill_done(self, slot, prompt):
        pass

    def release_slot(self, slot):
        self.cache.release(slot)

    def admit_prompt(self, prompt):
        from deeplearning4j_tpu.serving.kvcache import AdmitInfo
        slot = self.cache.admit(len(prompt))
        return None if slot is None else AdmitInfo(slot, 0)

    def prefill(self, slot, prompt, temperature, top_k):
        with monitor.span("serving/prefill", model="fake", bucket=8):
            return 1, None

    def step(self, exclude=()):
        act = np.zeros((self.cache.slots,), bool)
        for s in self.cache.active_slots():
            if s in set(exclude):
                continue
            act[s] = True
            self.cache.seq_lens[s] += 1
        return np.full((self.cache.slots,), 2, np.int32), act, None

    def close(self):
        self.closed = True


def test_decode_scheduler_stream_spans_and_flight_timeline():
    from deeplearning4j_tpu.serving.decode import (
        DecodeScheduler, GenerateRequest,
    )
    monitor.enable_tracing()
    flight.enable_flight()
    ctx = monitor.mint_context()
    sched = DecodeScheduler("fake", queue_limit=4)
    sched.install(_FakeEngine(), version=1)
    fr = flight.begin(ctx, "stream", model="fake")
    with monitor.bind_context(ctx):
        req = GenerateRequest([1, 2, 3], max_new_tokens=3)
    assert req.ctx is ctx
    sched.submit(req)
    assert req.done.wait(5.0), "stream did not finish"
    sched.drain(timeout=2.0)
    flight.finish(fr, "ok", code=200)
    evs = [e for e in monitor.trace_events() if e.get("ph") == "X"
           and (e.get("args") or {}).get("trace_id") == ctx.trace_id]
    names = {e["name"] for e in evs}
    assert "serving/prefill" in names            # bound around prefill
    assert "serving/stream" in names             # whole-stream span
    assert "decode/itl_gap" in names             # per-token-gap spans
    stream = next(e for e in evs if e["name"] == "serving/stream")
    assert stream["args"]["reason"] == "length"
    assert stream["args"]["tokens"] == 3
    rec = flight.snapshot()["records"][-1]
    ev_names = [e["event"] for e in rec["events"]]
    assert ev_names[0] == "queued"
    assert "admitted" in ev_names and "finish" in ev_names
    admitted = next(e for e in rec["events"] if e["event"] == "admitted")
    assert admitted["engine_version"] == 1


def test_router_passes_traceparent_through_when_recorder_off():
    """With the router's tracing AND flight recorder off (the autouse
    fixture's state), a client's traceparent must still reach the
    replica untouched — recorder-enabled replicas downstream keep the
    trace intact."""
    from deeplearning4j_tpu.serving.fleet import Replica
    from deeplearning4j_tpu.serving.router import ResilientRouter
    seen = {}

    def transport(replica, path, body, headers, timeout):
        seen.update(headers)
        return 200, {"Content-Type": "application/json"}, b"{}"

    rep = Replica("r0")
    rep.url = "http://fake"
    router = ResilientRouter(lambda: [rep], transport=transport,
                             hedge=False)
    hdr = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
    code, _, _ = router.route_predict("m", b"{}", {"Traceparent": hdr})
    assert code == 200
    assert seen.get("traceparent") == hdr


def test_subprocess_replica_argv_threads_flight_knobs():
    """--no-flight / --flight-records / --trace-out / --postmortem-dir
    must reach every subprocess replica, not just the router."""
    from deeplearning4j_tpu.serving.fleet import (
        ReplicaSpec, SubprocessReplica,
    )
    spec = ReplicaSpec([("m", "zoo:LeNet")], flight=False,
                       trace_out="/t/fleet.json", postmortem_dir="/t/pm")
    argv = SubprocessReplica("replica-0", spec)._argv()
    assert "--no-flight" in argv
    assert "/t/fleet.replica-0.json" in argv
    assert "--postmortem-dir" in argv and "/t/pm" in argv
    spec2 = ReplicaSpec([("m", "zoo:LeNet")], flight_records=64)
    argv2 = SubprocessReplica("replica-1", spec2)._argv()
    assert argv2[argv2.index("--flight-records") + 1] == "64"
    assert "--no-flight" not in argv2


# ------------------------------------------------- supervisor wedge trip
def test_supervisor_wedge_trips_postmortem(tmp_path):
    import random
    from deeplearning4j_tpu.serving.fleet import Replica, ReplicaSupervisor

    class FakeReplica(Replica):
        def __init__(self, name, spec=None):
            super().__init__(name, spec)
            self.alive_flag = False
            self.probe_ok = True

        def launch(self):
            self.alive_flag = True
            self.url = "http://fake"

        def alive(self):
            return self.alive_flag

        def kill(self):
            self.alive_flag = False

    flight.enable_flight(capacity=8, dump_dir=str(tmp_path))
    clock = [0.0]
    sup = ReplicaSupervisor(
        lambda i: FakeReplica(f"f{i}"), 1, unhealthy_after=2,
        time_fn=lambda: clock[0], sleep_fn=lambda s: None,
        rng=random.Random(0),
        probe_fn=lambda r, timeout: r.probe_ok and r.alive(),
        spawn_fn=lambda fn, name: (fn(), None)[1])
    (r,) = sup.replicas
    r.launch()
    sup.tick()                                   # ready (probe ok)
    assert r.state == "ready"
    r.probe_ok = False                           # wedged: alive, no probes
    for _ in range(2):
        clock[0] += 1.0
        sup.tick()
    dumps = [f for f in os.listdir(tmp_path)
             if "replica_wedged" in f and f.endswith(".json")]
    assert dumps, "wedge detection did not dump a postmortem"
    doc = json.load(open(tmp_path / dumps[0]))
    assert doc["meta"]["replica"] == "f0"
    assert doc["meta"]["generation"] == 0
    assert doc["meta"]["probe_failures"] == 2


# ----------------------------------------------------------- trace merge
def _seg(pid, name, trace_id=None, label=None):
    args = {"trace_id": trace_id} if trace_id else {}
    return {"traceEvents": [
        {"name": name, "ph": "X", "ts": 1.0, "dur": 2.0, "pid": pid,
         "tid": 7, "args": args},
        {"name": "thread_name", "ph": "M", "pid": pid, "tid": 7,
         "args": {"name": "worker"}},
    ]}


def test_trace_report_merges_and_remaps_pid_collisions(tmp_path):
    a, b = tmp_path / "router.json", tmp_path / "replica.json"
    tid = "ee" * 16
    json.dump(_seg(42, "serving/route", tid), open(a, "w"))
    json.dump(_seg(42, "serving/request", tid), open(b, "w"))  # SAME pid
    doc = trace_report.merge_trace_files([("router", str(a)),
                                          ("replica-0", str(b))])
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert len({e["pid"] for e in spans}) == 2, \
        "colliding pids were not remapped onto separate tracks"
    pnames = {e["pid"]: e["args"]["name"] for e in doc["traceEvents"]
              if e.get("ph") == "M" and e["name"] == "process_name"}
    assert sorted(pnames.values()) == ["replica-0", "router"]
    # both spans still carry the trace id; the filter keeps them + meta
    sub = trace_report.filter_to_trace(doc, tid)
    kept = [e for e in sub["traceEvents"] if e.get("ph") == "X"]
    assert {e["name"] for e in kept} == {"serving/route",
                                         "serving/request"}
    json.loads(json.dumps(sub))                   # still valid JSON


def test_trace_report_cli_errors_on_missing_input(tmp_path, capsys):
    rc = trace_report.main([str(tmp_path / "nope.json")])
    assert rc == 2
    rc = trace_report.main(["--trace-id", "ff" * 16,
                            str(tmp_path / "nope.json")])
    assert rc == 2


# --------------------------------------- the CLI-fleet acceptance (slow)
@pytest.mark.slow
def test_cli_fleet_one_request_one_trace_merged(tmp_path):
    """Acceptance: a single client request through the CLI fleet (router
    + 2 subprocess replicas) yields ONE trace_id present in router,
    replica-server, and batcher spans, and trace_report merges the
    per-process segments into one valid Perfetto trace."""
    from deeplearning4j_tpu.util.serialization import save_model
    model_zip = str(tmp_path / "model.zip")
    save_model(_net(), model_zip)
    trace_out = str(tmp_path / "fleet.json")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-m", "deeplearning4j_tpu.serving",
         "--model", f"m={model_zip}", "--replicas", "2",
         "--replica-mode", "subprocess", "--port", "0",
         "--buckets", "1,8", "--trace-out", trace_out,
         "--postmortem-dir", str(tmp_path / "pm"),
         "--drain-timeout-s", "10"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        cwd=_REPO, env=env)
    try:
        line = proc.stdout.readline()
        ann = json.loads(line)
        assert ann.get("role") == "router", ann
        url = ann["serving"]
        body = json.dumps({"inputs": [[0.1] * 6]}).encode()
        r = urllib.request.urlopen(urllib.request.Request(
            url + "/v1/models/m/predict", data=body,
            headers={"Content-Type": "application/json"}), timeout=60)
        r.read()
        assert r.status == 200
        tid = r.headers.get("X-Trace-Id")
        assert tid, "router response carries no X-Trace-Id"
        served_by = r.headers.get("X-Served-By")
        assert served_by in ("replica-0", "replica-1")
    finally:
        proc.send_signal(2)                       # SIGINT -> fleet drain
        try:
            proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            proc.kill()
            raise
    assert proc.returncode == 0
    segments = [("router", trace_out)]
    for i in range(2):
        seg = str(tmp_path / f"fleet.replica-{i}.json")
        assert os.path.isfile(seg), f"replica {i} saved no trace segment"
        segments.append((f"replica-{i}", seg))
    merged = trace_report.merge_trace_files(segments)
    json.loads(json.dumps(merged))                # valid Perfetto JSON
    spans = trace_report.events_for_trace(merged, tid)
    names = {e["name"] for e in spans}
    pids = {e["pid"] for e in spans}
    assert "serving/route" in names, names        # router hop
    assert "serving/request" in names, names      # replica server hop
    assert names & {"serving/batch", "serving/queue_wait"}, names
    assert len(pids) >= 2, \
        f"trace {tid} did not cross a process boundary: {sorted(names)}"
    # the filtered single-request view stays loadable
    sub = trace_report.filter_to_trace(merged, tid)
    assert trace_report.events_for_trace(sub, tid)
