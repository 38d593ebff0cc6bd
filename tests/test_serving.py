"""Model-serving subsystem tests (serving/ registry + batcher + server).

Covers the acceptance contract: registry load/verify/swap/rollback,
bucket padding with at-most-once-compile-per-bucket, 429 under a
saturated queue, expired deadline -> 504, the live healthz -> readyz ->
predict -> swap-under-traffic round trip, and serving_* families on the
server's own /metrics. Small FF nets keep CPU compiles sub-second; the
zoo-LeNet end-to-end lives in tools/serve_smoke.py.
"""
import json
import logging
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.nn.conf.base import InputType
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updaters import Adam
from deeplearning4j_tpu.serving import (
    DeadlineExceededError, ModelLoadError, ModelRegistry, ModelServer,
    ServerOverloadedError, ShapeBucketedBatcher, load_servable,
)

N_IN, N_OUT = 6, 3


def _net(seed=0):
    conf = (NeuralNetConfiguration.Builder().seed(seed).updater(Adam(1e-2))
            .list()
            .layer(DenseLayer(n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=N_OUT, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(N_IN)).build())
    return MultiLayerNetwork(conf).init()


def _deploy(registry, name="m", seed=0, **kw):
    kw.setdefault("buckets", (1, 4, 16))
    kw.setdefault("max_delay_ms", 2.0)
    return registry.deploy(name, _net(seed), **kw)


def _post(url, body: bytes, timeout=30, ctype="application/json"):
    req = urllib.request.Request(url, data=body,
                                 headers={"Content-Type": ctype})
    r = urllib.request.urlopen(req, timeout=timeout)
    return r.status, json.loads(r.read())


@pytest.fixture
def registry():
    reg = ModelRegistry()
    yield reg
    reg.shutdown(drain=False)


# ---------------------------------------------------------------- registry
def test_load_servable_sources(tmp_path):
    from deeplearning4j_tpu.util.serialization import save_model
    # live object passes through
    net = _net()
    assert load_servable(net) is net
    # save_model zip
    path = str(tmp_path / "m.zip")
    save_model(net, path)
    loaded = load_servable(path)
    x = np.random.RandomState(0).randn(2, N_IN).astype("float32")
    np.testing.assert_allclose(np.asarray(net.output(x)),
                               np.asarray(loaded.output(x)), atol=1e-6)
    # zoo: name resolution (no init — that's model_by_name's caller)
    from deeplearning4j_tpu.models import model_by_name
    assert type(model_by_name("lenet")).__name__ == "LeNet"
    with pytest.raises(KeyError):
        model_by_name("NoSuchArch")
    # unknown path
    with pytest.raises(ModelLoadError):
        load_servable(str(tmp_path / "missing.zip"))


def test_load_servable_checkpoint_dir_verifies_sha(tmp_path):
    """Manifest-directory source: newest SHA-256-verified entry wins; a
    corrupted newest checkpoint falls back to the next-newest."""
    from deeplearning4j_tpu.train.resilience import CheckpointManager
    ckdir = str(tmp_path / "ckpts")
    mgr = CheckpointManager(ckdir, keep_last=3)
    net_a, net_b = _net(1), _net(2)
    mgr.save(net_a, {"step_in_epoch": 0})
    path_b = mgr.save(net_b, {"step_in_epoch": 0})
    x = np.random.RandomState(0).randn(2, N_IN).astype("float32")
    # newest (net_b) loads
    loaded = load_servable(ckdir)
    np.testing.assert_allclose(np.asarray(loaded.output(x)),
                               np.asarray(net_b.output(x)), atol=1e-6)
    # corrupt newest -> falls back to net_a
    with open(path_b, "r+b") as f:
        f.seek(20)
        f.write(b"\xde\xad\xbe\xef")
    loaded = load_servable(ckdir)
    np.testing.assert_allclose(np.asarray(loaded.output(x)),
                               np.asarray(net_a.output(x)), atol=1e-6)
    # empty/never-valid dir
    with pytest.raises(ModelLoadError):
        load_servable(str(tmp_path / "empty"))


def test_registry_swap_and_rollback(registry):
    served = _deploy(registry, seed=0)
    x = np.random.RandomState(0).randn(3, N_IN).astype("float32")
    y1 = served.predict(x)
    info = served.swap(_net(1))
    assert info["version"] == 2
    y2 = served.predict(x)
    assert not np.allclose(y1, y2, atol=1e-6)
    info = served.rollback()
    assert info["version"] == 1
    np.testing.assert_allclose(served.predict(x), y1, atol=1e-6)
    # rollback below the history floor is a clean error
    with pytest.raises(ModelLoadError):
        served.rollback()


def test_swap_rejects_incompatible_input_shape(registry):
    served = _deploy(registry)
    wide = (NeuralNetConfiguration.Builder().seed(0).updater(Adam(1e-2))
            .list()
            .layer(OutputLayer(n_out=N_OUT, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(N_IN + 1)).build())
    with pytest.raises(ModelLoadError, match="swap rejected"):
        served.swap(MultiLayerNetwork(wide).init())
    # still serving v1 afterwards
    assert served.versions[served.active].version == 1
    served.predict(np.zeros((2, N_IN), "float32"))


# ----------------------------------------------------------------- batcher
def test_bucket_padding_correct_and_compiles_once(registry):
    monitor.REGISTRY.reset()
    served = _deploy(registry, seed=3)
    net = served.versions[0].model
    rs = np.random.RandomState(1)
    for n in (1, 2, 3, 4, 5, 7, 11, 16):
        x = rs.randn(n, N_IN).astype("float32")
        y = served.predict(x)
        assert y.shape == (n, N_OUT)
        np.testing.assert_allclose(y, np.asarray(net.output(x)), atol=1e-5)
    # ledger: every bucket compiled exactly once (at warmup), and the
    # varied request sizes above added NO request-path compiles
    fam = monitor.REGISTRY.collect("serving_bucket_compiles_total")
    for b in served.batcher.buckets:
        assert fam.value(model="m", bucket=str(b)) == 1
    warmups = monitor.REGISTRY.collect("serving_warmup_runs_total")
    assert warmups.value(model="m") == len(served.batcher.buckets)


def test_bucket_oversize_request_chunks_to_ladder(registry):
    monitor.REGISTRY.reset()
    served = _deploy(registry, seed=4)      # max bucket 16
    net = served.versions[0].model
    x = np.random.RandomState(2).randn(41, N_IN).astype("float32")
    y = served.predict(x)
    assert y.shape == (41, N_OUT)
    np.testing.assert_allclose(y, np.asarray(net.output(x)), atol=1e-5)
    fam = monitor.REGISTRY.collect("serving_bucket_compiles_total")
    total = sum(fam.value(model="m", bucket=str(b))
                for b in served.batcher.buckets)
    assert total == len(served.batcher.buckets)     # chunking, no new shape


def test_batcher_coalesces_concurrent_requests():
    """Concurrent callers coalesce into one device batch (run-count < N)."""
    runs = []

    def runner(x):
        runs.append(x.shape[0])
        time.sleep(0.01)
        return x * 2.0

    with ShapeBucketedBatcher(runner, (N_IN,), buckets=(1, 4, 16),
                              max_delay_ms=25.0, name="co") as b:
        b.warm()
        runs.clear()
        outs = [None] * 8

        def call(i):
            outs[i] = b.predict(np.full((1, N_IN), float(i), "float32"))

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i in range(8):
            np.testing.assert_allclose(outs[i], np.full((1, N_IN),
                                                        2.0 * i), atol=0)
        assert len(runs) < 8            # coalescing actually happened


def test_batcher_queue_full_raises_overload():
    release = threading.Event()

    def slow_runner(x):
        release.wait(5)
        return x

    with ShapeBucketedBatcher(slow_runner, (N_IN,), buckets=(1,),
                              max_delay_ms=0.0, queue_limit=2,
                              name="oq") as b:
        def quiet_predict():
            try:
                b.predict(np.zeros((1, N_IN), "float32"))
            except Exception:  # noqa: BLE001 — races are the main path's
                pass

        # stall the worker on the first request...
        stalled = threading.Thread(target=quiet_predict, daemon=True)
        stalled.start()
        time.sleep(0.2)
        # ...fill the bounded queue behind it...
        waiters = [threading.Thread(target=quiet_predict, daemon=True)
                   for _ in range(2)]
        for t in waiters:
            t.start()
        time.sleep(0.2)
        # ...then require explicit backpressure, not silent queueing
        with pytest.raises(ServerOverloadedError):
            b.predict(np.zeros((1, N_IN), "float32"))
        release.set()
        stalled.join(timeout=5)
        for t in waiters:
            t.join(timeout=5)


def test_batcher_deadline_expired_in_queue():
    def slow_runner(x):
        time.sleep(0.3)
        return x

    with ShapeBucketedBatcher(slow_runner, (N_IN,), buckets=(1,),
                              max_delay_ms=0.0, name="dl") as b:
        t1 = threading.Thread(
            target=lambda: b.predict(np.zeros((1, N_IN), "float32")),
            daemon=True)
        t1.start()                       # occupies the worker ~0.3s
        time.sleep(0.05)
        with pytest.raises(DeadlineExceededError):
            b.predict(np.zeros((1, N_IN), "float32"), deadline=0.05)
        t1.join(timeout=5)


# ------------------------------------------------------------------ server
@pytest.fixture
def server(registry):
    _deploy(registry, seed=0)
    srv = ModelServer(registry, port=0, default_deadline_s=30.0)
    yield srv
    srv.stop()


def test_server_predict_json_and_npy(server):
    url = f"{server.url}/v1/models/m/predict"
    x = np.random.RandomState(0).randn(3, N_IN).astype("float32")
    code, out = _post(url, json.dumps({"inputs": x.tolist()}).encode())
    assert code == 200 and out["version"] == 1
    assert np.asarray(out["outputs"]).shape == (3, N_OUT)
    # npy in, npy out
    import io
    buf = io.BytesIO()
    np.save(buf, x, allow_pickle=False)
    req = urllib.request.Request(
        url, data=buf.getvalue(),
        headers={"Content-Type": "application/octet-stream",
                 "Accept": "application/octet-stream"})
    r = urllib.request.urlopen(req, timeout=30)
    y = np.load(io.BytesIO(r.read()), allow_pickle=False)
    assert y.shape == (3, N_OUT)
    # single unbatched example round-trips unbatched
    code, out = _post(url, json.dumps(
        {"inputs": x[0].tolist()}).encode())
    assert np.asarray(out["outputs"]).shape == (N_OUT,)


def test_server_clean_errors_never_traceback(server):
    url = server.url
    # unknown model -> 404
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(f"{url}/v1/models/nope/predict", b'{"inputs": [[1]]}')
    assert e.value.code == 404 and "error" in json.loads(e.value.read())
    # malformed body -> 400
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(f"{url}/v1/models/m/predict", b"not json")
    assert e.value.code == 400
    # wrong feature width -> 400
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(f"{url}/v1/models/m/predict",
              json.dumps({"inputs": [[1.0, 2.0]]}).encode())
    assert e.value.code == 400
    # bad swap body -> 400
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(f"{url}/v1/models/m/swap", b"{}")
    assert e.value.code == 400


def test_server_deadline_504(server, registry):
    served = registry.get("m")
    real = served.batcher.runner
    served.batcher.runner = lambda x: (time.sleep(0.2), real(x))[1]
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(f"{server.url}/v1/models/m/predict?deadline_ms=0.01",
                  json.dumps({"inputs": np.zeros((1, N_IN)).tolist()}
                             ).encode())
        assert e.value.code == 504
        assert "error" in json.loads(e.value.read())
    finally:
        served.batcher.runner = real


def test_server_saturated_queue_429(registry):
    served = _deploy(registry, name="sat", queue_limit=2)
    release = threading.Event()
    real = served.batcher.runner
    served.batcher.runner = lambda x: (release.wait(10), real(x))[1]
    # short default deadline: a probe that DOES get admitted behind the
    # stalled worker 504s quickly instead of hanging out its socket
    srv = ModelServer(registry, port=0, default_deadline_s=0.5)
    try:
        url = f"{srv.url}/v1/models/sat/predict"
        body = json.dumps({"inputs": np.zeros((1, N_IN)).tolist()}).encode()

        def quiet_post():
            try:
                _post(url, body, timeout=30)
            except Exception:  # noqa: BLE001 — a racy 429 here is fine too
                pass

        threads = [threading.Thread(target=quiet_post, daemon=True)
                   for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.5)                  # worker stalled, queue filling
        saw_429 = False
        for _ in range(8):
            try:
                _post(url, body, timeout=5)
            except urllib.error.HTTPError as e:
                e.read()
                if e.code == 429:
                    saw_429 = True
                    # derived + jittered, never the old constant stampede
                    # magnet: an integer in the [1, 5] ceiling range
                    assert 1 <= int(e.headers.get("Retry-After")) <= 5
                    break
            except Exception:  # noqa: BLE001 — admitted probe timed out
                pass           # behind the stall; keep probing for the 429
        assert saw_429
        release.set()
        for t in threads:
            t.join(timeout=10)
    finally:
        release.set()
        served.batcher.runner = real
        srv.stop()


def test_health_ready_swap_under_traffic_round_trip(registry):
    """The acceptance round trip: healthz -> readyz -> predict under
    concurrent load -> hot-swap -> rollback mid-traffic, zero failures."""
    _deploy(registry, name="rt", seed=0)
    srv = ModelServer(registry, port=0)
    try:
        url = srv.url
        assert urllib.request.urlopen(f"{url}/healthz",
                                      timeout=10).status == 200
        assert urllib.request.urlopen(f"{url}/readyz",
                                      timeout=10).status == 200
        predict = f"{url}/v1/models/rt/predict"
        rs = np.random.RandomState(0)
        bodies = [json.dumps({"inputs": rs.rand(b, N_IN).tolist()}).encode()
                  for b in (1, 2, 4)]
        results = {"ok": 0, "fail": []}
        lock = threading.Lock()
        versions = set()
        # event-gated so the traffic deterministically SPANS the swap
        # window: on a fast box all 80 predicts used to finish before
        # the swap landed (versions == {1}, flaky). Workers hold half
        # their requests until the swap returned, and the rollback
        # waits until some predict actually observed v2.
        swap_live = threading.Event()
        seen_v2 = threading.Event()

        def worker(k):
            for i in range(20):
                if i == 10:
                    swap_live.wait(timeout=60)
                try:
                    code, out = _post(predict, bodies[(k + i) % 3])
                    with lock:
                        results["ok"] += 1
                        versions.add(out["version"])
                    if out["version"] == 2:
                        seen_v2.set()
                except Exception as e:  # noqa: BLE001
                    with lock:
                        results["fail"].append(repr(e))

        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.05)
        from deeplearning4j_tpu.util.serialization import save_model
        import tempfile, os
        v2 = os.path.join(tempfile.mkdtemp(prefix="srvt_"), "v2.zip")
        save_model(_net(9), v2)
        code, _ = _post(f"{url}/v1/models/rt/swap",
                        json.dumps({"source": v2}).encode(), timeout=60)
        assert code == 200
        swap_live.set()
        assert seen_v2.wait(timeout=60), "no predict observed v2 live"
        code, _ = _post(f"{url}/v1/models/rt/rollback", b"{}", timeout=60)
        assert code == 200
        for t in threads:
            t.join(timeout=60)
        assert results["fail"] == []
        assert results["ok"] == 80
        assert 2 in versions             # the swap was observed live
    finally:
        srv.stop()


def test_metrics_families_on_server(server):
    _post(f"{server.url}/v1/models/m/predict",
          json.dumps({"inputs": np.zeros((2, N_IN)).tolist()}).encode())
    text = urllib.request.urlopen(f"{server.url}/metrics",
                                  timeout=10).read().decode()
    for fam in ("serving_requests_total", "serving_request_seconds",
                "serving_batch_size", "serving_queue_depth",
                "serving_bucket_compiles_total",
                "serving_warmup_runs_total", "serving_model_ready"):
        assert fam in text, f"missing {fam} on /metrics"
    assert 'serving_requests_total{model="m",code="200"}' in text


def test_drain_flips_readyz_and_flushes(registry):
    from deeplearning4j_tpu.serving import ServerDrainingError
    served = _deploy(registry, name="dr")
    srv = ModelServer(registry, port=0)
    url = srv.url
    assert urllib.request.urlopen(f"{url}/readyz", timeout=10).status == 200
    srv.drain(timeout=10)
    assert srv.draining and not srv.ready()
    # the batcher stopped admitting — no request can sneak in post-drain
    with pytest.raises(ServerDrainingError):
        served.predict(np.zeros((1, N_IN), "float32"))


def test_retry_after_derived_from_queue_and_jittered(registry):
    """The 429/503 Retry-After header derives from queue fullness and is
    jittered per response (no synchronized client retry stampede): a
    saturated queue must produce spread across the [1, ceiling] range."""
    import random as _random

    from deeplearning4j_tpu.serving.batcher import _Request

    served = _deploy(registry, name="ra", queue_limit=8)
    srv = ModelServer(registry, port=0,
                      retry_jitter=_random.Random(7))
    release = threading.Event()
    entered = threading.Event()
    real = served.batcher.runner
    try:
        # stall the worker inside the runner, then stuff the queue to the
        # brim directly — exact, reproducible queue depth, no HTTP races
        def stall_runner(x):
            entered.set()
            release.wait(10)
            return real(x)

        served.batcher.runner = stall_runner
        stalled = threading.Thread(
            target=lambda: served.predict(np.zeros((1, N_IN), "float32")),
            daemon=True)
        stalled.start()
        assert entered.wait(10)            # worker now inside the stall
        for _ in range(8):
            served.batcher._queue.put_nowait(
                _Request(np.zeros((1, N_IN), "float32"), None))
        # full queue -> ceiling 5, jittered draws spread over [1, 5]
        values = {int(srv.retry_after(served)) for _ in range(40)}
        assert values <= {1, 2, 3, 4, 5} and len(values) >= 3, values
        # and the live HTTP 429 carries one of those derived values
        url = f"{srv.url}/v1/models/ra/predict"
        body = json.dumps({"inputs": np.zeros((1, N_IN)).tolist()}).encode()
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(url, body, timeout=10)
        assert e.value.code == 429
        assert 1 <= int(e.value.headers["Retry-After"]) <= 5
        e.value.read()
        # empty queue, not draining -> always the 1s floor
        release.set()
        stalled.join(timeout=10)
        for _ in range(600):               # generous: loaded CI boxes
            if served.batcher._queue.empty():
                break
            time.sleep(0.05)
        assert served.batcher._queue.empty(), "batcher never drained"
        assert {int(srv.retry_after(served)) for _ in range(20)} == {1}
    finally:
        release.set()
        served.batcher.runner = real
        srv.stop()
    # draining server: readyz 503 carries the flat drain horizon
    srv2 = ModelServer(registry, port=0)
    try:
        srv2.draining = True
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(f"{srv2.url}/readyz", timeout=10)
        assert e.value.code == 503
        assert 1 <= int(e.value.headers["Retry-After"]) <= 5
        e.value.read()
    finally:
        srv2.draining = False
        srv2.stop()


def test_drain_race_inflight_predict_and_swap_never_5xx_or_hang(registry):
    """The graceful-drain race matrix: concurrent SIGTERM-equivalent
    drain + in-flight predicts + a hot-swap must produce only
    {200, 429, 503, 504} (never a 500-class server error) and every
    socket must complete — no request may hang past its timeout and no
    connection may be torn mid-response."""
    _deploy(registry, name="race", seed=0)
    srv = ModelServer(registry, port=0, default_deadline_s=5.0)
    url = f"{srv.url}/v1/models/race/predict"
    rs = np.random.RandomState(0)
    bodies = [json.dumps({"inputs": rs.rand(b, N_IN).tolist()}).encode()
              for b in (1, 2, 4)]
    outcomes = []
    violations = []
    lock = threading.Lock()
    start = threading.Barrier(8 + 2, timeout=10)
    drain_started = [None]                  # wall time the drain began

    def predictor(k):
        start.wait()
        for i in range(15):
            try:
                code, _ = _post(url, bodies[(k + i) % 3], timeout=15)
                kind = code
            except urllib.error.HTTPError as e:
                e.read()
                kind = e.code
            except Exception as e:  # noqa: BLE001
                # connection-level outcome. AFTER the drain began, the
                # contract moved to the balancer (/readyz went 503):
                # clients that keep hammering a stopping listener get
                # refused/reset — acceptable. BEFORE it: a violation.
                ds = drain_started[0]
                if ds is not None and time.monotonic() >= ds:
                    kind = f"conn_after_drain:{type(e).__name__}"
                else:
                    kind = f"violation:{type(e).__name__}"
            with lock:
                outcomes.append(kind)
                if isinstance(kind, str) and kind.startswith("violation"):
                    violations.append(kind)

    # pinned BEFORE the race: the registry pops the servable at drain
    # start, so a late registry.get() would race get-vs-undeploy (None)
    # instead of the swap-vs-drain contract under test — ServedModel.swap
    # on a draining servable must raise ServerDrainingError either way
    race_served = registry.get("race")

    def swapper():
        start.wait()
        time.sleep(0.02)
        # the same race the HTTP swap verb runs: losing to the drain must
        # surface as an explicit draining error (503), never a 500
        try:
            race_served.swap(_net(5))
            with lock:
                outcomes.append("swap:200")
        except Exception as e:  # noqa: BLE001
            from deeplearning4j_tpu.serving import ServerDrainingError
            with lock:
                outcomes.append(f"swap:{type(e).__name__}")
                if not isinstance(e, ServerDrainingError):
                    violations.append(f"swap:{type(e).__name__}")

    def drainer():
        start.wait()
        # let real traffic land first (the 200-in-codes half of the
        # assertion), then race the drain against the rest of it
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            with lock:
                if any(o == 200 for o in outcomes):
                    break
            time.sleep(0.005)
        drain_started[0] = time.monotonic()
        srv.drain(timeout=10)

    threads = [threading.Thread(target=predictor, args=(k,))
               for k in range(8)]
    threads.append(threading.Thread(target=swapper))
    threads.append(threading.Thread(target=drainer))
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    hung = [t for t in threads if t.is_alive()]
    assert not hung, f"{len(hung)} threads hung past the drain"
    assert not violations, f"drain race violations: {violations}"
    codes = {o for o in outcomes if isinstance(o, int)}
    assert codes <= {200, 429, 503, 504}, codes
    assert 200 in codes                     # traffic really flowed


def test_drain_racing_swap_returns_503_not_500(registry):
    """A swap that loses the race with shutdown gets an explicit
    ServerDrainingError (HTTP 503), never a 500."""
    from deeplearning4j_tpu.serving import ServerDrainingError
    served = _deploy(registry, name="ds")
    served.shutdown(drain=False)
    with pytest.raises(ServerDrainingError):
        served.swap(_net(3))
    with pytest.raises(ServerDrainingError):
        served.rollback()


def test_fault_endpoint_gated_and_wedges_probes(registry):
    """/v1/faults exists only with enable_faults; a wedged server fails
    its probes the way the supervisor expects (500 on probe_error)."""
    from deeplearning4j_tpu.util.faults import serving_faults
    _deploy(registry, name="fz")
    plain = ModelServer(registry, port=0)
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(f"{plain.url}/v1/faults", b'{"probe_error": true}')
        assert e.value.code == 404          # hidden without the flag
        e.value.read()
    finally:
        plain.stop()
    srv = ModelServer(registry, port=0, enable_faults=True)
    try:
        code, doc = _post(f"{srv.url}/v1/faults", b'{"probe_error": true}')
        assert code == 200 and doc["probe_error"] is True
        for path in ("/healthz", "/readyz"):
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(srv.url + path, timeout=10)
            assert e.value.code == 500
            e.value.read()
        # unknown fault key -> clean 400
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(f"{srv.url}/v1/faults", b'{"nope": 1}')
        assert e.value.code == 400
        e.value.read()
        # clearing restores the probes
        code, doc = _post(f"{srv.url}/v1/faults", b'{"probe_error": false}')
        assert code == 200
        assert urllib.request.urlopen(f"{srv.url}/healthz",
                                      timeout=10).status == 200
    finally:
        serving_faults().clear()
        srv.stop()


def test_fault_injection_is_per_server_instance(registry):
    """Two servers with their own ServingFaults instances: wedging one
    must not wedge the other (in-process fleet replicas rely on this)."""
    from deeplearning4j_tpu.util.faults import ServingFaults

    _deploy(registry, name="iso")
    srv_a = ModelServer(registry, port=0, enable_faults=True,
                        faults=ServingFaults())
    srv_b = ModelServer(registry, port=0, enable_faults=True,
                        faults=ServingFaults())
    try:
        code, _ = _post(f"{srv_a.url}/v1/faults", b'{"probe_error": true}')
        assert code == 200
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(f"{srv_a.url}/healthz", timeout=10)
        assert e.value.code == 500
        e.value.read()
        # sibling server is untouched
        assert urllib.request.urlopen(f"{srv_b.url}/healthz",
                                      timeout=10).status == 200
    finally:
        srv_a.stop()
        srv_b.stop()


# -------------------------------------------------------------- satellites
def test_uint8_no_preprocessor_warns_once(caplog):
    from deeplearning4j_tpu.data import records as records_mod
    from deeplearning4j_tpu.data.records import (
        RecordReader, RecordReaderDataSetIterator,
    )

    class FakeImages(RecordReader):
        is_image = True

        def records(self):
            for i in range(4):
                yield (np.full((4, 4, 1), 100, np.uint8), i % 2)

    records_mod._warned_raw_uint8 = False
    it = RecordReaderDataSetIterator(FakeImages(), batch_size=2,
                                     label_index=-1, num_classes=2)
    with caplog.at_level(logging.WARNING, logger="deeplearning4j_tpu"):
        list(it)
        list(it)                         # second epoch: still once
    hits = [r for r in caplog.records
            if "no pre_processor" in r.getMessage()]
    assert len(hits) == 1
    # with a normalizer attached: silent
    from deeplearning4j_tpu.data.normalization import (
        ImagePreProcessingScaler,
    )
    records_mod._warned_raw_uint8 = False
    it2 = RecordReaderDataSetIterator(FakeImages(), batch_size=2,
                                      label_index=-1, num_classes=2)
    it2.set_pre_processor(ImagePreProcessingScaler())
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="deeplearning4j_tpu"):
        list(it2)
    assert not [r for r in caplog.records
                if "no pre_processor" in r.getMessage()]


def test_device_norm_kill_switch_semantics(monkeypatch):
    """DL4J_TPU_DEVICE_NORM: only the documented '0' disables — 'true',
    'yes', '' behave as enabled, matching DL4J_TPU_FLASH/HOST_CAST."""
    from deeplearning4j_tpu.data.iterator import ArrayDataSetIterator
    from deeplearning4j_tpu.data.normalization import (
        ImagePreProcessingScaler, engaged_device_affine,
    )
    it = ArrayDataSetIterator(np.zeros((8, 4), "float32"),
                              np.zeros((8, 2), "float32"), batch_size=4)
    it.set_pre_processor(ImagePreProcessingScaler())
    for val, engaged in (("0", False), ("1", True), ("true", True),
                         ("yes", True)):
        monkeypatch.setenv("DL4J_TPU_DEVICE_NORM", val)
        with engaged_device_affine(it) as aff:
            assert (aff is not None) == engaged, (val, aff)
        assert it.pre_processor is not None      # always restored


def test_accum_partial_group_warns(caplog):
    from deeplearning4j_tpu.data.iterator import ArrayDataSetIterator
    rs = np.random.RandomState(0)
    X = rs.randn(10, N_IN).astype("float32")     # batch 4 -> 4,4,2 tail
    Y = np.eye(N_OUT, dtype="float32")[rs.randint(0, N_OUT, 10)]
    it = ArrayDataSetIterator(X, Y, batch_size=4, drop_last=False)
    net = _net()
    with caplog.at_level(logging.WARNING, logger="deeplearning4j_tpu"):
        net.fit(it, epochs=1, accumulate_steps=2, prefetch=False)
    hits = [r for r in caplog.records
            if "accumulation group" in r.getMessage()]
    assert len(hits) == 1
    assert "shape changed" in hits[0].getMessage()
