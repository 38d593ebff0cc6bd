"""ModelServer — the network-facing serving front end.

A threaded stdlib HTTP server (no new dependencies — the same
ThreadingHTTPServer pattern as ui/server.py) in front of a ModelRegistry:

    POST /v1/models/{name}/predict    JSON {"inputs": [...]} or raw .npy
    POST /v1/models/{name}/generate   LM token generation; SSE stream
                                      (chunked text/event-stream) or
                                      buffered JSON (``stream: false``)
    GET  /v1/models                   all servables, versions, status
    GET  /v1/models/{name}            one servable
    POST /v1/models/{name}/swap       {"source": <path|zoo:Arch>}
    POST /v1/models/{name}/rollback
    GET  /healthz                     process liveness (always 200)
    GET  /readyz                      200 only when warmed and not draining
    GET  /metrics                     Prometheus exposition (monitor/);
                                      ``?format=openmetrics`` adds
                                      trace exemplars + ``# EOF``
    GET  /v1/debug/flight             flight-recorder snapshot (monitor/
                                      flight.py): recent request
                                      timelines, postmortems, exemplars
    GET  /v1/slo                      SLO verdict (monitor/slo.py):
                                      burn rates + alert states, or
                                      {"enabled": false} when off
    GET  /v1/timeseries               windowed series views (monitor/
                                      timeseries.py): ?series=&window=

Every request adopts the caller's ``traceparent`` header (or mints a
fresh trace context at ingress), binds it to the handling thread so the
request/batch/decode spans carry one trace_id, opens a flight-recorder
record, and answers with an ``X-Trace-Id`` response header — see
docs/OBSERVABILITY.md "Tracing a single request". An unexpected 500
trips an automatic flight postmortem.

Failure discipline (the acceptance contract): admission control maps a
full request queue to **429** with Retry-After (bounded queue -> explicit
backpressure, never an unbounded latency collapse), an expired per-request
deadline to **504**, a draining/not-ready server to **503**, bad payloads
to **400**, and anything unexpected to a JSON **500** with the error class
only — a traceback never crosses the wire. Every response increments
``serving_requests_total{model,code}`` and observes
``serving_request_seconds`` so the /metrics scrape sees exactly what
clients saw.

Shutdown: `drain()` (wired to SIGTERM by the CLI) flips /readyz to 503 so
load balancers stop routing, lets in-flight + queued requests flush
through the batchers, then stops the listener — the serving analog of
ResilientTrainer's preemption-to-clean-exit contract.
"""
from __future__ import annotations

import io
import json
import logging
import random
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional
from urllib.parse import parse_qs, urlparse

import numpy as np

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.monitor import flight, slo, timeseries
from deeplearning4j_tpu.serving.batcher import (
    DeadlineExceededError, ServerDrainingError, ServerOverloadedError,
)
from deeplearning4j_tpu.serving import kvfabric
from deeplearning4j_tpu.serving.registry import ModelLoadError, ModelRegistry
from deeplearning4j_tpu.util import faults as fault_util
from deeplearning4j_tpu.util.platform import device_info

log = logging.getLogger("deeplearning4j_tpu")

_MAX_BODY = 256 << 20           # admission guard on Content-Length


def retry_after_seconds(queue_depth: int, queue_limit: int,
                        draining: bool = False,
                        rng: Optional[random.Random] = None) -> int:
    """Backpressure hint for 429/503 responses, derived and jittered.

    A constant Retry-After synchronizes every shed client into a retry
    stampede that re-saturates the queue at the exact same instant — the
    classic thundering herd. Instead: the *ceiling* of the hint scales
    with how far gone the server is (queue fullness, or a flat horizon
    while draining — a draining process never recovers, the client's
    next attempt belongs at the balancer), and the returned value is
    drawn uniformly from [1, ceiling] so retries spread out over the
    whole window. RFC 7231 requires integer delay-seconds, so jitter is
    realized as a per-response draw, not a fractional offset.
    """
    rng = rng if rng is not None else random
    if draining:
        ceiling = 5                       # replacement capacity, not ours
    else:
        fullness = min(1.0, queue_depth / max(1, queue_limit))
        ceiling = 1 + int(round(4 * fullness))
    return rng.randint(1, max(1, ceiling))


def metrics_payload(query: str):
    """``GET /metrics`` body + content type, shared with RouterServer.
    ``?format=openmetrics`` opts into the exemplar-carrying OpenMetrics
    exposition; the default stays the byte-identical v0.0.4 text."""
    fmt = parse_qs(query or "").get("format", [""])[0]
    if fmt == "openmetrics":
        return (monitor.openmetrics_text().encode(),
                "application/openmetrics-text; version=1.0.0; "
                "charset=utf-8")
    return (monitor.prometheus_text().encode(),
            "text/plain; version=0.0.4; charset=utf-8")


def timeseries_doc(ring, query: str) -> dict:
    """The ``GET /v1/timeseries`` document, shared with RouterServer.
    No ``series`` param lists the ring (names + coverage);
    ``series=<family>&window=<seconds>`` answers the typed windowed
    view, and every other query param pins a label value
    (e.g. ``&model=m``)."""
    if ring is None:
        return {"enabled": False}
    q = {k: v[0] for k, v in parse_qs(query or "").items()}
    series = q.pop("series", None)
    try:
        window = float(q.pop("window", 60.0))
    except (TypeError, ValueError):
        return {"enabled": True, "error": "window must be a number"}
    if series is None:
        doc = ring.describe()
    else:
        doc = ring.query(series, window, **q)
    doc["enabled"] = True
    return doc


class _Handler(BaseHTTPRequestHandler):
    server_version = "DL4JTPU-Serving/1.0"
    protocol_version = "HTTP/1.1"

    def log_message(self, *a):          # requests are metered, not logged
        pass

    # ------------------------------------------------------------- plumbing
    @property
    def _srv(self) -> "ModelServer":
        return self.server.model_server        # type: ignore[attr-defined]

    def _reply(self, code: int, body: bytes, ctype: str, extra=()):
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        ctx = getattr(self, "_trace_ctx", None)
        if ctx is not None:
            self.send_header("X-Trace-Id", ctx.trace_id)
        for k, v in extra:
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def _json(self, obj, code: int = 200, extra=()):
        self._reply(code, json.dumps(obj).encode(), "application/json",
                    extra)

    def _ingress(self):
        """Adopt/mint the request's trace context (None while tracing
        and the flight recorder are both disabled) and remember it so
        every response carries X-Trace-Id."""
        ctx = flight.request_context(
            self.headers.get(monitor.TRACEPARENT_HEADER), "server")
        self._trace_ctx = ctx
        return ctx

    def _meter(self, model: str, code: int, t0: float):
        if code == 404:
            # client-supplied names that don't resolve must not mint new
            # label sets — a URL prober would grow the registry unbounded
            model = "_unknown"
        monitor.counter("serving_requests_total",
                        "HTTP serving requests by model and status code",
                        labels=("model", "code")).inc(
            model=model, code=str(code))
        ctx = getattr(self, "_trace_ctx", None)
        monitor.histogram("serving_request_seconds",
                          "End-to-end HTTP request latency",
                          labels=("model",)).observe(
            time.perf_counter() - t0, model=model,
            exemplar=None if ctx is None else ctx.trace_id)

    def _body(self) -> bytes:
        try:
            length = int(self.headers.get("Content-Length", "0"))
        except (TypeError, ValueError):
            raise ValueError("bad Content-Length header")
        if length < 0 or length > _MAX_BODY:
            raise ValueError(f"unreasonable Content-Length {length}")
        return self.rfile.read(length)

    # ---------------------------------------------------------------- GET
    def do_GET(self):
        self._trace_ctx = None          # keep-alive: no stale ids
        url = urlparse(self.path)
        parts = [p for p in url.path.split("/") if p]
        if url.path == "/v1/debug/flight":
            self._json(flight.snapshot())
            return
        if url.path in ("/healthz", "/readyz"):
            try:
                # fault point: a wedged replica answers probes slowly (or
                # not at all) — exactly what the fleet supervisor's probe
                # deadline exists to catch
                self._srv.faults.on_probe()
            except Exception as e:      # noqa: BLE001 — injected blackhole
                self._json({"error": f"{type(e).__name__}: {e}"}, code=500)
                return
        if url.path == "/healthz":
            self._json({"status": "alive"})
            return
        if url.path == "/readyz":
            if self._srv.ready():
                self._json({"status": "ready",
                            "models": self._srv.registry.names(),
                            # the device this process serves from, as
                            # JAX reports it: a probe can tell a chip
                            # from a CPU that took its place
                            "device": device_info(),
                            "role": self._srv.role,
                            "rollout_generation":
                                self._srv.rollout_generation,
                            # KV-fabric publication: disaggregation role
                            # + per-LM leading-block ownership digests,
                            # consumed by the fleet probe for
                            # prefix-affinity routing
                            "kv_role": self._srv.kv_role,
                            "kv_ownership": self._srv.kv_ownership()})
            else:
                self._json({"status": "draining"
                            if self._srv.draining else "loading"}, code=503,
                           extra=(("Retry-After",
                                   self._srv.retry_after()),))
            return
        if url.path == "/v1/faults":
            if not self._srv.enable_faults:
                self._json({"error": "not found"}, code=404)
            else:
                self._json(self._srv.faults.describe())
            return
        if url.path == "/metrics":
            body, ctype = metrics_payload(url.query)
            self._reply(200, body, ctype)
            return
        if url.path == "/v1/slo":
            engine = self._srv.slo_engine or slo.default_engine()
            self._json(engine.verdict() if engine is not None
                       else {"enabled": False})
            return
        if url.path == "/v1/timeseries":
            ring = self._srv.timeseries_ring or timeseries.default_ring()
            self._json(timeseries_doc(ring, url.query))
            return
        if parts[:2] == ["v1", "models"]:
            if len(parts) == 2:
                self._json(self._srv.registry.describe())
                return
            if len(parts) == 3:
                served = self._srv.registry.get(parts[2])
                if served is None:
                    self._json({"error": f"unknown model {parts[2]!r}"},
                               code=404)
                else:
                    self._json(served.describe())
                return
        self._json({"error": "not found"}, code=404)

    # --------------------------------------------------------------- POST
    def do_POST(self):
        self._trace_ctx = None          # keep-alive: no stale ids
        url = urlparse(self.path)
        parts = [p for p in url.path.split("/") if p]
        if parts[:2] == ["v1", "models"] and len(parts) == 4:
            name, verb = parts[2], parts[3]
            if verb == "predict":
                self._predict(name, url)
                return
            if verb == "generate":
                self._generate(name, url)
                return
            if verb in ("swap", "rollback"):
                self._admin(name, verb)
                return
        if parts[:2] == ["v1", "models"] and len(parts) == 5 \
                and parts[3] == "kv" and parts[4] in ("export", "import"):
            self._kv(parts[2], parts[4])
            return
        if url.path == "/v1/rollout/role":
            # rollout control surface: the fleet's RolloutController (or
            # SubprocessReplica.set_role relaying for it) marks this
            # replica canary/stable so the replica's OWN /readyz agrees
            # with the fleet view operators see on /v1/fleet
            try:
                payload = json.loads(self._body() or b"{}")
                role = payload.get("role")
                if role not in ("stable", "canary"):
                    raise ValueError('role must be "stable" or "canary"')
                self._srv.role = role
                self._srv.rollout_generation = int(
                    payload.get("rollout_generation", 0))
            except (ValueError, TypeError) as e:
                self._json({"error": str(e)}, code=400)
                return
            self._json({"role": self._srv.role,
                        "rollout_generation": self._srv.rollout_generation})
            return
        if url.path == "/v1/faults" and self._srv.enable_faults:
            # chaos-tool surface: wedge/unwedge THIS replica mid-traffic.
            # Only exists when fault injection was requested at startup.
            try:
                payload = json.loads(self._body() or b"{}")
                if not isinstance(payload, dict):
                    raise ValueError("body must be a JSON object")
                self._srv.faults.set(**payload)
                self._json(self._srv.faults.describe())
            except (ValueError, TypeError) as e:
                self._json({"error": str(e)}, code=400)
            return
        self._json({"error": "not found"}, code=404)

    def _parse_inputs(self, url) -> np.ndarray:
        """Request payload -> float array. JSON {"inputs": nested lists}
        or a raw .npy body (Content-Type: application/octet-stream)."""
        body = self._body()
        ctype = (self.headers.get("Content-Type") or "").split(";")[0]
        if ctype == "application/octet-stream":
            x = np.load(io.BytesIO(body), allow_pickle=False)
        else:
            payload = json.loads(body or b"{}")
            if not isinstance(payload, dict) or "inputs" not in payload:
                raise ValueError('JSON body must be {"inputs": [...]}')
            x = np.asarray(payload["inputs"], "float32")
        if x.ndim == 0:
            raise ValueError("inputs must be at least rank 1")
        return x

    def _predict(self, name: str, url):
        t0 = time.perf_counter()
        ctx = self._ingress()
        q = parse_qs(url.query)
        served = self._srv.registry.get(name)
        if served is None:
            if self._srv.draining:
                # the drain emptied the registry — this is "server going
                # away" (503 + Retry-After), not "no such model" (404)
                self._meter(name, 503, t0)
                self._json({"error": "server draining"}, code=503,
                           extra=(("Retry-After", self._srv.retry_after()),))
                return
            self._meter(name, 404, t0)
            self._json({"error": f"unknown model {name!r}"}, code=404)
            return
        fr = flight.begin(ctx, "predict", model=name)
        code = 500
        try:
            with monitor.bind_context(ctx), \
                    monitor.span("serving/request", model=name):
                x = self._parse_inputs(url)
                batched = x.shape[1:] == served.input_shape
                if not batched and x.shape == served.input_shape:
                    x = x[None]          # single unbatched example
                try:
                    deadline = float(q["deadline_ms"][0]) / 1e3 \
                        if "deadline_ms" in q else self._srv.default_deadline
                except ValueError:
                    raise ValueError("deadline_ms must be a number")
                self._srv.faults.on_predict()
                y = served.predict(x, deadline=deadline)
                if not batched and y.shape[0] == 1:
                    y = y[0]
            accept = self.headers.get("Accept", "")
            code = 200
            if "application/octet-stream" in accept:
                buf = io.BytesIO()
                np.save(buf, np.asarray(y), allow_pickle=False)
                self._reply(200, buf.getvalue(), "application/octet-stream")
            else:
                self._json({
                    "model": name,
                    "version": served.active_info["version"],
                    "outputs": np.asarray(y).tolist(),
                    "latency_ms": round(
                        (time.perf_counter() - t0) * 1e3, 3),
                })
        except ServerOverloadedError as e:
            code = 429
            self._json({"error": str(e)}, code=429,
                       extra=(("Retry-After",
                               self._srv.retry_after(served)),))
        except DeadlineExceededError as e:
            code = 504
            self._json({"error": str(e)}, code=504)
        except ServerDrainingError as e:
            code = 503
            self._json({"error": str(e)}, code=503,
                       extra=(("Retry-After",
                               self._srv.retry_after(served)),))
        except ValueError as e:
            code = 400
            self._json({"error": str(e)}, code=400)
        except Exception as e:          # noqa: BLE001 — never a traceback
            code = 500
            log.exception("serving[%s]: predict failed", name)
            self._json({"error": f"{type(e).__name__}: {e}"}, code=500)
            flight.trip("http_5xx", model=name,
                        error=type(e).__name__,
                        trace_id=None if ctx is None else ctx.trace_id)
        finally:
            self._meter(name, code, t0)
            flight.finish(fr, "ok" if code == 200 else f"http_{code}",
                          code=code)

    # ---------------------------------------------------------- generation
    def _sse(self, obj) -> bytes:
        return b"data: " + json.dumps(obj).encode() + b"\n\n"

    def _chunk(self, data: bytes):
        """One HTTP/1.1 chunked-transfer frame (we stream without a
        Content-Length, so chunking is mandatory on a keep-alive wire)."""
        self.wfile.write(f"{len(data):X}\r\n".encode())
        self.wfile.write(data)
        self.wfile.write(b"\r\n")
        self.wfile.flush()

    def _generate(self, name: str, url):
        """POST /v1/models/{name}/generate — token-level generation on a
        decode servable (serving/decode.py). JSON body::

            {"prompt": [ids...], "max_tokens": 32, "temperature": 0.0,
             "top_k": 0, "eos_id": null, "stream": true}

        stream=true (default) answers ``text/event-stream`` over chunked
        transfer — one ``data: {"token": id, "index": i}`` event per
        generated token as it is sampled, closed by a ``done`` event
        with the finish reason. stream=false buffers the full generation
        into one JSON response. Status mapping matches predict: 429
        (join queue full, Retry-After), 503 (draining), 504 (deadline
        before the first token), 400 (bad prompt/params)."""
        t0 = time.perf_counter()
        ctx = self._ingress()
        q = parse_qs(url.query)
        served = self._srv.registry.get(name)
        if served is None:
            if self._srv.draining:
                self._meter(name, 503, t0)
                self._json({"error": "server draining"}, code=503,
                           extra=(("Retry-After", self._srv.retry_after()),))
                return
            self._meter(name, 404, t0)
            self._json({"error": f"unknown model {name!r}"}, code=404)
            return
        fr = flight.begin(ctx, "stream", model=name)
        code = 500
        self._gen_started = False
        req = None
        try:
            if not hasattr(served, "generate"):
                raise ValueError(
                    f"model {name!r} is a predict servable; generation "
                    "needs an LM deployed via --lm / deploy_lm")
            payload = json.loads(self._body() or b"{}")
            if not isinstance(payload, dict) or "prompt" not in payload:
                raise ValueError('JSON body must be {"prompt": [ids...]}')
            stream = bool(payload.get("stream", True))
            try:
                deadline = float(q["deadline_ms"][0]) / 1e3 \
                    if "deadline_ms" in q else self._srv.default_deadline
            except ValueError:
                raise ValueError("deadline_ms must be a number")
            self._srv.faults.on_predict()
            stream_attr = 1 if stream else 0
            with monitor.bind_context(ctx), \
                    monitor.span("serving/generate", model=name,
                                 stream=stream_attr):
                req = served.generate(
                    payload["prompt"],
                    max_new_tokens=int(payload.get("max_tokens", 32)),
                    temperature=float(payload.get("temperature", 0.0)),
                    top_k=int(payload.get("top_k", 0)),
                    eos_id=payload.get("eos_id"),
                    deadline=deadline)
                code = self._relay_generation(name, req, t0, deadline,
                                              stream)
        except ServerOverloadedError as e:
            code = 429
            self._json({"error": str(e)}, code=429,
                       extra=(("Retry-After",
                               self._srv.retry_after(served)),))
        except DeadlineExceededError as e:
            code = 504
            self._json({"error": str(e)}, code=504)
        except ServerDrainingError as e:
            code = 503
            self._json({"error": str(e)}, code=503,
                       extra=(("Retry-After",
                               self._srv.retry_after(served)),))
        except (ValueError, TypeError) as e:
            code = 400
            self._json({"error": str(e)}, code=400)
        except (BrokenPipeError, ConnectionResetError):
            # client went away mid-stream: free the slot, nothing to send
            code = 499
            if req is not None:
                req.cancel()
        except Exception as e:          # noqa: BLE001 — never a traceback
            code = 500
            log.exception("serving[%s]: generate failed", name)
            if req is not None:
                req.cancel()
            if not self._gen_started:   # headers not sent: clean JSON 500
                self._json({"error": f"{type(e).__name__}: {e}"}, code=500)
            flight.trip("http_5xx", model=name,
                        error=type(e).__name__,
                        trace_id=None if ctx is None else ctx.trace_id)
        finally:
            self._meter(name, code, t0)
            flight.finish(fr, "ok" if code == 200 else f"http_{code}",
                          code=code,
                          finish_reason=None if req is None
                          else req.finish_reason,
                          tokens=None if req is None else req.n_emitted,
                          cached_tokens=None if req is None
                          else req.cached_tokens)

    def _relay_generation(self, name: str, req, t0: float,
                          deadline: float, stream: bool) -> int:
        """Pump one GenerateRequest's event queue onto the wire. Returns
        the HTTP status metered for the request; raises the serving
        errors the caller maps (only BEFORE the first byte is sent)."""
        wait = max(0.05, deadline) + 5.0
        first = self._event(req, wait)
        # first event decides the status line: an error before any token
        # maps to a clean non-200 exactly like predict
        if first[0] == "error":
            raise first[1]
        if not stream:
            tokens = []
            ev = first
            while ev[0] == "token":
                tokens.append(ev[1])
                ev = self._event(req, wait)
            if ev[0] == "error":
                raise ev[1]
            info = ev[1]
            self._json({
                "model": name, "version": info.get("version"),
                "tokens": tokens,
                "finish_reason": info.get("finish_reason"),
                # prefix-cache telemetry per generation: prompt positions
                # served from shared KV pages + prefill chunk count (the
                # SSE path carries the same fields on its done event)
                "cached_tokens": info.get("cached_tokens"),
                "prefill_chunks": info.get("prefill_chunks"),
                # speculative-decoding telemetry: draft tokens proposed /
                # accepted and verify rounds for this stream (0 on plain
                # decode; the SSE done event carries the same fields)
                "spec_proposed": info.get("spec_proposed"),
                "spec_accepted": info.get("spec_accepted"),
                "spec_rounds": info.get("spec_rounds"),
                "ttft_ms": round((req.first_token_at - req.enqueued) * 1e3,
                                 3) if req.first_token_at else None,
                "latency_ms": round((time.perf_counter() - t0) * 1e3, 3),
            })
            return 200
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Cache-Control", "no-cache")
        self.send_header("Transfer-Encoding", "chunked")
        ctx = getattr(self, "_trace_ctx", None)
        if ctx is not None:
            self.send_header("X-Trace-Id", ctx.trace_id)
        if req.version is not None:
            self.send_header("X-Model-Version", str(req.version))
        self.end_headers()
        self._gen_started = True
        ev, index = first, 0
        while True:
            if ev[0] == "token":
                self._chunk(self._sse({"token": ev[1], "index": index}))
                index += 1
            elif ev[0] == "done":
                info = dict(ev[1])
                info["done"] = True
                self._chunk(self._sse(info))
                break
            else:                               # mid-stream failure
                self._chunk(self._sse(
                    {"error": f"{type(ev[1]).__name__}: {ev[1]}"}))
                break
            ev = self._event(req, wait)
        self.wfile.write(b"0\r\n\r\n")
        self.wfile.flush()
        return 200

    def _event(self, req, wait: float):
        """Next scheduler event, or a synthesized deadline error if the
        stream stalls past its budget."""
        import queue as _queue
        try:
            return req.events.get(timeout=wait)
        except _queue.Empty:
            req.cancel()
            return ("error", DeadlineExceededError(
                "generation produced no event within "
                f"{wait:.1f}s"))

    def _admin(self, name: str, verb: str):
        t0 = time.perf_counter()
        self._ingress()
        served = self._srv.registry.get(name)
        if served is None:
            if self._srv.draining:
                self._meter(name, 503, t0)
                self._json({"error": "server draining"}, code=503,
                           extra=(("Retry-After", self._srv.retry_after()),))
                return
            self._meter(name, 404, t0)
            self._json({"error": f"unknown model {name!r}"}, code=404)
            return
        code = 500
        try:
            if verb == "swap":
                payload = json.loads(self._body() or b"{}")
                source = payload.get("source") \
                    if isinstance(payload, dict) else None
                if not source:
                    raise ValueError('body must be {"source": <path>}')
                info = served.swap(source)
            else:
                info = served.rollback()
            code = 200
            self._json({"model": name, "active": info})
        except ServerDrainingError as e:
            # swap/rollback racing a drain is an expected shutdown-window
            # outcome, not a server fault — 503, never a 500
            code = 503
            self._json({"error": str(e)}, code=503,
                       extra=(("Retry-After",
                               self._srv.retry_after(served)),))
        except (ValueError, ModelLoadError) as e:
            code = 400
            self._json({"error": str(e)}, code=400)
        except Exception as e:          # noqa: BLE001
            code = 500
            log.exception("serving[%s]: %s failed", name, verb)
            self._json({"error": f"{type(e).__name__}: {e}"}, code=500)
            flight.trip("http_5xx", model=name, verb=verb,
                        error=type(e).__name__)
        finally:
            self._meter(name, code, t0)

    # ----------------------------------------------------------- kv fabric
    def _kv(self, name: str, verb: str):
        """POST /v1/models/{name}/kv/export — JSON {"prompt": [ids...]}
        answered with the framed page-transfer blob (octet-stream);
        POST /v1/models/{name}/kv/import — a blob produced by export,
        landed into this replica's prefix cache. The disaggregation wire:
        a prefill replica answers export, the decode replica's import
        adopts the pages, and the subsequent generate is a prefix-cache
        hit. Corrupt/truncated frames map to a clean 400 — never a
        scheduler-thread death (kvfabric verifies before any pool
        write)."""
        t0 = time.perf_counter()
        ctx = self._ingress()
        served = self._srv.registry.get(name)
        if served is None:
            if self._srv.draining:
                self._meter(name, 503, t0)
                self._json({"error": "server draining"}, code=503,
                           extra=(("Retry-After", self._srv.retry_after()),))
                return
            self._meter(name, 404, t0)
            self._json({"error": f"unknown model {name!r}"}, code=404)
            return
        code = 500
        nbytes = 0
        try:
            if not hasattr(served, "export_prefix"):
                raise ValueError(
                    f"model {name!r} is a predict servable; the KV "
                    "fabric needs an LM deployed via --lm / deploy_lm")
            with monitor.bind_context(ctx), \
                    monitor.span(f"serving/kv_{verb}", model=name):
                if verb == "export":
                    payload = json.loads(self._body() or b"{}")
                    if not isinstance(payload, dict) \
                            or "prompt" not in payload:
                        raise ValueError(
                            'JSON body must be {"prompt": [ids...]}')
                    blob = served.export_prefix(payload["prompt"])
                    nbytes = len(blob)
                    code = 200
                    self._reply(200, blob, "application/octet-stream")
                else:
                    body = self._body()
                    nbytes = len(body)
                    info = served.import_prefix(body)
                    code = 200
                    self._json(dict(info, model=name))
        except ServerOverloadedError as e:
            code = 429
            self._json({"error": str(e)}, code=429,
                       extra=(("Retry-After",
                               self._srv.retry_after(served)),))
        except DeadlineExceededError as e:
            code = 504
            self._json({"error": str(e)}, code=504)
        except ServerDrainingError as e:
            code = 503
            self._json({"error": str(e)}, code=503,
                       extra=(("Retry-After",
                               self._srv.retry_after(served)),))
        except (ValueError, TypeError) as e:
            # kvfabric.FrameError subclasses ValueError: a corrupt or
            # mismatched shipment is the sender's fault, not ours
            code = 400
            self._json({"error": f"{type(e).__name__}: {e}"}, code=400)
        except Exception as e:          # noqa: BLE001 — never a traceback
            code = 500
            log.exception("serving[%s]: kv %s failed", name, verb)
            self._json({"error": f"{type(e).__name__}: {e}"}, code=500)
            flight.trip("http_5xx", model=name, verb=f"kv_{verb}",
                        error=type(e).__name__,
                        trace_id=None if ctx is None else ctx.trace_id)
        finally:
            outcome = "ok" if code == 200 else (
                "rejected" if code == 400 else "error")
            monitor.counter(
                "serving_transfer_requests_total",
                "KV page-transfer requests by direction and outcome",
                labels=("model", "direction", "outcome")).inc(
                model=name, direction=verb, outcome=outcome)
            if nbytes:
                monitor.counter(
                    "serving_transfer_bytes_total",
                    "Serialized KV page bytes moved over the fabric",
                    labels=("model", "direction")).inc(
                    nbytes, model=name, direction=verb)
            monitor.histogram(
                "serving_transfer_seconds",
                "KV page transfer handling latency",
                labels=("model", "direction"),
                buckets=(0.005, 0.025, 0.1, 0.25, 1, 2.5, 10, 30)
            ).observe(time.perf_counter() - t0, model=name,
                      direction=verb)
            self._meter(name, code, t0)


class ModelServer:
    """HTTP front end over a ModelRegistry.

    Usage:
        registry = ModelRegistry()
        registry.deploy("lenet", "zoo:LeNet")
        server = ModelServer(registry, port=8500)   # serving immediately
        ...
        server.drain()                              # graceful stop
    """

    def __init__(self, registry: Optional[ModelRegistry] = None,
                 host: str = "127.0.0.1", port: int = 0,
                 default_deadline_s: float = 30.0,
                 enable_faults: bool = False,
                 retry_jitter: Optional[random.Random] = None,
                 faults: Optional[fault_util.ServingFaults] = None,
                 slo_engine=None, timeseries_ring=None,
                 kv_role: str = "mixed"):
        self.registry = registry if registry is not None else ModelRegistry()
        self.default_deadline = float(default_deadline_s)
        self.enable_faults = bool(enable_faults)
        # GET /v1/slo and /v1/timeseries sources; None falls back to the
        # process defaults (slo.default_engine() / timeseries.
        # default_ring()) so the CLI's enable_* calls just work
        self.slo_engine = slo_engine
        self.timeseries_ring = timeseries_ring
        # fault toggles are per-server injectable so in-process fleets
        # can wedge ONE replica; the default stays the process singleton
        # (env-armed subprocess children, existing tests)
        self.faults = faults if faults is not None \
            else fault_util.serving_faults()
        self._retry_rng = retry_jitter          # None -> module-level random
        if self.enable_faults:
            self.faults.apply_env()
        self.draining = False
        # rollout state mirrored from the fleet (POST /v1/rollout/role):
        # surfaced on /readyz so operators and the drill can see which
        # replica is under canary evaluation
        self.role = "stable"
        self.rollout_generation = 0
        # KV-fabric disaggregation role: "prefill" replicas compute KV
        # for long prompts and ship pages, "decode" replicas only serve
        # generation, "mixed" (default) does both — published on /readyz
        if kv_role not in ("prefill", "decode", "mixed"):
            raise ValueError(
                f'kv_role must be "prefill", "decode" or "mixed", '
                f"got {kv_role!r}")
        self.kv_role = kv_role
        self._httpd = ThreadingHTTPServer((host, port), _Handler)
        self._httpd.model_server = self          # type: ignore[attr-defined]
        self.host = host
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True, name="ModelServer")
        self._thread.start()
        log.info("serving: listening on http://%s:%d", host, self.port)

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def ready(self) -> bool:
        return not self.draining and self.registry.all_ready()

    def kv_ownership(self) -> dict:
        """Per-LM prefix-ownership advertisement for /readyz: the block
        size plus the leading-block digests this replica can serve warm
        (HBM-resident or spill-tier). The fleet probe stashes this on
        the replica handle; the router's affinity pick consumes it."""
        own = {}
        for name in self.registry.names():
            served = self.registry.get(name)
            sched = getattr(served, "scheduler", None)
            if sched is None:
                continue
            engine = sched.admitting_engine()
            if engine is None or not engine.cfg.prefix_cache:
                continue
            own[name] = {"block": int(engine.cfg.page_size),
                         "digests": engine.cache.ownership_digests()}
        return own

    @staticmethod
    def _queue_state(served):
        """(depth, limit) of a servable's admission queue — predict
        servables expose the batcher queue, decode servables the join
        queue (ServedLM.queue_state)."""
        batcher = getattr(served, "batcher", None)
        if batcher is not None:
            return batcher._queue.qsize(), batcher._queue.maxsize or 1
        state = getattr(served, "queue_state", None)
        if state is not None:
            depth, limit = state()
            return depth, limit or 1
        return 0, 1

    def retry_after(self, served=None) -> str:
        """Derived, jittered Retry-After header value for 429/503
        responses (see retry_after_seconds). Uses the deepest admission
        queue when no specific servable is implicated."""
        depth, limit = 0, 1
        if served is not None:
            depth, limit = self._queue_state(served)
        else:
            for name in self.registry.names():
                m = self.registry.get(name)
                if m is None:
                    continue
                d, lim = self._queue_state(m)
                if lim and d / lim >= depth / limit:
                    depth, limit = d, lim
        return str(retry_after_seconds(depth, limit,
                                       draining=self.draining,
                                       rng=self._retry_rng))

    def drain(self, timeout: float = 30.0):
        """Graceful shutdown: stop admitting (readyz -> 503 so the load
        balancer drains us), flush in-flight and queued requests, then
        stop the listener."""
        if self.draining:
            return
        self.draining = True
        monitor.counter("serving_drains_total",
                        "Graceful drain/shutdown sequences").inc()
        log.warning("serving: draining (readyz now 503; flushing queues)")
        self.registry.shutdown(drain=True, timeout=timeout)
        self.stop()
        log.warning("serving: drained and stopped")

    def stop(self):
        self._httpd.shutdown()
        self._httpd.server_close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        if not self.draining:
            self.drain(timeout=5.0)
