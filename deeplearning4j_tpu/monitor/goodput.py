"""Goodput ledger — wall-clock attribution for training (and decode).

`examples_per_sec` answers "how fast"; this module answers "where did
the time go". A `GoodputLedger` consumes the span stream the fit loops
already emit (`train/etl`, `train/host_sync`, `xla/compile`, the
resilience checkpoint spans, the chunked path's `train/stage` /
`train/launch` / `train/loss_fetch`, the prefetch pump's
`etl/queue_wait`, plus the emission points this module added:
`train/device_wait`, `train/resume_replay`, `resilience/eval_gate`) and
attributes every wall-clock second of a `fit()` to exactly ONE of a
closed category set:

==============  ======================================================
category        meaning
==============  ======================================================
step_compute    device executing the compiled step (the goodput)
data_wait       blocked on the ETL/input pipeline (`etl/queue_wait`
                and the rest of `train/etl`)
host_sync       the deliberate loss fetch's D2H transfer + Python
compile         XLA compilation (`xla/compile`)
checkpoint      checkpoint save/restore IO
eval_gate       blessing-gate evaluation between checkpoints
resume_replay   fast-forwarding an iterator after preempt->resume
other           everything unattributed (framework overhead, listener
                callbacks, logging, ...)
==============  ======================================================

Exclusivity is the contract: the categories of a finished session sum
to its measured wall-clock exactly (`other` is defined as the
remainder), which `tools/telemetry_smoke.py` enforces in CI against an
externally measured wall-clock. Only LEAF spans carry a category: a
parent (`train/chunk`, `train/dispatch`, `train/chunk_sync`) is never
summed with its children, and a span around a pull from the iterator
(`train/etl`, `train/resume_replay`) counts only what its
`etl/queue_wait` children left over.

Zero-cost-when-disabled follows `span()`/flight: while disabled the fit
loops' `add_span()` calls keep their original single-flag fast path and
`device_wait()` degrades to a bare `block_until_ready()`. Enabling
installs the ledger as the trace-module span sink, so attribution works
whether or not tracing itself is on.

Extras carried by the ledger:

- live `train_goodput_pct` gauge + `train_time_seconds_total{category}`
  counters, and a per-session summary in `FitReport`
  (`goodput_pct`, `time_by_category`);
- a per-step anomaly detector — rolling median/MAD over the
  step-to-step wall spacing (`train/step` ends on the per-call paths,
  `train/chunk` ends on the chunked ones); a spike logs one WARNING
  and fires `flight.trip("step_time_anomaly")` with a postmortem, both
  naming the dominant category, the leaf span and thread that hold
  most of the excess, step index and trace id (plus the all-thread
  stack snapshot trip() attaches);
- per-step barrier wait under multi-device ShardingPlan fits: the
  spread between the first and last shard finishing banks as
  `train_barrier_wait_seconds_total` (straggler time, reported beside
  the closed partition, not inside it);
- a decode-side split for the scheduler loop:
  `serving_decode_time_seconds_total{model,category}` over
  ``admission`` / ``step_compute`` / ``page_stall`` / ``idle``.
"""
from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Dict, Optional

from deeplearning4j_tpu.monitor import metrics, trace

log = logging.getLogger("deeplearning4j_tpu")

#: the closed partition every attributed second falls into
CATEGORIES = ("step_compute", "data_wait", "host_sync", "compile",
              "checkpoint", "eval_gate", "resume_replay", "other")

#: leaf span name -> category: the consumed stream. `train/step` and
#: `train/chunk` close an iteration (a step's residual after contained
#: child spans is step_compute; a chunk's is host bookkeeping and stays
#: unattributed), and `train/barrier_wait` banks outside the partition.
#: Listener time is host work and goes where unattributed host time goes.
SPAN_CATEGORY = {
    "etl/queue_wait": "data_wait",
    "train/etl": "data_wait",
    "train/device_wait": "step_compute",
    "train/stage": "step_compute",
    "train/launch": "step_compute",
    "train/loss_fetch": "step_compute",
    "train/listeners": "other",
    "train/host_sync": "host_sync",
    "xla/compile": "compile",
    "resilience/checkpoint_save": "checkpoint",
    "resilience/checkpoint_restore": "checkpoint",
    "resilience/eval_gate": "eval_gate",
    "train/resume_replay": "resume_replay",
}
#: spans around a pull from the iterator: they count only what the
#: `etl/queue_wait` spans inside them (the prefetch pump) left over
_PULL_SPANS = ("train/etl", "train/resume_replay")
#: feed-thread spans a stall is traced into when the fit() thread spent
#: it in `etl/queue_wait`. `etl/queue_put` is left out: a feed blocked
#: on a full queue is the healthy state.
_FEED_SPANS = ("etl/source_next", "etl/stage")
#: a stalled interval's time on the fit() thread under no leaf span
NO_SPAN = "_no_span_"

_TIME_HELP = ("Attributed fit() wall-clock seconds per goodput "
              "category (docs/OBSERVABILITY.md 'Goodput accounting')")
_PCT_HELP = ("Share of fit() wall-clock spent in device step compute "
             "(live during a session, final value at session end)")

_enabled = False
_ledger: Optional["GoodputLedger"] = None


def _cat_counter():
    return metrics.counter("train_time_seconds_total", _TIME_HELP,
                           labels=("category",))


class _Session:
    """One fit()'s accounting state. Touched only from the fit thread
    (the sink filters on `tid`), except the swap in/out under the
    ledger lock and `leaf_now`, which the feed threads add to under
    `leaf_lock`."""

    __slots__ = ("kind", "tid", "thread", "t0", "categories", "buffer",
                 "waits", "barrier_wait_s", "steps", "anomalies",
                 "prev_step_end", "iter_walls", "cat_mark",
                 "last_anomaly_step", "ctx", "_binder", "leaf_lock",
                 "leaf_now", "leaf_hist")

    def __init__(self, kind: str, clock_now: float, window: int):
        self.kind = kind
        self.tid = threading.get_ident()
        self.thread = threading.current_thread().name
        self.t0 = clock_now
        self.categories: Dict[str, float] = {c: 0.0 for c in CATEGORIES}
        self.buffer = []              # (t0, t1, dur) since last step
        self.waits = []               # etl/queue_wait since the last pull
        self.barrier_wait_s = 0.0
        self.steps = 0
        self.anomalies = 0
        self.prev_step_end: Optional[float] = None
        self.iter_walls: deque = deque(maxlen=window)
        self.cat_mark: Dict[str, float] = dict(self.categories)
        self.last_anomaly_step = -10**9
        self.ctx = None
        self._binder = None
        # what names a stall: seconds by (leaf span, thread) in the
        # iteration under way (feed threads write here too, hence the
        # lock) and by leaf span in each of the last `window` ones (by
        # name alone: every epoch's pump is a new thread, and its
        # batches are measured against the pumps before it)
        self.leaf_lock = threading.Lock()
        self.leaf_now: Dict[tuple, float] = {}
        self.leaf_hist: Dict[str, deque] = {}


def _median(values) -> float:
    s = sorted(values)
    n = len(s)
    mid = n // 2
    return s[mid] if n % 2 else 0.5 * (s[mid - 1] + s[mid])


def blame(leaves: Dict[tuple, float], usual: Dict[str, float],
          thread) -> tuple:
    """(leaf span, thread, seconds over that leaf's usual) of the leaf
    that holds most of a slow iteration's excess. `leaves` is the
    iteration's seconds by (leaf span, thread), `usual` a sound
    iteration's seconds by leaf span, `thread` the one fit() runs on.
    It is fit()'s worst leaf or, when that is the wait for the feed
    (`etl/queue_wait`), the worst leaf of a feed thread, if one ran
    over. The one rule for the detector below and for the benchmark's
    readers of the same spans (`benchmark/lib/spans.py`)."""
    def excess(key):
        return leaves[key] - usual.get(key[0], 0.0)

    worst = max((k for k in leaves if k[1] == thread), key=excess)
    if worst[0] == "etl/queue_wait":
        feed = [k for k in leaves if k[1] != thread]
        if feed and excess(max(feed, key=excess)) > 0.0:
            worst = max(feed, key=excess)
    return worst[0], worst[1], excess(worst)


class GoodputLedger:
    """Span-stream consumer + per-fit session accounting. One instance
    is installed process-wide by `enable_goodput()`; `on_span` runs
    inline on every span boundary, so it must stay cheap (two dict
    lookups and a float add on the common path)."""

    def __init__(self, window: int = 64, warmup_steps: int = 16,
                 mad_k: float = 6.0, anomaly_min_s: float = 0.02,
                 anomaly_min_ratio: float = 2.0,
                 anomaly_cooldown_steps: int = 32,
                 clock=time.perf_counter):
        self.window = int(window)
        self.warmup_steps = int(warmup_steps)
        self.mad_k = float(mad_k)
        self.anomaly_min_s = float(anomaly_min_s)
        self.anomaly_min_ratio = float(anomaly_min_ratio)
        self.anomaly_cooldown_steps = int(anomaly_cooldown_steps)
        self.clock = clock
        self._lock = threading.Lock()
        self._session: Optional[_Session] = None
        self._last_summary: Optional[dict] = None
        self._decode_totals: Dict[tuple, float] = {}

    # ------------------------------------------------------ sessions
    def fit_begin(self, kind: str = "train") -> Optional[_Session]:
        """Open a session on the calling thread. Returns the token
        `fit_end` takes — None when a session is already active (nested
        fits: the outer one owns the wall-clock)."""
        with self._lock:
            if self._session is not None:
                return None
            s = _Session(kind, self.clock(), self.window)
            self._session = s
        # label the whole fit with a trace id so the anomaly postmortem,
        # the Perfetto trace and the flight ring all name one session —
        # only when something downstream records it (zero-cost contract)
        from deeplearning4j_tpu.monitor import flight
        if trace.tracing_enabled() or flight.enabled():
            ctx = trace.current_context()
            if ctx is None:
                ctx = trace.mint_context()
                s._binder = trace.bind_context(ctx)
                s._binder.__enter__()
            s.ctx = ctx
        return s

    def fit_end(self, session: Optional[_Session]) -> Optional[dict]:
        """Close a session token: computes `other` as the unattributed
        remainder (the exclusivity contract), publishes the final gauge,
        and returns the summary dict. None-safe (nested/disabled)."""
        if session is None:
            return None
        t1 = self.clock()
        with self._lock:
            if self._session is not session:
                return None
            self._session = None
        if session._binder is not None:
            session._binder.__exit__(None, None, None)
        wall = max(t1 - session.t0, 0.0)
        attributed = sum(session.categories.values())
        other = max(wall - attributed, 0.0)
        if other > 0.0:
            session.categories["other"] += other
            _cat_counter().inc(other, category="other")
        pct = (100.0 * session.categories["step_compute"] / wall
               if wall > 0 else 0.0)
        metrics.gauge("train_goodput_pct", _PCT_HELP).set(round(pct, 3))
        summary = {
            "kind": session.kind,
            "wall_s": round(wall, 6),
            "categories": {k: round(v, 6)
                           for k, v in session.categories.items()},
            "goodput_pct": round(pct, 2),
            "steps": session.steps,
            "anomalies": session.anomalies,
            "barrier_wait_s": round(session.barrier_wait_s, 6),
            "trace_id": session.ctx.trace_id if session.ctx else None,
        }
        self._last_summary = summary
        return summary

    def last_session(self) -> Optional[dict]:
        return self._last_summary

    # ------------------------------------------------------ span sink
    def on_span(self, name: str, t0: float, t1: float, attrs: dict):
        s = self._session
        if s is None:
            return
        dur = t1 - t0
        if dur < 0.0:
            return
        if threading.get_ident() != s.tid:
            if name in _FEED_SPANS:
                self._note_leaf(s, name, threading.current_thread().name,
                                dur)
            return
        if name == "train/step":
            # residual: the step extent minus the child spans it contains
            # (device_wait/host_sync...) is device execution the loop
            # didn't bracket separately -> step_compute
            residual = max(dur - self._contained(s.buffer, t0, t1), 0.0)
            if residual > 0.0:
                self._bank(s, name, "step_compute", residual)
            self._end_iteration(s, t1, dur, 1, attrs)
            return
        if name == "train/chunk":
            # one turn of the chunked pipeline closes an iteration of
            # `steps` optimizer steps; what its leaves did not cover is
            # host bookkeeping and stays unattributed
            self._end_iteration(s, t1, dur, int(attrs.get("steps", 0)),
                                attrs)
            return
        if name == "train/barrier_wait":
            s.barrier_wait_s += dur
            metrics.counter(
                "train_barrier_wait_seconds_total",
                "Per-step spread between the first and last shard "
                "finishing under a multi-device plan (straggler time; "
                "reported beside the goodput partition, not inside "
                "it)").inc(dur)
            return
        cat = SPAN_CATEGORY.get(name)
        if cat is None:
            return
        if name == "etl/queue_wait":
            s.waits.append((t0, t1, dur))
        elif name in _PULL_SPANS:
            dur = max(dur - self._contained(s.waits, t0, t1), 0.0)
            s.waits.clear()
        s.buffer.append((t0, t1, dur))
        self._bank(s, name, cat, dur)

    @staticmethod
    def _contained(spans, t0: float, t1: float) -> float:
        eps = 1e-9
        return sum(d for (c0, c1, d) in spans
                   if c0 >= t0 - eps and c1 <= t1 + eps)

    def _bank(self, s: _Session, name: str, cat: str, dur: float):
        s.categories[cat] += dur
        _cat_counter().inc(dur, category=cat)
        self._note_leaf(s, name, s.thread, dur)

    @staticmethod
    def _note_leaf(s: _Session, name: str, thread: str, dur: float):
        key = (name, thread)
        with s.leaf_lock:
            s.leaf_now[key] = s.leaf_now.get(key, 0.0) + dur

    def _end_iteration(self, s: _Session, t1: float, dur: float,
                       steps: int, attrs: dict):
        s.buffer.clear()
        s.waits.clear()         # a pump driven under no pull span
        s.steps += steps
        # iteration wall: spacing between consecutive step/chunk ENDS —
        # it covers the inter-step gap (ETL, checkpoints), so a stall
        # anywhere in the loop surfaces, not just a slow step
        iter_wall = (t1 - s.prev_step_end
                     if s.prev_step_end is not None else dur)
        s.prev_step_end = t1
        deltas = {k: s.categories[k] - s.cat_mark[k]
                  for k in s.categories}
        s.cat_mark = dict(s.categories)
        with s.leaf_lock:
            leaves, s.leaf_now = s.leaf_now, {}
        wall = t1 - s.t0
        if wall > 0:
            metrics.gauge("train_goodput_pct", _PCT_HELP).set(
                round(100.0 * s.categories["step_compute"] / wall, 3))
        if steps <= 0:
            # a turn that reported no step (the chunked pipeline's fill,
            # an empty last pull) is no sample of the iteration wall
            return
        busy = sum(v for (_, th), v in leaves.items() if th == s.thread)
        leaves[(NO_SPAN, s.thread)] = max(iter_wall - busy, 0.0)
        self._check_anomaly(s, iter_wall, deltas, leaves, attrs)
        # after the check: a spike must not raise its own baseline
        s.iter_walls.append(iter_wall)
        by_leaf: Dict[str, float] = {}
        for (name, _), v in leaves.items():
            by_leaf[name] = by_leaf.get(name, 0.0) + v
        for name in set(by_leaf) | set(s.leaf_hist):
            s.leaf_hist.setdefault(name, deque(maxlen=self.window)).append(
                by_leaf.get(name, 0.0))

    def _check_anomaly(self, s: _Session, iter_wall: float,
                       deltas: Dict[str, float],
                       leaves: Dict[tuple, float], attrs: dict):
        hist = s.iter_walls
        if len(hist) < self.warmup_steps:
            return
        med = _median(hist)
        mad = _median([abs(x - med) for x in hist])
        threshold = max(med + self.mad_k * 1.4826 * mad,
                        med * self.anomaly_min_ratio,
                        self.anomaly_min_s)
        if iter_wall <= threshold:
            return
        if s.steps - s.last_anomaly_step < self.anomaly_cooldown_steps:
            return
        s.last_anomaly_step = s.steps
        s.anomalies += 1
        metrics.counter(
            "train_step_anomalies_total",
            "Step-time spikes caught by the rolling median/MAD "
            "detector (each logs a WARNING, and fires a "
            "step_time_anomaly postmortem when the flight recorder is "
            "on)").inc()
        # the interval's dominant category names the suspect; the
        # unattributed remainder is `other`, so when it dominates the
        # trip says "other" honestly
        deltas["other"] += max(iter_wall - sum(deltas.values()), 0.0)
        dominant = max(deltas, key=deltas.get)
        dom_s = deltas[dominant]
        leaf, thread, leaf_s = blame(
            leaves, {n: _median(h) for n, h in s.leaf_hist.items()},
            s.thread)
        step = attrs.get("iteration", attrs.get(
            "step", attrs.get("chunk", s.steps)))
        # the operator's line: the library never turns the flight
        # recorder on, so the log is what a plain fit() user gets
        log.warning(
            "fit() stalled at %s %s: %.3f s against a median of %.3f s; "
            "%.3f s in %s, most of the excess (%.3f s) in %s on thread "
            "%s", "chunk" if "chunk" in attrs else "step", step,
            iter_wall, med, dom_s, dominant, leaf_s, leaf, thread)
        from deeplearning4j_tpu.monitor import flight
        flight.trip(
            "step_time_anomaly",
            step=step,
            iteration_wall_s=round(iter_wall, 6),
            median_s=round(med, 6),
            threshold_s=round(threshold, 6),
            dominant_category=dominant,
            dominant_seconds=round(dom_s, 6),
            leaf_span=leaf,
            leaf_thread=thread,
            leaf_excess_s=round(leaf_s, 6),
            trace_id=s.ctx.trace_id if s.ctx else None)

    # ------------------------------------------------------ live view
    def live_stats(self) -> Optional[dict]:
        """Goodput% + dominant stall of the ACTIVE session — what
        PerformanceListener prints beside examples/sec. Reads and
        publishes through the same accumulators as `/metrics`, so the
        log line and the gauge cannot disagree."""
        s = self._session
        if s is None:
            return None
        wall = self.clock() - s.t0
        if wall <= 0:
            return None
        cats = dict(s.categories)
        cats["other"] += max(wall - sum(cats.values()), 0.0)
        pct = round(100.0 * cats["step_compute"] / wall, 2)
        stall = max((k for k in cats if k != "step_compute"),
                    key=lambda k: cats[k])
        metrics.gauge("train_goodput_pct", _PCT_HELP).set(pct)
        return {"goodput_pct": pct, "dominant_stall": stall,
                "stall_seconds": round(cats[stall], 6)}

    # ------------------------------------------------------ decode
    def decode_note(self, model: str, category: str, seconds: float):
        """Bank scheduler-loop seconds for one decode category
        (``admission`` / ``step_compute`` / ``page_stall`` / ``idle``)."""
        if seconds <= 0.0:
            return
        key = (model, category)
        with self._lock:
            self._decode_totals[key] = \
                self._decode_totals.get(key, 0.0) + seconds
        metrics.counter(
            "serving_decode_time_seconds_total",
            "Decode scheduler-loop wall-clock split per model: engine "
            "step compute vs page-stall slot time vs admission vs "
            "idle", labels=("model", "category")).inc(
            seconds, model=model, category=category)

    def decode_totals(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            out: Dict[str, Dict[str, float]] = {}
            for (model, cat), secs in self._decode_totals.items():
                out.setdefault(model, {})[cat] = round(secs, 6)
            return out


# ---------------------------------------------------------- module API
def enable_goodput(**knobs) -> GoodputLedger:
    """Install a fresh ledger as the span sink (idempotent with the
    same effect: a new ledger replaces the old). Knobs forward to
    `GoodputLedger` (window, warmup_steps, mad_k, anomaly_min_s,
    anomaly_min_ratio, anomaly_cooldown_steps, clock)."""
    global _enabled, _ledger
    _ledger = GoodputLedger(**knobs)
    trace.set_span_sink(_ledger.on_span)
    _enabled = True
    return _ledger


def disable_goodput():
    global _enabled, _ledger
    trace.set_span_sink(None)
    _enabled = False
    _ledger = None


def goodput_enabled() -> bool:
    return _enabled


def ledger() -> Optional[GoodputLedger]:
    return _ledger


def fit_begin(kind: str = "train"):
    """Session open for the fit loops: None (no-op token) while
    disabled or when an outer session already owns the wall-clock."""
    led = _ledger
    if led is None:
        return None
    return led.fit_begin(kind)


def fit_end(session) -> Optional[dict]:
    led = _ledger
    if led is None or session is None:
        return None
    return led.fit_end(session)


def last_session() -> Optional[dict]:
    led = _ledger
    return led.last_session() if led is not None else None


def live_stats() -> Optional[dict]:
    led = _ledger
    return led.live_stats() if led is not None else None


def decode_note(model: str, category: str, seconds: float):
    led = _ledger
    if led is not None:
        led.decode_note(model, category, seconds)


def decode_totals() -> Dict[str, Dict[str, float]]:
    led = _ledger
    return led.decode_totals() if led is not None else {}


def device_wait(value):
    """Block until `value`'s device computation finished, WITHOUT
    transferring it — the fit loops call this right before the one
    budgeted `float(loss)` so the ledger can split device execution
    (`train/device_wait` -> step_compute) from the narrow D2H fetch
    (`train/host_sync`). While the ledger is off this is a bare
    `block_until_ready()`; non-array values pass through untouched.

    Under an active session, a value sharded across >1 addressable
    device is blocked shard-by-shard and the first->last completion
    spread banks as `train/barrier_wait` (straggler time)."""
    block = getattr(value, "block_until_ready", None)
    if block is None:
        return value
    led = _ledger
    if led is None or led._session is None:
        block()
        return value
    shards = getattr(value, "addressable_shards", None)
    try:
        n = len(shards) if shards is not None else 0
    except Exception:
        # a value without a usable shard list degrades to the plain
        # whole-array block below; never break the fit loop over a
        # telemetry refinement
        n = 0
    if n < 2:
        block()
        return value
    try:
        t_first = None
        t_last = None
        for sh in shards:
            sh.data.block_until_ready()
            t_last = time.perf_counter()
            if t_first is None:
                t_first = t_last
        if t_last > t_first:
            trace.add_span("train/barrier_wait", t_first, t_last,
                           shards=n)
    except Exception:
        # shard-probe failure (backend without per-shard handles)
        # degrades to the plain block
        block()
    return value
