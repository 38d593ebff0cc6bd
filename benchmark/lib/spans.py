"""The program's own spans, for the per-layer readers that time the fit
loop and the data plane where the work happens.

A traced run enables ``monitor.enable_tracing()`` before its first
``fit()``, so when the readers run the whole process is in the program's
event buffer: every ``fit()`` call as a ``train/epoch`` span, each turn of
the chunked pipeline as a ``train/chunk`` with its children, each batch of
the feed as ``etl/source_next`` / ``etl/stage`` / ``etl/queue_put`` on the
prefetch thread and ``etl/queue_wait`` on the ``fit()`` thread, and what
JAX spent compiling as ``xla/trace`` / ``xla/lower`` /
``xla/backend_compile``. The window's ``fit()`` is the LAST ``train/epoch``
(the reference that follows it calls no ``fit()``); "before the profiler"
is its first ``ctx["fit_s"]["wall"]`` seconds, as the goodput shares are
taken. Times are seconds on the program's clock.

A program without these spans (the parent of the PR that added them) gives
every function here nothing to return, and the readers ``None``.
"""
from __future__ import annotations

import bisect
import statistics
from collections import namedtuple

Span = namedtuple("Span", "name t0 t1 tid args")

#: spans of the ``fit()`` thread that hold no other span of the tree
FIT_LEAVES = ("etl/queue_wait", "train/stage", "train/launch",
              "train/loss_fetch", "train/listeners")
#: what the feed thread does for a batch (``etl/queue_put`` is the feed
#: waiting for room, not work)
FEED_WORK = ("etl/source_next", "etl/stage")
COMPILE = ("xla/trace", "xla/lower", "xla/backend_compile")


def program_spans():
    """Every complete event of the program's buffer, by start time."""
    from deeplearning4j_tpu import monitor
    out = [Span(e["name"], e["ts"] * 1e-6, (e["ts"] + e["dur"]) * 1e-6,
                e["tid"], e.get("args") or {})
           for e in monitor.trace_events() if e.get("ph") == "X"]
    out.sort(key=lambda s: (s.t0, -s.t1))
    return out


def thread_name(tid) -> str:
    from deeplearning4j_tpu import monitor
    names = getattr(monitor, "thread_names", dict)()
    return names.get(tid, f"thread-{tid}")


def named(spans, *names):
    return [s for s in spans if s.name in names]


def within(spans, outer):
    """The spans that lie inside ``outer`` on its thread."""
    return [s for s in spans if s.tid == outer.tid and s is not outer
            and s.t0 >= outer.t0 and s.t1 <= outer.t1]


def seconds(spans) -> float:
    return sum(s.t1 - s.t0 for s in spans)


def overlap(spans, lo, hi) -> float:
    return sum(max(min(s.t1, hi) - max(s.t0, lo), 0.0) for s in spans)


class Window:
    """The window's ``fit()`` up to the profiler's start: its
    ``train/epoch`` span, the moment the profiler came, and the turns of
    the chunked pipeline between the two, the first (the pipeline's
    fill) and any that pulled nothing (the drain) left out."""

    def __init__(self, ctx, spans):
        epochs = named(spans, "train/epoch")
        self.epoch = epochs[-1] if epochs else None
        self.spans, self.turns, self.chunks, self.steady = [], [], [], None
        self._leaves = {}
        if self.epoch is None:
            return
        #: what happened during the window's ``fit()``, on any thread
        self.spans = [s for s in spans if s.t1 > self.epoch.t0
                      and s.t0 < self.epoch.t1]
        self.cut = self.epoch.t0 + ctx["fit_s"]["wall"]
        #: every turn of the window's ``fit()`` that pulled a chunk
        self.turns = [c for c in within(named(self.spans, "train/chunk"),
                                        self.epoch) if c.args.get("batches")]
        self.chunks = [c for c in self.turns[1:] if c.t1 <= self.cut]
        #: from the end of the fill to the profiler's start
        self.steady = (self.turns[0].t1, self.cut) if self.turns else None

    def steady_spans(self, *names):
        """Spans of those names, on any thread, that lie in the steady
        part of the window."""
        if self.steady is None:
            return []
        lo, hi = self.steady
        return [s for s in named(self.spans, *names)
                if s.t0 >= lo and s.t1 <= hi]

    def leaf_seconds(self, chunk):
        """Seconds by (span name, thread id) during one turn: the
        ``fit()`` thread's leaves inside it, what of ``train/etl`` its
        queue waits left over, the turn's time under no leaf, and the
        feed threads' work that overlaps the turn. All turns are cut in
        one pass over the window's spans and kept."""
        if not self._leaves:
            self._cut_leaves()
        return self._leaves[id(chunk)]

    def _cut_leaves(self):
        from deeplearning4j_tpu.monitor import goodput
        tid = self.epoch.tid
        turns = self.turns
        starts = [c.t0 for c in turns]
        sums = [{} for _ in turns]

        def add(i, key, v):
            sums[i][key] = sums[i].get(key, 0.0) + v

        for s in named(self.spans, "train/etl", *FIT_LEAVES, *FEED_WORK):
            # the turn that holds the span's start, and for feed work
            # every later turn it reaches into
            i = bisect.bisect_right(starts, s.t0) - 1
            if s.tid == tid:
                if i >= 0 and s.t1 <= turns[i].t1:
                    add(i, (s.name, tid), s.t1 - s.t0)
                continue
            i = max(i, 0)
            while i < len(turns) and turns[i].t0 < s.t1:
                add(i, (s.name, s.tid), overlap([s], turns[i].t0,
                                                turns[i].t1))
                i += 1
        for c, out in zip(turns, sums):
            out[("train/etl", tid)] = max(
                out.get(("train/etl", tid), 0.0)
                - out.get(("etl/queue_wait", tid), 0.0), 0.0)
            out[(goodput.NO_SPAN, tid)] = max((c.t1 - c.t0) - sum(
                v for k, v in out.items() if k[1] == tid), 0.0)
            self._leaves[id(c)] = {k: v for k, v in out.items() if v > 0.0
                                   or k[1] == tid}


def blame(window, chunk):
    """(span name, thread id, seconds over that leaf's median turn) of the
    leaf that holds most of a slow turn's excess, by the program's own
    rule (``monitor/goodput.py::blame``, what its stall detector logs):
    the ``fit()`` thread's worst leaf or, where that is the wait for the
    feed, the feed thread's."""
    from deeplearning4j_tpu.monitor import goodput

    def by_leaf(turn):
        out = {}
        for (name, _), v in window.leaf_seconds(turn).items():
            out[name] = out.get(name, 0.0) + v
        return out

    mine = window.leaf_seconds(chunk)
    others = [by_leaf(c) for c in window.chunks if c is not chunk]
    usual = {name: statistics.median(o.get(name, 0.0) for o in others)
             for name, _ in mine} if others else {}
    return goodput.blame(mine, usual, chunk.tid)
