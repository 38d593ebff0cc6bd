"""From a profiler trace (``.xplane.pb``) to numbers.

What a TPU trace holds (looked at by hand, ``tools/record_fixture.py``
prints it): one plane ``/device:TPU:<i>`` per chip with the lines
``XLA Modules`` (one event per run of a compiled program, named
``jit_<fn>(<fingerprint>)``) and ``XLA Ops`` (one event per HLO op, named
by its HLO text ``%name = ...``; the body ops of a ``while`` lie inside the
``%while`` event); and a plane ``/host:CPU`` with one line per host thread,
on which ``jax.profiler.TraceAnnotation`` spans appear under their own
names. All times are nanoseconds on one clock (device and host agree to a
millisecond or two).

This module is the whole reduction: the union of the intervals in which an
op ran (busy), its complement inside the traced window (idle gaps, each
named by the program span the host was in), the device time of each
program, and the time of each op. Checked on a recorded trace by
``tests/test_xplane.py``.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import time

_SPAN = re.compile(r"^[a-z_0-9]+/[a-z_0-9/]+$")
#: ops that only hold other ops: their time is their children's
_CONTAINERS = ("while", "conditional", "call")
WINDOW_OPEN, WINDOW_CLOSE = "bench/window_open", "bench/window_close"


def op_name(hlo_text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%").strip()


def op_kind(hlo_text: str) -> str:
    """The fusion kind in an op's HLO text (``kind=kOutput`` for a fusion
    around a convolution or a dot, ``kLoop``/``kInput`` for element-wise
    and reduction fusions), or ``""``."""
    m = re.search(r"kind=(k[A-Za-z]+)", hlo_text)
    return m.group(1) if m else ""


def _is(name: str, kinds) -> bool:
    base = name.split(".", 1)[0]
    return any(base == k or base.startswith(k + "-") for k in kinds)


def union(intervals):
    """Merged, sorted, non-overlapping ``(start, end)`` list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def measure(merged) -> float:
    return sum(e - s for s, e in merged)


def subtract(a, b):
    """Parts of the merged list ``a`` that no interval of merged ``b``
    covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


class Trace:
    """The reduced view of one ``.xplane.pb``. Times in seconds."""

    def __init__(self, path: str, chips: int = 1):
        import jax
        data = jax.profiler.ProfileData.from_file(path)
        self.devices = []       # per chip: {"ops": [...], "modules": [...]}
        self.spans = []         # (name, start, end) of program spans
        for plane in data.planes:
            m = re.match(r"^/device:TPU:(\d+)$", plane.name)
            if m and int(m.group(1)) < chips:
                dev = {"ops": [], "modules": []}
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        dev["ops"] = [(op_name(e.name), e.start_ns * 1e-9,
                                       (e.start_ns + e.duration_ns) * 1e-9,
                                       op_kind(e.name))
                                      for e in line.events]
                    elif line.name == "XLA Modules":
                        dev["modules"] = [
                            (e.name.split("(", 1)[0], e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events]
                self.devices.append(dev)
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    for e in line.events:
                        if _SPAN.match(e.name):
                            self.spans.append(
                                (e.name, e.start_ns * 1e-9,
                                 (e.start_ns + e.duration_ns) * 1e-9))
        marks = {n: (s, e) for n, s, e in self.spans
                 if n in (WINDOW_OPEN, WINDOW_CLOSE)}
        self.spans = [s for s in self.spans
                      if s[0] not in (WINDOW_OPEN, WINDOW_CLOSE)]
        every = [t for d in self.devices for _, s, e, _ in d["ops"]
                 for t in (s, e)]
        lo = marks[WINDOW_OPEN][1] if WINDOW_OPEN in marks else min(
            every, default=0.0)
        hi = marks[WINDOW_CLOSE][0] if WINDOW_CLOSE in marks else max(
            every, default=0.0)
        self.window = (lo, hi)

    # ------------------------------------------------------------- time
    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def _leaf_ops(self, dev):
        return [op for op in dev["ops"] if not _is(op[0], _CONTAINERS)]

    def busy(self, dev):
        """Merged intervals, inside the window, in which an op ran."""
        return union(clip([(s, e) for _, s, e, _ in self._leaf_ops(dev)],
                          *self.window))

    def busy_s(self) -> float:
        """Seconds an op ran on the device, averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(measure(self.busy(d)) for d in self.devices) \
            / len(self.devices)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def program_spans(self, name: str):
        """(chip, start, end) of each run of the program ``name`` (the
        jitted function's name, ``jit_<fn>``) that lies whole inside the
        window. A run that ends with the window is left out: where the
        window ends with the trace, the profiler may have cut it short."""
        lo, hi = self.window
        return [(i, s, e) for i, d in enumerate(self.devices)
                for n, s, e in d["modules"]
                if n == name and s >= lo and e < hi]

    def program_runs(self, name: str):
        """Device seconds of each such run, over all chips."""
        return [e - s for _, s, e in self.program_spans(name)]

    def op_seconds(self, match=None, inside=None):
        """Seconds by op name, summed over its events inside the window
        (or, with ``inside``, inside those runs of a program, as
        ``program_spans`` gives them) and averaged over the chips, for the
        ops whose (name, fusion kind) ``match`` accepts."""
        out = {}
        for i, d in enumerate(self.devices):
            spans = [self.window] if inside is None else \
                [(s, e) for chip, s, e in inside if chip == i]
            for n, s, e, kind in self._leaf_ops(d):
                if (match is None or match(n, kind)) and any(
                        s >= lo and e <= hi for lo, hi in spans):
                    out[n] = out.get(n, 0.0) + (e - s)
        k = max(len(self.devices), 1)
        return {n: v / k for n, v in out.items()}

    # ------------------------------------------------------------- gaps
    def idle_gaps(self):
        """Idle seconds of chip 0 by what the host was doing: each gap
        goes to the shortest program span that covers at least half of it,
        else to the span that overlaps it most, else ``_no_host_span_``."""
        if not self.devices:
            return {}
        gaps = subtract([self.window], self.busy(self.devices[0]))
        out = {}
        for gs, ge in gaps:
            best, best_key = "_no_host_span_", None
            for n, s, e in self.spans:
                ov = min(e, ge) - max(s, gs)
                if ov <= 0:
                    continue
                covers = ov >= 0.5 * (ge - gs)
                key = (covers, -(e - s) if covers else ov)
                if best_key is None or key > best_key:
                    best, best_key = n, key
            out[best] = out.get(best, 0.0) + (ge - gs)
        return out

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])
        gaps = sorted(self.idle_gaps().items(), key=lambda kv: -kv[1])
        return {"device_ops": [[n, v] for n, v in ops[:top]],
                "idle_gaps": [[n, v] for n, v in gaps[:top]]}


class Session:
    """The profiler around a short stretch of work (a trace of a whole
    window would take minutes to stop and be too large to bring back).
    The stretch that is read lies between ``start()`` and ``stop()``; both
    leave a mark in the trace itself."""

    def __init__(self, out_dir, chips):
        self.dir = os.path.join(out_dir, "profile")
        self.chips = chips
        self.on = False

    @staticmethod
    def _options():
        import jax
        # user annotations only on the host side, no Python call tracing
        # (which slows the host several times over), no HLO dump
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        options.enable_hlo_proto = False
        return options

    def start(self):
        import jax
        jax.profiler.start_trace(self.dir, profiler_options=self._options())
        self.on = True
        with jax.profiler.TraceAnnotation(WINDOW_OPEN):
            pass

    def stop(self):
        if not self.on:
            return
        import jax
        with jax.profiler.TraceAnnotation(WINDOW_CLOSE):
            pass
        t = time.monotonic()
        jax.profiler.stop_trace()
        print(f"[trace] stopping the profiler took "
              f"{time.monotonic() - t:.1f} s", flush=True)
        self.on = False

    def reduce(self):
        found = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            return None
        reduced = Trace(found[-1], self.chips)
        shutil.rmtree(self.dir, ignore_errors=True)     # tens of megabytes
        return reduced
