"""Time on the device from the end of one run of the compiled step program
to the start of the next (chip 0, the median over every pair of runs in the
trace, which goes on recording the device for some seconds after the
stretch that is read). A ``[gap]`` line gives every gap and, for the one
the median of the stretch's own gaps falls on (the lower of two; host spans
exist only there), the part of it in which other programs ran and the idle
rest by the host span it lies under, as ``Trace.idle_gaps()`` puts it."""
import copy
import statistics

from benchmark.lib import xplane


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.devices:
        return None
    dev = tr.devices[0]
    runs = sorted((s, e) for n, s, e in dev["modules"]
                  if n == ctx["system"].STEP_PROGRAM)
    gaps = [(a[1], b[0]) for a, b in zip(runs, runs[1:]) if b[0] > a[1]]
    if not gaps:
        return None
    lengths = sorted(e - s for s, e in gaps)
    inside = [g for g in gaps if tr.window[0] <= g[0] < tr.window[1]] or gaps
    pick = statistics.median_low(e - s for s, e in inside)
    # the trace's own view of that one gap: its busy time and its rule
    # for which host span an idle piece goes to
    piece = copy.copy(tr)
    piece.window = next(g for g in inside if g[1] - g[0] == pick)
    under = sorted(piece.idle_gaps().items(), key=lambda kv: -kv[1])
    print(f"[gap] {len(gaps)} gaps between runs of "
          f"{ctx['system'].STEP_PROGRAM}, ms: "
          f"{[round(1e3 * x, 3) for x in lengths]}; of the "
          f"{1e3 * pick:.3f} ms one, other programs ran for "
          f"{1e3 * xplane.measure(piece.busy(dev)):.3f} ms and the idle "
          f"rest lies under, ms: "
          f"{[(n, round(1e3 * v, 3)) for n, v in under]}", flush=True)
    return 1e3 * statistics.median(lengths)
