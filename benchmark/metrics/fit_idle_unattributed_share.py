"""Share of the traced stretch's idle seconds that ``Trace.idle_gaps()``
could put under no host span (``_no_host_span_``): how much of the
device's idle time the profiler's host plane cannot name. Every phase of
the chunked ``fit()`` path and of its feed is an entered span, but the
profiler keeps a host span only if it ENDS while the host tracer is on: a
span that covers a hole which lasts until the profiler is told to stop is
not in the plane. So an ``[idle]`` line names, from the program's own
buffer (which does not stop), the longest turn of the pipeline during the
stretch and the leaf span and thread that hold most of its excess."""
from benchmark.lib import spans


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.devices:
        return None
    gaps = tr.idle_gaps()
    idle = sum(gaps.values())
    if idle <= 0:
        return None
    unnamed = gaps.get("_no_host_span_", 0.0)
    w = spans.Window(ctx, spans.program_spans())
    during = [c for c in w.turns
              if c.t1 > w.cut and c.t0 < w.cut + tr.window_s]
    if during and w.chunks:
        worst = max(during, key=lambda c: c.t1 - c.t0)
        leaf, tid, leaf_s = spans.blame(w, worst)
        print(f"[idle] {unnamed:.3f} of {idle:.3f} idle s under no span of "
              f"the profiler's host plane; in the program's buffer the "
              f"longest turn of the stretch is chunk "
              f"{worst.args.get('chunk')}, "
              f"{1e3 * (worst.t1 - worst.t0):.3f} ms, "
              f"{1e3 * leaf_s:.3f} ms over its median in {leaf} on "
              f"{spans.thread_name(tid)}", flush=True)
    return 100.0 * unnamed / idle
