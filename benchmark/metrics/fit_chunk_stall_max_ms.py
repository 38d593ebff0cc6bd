"""The largest excess of one turn of the chunked pipeline over the median
turn, in the window before the profiler (the fill left out): a few ms in a
sound run, seconds when the host stalled. A ``[stall]`` line names the
chunk and the leaf span and thread that hold most of the excess."""
import statistics

from benchmark.lib import spans


def read(ctx):
    w = spans.Window(ctx, spans.program_spans())
    if len(w.chunks) < 2:
        return None
    usual = statistics.median(c.t1 - c.t0 for c in w.chunks)
    worst = max(w.chunks, key=lambda c: c.t1 - c.t0)
    excess = (worst.t1 - worst.t0) - usual
    leaf, tid, leaf_s = spans.blame(w, worst)
    print(f"[stall] of {len(w.chunks)} turns the longest is chunk "
          f"{worst.args.get('chunk')}, {1e3 * (worst.t1 - worst.t0):.3f} ms "
          f"against a median of {1e3 * usual:.3f}; {1e3 * leaf_s:.3f} ms "
          f"of the excess in {leaf} on {spans.thread_name(tid)}",
          flush=True)
    return 1e3 * excess
