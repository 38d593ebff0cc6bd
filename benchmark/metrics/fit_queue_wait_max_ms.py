"""The longest single ``etl/queue_wait`` of the window before the profiler
(the fill left out): the longest the ``fit()`` thread waited for the data
plane to hand it one batch."""
from benchmark.lib import spans


def read(ctx):
    w = spans.Window(ctx, spans.program_spans())
    waits = w.steady_spans("etl/queue_wait")
    if not waits:
        return None
    return 1e3 * max(s.t1 - s.t0 for s in waits)
