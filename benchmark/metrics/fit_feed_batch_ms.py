"""Time the data plane is busy for one batch: the median, over the batches
the feed thread made in the window before the profiler (the fill left
out), of ``etl/source_next`` + ``etl/stage`` of one ``seq``. Read it
against the time the device needs for a batch: a feed that needs more
starves it."""
import statistics

from benchmark.lib import spans


def read(ctx):
    w = spans.Window(ctx, spans.program_spans())
    per_batch = {}
    for s in w.steady_spans(*spans.FEED_WORK):
        key = (s.tid, s.args.get("seq"))
        per_batch[key] = per_batch.get(key, 0.0) + (s.t1 - s.t0)
    if not per_batch:
        return None
    return 1e3 * statistics.median(per_batch.values())
