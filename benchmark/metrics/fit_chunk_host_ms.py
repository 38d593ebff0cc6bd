"""What the ``fit()`` thread itself spends on one turn of the chunked
pipeline: the median, over the window's turns before the profiler (the
fill left out), of ``train/chunk`` minus the ``etl/queue_wait`` and
``train/loss_fetch`` inside it, the two places where the thread only
waits. Staging, launching, the listeners and the loop's bookkeeping are
what is left; read it against the chunk's time on the device. Where a call
inside ``train/stage`` or ``train/launch`` itself blocks until the device
is free, that wait is in this number: it then reads about a chunk's device
time, and says that the host does not run ahead of the device."""
import statistics

from benchmark.lib import spans


def read(ctx):
    w = spans.Window(ctx, spans.program_spans())
    if not w.chunks:
        return None
    return 1e3 * statistics.median(
        (c.t1 - c.t0) - spans.seconds(spans.named(
            spans.within(w.spans, c), "etl/queue_wait", "train/loss_fetch"))
        for c in w.chunks)
