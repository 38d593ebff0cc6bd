"""Time the program's denoising pre-processor takes for one batch on the
feed thread: the median length of the spans ``etl/denoise`` (one a batch:
the noise drawn, the masked copy, the stream [noisy ; clean], the weights)
in the window before the profiler, the fill left out. It is a part of the
feed's ``etl/source_next``; read it against the device's time for a batch.
``None`` where the program has no such span."""
import statistics

from benchmark.lib import spans


def read(ctx):
    w = spans.Window(ctx, spans.program_spans())
    took = [s.t1 - s.t0 for s in w.steady_spans("etl/denoise")]
    if not took:
        return None
    return 1e3 * statistics.median(took)
