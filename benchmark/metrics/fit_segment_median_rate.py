"""Median of the segments' rates: the steady statistic beside the
end-to-end rate, which a stall inside the window does not move. A segment
is ``segment_steps`` optimizer steps between two stamps of the listener;
in a traced run only segments before the profiler is switched on count."""
import statistics


def read(ctx):
    if not ctx["segment_rates"]:
        return None
    return statistics.median(ctx["segment_rates"])
