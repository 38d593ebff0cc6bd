"""Whole-window rate (all examples over all the time, the pipeline's fill
included) as a share of the median segment rate: under 100 by what the
fill and any stalled segment cost. In a traced run both are taken over the
part of the window before the profiler is switched on."""
import statistics


def read(ctx):
    if not ctx["segment_rates"]:
        return None
    return 100.0 * ctx["window_rate"] / statistics.median(
        ctx["segment_rates"])
