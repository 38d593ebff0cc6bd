"""Device time of one optimizer step of the LM cell: the median length of
the WHOLE runs of the compiled step program in the profiler trace
(`scopes.step_runs`: the run that the profiler's start cut, which
`train_step_device_ms` counts as a whole one, is left out; the median also
shrugs off a last run that the profiler's end cut by less than a tenth),
over the optimizer steps one run holds."""
import statistics

from benchmark.lib import scopes


def read(ctx):
    runs = scopes.step_runs(ctx)
    if not runs:
        return None
    return 1e3 * statistics.median(e - s for _, s, e in runs) \
        / ctx["steps_per_call"]
