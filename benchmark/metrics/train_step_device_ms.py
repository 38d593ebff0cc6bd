"""Device time of one optimizer step: the mean duration of the compiled
step program's runs in the profiler trace, over the steps one run fuses."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    runs = tr.program_runs(ctx["system"].STEP_PROGRAM)
    if not runs:
        return None
    return 1e3 * sum(runs) / len(runs) / ctx["steps_per_call"]
