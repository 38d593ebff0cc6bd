"""Length of the process's first ``train/epoch`` span: the ``fit()`` call
that builds, traces, lowers and compiles (or reads from the compile cache)
the step program. The part of ``setup_s`` only the program can shorten."""
from benchmark.lib import spans


def read(ctx):
    epochs = spans.named(spans.program_spans(), "train/epoch")
    if not epochs:
        return None
    return epochs[0].t1 - epochs[0].t0
