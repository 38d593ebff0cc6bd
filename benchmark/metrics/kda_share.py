"""Share of the step program's device time that Kimi Delta Attention
takes: the ops under the scopes ``kda/proj`` (projections, convolutions,
gates), ``kda/scan`` (the chunked recurrence) and ``kda/out`` (gated norm,
output projection), forward, rematerialised forward and backward, in whole
runs of the step program."""
from benchmark.lib import scopes


def read(ctx):
    return scopes.share(ctx, "kda/proj", "kda/scan", "kda/out")
