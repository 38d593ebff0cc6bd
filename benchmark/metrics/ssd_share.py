"""Share of the step program's device time that the Mamba-2 layers take:
the ops under the scopes ``ssd/proj`` (the input projection), ``ssd/conv``
(taps, bias, SiLU), ``ssd/scan`` (step size, decay, the chunked recurrence)
and ``ssd/out`` (gated group norm, output projection), forward,
rematerialised forward and backward, in whole runs of the step program;
also printed as milliseconds a step, part by part. None where the program
has no such scopes."""
from benchmark.lib import scopes

_PARTS = ("ssd/proj", "ssd/conv", "ssd/scan", "ssd/out")


def read(ctx):
    parts = {m: scopes.seconds(ctx, lambda n, scope, m=m: m in scope)
             for m in _PARTS}
    if None in parts.values() or not parts["ssd/proj"][1]:
        return None
    ms = {m: round(1e3 * took / steps, 3)
          for m, (took, _, steps) in parts.items()}
    print(f"[ssd_share] device ms a step {ms}", flush=True)
    # no op is under two of the scopes: the share of all is their sum's
    took = sum(took for took, _, _ in parts.values())
    return 100.0 * took / parts["ssd/proj"][1] if took else None
