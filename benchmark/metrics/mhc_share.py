"""Share of the step program's device time that the residual mappings of a
multi-stream residual path take (manifold-constrained hyper-connections):
the ops under the scopes ``mhc/pre`` (the RMS of the streams' row, the
24-column product, the weighted sum a sub-layer reads), ``mhc/sinkhorn``
(the three mappings and their Sinkhorn steps), ``mhc/post`` (the streams a
sub-layer leaves) and ``mhc/io`` (one stream made four, four made one),
forward, rematerialised forward and backward, in whole runs of the step
program; also printed as milliseconds a step, part by part. None where the
program has no such scopes."""
from benchmark.lib import scopes

_PARTS = ("mhc/pre", "mhc/sinkhorn", "mhc/post", "mhc/io")


def read(ctx):
    parts = {m: scopes.seconds(ctx, lambda n, scope, m=m: m in scope)
             for m in _PARTS}
    if None in parts.values() or not parts["mhc/pre"][1]:
        return None
    ms = {m: round(1e3 * took / steps, 3)
          for m, (took, _, steps) in parts.items()}
    print(f"[mhc_share] device ms a step {ms}", flush=True)
    # no op is under two of the scopes: the share of all is their sum's
    took = sum(took for took, _, _ in parts.values())
    return 100.0 * took / parts["mhc/pre"][1] if took else None
