"""Share of the step program's device time that the latent attention
spends around its kernels: the ops under the scopes ``mla/proj`` (the
low-rank query and key-value projections, their norms, the broadcast of
the shared rotated key and the concatenations) and ``mla/rope`` (the
rotation of q's and k's position dims), forward, rematerialised forward
and backward, in whole runs of the step program; also printed as
milliseconds a step, since the ``[scopes]`` table files ``mla/rope`` under
``other``."""
from benchmark.lib import scopes


def read(ctx):
    parts = {m: scopes.seconds(ctx, lambda n, scope, m=m: m in scope)
             for m in ("mla/proj", "mla/rope")}
    if any(got is None or not got[1] for got in parts.values()):
        return None
    ms = {m: round(1e3 * took / steps, 3)
          for m, (took, _, steps) in parts.items()}
    print(f"[mla_proj_share] device ms a step {ms}", flush=True)
    # no op is under both scopes: the share of either is their sum's
    whole = parts["mla/proj"][1]
    return 100.0 * sum(took for took, _, _ in parts.values()) / whole
