"""Roofline share of the held experts' grouped matrix products: the least
time their three products can take, forward and backward, for the token
rows the router really sent them (the program's
``moe_tokens_routed_total{held="yes"}`` over its steps; per product the
larger of FLOPs/peak and bytes/peak, from the reference file's
``experts_min_seconds``) over the device time of the ops under the scope
``moe/experts`` and of XLA's grouped-product kernels, which keep no scope
and are named ``ragged-dot-*``, in whole runs of the step program. The
worst-case static rows a dispatch is sized for show here as a loss."""
from benchmark.lib import scopes


def read(ctx):
    rows = getattr(ctx["system"], "expert_rows_per_step", lambda: None)()
    if not rows or ctx["peaks"] is None:
        return None
    got = scopes.seconds(ctx, lambda n, scope: n.startswith("ragged-dot")
                         or "moe/experts" in scope)
    if got is None or not got[0]:
        return None
    took, _, steps = got
    cfg = ctx["cell"].config
    least = [ctx["reference"].experts_min_seconds(cfg, ctx["peaks"], r)
             for r in rows.values()]
    print(f"[moe_experts_roofline] rows a step by layer "
          f"{ {k: round(v) for k, v in rows.items()} }; least a step "
          f"{sum(x['least_s'] for x in least):.4e} s (by FLOPs "
          f"{sum(x['flops_s'] for x in least):.4e}, by bytes "
          f"{sum(x['bytes_s'] for x in least):.4e}); the ops took "
          f"{took / steps:.4e} s", flush=True)
    return 100.0 * sum(x["least_s"] for x in least) * steps / took
