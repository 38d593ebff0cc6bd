"""Roofline share of the grouped-query attention: the least time the scores
and weighted values INSIDE the causal mask can take in a training step at
all the query heads, forward and backward, k and v read once a group (the
reference file's ``gqa_attn_min_seconds``, reckoned from the configuration
whatever implements it), over the device time of the ops under the scope
``mha/attn`` (the flash kernels at 64-wide heads, 32 on 8, and the layout
changes around them) in whole runs of the step program. A block computed
and then masked, half-filled lanes, and a key tile fetched again for each
query head of its group show as a loss."""
from benchmark.lib import scopes


def read(ctx):
    least_of = getattr(ctx["reference"], "gqa_attn_min_seconds", None)
    if ctx["peaks"] is None or least_of is None:
        return None
    got = scopes.seconds(ctx, lambda n, scope: "mha/attn" in scope)
    if got is None or not got[0]:
        return None
    took, _, steps = got
    least = least_of(ctx["cell"].config, ctx["peaks"], ctx["batch"])
    print(f"[gqa_attn_roofline] least a step {least['least_s']:.4e} s (by "
          f"FLOPs {least['flops_s']:.4e}, by bytes {least['bytes_s']:.4e}); "
          f"a step's ops took {took / steps:.4e} s", flush=True)
    return 100.0 * least["least_s"] * steps / took
