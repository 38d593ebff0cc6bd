"""Roofline share of the sparse attention: the least time scores and
weighted values over the SELECTED (query, key) pairs only can take in a
training step at all the query heads, forward and backward, k and v read
once a group (the reference file's ``dsa_attn_min_seconds``, reckoned from
the configuration whatever implements it), over the device time of the ops
under the scope ``dsa/attn`` in whole runs of the step program. A masked
dense product over every causal pair does about eight times the selected
pairs' arithmetic at 32,768 positions and 2,048 keys a query, and reads
about an eighth here for that alone; the indexer's tile made again beside
every attention tile and the indexer's loss's backward, which rides the
same kernels, show as a loss too."""
from benchmark.lib import scopes


def read(ctx):
    least_of = getattr(ctx["reference"], "dsa_attn_min_seconds", None)
    if ctx["peaks"] is None or least_of is None:
        return None
    got = scopes.seconds(ctx, lambda n, scope: "dsa/attn" in scope)
    if got is None or not got[0]:
        return None
    took, _, steps = got
    least = least_of(ctx["cell"].config, ctx["peaks"], ctx["batch"])
    print(f"[dsa_attn_roofline] least a step {least['least_s']:.4e} s (by "
          f"FLOPs {least['flops_s']:.4e}, by bytes {least['bytes_s']:.4e}); "
          f"a step's ops took {took / steps:.4e} s", flush=True)
    return 100.0 * least["least_s"] * steps / took
