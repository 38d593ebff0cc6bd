"""Roofline share of the sparse attention's indexer: the least time its
score products can take where a step makes them for the selection (every
causal pair at 16 heads of 64, once a layer and step: the reference file's
``dsa_index_min_seconds``, reckoned from the configuration whatever
implements it) over the device time of the ops under the scope
``dsa/index`` in whole runs of the step program. A contraction of 64 on a
128-deep MXU, the ReLU and the weighted sum over the heads, the tiles
above the diagonal and the scores' trip through HBM to the selection show
as a loss. Where a program makes the scores again (beside the attention's
probabilities, for the indexer's loss), that is under ``dsa/attn`` and
counted there as time, not here as work."""
from benchmark.lib import scopes


def read(ctx):
    least_of = getattr(ctx["reference"], "dsa_index_min_seconds", None)
    if ctx["peaks"] is None or least_of is None:
        return None
    # `dsa/index/proj` is the indexer's projections: another scope
    got = scopes.seconds(ctx, lambda n, scope: "dsa/index" in scope
                         and "dsa/index/proj" not in scope)
    if got is None or not got[0]:
        return None
    took, _, steps = got
    least = least_of(ctx["cell"].config, ctx["peaks"], ctx["batch"])
    print(f"[dsa_index_roofline] least a step {least['least_s']:.4e} s (by "
          f"FLOPs {least['flops_s']:.4e}, by bytes {least['bytes_s']:.4e}); "
          f"a step's ops took {took / steps:.4e} s", flush=True)
    return 100.0 * least["least_s"] * steps / took
