"""Roofline share of the KDA recurrences: the least time the gated delta
rule of every KDA layer can take in a training step, forward and backward
(the token-by-token multiply-adds against reading q, k, v, decay, beta and
writing o, from the reference file's ``kda_scan_min_seconds``), over the
device time of the ops under the scope ``kda/scan`` (the chunk algebra,
the triangular solve, the scan over chunks; forward, the forward made
again for the backward, and the backward) in whole runs of the step
program. The chunked form does several times the recurrence's FLOPs and
makes them again for the backward: that shows here as a loss."""
from benchmark.lib import scopes


def read(ctx):
    if ctx["peaks"] is None:
        return None
    got = scopes.seconds(ctx, lambda n, scope: "kda/scan" in scope)
    if got is None or not got[0]:
        return None
    took, _, steps = got
    least = ctx["reference"].kda_scan_min_seconds(
        ctx["cell"].config, ctx["peaks"], ctx["batch"])
    print(f"[kda_scan_roofline] least a step {least['least_s']:.4e} s (by "
          f"FLOPs {least['flops_s']:.4e}, by bytes {least['bytes_s']:.4e}); "
          f"a step's ops took {took / steps:.4e} s", flush=True)
    return 100.0 * least["least_s"] * steps / took
