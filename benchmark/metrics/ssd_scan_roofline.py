"""Roofline share of the Mamba-2 recurrences: the least time the
state-space recurrence of every Mamba-2 layer can take in a training step,
forward and backward (the token-by-token multiply-adds against reading x',
B, C and the step size and writing y, from the reference file's
``ssd_scan_min_seconds``, reckoned from the configuration whatever
implements it), over the device time of the ops under the scope
``ssd/scan`` (the step size, the decay and its running sums, the chunk
kernels and the layout changes around them; forward, the forward made again
for the backward, and the backward) in whole runs of the step program. The
chunked form does several times the recurrence's FLOPs and makes its
forward again for the backward: that shows here as a loss. None where the
program has no such scope."""
from benchmark.lib import scopes


def read(ctx):
    least_of = getattr(ctx["reference"], "ssd_scan_min_seconds", None)
    if ctx["peaks"] is None or least_of is None:
        return None
    got = scopes.seconds(ctx, lambda n, scope: "ssd/scan" in scope)
    if got is None or not got[0]:
        return None
    took, _, steps = got
    least = least_of(ctx["cell"].config, ctx["peaks"], ctx["batch"])
    print(f"[ssd_scan_roofline] least a step {least['least_s']:.4e} s (by "
          f"FLOPs {least['flops_s']:.4e}, by bytes {least['bytes_s']:.4e}); "
          f"a step's ops took {took / steps:.4e} s", flush=True)
    return 100.0 * least["least_s"] * steps / took
