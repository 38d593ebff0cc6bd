"""Roofline share of the gated short convolution's gates and taps: the
least time by bytes (forward B, C and u read and one row written; backward
the row's gradient and B, C, u read and their three gradients written;
bf16: the reference file's ``shortconv_min_seconds``, reckoned from the
configuration whatever implements it) over the device time of the ops
under the scope ``sconv/mix``, forward, rematerialised forward and
backward, in whole runs of the step program. What a rematerialised block
computes a second time, float32 temporaries that reach HBM and the taps'
gradient as a reduction of its own show as a loss."""
from benchmark.lib import scopes


def read(ctx):
    least_of = getattr(ctx["reference"], "shortconv_min_seconds", None)
    if ctx["peaks"] is None or least_of is None:
        return None
    got = scopes.seconds(ctx, lambda n, scope: "sconv/mix" in scope)
    if got is None or not got[0]:
        return None
    took, _, steps = got
    least = least_of(ctx["cell"].config, ctx["peaks"], ctx["batch"])
    print(f"[shortconv_roofline] least a step {least['least_s']:.4e} s (by "
          f"FLOPs {least['flops_s']:.4e}, by bytes {least['bytes_s']:.4e}); "
          f"a step's ops took {took / steps:.4e} s", flush=True)
    return 100.0 * least["least_s"] * steps / took
