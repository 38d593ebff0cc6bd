"""Roofline share of the latent attention: the least time the scores and
weighted values INSIDE the causal mask can take in a training step, forward
and backward (the reference file's ``mla_attn_min_seconds``), over the
device time of the ops under the scope ``mla/attn`` (the flash kernels at
192-wide q.k and 128-wide v and the layout changes around them) in whole
runs of the step program. A block computed and then masked, and the forward
kernel's second run when the block is rematerialised, show as a loss."""
from benchmark.lib import scopes


def read(ctx):
    if ctx["peaks"] is None:
        return None
    got = scopes.seconds(ctx, lambda n, scope: "mla/attn" in scope)
    if got is None or not got[0]:
        return None
    took, _, steps = got
    least = ctx["reference"].mla_attn_min_seconds(
        ctx["cell"].config, ctx["peaks"], ctx["batch"])
    print(f"[mla_attn_roofline] least a step {least['least_s']:.4e} s (by "
          f"FLOPs {least['flops_s']:.4e}, by bytes {least['bytes_s']:.4e}); "
          f"a step's ops took {took / steps:.4e} s", flush=True)
    return 100.0 * least["least_s"] * steps / took
