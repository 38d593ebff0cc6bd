"""Share of the step program's device time under the attention's scope
``mha/attn`` (the flash kernels under the block-diffusion rule and the
layout changes around them), forward and backward, in whole runs of the
step program: how much of the step the mechanism is (every row exists
twice and sees a quarter of the stream's square); also printed as
milliseconds a step by the attention's scopes."""
from benchmark.lib import scopes

_PRINTED = ("mha/proj", "mha/norm", "mha/rope", "mha/attn")


def read(ctx):
    whole = scopes.seconds(ctx, lambda n, scope: "mha/attn" in scope)
    if whole is None or not whole[0] or not whole[1]:
        return None
    ms = {m: round(1e3 * scopes.seconds(
        ctx, lambda n, scope, m=m: m in scope)[0] / whole[2], 3)
        for m in _PRINTED}
    print(f"[bd_attn_share] device ms a step {ms}", flush=True)
    return 100.0 * whole[0] / whole[1]
