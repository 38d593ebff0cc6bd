"""Share of the step program's device time under the sparse attention's
scopes, all of ``dsa/*`` (the indexer's projections and scores, the
selection, the attention over the kept keys, the indexer's loss), forward,
rematerialised forward and backward, in whole runs of the step program:
how much of the step the mechanism is; also printed as milliseconds a step
by scope, beside the attention's own ``mha/*`` scopes."""
from benchmark.lib import scopes

_PRINTED = ("dsa/index/proj", "dsa/index", "dsa/select", "dsa/attn",
            "dsa/kl", "mha/proj", "mha/norm", "mha/rope")


def _under(marker):
    if marker == "dsa/index":
        return lambda n, scope: "dsa/index" in scope \
            and "dsa/index/proj" not in scope
    return lambda n, scope: marker in scope


def read(ctx):
    whole = scopes.seconds(ctx, lambda n, scope: "dsa/" in scope)
    if whole is None or not whole[0] or not whole[1]:
        return None
    ms = {m: round(1e3 * scopes.seconds(ctx, _under(m))[0] / whole[2], 3)
          for m in _PRINTED}
    print(f"[dsa_share] device ms a step {ms}", flush=True)
    return 100.0 * whole[0] / whole[1]
