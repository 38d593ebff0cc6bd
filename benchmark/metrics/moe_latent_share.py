"""Share of the step program's device time that the expert layers' two
latent projections take (stream -> latent in front of the routed experts,
latent -> stream behind their weighted sum): the ops under the scope
``moe/latent``, forward, rematerialised forward and backward, in whole runs
of the step program. None where the program has no such scope."""
from benchmark.lib import scopes


def read(ctx):
    return scopes.share(ctx, "moe/latent") or None
