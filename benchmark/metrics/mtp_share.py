"""Share of the step program's device time that the multi-token-prediction
module takes: every op under the scope ``mtp`` (the shifted ids' embedding,
the two norms, the merge and its projection, one more block of latent
attention and experts, the final norm and the shared head's second loss),
forward, rematerialised forward and backward, in whole runs of the step
program. The inner scopes of those ops (``mla/attn``, ``moe/experts``,
``head/loss``) still count them for the readers of those."""
from benchmark.lib import scopes


def read(ctx):
    return scopes.share(ctx, "mtp")
