"""Share of the step program's device time that routing, dispatch and
combine take: the ops under the scopes ``moe/route`` (router logits,
top-k, weights), ``moe/dispatch`` (the sort by expert, the gather of token
rows into expert order) and ``moe/combine`` (back to token order, the
weighted sum), forward, rematerialised forward and backward."""
from benchmark.lib import scopes


def read(ctx):
    return scopes.share(ctx, "moe/route", "moe/dispatch", "moe/combine")
