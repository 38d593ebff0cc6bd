"""Share of the step program's device time under the scope ``opt/update``:
AdamW's moments, the decayed weights and the new parameters, a
bandwidth-bound pass over 16 bytes a parameter."""
from benchmark.lib import scopes


def read(ctx):
    scopes.table(ctx)       # the step by scope, once a traced run
    return scopes.share(ctx, "opt/update")
