"""Share of the step program's device time under the scope ``dsa/select``:
the exact selection of each query's keys from its indexer scores (the
threshold a query and the tie it ends on), in whole runs of the step
program."""
from benchmark.lib import scopes


def read(ctx):
    got = scopes.seconds(ctx, lambda n, scope: "dsa/select" in scope)
    if got is None or not got[0] or not got[1]:
        return None
    return 100.0 * got[0] / got[1]
