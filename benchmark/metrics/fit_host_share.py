"""Share of ``fit()``'s wall time that the goodput ledger puts on the
host: ``host_sync`` plus what it could attribute to no span. Taken over the
part of the window before the profiler is switched on."""


def read(ctx):
    wall, by = ctx["fit_s"]["wall"], ctx["fit_s"]["by_category"]
    if wall <= 0:
        return None
    return 100.0 * (by["host_sync"] + max(wall - sum(by.values()), 0.0)) \
        / wall
