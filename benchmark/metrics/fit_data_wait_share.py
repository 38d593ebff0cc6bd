"""Share of ``fit()``'s wall time spent waiting for the input pipeline
(``train/etl`` spans, the goodput ledger's ``data_wait``). Taken over the
part of the window before the profiler is switched on."""


def read(ctx):
    wall = ctx["fit_s"]["wall"]
    if wall <= 0:
        return None
    return 100.0 * ctx["fit_s"]["by_category"]["data_wait"] / wall
