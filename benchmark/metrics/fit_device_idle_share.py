"""Share of the traced stretch of the window in which no operation ran on
the device: 1 - busy union / traced stretch, from the trace alone. The
stretch lies inside the window's one ``fit()`` call, in steady state; what
the profiler itself costs the host while it is on falls into it."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.devices:
        return None
    return 100.0 * tr.idle_share()
