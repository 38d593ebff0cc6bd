"""1 - (union of the intervals in which an op ran) / traced window."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.devices:
        return None
    return 100.0 * tr.idle_share()
