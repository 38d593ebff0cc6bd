"""Share of the scheduler loop's time spent admitting requests, from the
program's own split of its loop (``admission`` / ``step_compute`` /
``page_stall`` / ``idle`` seconds, read at the window's two edges)."""


def read(ctx):
    total = sum(ctx["split"].values())
    if not total:
        return None
    return 100.0 * ctx["split"].get("admission", 0.0) / total
