"""ttft_p50 of the time to first token over the requests due inside the
window, timed from when each was due (the load generator's records)."""


def read(ctx):
    return ctx["stats"].get("ttft_p50_ms")
