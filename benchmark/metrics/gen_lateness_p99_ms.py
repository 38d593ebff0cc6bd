"""How late the load generator ran: 99th percentile of (sent - due) over
the requests due inside the window. A starved generator must not read as a
fast server."""


def read(ctx):
    return ctx["stats"].get("gen_lateness_p99_ms")
