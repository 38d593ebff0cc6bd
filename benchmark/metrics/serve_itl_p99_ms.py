"""99th percentile of the gaps between tokens that end inside the window.
Not a deciding metric: with a decode step of 141 ms and prefill chunks of
46 ms a gap is 150, 199 or 248 ms as none, one or two chunks fall into it,
and the 99th percentile sits where the third class begins (readings of 240
and of 312 ms from the same code and seed)."""


def read(ctx):
    return ctx["stats"].get("itl_p99_ms")
