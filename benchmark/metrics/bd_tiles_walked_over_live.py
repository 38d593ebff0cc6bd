"""Tiles the flash kernels fetch and compute over the (q block, k block)
tiles in which the call's visibility rule lets some query see some key:
the program's gauge ``flash_tiles_walked_over_live`` (set where a call of
``ops.flash_attention`` is traced: the walk counted from the step -> tile
maps of its geometry, the tiles with a pair counted tile by tile from the
rule), through the adapter's ``tiles_walked_over_live()``. 1.0 where no
empty tile is walked; a causal walk over the 2L stream with the
block-diffusion rule as a mask would read 528 / 288 = 1.83 at L = 8,192 in
blocks of 512. ``None`` where the program has no such gauge."""


def read(ctx):
    return getattr(ctx["system"], "tiles_walked_over_live", lambda: None)()
