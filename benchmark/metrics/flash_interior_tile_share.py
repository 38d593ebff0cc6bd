"""Share of the flash attention's live (q block, k block) tiles on which
its kernels mask nothing: the program's gauge
``flash_tile_share{kind="interior"}`` (set where a call of
``ops.flash_attention`` is traced, from the call's geometry: whether it has
a key mask, whether it is causal, and where each tile lies against the
diagonal), read from ``deeplearning4j_tpu.monitor.dump()``. 88.2 % (120 of
136 live tiles) for a causal call over 8,192 positions at blocks of 512
with no key mask; 0 for a call with a key mask, which is applied on every
tile. ``None`` where the program has no such gauge."""


def read(ctx):
    from deeplearning4j_tpu import monitor
    series = monitor.dump().get("flash_tile_share", {}).get("series", [])
    for s in series:
        if s["labels"].get("kind") == "interior":
            return s["value"]
    return None
