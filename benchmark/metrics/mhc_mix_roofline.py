"""Roofline share of the mixing of a multi-stream residual path: the least
time the two sides of every sub-layer (the reading side: RMS, the
24-column product, the weighted sum ``u``; the writing side: ``X'`` from
``X``, ``y`` and the mappings) can take in a training step, over the device
time of the ops under the scopes ``mhc/pre`` and ``mhc/post`` (forward, the
forward made again for the backward, and the backward) in whole runs of the
step program. The work is bound by bytes, not by FLOPs (24 multiply-adds an
element of the row against 2 bytes), so the least time is `least_bytes`
over the chip's HBM bandwidth. None where the program has no such scopes.
The Sinkhorn steps (``mhc/sinkhorn``) move 24 columns a token, nothing
beside the streams, and are `mhc_share`'s to show."""
from benchmark.lib import scopes


def least_bytes(cfg, batch: int) -> dict:
    """The least bytes the mixing of ONE training step of ``batch``
    sequences has to move, counted from the configuration whatever
    implements it, in elements of width C = ``hidden_size`` a token, n =
    ``hc_mult`` streams, each element in the compute dtype. A layer is two
    sub-layers and is checkpointed: what is kept is its input.

    forward, 14 C a sub-layer (at n = 4): the reading side reads X once
      (n C) and writes u (C); the writing side reads X once (n C) and y (C)
      and writes X' (n C): (3 n + 2) C;
    forward made again, 19 C a LAYER: its first sub-layer whole ((3 n + 2)
      C: the second sub-layer's backward reads the streams it left) and the
      reading side of its second ((n + 1) C); the streams the layer leaves
      are nobody's to read again;
    backward, 27 C a sub-layer: the writing side reads X' 's cotangent
      (n C), X (n C) and y (C) and writes X's part (n C) and y's cotangent
      (C); the reading side reads X (n C), u's cotangent (C) and that part
      (n C) and writes X's cotangent (n C): (6 n + 3) C;

    101 C a token and layer; plus, a pass of a sub-layer, the 24-column
    product's other operand ``phi`` (n C x (2 n + n^2)), read once forward
    and once made again and, backward, read once and written once as its
    gradient; the columns themselves are 24 numbers a token. A sub-layer F
    between the two sides keeps them from being one pass."""
    n, c = cfg["hc_mult"], cfg["hidden_size"]
    size = 2 if cfg["compute_dtype"] == "bfloat16" else 4
    tokens = batch * cfg["image_size"] ** 2 * cfg["channels"] // 2
    layers = cfg["num_hidden_layers"]
    a_layer = 2 * (3 * n + 2) + (3 * n + 2) + (n + 1) + 2 * (6 * n + 3)
    streams = layers * tokens * size * c * a_layer
    phi = 2 * layers * 4 * size * n * c * (2 * n + n * n)
    return {"bytes": streams + phi, "streams": streams, "phi": phi}


def read(ctx):
    if ctx["peaks"] is None:
        return None
    got = scopes.seconds(ctx, lambda n, scope: "mhc/pre" in scope
                         or "mhc/post" in scope)
    if got is None or not got[0]:
        return None
    took, _, steps = got
    least = least_bytes(ctx["cell"].config, ctx["batch"])
    least_s = least["bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    print(f"[mhc_mix_roofline] least a step {least['bytes'] / 1e9:.3f} GB = "
          f"{least_s:.4e} s at the HBM's peak; a step's ops took "
          f"{took / steps:.4e} s", flush=True)
    return 100.0 * least_s * steps / took
