"""The scopes inside a compiled step: which layer an op belongs to, and
which part of it.

Every device op of a training step carries a `jax.named_scope` path in its
`op_name`. Two kinds of scope stand in that path, and this module is the
ONE place that spells and parses either:

- the **layer**: the containers (`nn/multilayer.py`, `nn/graph.py`) enter
  `layer_scope(name)` around every call of a layer or vertex, outside
  `jax.checkpoint`, so that the forward pass, the forward made again for
  the backward pass and the backward pass all carry it. It reads
  ``layer:<name>`` (`LAYER`); a reader tells it from a part by that
  prefix alone, whatever the model calls its layers;
- the **part**: what the op does inside its layer, entered with a literal
  `jax.named_scope("...")` at the place the work is done. `PART_SCOPES`
  lists every one the package enters, with a line on what lies under it;
  `docs/OBSERVABILITY.md` "Scopes inside the compiled step" is written
  from it and `tests/test_step_scopes.py` holds code, tuple and docs to
  each other.

`parse(op_name)` gives ``(layer, part)``: the layer is the LAST
``layer:`` component of the path, the part the LAST entry of `PART_SCOPES`
behind it (the innermost: a layer may put its own scan under one part,
`moe/blocks`, and the work inside it under others), else the last one
before it (a scope that a graph vertex was given with `add_layer(scope=)`
stands outside the vertex's layer scope), else None. An op outside every
layer (`opt/update`) has a part and no layer. So that the innermost part
is the one meant, no part's code calls a layer that enters another
(`MultiHeadAttention` norms q and k with `rms_norm`, not through
`RMSNormLayer.apply`).
"""
from __future__ import annotations

import re
from typing import Optional, Tuple

import jax

#: the prefix of a container's scope around one layer or vertex
LAYER = "layer:"

#: every part scope the package enters, and what lies under it
PART_SCOPES = (
    ("kda/proj", "Kimi Delta Attention's projections and the layout "
                 "change to (sequence, head) pairs"),
    ("kda/scan", "its convolutions, decay and the chunked recurrence (two "
                 "kernels that hand the state over; off the TPU a scan)"),
    ("kda/out", "its per-head norm, output gate and output projection"),
    ("mla/proj", "latent attention's projections, latent norms and "
                 "concatenations"),
    ("mla/rope", "the rotation of a rotated latent attention's position "
                 "dims"),
    ("mla/attn", "latent attention's kernels and the layout changes "
                 "around them"),
    ("mla/out", "latent attention's output projection"),
    ("mha/proj", "MultiHeadAttention's q, k, v and output projections"),
    ("mha/norm", "its RMS norms of q and k over the head width"),
    ("mha/rope", "its rotation of q and k"),
    ("mha/attn", "its attention kernels and the layout changes around "
                 "them"),
    ("dsa/index/proj", "a sparse attention's indexer: its three "
                       "projections, the key's norm, the rotation"),
    ("dsa/index", "the indexer's scores of every causal (query, key) "
                  "pair, made for the selection"),
    ("dsa/select", "the exact selection of each query's keys from its "
                   "scores, and the counts of the pairs kept"),
    ("dsa/attn", "attention over the kept keys, forward and backward "
                 "(the indexer's loss's backward rides its kernels)"),
    ("dsa/kl", "the indexer's loss: its divergence from the attention's "
               "mean probabilities, and the loss's way into the step"),
    ("sconv/proj", "the gated short convolution's two projections"),
    ("sconv/mix", "its gates and depth-wise causal taps"),
    ("ssd/proj", "a Mamba-2 mixer's input projection to [z ; x' ; B ; C ; "
                 "dt]"),
    ("ssd/conv", "its depth-wise causal taps, their bias and SiLU"),
    ("ssd/scan", "its step size, decay and the chunked state-space "
                 "recurrence (two kernels that hand the state over; off "
                 "the TPU a scan), the D skip"),
    ("ssd/out", "its gated group norm and output projection"),
    ("moe/route", "router scores, top-k and the kept experts' weights"),
    ("moe/latent", "the two projections of an expert layer whose routed "
                   "experts live in a latent: stream -> latent, latent -> "
                   "stream"),
    ("moe/dispatch", "the sort of (token, slot) pairs and the gather of "
                     "token rows"),
    ("moe/experts", "the grouped matrix products and the activation "
                    "between them"),
    ("moe/shared", "the shared expert on every token"),
    ("moe/combine", "rows back to tokens and the weighted sum over "
                    "slots"),
    ("moe/blocks", "the expert layer's scan over token blocks: its "
                   "slices and stacks, the weights' copies, the sums of "
                   "their gradients"),
    ("mlp/gated", "a dense gated MLP"),
    ("mhc/pre", "the reading side of a multi-stream residual's sub-layer: "
                "the RMS of the streams' row, the product with the "
                "mappings' columns, the weighted sum the sub-layer reads"),
    ("mhc/sinkhorn", "its three mappings from the columns: two sigmoids, "
                     "the clamped exponential and the Sinkhorn steps; the "
                     "block's two gauges"),
    ("mhc/post", "the writing side: the streams mixed by H_res plus the "
                 "sub-layer's output weighed by H_post"),
    ("mhc/io", "the ends of a multi-stream residual: one stream copied to "
               "several, several summed to one"),
    ("head/loss", "an output layer's product with its matrix and the "
                  "loss over it"),
    ("opt/update", "the updater's transform and the new parameters"),
    ("mtp", "a graph vertex given scope=\"mtp\": the zoo's multi-token-"
            "prediction module, outside its vertices' layer scopes"),
    ("cast", "a layer's weights cast to the compute dtype, and the "
             "gradient cast back"),
    ("embed", "the embedding's gather and its scatter-add gradient"),
    ("norm", "a layer or RMS norm over the feature axis"),
    ("residual", "a block's residual adds, residual dropout and mask"),
    ("proj", "a bias-free linear projection (LinearProjection)"),
    ("dense", "a dense layer's product, bias and activation"),
    ("conv", "a convolution and its bias"),
    ("bn", "batch normalisation: statistics, normalise, scale, shift"),
    ("act", "an activation on its own"),
    ("pool", "spatial and global pooling"),
    ("merge", "a vertex that joins inputs: concatenate, add and the "
              "other element-wise joins"),
    ("shift", "a time-series shift vertex"),
    ("layout", "padding, space-to-depth, the reshapes between layer "
               "kinds and a time-slice vertex"),
    ("reg", "the l1/l2 penalty on a layer's weights"),
)

_PARTS = {tuple(name.split("/")) for name, _ in PART_SCOPES}
# `jit(kstep)/transpose(jvp(layer:blk))/mlp/gated/mul`: the transforms'
# wrappers hide nothing the path needs
_WRAPPERS = re.compile(r"[\w\-]+\(|\)")


def layer_scope(name):
    """The `jax.named_scope` a container enters around the layer or
    vertex `name` (its index in a MultiLayerNetwork)."""
    return jax.named_scope(LAYER + re.sub(r"[/()]", "_", str(name)))


def _parts(path):
    """The entries of `PART_SCOPES` along a path, in its order."""
    i = 0
    while i < len(path):
        for n in (3, 2):                  # the longest spelling first
            if tuple(path[i:i + n]) in _PARTS:
                yield "/".join(path[i:i + n])
                i += n
                break
        else:
            if (path[i],) in _PARTS:
                yield path[i]
            i += 1


def parse(op_name: str) -> Tuple[Optional[str], Optional[str]]:
    """``(layer, part)`` of an instruction's `op_name`; None for what the
    path does not hold."""
    path = _WRAPPERS.sub("", op_name or "").split("/")
    at = max((i for i, c in enumerate(path) if c.startswith(LAYER)),
             default=-1)
    part = None
    for part in _parts(path[:max(at, 0)]):
        pass
    for part in _parts(path[at + 1:]):
        pass
    return (path[at][len(LAYER):] if at >= 0 else None), part
