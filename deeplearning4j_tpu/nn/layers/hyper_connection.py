"""A residual path of several streams: manifold-constrained
hyper-connections (mHC, arXiv 2512.24880, over hyper-connections, arXiv
2409.19606), the Xing4.0 family's ``hc_mult`` streams.

Every other block of the package adds its sub-layer's output to ONE stream
(`attention.TransformerBlock`, `attention.MixerBlock`: ``x + F(norm(x))``).
`HyperConnectedBlock` carries ``n_streams`` of them side by side, (B, T,
n_streams x C) with stream 0 first. Each of its two sub-layers, the
attention and then the feed-forward, has mapping parameters of its OWN and
per token reads ``u = sum_j H_pre[j] X[j]``, runs ``y = F(norm(u))`` and
leaves ``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``, the three mappings
made from the token's own streams and ``H_res`` brought onto the doubly
stochastic matrices by Sinkhorn steps (`ops/mhc_mix.py`: `pre` and `post`,
the ONE form of either side; their equations stand there). The two ends are
graph vertices (`nn/conf/graph_vertices.py`): `StreamsInVertex` (one stream
made ``n_streams`` behind the embedding: copies) and `StreamsOutVertex`
(``n_streams`` made one before the final norm: their sum).

What a checkpointed block keeps is its input; the mappings are made again
in the backward pass.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.base import (
    InputType, Kind, LayerConf, register_layer,
)
from deeplearning4j_tpu.nn.layers.attention import _norm_layer
from deeplearning4j_tpu.ops import mhc_mix


@register_layer
@dataclasses.dataclass(frozen=True)
class HyperConnectedBlock(LayerConf):
    """One decoder layer on ``n_streams`` residual streams, (B, T,
    n_streams x n_out) in and out: the sub-layer ``attn`` (a
    `linear_attention.MultiHeadLatentAttention`, any layer (B, T, n_out)
    -> (B, T, n_out)) and then ``ffn`` (`attention.GatedMLP`,
    `attention.MoEFeedForward`), each behind its own pre-norm (``norm``,
    ``norm_epsilon``) and its own mappings: the leaves ``hc_attn`` and
    ``hc_ffn``, each ``phi`` (n_streams n_out, 2 n_streams + n_streams^2),
    ``bias`` (the columns' static part, [pre | post | res row-major]) and
    ``alpha`` (the three scalars on the dynamic part). ``sinkhorn_iters``
    alternating normalisations with ``hc_eps`` in the sums, the 4 x 4
    logits clamped to ``res_clamp``, the RMS of the mappings' input with
    ``norm_epsilon``.

    A fresh layer starts as the papers have it, all but a plain residual:
    ``alpha`` ``alpha_init`` (small: the static part decides), ``phi``
    N(0, 1 / width), the diagonal of the 4 x 4 part of ``bias``
    ``res_diagonal``. The state keeps the sub-layers' own (an expert
    layer's counters under ``ffn``) and two gauges of the last step under
    ``mhc``: ``res_gap`` (how far H_res's row and column sums are from 1
    after the Sinkhorn steps, the largest over tokens and sub-layers) and
    ``pre_entropy`` (the mean entropy, nats, of H_pre over its sum: ln
    n_streams where a sub-layer reads the streams alike, 0 where it reads
    one)."""
    n_out: int = 0
    n_streams: int = 4
    attn: Optional[LayerConf] = None
    ffn: Optional[LayerConf] = None
    norm: str = "rms"
    norm_epsilon: float = 1e-6
    sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    res_clamp: Tuple[float, float] = (-30.0, 30.0)
    alpha_init: float = 0.01
    res_diagonal: float = 4.0

    _SUBS = (("attn", "ln1"), ("ffn", "ln2"))

    def output_type(self, input_type: InputType) -> InputType:
        return InputType(Kind.RNN, (input_type.shape[0],
                                    self.n_streams * self.n_out))

    def mix(self) -> mhc_mix.Mix:
        return mhc_mix.Mix(n=self.n_streams, iters=self.sinkhorn_iters,
                           eps=self.hc_eps, rms_eps=self.norm_epsilon,
                           clamp=tuple(self.res_clamp))

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        width = self.n_streams * self.n_out
        if self.attn is None or self.ffn is None \
                or input_type.features != width:
            raise ValueError(
                f"HyperConnectedBlock needs attn, ffn and an input of "
                f"n_streams x n_out features ({input_type.features} != "
                f"{self.n_streams} x {self.n_out}); StreamsInVertex makes them")
        one = InputType(Kind.RNN, (input_type.shape[0], self.n_out))
        n, columns = self.n_streams, self.mix().columns
        keys = jax.random.split(key, 6)
        ln = _norm_layer(self.norm, self.norm_epsilon)
        params, state = {}, {"mhc": {
            "res_gap": jnp.zeros((), jnp.float32),
            "pre_entropy": jnp.zeros((), jnp.float32)}}
        for i, (name, norm) in enumerate(self._SUBS):
            params[norm], _ = ln.init(keys[3 * i], one, dtype)
            params[name], sub = getattr(self, name).init(
                keys[3 * i + 1], one, dtype)
            if sub:
                state[name] = sub
            params["hc_" + name] = {
                "phi": (jax.random.normal(keys[3 * i + 2], (width, columns))
                        / jnp.sqrt(width)).astype(dtype),
                "bias": jnp.concatenate([
                    jnp.zeros((2 * n,)),
                    self.res_diagonal * jnp.eye(n).reshape(-1)]).astype(
                        dtype),
                "alpha": jnp.full((3,), self.alpha_init, dtype)}
        return params, state

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        b, t, width = x.shape
        mix, ln = self.mix(), _norm_layer(self.norm, self.norm_epsilon)
        rngs = (None, None) if rng is None else jax.random.split(rng)
        with jax.named_scope("mhc/pre"):
            rows = x.reshape(b * t, width)
        gaps, entropies = [], []
        for (name, norm), sub_rng in zip(self._SUBS, rngs):
            hc = params["hc_" + name]
            u, h_pre, h_post, h_res, rows = mhc_mix.pre(
                rows, hc["phi"], hc["bias"], hc["alpha"], mix)
            with jax.named_scope("mhc/pre"):
                u = u.reshape(b, t, self.n_out)
            h, _ = ln.apply(params[norm], {}, u)
            y, sub = getattr(self, name).apply(
                params[name], state.get(name, {}), h, train=train,
                rng=sub_rng, mask=mask)
            if sub:
                state = {**state, name: sub}
            with jax.named_scope("mhc/post"):
                y = y.reshape(b * t, self.n_out)
            rows = mhc_mix.post(rows, y, h_res, h_post, mix)
            with jax.named_scope("mhc/sinkhorn"):
                gap, entropy = mhc_mix.gauges(h_pre, h_res)
            gaps.append(gap)
            entropies.append(entropy)
        with jax.named_scope("mhc/post"):
            y = rows.reshape(b, t, width)
            if mask is not None:
                y = y * mask[..., None].astype(y.dtype)
        with jax.named_scope("mhc/sinkhorn"):
            gauges = {"res_gap": jnp.maximum(*gaps),
                      "pre_entropy": (entropies[0] + entropies[1]) / 2}
        return y, {**state, "mhc": gauges}
