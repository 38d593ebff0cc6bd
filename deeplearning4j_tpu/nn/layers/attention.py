"""Attention / transformer layers — TPU-native capability.

No DL4J analog (SURVEY.md §5.7: the reference predates attention; its only
long-sequence tools are truncated BPTT + masking). These layers are the
foundation the sequence-parallel / ring-attention machinery
(`parallel/ring.py`) builds on, designed mesh-first:

- activations are (B, T, F) — the framework's RNN kind — so attention
  composes with the existing recurrent/masking infrastructure;
- head and MLP dims are sized for MXU tiles (multiples of 128 recommended);
- `MultiHeadAttention.apply` uses a blockwise-stable softmax and respects
  (B, T) masks with DL4J mask semantics (0 = padded step);
- sharding rules: "model"-axis tensor parallelism shards head projections
  column-wise and output row-wise (Megatron pattern), "seq"-axis sequence
  parallelism is handled by ring attention at the network level;
- ONE expert layer, `MoEFeedForward`: it routes every token over all the
  experts of the layer, is told which of them it holds, sorts the
  (token, slot) pairs by expert into grouped matrix products over the
  held ones (cost follows the row tier that holds the pairs routed here,
  not the expert count and not the worst case), drops nothing, and adds
  a shared expert where the model has one;
- a layer may add a loss of its own to the step (`attach_auxiliary_loss`):
  `MultiHeadAttention(indexer=)`, the learned sparse attention, trains its
  `LightningIndexer` that way while the containers' score stays the
  output layers'. The linear
  and latent attentions of the hybrid LMs live beside this file in
  `linear_attention.py` and ride `TransformerBlock` through its ``attn``
  field.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from deeplearning4j_tpu.nn.conf.base import (
    InputType, Kind, LayerConf, register_layer,
)
from deeplearning4j_tpu.nn.initializers import get_initializer
from deeplearning4j_tpu.ops import REMAT_KEEP
from deeplearning4j_tpu.util.platform import is_tpu_backend

# --- context-parallel mode -------------------------------------------------
# When the sequence axis is sharded over the mesh (ContextParallelTrainer,
# parallel/context.py), attention must (a) use ring attention instead of
# local dense attention and (b) offset positions by this shard's global
# start. The trainer announces the active mesh axis here; layers read it.
_CONTEXT_PARALLEL_AXIS: Optional[str] = None


class context_parallel:
    """Context manager marking that the T axis is sharded over `axis_name`
    (inside shard_map). Used by ContextParallelTrainer."""

    def __init__(self, axis_name: str):
        self.axis_name = axis_name

    def __enter__(self):
        global _CONTEXT_PARALLEL_AXIS
        self._prev = _CONTEXT_PARALLEL_AXIS
        _CONTEXT_PARALLEL_AXIS = self.axis_name
        return self

    def __exit__(self, *exc):
        global _CONTEXT_PARALLEL_AXIS
        _CONTEXT_PARALLEL_AXIS = self._prev


def _seq_offset(t_local):
    """Global position offset of this shard's sequence slice (0 when the
    sequence axis is not sharded)."""
    if _CONTEXT_PARALLEL_AXIS is None:
        return 0
    return jax.lax.axis_index(_CONTEXT_PARALLEL_AXIS) * t_local


@register_layer
@dataclasses.dataclass(frozen=True)
class LayerNormLayer(LayerConf):
    """Layer normalization over the feature axis."""
    epsilon: float = 1e-5

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        f = input_type.features
        return {"gamma": jnp.ones((f,), dtype),
                "beta": jnp.zeros((f,), dtype)}, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        with jax.named_scope("norm"):
            mean = jnp.mean(x, axis=-1, keepdims=True)
            var = jnp.var(x, axis=-1, keepdims=True)
            y = (x - mean) * jax.lax.rsqrt(var + self.epsilon)
            return y * params["gamma"] + params["beta"], state


@register_layer
@dataclasses.dataclass(frozen=True)
class RMSNormLayer(LayerConf):
    """Root-mean-square normalization over the feature axis (no mean, no
    bias): ``x / sqrt(mean(x^2) + eps) * gamma``, the statistics in
    float32 whatever the compute dtype."""
    epsilon: float = 1e-6

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        return {"gamma": jnp.ones((input_type.features,), dtype)}, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        with jax.named_scope("norm"):
            return rms_norm(x, params["gamma"], self.epsilon), state


def rms_norm(x, gamma, epsilon):
    """`RMSNormLayer`'s arithmetic under no scope of its own, for a layer
    that norms inside one of its parts (`mha/norm`)."""
    acc_t = jnp.promote_types(jnp.float32, x.dtype)
    xf = x.astype(acc_t)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + epsilon)
    return (y * gamma.astype(acc_t)).astype(x.dtype)


def _norm_layer(kind: str, epsilon: Optional[float]):
    """The normalization a block names: "layer" (LayerNormLayer) or "rms"
    (RMSNormLayer); ``epsilon`` None keeps the layer's own default."""
    cls = {"layer": LayerNormLayer, "rms": RMSNormLayer}.get(kind)
    if cls is None:
        raise ValueError(f"unknown norm {kind!r}: 'layer' or 'rms'")
    return cls() if epsilon is None else cls(epsilon=epsilon)


def _split_heads(x, n_heads):
    b, t, f = x.shape
    return x.reshape(b, t, n_heads, f // n_heads)


def _merge_heads(x):
    b, t, h, d = x.shape
    return x.reshape(b, t, h * d)


def rope(x, positions, base: float = 10000.0, sections=None):
    """Rotary position embedding on (B, T, H, D): dim ``j`` of the first
    half turned against dim ``j + D/2`` by ``positions * base^(-j/(D/2))``.
    ``sections`` (their sum ``D/2``) shares the frequencies out among
    several ROWS of positions, ``positions`` then (rows, B?, T): frequency
    ``j`` turns by the row whose section holds ``j`` (a multimodal LM's
    time, height and width rows, sections 16/24/24 of 64; on text the rows
    coincide and the result is that of one row)."""
    d = x.shape[-1]
    half = d // 2
    # trig in >= f32 (f64 under float64 gradient checking — a hard f32 cast
    # here corrupts the finite-difference oracle)
    acc_t = jnp.promote_types(jnp.float32, x.dtype)
    freqs = base ** (-jnp.arange(0, half, dtype=acc_t) / half)
    if sections is not None:
        if sum(sections) != half or len(sections) != positions.shape[0]:
            raise ValueError(f"sections {tuple(sections)} have to add up to "
                             f"{half} and name {positions.shape[0]} rows")
        row_of = [r for r, n in enumerate(sections) for _ in range(n)]
        positions = jnp.moveaxis(positions[jnp.asarray(row_of)], 0, -1)
        angles = positions.astype(acc_t) * freqs          # (B?, T, half)
    else:
        angles = positions[..., None].astype(acc_t) * freqs
    while angles.ndim < x.ndim:
        angles = angles[..., None, :] if angles.ndim == x.ndim - 1 \
            else angles[None]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin],
        axis=-1).astype(x.dtype)


def dot_product_attention(q, k, v, *, mask=None, causal=False,
                          q_offset=0, k_offset=0, dropout=0.0, rng=None):
    """Stable softmax attention on (B, T, H, D) tensors.

    mask: (B, Tk) 0/1 key-validity mask (DL4J mask semantics).
    q_offset/k_offset: global position offsets (used by ring attention to
    apply causal masking across sequence shards). v's head width may
    differ from q's and k's (latent attention: 192-wide q.k, 128-wide v,
    or 256 and 256);
    the scale is that of q's width."""
    b, tq, h, d = q.shape
    tk = k.shape[1]
    # accumulate scores in >= f32 (bf16 inputs -> f32 on the MXU; f64 stays
    # f64 so float64 gradient checks keep a clean numeric oracle)
    acc_t = jnp.promote_types(jnp.float32, q.dtype)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, acc_t))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=acc_t) * scale
    neg = jnp.asarray(-1e30, acc_t)
    if causal:
        qpos = q_offset + jnp.arange(tq)
        kpos = k_offset + jnp.arange(tk)
        causal_mask = qpos[:, None] >= kpos[None, :]
        scores = jnp.where(causal_mask[None, None], scores, neg)
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :].astype(bool), scores, neg)
    # fully-masked query rows (all keys invalid) softmax to uniform garbage;
    # zero them at the end via the weights' max
    m = jnp.max(scores, axis=-1, keepdims=True)
    weights = jnp.exp(scores - jax.lax.stop_gradient(m))
    denom = jnp.sum(weights, axis=-1, keepdims=True)
    weights = weights / jnp.maximum(denom, 1e-30)
    weights = jnp.where(m <= neg / 2, 0.0, weights)
    if dropout > 0.0 and rng is not None:
        keep = 1.0 - dropout
        weights = weights * jax.random.bernoulli(rng, keep, weights.shape) / keep
    out = jnp.einsum("bhqk,bkhd->bqhd", weights.astype(v.dtype), v)
    return out


def block_diffusion_visible(rows, cols, length: int, block: int):
    """The visibility rule of block-diffusion training over a stream of
    ``2 * length`` rows ``[noisy copy ; clean copy]``: bool (rows, cols)
    from absolute row and column indices. With ``noisy(i) = i < length``,
    ``pos(i) = i mod length`` and ``blk(i) = pos(i) // block``, row i sees
    row j iff both are noisy and ``blk(j) == blk(i)`` (a noisy block sees
    itself, both directions), or i is noisy, j clean and ``blk(j) <
    blk(i)`` (and the clean copies of the blocks before it), or both are
    clean and ``blk(j) <= blk(i)`` (block-causal); a clean row never sees
    a noisy one (BD3-LMs' three mask parts)."""
    qn, kn = (rows < length)[:, None], (cols < length)[None, :]
    qb = ((rows % length) // block)[:, None]
    kb = ((cols % length) // block)[None, :]
    return (qn & kn & (kb == qb)) | (qn & ~kn & (kb < qb)) \
        | (~qn & ~kn & (kb <= qb))


def block_diffusion_attention(q, k, v, *, block: int,
                              block_size: Optional[int] = None):
    """Softmax attention on (B, 2L, H, D) under `block_diffusion_visible`
    in plain XLA: the path of the CPU, the tests and a TPU layer whose
    implementation is "dense". ``block_size`` queries at a time (None: all
    at once) against every key, each block's scores made again in the
    backward pass, so that no (2L)^2 array outlives a block; every row
    sees a key (a noisy row itself, a clean row key 0 of its half)."""
    b, t, h, d = q.shape
    acc_t = jnp.promote_types(jnp.float32, q.dtype)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, acc_t))
    bs = t if block_size is None else min(block_size, t)
    if t % bs:
        raise ValueError(f"stream {t} not divisible by block {bs}")
    cols = jnp.arange(t)

    @jax.checkpoint
    def one(qb, start):
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k,
                       preferred_element_type=acc_t) * scale
        seen = block_diffusion_visible(start + jnp.arange(bs), cols, t // 2,
                                       block)
        w = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), v)

    if bs == t:
        return one(q, 0)
    out = jax.lax.map(
        lambda a: one(*a),
        (jnp.moveaxis(q.reshape(b, t // bs, bs, h, d), 1, 0),
         jnp.arange(0, t, bs)))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def attach_auxiliary_loss(y, loss, coef: float):
    """How a layer adds a loss of its own to the step: the identity on
    ``y`` whose backward pass hands the scalar ``loss`` a cotangent of
    ``coef``, so that the step's gradient is that of ``score + coef *
    loss`` while the score the containers report and ``fit()`` prints
    stays the output layers' (the way the sparse-expert families' modelling
    code attaches an auxiliary loss to a hidden state). No container code
    is involved: it works on every fit path of both containers and under
    gradient checkpointing. The cotangent is ``coef`` whatever the score's
    own scale: a caller that scales the score (loss scaling) scales
    ``coef`` likewise. The layer keeps the loss's VALUE in its state for a
    listener to publish."""
    return y


def _attach_fwd(y, loss, coef):
    return y, loss


def _attach_bwd(coef, loss, g):
    return g, jnp.full_like(loss, coef)


attach_auxiliary_loss.defvjp(_attach_fwd, _attach_bwd)


def _add_u64(total, counts):
    """``total`` ((2,) uint32: low word, high word) plus the sum of
    ``counts`` ((B,) uint32, fewer than 65,536 of them), with the carries:
    a layer's state counts in uint32 words and a step of long sequences
    passes 2**32 pairs in a few steps."""
    counts = counts.astype(jnp.uint32)
    lo16, hi16 = jnp.sum(counts & 0xFFFF), jnp.sum(counts >> 16)
    shifted = hi16 << 16
    add_lo = shifted + lo16
    add_hi = (hi16 >> 16) + (add_lo < shifted).astype(jnp.uint32)
    lo = total[0] + add_lo
    hi = total[1] + add_hi + (lo < total[0]).astype(jnp.uint32)
    return jnp.stack([lo, hi])


@register_layer
@dataclasses.dataclass(frozen=True)
class LightningIndexer(LayerConf):
    """The learned indexer of a sparse attention (`MultiHeadAttention(
    indexer=)`; DeepSeek-V3.2's "lightning indexer"): ``n_heads`` small
    query heads of ``head_dim`` on ONE key head, the key LayerNormed
    (gain and bias of ``head_dim``), both rotated over all ``head_dim``
    dims at ``rope_base``, and a weight a query and head,

        I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s]),
        w = x Ww * n_heads^-1/2 * head_dim^-1/2;

    a query attends the ``topk`` keys ``s <= t`` with the largest ``I``.
    It reads the DETACHED input of the attention and is trained by
    ``loss_coef`` times the Kullback-Leibler divergence of its softmax
    over the kept keys from the attention's probabilities averaged over
    the query heads (`ops/dsa_attention.py`), and by nothing else; the
    attention's own weights get nothing from that loss."""
    n_heads: int = 16
    head_dim: int = 64
    topk: int = 2048
    rope_base: float = 10000.0
    norm_epsilon: float = 1e-6
    loss_coef: float = 1.0
    weight_init: str = "xavier"

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        f_in, wide = input_type.features, self.n_heads * self.head_dim
        w_init = get_initializer(self.weight_init)
        ks = jax.random.split(key, 3)
        return {
            "Wq": w_init(ks[0], (f_in, wide), f_in, wide, dtype),
            "Wk": w_init(ks[1], (f_in, self.head_dim), f_in, self.head_dim,
                         dtype),
            "Ww": w_init(ks[2], (f_in, self.n_heads), f_in, self.n_heads,
                         dtype),
            "k_gamma": jnp.ones((self.head_dim,), dtype),
            "k_beta": jnp.zeros((self.head_dim,), dtype),
        }, {}

    def project(self, params, x, positions):
        """x (B, T, F) -> qI (B, T, n_heads, head_dim), kI (B, T,
        head_dim), both in x's dtype, and w (B, T, n_heads) float32."""
        acc_t = jnp.promote_types(jnp.float32, x.dtype)
        qi = _split_heads(x @ params["Wq"], self.n_heads)
        ki = (x @ params["Wk"]).astype(acc_t)
        mean = jnp.mean(ki, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(ki - mean), axis=-1, keepdims=True)
        ki = (ki - mean) * jax.lax.rsqrt(var + self.norm_epsilon) \
            * params["k_gamma"].astype(acc_t) + params["k_beta"].astype(acc_t)
        qi = rope(qi, positions, self.rope_base)
        ki = rope(ki.astype(x.dtype)[:, :, None, :], positions,
                  self.rope_base)[:, :, 0, :]
        w = jnp.dot(x, params["Ww"], preferred_element_type=acc_t) \
            * (self.n_heads ** -0.5 * self.head_dim ** -0.5)
        return qi, ki, w

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        raise TypeError("LightningIndexer is a part of MultiHeadAttention("
                        "indexer=), not a layer of its own")


@register_layer
@dataclasses.dataclass(frozen=True)
class MultiHeadAttention(LayerConf):
    """Multi-head self-attention over (B, T, F).

    n_out: model width (must divide by n_heads). causal: autoregressive
    masking. use_rope: rotary positions at base ``rope_base``, the halves
    of a head rotated against each other (otherwise positions come from an
    embedding layer upstream). ``n_kv_heads`` (None: ``n_heads``) is
    grouped-query attention: ``Wk`` and ``Wv`` are ``(f_in, n_kv_heads *
    head_dim)`` and query head ``h`` reads key/value head ``h // (n_heads
    // n_kv_heads)``; the fused kernel reads k and v at their own head
    count, every other path repeats them. ``qk_norm`` adds an RMSNorm over
    the head width with a learned gain of ``head_dim`` each to q
    (``q_norm``) and to k (``k_norm``), every head alike, BEFORE the
    rotation (``norm_epsilon``). Masks follow DL4J semantics: (B, T) 0/1,
    masked steps neither attend nor get attended to, and their outputs are
    zeroed (MaskZeroLayer behavior). ``head_dim`` (None: ``n_out //
    n_heads``) is the head width where the heads together are not the
    stream's width: ``Wq`` is ``(f_in, n_heads * head_dim)``, ``Wo``
    ``(n_heads * head_dim, n_out)``. ``rope_sections`` shares the rotary
    frequencies out among three rows of positions (`rope`; on text, which
    is all this layer is given, the rows coincide). ``indexer`` (a
    `LightningIndexer`) makes the attention SPARSE: causal, every query
    head attends only the ``indexer.topk`` keys the indexer scores highest
    for the query (exact; `ops/dsa_attention.py`), and the indexer is
    trained by its own loss through `attach_auxiliary_loss`. Such a layer
    keeps in its state the pairs it kept and the causal pairs it chose
    among (``pairs_selected_total``, ``pairs_causal_total``: two uint32
    words each, low then high) and its last ``indexer_kl``;
    ``train.listeners.ExpertLoadListener`` publishes them.
    ``block_diffusion`` (a block length) is the visibility rule of
    block-diffusion training in ``causal``'s place
    (`block_diffusion_visible`): the input is the stream ``[noisy copy ;
    clean copy]`` of ``2 L`` steps, rotary positions restart at the clean
    half (``pos(i) = i mod L``), the fused kernel walks only the tiles
    the rule leaves visible and the XLA paths compute the same rule. It
    is refused, by name, together with ``causal``, an indexer, attention
    dropout, a key mask or a context-parallel axis. Scopes:
    ``mha/proj``, ``mha/norm``, ``mha/rope``, ``mha/attn``; with an
    indexer ``dsa/index/proj``, ``dsa/index``, ``dsa/select``,
    ``dsa/attn``, ``dsa/kl`` in ``mha/attn``'s place."""
    n_out: int = 0
    n_heads: int = 8
    n_in: Optional[int] = None
    head_dim: Optional[int] = None
    rope_sections: Optional[Tuple[int, ...]] = None
    indexer: Optional[LayerConf] = None
    causal: bool = False
    block_diffusion: Optional[int] = None
    use_rope: bool = True
    n_kv_heads: Optional[int] = None
    qk_norm: bool = False
    norm_epsilon: float = 1e-5          # of the q/k norms
    rope_base: float = 10000.0
    attention_dropout: float = 0.0
    weight_init: str = "xavier"
    has_bias: bool = False
    # "dense" | "blockwise" (O(T*block) memory) | "flash" (fused Pallas
    # kernel, ops/flash_attention.py). On TPU, dropout-free blockwise AND
    # flash both run the fused kernel (same algorithm; the kernel is its
    # fastest realization); with attention dropout or off-TPU they use
    # the XLA blockwise lowering. Under a ContextParallelTrainer the
    # layer switches to ring attention (fused per-shard on TPU)
    attention_impl: str = "dense"
    block_size: int = 512

    def output_type(self, input_type: InputType) -> InputType:
        t = input_type.shape[0]
        return InputType(Kind.RNN, (t, self.n_out))

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        if self.head_dim is None and self.n_out % self.n_heads:
            raise ValueError(f"n_out {self.n_out} not divisible by "
                             f"n_heads {self.n_heads}")
        d = self._head_dim()
        if self.use_rope and d % 2:
            raise ValueError(
                f"rotary embeddings need an even head dim; got "
                f"{d} (n_out={self.n_out}, "
                f"n_heads={self.n_heads}) — disable use_rope or resize")
        if self.n_heads % self._kv_heads():
            raise ValueError(f"n_kv_heads {self.n_kv_heads} does not divide "
                             f"n_heads {self.n_heads}")
        if self.indexer is not None and not (
                self.causal and self.attention_dropout == 0.0):
            raise ValueError("an indexer needs a causal attention without "
                             "attention dropout")
        if self.block_diffusion is not None:
            self._check_block_diffusion(input_type.shape[0])
        f_in = self.n_in or input_type.features
        wide = self.n_heads * d
        kv = self._kv_heads() * d
        w_init = get_initializer(self.weight_init)
        ks = jax.random.split(key, 4)
        p = {
            "Wq": w_init(ks[0], (f_in, wide), f_in, wide, dtype),
            "Wk": w_init(ks[1], (f_in, kv), f_in, kv, dtype),
            "Wv": w_init(ks[2], (f_in, kv), f_in, kv, dtype),
            "Wo": w_init(ks[3], (wide, self.n_out), wide, self.n_out, dtype),
        }
        if self.has_bias:
            for b, n in (("bq", wide), ("bk", kv), ("bv", kv),
                         ("bo", self.n_out)):
                p[b] = jnp.zeros((n,), dtype)
        if self.qk_norm:
            for g in ("q_norm", "k_norm"):
                p[g] = jnp.ones((d,), dtype)
        if self.indexer is None:
            return p, {}
        p["indexer"], _ = self.indexer.init(
            jax.random.fold_in(key, 4), InputType(Kind.RNN, (
                input_type.shape[0], f_in)), dtype)
        words = jnp.zeros((2,), jnp.uint32)
        return p, {"pairs_selected_total": words, "pairs_causal_total": words,
                   "indexer_kl": jnp.zeros((), jnp.float32)}

    def _kv_heads(self):
        return self.n_kv_heads or self.n_heads

    def _check_block_diffusion(self, t, mask=None):
        """Refuse by name what the block-diffusion rule is not combined
        with, where the layer is initialised and where it is applied."""
        for what, given in (
                ("an indexer", self.indexer is not None),
                ("causal", self.causal),
                ("attention dropout", self.attention_dropout != 0.0),
                ("a key mask", mask is not None),
                ("a context-parallel axis",
                 _CONTEXT_PARALLEL_AXIS is not None)):
            if given:
                raise ValueError(
                    f"block_diffusion attention does not take {what}: the "
                    "rule is a visibility of its own over whole unpadded "
                    "[noisy ; clean] streams on one device")
        if self.block_diffusion < 1 or (t is not None and (
                t % 2 or (t // 2) % self.block_diffusion)):
            raise ValueError(
                f"block_diffusion {self.block_diffusion} needs a stream of "
                f"twice a whole number of blocks, not {t} steps")

    def _head_dim(self):
        return self.head_dim or self.n_out // self.n_heads

    def _qkv(self, params, x):
        q = x @ params["Wq"]
        k = x @ params["Wk"]
        v = x @ params["Wv"]
        if self.has_bias:
            q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
        h, hk = self.n_heads, self._kv_heads()
        return _split_heads(q, h), _split_heads(k, hk), _split_heads(v, hk)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        attn_rng = None
        if rng is not None:
            rng, attn_rng = jax.random.split(rng)
        x = self.maybe_dropout_input(x, train, rng)
        with jax.named_scope("mha/proj"):
            q, k, v = self._qkv(params, x)
        if self.qk_norm:
            with jax.named_scope("mha/norm"):
                q = rms_norm(q, params["q_norm"], self.norm_epsilon)
                k = rms_norm(k, params["k_norm"], self.norm_epsilon)
        t_loc = x.shape[1]
        if self.block_diffusion is not None:
            self._check_block_diffusion(t_loc, mask)
        offset = _seq_offset(t_loc)
        if self.use_rope:
            with jax.named_scope("mha/rope"):
                pos = (offset + jnp.arange(t_loc))[None]
                if self.block_diffusion is not None:
                    pos = pos % (t_loc // 2)   # both halves count 0..L-1
                rows = pos if self.rope_sections is None else \
                    jnp.broadcast_to(pos, (len(self.rope_sections),)
                                     + pos.shape)
                q = rope(q, rows, self.rope_base, self.rope_sections)
                k = rope(k, rows, self.rope_base, self.rope_sections)
        if self.indexer is not None:
            out, state = self._sparse(params, state, x, q, k, v, mask)
            return self._project_out(params, out, mask), state
        drop = self.attention_dropout if train else 0.0
        # fused-kernel eligibility, shared by the context-parallel and
        # single-device dispatches, decided from what the code can see:
        # the platform (the Pallas interpreter off-TPU would be far
        # slower than XLA) and dropout (the kernel has no dropout RNG).
        # "blockwise" is the algorithm; on TPU the fused flash kernel IS
        # its fastest realization, so both impls ride it when eligible.
        # On a TPU an eligible layer runs the kernel or the call raises.
        use_flash = (self.attention_impl in ("flash", "blockwise")
                     and drop == 0.0
                     and is_tpu_backend())
        with jax.named_scope("mha/attn"):
            out = self._attend(q, k, v, mask, drop, attn_rng, use_flash)
        return self._project_out(params, out, mask), state

    def _project_out(self, params, out, mask):
        with jax.named_scope("mha/proj"):
            y = _merge_heads(out) @ params["Wo"]
            if self.has_bias:
                y = y + params["bo"]
        if mask is not None:
            y = y * mask[..., None].astype(y.dtype)
        return y

    def _sparse(self, params, state, x, q, k, v, mask):
        """``apply``'s attention with an indexer: its projections on the
        detached input, the sparse attention with the indexer's loss
        attached to its result, the counts into the state."""
        from deeplearning4j_tpu.ops.dsa_attention import (
            pairs_causal, sparse_attention,
        )
        if mask is not None or _CONTEXT_PARALLEL_AXIS is not None:
            raise NotImplementedError(
                "sparse attention takes whole unpadded sequences on one "
                "device: no key mask and no sequence parallelism yet")
        b, t = x.shape[:2]
        with jax.named_scope("dsa/index/proj"):
            qi, ki, wi = self.indexer.project(
                params["indexer"], jax.lax.stop_gradient(x),
                jnp.arange(t)[None])
        out, kl, kept = sparse_attention(
            q, k, v, qi, ki, wi, topk=self.indexer.topk,
            block_k=self.block_size)
        with jax.named_scope("dsa/kl"):
            loss = jnp.mean(kl)
            out = attach_auxiliary_loss(out, loss, self.indexer.loss_coef)
        with jax.named_scope("dsa/select"):
            state = {
                "pairs_selected_total": _add_u64(
                    state["pairs_selected_total"],
                    jnp.sum(kept, axis=1, dtype=jnp.uint32)),
                "pairs_causal_total": _add_u64(
                    state["pairs_causal_total"],
                    jnp.full((b,), pairs_causal(t), jnp.uint32)),
                "indexer_kl": jax.lax.stop_gradient(loss)}
        return out, state

    def _attend(self, q, k, v, mask, drop, attn_rng, use_flash):
        """(B, T, H, D) weighted values from q and the k, v of
        ``n_kv_heads`` heads, by the path the layer's fields and the
        platform name."""
        group = self.n_heads // self._kv_heads()
        if group > 1 and not (use_flash and _CONTEXT_PARALLEL_AXIS is None):
            # only the fused kernel reads a key head for its group
            k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        if self.block_diffusion is not None:
            if use_flash:
                from deeplearning4j_tpu.ops import flash_attention
                return flash_attention(
                    q, k, v, block_diffusion=self.block_diffusion,
                    block_q=self.block_size, block_k=self.block_size)
            return block_diffusion_attention(
                q, k, v, block=self.block_diffusion,
                block_size=None if self.attention_impl == "dense"
                else self.block_size)
        if _CONTEXT_PARALLEL_AXIS is not None:
            if use_flash:
                from deeplearning4j_tpu.parallel.ring import (
                    ring_flash_self_attention,
                )
                out = ring_flash_self_attention(
                    q, k, v, axis_name=_CONTEXT_PARALLEL_AXIS,
                    causal=self.causal, mask=mask,
                    block_q=self.block_size, block_k=self.block_size)
            else:
                from deeplearning4j_tpu.parallel.ring import (
                    ring_self_attention,
                )
                out = ring_self_attention(q, k, v,
                                          axis_name=_CONTEXT_PARALLEL_AXIS,
                                          causal=self.causal, mask=mask,
                                          dropout=drop, rng=attn_rng)
        elif use_flash:
            from deeplearning4j_tpu.ops import flash_attention
            out = flash_attention(q, k, v, mask=mask, causal=self.causal,
                                  block_q=self.block_size,
                                  block_k=self.block_size)
        elif self.attention_impl in ("flash", "blockwise"):
            # off-TPU (the Pallas interpreter would be orders of magnitude
            # slower than XLA) or dropout on: blockwise
            # recomputation, clamped + padded to the block size like the
            # flash wrapper pads — a sequence shorter than / not divisible
            # by block_size must work, not raise
            from deeplearning4j_tpu.parallel.ring import blockwise_attention
            t = q.shape[1]
            bs = min(self.block_size, t)
            pad = (-t) % bs
            if pad:
                qp, kp, vp = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                              for a in (q, k, v))
                mp = jnp.ones((q.shape[0], t), q.dtype) if mask is None \
                    else mask
                mp = jnp.pad(mp, ((0, 0), (0, pad)))
                out = blockwise_attention(qp, kp, vp, block_size=bs,
                                          causal=self.causal, mask=mp,
                                          dropout=drop,
                                          rng=attn_rng)[:, :t]
            else:
                out = blockwise_attention(q, k, v, block_size=bs,
                                          causal=self.causal, mask=mask,
                                          dropout=drop, rng=attn_rng)
        else:
            out = dot_product_attention(
                q, k, v, mask=mask, causal=self.causal,
                dropout=drop, rng=attn_rng)
        return out


# --- expert dispatch -------------------------------------------------------
# Rows move between token order and expert order by GATHERS in both
# directions: each of the two moves below is a permutation (or a k-fold
# copy) whose transpose is again a gather through the inverse permutation.
# Autodiff of `x[idx]` would emit a scatter-add instead.

@jax.custom_vjp
def _rows_to_experts(h, order, inverse):
    """(N, F) token rows -> (N*k, F) rows in expert order: sorted row r is
    token ``order[r] // k``."""
    return h[order // (order.shape[0] // h.shape[0])]


def _rows_to_experts_fwd(h, order, inverse):
    return _rows_to_experts(h, order, inverse), (inverse, h.shape[0])


def _rows_to_experts_bwd(res, g):
    inverse, n = res
    acc_t = jnp.promote_types(jnp.float32, g.dtype)
    return g[inverse].reshape(n, -1, g.shape[-1]).astype(acc_t).sum(1) \
        .astype(g.dtype), None, None


_rows_to_experts.defvjp(_rows_to_experts_fwd, _rows_to_experts_bwd)


@jax.custom_vjp
def _rows_to_tokens(y, order, inverse):
    """(N*k, F) rows in expert order -> the same rows in (token, slot)
    order."""
    return y[inverse]


def _rows_to_tokens_fwd(y, order, inverse):
    return y[inverse], order


def _rows_to_tokens_bwd(order, g):
    return g[order], None, None


_rows_to_tokens.defvjp(_rows_to_tokens_fwd, _rows_to_tokens_bwd)


# A dispatch that walks M of its N*k pairs moves rows by gathers too.
# ``pairs`` (M,) names the (token, slot) pair of each row and ``row_of``
# (N*k,) the row of each pair, M for a pair that has none: that one reads
# zeros. A sum over the rows of a token is k gathers a token from those M
# rows; a scatter-add of the M rows took 1.2 to 2.2 times as long on the
# v5e (PERF.md section 6, PR 30).

def _rows_by_pair(y, row_of, k):
    """(M, F) rows -> (N, k, F): every (token, slot) pair's row, zeros for
    a pair that has none."""
    return y.at[row_of].get(mode="fill", fill_value=0) \
        .reshape(-1, k, y.shape[-1])


@jax.custom_vjp
def _rows_of_pairs(h, pairs, row_of):
    """(N, F) token rows -> (M, F): row r is the token of pair
    ``pairs[r]``."""
    return h[pairs // (row_of.shape[0] // h.shape[0])]


def _rows_of_pairs_fwd(h, pairs, row_of):
    return _rows_of_pairs(h, pairs, row_of), (row_of, h.shape[0])


def _rows_of_pairs_bwd(res, g):
    row_of, n = res
    acc_t = jnp.promote_types(jnp.float32, g.dtype)
    return _rows_by_pair(g, row_of, row_of.shape[0] // n).astype(acc_t) \
        .sum(1).astype(g.dtype), None, None


_rows_of_pairs.defvjp(_rows_of_pairs_fwd, _rows_of_pairs_bwd)


@jax.custom_vjp
def _weighed_to_tokens(y, w, pairs, row_of):
    """(M, F) rows and the (N, k) weights of all pairs -> (N, F): every
    token's sum of its pairs' rows under their weights, in ``w``'s type."""
    return jnp.einsum("nkf,nk->nf", _rows_by_pair(y, row_of, w.shape[1]), w,
                      preferred_element_type=w.dtype)


def _weighed_to_tokens_fwd(y, w, pairs, row_of):
    return _weighed_to_tokens(y, w, pairs, row_of), (y, w, pairs)


def _weighed_to_tokens_bwd(res, g):
    y, w, pairs = res
    of_token = g[pairs // w.shape[1]]                       # (M, F)
    of_pair = w.reshape(-1).at[pairs].get(unique_indices=True)
    dw = jnp.zeros((w.size,), w.dtype).at[pairs].set(
        jnp.sum(of_token * y.astype(g.dtype), axis=-1),
        unique_indices=True)
    return (of_token * of_pair[:, None]).astype(y.dtype), \
        dw.reshape(w.shape), None, None


_weighed_to_tokens.defvjp(_weighed_to_tokens_fwd, _weighed_to_tokens_bwd)


#: the rows of one dispatch's FULL tier (tokens x top_k, each an input, a
#: hidden and an output row) may take this much; more tokens go in blocks.
#: It sizes the full tier although most dispatches walk a smaller one: a
#: block whose every pair is held here must still fit
_DISPATCH_LIVE_BYTES = 512 << 20

# The row tiers of a dispatch that holds a share of its experts are the
# layer's own (`MoEFeedForward._tier_divisors`): a small tier sized from
# the share held, twice the balanced load, and every doubling of it up to
# the whole. A layer that holds 8 of 64 would overflow a 1/16 on every
# dispatch, so its small tier is 1/4 (PR 31); a dispatch that tips over a
# tier walks twice its rows, not the whole's: where the loads are skewed a
# handful of a step's dispatches tip over, another handful every step
# (PERF.md section 6, PR 40).

#: the parts of its tokens in which a switch's last tier walks them: in
#: one part that tier sized the LM step (4.34 GB of temporaries where the
#: small tier alone needs 3.70), in two 4.06 (PERF.md section 6, PR 30)
_WHOLE_TIER_PARTS = 2


def _grouped_matmul(x, w, sizes):
    """Rows of ``x`` (M, K), sorted by group, times their group's matrix
    of ``w`` (G, K, N): row r of group g gives ``x[r] @ w[g]``. Only the
    first ``sum(sizes)`` rows are products; the rest is undefined (zeros
    on the CPU, whatever the buffer held on the TPU), here and in the
    transposed products of the backward pass, and the caller masks it.
    On the v5e ``jax.lax.ragged_dot`` walks ALL M static rows, tile by
    tile, each with the one or two matrices it needs: the cost follows
    the static row count (not M x G), whatever share of the rows is in a
    group, which is why a dispatch hands it the rows of its tier and not
    its worst case (measured: PERF.md section 5)."""
    return jax.lax.ragged_dot(x, w, sizes.astype(jnp.int32))


def _expert_products(weights, xs, sizes, of_row, live, activation, product):
    """The held experts applied to the rows ``xs`` (M, F), sorted by
    expert in groups of ``sizes``, with ``product`` as the grouped matrix
    product (`_grouped_matmul`); ``live`` zeroes the rows behind the last
    group wherever they enter or leave a grouped product, ``of_row`` is
    each row's expert (layers with biases only)."""
    from deeplearning4j_tpu.nn.activations import get_activation
    act = get_activation(activation)
    if "Wgate" in weights:
        mid = act(live(product(xs, weights["Wgate"], sizes))) \
            * live(product(xs, weights["Wup"], sizes))
        return live(product(live(mid), weights["Wdown"], sizes))
    mid = product(xs, weights["W1"], sizes)
    if of_row is not None:
        mid = mid + weights["b1"][of_row]
    ys = product(live(act(live(mid))), weights["W2"], sizes)
    if of_row is not None:
        ys = ys + weights["b2"][of_row]
    return live(ys)


def _expert_of_rows(weights, local, e):
    """Each row's expert among the ``e`` held, from its pair's ``local``
    expert (a row behind the last group names the last expert and is
    masked): for the biases of plain experts, None where there are none."""
    if "Wgate" in weights or "b1" not in weights:
        return None
    return jnp.minimum(local, e - 1)


def _counts(keys, buckets):
    """How many of ``keys`` (P,) have each of ``buckets`` values, as
    ``jnp.bincount(keys, length=buckets)`` gives them, without its
    scatter-add, whose updates the chip walks one after another (0.40 ms
    on the v5e for a dispatch's 45,056 pairs, twice a dispatch, where
    their stable sort takes 0.055 and this 0.011 for 9 buckets, 0.008
    for 512: PERF.md section 6, PR 45): a key is two digits, and the
    counts are the product over the keys of the digits' one-hots (0/1 in
    bfloat16, sums in float32: exact below 2**24 keys)."""
    low = min(buckets, 32)
    high = -(-buckets // low)
    hot = lambda digit, n: (digit[None, :] == jnp.arange(
        n, dtype=keys.dtype)[:, None]).astype(jnp.bfloat16)
    return jnp.einsum("hp,lp->hl", hot(keys // low, high),
                      hot(keys % low, low),
                      preferred_element_type=jnp.float32) \
        .astype(jnp.int32).reshape(-1)[:buckets]


def _walk_all(weights, h, w, here, local, order, inverse, sizes, *,
              activation, product):
    """A dispatch behind its sort, on every one of its N*k (token, slot)
    pairs: the last tier, and all a layer that holds every expert runs."""
    n, k = w.shape
    e = sizes.shape[0]
    with jax.named_scope("moe/dispatch"):
        in_group = (jnp.arange(n * k) < sizes.sum())[:, None]
        of_row = _expert_of_rows(weights, local[order], e)
        # the rows behind the last group belong to no expert held
        # here. A grouped product leaves such rows of its result
        # UNDEFINED (the TPU's kernel never writes them), forward and
        # in its transposes, so every tensor of that many rows is
        # selected to zero where it enters and where it leaves a
        # product: no undefined row reaches a sum, in either direction
        live = lambda a: jnp.where(in_group, a, 0).astype(h.dtype)
        xs = live(_rows_to_experts(h, order, inverse))  # (n*k, F)
    with jax.named_scope("moe/experts"):
        ys = _expert_products(weights, xs, sizes, of_row, live, activation,
                              product)
    with jax.named_scope("moe/combine"):
        per_slot = _rows_to_tokens(ys, order, inverse).reshape(n, k, -1)
        wk = jnp.where(here.reshape(n, k), w, 0)
        out = jnp.einsum("nkf,nk->nf", per_slot, wk.astype(w.dtype),
                         preferred_element_type=w.dtype)
    return out.astype(h.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _switch_remat(branches, index, weights, h, w, *ints):
    """``jax.lax.switch`` over ``branches(weights, h, w, *ints)`` that
    keeps no branch's residuals: the backward pass runs the chosen branch
    again, forward and backward inside ONE branch of a second switch.
    Differentiated as it stands, a switch returns every branch's
    residuals from each branch, as zeros from the ones not taken: a small
    tier would write the full tier's rows (1.1 GB a dispatch at the LM
    cell's sizes) and the step would hold them all."""
    return jax.lax.switch(index, branches, weights, h, w, *ints)


def _switch_remat_fwd(branches, index, weights, h, w, *ints):
    return _switch_remat(branches, index, weights, h, w, *ints), \
        (index, weights, h, w, ints)


def _switch_remat_bwd(branches, res, g):
    index, weights, h, w, ints = res
    back = lambda branch: lambda g, weights, h, w: jax.vjp(
        lambda *a: branch(*a, *ints), weights, h, w)[1](g)
    return (None, *jax.lax.switch(index, [back(b) for b in branches], g,
                                  weights, h, w), *[None] * len(ints))


_switch_remat.defvjp(_switch_remat_fwd, _switch_remat_bwd)


# the tiers are jitted, so that the expert layers of a model share one
# trace of each; the grouped product is an argument of theirs, so that a
# trace never outlives the `_grouped_matmul` it was made with
@functools.partial(jax.jit,
                   static_argnames=("rows", "activation", "product"))
def _walk(weights, h, w, local, order, sizes, *, rows, activation, product):
    """A dispatch behind its sort, on the first ``rows`` rows of the
    expert order, which hold every pair held here (`_dispatch` chose the
    tier so): the gather, the grouped products and the rows the combine
    reads are ``rows`` rows."""
    n, k = w.shape
    e = sizes.shape[0]
    with jax.named_scope("moe/dispatch"):
        pairs = order[:rows]            # the (token, slot) pair of a row
        row_of = jnp.full((n * k,), rows, jnp.int32).at[pairs].set(
            jnp.arange(rows, dtype=jnp.int32), unique_indices=True)
        in_group = (jnp.arange(rows) < sizes.sum())[:, None]
        of_row = _expert_of_rows(weights, local[pairs], e)
        live = lambda a: jnp.where(in_group, a, 0).astype(h.dtype)
        xs = live(_rows_of_pairs(h, pairs, row_of))
    with jax.named_scope("moe/experts"):
        ys = _expert_products(weights, xs, sizes, of_row, live, activation,
                              product)
    with jax.named_scope("moe/combine"):
        # a pair behind the last group has a row of zeros (`live`): its
        # weight reaches no sum and gets no gradient
        out = _weighed_to_tokens(ys, w, pairs, row_of)
    return out.astype(h.dtype)


@functools.partial(jax.jit, static_argnames=("activation", "product"))
def _walk_whole(weights, h, w, local, order, sizes, *, activation, product):
    """The last of a layer's tiers: every pair of the dispatch, in
    `_WHOLE_TIER_PARTS` parts of its tokens, one after the other, each
    sorted again and walked on all of ITS pairs. A switch reserves the
    temporaries of its largest branch whichever it takes, so the tier
    that is there for the worst case may not be what sizes the step."""
    n, k = w.shape
    e = sizes.shape[0]
    parts = next(c for c in range(_WHOLE_TIER_PARTS, 0, -1) if n % c == 0)

    @jax.checkpoint
    def one(part):
        h, w, local = part
        with jax.named_scope("moe/dispatch"):
            order = jnp.argsort(local, stable=True).astype(jnp.int32)
            sizes = _counts(local, e + 1)[:e]
        return _walk(weights, h, w, local, order, sizes, rows=local.size,
                     activation=activation, product=product)

    return jax.lax.map(one, (h.reshape(parts, -1, h.shape[-1]),
                             w.reshape(parts, -1, k),
                             local.reshape(parts, -1))).reshape(h.shape)


def _gated_mlp(x, wgate, wup, wdown, activation):
    """``(act(x Wgate) * (x Wup)) Wdown``."""
    from deeplearning4j_tpu.nn.activations import get_activation
    return (get_activation(activation)(x @ wgate) * (x @ wup)) @ wdown


@register_layer
@dataclasses.dataclass(frozen=True)
class MoEFeedForward(LayerConf):
    """Mixture-of-experts FFN with real top-k dispatch — the
    expert-parallel (EP) building block.

    Every token is routed over ALL ``n_experts`` by the router ``Wr``.
    ``router="softmax"``: the ``top_k`` largest logits kept, a softmax over
    the kept ones. ``router="sigmoid"``: scores ``s = sigmoid(logits)``,
    the ``top_k`` largest of ``s + b`` kept (``b``: a NON-trained
    correction vector kept in the layer's state as ``route_bias``, zero
    at init), weights ``s_i / sum_kept(s) * routed_scale``. The layer
    computes the part of the result that the experts it HOLDS give:
    ``experts_held=(lo, hi)`` names them (None: all), the weights of a
    token's other experts are left as routed, and their part is left out
    — on one chip of an expert-parallel layout that partial sum is what
    goes on (the exchange that would add the other chips' parts is not
    here). Expert weights stack on a leading axis sized by the experts
    held; sharding rule P("model") on that axis = expert parallelism.

    Dispatch has static shapes and drops nothing: the (token, slot) pairs
    are sorted by expert (pairs of experts not held last), the token rows
    of the pairs held here gathered in that order, the experts' matrices
    applied as grouped matrix products over them (`_grouped_matmul`) and
    the rows summed back to their tokens under the routing weights, in
    float32. All of it has a static row count, and its cost follows that
    count, so a layer that holds a share of its experts keeps a ladder of
    row TIERS (`_tier_divisors`: a small tier of twice the balanced
    load, ``2 * held / n_experts`` of the dispatch's N*top_k pairs, 1/16
    where 8 of 256 are held and 1/4 where 8 of 64 are, and every doubling
    of it up to the whole: 1/4, 1/2, 1/1): a dispatch counts the pairs
    held here, which it has on the device, and walks the smallest tier
    that holds them (`jax.lax.switch`), at most twice the rows it needs
    once it is over the small tier. The whole, the worst case of every
    token's every expert held here, is always there to be taken, so no
    routing drops a pair (a switch walks it in `_WHOLE_TIER_PARTS` parts,
    because the step reserves the memory of a switch's largest branch
    whichever runs); a layer that holds ALL its experts has that one tier
    and no switch. Experts are ``act(x W1 + b1) W2 + b2`` or, ``gated``,
    ``(act(x Wgate) * (x Wup)) Wdown`` (ReGLU with ``activation="relu"``,
    SwiGLU with ``"swish"``).
    Where the full tier's rows of all N tokens (an input, a hidden and an
    output row each) would pass `_DISPATCH_LIVE_BYTES`, the tokens are
    dispatched in the fewest equal blocks that stay under it, one block
    after another and each rematerialised in the backward pass.
    ``n_shared > 0`` adds ONE expert of the experts' own kind (gated
    beside gated experts: ``Wgate_s``, ``Wup_s``, ``Wdown_s``; plain and
    bias-free beside plain ones: ``W1_s``, ``W2_s``) that every token
    passes (scope ``moe/shared``), of width ``shared_hidden`` (None:
    ``n_shared * hidden``); a chip that holds a share of the experts holds
    the shared expert whole. ``latent`` (a width) puts the ROUTED experts
    in a latent of the stream: the router and the shared expert read the
    stream, the held experts read ``x Wl_down`` (F -> latent) and work at
    the latent's width, and their weighted sum goes through ``Wl_up``
    (latent -> F) (scope ``moe/latent``: the two projections, whole on
    every chip). What is not built is refused by name at `init`: a shared
    expert beside experts with biases, a latent with biases or with the
    softmax router.

    The layer's state keeps the tokens each of the ``n_experts`` experts
    drew in the last step (``tokens_routed``) and in all steps so far
    (``tokens_routed_total``, uint32: it wraps, so a reader takes
    differences modulo 2**32) and, where the layer holds a share of its
    experts, its dispatches by the tier they walked (``tier_hits``, one
    count a tier), the rows those tiers had (``rows_walked_total``) and
    the tokens with at least one of their ``top_k`` experts held here
    (``tokens_with_held_pair_total``: with no shared expert every other
    token gets exactly zero from the layer), uint32 all;
    ``train.listeners.ExpertLoadListener`` turns them into counters, on
    every fit path of both containers."""
    n_out: int = 0
    n_experts: int = 8
    top_k: int = 2
    mlp_ratio: int = 4
    hidden: Optional[int] = None        # expert width (None: mlp_ratio*n_out)
    activation: str = "gelu"
    gated: bool = False
    has_bias: bool = True
    experts_held: Optional[Tuple[int, int]] = None
    router: str = "softmax"             # | "sigmoid"
    routed_scale: float = 1.0           # sigmoid router only
    n_shared: int = 0                   # one expert every token passes
    shared_hidden: Optional[int] = None  # its width (None: n_shared*hidden)
    latent: Optional[int] = None        # the routed experts' width (None: F)
    weight_init: str = "xavier"

    def output_type(self, input_type: InputType) -> InputType:
        t = input_type.shape[0]
        return InputType(Kind.RNN, (t, self.n_out))

    def _held(self):
        lo, hi = self.experts_held or (0, self.n_experts)
        if not 0 <= lo < hi <= self.n_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the {self.n_experts} experts")
        return int(lo), int(hi)

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        f_in = input_type.features
        if f_in != self.n_out:
            raise ValueError("MoEFeedForward requires input width == n_out")
        if not 0 < self.top_k <= self.n_experts:
            raise ValueError(f"top_k {self.top_k} of {self.n_experts}")
        if self.router not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown router {self.router!r}: 'softmax' "
                             "or 'sigmoid'")
        if self.n_shared and self.has_bias:
            raise ValueError("a shared expert beside experts with biases is "
                             "not built: has_bias=False")
        if self.latent and (self.has_bias or self.router != "sigmoid"):
            raise ValueError("experts in a latent are built bias-free "
                             "behind the sigmoid router only: not with "
                             f"has_bias={self.has_bias}, "
                             f"router={self.router!r}")
        hidden = self.hidden or self.mlp_ratio * self.n_out
        w_init = get_initializer(self.weight_init)
        ks = jax.random.split(key, 3)
        lo, hi = self._held()
        stream, f_in = f_in, self.latent or f_in   # the experts' own width

        def ew(k, shape, fi, fo):
            # one key per expert of the WHOLE layer: a share holds the
            # same matrices the uncut layer has for its experts
            keys = jax.random.split(k, self.n_experts)
            return jnp.stack([w_init(keys[i], shape, fi, fo, dtype)
                              for i in range(lo, hi)])

        p = {"Wr": w_init(ks[0], (stream, self.n_experts), stream,
                          self.n_experts, dtype)}
        if self.gated:
            p["Wgate"] = ew(ks[1], (f_in, hidden), f_in, hidden)
            p["Wup"] = ew(jax.random.fold_in(ks[1], 1), (f_in, hidden), f_in,
                          hidden)
            p["Wdown"] = ew(ks[2], (hidden, f_in), hidden, f_in)
        else:
            p["W1"] = ew(ks[1], (f_in, hidden), f_in, hidden)
            p["W2"] = ew(ks[2], (hidden, f_in), hidden, f_in)
        if self.has_bias:
            p["b1"] = jnp.zeros((hi - lo, hidden), dtype)
            p["b2"] = jnp.zeros((hi - lo, self.n_out), dtype)
        if self.latent:
            kd, ku = jax.random.split(jax.random.fold_in(key, 11))
            p["Wl_down"] = w_init(kd, (stream, f_in), stream, f_in, dtype)
            p["Wl_up"] = w_init(ku, (f_in, stream), f_in, stream, dtype)
        if self.n_shared:
            wide = self.shared_hidden or self.n_shared * hidden
            kg, ku, kd = jax.random.split(jax.random.fold_in(key, 7), 3)
            mat = lambda k, fi, fo: w_init(k, (fi, fo), fi, fo, dtype)
            if self.gated:
                p["Wgate_s"] = mat(kg, stream, wide)
                p["Wup_s"] = mat(ku, stream, wide)
                p["Wdown_s"] = mat(kd, wide, stream)
            else:
                p["W1_s"] = mat(kg, stream, wide)
                p["W2_s"] = mat(kd, wide, stream)
        state = {"tokens_routed": jnp.zeros((self.n_experts,), jnp.int32),
                 "tokens_routed_total": jnp.zeros((self.n_experts,),
                                                  jnp.uint32)}
        tiers = len(self._tier_divisors())
        if tiers > 1:
            state["tier_hits"] = jnp.zeros((tiers,), jnp.uint32)
            state["rows_walked_total"] = jnp.zeros((), jnp.uint32)
        if hi - lo < self.n_experts:
            state["tokens_with_held_pair_total"] = jnp.zeros((), jnp.uint32)
        if self.router == "sigmoid":
            state["route_bias"] = jnp.zeros((self.n_experts,), jnp.float32)
        return p, state

    # ------------------------------------------------------------ routing
    def route(self, params, state, x):
        """(..., F) -> the ``top_k`` experts of every token and their
        weights, ``(N, k)`` each, in float32 (float64 under gradient
        checking)."""
        with jax.named_scope("moe/route"):
            acc_t = jnp.promote_types(jnp.float32, x.dtype)
            r = jnp.dot(x.reshape(-1, x.shape[-1]), params["Wr"],
                        preferred_element_type=acc_t)
            if self.router == "softmax":
                top, idx = jax.lax.top_k(r, self.top_k)
                # a softmax over all experts renormalised over the kept
                # ones is a softmax over the kept logits
                return idx.astype(jnp.int32), jax.nn.softmax(top, axis=-1)
            s = jax.nn.sigmoid(r)
            _, idx = jax.lax.top_k(
                jax.lax.stop_gradient(s) + state["route_bias"].astype(acc_t),
                self.top_k)
            kept = jnp.take_along_axis(s, idx, axis=-1)
            w = kept / jnp.sum(kept, axis=-1, keepdims=True)
            return idx.astype(jnp.int32), w * self.routed_scale

    # ------------------------------------------------------------ experts
    def experts(self, params, h, idx, w):
        """The held experts' part of the layer's result for the rows of
        ``h`` (..., F) under the routing ``(idx, w)``; also the tokens
        each of the ``n_experts`` experts drew."""
        shape = h.shape
        h = h.reshape(-1, shape[-1])
        n = h.shape[0]
        blk = n // self._dispatch_blocks(params, h)
        if blk == n:
            out, counts = self._dispatch(params, h, idx, w)
            return out.reshape(shape), counts
        weights = {k: v for k, v in params.items()
                   if k != "Wr" and not k.endswith("_s")
                   and not k.startswith("Wl_")}
        # a dispatch over tiers rematerialises the tier it walked itself
        one = self._dispatch if len(self._tiers(blk * self.top_k)) > 1 \
            else jax.checkpoint(self._dispatch)
        with jax.named_scope("moe/blocks"):
            out, counts = jax.lax.map(
                lambda a: one(weights, *a),
                (h.reshape(-1, blk, shape[-1]),
                 idx.reshape(-1, blk, self.top_k),
                 w.reshape(-1, blk, self.top_k)))
        # a block rematerialised under the containers' gradient
        # checkpointing keeps this result: its second forward pass does
        # not dispatch again
        out = checkpoint_name(out.reshape(shape), REMAT_KEEP)
        return out, {name: c.sum(0) for name, c in counts.items()}

    def _dispatch_blocks(self, params, h):
        """Blocks the (N, F) token rows are dispatched in: the fewest that
        divide N and keep the rows of one block's full tier under
        `_DISPATCH_LIVE_BYTES`."""
        n, f = h.shape
        hidden = params["Wdown" if self.gated else "W2"].shape[1]
        row = (2 * f + (2 if self.gated else 1) * hidden) * h.dtype.itemsize
        most = max(_DISPATCH_LIVE_BYTES // (row * self.top_k), 1)
        return next(g for g in range(-(-n // most), n + 1) if n % g == 0)

    def _tier_divisors(self):
        """The layer's row tiers as divisors of a dispatch's pairs,
        smallest tier first, the whole (1) last. The small tier is twice
        the balanced load of the share held: ``n_experts // (2 * held)``
        (16 for 8 of 256, 4 for 8 of 64), and every halving of that
        divisor down to the whole is a tier (4, 2, 1); a layer that holds
        every expert, or so many that twice its load is the whole, has
        the one tier."""
        lo, hi = self._held()
        small = max(self.n_experts // (2 * (hi - lo)), 1)
        return tuple(small >> i for i in range(small.bit_length()))

    def _tiers(self, pairs):
        """The rows a dispatch of ``pairs`` (token, slot) pairs may walk,
        smallest first, the whole last."""
        return tuple(max(pairs // d, 1) for d in self._tier_divisors())

    def tier_names(self):
        """The row tiers as the shares of a dispatch's pairs they are,
        in the order of the state's ``tier_hits``."""
        return tuple(f"1/{d}" for d in self._tier_divisors())

    def _dispatch(self, params, h, idx, w):
        """`experts` for the (N, F) token rows of one dispatch: the result
        and what it counted."""
        lo, hi = self._held()
        e = hi - lo
        tiers = self._tiers(idx.size)
        how = dict(activation=self.activation, product=_grouped_matmul)
        with jax.named_scope("moe/dispatch"):
            flat = idx.reshape(-1)
            here = (flat >= lo) & (flat < hi)
            local = jnp.where(here, flat - lo, e)   # not held: behind all
            order = jnp.argsort(local, stable=True).astype(jnp.int32)
            if len(tiers) == 1:
                inverse = jnp.argsort(order).astype(jnp.int32)
            sizes = _counts(local, e + 1)[:e]
            counts = {"tokens_routed": _counts(flat, self.n_experts)}
            if e < self.n_experts:
                # the tokens this share adds anything to: every other
                # token's row of the result is exactly zero
                counts["tokens_with_held_pair"] = jnp.sum(
                    jnp.any(here.reshape(idx.shape), axis=-1), dtype=jnp.int32)
        if len(tiers) == 1:
            out = _walk_all(params, h, w, here, local, order, inverse, sizes,
                            **how)
            return out, counts
        # the pairs held here come first in the expert order, so the
        # first tier that holds them walks them all: none is dropped
        tier = jnp.sum(sizes.sum() > jnp.asarray(tiers[:-1]))
        out = _switch_remat(
            (*(functools.partial(_walk, rows=m, **how) for m in tiers[:-1]),
             functools.partial(_walk_whole, **how)),
            tier, params, h, w, local, order, sizes)
        return out, {**counts,
                     "tier_hits": (jnp.arange(len(tiers)) == tier)
                     .astype(jnp.int32),
                     "rows_walked": jnp.asarray(tiers, jnp.int32)[tier]}

    def shared(self, params, h):
        """The shared expert's part: every token, no routing."""
        from deeplearning4j_tpu.nn.activations import get_activation
        with jax.named_scope("moe/shared"):
            if self.gated:
                return _gated_mlp(h, params["Wgate_s"], params["Wup_s"],
                                  params["Wdown_s"], self.activation)
            return get_activation(self.activation)(h @ params["W1_s"]) \
                @ params["W2_s"]

    def counted(self, state, counts):
        """``state`` with what a step's dispatches counted added in."""
        new = {**state, "tokens_routed": counts["tokens_routed"],
               "tokens_routed_total": state["tokens_routed_total"]
               + counts["tokens_routed"].astype(jnp.uint32)}
        if "tier_hits" in counts:
            new["tier_hits"] = state["tier_hits"] \
                + counts["tier_hits"].astype(jnp.uint32)
            new["rows_walked_total"] = state["rows_walked_total"] \
                + counts["rows_walked"].astype(jnp.uint32)
        if "tokens_with_held_pair" in counts:
            new["tokens_with_held_pair_total"] = \
                state["tokens_with_held_pair_total"] \
                + counts["tokens_with_held_pair"].astype(jnp.uint32)
        return new

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        routing = self.route(params, state, x)
        if self.latent:
            with jax.named_scope("moe/latent"):
                rows = (x @ params["Wl_down"]).reshape(-1, self.latent)
            out, counts = self.experts(params, rows, *routing)
            with jax.named_scope("moe/latent"):
                out = (out @ params["Wl_up"]).reshape(x.shape)
        else:
            out, counts = self.experts(params, x, *routing)
        shared = self.shared(params, x) if self.n_shared else None
        with jax.named_scope("residual"):
            if shared is not None:
                out = out + shared
            if mask is not None:
                out = out * mask[..., None].astype(out.dtype)
        with jax.named_scope("moe/route"):
            return out, self.counted(state, counts)


@register_layer
@dataclasses.dataclass(frozen=True)
class GatedMLP(LayerConf):
    """Bias-free gated feed-forward ``(act(x Wgate) * (x Wup)) Wdown`` of
    width ``hidden`` (SwiGLU with ``activation="swish"``)."""
    n_out: int = 0
    hidden: int = 0
    activation: str = "swish"
    weight_init: str = "xavier"

    def output_type(self, input_type: InputType) -> InputType:
        return InputType(Kind.RNN, (input_type.shape[0], self.n_out))

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        f_in = input_type.features
        w_init = get_initializer(self.weight_init)
        kg, ku, kd = jax.random.split(key, 3)
        return {"Wgate": w_init(kg, (f_in, self.hidden), f_in, self.hidden,
                                dtype),
                "Wup": w_init(ku, (f_in, self.hidden), f_in, self.hidden,
                              dtype),
                "Wdown": w_init(kd, (self.hidden, self.n_out), self.hidden,
                                self.n_out, dtype)}, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        with jax.named_scope("mlp/gated"):
            return _gated_mlp(x, params["Wgate"], params["Wup"],
                              params["Wdown"], self.activation), state


@register_layer
@dataclasses.dataclass(frozen=True)
class LinearProjection(LayerConf):
    """Bias-free linear map of the feature axis, (..., F) -> (..., n_out),
    at every position of a sequence (what joins two merged streams back
    to the model's width)."""
    n_out: int = 0
    weight_init: str = "xavier"

    def output_type(self, input_type: InputType) -> InputType:
        return InputType(input_type.kind,
                         tuple(input_type.shape[:-1]) + (self.n_out,))

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        f_in = input_type.features
        return {"W": get_initializer(self.weight_init)(
            key, (f_in, self.n_out), f_in, self.n_out, dtype)}, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        with jax.named_scope("proj"):
            return x @ params["W"], state


@register_layer
@dataclasses.dataclass(frozen=True)
class TransformerBlock(LayerConf):
    """Pre-norm transformer block: norm -> MHA -> +res -> norm -> FFN ->
    +res.

    One declarative unit so deep stacks stay compact in configs (the zoo's
    TransformerLM stacks these). The FFN is a dense MLP of hidden width
    mlp_ratio*n_out (``has_bias`` False: no biases) or, with ``ffn`` set,
    that layer (`MoEFeedForward`, `GatedMLP`) on the normed stream. The
    attention is a `MultiHeadAttention` built from the block's own fields
    or, with ``attn`` set, that layer
    (`linear_attention.KimiDeltaAttention`,
    `linear_attention.MultiHeadLatentAttention`). ``norm``: "layer"
    (LayerNorm) or "rms" (RMSNorm)."""
    n_out: int = 0
    n_heads: int = 8
    mlp_ratio: int = 4
    causal: bool = True
    use_rope: bool = True
    activation: str = "gelu"
    attention_dropout: float = 0.0
    residual_dropout: float = 0.0
    weight_init: str = "xavier"
    attention_impl: str = "dense"       # forwarded to MultiHeadAttention
    block_size: int = 512
    norm: str = "layer"
    norm_epsilon: Optional[float] = None
    has_bias: bool = True
    ffn: Optional[LayerConf] = None
    attn: Optional[LayerConf] = None

    def output_type(self, input_type: InputType) -> InputType:
        t = input_type.shape[0]
        return InputType(Kind.RNN, (t, self.n_out))

    def _sub(self):
        attn = self.attn or MultiHeadAttention(
            n_out=self.n_out, n_heads=self.n_heads, causal=self.causal,
            use_rope=self.use_rope, attention_dropout=self.attention_dropout,
            weight_init=self.weight_init, attention_impl=self.attention_impl,
            block_size=self.block_size)
        return _norm_layer(self.norm, self.norm_epsilon), attn

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        f_in = input_type.features
        if f_in != self.n_out:
            raise ValueError(
                f"TransformerBlock requires input width == n_out "
                f"({f_in} != {self.n_out}); project with a DenseLayer first")
        ln, attn = self._sub()
        ks = jax.random.split(key, 4)
        ln_p, _ = ln.init(ks[0], input_type, dtype)
        attn_p, attn_state = attn.init(ks[1], input_type, dtype)
        p = {"ln1": ln_p, "attn": attn_p,
             "ln2": ln.init(ks[0], input_type, dtype)[0]}
        # an attention that counts (a sparse one) keeps its state here
        state = {"attn": attn_state} if attn_state else {}
        if self.ffn is not None:
            p["ffn"], ffn_state = self.ffn.init(ks[2], input_type, dtype)
            return p, ({**state, "ffn": ffn_state} if ffn_state else state)
        hidden = self.mlp_ratio * self.n_out
        w_init = get_initializer(self.weight_init)
        p["W1"] = w_init(ks[2], (self.n_out, hidden), self.n_out, hidden,
                         dtype)
        p["W2"] = w_init(ks[3], (hidden, self.n_out), hidden, self.n_out,
                         dtype)
        if self.has_bias:
            p["b1"] = jnp.zeros((hidden,), dtype)
            p["b2"] = jnp.zeros((self.n_out,), dtype)
        return p, state

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        from deeplearning4j_tpu.nn.activations import get_activation
        ln, attn = self._sub()
        r1 = r2 = None
        if rng is not None:
            rng, r1, r2 = jax.random.split(rng, 3)
        h, _ = ln.apply(params["ln1"], {}, x)
        a, attn_state = attn.apply(params["attn"], state.get("attn", {}), h,
                                   train=train, rng=r1, mask=mask)
        if attn_state:
            state = {**state, "attn": attn_state}
        with jax.named_scope("residual"):
            if train and self.residual_dropout > 0 and r2 is not None:
                keep = 1.0 - self.residual_dropout
                a = a * jax.random.bernoulli(r2, keep, a.shape) / keep
            x = x + a
        h, _ = ln.apply(params["ln2"], {}, x)
        if self.ffn is not None:
            h, ffn_state = self.ffn.apply(params["ffn"],
                                          state.get("ffn", {}), h)
            if ffn_state:
                state = {**state, "ffn": ffn_state}
        else:
            with jax.named_scope("dense"):
                h = h @ params["W1"]
                if self.has_bias:
                    h = h + params["b1"]
                h = get_activation(self.activation)(h) @ params["W2"]
                if self.has_bias:
                    h = h + params["b2"]
        with jax.named_scope("residual"):
            y = x + h
            if mask is not None:
                y = y * mask[..., None].astype(y.dtype)
        return y, state



@register_layer
@dataclasses.dataclass(frozen=True)
class MixerBlock(LayerConf):
    """A block that is ONE mixer behind a pre-norm: ``y = x +
    mixer(norm(x))`` (the Nemotron-H family's block, whose pattern string
    names each block's mixer: a `linear_attention.Mamba2Mixer`, a
    `MultiHeadAttention` or a `MoEFeedForward`; `TransformerBlock` is an
    attention AND a feed-forward). ``norm``: "layer" or "rms". The
    mixer's state (an expert layer's counters) is the block's own."""
    n_out: int = 0
    mixer: Optional[LayerConf] = None
    norm: str = "rms"
    norm_epsilon: Optional[float] = None

    def output_type(self, input_type: InputType) -> InputType:
        return InputType(Kind.RNN, (input_type.shape[0], self.n_out))

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        if self.mixer is None or input_type.features != self.n_out:
            raise ValueError(
                f"MixerBlock needs a mixer and input width == n_out "
                f"({input_type.features} != {self.n_out})")
        k_norm, k_mixer = jax.random.split(key)
        ln, _ = _norm_layer(self.norm, self.norm_epsilon).init(
            k_norm, input_type, dtype)
        mixer, state = self.mixer.init(k_mixer, input_type, dtype)
        return {"ln": ln, "mixer": mixer}, state

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        h, _ = _norm_layer(self.norm, self.norm_epsilon).apply(
            params["ln"], {}, x)
        h, state = self.mixer.apply(params["mixer"], state, h, train=train,
                                    rng=rng, mask=mask)
        with jax.named_scope("residual"):
            y = x + h
            if mask is not None:
                y = y * mask[..., None].astype(y.dtype)
        return y, state


@register_layer
@dataclasses.dataclass(frozen=True)
class PositionalEmbeddingLayer(LayerConf):
    """Learned absolute position embeddings added to (B, T, F)."""
    max_length: int = 2048

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        f = input_type.features
        return {"P": jax.random.normal(key, (self.max_length, f), dtype)
                * 0.02}, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        with jax.named_scope("embed"):
            t = x.shape[1]
            start = _seq_offset(t)
            if isinstance(start, int) and start == 0:
                if t > self.max_length:
                    raise ValueError(
                        f"sequence length {t} exceeds max_length "
                        f"{self.max_length}")
                pos = params["P"][:t]
            else:    # context-parallel shard: take this shard's slice
                # the global length is static (shard count x local length);
                # reject overflow at trace time — dynamic_slice would silently
                # clamp late shards onto the tail rows
                global_t = t * jax.lax.psum(1, _CONTEXT_PARALLEL_AXIS)
                if int(global_t) > self.max_length:
                    raise ValueError(
                        f"global sequence length {int(global_t)} exceeds "
                        f"max_length {self.max_length}")
                pos = jax.lax.dynamic_slice_in_dim(params["P"], start, t)
            return x + pos[None], state


@register_layer
@dataclasses.dataclass(frozen=True)
class EmbeddingSequenceLayer(LayerConf):
    """Token-id sequence -> embedding sequence: (B, T) or (B, T, 1) int ids
    to (B, T, n_out). The sequence analog of EmbeddingLayer (DL4J gained
    EmbeddingSequenceLayer later than the reference vintage; needed here as
    the transformer LM front end)."""
    n_out: int = 0
    n_in: Optional[int] = None      # vocabulary size (required)
    weight_init: str = "normal"

    def output_type(self, input_type: InputType) -> InputType:
        t = input_type.shape[0]
        return InputType(Kind.RNN, (t, self.n_out))

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        if not self.n_in:
            raise ValueError("EmbeddingSequenceLayer requires n_in "
                             "(vocabulary size)")
        table = jax.random.normal(key, (self.n_in, self.n_out),
                                  dtype) * 0.02
        return {"W": table}, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        with jax.named_scope("embed"):
            if x.ndim == 3:
                x = x[..., 0]
            idx = x.astype(jnp.int32)
            y = jnp.take(params["W"], idx, axis=0)
            if mask is not None:
                y = y * mask[..., None].astype(y.dtype)
            return y, state
