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
  parallelism is handled by ring attention at the network level.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.base import (
    InputType, Kind, LayerConf, register_layer,
)
from deeplearning4j_tpu.nn.initializers import get_initializer
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
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + self.epsilon)
        return y * params["gamma"] + params["beta"], state


def _split_heads(x, n_heads):
    b, t, f = x.shape
    return x.reshape(b, t, n_heads, f // n_heads)


def _merge_heads(x):
    b, t, h, d = x.shape
    return x.reshape(b, t, h * d)


def rope(x, positions, base: float = 10000.0):
    """Rotary position embedding on (B, T, H, D)."""
    d = x.shape[-1]
    half = d // 2
    # trig in >= f32 (f64 under float64 gradient checking — a hard f32 cast
    # here corrupts the finite-difference oracle)
    acc_t = jnp.promote_types(jnp.float32, x.dtype)
    freqs = base ** (-jnp.arange(0, half, dtype=acc_t) / half)
    angles = positions[..., None].astype(acc_t) * freqs   # (B?, T, half)
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
    apply causal masking across sequence shards)."""
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


@register_layer
@dataclasses.dataclass(frozen=True)
class MultiHeadAttention(LayerConf):
    """Multi-head self-attention over (B, T, F).

    n_out: model width (must divide by n_heads). causal: autoregressive
    masking. use_rope: rotary positions (otherwise positions come from an
    embedding layer upstream). Masks follow DL4J semantics: (B, T) 0/1,
    masked steps neither attend nor get attended to, and their outputs are
    zeroed (MaskZeroLayer behavior)."""
    n_out: int = 0
    n_heads: int = 8
    n_in: Optional[int] = None
    causal: bool = False
    use_rope: bool = True
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
        if self.n_out % self.n_heads:
            raise ValueError(f"n_out {self.n_out} not divisible by "
                             f"n_heads {self.n_heads}")
        if self.use_rope and (self.n_out // self.n_heads) % 2:
            raise ValueError(
                f"rotary embeddings need an even head dim; got "
                f"{self.n_out // self.n_heads} (n_out={self.n_out}, "
                f"n_heads={self.n_heads}) — disable use_rope or resize")
        f_in = self.n_in or input_type.features
        w_init = get_initializer(self.weight_init)
        ks = jax.random.split(key, 4)
        p = {
            "Wq": w_init(ks[0], (f_in, self.n_out), f_in, self.n_out, dtype),
            "Wk": w_init(ks[1], (f_in, self.n_out), f_in, self.n_out, dtype),
            "Wv": w_init(ks[2], (f_in, self.n_out), f_in, self.n_out, dtype),
            "Wo": w_init(ks[3], (self.n_out, self.n_out), self.n_out,
                         self.n_out, dtype),
        }
        if self.has_bias:
            for b in ("bq", "bk", "bv", "bo"):
                p[b] = jnp.zeros((self.n_out,), dtype)
        return p, {}

    def _qkv(self, params, x):
        q = x @ params["Wq"]
        k = x @ params["Wk"]
        v = x @ params["Wv"]
        if self.has_bias:
            q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
        h = self.n_heads
        return _split_heads(q, h), _split_heads(k, h), _split_heads(v, h)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        attn_rng = None
        if rng is not None:
            rng, attn_rng = jax.random.split(rng)
        x = self.maybe_dropout_input(x, train, rng)
        q, k, v = self._qkv(params, x)
        t_loc = x.shape[1]
        offset = _seq_offset(t_loc)
        if self.use_rope:
            pos = (offset + jnp.arange(t_loc))[None]
            q = rope(q, pos)
            k = rope(k, pos)
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
        y = _merge_heads(out) @ params["Wo"]
        if self.has_bias:
            y = y + params["bo"]
        if mask is not None:
            y = y * mask[..., None].astype(y.dtype)
        return y, state


@register_layer
@dataclasses.dataclass(frozen=True)
class TransformerBlock(LayerConf):
    """Pre-norm transformer block: LN -> MHA -> +res -> LN -> MLP -> +res.

    One declarative unit so deep stacks stay compact in configs (the zoo's
    TransformerLM stacks these). mlp_ratio*n_out is the hidden width."""
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

    def output_type(self, input_type: InputType) -> InputType:
        t = input_type.shape[0]
        return InputType(Kind.RNN, (t, self.n_out))

    def _sub(self):
        attn = MultiHeadAttention(
            n_out=self.n_out, n_heads=self.n_heads, causal=self.causal,
            use_rope=self.use_rope, attention_dropout=self.attention_dropout,
            weight_init=self.weight_init, attention_impl=self.attention_impl,
            block_size=self.block_size)
        ln = LayerNormLayer()
        return ln, attn

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        f_in = input_type.features
        if f_in != self.n_out:
            raise ValueError(
                f"TransformerBlock requires input width == n_out "
                f"({f_in} != {self.n_out}); project with a DenseLayer first")
        ln, attn = self._sub()
        ks = jax.random.split(key, 4)
        ln_p, _ = ln.init(ks[0], input_type, dtype)
        attn_p, _ = attn.init(ks[1], input_type, dtype)
        hidden = self.mlp_ratio * self.n_out
        w_init = get_initializer(self.weight_init)
        return {
            "ln1": ln_p,
            "attn": attn_p,
            "ln2": {"gamma": jnp.ones((self.n_out,), dtype),
                    "beta": jnp.zeros((self.n_out,), dtype)},
            "W1": w_init(ks[2], (self.n_out, hidden), self.n_out, hidden,
                         dtype),
            "b1": jnp.zeros((hidden,), dtype),
            "W2": w_init(ks[3], (hidden, self.n_out), hidden, self.n_out,
                         dtype),
            "b2": jnp.zeros((self.n_out,), dtype),
        }, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        from deeplearning4j_tpu.nn.activations import get_activation
        ln, attn = self._sub()
        r1 = r2 = None
        if rng is not None:
            rng, r1, r2 = jax.random.split(rng, 3)
        h, _ = ln.apply(params["ln1"], {}, x)
        a, _ = attn.apply(params["attn"], {}, h, train=train, rng=r1,
                          mask=mask)
        if train and self.residual_dropout > 0 and r2 is not None:
            keep = 1.0 - self.residual_dropout
            a = a * jax.random.bernoulli(r2, keep, a.shape) / keep
        x = x + a
        h, _ = ln.apply(params["ln2"], {}, x)
        h = get_activation(self.activation)(h @ params["W1"] + params["b1"])
        h = h @ params["W2"] + params["b2"]
        y = x + h
        if mask is not None:
            y = y * mask[..., None].astype(y.dtype)
        return y, state


@register_layer
@dataclasses.dataclass(frozen=True)
class MoEFeedForward(LayerConf):
    """Mixture-of-experts FFN with top-2 soft routing — the expert-parallel
    (EP) building block. Experts stack on a leading axis sized n_experts;
    sharding rule P("model") on that axis = expert parallelism (each model-
    axis group holds a subset of experts; the einsum dispatch becomes an
    all-to-all under the partitioner).

    Capacity-less dense routing (every token scores every expert, weighted
    by the top-2 normalized gates): simpler than Switch-style dispatch and
    XLA-friendly (no dynamic shapes); fine up to ~16 experts."""
    n_out: int = 0
    n_experts: int = 8
    top_k: int = 2
    mlp_ratio: int = 4
    activation: str = "gelu"
    weight_init: str = "xavier"

    def output_type(self, input_type: InputType) -> InputType:
        t = input_type.shape[0]
        return InputType(Kind.RNN, (t, self.n_out))

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        f_in = input_type.features
        if f_in != self.n_out:
            raise ValueError("MoEFeedForward requires input width == n_out")
        hidden = self.mlp_ratio * self.n_out
        w_init = get_initializer(self.weight_init)
        ks = jax.random.split(key, 3)
        e = self.n_experts

        def ew(k, shape, fi, fo):
            keys = jax.random.split(k, e)
            return jnp.stack([w_init(keys[i], shape, fi, fo, dtype)
                              for i in range(e)])

        return {
            "Wg": w_init(ks[0], (f_in, e), f_in, e, dtype),
            "W1": ew(ks[1], (f_in, hidden), f_in, hidden),
            "b1": jnp.zeros((e, hidden), dtype),
            "W2": ew(ks[2], (hidden, self.n_out), hidden, self.n_out),
            "b2": jnp.zeros((e, self.n_out), dtype),
        }, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        from deeplearning4j_tpu.nn.activations import get_activation
        gates = jax.nn.softmax(x @ params["Wg"], axis=-1)   # (B, T, E)
        if self.top_k < self.n_experts:
            top_vals, _ = jax.lax.top_k(gates, self.top_k)
            thresh = top_vals[..., -1:]
            gates = jnp.where(gates >= thresh, gates, 0.0)
            gates = gates / jnp.maximum(
                jnp.sum(gates, -1, keepdims=True), 1e-9)
        act = get_activation(self.activation)
        h = jnp.einsum("btf,efh->bteh", x, params["W1"]) + params["b1"]
        h = act(h)
        y = jnp.einsum("bteh,eho->bteo", h, params["W2"]) + params["b2"]
        out = jnp.einsum("bteo,bte->bto", y, gates)
        if mask is not None:
            out = out * mask[..., None].astype(out.dtype)
        return out, state


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
        if x.ndim == 3:
            x = x[..., 0]
        idx = x.astype(jnp.int32)
        y = jnp.take(params["W"], idx, axis=0)
        if mask is not None:
            y = y * mask[..., None].astype(y.dtype)
        return y, state
