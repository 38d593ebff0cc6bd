"""Ring attention — sequence/context parallelism over the mesh "seq" axis.

No DL4J analog (SURVEY.md §5.7: the reference's only long-sequence tool is
truncated BPTT); this is new TPU-native capability, following the blockwise/
ring-attention recipe (Liu et al.; see PAPERS.md): each device holds a
sequence shard of Q/K/V, K/V blocks rotate around the ring via `ppermute`
while each device accumulates its queries' attention with an online
(streaming) softmax. Peak memory per device is O(T/S) in sequence length,
and the K/V transfer for step s+1 overlaps the compute of step s (XLA
schedules the ppermute DMA concurrently with the einsums — the classic
compute/communication overlap on ICI).

Causality across shards falls out of global position offsets: device i's
queries start at i*T_loc, the block received at ring step s originated on
device (i - s) mod S, so its keys start at ((i - s) mod S)*T_loc.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import SEQ_AXIS


def _online_block(q, k, v, o, m, l, *, causal, q_start, k_start, scale,
                  mask_block=None, dropout=0.0, rng=None):
    """One blockwise online-softmax update.

    q: (B, Tq, H, D); k/v: (B, Tk, H, D); o: (B, Tq, H, D) running output
    numerator; m: (B, H, Tq) running max; l: (B, H, Tq) running denominator.

    Attention dropout applies to the NUMERATOR only (the denominator l keeps
    every key): out = sum(p*bern/keep @ v)/sum(p) — algebraically identical
    to dropping the normalized weights in dense attention.
    """
    # accumulate in >= f32 (f64 under float64 gradient checking; a hard f32
    # cast would corrupt the finite-difference oracle)
    acc_t = jnp.promote_types(jnp.float32, q.dtype)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=acc_t) * scale
    neg = jnp.asarray(-1e30, acc_t)
    if causal:
        qpos = q_start + jnp.arange(q.shape[1])
        kpos = k_start + jnp.arange(k.shape[1])
        scores = jnp.where((qpos[:, None] >= kpos[None, :])[None, None],
                           scores, neg)
    if mask_block is not None:
        scores = jnp.where(mask_block[:, None, None, :].astype(bool),
                           scores, neg)
    m_new = jnp.maximum(m, jnp.max(scores, axis=-1))          # (B,H,Tq)
    # guard fully-masked rows: exp(neg - neg) would be 1 and poison l
    alive = m_new > neg / 2
    corr = jnp.where(alive, jnp.exp(m - m_new), 0.0)
    p = jnp.where(alive[..., None], jnp.exp(scores - m_new[..., None]), 0.0)
    l_new = l * corr + jnp.sum(p, axis=-1)
    p_num = p
    if dropout > 0.0 and rng is not None:
        keep = 1.0 - dropout
        p_num = p * jax.random.bernoulli(rng, keep, p.shape) / keep
    o_new = (o * corr.transpose(0, 2, 1)[..., None] +
             jnp.einsum("bhqk,bkhd->bqhd", p_num.astype(v.dtype), v))
    return o_new, m_new, l_new


def ring_self_attention(q, k, v, *, axis_name: str = SEQ_AXIS,
                        causal: bool = True, mask=None,
                        dropout: float = 0.0, rng=None):
    """Sequence-sharded attention, called INSIDE shard_map over `axis_name`.

    q/k/v: the local shard (B, T_local, H, D); mask: local (B, T_local) key
    mask or None. Returns the local output shard (B, T_local, H, D)."""
    size = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, t_loc, h, d = q.shape
    acc_t = jnp.promote_types(jnp.float32, q.dtype)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, acc_t))
    q_start = idx * t_loc

    def rotate(x):
        return jax.lax.ppermute(
            x, axis_name,
            [(j, (j + 1) % size) for j in range(size)])

    o = jnp.zeros((b, t_loc, h, d), acc_t)
    m = jnp.full((b, h, t_loc), -jnp.inf, acc_t)
    l = jnp.zeros((b, h, t_loc), acc_t)

    def body(s, carry):
        o, m, l, k_cur, v_cur, mask_cur = carry
        src = (idx - s) % size
        o, m, l = _online_block(
            q, k_cur, v_cur, o, m, l, causal=causal,
            q_start=q_start, k_start=src * t_loc, scale=scale,
            mask_block=mask_cur, dropout=dropout,
            rng=None if rng is None else jax.random.fold_in(rng, s))
        k_nxt = rotate(k_cur)
        v_nxt = rotate(v_cur)
        mask_nxt = None if mask_cur is None else rotate(mask_cur)
        return o, m, l, k_nxt, v_nxt, mask_nxt

    carry = (o, m, l, k, v, mask)
    # static unroll over ring steps: `size` is a trace-time constant and the
    # per-step masks/offsets differ; XLA pipelines the ppermutes
    for s in range(size):
        carry = body(s, carry)
    o, m, l = carry[0], carry[1], carry[2]
    l_t = l.transpose(0, 2, 1)[..., None]            # (B,Tq,H,1)
    out = o / jnp.maximum(l_t, 1e-30)
    return out.astype(q.dtype)


def make_ring_attention(mesh: Mesh, *, causal: bool = True,
                        axis_name: str = SEQ_AXIS):
    """Wrap ring_self_attention in shard_map for (B, T, H, D) global views:
    T sharded over the seq axis, everything else replicated."""

    spec_qkv = P(None, axis_name, None, None)
    spec_mask = P(None, axis_name)

    def masked(q, k, v, mask):
        return ring_self_attention(q, k, v, axis_name=axis_name,
                                   causal=causal, mask=mask)

    def unmasked(q, k, v):
        return ring_self_attention(q, k, v, axis_name=axis_name,
                                   causal=causal, mask=None)

    f_masked = jax.shard_map(
        masked, mesh=mesh,
        in_specs=(spec_qkv, spec_qkv, spec_qkv, spec_mask),
        out_specs=spec_qkv, check_vma=False)
    f_unmasked = jax.shard_map(
        unmasked, mesh=mesh, in_specs=(spec_qkv, spec_qkv, spec_qkv),
        out_specs=spec_qkv, check_vma=False)
    size = int(mesh.shape[axis_name])

    def attend(q, k, v, mask=None):
        # host-side telemetry at the shard_map boundary: counts calls and
        # the ICI traffic the ring schedules ((size-1) K/V rotations of
        # one shard each, per device). Under an enclosing jit these fire
        # at trace time only — the compiled path stays untouched.
        from deeplearning4j_tpu import monitor
        nbytes = lambda a: 0 if a is None else \
            int(np.prod(np.shape(a))) * np.dtype(a.dtype).itemsize
        monitor.counter("ring_attention_calls_total",
                        "ring attention invocations (trace-time under "
                        "jit)").inc()
        monitor.counter("ring_bytes_rotated_total",
                        "K/V (+mask) bytes scheduled over the ring per "
                        "call (trace-time under jit: counts traced "
                        "builds, not executed steps)").inc(
            (size - 1) * (nbytes(k) + nbytes(v) + nbytes(mask)))
        with monitor.span("parallel/ring_attention", seq_shards=size):
            if mask is None:
                return f_unmasked(q, k, v)
            return f_masked(q, k, v, mask)

    return attend


def blockwise_attention(q, k, v, *, block_size: int = 512,
                        causal: bool = True, mask=None,
                        dropout: float = 0.0, rng=None):
    """Single-device memory-efficient attention: the same online-softmax
    accumulation as the ring, but over local K/V blocks via lax.scan —
    O(T * block) memory instead of O(T^2). The single-chip half of the
    long-context story (ring = cross-chip, blockwise = on-chip)."""
    b, t, h, d = q.shape
    if t % block_size:
        raise ValueError(f"sequence {t} not divisible by block {block_size}")
    n_blocks = t // block_size
    acc_t = jnp.promote_types(jnp.float32, q.dtype)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, acc_t))
    kb = k.reshape(b, n_blocks, block_size, h, d)
    vb = v.reshape(b, n_blocks, block_size, h, d)
    maskb = None if mask is None else mask.reshape(b, n_blocks, block_size)

    o = jnp.zeros((b, t, h, d), acc_t)
    m = jnp.full((b, h, t), -jnp.inf, acc_t)
    l = jnp.zeros((b, h, t), acc_t)

    def body(carry, s):
        o, m, l = carry
        k_cur = kb[:, s]
        v_cur = vb[:, s]
        mask_cur = None if maskb is None else maskb[:, s]
        o, m, l = _online_block(q, k_cur, v_cur, o, m, l, causal=causal,
                                q_start=0, k_start=s * block_size,
                                scale=scale, mask_block=mask_cur,
                                dropout=dropout,
                                rng=None if rng is None
                                else jax.random.fold_in(rng, s))
        return (o, m, l), None

    (o, m, l), _ = jax.lax.scan(body, (o, m, l), jnp.arange(n_blocks))
    out = o / jnp.maximum(l.transpose(0, 2, 1)[..., None], 1e-30)
    return out.astype(q.dtype)


def ring_flash_self_attention(q, k, v, *, axis_name: str = SEQ_AXIS,
                              causal: bool = True, mask=None,
                              block_q: int = 128, block_k: int = 128,
                              interpret=None):
    """Ring attention with the FUSED Pallas flash kernel per shard pair
    (ops/flash_attention.py), composed across ring steps with the exact
    LSE merge rule. Per-pair causality never needs position offsets
    inside the kernel: the diagonal pair (ring step 0) is locally causal,
    earlier shards attend fully, later shards are excluded entirely via
    the merge weights — shard granularity makes those the only cases.
    The LSE output is differentiable, so training through the merge is
    exact (tested against dense attention). No dropout (the kernel has
    no RNG plumbing); callers fall back to ring_self_attention for it."""
    from deeplearning4j_tpu.ops import flash_attention

    size = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, t_loc, h, d = q.shape
    NEG = -1e30

    def rotate(x):
        return jax.lax.ppermute(
            x, axis_name, [(j, (j + 1) % size) for j in range(size)])

    # unnormalized accumulation (one divide at the end, matching the
    # sibling online-softmax loops): num = sum_s o_s * w_s, z = sum_s w_s
    # with w_s = exp(lse_s - m_acc) rescaled as the running max moves
    num = jnp.zeros((b, t_loc, h, d), jnp.float32)
    z = jnp.zeros((b, t_loc, h), jnp.float32)
    m_acc = jnp.full((b, t_loc, h), NEG, jnp.float32)
    k_cur, v_cur, mask_cur = k, v, mask
    for s in range(size):
        src = (idx - s) % size
        o_s, l_s = flash_attention(
            q, k_cur, v_cur, mask=mask_cur,
            causal=(causal and s == 0),     # diagonal pair only
            block_q=block_q, block_k=block_k, return_lse=True,
            interpret=interpret)
        l_s = l_s.astype(jnp.float32)
        if causal and s > 0:
            # ring step s>0 holds shard `src`; it is entirely in the past
            # iff src < idx, else entirely in the future -> excluded
            l_s = jnp.where(src < idx, l_s, NEG)
        m_new = jnp.maximum(m_acc, l_s)
        corr = jnp.exp(m_acc - m_new)
        w_s = jnp.exp(l_s - m_new)
        num = num * corr[..., None] + w_s[..., None] * o_s.astype(
            jnp.float32)
        z = z * corr + w_s
        m_acc = m_new
        if s + 1 < size:
            k_cur = rotate(k_cur)
            v_cur = rotate(v_cur)
            mask_cur = None if mask_cur is None else rotate(mask_cur)
    out = num / jnp.maximum(z, 1e-30)[..., None]
    return out.astype(q.dtype)
