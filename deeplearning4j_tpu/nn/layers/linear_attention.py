"""Linear and latent attention for hybrid LMs (Kimi-Linear's two kinds)
and the gated short convolution of the LFM2 family.

`KimiDeltaAttention` (KDA) is a gated delta rule with a per-channel decay.
Per head, with state ``S`` (d_k x d_v)::

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

`kda_chunked` computes it in chunks of ``chunk`` positions (the WY form).
With ``g_t`` the running sum of ``log a`` inside a chunk and ``S`` the state
the chunk receives::

    A[t,i]   = b_t sum_c k_tc k_ic exp(g_tc - g_ic)        (i <  t)
    Aqk[t,i] =     sum_c q_tc k_ic exp(g_tc - g_ic)        (i <= t)
    (I + A) [W | U0] = [b k exp(g) | b v]          one triangular solve
    U = U0 - W S;  O = (q exp(g)) S + Aqk U
    S' = Diag(exp(g_C)) S + (k exp(g_C - g))^T U          the hand-over

Everything but the last two lines is independent of ``S``. Both halves
are `ops/kda_chunk.py` (two tile functions written once): on a TPU at
widths of 128 one Pallas kernel forward and one backward that walk a
pair's chunks in order with ``S`` (float32) in VMEM, so that neither the
five intermediates nor the chunks' states' hand-over touch HBM; elsewhere
the same functions under XLA, the first a map over the (pair, chunk)
tiles, the second a `lax.scan` over the chunks, with autodiff. No
exponent is ever positive, whatever the decay. The (sequence, head) pairs
go through in groups, each rematerialised, so that only one group's
temporaries are alive.

`MultiHeadLatentAttention` (MLA) is DeepSeek's latent attention in the
expanded form used for training: q of ``nope + rope`` a head, projected
directly or through a normed low-rank latent (``q_rank``), a
``kv_rank + rope`` latent whose first part is normed and expanded to k and
v a head, the last ``rope`` dims shared by all heads as the rest of k.
Those dims are carried as they are (``rotate=False``: Kimi-Linear's
``mla_use_nope``, 128 + 64 / 128) or rotated by the token's position
(``rotate=True``: the GLM / DeepSeek families, 192 + 64 / 256).

`Mamba2Mixer` is the Nemotron-H family's state-space layer (Mamba-2): a
scalar decay a head over a state of ``head_dim x state_dim``, the heads of
a group sharing their ``B`` and ``C``, in chunks (`ops/ssd_chunk.py`: on a
TPU two kernels that hand the state over in VMEM, elsewhere the same chunk
function under a `lax.scan`), behind depth-wise causal taps with a bias and
in front of a gated group norm.

`GatedShortConv` is LFM2's operator, three layers to one of grouped-query
attention there: a depth-wise causal convolution of a few taps
(`causal_conv`, the helper KDA's q, k and v branches run on) between two
element-wise gates, with no state beyond the last ``conv_kernel - 1`` rows.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from deeplearning4j_tpu.nn.conf.base import (
    InputType, Kind, LayerConf, register_layer,
)
from deeplearning4j_tpu.nn.initializers import get_initializer
from deeplearning4j_tpu.nn.layers.attention import (
    dot_product_attention, rope,
)
from deeplearning4j_tpu.ops import REMAT_KEEP
from deeplearning4j_tpu.ops.kda_chunk import chunk_scan
from deeplearning4j_tpu.ops.ssd_chunk import ssd_chunked
from deeplearning4j_tpu.util.platform import is_tpu_backend


def _rms(x, gamma, eps):
    acc_t = jnp.promote_types(jnp.float32, x.dtype)
    xf = x.astype(acc_t)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y * gamma.astype(acc_t)


def causal_conv(x, taps):
    """Depth-wise causal convolution over time: x (B, T, C), taps (K, C),
    ``y_t = sum_j taps[j] x_{t-(K-1)+j}`` (positions before 0 are zero)."""
    k = taps.shape[0]
    t = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, j:j + t] * taps[j] for j in range(k))


# ------------------------------------------------------------- KDA core
def _kda_core(q, k, v, log_a, beta, s0, *, mm):
    """`kda_chunked` for M (sequence, head) pairs: q, k, log_a
    (M, N, C, d_k), v (M, N, C, d_v), beta (M, N, C, 1), all float32, in N
    chunks of C positions; s0 (M, d_k, d_v). Returns (o (M, N, C, d_v),
    the final state)."""
    g = jnp.cumsum(log_a, axis=2)                        # (M,N,C,dk)
    return chunk_scan(q, k, v, g, beta, s0, mm=mm)


#: the temporaries of the (sequence, head) pairs that go through the
#: recurrence together may take this much; more pairs go in groups (on the
#: v5e 8 groups of 8 pairs of 8,192 positions are 2 % faster a step than 4
#: of 16, and reserve 0.35 GB less)
_SCAN_LIVE_BYTES = 1 << 30


def _in_groups(fn, arrays, t, d):
    """``fn`` over equal slices of the arrays' leading axis (the
    independent (sequence, head) pairs), one slice after another and each
    rematerialised in the backward pass: only one group's temporaries
    (some twenty tensors of T x d_k floats a pair: the v5e compile of one
    group's backward pass holds 13 to 16 beside its arguments and results
    of 2 each) are alive at a time. The groups are the fewest that divide
    the pairs and keep those temporaries under `_SCAN_LIVE_BYTES`."""
    m = arrays[0].shape[0]
    most = max(_SCAN_LIVE_BYTES // (20 * t * d * 4), 1)
    groups = next(g for g in range(-(-m // most), m + 1) if m % g == 0)
    split = lambda x: x.reshape((groups, m // groups) + x.shape[1:])
    out = jax.lax.map(lambda xs: jax.checkpoint(fn)(*xs),
                      tuple(split(x) for x in arrays))
    return tuple(x.reshape((m,) + x.shape[2:]) for x in out)


def _chunked(x, chunk):
    """(M, T, ...) -> (M, N, C, ...), the tail padded with zeros."""
    pad = (-x.shape[1]) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    return x.reshape((x.shape[0], -1, chunk) + x.shape[2:])


def _pairs(x):
    """(B, T, H, ...) -> (B*H, T, ...): one row a (sequence, head) pair."""
    x = jnp.moveaxis(x, 2, 1)
    return x.reshape((-1,) + x.shape[2:])


def kda_chunked(q, k, v, log_a, beta, *, chunk=64, initial_state=None,
                mm_dtype=None):
    """The gated delta rule of the module docstring, in chunks.

    q, k: (B, T, H, d_k) (normalised and scaled by the caller), v:
    (B, T, H, d_v), log_a: (B, T, H, d_k) <= 0, beta: (B, T, H). Returns
    ``(o (B, T, H, d_v), final state (B, H, d_k, d_v))``, both float32.
    ``T`` need not divide by ``chunk`` (a power of two): the tail is
    padded with positions that leave the state as it is. ``mm_dtype``: the
    dtype the matrix products take their operands in (None: float32);
    sums, decays, the solve and the state stay float32. The B x H
    (sequence, head) pairs are taken in groups (`_in_groups`)."""
    b, t, h, dk = k.shape
    dv = v.shape[-1]
    f32 = jnp.promote_types(jnp.float32, q.dtype)
    prep = lambda x: _chunked(_pairs(x.astype(f32)), chunk)
    s0 = jnp.zeros((b * h, dk, dv), f32) if initial_state is None \
        else initial_state.astype(f32).reshape(b * h, dk, dv)
    o, s = _in_groups(
        functools.partial(_kda_core, mm=mm_dtype or f32),
        (prep(q), prep(k), prep(v), prep(log_a), prep(beta[..., None]), s0),
        t, dk)
    o = jnp.moveaxis(o.reshape(b, h, -1, dv), 1, 2)[:, :t]
    return o, s.reshape(b, h, dk, dv)


@register_layer
@dataclasses.dataclass(frozen=True)
class KimiDeltaAttention(LayerConf):
    """Kimi Delta Attention over (B, T, F), causal by construction.

    ``q, k, v = SiLU(conv(W x))`` (causal depth-wise convolution of
    ``conv_kernel`` taps), q and k L2-normalised per head, q scaled by
    ``head_dim^-1/2``; per-channel decay ``log a = -exp(A_log) *
    softplus(Wa_up Wa_down x + dt_bias)``; ``b = sigmoid(Wb x)`` per head;
    the recurrence of the module docstring (`kda_chunked`, the
    (sequence, head) pairs in groups: `_in_groups`); output
    ``Wo (RMSNorm_head(o) * sigmoid(Wg_up Wg_down x))``. ``low_rank`` is
    the rank of the decay and gate projections (0: ``head_dim``)."""
    n_out: int = 0
    n_heads: int = 8
    head_dim: int = 128
    conv_kernel: int = 4
    low_rank: int = 0
    chunk: int = 64
    norm_epsilon: float = 1e-5
    weight_init: str = "xavier"

    def output_type(self, input_type: InputType) -> InputType:
        return InputType(Kind.RNN, (input_type.shape[0], self.n_out))

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        f, hd = input_type.features, self.n_heads * self.head_dim
        r = self.low_rank or self.head_dim
        w_init = get_initializer(self.weight_init)
        ks = jax.random.split(key, 14)
        mat = lambda i, fi, fo: w_init(ks[i], (fi, fo), fi, fo, dtype)
        # the family's convention (fla's KimiDeltaAttention): a depth-wise
        # Conv1d's default taps U(-K^-1/2, K^-1/2), A uniform in [1, 16],
        # dt log-uniform in [1e-3, 1e-1] with dt_bias its inverse softplus
        bound = self.conv_kernel ** -0.5
        taps = lambda i: jax.random.uniform(
            ks[i], (self.conv_kernel, hd), dtype, -bound, bound)
        dt = jnp.exp(jax.random.uniform(ks[12], (hd,), jnp.float32)
                     * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
        return {
            "Wq": mat(0, f, hd), "Wk": mat(1, f, hd), "Wv": mat(2, f, hd),
            "conv_q": taps(3), "conv_k": taps(4), "conv_v": taps(5),
            "Wa_down": mat(6, f, r), "Wa_up": mat(7, r, hd),
            "A_log": jnp.log(jax.random.uniform(
                ks[13], (self.n_heads,), jnp.float32, 1.0, 16.0)
            ).astype(dtype),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "Wb": mat(8, f, self.n_heads),
            "Wg_down": mat(9, f, r), "Wg_up": mat(10, r, hd),
            "o_norm": jnp.ones((self.head_dim,), dtype),
            "Wo": mat(11, hd, self.n_out),
        }, {}

    def _recurrence(self, q, k, v, z, b, conv_q, conv_k, conv_v, a_log,
                    dt_bias):
        """From the raw projections of M (sequence, head) pairs, (M, T, d)
        each in the compute dtype (b: (M, T)), and the pairs' own taps
        (M, K, d), A_log (M,) and dt_bias (M, d), to o (M, T, d) in the
        compute dtype: convolutions, SiLU, normalisation, decay and the
        chunked recurrence, all in float32 inside."""
        f32 = jnp.promote_types(jnp.float32, q.dtype)
        t, d = q.shape[1], q.shape[2]
        branch = lambda a, taps: jax.nn.silu(jax.vmap(causal_conv)(
            a.astype(f32)[:, None], taps.astype(f32))[:, 0])
        unit = lambda a: a * jax.lax.rsqrt(
            jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
        log_a = -jnp.exp(a_log.astype(f32))[:, None, None] * jax.nn.softplus(
            z.astype(f32) + dt_bias.astype(f32)[:, None, :])
        beta = jax.nn.sigmoid(b.astype(f32))[..., None]
        chunks = lambda a: _chunked(a, self.chunk)
        o, _ = _kda_core(
            chunks(unit(branch(q, conv_q)) * d ** -0.5),
            chunks(unit(branch(k, conv_k))), chunks(branch(v, conv_v)),
            chunks(log_a), chunks(beta),
            jnp.zeros((q.shape[0], d, d), f32), mm=q.dtype)
        return (o.reshape(o.shape[0], -1, d)[:, :t].astype(q.dtype),)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        if mask is not None:
            raise NotImplementedError(
                "KimiDeltaAttention takes whole sequences (no mask)")
        b, t, _ = x.shape
        h, d = self.n_heads, self.head_dim
        f32 = jnp.promote_types(jnp.float32, x.dtype)
        heads = lambda a: a.reshape(b, t, h, d)
        with jax.named_scope("kda/proj"):
            raw = [_pairs(heads(x @ params[w])) for w in ("Wq", "Wk", "Wv")]
            raw.append(_pairs(heads(
                (x @ params["Wa_down"]) @ params["Wa_up"])))
            raw.append(_pairs((x @ params["Wb"])[..., None])[..., 0])
            # each pair's own taps, A_log and dt_bias (its head's)
            own = lambda a: jnp.tile(a, (b,) + (1,) * (a.ndim - 1))
            for taps in ("conv_q", "conv_k", "conv_v"):
                raw.append(own(jnp.moveaxis(
                    params[taps].reshape(-1, h, d), 1, 0)))
            raw.append(own(params["A_log"]))
            raw.append(own(params["dt_bias"].reshape(h, d)))
            gate = jax.nn.sigmoid(
                ((x @ params["Wg_down"]) @ params["Wg_up"]).astype(f32))
        with jax.named_scope("kda/scan"):
            (o,) = _in_groups(self._recurrence, raw, t, d)
            o = jnp.moveaxis(o.reshape(b, h, t, d), 1, 2)
            # a block rematerialised under the containers' gradient
            # checkpointing keeps the recurrence's result, so that its
            # second forward pass does not run the recurrence again
            o = checkpoint_name(o, REMAT_KEEP)
        with jax.named_scope("kda/out"):
            o = _rms(o, params["o_norm"], self.norm_epsilon) * heads(gate)
            y = o.reshape(b, t, h * d).astype(x.dtype) @ params["Wo"]
        return y, state


@register_layer
@dataclasses.dataclass(frozen=True)
class GatedShortConv(LayerConf):
    """Gated short convolution over (B, T, F), causal by construction
    (LFM2's operator): ``[B; C; u] = x W_in`` (F -> 3 * n_out, no bias),
    ``z = B * u``, ``c = causal_conv(z, taps)`` (depth-wise,
    ``conv_kernel`` taps a channel, no bias, zeros before position 0; the
    LAST tap meets the current position, as a Conv1d's weight lies),
    ``y = (C * c) W_out``. No activation. The gates and the taps run in
    float32 whatever the compute dtype (the three reads and the one
    write are what they cost). Scopes: ``sconv/proj`` (the two
    projections), ``sconv/mix`` (gates and taps)."""
    n_out: int = 0
    conv_kernel: int = 3
    weight_init: str = "xavier"

    def output_type(self, input_type: InputType) -> InputType:
        return InputType(Kind.RNN, (input_type.shape[0], self.n_out))

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        f, n = input_type.features, self.n_out
        w_init = get_initializer(self.weight_init)
        k_in, k_taps, k_out = jax.random.split(key, 3)
        bound = self.conv_kernel ** -0.5       # a depth-wise Conv1d's default
        return {
            "Win": w_init(k_in, (f, 3 * n), f, 3 * n, dtype),
            "conv": jax.random.uniform(k_taps, (self.conv_kernel, n), dtype,
                                       -bound, bound),
            "Wout": w_init(k_out, (n, n), n, n, dtype),
        }, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        if mask is not None:
            raise NotImplementedError(
                "GatedShortConv takes whole sequences (no mask)")
        n = self.n_out
        f32 = jnp.promote_types(jnp.float32, x.dtype)
        with jax.named_scope("sconv/proj"):
            bcu = x @ params["Win"]
        with jax.named_scope("sconv/mix"):
            b, c, u = (bcu[..., i * n:(i + 1) * n].astype(f32)
                       for i in range(3))
            mixed = (c * causal_conv(b * u, params["conv"].astype(f32))
                     ).astype(x.dtype)
        with jax.named_scope("sconv/proj"):
            return mixed @ params["Wout"], state


@register_layer
@dataclasses.dataclass(frozen=True)
class Mamba2Mixer(LayerConf):
    """Mamba-2 over (B, T, F), causal by construction (the Nemotron-H
    family's ``M`` mixer). With ``d_inner = n_heads * head_dim`` and
    ``n_groups`` groups of state ``state_dim``: ``[z ; u ; dt] = x W_in``
    (z of d_inner, u = [x' ; B ; C] of d_inner + 2 * n_groups * state_dim,
    dt of n_heads; no bias); ``u <- silu(causal_conv(u, taps) + conv_b)``
    (depth-wise, ``conv_kernel`` taps, zeros before position 0, the LAST
    tap meets the current position); ``D_t = softplus(dt_t + dt_bias)``,
    ``a_t = exp(D_t A)`` with ``A = -exp(A_log)`` one scalar a head; head
    h of group ``h // (n_heads / n_groups)`` keeps a state of ``head_dim x
    state_dim``: ``S_t = a_t S_{t-1} + D_t x'_t B_t^T``, ``y_t = S_t C_t +
    D_h x'_t`` (`ops.ssd_chunk.ssd_chunked`, chunks of ``chunk``); ``o =
    RMSNorm_group(y * silu(z)) * gamma``, the mean of squares over each
    GROUP's ``d_inner / n_groups`` channels (the gate before the norm);
    ``o W_out``. The taps, the step size, the decay and the norm run in
    float32 whatever the compute dtype; the products (the two projections
    and the chunk algebra's four) take their operands in it. A
    tensor-parallel slice of the layer IS a smaller layer (fewer heads and
    groups): there is no option for a share held. Scopes: ``ssd/proj``,
    ``ssd/conv``, ``ssd/scan``, ``ssd/out``."""
    n_out: int = 0
    n_heads: int = 8
    head_dim: int = 64
    n_groups: int = 1
    state_dim: int = 128
    conv_kernel: int = 4
    chunk: int = 128
    norm_epsilon: float = 1e-5
    dt_min: float = 1e-3
    dt_max: float = 0.1
    dt_floor: float = 1e-4
    weight_init: str = "xavier"

    def output_type(self, input_type: InputType) -> InputType:
        return InputType(Kind.RNN, (input_type.shape[0], self.n_out))

    def _widths(self):
        """(d_inner, the convolved width d_inner + 2 * groups * state)."""
        inner = self.n_heads * self.head_dim
        return inner, inner + 2 * self.n_groups * self.state_dim

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        if self.n_heads % self.n_groups:
            raise ValueError(f"n_groups {self.n_groups} does not divide "
                             f"n_heads {self.n_heads}")
        f, h = input_type.features, self.n_heads
        inner, conv = self._widths()
        w_init = get_initializer(self.weight_init)
        k_in, k_taps, k_dt, k_a, k_out = jax.random.split(key, 5)
        # the family's convention: a depth-wise Conv1d's default taps
        # U(-K^-1/2, K^-1/2) and a zero bias, dt log-uniform in [dt_min,
        # dt_max] floored at dt_floor with dt_bias its inverse softplus, A
        # uniform in [1, 16], D = 1
        bound = self.conv_kernel ** -0.5
        dt = jnp.maximum(jnp.exp(
            jax.random.uniform(k_dt, (h,), jnp.float32)
            * (jnp.log(self.dt_max) - jnp.log(self.dt_min))
            + jnp.log(self.dt_min)), self.dt_floor)
        return {
            "Win": w_init(k_in, (f, inner + conv + h), f, inner + conv + h,
                          dtype),
            "conv": jax.random.uniform(k_taps, (self.conv_kernel, conv),
                                       dtype, -bound, bound),
            "conv_b": jnp.zeros((conv,), dtype),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "A_log": jnp.log(jax.random.uniform(
                k_a, (h,), jnp.float32, 1.0, 16.0)).astype(dtype),
            "D": jnp.ones((h,), dtype),
            "norm": jnp.ones((inner,), dtype),
            "Wout": w_init(k_out, (inner, self.n_out), inner, self.n_out,
                           dtype),
        }, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        if mask is not None:
            raise NotImplementedError(
                "Mamba2Mixer takes whole sequences (no mask)")
        b, t, _ = x.shape
        h, p, g, n = (self.n_heads, self.head_dim, self.n_groups,
                      self.state_dim)
        inner, conv = self._widths()
        f32 = jnp.promote_types(jnp.float32, x.dtype)
        with jax.named_scope("ssd/proj"):
            zud = x @ params["Win"]
        with jax.named_scope("ssd/conv"):
            u = jax.nn.silu(
                causal_conv(zud[..., inner:inner + conv].astype(f32),
                            params["conv"].astype(f32))
                + params["conv_b"].astype(f32))
        with jax.named_scope("ssd/scan"):
            step = jax.nn.softplus(zud[..., inner + conv:].astype(f32)
                                   + params["dt_bias"].astype(f32))
            xs = u[..., :inner].reshape(b, t, h, p)
            bc = u[..., inner:].reshape(b, t, 2, g, n)
            y, _ = ssd_chunked(
                xs, step, -jnp.exp(params["A_log"].astype(f32)), bc[:, :, 0],
                bc[:, :, 1], chunk=self.chunk, mm_dtype=x.dtype)
            y = y + params["D"].astype(f32)[:, None] * xs
        with jax.named_scope("ssd/out"):
            gated = (y.reshape(b, t, inner)
                     * jax.nn.silu(zud[..., :inner].astype(f32)))
            of_group = gated.reshape(b, t, g, inner // g)
            o = (of_group * jax.lax.rsqrt(
                jnp.mean(of_group * of_group, axis=-1, keepdims=True)
                + self.norm_epsilon)).reshape(b, t, inner) \
                * params["norm"].astype(f32)
            return o.astype(x.dtype) @ params["Wout"], state


def rope_pairs(x, positions, theta, freqs=None, amplitude=1.0):
    """Rotary positions on the last axis of x (B, T, H, D), all D dims,
    the pairs (2j, 2j+1) rotated by ``positions[t] * theta^(-2j/D)`` (the
    interleaved layout of the GLM / DeepSeek latent attentions). The
    result holds the rotated pairs' first members in its first half and
    the second members in its second: one fixed permutation of the dims,
    the same in q and in k, which no score ``q . k`` can see, and which
    saves putting the pairs back side by side. So it is `rope` (which
    pairs dim j with dim j + D/2) on the de-interleaved dims. ``freqs``
    (D/2 of them, `yarn_frequencies`) turns pair j by ``positions[t] *
    freqs[j]`` instead, cos and sin times ``amplitude``."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    if freqs is None:
        return rope(x, positions, theta)
    half = x.shape[-1] // 2
    acc_t = jnp.promote_types(jnp.float32, x.dtype)
    angles = positions[:, None].astype(acc_t) * jnp.asarray(freqs, acc_t)
    cos = (amplitude * jnp.cos(angles))[None, :, None, :]
    sin = (amplitude * jnp.sin(angles))[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def yarn_frequencies(dim, theta, *, factor, original_max_position,
                     beta_fast=32.0, beta_slow=1.0, mscale=1.0,
                     mscale_all_dim=0.0):
    """YaRN's rotation as the DeepSeek family's code has it: ``(the dim / 2
    frequencies, the factor on cos and sin, the factor on the softmax
    scale)``. With ``f_j = theta^(-2j/dim)`` and ``where(b) = dim ln(
    original_max_position / (2 pi b)) / (2 ln theta)`` (the pair that turns
    ``b`` times over the original length), ``low = floor(where(beta_fast))``
    and ``high = ceil(where(beta_slow))`` (within 0 .. dim - 1), pair j
    turns by ``f_j / factor * ramp_j + f_j (1 - ramp_j)``, ``ramp_j =
    clip((j - low) / (high - low), 0, 1)``: the fast pairs as they were,
    the slow ones stretched by ``factor``. ``m(s) = 0.1 s ln factor + 1``
    (1 where ``factor <= 1`` or ``s`` is 0); cos and sin are times
    ``m(mscale) / m(mscale_all_dim)`` and the softmax scale times
    ``m(mscale_all_dim)^2``."""
    import math

    import numpy as np
    plain = float(theta) ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    where = lambda turns: dim * math.log(
        original_max_position / (turns * 2 * math.pi)) \
        / (2 * math.log(float(theta)))
    low = max(math.floor(where(beta_fast)), 0)
    high = min(math.ceil(where(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 0.001), 0.0, 1.0)
    m = lambda s: 0.1 * s * math.log(factor) + 1.0 if factor > 1 else 1.0
    freqs = plain / factor * ramp + plain * (1.0 - ramp)
    return (tuple(float(f) for f in freqs), m(mscale) / m(mscale_all_dim),
            m(mscale_all_dim) ** 2)


@register_layer
@dataclasses.dataclass(frozen=True)
class MultiHeadLatentAttention(LayerConf):
    """Latent attention (MLA) over (B, T, F), expanded form, causal:
    ``q = Wq x`` or, with ``q_rank``, ``q = Wqb RMSNorm(Wqa x)``, split
    ``nope_dim + rope_dim`` a head; ``[c; k_r] = Wkva x`` (``kv_rank +
    rope_dim``), ``c <- RMSNorm(c)``, ``[k_nope; v] = Wkvb c`` (``nope_dim
    + v_dim`` a head), ``k = [k_nope; k_r]`` with ``k_r`` shared by the
    heads; ``softmax(q k^T / sqrt(nope_dim + rope_dim)) v``; ``Wo``.
    ``rotate`` False carries the ``rope_dim`` part as it is (no positions
    at all); True rotates it, in q and in the shared ``k_r``, by the
    token's position over ALL ``rope_dim`` dims, the pairs (2j, 2j+1) as
    the family lays them out (`rope_pairs`). Which frequencies and which
    temperature: with ``rope_scaling`` None, pair j turns by ``position *
    rope_theta^(-2j/rope_dim)`` and the softmax scale is ``(nope_dim +
    rope_dim)^-1/2``; with ``rope_scaling`` "yarn" (the only type there
    is: any other name is refused) the frequencies are YaRN's blend of
    those and of those over ``rope_factor`` (`yarn_frequencies`, from
    ``rope_original_max_position``, ``rope_beta_fast`` / ``_slow``), cos
    and sin are times ``m(rope_mscale) / m(rope_mscale_all_dim)`` and the
    softmax scale times ``m(rope_mscale_all_dim)^2``, ``m(s) = 0.1 s ln
    rope_factor + 1`` (q carries that factor into the kernels, which
    scale by the head width alone). On a TPU the fused flash kernel runs
    the attention at the layer's head sizes (192/128, 256/256, ...)."""
    n_out: int = 0
    n_heads: int = 8
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    kv_rank: int = 512
    q_rank: Optional[int] = None
    rotate: bool = False
    rope_theta: float = 10000.0
    norm_epsilon: float = 1e-5
    block_size: int = 512
    weight_init: str = "xavier"
    rope_scaling: Optional[str] = None
    rope_factor: float = 1.0
    rope_original_max_position: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0

    def output_type(self, input_type: InputType) -> InputType:
        return InputType(Kind.RNN, (input_type.shape[0], self.n_out))

    def _rotation(self):
        """(frequencies or None for the plain ones, the factor on cos and
        sin, the factor on the softmax scale)."""
        if self.rope_scaling is None:
            return None, 1.0, 1.0
        if self.rope_scaling != "yarn" or not self.rotate:
            raise ValueError(
                f"rope_scaling {self.rope_scaling!r}: the one type there is "
                "is \"yarn\", on a layer that rotates (rotate=True)")
        return yarn_frequencies(
            self.rope_dim, self.rope_theta, factor=self.rope_factor,
            original_max_position=self.rope_original_max_position,
            beta_fast=self.rope_beta_fast, beta_slow=self.rope_beta_slow,
            mscale=self.rope_mscale,
            mscale_all_dim=self.rope_mscale_all_dim)

    def init(self, key, input_type: InputType, dtype=jnp.float32):
        self._rotation()                 # an unknown type is refused here
        f, h = input_type.features, self.n_heads
        w_init = get_initializer(self.weight_init)
        ks = list(jax.random.split(key, 4)) + [jax.random.fold_in(key, 4)]
        mat = lambda i, fi, fo: w_init(ks[i], (fi, fo), fi, fo, dtype)
        qk = h * (self.nope_dim + self.rope_dim)
        query = {"Wq": mat(0, f, qk)} if self.q_rank is None else {
            "Wqa": mat(0, f, self.q_rank),
            "q_norm": jnp.ones((self.q_rank,), dtype),
            "Wqb": mat(4, self.q_rank, qk)}
        return {
            **query,
            "Wkva": mat(1, f, self.kv_rank + self.rope_dim),
            "kv_norm": jnp.ones((self.kv_rank,), dtype),
            "Wkvb": mat(2, self.kv_rank, h * (self.nope_dim + self.v_dim)),
            "Wo": mat(3, h * self.v_dim, self.n_out),
        }, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        b, t, _ = x.shape
        h = self.n_heads
        with jax.named_scope("mla/proj"):
            if self.q_rank is None:
                q = x @ params["Wq"]
            else:
                q = _rms(x @ params["Wqa"], params["q_norm"],
                         self.norm_epsilon).astype(x.dtype) @ params["Wqb"]
            q = q.reshape(b, t, h, -1)
            ckr = x @ params["Wkva"]
            c = _rms(ckr[..., :self.kv_rank], params["kv_norm"],
                     self.norm_epsilon).astype(x.dtype)
            kv = (c @ params["Wkvb"]).reshape(b, t, h, -1)
            k_r = ckr[:, :, None, self.kv_rank:]
        if self.rotate:
            freqs, amplitude, temperature = self._rotation()
            with jax.named_scope("mla/rope"):
                at = jnp.arange(t)
                q = jnp.concatenate(
                    [q[..., :self.nope_dim],
                     rope_pairs(q[..., self.nope_dim:], at, self.rope_theta,
                                freqs, amplitude)], axis=-1)
                if temperature != 1.0:
                    # the kernels scale the scores by the head width alone
                    q = (q * temperature).astype(q.dtype)
                k_r = rope_pairs(k_r, at, self.rope_theta, freqs, amplitude)
        with jax.named_scope("mla/proj"):
            k_r = jnp.broadcast_to(k_r, (b, t, h, self.rope_dim))
            k = jnp.concatenate([kv[..., :self.nope_dim], k_r], axis=-1)
            v = kv[..., self.nope_dim:]
        with jax.named_scope("mla/attn"):
            if is_tpu_backend():
                from deeplearning4j_tpu.ops import flash_attention
                out = flash_attention(q, k, v, mask=mask, causal=True,
                                      block_q=self.block_size,
                                      block_k=self.block_size)
            else:
                out = dot_product_attention(q, k, v, mask=mask, causal=True)
        with jax.named_scope("mla/out"):
            y = out.reshape(b, t, h * self.v_dim) @ params["Wo"]
            if mask is not None:
                y = y * mask[..., None].astype(y.dtype)
        return y, state
