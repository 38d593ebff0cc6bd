"""Plain reference for ``redpajama-incite-3b``: the GPT-NeoX decoder
(sequential residual, LayerNorm, rotary embedding over the whole head in
the half-split form, GELU MLP, untied output head) as one full forward
pass in float32 ``jax.numpy`` at ``highest`` matmul precision. No cache, no
pages, no batching, no kernels; it imports nothing of the program.

The weights are made here from the benchmark's seed, layer by layer, and
are the SAME VALUES the system serves (``make_weights`` is the one
generator; the system's adapter only renames the leaves). The forward pass
makes each layer's weights when it reaches the layer, so a 2.8 B-parameter
model is followed in float32 with a few hundred megabytes.

Departures from the published model, as the configuration's file lists
them: no attention biases, tanh-form GELU (``gelu_approximate``), a bias on
the output head.

``precision="fp8"`` is the control, not a reference: the same mathematics
with the operands of every matmul, attention's included, rounded to float8
(e4m3, one scale per tensor), the step below the 16-bit type served.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
SERVE = jnp.bfloat16


def dims(cfg):
    return (cfg["hidden_size"], cfg["num_hidden_layers"],
            cfg["num_attention_heads"], cfg["intermediate_size"],
            cfg["vocab_size"])


# ------------------------------------------------------------------- costs
def matmul_params(cfg) -> int:
    """Weights every decoded token multiplies by: the blocks' six matrices
    and the output head."""
    h, n, _, f, v = dims(cfg)
    return n * (4 * h * h + 2 * h * f) + h * v


def kv_bytes_per_token(cfg) -> int:
    h, n, _, _, _ = dims(cfg)
    return 2 * n * h * 2


def decode_step_min_seconds(cfg, peaks, live_slots, live_tokens) -> dict:
    """The least time one decode step can take: every weight read once in
    bf16 plus the keys and values of the live tokens, over the memory
    bandwidth; or its FLOPs (2 per weight per live sequence, 4 per cached
    token per layer and unit of width for attention) over the peak;
    whichever is larger."""
    h, n, _, _, _ = dims(cfg)
    byts = 2 * matmul_params(cfg) + kv_bytes_per_token(cfg) * live_tokens
    flops = 2.0 * matmul_params(cfg) * live_slots + 4.0 * n * h * live_tokens
    tb, tf = byts / peaks["hbm_bytes_per_s"], flops / peaks["flops_bf16"]
    return {"least_s": max(tb, tf), "bytes_s": tb, "flops_s": tf}


# ----------------------------------------------------------------- weights
def _u(key, shape, a):
    return jax.random.uniform(key, shape, jnp.float32, -a, a).astype(SERVE)


def _xavier(key, fan_in, fan_out):
    return _u(key, (fan_in, fan_out), (6.0 / (fan_in + fan_out)) ** 0.5)


def _ln(key, h):
    k1, k2 = jax.random.split(key)
    return {"gamma": (1.0 + jax.random.uniform(k1, (h,), jnp.float32, -0.1,
                                               0.1)).astype(SERVE),
            "beta": _u(k2, (h,), 0.02)}


def _layer_weights(h, f, key):
    ks = jax.random.split(key, 10)
    return {"ln1": _ln(ks[0], h), "Wq": _xavier(ks[1], h, h),
            "Wk": _xavier(ks[2], h, h), "Wv": _xavier(ks[3], h, h),
            "Wo": _xavier(ks[4], h, h), "ln2": _ln(ks[5], h),
            "W1": _xavier(ks[6], h, f), "b1": _u(ks[7], (f,), 0.02),
            "W2": _xavier(ks[8], f, h), "b2": _u(ks[9], (h,), 0.02)}


def _ends_weights(h, v, key):
    ks = jax.random.split(key, 4)
    return {"embed": _u(ks[0], (v, h), (6.0 / (v + h)) ** 0.5),
            "final_ln": _ln(ks[1], h),
            "head": {"W": _xavier(ks[2], h, v), "b": _u(ks[3], (v,), 0.02)}}


@functools.partial(jax.jit, static_argnums=(0,))
def _make_weights(d, key):
    h, n, _, f, v = d
    out = _ends_weights(h, v, jax.random.fold_in(key, 1 << 20))
    out["layers"] = [_layer_weights(h, f, jax.random.fold_in(key, i))
                     for i in range(n)]
    return out


def make_weights(cfg, seed: int):
    """All the weights in the served type, on the device, in one jitted
    call: ``embed``, ``layers`` (a list), ``final_ln``, ``head``."""
    return _make_weights(dims(cfg), jax.random.PRNGKey(seed))


@functools.partial(jax.jit, static_argnums=(0,))
def _one_layer(d, key, i):
    return _layer_weights(d[0], d[3], jax.random.fold_in(key, i))


@functools.partial(jax.jit, static_argnums=(0,))
def _ends(d, key):
    return _ends_weights(d[0], d[4], jax.random.fold_in(key, 1 << 20))


# ----------------------------------------------------------------- forward
def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(x.dtype) * s


def _mm(spec, a, b, precision):
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * p["gamma"] + p["beta"]


def _rope(x, base):
    """x: (T, heads, d). Rotary embedding over the whole head, pairing
    unit i with unit i + d/2 (GPT-NeoX's rotate_half)."""
    t, _, d = x.shape
    half = d // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _block(heads, eps, base, approx, precision, x, w):
    w = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w)
    t, h = x.shape
    a = _layer_norm(x, w["ln1"], eps)
    q = _mm("th,hk->tk", a, w["Wq"], precision).reshape(t, heads, -1)
    k = _mm("th,hk->tk", a, w["Wk"], precision).reshape(t, heads, -1)
    v = _mm("th,hk->tk", a, w["Wv"], precision).reshape(t, heads, -1)
    q, k = _rope(q, base), _rope(k, base)
    s = _mm("qnd,knd->nqk", q, k, precision) / jnp.sqrt(
        jnp.float32(h // heads))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("nqk,knd->qnd", p, v, precision).reshape(t, h)
    x = x + _mm("th,hk->tk", o, w["Wo"], precision)
    a = _layer_norm(x, w["ln2"], eps)
    a = jax.nn.gelu(_mm("th,hf->tf", a, w["W1"], precision) + w["b1"],
                    approximate=approx)
    return x + _mm("tf,fh->th", a, w["W2"], precision) + w["b2"]


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(eps, precision, x, w):
    w = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w)
    a = _layer_norm(x, w["final_ln"], eps)
    return _mm("th,hv->tv", a, w["head"]["W"], precision) + w["head"]["b"]


def forward_logits(cfg, seed: int, tokens, first: int, count: int,
                   precision="highest", pad_to: int = 512):
    """One full forward pass over ``tokens`` (1-D ids). Returns the
    float32 logits (count, vocab) of positions ``first .. first+count-1``.
    The sequence is padded to a multiple of ``pad_to`` so that a handful of
    programs serve every length; causal attention keeps the padding out of
    every position before it."""
    d = dims(cfg)
    key = jax.random.PRNGKey(seed)
    n = len(tokens)
    t = -(-n // pad_to) * pad_to
    ids = np.zeros((t,), np.int32)
    ids[:n] = tokens
    ends = _ends(d, key)
    x = jnp.take(ends["embed"], jnp.asarray(ids), axis=0).astype(jnp.float32)
    for i in range(d[1]):
        x = _block(d[2], cfg["layer_norm_eps"], float(cfg["rotary_emb_base"]),
                   bool(cfg["gelu_approximate"]), precision, x,
                   _one_layer(d, key, i))
    # rows padded to a multiple of 128 so that few head programs exist
    idx = np.minimum(np.arange(first, first + -(-count // 128) * 128), t - 1)
    rows = jnp.take(x, jnp.asarray(idx), axis=0)
    out = _head(cfg["layer_norm_eps"], precision, rows,
                {"final_ln": ends["final_ln"], "head": ends["head"]})
    return np.asarray(out[:count])
