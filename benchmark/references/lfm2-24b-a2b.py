"""Plain reference for ``lfm2-24b-a2b``: the decoder layers of Liquid AI's
LFM2-24B-A2B (config.json, ``model_type`` ``lfm2_moe``), the loss, its
gradients and the AdamW step in straightforward float32 ``jax.numpy`` at
``highest`` matmul precision: the gated short convolution as a sum over its
taps, grouped-query attention under a dense T x T mask one (sequence, head)
at a time, a Python loop over the experts held, no kernel, no dispatch,
AdamW written out, ONE sequence of a batch at a time (the loss is the mean
over sequences of equal weight, so the gradient is the mean of theirs) and
AdamW's moments on the host between steps, which is what lets it fit the
chip. It imports nothing of the program and takes nothing the program made:
weights come from the configuration's ``weights_seed``, batches from the
benchmark's seed; leaves are named as the zoo model's graph names them.

One block (h: T x 2048): ``h += Op(RMSNorm(h))``; ``h += FFN(RMSNorm(h))``;
RMSNorm ``x / sqrt(mean(x^2) + 1e-5) * gamma``. After the last block a final
RMSNorm, logits ``= h E^T`` with ``E`` the embedding (tied), mean next-token
cross-entropy over the held slice of the vocabulary.

Op of a ``conv`` layer, x = the normed stream:
  [B; C; u] = x W_in (2048 -> 3 x 2048); z = B * u;
  c_t = sum_{j=0..2} w_j * z_{t-j} (depth-wise, zeros before position 0;
  w_j is row K-1-j of the leaf ``conv``, which lies as a Conv1d's weight
  does); Op = (C * c) W_out.
Op of a ``full_attention`` layer (32 query heads on 8 key/value heads, 64
wide):
  q = x W_q (32 x 64); k = x W_k, v = x W_v (8 x 64);
  q <- RMSNorm_64(q), k <- RMSNorm_64(k) (one learned gain of 64 each, every
  head alike) BEFORE the rotation; RoPE(theta 1e6, all 64 dims, dim j turned
  against dim j + 32 by t * theta^(-j/32)); query head h reads key head
  h // 4; causal softmax(q k^T / 8) v; W_o.
FFN of the leading dense layer: W_down(SiLU(x W_gate) * (x W_up)) at 11776.
FFN of the others, x = the normed stream after the operator:
  s = sigmoid(x W_r) over all 64; S = the 4 largest of s + b (b = 0, not
  trained); w_i = s_i / (sum_{j in S} s_j + 1e-6) * 1;
  y = sum_{i in S, i held} w_i W_down,i(SiLU(x W_gate,i) * (x W_up,i))
  and NO shared expert: a token none of whose four experts is held gets 0.

Departures from the published model, each also under ``assumed`` in the
configuration's file: the expert bias is zero and never updated; the chip's
share is the experts ``experts_held`` of the 64 routed over and the first
``vocab_size`` ids; what the other chips' experts would add is left out,
here as in the program.

``precision="fp8"`` is the control, not a reference: the same mathematics
with the operands of every matrix product rounded to float8 (e4m3, one
scale a tensor), the step below the bf16 the configuration states.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
#: positions whose logits the loss holds at once
LOSS_BLOCK = 2048
#: the faults `train_steps` can plant; the cell's limits have to catch each
#: (benchmark/tools/plant_faults.py)
FAULTS = ("half_batch", "kv_head_mod", "taps_reversed", "no_qk_norm",
          "no_rope", "no_renorm")
#: the renormalisation's epsilon in the family's modelling code
RENORM_EPS = 1e-6


# ----------------------------------------------------------------- tokens
@functools.lru_cache(maxsize=None)
def zipf_table(vocab: int, s: float) -> np.ndarray:
    """65,536 token ids: entry u is the id whose Zipf(s) cumulative
    probability over ``vocab`` ids first reaches (u + 0.5) / 65536."""
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** s
    cdf = np.cumsum(p / p.sum())
    u = (np.arange(65536, dtype=np.float64) + 0.5) / 65536.0
    return np.minimum(np.searchsorted(cdf, u), vocab - 1).astype(np.int32)


def seq_length(cfg) -> int:
    return cfg["image_size"] * cfg["image_size"] * cfg["channels"] // 2


def decode_tokens(cfg, rows) -> np.ndarray:
    """The harness's uint8 batch (B, image_size, image_size, channels) ->
    int32 token ids (B, T): each little-endian uint16 of a row through the
    Zipf table. The ONE decode, for the adapter's feed and for
    ``train_steps`` below."""
    rows = np.ascontiguousarray(np.asarray(rows, np.uint8))
    u = rows.reshape(rows.shape[0], -1).view("<u2")
    return zipf_table(int(cfg["vocab_size"]), float(cfg["zipf_s"]))[u]


def targets(ids):
    """(next-token ids, 0/1 weights): position t predicts token t + 1; the
    last position of a sequence has no target."""
    ids = np.asarray(ids)
    keep = np.ones(ids.shape, np.float32)
    keep[:, -1] = 0.0
    return np.roll(ids, -1, axis=1), keep


# ----------------------------------------------------------------- shapes
def layer_kinds(cfg):
    """(operator, ffn) of each layer run: ("conv" | "full_attention",
    "dense" | "experts"). ``layer_types`` is the published list of all 40;
    the layers run are the ``num_hidden_layers`` from ``first_layer`` on,
    of which the first ``num_dense_layers`` are dense."""
    first, n = cfg["first_layer"], cfg["num_hidden_layers"]
    kinds = cfg["layer_types"][first:first + n]
    assert len(kinds) == n and set(kinds) <= {"conv", "full_attention"}
    return [(kind, "dense" if i < cfg["num_dense_layers"] else "experts")
            for i, kind in enumerate(kinds)]


def _held(cfg):
    lo, hi = cfg["experts_held"]
    assert hi - lo == cfg["num_experts"]
    return lo, hi


def _heads(cfg):
    """(query heads, key/value heads, head width)."""
    mh, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return mh, kv, cfg["hidden_size"] // mh


def _block_shapes(cfg, kind: str, ffn: str) -> dict:
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    mh, kv, d = _heads(cfg)
    if kind == "conv":
        op = {"Win": (h, 3 * h), "conv": (cfg["conv_L_cache"], h),
              "Wout": (h, h)}
    else:
        op = {"Wq": (h, mh * d), "Wk": (h, kv * d), "Wv": (h, kv * d),
              "Wo": (mh * d, h), "q_norm": (d,), "k_norm": (d,)}
    if ffn == "dense":
        w = cfg["intermediate_size"]
        mlp = {"Wgate": (h, w), "Wup": (h, w), "Wdown": (w, h)}
    else:
        lo, hi = _held(cfg)
        e = hi - lo
        mlp = {"Wr": (h, cfg["router_experts"]), "Wgate": (e, h, f),
               "Wup": (e, h, f), "Wdown": (e, f, h)}
    return {"attn": op, "ffn": mlp, "ln1": {"gamma": (h,)},
            "ln2": {"gamma": (h,)}}


def param_shapes(cfg) -> dict:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    out = {"embed": {"W": (v, h)}}
    for i, (kind, ffn) in enumerate(layer_kinds(cfg)):
        out[f"layer{i}"] = _block_shapes(cfg, kind, ffn)
    out["norm"] = {"gamma": (h,)}
    return out


def stage_of(cfg, leaf: str) -> str:
    """The stage a parameter leaf (by its path,
    ``['layer2']['attn']['Win']``) belongs to: ``layer0`` .. ``layer4``,
    or ``head`` for the final norm and the ONE matrix that is embedding
    and head (it stands next to the loss, and the head's use gives nearly
    all of its gradient)."""
    top = leaf.split("'")[1]
    return "head" if top in ("norm", "embed") else top


_OUT_PROJECTIONS = ("Wout", "Wo", "Wdown")


def make_params(cfg, seed: int = 0):
    """Seeded float32 weights on the device, ALL from the configuration's
    ``weights_seed`` (``seed``, the run's, draws the token ids only: the
    weights decide which experts a token draws, so how many rows the held
    experts multiply, and a run's seed is not to move the amount of work).
    The tied embedding N(0, embedding_std^2); the taps N(0, conv_std^2);
    the output projections of convolution, attention, MLP and experts
    N(0, out_proj_std^2); every other matrix N(0, matrix_std^2); gains 1."""
    root = jax.random.PRNGKey(int(cfg["weights_seed"]))
    flat, tree = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(flat):
        name = path[-1].key
        if len(shape) == 1:
            out.append(jnp.ones(shape, jnp.float32))
            continue
        std = cfg["embedding_std"] if path[0].key == "embed" else (
            cfg["conv_std"] if name == "conv" else
            cfg["out_proj_std"] if name in _OUT_PROJECTIONS
            else cfg["matrix_std"])
        out.append(_normal(jax.random.fold_in(root, i), shape, float(std)))
    return jax.tree_util.tree_unflatten(tree, out)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _normal(key, shape, std):
    return std * jax.random.normal(key, shape, jnp.float32)


# ------------------------------------------------------------------ counts
def _count(cfg, kind: str) -> int:
    return sum(k == kind for k, _ in layer_kinds(cfg))


def _experts_macs(cfg) -> float:
    """Router and the held experts' EXPECTED rows, a token."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    lo, hi = _held(cfg)
    return float(h * cfg["router_experts"]
                 + 3 * h * f * cfg["num_experts_per_tok"] * (hi - lo)
                 / cfg["router_experts"])


def _per_token_macs(cfg) -> dict:
    """Multiply-adds a token in the matrix products of the layers run, by
    what they belong to; attention's own token-mixing is counted apart."""
    h = cfg["hidden_size"]
    mh, kv, d = _heads(cfg)
    kinds = layer_kinds(cfg)
    return {
        "operator projections": float(
            _count(cfg, "conv") * 4 * h * h
            + _count(cfg, "full_attention") * (2 * h * mh * d
                                               + 2 * h * kv * d)),
        "dense MLP": float(sum(f == "dense" for _, f in kinds)
                           * 3 * h * cfg["intermediate_size"]),
        "held experts": sum(f == "experts" for _, f in kinds)
        * _experts_macs(cfg),
        "head": float(h * cfg["vocab_size"]),
    }


def _causal_pairs(t):
    return t * (t + 1) / 2.0


def flops_shares(cfg) -> dict:
    """Share of `train_flops_per_example` by part, for the cell's ``why``."""
    t = seq_length(cfg)
    mh, _, d = _heads(cfg)
    parts = {k: v * t for k, v in _per_token_macs(cfg).items()}
    parts["attention scores"] = _count(cfg, "full_attention") \
        * _causal_pairs(t) * mh * 2 * d
    total = sum(parts.values())
    return {k: v / total for k, v in parts.items()}


def train_flops_per_example(cfg) -> float:
    """Model FLOPs of one sequence in a training step for the share held
    here: 2 per multiply-add forward and twice that again backward, in
    the operators' projections, attention's scores and weighted values
    inside the causal mask at 32 query heads, the router, the held
    experts' three products for the rows they are EXPECTED to draw, the
    dense MLP and the head. Recomputation, norms, softmax, rotation, the
    convolution's gates and taps (element-wise), the embedding gather and
    the optimizer are left out, as MFU's convention has it."""
    t = seq_length(cfg)
    mh, _, d = _heads(cfg)
    macs = sum(_per_token_macs(cfg).values()) * t
    macs += _count(cfg, "full_attention") * _causal_pairs(t) * mh * 2 * d
    return 2.0 * macs * 3


def experts_min_seconds(cfg, peaks, rows: float) -> dict:
    """The least time the held experts' three products of ONE layer can
    take in a training step, forward and backward (each product once
    forward and twice backward: the input's and the weight's gradient),
    for ``rows`` token rows routed to them: the larger of FLOPs/peak and
    bytes/peak, bf16 operands read once and results written once."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    lo, hi = _held(cfg)
    flops = 2.0 * rows * h * f
    tf = tb = 0.0
    for cin, cout in ((h, f), (h, f), (f, h)):
        w = (hi - lo) * cin * cout * 2
        x, y = rows * cin * 2, rows * cout * 2
        tf += 3 * flops / peaks["flops_bf16"]
        tb += 3 * (x + y + w) / peaks["hbm_bytes_per_s"]
    return {"least_s": max(tf, tb), "flops_s": tf, "bytes_s": tb}


def gqa_attn_min_seconds(cfg, peaks, batch: int) -> dict:
    """The least time the grouped-query attentions of the layers run can
    take in a training step of ``batch`` sequences (the scope
    ``mha/attn``: from normed, rotated q, k, v to the weighted values):
    scores and weighted values INSIDE the causal mask at all 32 query
    heads, two products forward and four backward, against reading q and
    the output at 32 heads and k and v at their own 8 (once a group, not
    once a query head) and their gradients once, bf16."""
    t = seq_length(cfg)
    mh, kv, d = _heads(cfg)
    layers = _count(cfg, "full_attention")
    tf = layers * batch * 3 * 2.0 * _causal_pairs(t) * mh * 2 * d \
        / peaks["flops_bf16"]
    tb = layers * batch * 2 * t * (2 * mh + 2 * kv) * d * 2 \
        / peaks["hbm_bytes_per_s"]
    return {"least_s": max(tf, tb), "flops_s": tf, "bytes_s": tb}


def shortconv_min_seconds(cfg, peaks, batch: int) -> dict:
    """The least time the gates and taps of the gated short convolutions
    of the layers run can take in a training step of ``batch`` sequences
    (the scope ``sconv/mix``: from [B; C; u] to the row W_out takes), by
    bytes: forward B, C and u read and one row written; backward the
    row's gradient and B, C, u read and their three gradients written;
    bf16. The arithmetic (some ten operations a channel) is nowhere near
    the peak."""
    t, h = seq_length(cfg), cfg["hidden_size"]
    layers = _count(cfg, "conv")
    rows = (3 + 1) + (1 + 3 + 3)
    tb = layers * batch * t * rows * h * 2 / peaks["hbm_bytes_per_s"]
    tf = layers * batch * t * h * 3 * 2.0 * (2 + cfg["conv_L_cache"]) \
        / peaks["flops_bf16"]
    return {"least_s": max(tf, tb), "flops_s": tf, "bytes_s": tb}


# ----------------------------------------------------------------- forward
def _fp8(x):
    """Round to float8 e4m3 with one scale per tensor; gradients pass
    straight through."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / s).astype(jnp.float8_e4m3fn).astype(x.dtype) * s
    return x + lax.stop_gradient(q - x)


def _mm(a, b, precision):
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, gamma, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gamma


def short_conv(cfg, p, x, precision="highest", fault=None):
    """The gated short convolution on x (B, T, hidden), normed."""
    h, taps = cfg["hidden_size"], cfg["conv_L_cache"]
    t = x.shape[1]
    bcu = _mm(x, p["Win"], precision)
    b, c, u = bcu[..., :h], bcu[..., h:2 * h], bcu[..., 2 * h:]
    z = jnp.pad(b * u, ((0, 0), (taps - 1, 0), (0, 0)))
    # w_j meets z_{t-j}; the leaf keeps w_j as its row K-1-j
    w = p["conv"] if fault == "taps_reversed" else p["conv"][::-1]
    conv = sum(w[j] * z[:, taps - 1 - j:taps - 1 - j + t]
               for j in range(taps))
    return _mm(c * conv, p["Wout"], precision)


def rotate(x, theta):
    """RoPE on x (B, T, H, D) at positions 0 .. T-1 over all D dims: dim
    j turned against dim j + D/2 by the angle ``t * theta^(-2j/D)``."""
    t, half = x.shape[1], x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv      # (T, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention_inputs(cfg, p, x, precision="highest", fault=None):
    """x (B, T, hidden), normed -> q (B, T, 32, 64), k and v (B, T, 8,
    64): projected, q and k normed over the head width, then rotated."""
    b, t, _ = x.shape
    mh, kv, d = _heads(cfg)
    eps = cfg["norm_eps"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    q = _mm(x, p["Wq"], precision).reshape(b, t, mh, d)
    k = _mm(x, p["Wk"], precision).reshape(b, t, kv, d)
    v = _mm(x, p["Wv"], precision).reshape(b, t, kv, d)
    if fault != "no_qk_norm":
        q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
    if fault != "no_rope":
        q, k = rotate(q, theta), rotate(k, theta)
    return q, k, v


def attention(cfg, p, x, precision="highest", fault=None):
    """x (B, T, hidden), normed -> (B, T, hidden). One (sequence, query
    head) at a time under a dense T x T mask, each with the key/value
    head of its group."""
    b, t, _ = x.shape
    mh, kv, d = _heads(cfg)
    q, k, v = attention_inputs(cfg, p, x, precision, fault)
    of_query = jnp.arange(mh) % kv if fault == "kv_head_mod" \
        else jnp.arange(mh) // (mh // kv)
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    rows = lambda a: a.transpose(0, 2, 1, 3).reshape(-1, t, d)
    k, v = rows(k[:, :, of_query]), rows(v[:, :, of_query])

    @jax.checkpoint
    def one(q1, k1, v1):
        s = _mm(q1, k1.T, precision) * d ** -0.5
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _mm(w, v1, precision)

    out = lax.map(lambda a: one(*a), (rows(q), k, v))
    out = out.reshape(b, mh, t, d).transpose(0, 2, 1, 3)
    return _mm(out.reshape(b, t, mh * d), p["Wo"], precision)


def _gated(x, wg, wu, wd, precision):
    return _mm(jax.nn.silu(_mm(x, wg, precision)) * _mm(x, wu, precision),
               wd, precision)


def routing(cfg, p, x, precision="highest", fault=None):
    """(experts chosen (N, 4), their weights (N, 4)) for x (N, hidden);
    the scores in float32 whatever the precision of the products."""
    s = jax.nn.sigmoid(_mm(x, p["Wr"], precision))
    _, idx = lax.top_k(lax.stop_gradient(s), cfg["num_experts_per_tok"])
    kept = jnp.take_along_axis(s, idx, axis=-1)
    if fault != "no_renorm":
        kept = kept / (jnp.sum(kept, axis=-1, keepdims=True) + RENORM_EPS)
    return idx, kept * cfg["routed_scaling_factor"]


def experts(cfg, p, x, precision="highest", fault=None):
    """The held experts' part for x (N, hidden); nothing else: a token
    with no held expert gets exactly zero."""
    lo, hi = _held(cfg)
    idx, w = routing(cfg, p, x, precision, fault)
    expert = jax.checkpoint(functools.partial(_gated, precision=precision))
    y = jnp.zeros_like(x)
    for e in range(lo, hi):         # a plain loop over the experts held
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        y = y + w_e[:, None] * expert(x, p["Wgate"][e - lo],
                                      p["Wup"][e - lo], p["Wdown"][e - lo])
    return y


def layer(cfg, p, h, kind, ffn, precision="highest", held=None, fault=None):
    """One decoder layer on h (B, T, hidden): ``kind`` its operator,
    ``ffn`` "dense" or "experts". ``held`` overrides the configuration's
    range of experts (the shares-add-up test)."""
    if held is not None:
        cfg = {**cfg, "experts_held": list(held),
               "num_experts": held[1] - held[0]}
    eps = cfg["norm_eps"]
    op = short_conv if kind == "conv" else attention
    h = h + op(cfg, p["attn"], _rms(h, p["ln1"]["gamma"], eps), precision,
               fault)
    x = _rms(h, p["ln2"]["gamma"], eps)
    b, t, f = x.shape
    if ffn == "dense":
        return h + _gated(x, p["ffn"]["Wgate"], p["ffn"]["Wup"],
                          p["ffn"]["Wdown"], precision)
    return h + experts(cfg, p["ffn"], x.reshape(b * t, f), precision,
                       fault).reshape(b, t, f)


def _cross_entropy(x, embedding, y, keep, precision):
    """Mean over the kept positions of the cross-entropy of x (N, hidden)
    through the tied head against y (N,), in blocks of positions."""
    blk = min(LOSS_BLOCK, x.shape[0])

    @jax.checkpoint
    def block(e, xb, yb, kb):
        z = _mm(xb, e.T, precision)
        return jnp.sum(kb * (jax.nn.logsumexp(z, axis=-1)
                             - jnp.take_along_axis(z, yb[:, None],
                                                   axis=-1)[:, 0]))

    total = 0.0
    for s in range(0, x.shape[0], blk):
        total = total + block(embedding, x[s:s + blk], y[s:s + blk],
                              keep[s:s + blk])
    return total / jnp.sum(keep)


def hidden(cfg, params, ids, precision="highest", fault=None):
    """ids (B, T) -> the stream after the final norm (B, T, hidden)."""
    x = params["embed"]["W"][jnp.asarray(ids)]
    for i, (kind, ffn) in enumerate(layer_kinds(cfg)):
        x = jax.checkpoint(functools.partial(
            layer, cfg, kind=kind, ffn=ffn, precision=precision,
            fault=fault))(params[f"layer{i}"], x)
    return _rms(x, params["norm"]["gamma"], cfg["norm_eps"])


def logits(cfg, params, ids, precision="highest"):
    """(B, T, vocab) over the held slice (tests' sizes only)."""
    return _mm(hidden(cfg, params, ids, precision), params["embed"]["W"].T,
               precision)


def loss_fn(cfg, params, ids, precision="highest", fault=None):
    """Mean next-token cross-entropy of ids (B, T) int32, float32."""
    ids = jnp.asarray(ids)
    b, t = ids.shape
    x = hidden(cfg, params, ids, precision, fault)
    keep = jnp.broadcast_to(jnp.arange(t)[None, :] < t - 1,
                            (b, t)).astype(jnp.float32)
    return _cross_entropy(
        x.reshape(b * t, -1), params["embed"]["W"],
        jnp.roll(ids, -1, axis=1).reshape(-1), keep.reshape(-1), precision)


def train_steps(cfg, params, batches, precision="highest", devices=None,
                fault=None):
    """Follow AdamW through ``batches`` (the harness's (uint8 rows,
    one-hot) pairs; the one-hot is ignored). Returns (losses, first
    moment, final params), all float32, the trees on the host. Weight
    decay on the leaves of two or more dimensions; the tied matrix is one
    leaf, decayed once. One chip: ``devices`` is taken for the
    interface's sake. ``fault``: one of `FAULTS`, for the tests of the
    limits only.

    The gradient is taken ONE sequence at a time (every sequence has the
    same number of kept positions, so the batch's loss is the mean of the
    sequences' and its gradient the mean of theirs) and the update is
    applied one top-level entry of the parameters after another with
    AdamW's two moments kept on the HOST in between: 469 M parameters
    with their gradient, both moments AND the float32 temporaries of
    32,768 tokens at a width of 11,776 do not fit one chip's 16 GB
    together."""
    lr, b1, b2 = cfg["learning_rate"], cfg["beta1"], cfg["beta2"]
    eps, wd = cfg["epsilon"], cfg["weight_decay"]

    @jax.jit
    def gradient(params, ids):
        return jax.value_and_grad(
            lambda p: loss_fn(cfg, p, ids, precision, fault))(params)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def add(total, g):
        return jax.tree_util.tree_map(jnp.add, total, g)

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def update(params, g, m, v, count, n):
        g = jax.tree_util.tree_map(lambda g: g / n, g)
        m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = jax.tree_util.tree_map(
            lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        c1 = 1 - b1 ** count.astype(jnp.float32)
        c2 = 1 - b2 ** count.astype(jnp.float32)
        params = jax.tree_util.tree_map(
            lambda w, m, v: w - lr * (
                (m / c1) / (jnp.sqrt(v / c2) + eps)
                + (wd * w if w.ndim >= 2 else 0.0)), params, m, v)
        return params, m, v

    zeros = lambda t: jax.tree_util.tree_map(
        lambda a: np.zeros(a.shape, np.float32), t)
    params = dict(params)
    m, v = zeros(params), zeros(params)
    out = []
    for count, (rows, _) in enumerate(batches, start=1):
        ids = decode_tokens(cfg, rows)
        if fault == "half_batch":
            ids = ids[:max(len(ids) // 2, 1)]
        loss, g = 0.0, None
        for seq in ids:             # one sequence of the batch at a time
            l1, g1 = gradient(params, jnp.asarray(seq[None]))
            loss, g = loss + float(l1), g1 if g is None else add(g, g1)
        out.append(loss / len(ids))
        n = jnp.asarray(len(ids), jnp.float32)
        for stage in list(params):
            params[stage], m_new, v_new = update(
                params[stage], g.pop(stage), jax.device_put(m[stage]),
                jax.device_put(v[stage]), jnp.asarray(count, jnp.int32), n)
            m[stage], v[stage] = jax.device_get((m_new, v_new))
    return out, m, jax.device_get(params)
