"""Plain reference for ``sdar-30b-a3b-chat``: the decoder layers of JetLM's
SDAR-30B-A3B-Chat (config.json, ``model_type`` ``sdar_moe``) in their
TRAINING form, block diffusion over the stream ``[noisy copy ; clean
copy]``, the noising rule, the weighted denoising loss, its gradient and
the AdamW step in straightforward float32 ``jax.numpy`` at ``highest``
matmul precision: the visibility as a boolean built from its three
clauses, grouped-query attention under it with every head's scores of a
block of queries against ALL 2L keys held at once, a Python loop over the
experts held, no kernel, no dispatch, AdamW written out, AdamW's moments
on the host between steps. It imports nothing of the program and takes
nothing the program made: weights come from the configuration's
``weights_seed``, batches from the benchmark's seed, the noise from its own
copy of the rule; leaves are named as the zoo model's graph names them.

One block (h: 2L x 2048): ``h += Attn(RMSNorm(h))``; ``h += MoE(RMSNorm(
h))``; RMSNorm ``x / sqrt(mean(x^2) + 1e-6) * gamma``. After the last
block the first L rows (the noisy half), a final RMSNorm, logits ``= h
W_head`` (untied) over the held slice of the vocabulary.

Attn, x = the normed stream (32 query heads on 4 key/value heads of 128):
  q = x W_q (32 x 128); k = x W_k, v = x W_v (4 x 128);
  q <- RMSNorm_128(q), k <- RMSNorm_128(k) (one learned gain of 128 each,
  every head alike) BEFORE the rotation; RoPE(theta 1e6, all 128 dims, dim
  j of 64 turned against dim j + 64 by pos * theta^(-j/64)) at ``pos(i) =
  i mod L``: both halves count 0..L-1; query head h reads key head h // 8;
  softmax over the VISIBLE keys of q.k / sqrt(128); W_o.
Visibility over the 2L rows (``noisy(i) = i < L``, ``blk(i) = pos(i) //
  4``): row i sees row j iff
    noisy(i) and noisy(j) and blk(j) == blk(i)        (its own noisy block)
    or noisy(i) and not noisy(j) and blk(j) < blk(i)  (clean blocks before)
    or not noisy(i) and not noisy(j) and blk(j) <= blk(i)  (block-causal)
  (BD3-LMs, arXiv:2503.09573; SDAR, arXiv:2510.06303).
MoE, x = the normed stream after the attention:
  r = x W_r over all 128 (float32); S = the 8 largest; w = softmax over
  the kept ones (norm_topk_prob); y = sum_{i in S, i held} w_i
  W_down,i(SiLU(x W_gate,i) * (x W_up,i)), NO shared expert: a token none
  of whose eight experts is held gets 0.
Noise and loss: every block b of every sequence draws t_b ~ U(1e-3, 1);
  each token of the block becomes the mask id with probability t_b,
  independently; row i of the noisy half predicts x_0[i] ITSELF (no
  shift); ``loss = (1 / (N L)) sum_seq sum_{i masked} CE(logits_i, x_0[i])
  / t_blk(i)``, float32.

Departures from the published model, each also under ``assumed`` in the
configuration's file: block length, noise schedule and mask id are the
family's convention; the chip's share is the experts ``experts_held`` of
the 128 routed over and the first ``vocab_size`` ids; what the other
chips' experts would add is left out, here as in the program.

``precision="fp8"`` is the control, not a reference: the same mathematics
with the operands of every matrix product rounded to float8 (e4m3, one
scale a tensor as the product reads it), the step below the bf16 the
configuration states.
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
#: queries whose scores against all 2L keys are held at once, at every head
Q_BLOCK = 256
#: the faults `train_steps` can plant; the cell's limits have to catch each
#: (benchmark/tools/plant_faults.py)
FAULTS = ("plain_causal", "sees_own_clean_block", "noisy_sees_noisy_past",
          "positions_run_on", "no_weight", "mean_over_masked",
          "loss_on_clean_half", "kv_head_mod")


# ----------------------------------------------------------------- tokens
def mask_token_id(cfg) -> int:
    """The held slice's LAST id stands for the published mask token."""
    return int(cfg["vocab_size"]) - 1


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
    int32 token ids (B, L): each little-endian uint16 of a row through the
    Zipf table over the DATA ids (every id of the slice but the mask's).
    The ONE decode, for the adapter's feed and for ``train_steps``."""
    rows = np.ascontiguousarray(np.asarray(rows, np.uint8))
    u = rows.reshape(rows.shape[0], -1).view("<u2")
    return zipf_table(mask_token_id(cfg), float(cfg["zipf_s"]))[u]


def noise_seed(rows) -> int:
    """The seed of a run's noise: the first eight bytes of the run's
    first host batch (which the harness draws from ``--seed``) as a
    little-endian integer, halved. The harness hands the adapter's feed
    and ``train_steps`` the seed's batches, not the seed."""
    first = np.ascontiguousarray(np.asarray(rows, np.uint8)).reshape(-1)[:8]
    return int(first.view("<u8")[0] >> np.uint64(1))


def targets(cfg, ids, seed: int, first: int = 0, fault=None):
    """The reference's own copy of the noising rule: token ids (N, L) of
    the sequences ``first ..`` of a run whose noise seed is ``seed`` ->
    (stream (N, 2L): the noisy copy then the clean one, targets (N, L):
    the clean ids, weights (N, L) float32: 1 / t where masked, else 0).
    Sequence ``n`` draws from Philox keyed by (seed, n): L / block
    doubles u for the blocks' levels t = t_min + (1 - t_min) u, then L
    doubles for the tokens; a token is masked iff its double is below its
    block's t."""
    ids = np.asarray(ids, np.int32)
    n, length = ids.shape
    b, t_min = int(cfg["block_length"]), float(cfg["noise_t_min"])
    noisy, weights = ids.copy(), np.zeros((n, length), np.float32)
    for row in range(n):
        gen = np.random.Generator(np.random.Philox(key=[seed, first + row]))
        t = np.repeat(t_min + (1.0 - t_min) * gen.random(length // b), b)
        masked = gen.random(length) < t
        noisy[row, masked] = mask_token_id(cfg)
        weights[row, masked] = 1.0 if fault == "no_weight" \
            else (1.0 / t[masked]).astype(np.float32)
    return np.concatenate([noisy, ids], axis=1), ids, weights


# ----------------------------------------------------------------- shapes
def _held(cfg):
    lo, hi = cfg["experts_held"]
    assert hi - lo == cfg["num_experts"]
    return lo, hi


def _heads(cfg):
    """(query heads, key/value heads, head width)."""
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"])


def _block_shapes(cfg) -> dict:
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    mh, kv, d = _heads(cfg)
    lo, hi = _held(cfg)
    e = hi - lo
    attn = {"Wq": (h, mh * d), "Wk": (h, kv * d), "Wv": (h, kv * d),
            "Wo": (mh * d, h), "q_norm": (d,), "k_norm": (d,)}
    ffn = {"Wr": (h, cfg["router_experts"]), "Wgate": (e, h, f),
           "Wup": (e, h, f), "Wdown": (e, f, h)}
    return {"attn": attn, "ffn": ffn, "ln1": {"gamma": (h,)},
            "ln2": {"gamma": (h,)}}


def param_shapes(cfg) -> dict:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    out = {"embed": {"W": (v, h)}}
    for i in range(cfg["num_hidden_layers"]):
        out[f"layer{i}"] = _block_shapes(cfg)
    out["norm"] = {"gamma": (h,)}
    out["head"] = {"W": (h, v)}
    return out


def stage_of(cfg, leaf: str) -> str:
    """The stage a parameter leaf (by its path,
    ``['layer2']['attn']['Wq']``) belongs to: ``embed``, ``layer0`` ..
    ``layer4``, ``head`` (the final norm and the head's matrix)."""
    names = leaf.split("'")[1::2]
    return "head" if names[0] in ("norm", "head") else names[0]


_OUT_PROJECTIONS = ("Wo", "Wdown")


def make_params(cfg, seed: int = 0):
    """Seeded float32 weights on the device, ALL from the configuration's
    ``weights_seed`` (``seed``, the run's, draws the token ids and the
    noise only: the weights decide which experts a token draws, and a
    run's seed is not to move the amount of work). The embedding N(0,
    embedding_std^2); the output projections of attention and experts
    N(0, out_proj_std^2); every other matrix N(0, matrix_std^2); gains
    1."""
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
            cfg["out_proj_std"] if name in _OUT_PROJECTIONS
            else cfg["matrix_std"])
        out.append(_normal(jax.random.fold_in(root, i), shape, float(std)))
    return jax.tree_util.tree_unflatten(tree, out)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _normal(key, shape, std):
    return std * jax.random.normal(key, shape, jnp.float32)


# ------------------------------------------------------------------ counts
def pairs_visible(length: int, block: int) -> float:
    """Visible (query, key) pairs of one head and sequence: the clean
    copy's block-causal (L^2 + B L) / 2, the noisy rows' clean blocks
    before (L^2 - B L) / 2 and their own blocks B L: L^2 + B L."""
    return float(length) * length + float(block) * length


def _experts_macs(cfg) -> float:
    """Router and the held experts' EXPECTED rows, a stream row."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    lo, hi = _held(cfg)
    return float(h * cfg["router_experts"]
                 + 3 * h * f * cfg["num_experts_per_tok"] * (hi - lo)
                 / cfg["router_experts"])


def _macs(cfg) -> dict:
    """Multiply-adds of ONE sequence's forward pass (a stream of 2L
    rows) in the matrix products of the layers run, by what they belong
    to."""
    t, h, n = seq_length(cfg), cfg["hidden_size"], cfg["num_hidden_layers"]
    mh, kv, d = _heads(cfg)
    return {
        "attention projections": float(
            n * 2 * t * (2 * h * mh * d + 2 * h * kv * d)),
        "attention inside the rule":
            n * pairs_visible(t, cfg["block_length"]) * mh * 2 * d,
        "held experts": n * 2 * t * _experts_macs(cfg),
        "head": float(t * h * cfg["vocab_size"]),
    }


def flops_shares(cfg) -> dict:
    """Share of `train_flops_per_example` by part, for the cell's ``why``."""
    parts = _macs(cfg)
    total = sum(parts.values())
    return {k: v / total for k, v in parts.items()}


def train_flops_per_example(cfg) -> float:
    """Model FLOPs of one sequence in a training step for the share held
    here: 2 per multiply-add forward and twice that again backward, in
    the projections and the expert layers over all 2L stream rows (the
    mechanism makes every row twice), attention's scores and weighted
    values over the VISIBLE pairs only (L^2 + B L a head: a masked
    product's work on the other three quarters of the (2L)^2 square is
    not model work), the router, the held experts' three products for the
    rows they are EXPECTED to draw, and the head over the noisy half.
    Recomputation (the backward kernels' second pass over the scores
    too), norms, softmax, rotation, the embedding gather and the
    optimizer are left out, as MFU's convention has it."""
    return 2.0 * sum(_macs(cfg).values()) * 3


flops = train_flops_per_example


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


moe_experts_min_seconds = experts_min_seconds


def gqa_attn_min_seconds(cfg, peaks, batch: int) -> dict:
    """The least time the attentions of the layers run can take in a
    training step of ``batch`` sequences (the scope ``mha/attn``: from
    normed, rotated q, k, v to the weighted values): scores and weighted
    values INSIDE the rule (L^2 + B L pairs a head) at all 32 query
    heads, two products forward and four backward, against reading q and
    the output over the 2L stream rows at 32 heads and k and v at their
    own 4 (once a group, not once a query head) and their gradients once,
    bf16. Counted from the configuration whatever implements it: a causal
    walk over the 2L rows with the rule as a mask does about twice this
    arithmetic."""
    t, n = seq_length(cfg), cfg["num_hidden_layers"]
    mh, kv, d = _heads(cfg)
    tf = n * batch * 3 * 2.0 * pairs_visible(t, cfg["block_length"]) \
        * mh * 2 * d / peaks["flops_bf16"]
    tb = n * batch * 2 * (2 * t) * (2 * mh + 2 * kv) * d * 2 \
        / peaks["hbm_bytes_per_s"]
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


def rotate(x, theta, positions):
    """RoPE on x (T, H, D) over all D dims: dim j of the first half turned
    against dim j + D/2 by ``pos * theta^(-j / (D/2))``."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv          # (T, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def visible(rows, length: int, block: int, fault=None):
    """(rows, 2L) bool: the three clauses, for the absolute stream rows
    ``rows`` against every stream row."""
    i, j = jnp.asarray(rows)[:, None], jnp.arange(2 * length)[None, :]
    if fault == "plain_causal":         # causal over the 2L stream
        return j <= i
    ni, nj = i < length, j < length
    bi, bj = (i % length) // block, (j % length) // block
    own = bj <= bi if fault == "noisy_sees_noisy_past" else bj == bi
    before = bj <= bi if fault == "sees_own_clean_block" else bj < bi
    return (ni & nj & own) | (ni & ~nj & before) | (~ni & ~nj & (bj <= bi))


def attention_inputs(cfg, p, x, precision="highest", fault=None):
    """x (2L, hidden), normed -> q (2L, 32, 128), k and v (2L, 4, 128):
    projected, q and k normed over the head width, then rotated at
    ``pos(i) = i mod L``."""
    t = x.shape[0]
    mh, kv, d = _heads(cfg)
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    pos = jnp.arange(t) if fault == "positions_run_on" \
        else jnp.arange(t) % (t // 2)
    q = _mm(x, p["Wq"], precision).reshape(t, mh, d)
    k = _mm(x, p["Wk"], precision).reshape(t, kv, d)
    v = _mm(x, p["Wv"], precision).reshape(t, kv, d)
    q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
    return rotate(q, theta, pos), rotate(k, theta, pos), v


def attention_block(cfg, q, k, v, rows, precision="highest", fault=None):
    """One block of queries q (Tq, 32, 128), the stream rows ``rows``,
    against ALL keys k, v (2L, 4, 128): weighted values (Tq, 32, 128)."""
    tq = q.shape[0]
    mh, kv, d = _heads(cfg)
    # query head h reads key head h // 8: the heads as (key head, the
    # eight of its group); the planted fault reads h % 4: (eight, key head)
    split, to_s, to_o = ((mh // kv, kv), "qjgd,kgd->gjqk", "gjqk,kgd->qjgd") \
        if fault == "kv_head_mod" else \
        ((kv, mh // kv), "qgjd,kgd->gjqk", "gjqk,kgd->qgjd")
    seen = visible(rows, k.shape[0] // 2, cfg["block_length"], fault)
    if precision == "fp8":
        q, k, v = _fp8(q), _fp8(k), _fp8(v)
    s = jnp.einsum(to_s, q.reshape((tq,) + split + (d,)), k,
                   precision=HIGHEST) * d ** -0.5
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum(to_o, _fp8(p) if precision == "fp8" else p, v,
                      precision=HIGHEST).reshape(tq, mh, d)


def attention(cfg, p, x, precision="highest", fault=None):
    """x (2L, hidden), normed -> Attn (2L, hidden): queries in blocks of
    `Q_BLOCK`, each rematerialised in the backward pass."""
    t = x.shape[0]
    blk = min(Q_BLOCK, t)
    assert t % blk == 0
    q, k, v = attention_inputs(cfg, p, x, precision, fault)
    one = jax.checkpoint(functools.partial(
        attention_block, cfg, precision=precision, fault=fault))
    starts = jnp.arange(0, t, blk)
    out = lax.map(
        lambda a: one(a[0], k, v, a[1] + jnp.arange(blk)),
        (q.reshape((t // blk, blk) + q.shape[1:]), starts))
    return _mm(out.reshape(t, -1), p["Wo"], precision)


def _gated(x, wg, wu, wd, precision):
    return _mm(jax.nn.silu(_mm(x, wg, precision)) * _mm(x, wu, precision),
               wd, precision)


def routing(cfg, p, x, precision="highest"):
    """(experts chosen (N, 8), their weights (N, 8)) for x (N, hidden);
    the logits in float32 whatever the precision of the products."""
    r = jax.nn.softmax(_mm(x, p["Wr"], precision), axis=-1)
    _, idx = lax.top_k(lax.stop_gradient(r), cfg["num_experts_per_tok"])
    kept = jnp.take_along_axis(r, idx, axis=-1)
    assert cfg["norm_topk_prob"]
    return idx, kept / jnp.sum(kept, axis=-1, keepdims=True)


def experts(cfg, p, x, precision="highest"):
    """The held experts' part for x (N, hidden); nothing else: a token
    with no held expert gets exactly zero. Each expert's weighted result
    is rematerialised in the backward pass."""
    lo, hi = _held(cfg)
    idx, w = routing(cfg, p, x, precision)

    @jax.checkpoint
    def weighed(x, w_e, wg, wu, wd):
        return w_e[:, None] * _gated(x, wg, wu, wd, precision)

    y = jnp.zeros_like(x)
    for e in range(lo, hi):         # a plain loop over the experts held
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        y = y + weighed(x, w_e, p["Wgate"][e - lo], p["Wup"][e - lo],
                        p["Wdown"][e - lo])
    return y


def layer(cfg, p, h, precision="highest", held=None, fault=None):
    """One decoder layer on the stream h (2L, hidden). ``held`` overrides
    the configuration's range of experts (the shares-add-up test)."""
    if held is not None:
        cfg = {**cfg, "experts_held": list(held),
               "num_experts": held[1] - held[0]}
    eps = cfg["rms_norm_eps"]
    h = h + attention(cfg, p["attn"], _rms(h, p["ln1"]["gamma"], eps),
                      precision, fault)
    return h + experts(cfg, p["ffn"], _rms(h, p["ln2"]["gamma"], eps),
                       precision)


def _weighted_cross_entropy(x, head, y, w, precision):
    """sum_i w_i CE(x_i W_head, y_i) over x (N, hidden), in blocks of
    positions."""
    blk = min(LOSS_BLOCK, x.shape[0])

    @jax.checkpoint
    def block(head, xb, yb, wb):
        z = _mm(xb, head, precision)
        return jnp.sum(wb * (jax.nn.logsumexp(z, axis=-1)
                             - jnp.take_along_axis(z, yb[:, None],
                                                   axis=-1)[:, 0]))

    total = 0.0
    for s in range(0, x.shape[0], blk):
        total = total + block(head, x[s:s + blk], y[s:s + blk],
                              w[s:s + blk])
    return total


def hidden(cfg, params, stream, precision="highest", fault=None):
    """stream ids (2L,) -> the noisy half after the final norm (L,
    hidden)."""
    x = params["embed"]["W"][jnp.asarray(stream)]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(functools.partial(
            layer, cfg, precision=precision, fault=fault))(
                params[f"layer{i}"], x)
    length = x.shape[0] // 2
    half = x[length:] if fault == "loss_on_clean_half" else x[:length]
    return _rms(half, params["norm"]["gamma"], cfg["rms_norm_eps"])


def logits(cfg, params, stream, precision="highest"):
    """(L, vocab) of the noisy half over the held slice (tests' sizes
    only)."""
    return _mm(hidden(cfg, params, stream, precision), params["head"]["W"],
               precision)


def loss_fn(cfg, params, stream, y, w, precision="highest", fault=None):
    """The denoising loss of ONE sequence: stream (2L,), targets y (L,),
    weights w (L,): ``sum_i w_i CE_i / L``, float32."""
    x = hidden(cfg, params, stream, precision, fault)
    total = _weighted_cross_entropy(x, params["head"]["W"], jnp.asarray(y),
                                    jnp.asarray(w), precision)
    if fault == "mean_over_masked":     # normalised by the mask's sum
        return total / jnp.maximum(jnp.sum(w), 1.0)
    return total / x.shape[0]


def train_steps(cfg, params, batches, precision="highest", devices=None,
                fault=None):
    """Follow AdamW through ``batches`` (the harness's (uint8 rows,
    one-hot) pairs; the one-hot is ignored), the noise that of a run
    whose first batch is ``batches[0]`` (`noise_seed`), sequences counted
    from 0. Returns (losses, first moment, final params), all float32,
    the trees on the host; the losses are the denoising losses, as
    ``fit()`` reports them. Weight decay on the leaves of two or more
    dimensions. One chip: ``devices`` is taken for the interface's sake.
    ``fault``: one of `FAULTS`, for the tests of the limits only.

    The gradient is taken ONE sequence at a time (the loss is a sum over
    sequences of sums over positions, divided by N L: the batch's loss is
    the mean of the sequences' and the gradient the mean of theirs) and
    the update is applied one top-level entry of the parameters after
    another with AdamW's two moments kept on the HOST in between."""
    lr, b1, b2 = cfg["learning_rate"], cfg["beta1"], cfg["beta2"]
    eps, wd = cfg["epsilon"], cfg["weight_decay"]

    @jax.jit
    def gradient(params, stream, y, w):
        return jax.value_and_grad(
            lambda p: loss_fn(cfg, p, stream, y, w, precision, fault))(
                params)

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
    batches = list(batches)
    seed, seen, out = noise_seed(batches[0][0]), 0, []
    for count, (rows, _) in enumerate(batches, start=1):
        ids = decode_tokens(cfg, rows)
        streams, ys, ws = targets(cfg, ids, seed, seen, fault)
        seen += len(ids)
        loss, g = 0.0, None
        for seq in range(len(ids)):     # one sequence of the batch at a time
            l1, g1 = gradient(params, jnp.asarray(streams[seq]),
                              jnp.asarray(ys[seq]), jnp.asarray(ws[seq]))
            loss, g = loss + float(l1), g1 if g is None else add(g, g1)
        out.append(loss / len(ids))
        n = jnp.asarray(len(ids), jnp.float32)
        for stage in list(params):
            params[stage], m_new, v_new = update(
                params[stage], g.pop(stage), jax.device_put(m[stage]),
                jax.device_put(v[stage]), jnp.asarray(count, jnp.int32), n)
            m[stage], v[stage] = jax.device_get((m_new, v_new))
    return out, m, jax.device_get(params)
