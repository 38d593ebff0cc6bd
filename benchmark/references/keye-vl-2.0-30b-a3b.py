"""Plain reference for ``keye-vl-2.0-30b-a3b``: the decoder layers of the
LANGUAGE MODEL of Kwai-Keye's Keye-VL-2.0-30B-A3B (config.json,
``model_type`` ``KeyeVL2``; the vision tower is not part of it), the loss,
the indexer's own loss, their gradients and the AdamW step in
straightforward float32 ``jax.numpy`` at ``highest`` matmul precision: the
indexer's scores as a dense block of queries against the keys up to the
end of the block's quarter of the sequence (the keys behind it no query of
the quarter sees), ``lax.top_k`` for the selection and a dense mask
scattered from its ids, grouped-query attention under that mask with every
head's scores of a block of queries held at once (the heads grouped by
their key head, which is multiplied as it is and not copied eight times),
a Python loop over the experts held, no
kernel, no dispatch, AdamW written out, AdamW's moments on the host
between steps. It imports nothing of the program and takes nothing the
program made: weights come from the configuration's ``weights_seed``,
batches from the benchmark's seed; leaves are named as the zoo model's
graph names them.

One block (h: T x 2048): ``h += Attn(RMSNorm(h))``; ``h += MoE(RMSNorm(
h))``; RMSNorm ``x / sqrt(mean(x^2) + 1e-6) * gamma``. After the last
block a final RMSNorm, logits ``= h W_head`` (untied), mean next-token
cross-entropy ``CE`` over the held slice of the vocabulary.

Attn, x = the normed stream (32 query heads on 4 key/value heads of 128):
  q = x W_q (32 x 128); k = x W_k, v = x W_v (4 x 128);
  q <- RMSNorm_128(q), k <- RMSNorm_128(k) (one learned gain of 128 each,
  every head alike) BEFORE the rotation; RoPE(theta 1e7, all 128 dims, dim
  j of 64 turned against dim j + 64 by pos_r(j) * theta^(-j/64), r(j) the
  time row for j < 16, the height row for 16 <= j < 40, the width row
  above: on text all three are the position); query head h reads key head
  h // 8.
Indexer, on x DETACHED (16 heads of 64 on one key head):
  qI = x W_qI (16 x 64); kI = LayerNorm_64(x W_kI); both rotated over all
  64 dims (theta 1e7, time row); w = x W_w * 16^-1/2 * 64^-1/2;
  I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s]), s <= t.
Selection: S_t = the min(t + 1, 2048) keys s <= t with the largest
  I[t, s] (``lax.top_k``: ties to the lower s). Not differentiated.
  o[t, h] = sum_{s in S_t} softmax_{S_t}(q[t, h] . k[s, h // 8] / sqrt(128))
  v[s, h // 8]; Attn = o W_o.
Indexer's loss: p[t, s] = mean_h softmax_{S_t}(...)[t, h, s], detached;
  L_I = mean_t sum_{s in S_t} p (log p - log softmax_{S_t}(I[t, .])[s]).
  The step's gradient is that of CE + 1.0 * sum_layers L_I; the loss
  reported is CE.
MoE, x = the normed stream after the attention:
  r = x W_r over all 128 (float32); S = the 8 largest; w = softmax over
  the kept ones (norm_topk_prob); y = sum_{i in S, i held} w_i
  W_down,i(SiLU(x W_gate,i) * (x W_up,i)), NO shared expert: a token none
  of whose eight experts is held gets 0.

Departures from the published model, each also under ``assumed`` in the
configuration's file: the vision tower and image input are left out; the
chip's share is the experts ``experts_held`` of the 128 routed over and
the first ``vocab_size`` ids; what the other chips' experts would add is
left out, here as in the program.

``precision="fp8"`` is the control, not a reference: the same mathematics
with the operands of every matrix product rounded to float8 (e4m3, one
scale a tensor as the product reads it: of the keys, a block's prefix),
the step below the bf16 the configuration states.
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

HIGHEST = lax.Precision.HIGHEST
#: positions whose logits the loss holds at once
LOSS_BLOCK = 2048
#: queries whose scores against the keys are held at once, at every head
Q_BLOCK = 128
#: the sequence's parts whose queries are held against the keys up to the
#: part's end only
KEY_PREFIXES = 4
#: the faults `train_steps` can plant; the cell's limits have to catch each
#: (benchmark/tools/plant_faults.py)
FAULTS = ("no_relu", "no_head_weights", "half_topk", "sees_next",
          "no_indexer_loss", "kl_head0", "kv_head_mod", "no_renorm")
#: the weight of the indexer's loss in the step's gradient
INDEXER_LOSS_COEF = 1.0


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
def _held(cfg):
    lo, hi = cfg["experts_held"]
    assert hi - lo == cfg["num_experts"]
    return lo, hi


def _heads(cfg):
    """(query heads, key/value heads, head width)."""
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"])


def _indexer(cfg):
    """(heads, head width, keys kept a query)."""
    sa = cfg["sa_config"]
    assert sa["indexer_num_kv_heads"] == 1
    return sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]


def _block_shapes(cfg) -> dict:
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    mh, kv, d = _heads(cfg)
    ih, idim, _ = _indexer(cfg)
    lo, hi = _held(cfg)
    e = hi - lo
    attn = {"Wq": (h, mh * d), "Wk": (h, kv * d), "Wv": (h, kv * d),
            "Wo": (mh * d, h), "q_norm": (d,), "k_norm": (d,),
            "indexer": {"Wq": (h, ih * idim), "Wk": (h, idim),
                        "Ww": (h, ih), "k_gamma": (idim,),
                        "k_beta": (idim,)}}
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
    ``layer4``, ``head`` (the final norm and the head's matrix) and, for
    the indexers' leaves of ALL layers, ``indexer``: they are trained by
    the indexer's loss alone, whose gradient depends on the scores over
    exactly the kept keys, so it is the stage that shows a wrong
    selection."""
    names = leaf.split("'")[1::2]
    if "indexer" in names:
        return "indexer"
    return "head" if names[0] in ("norm", "head") else names[0]


_OUT_PROJECTIONS = ("Wo", "Wdown")


def make_params(cfg, seed: int = 0):
    """Seeded float32 weights on the device, ALL from the configuration's
    ``weights_seed`` (``seed``, the run's, draws the token ids only: the
    weights decide which experts a token draws and which keys a query
    keeps, and a run's seed is not to move the amount of work). The
    embedding N(0, embedding_std^2); the output projections of attention
    and experts N(0, out_proj_std^2); every other matrix N(0,
    matrix_std^2); gains 1, the indexer's key-norm bias 0."""
    root = jax.random.PRNGKey(int(cfg["weights_seed"]))
    flat, tree = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(flat):
        name = path[-1].key
        if len(shape) == 1:
            fill = jnp.zeros if name == "k_beta" else jnp.ones
            out.append(fill(shape, jnp.float32))
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
def pairs_causal(t: int) -> float:
    return t * (t + 1) / 2.0


def pairs_selected(cfg, t: int) -> float:
    """sum_t min(t + 1, topk): the pairs an exact selection keeps."""
    k = min(_indexer(cfg)[2], t)
    return k * (k + 1) / 2.0 + (t - k) * float(_indexer(cfg)[2])


def _experts_macs(cfg) -> float:
    """Router and the held experts' EXPECTED rows, a token."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    lo, hi = _held(cfg)
    return float(h * cfg["router_experts"]
                 + 3 * h * f * cfg["num_experts_per_tok"] * (hi - lo)
                 / cfg["router_experts"])


def _macs(cfg) -> dict:
    """Multiply-adds of ONE sequence's forward pass in the matrix products
    of the layers run, by what they belong to."""
    t, h, n = seq_length(cfg), cfg["hidden_size"], cfg["num_hidden_layers"]
    mh, kv, d = _heads(cfg)
    ih, idim, _ = _indexer(cfg)
    return {
        "attention projections": float(
            n * t * (2 * h * mh * d + 2 * h * kv * d)),
        "indexer projections": float(n * t * h * (ih * idim + idim + ih)),
        "indexer scores": n * pairs_causal(t) * ih * idim,
        "selected attention": n * pairs_selected(cfg, t) * mh * 2 * d,
        "held experts": n * t * _experts_macs(cfg),
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
    the projections, the indexer's scores over the causal pairs,
    attention's scores and weighted values over the SELECTED pairs only
    (sum_t min(t + 1, topk) a sequence: a masked dense product's work on
    the unselected pairs is not model work), the router, the held experts'
    three products for the rows they are EXPECTED to draw, and the head.
    Recomputation, the selection, norms, softmax, rotation, the embedding
    gather and the optimizer are left out, as MFU's convention has it."""
    return 2.0 * sum(_macs(cfg).values()) * 3


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


def dsa_index_min_seconds(cfg, peaks, batch: int) -> dict:
    """The least time the indexers' score products of the layers run can
    take where a step makes them for the selection (the scope
    ``dsa/index``): every causal pair at 16 heads of 64, once a step and
    layer, against reading qI, kI and w once, bf16. The equations need
    the scores a second time in the indexer's loss's backward; a program
    that makes them again there does so beside the attention's
    probabilities, under ``dsa/attn``, where they are not counted as
    work (`dsa_attn_min_seconds`)."""
    t, n = seq_length(cfg), cfg["num_hidden_layers"]
    ih, idim, _ = _indexer(cfg)
    tf = n * batch * 2.0 * pairs_causal(t) * ih * idim / peaks["flops_bf16"]
    tb = n * batch * t * (ih * idim + idim + 2 * ih) * 2 \
        / peaks["hbm_bytes_per_s"]
    return {"least_s": max(tf, tb), "flops_s": tf, "bytes_s": tb}


def dsa_attn_min_seconds(cfg, peaks, batch: int) -> dict:
    """The least time the sparse attentions of the layers run can take in
    a training step of ``batch`` sequences (the scope ``dsa/attn``):
    scores and weighted values over the SELECTED pairs only at all 32
    query heads, two products forward and four backward, against reading
    q and the output at 32 heads and k and v at their own 4 (once a
    group) and their gradients once, bf16. Counted from the configuration
    whatever implements it: a masked dense product over every causal pair
    does about eight times this arithmetic at 32,768 positions."""
    t, n = seq_length(cfg), cfg["num_hidden_layers"]
    mh, kv, d = _heads(cfg)
    tf = n * batch * 3 * 2.0 * pairs_selected(cfg, t) * mh * 2 * d \
        / peaks["flops_bf16"]
    tb = n * batch * 2 * t * (2 * mh + 2 * kv) * d * 2 \
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


def _layer_norm(x, gamma, beta, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * gamma + beta


def rotate(x, theta, positions, sections=None):
    """RoPE on x (T, H, D) over all D dims: dim j of the first half turned
    against dim j + D/2 by ``pos * theta^(-j / (D/2))``. ``positions`` is
    (T,) or, with ``sections``, (rows, T): frequency j then turns by the
    row whose section holds j (a plain loop over the frequencies'
    rows)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if sections is None:
        pos = jnp.broadcast_to(positions.astype(jnp.float32)[:, None],
                               (x.shape[0], half))
    else:
        assert sum(sections) == half
        pos = jnp.concatenate(
            [jnp.broadcast_to(positions[r].astype(jnp.float32)[:, None],
                              (x.shape[0], n))
             for r, n in enumerate(sections)], axis=1)
    ang = pos * inv                                             # (T, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention_inputs(cfg, p, x, precision="highest"):
    """x (T, hidden), normed -> q (T, 32, 128), k and v (T, 4, 128):
    projected, q and k normed over the head width, then rotated (text:
    the three rows of positions are the position)."""
    t = x.shape[0]
    mh, kv, d = _heads(cfg)
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    sections = tuple(cfg["rope_scaling"]["mrope_section"])
    rows = jnp.broadcast_to(jnp.arange(t)[None], (len(sections), t))
    q = _mm(x, p["Wq"], precision).reshape(t, mh, d)
    k = _mm(x, p["Wk"], precision).reshape(t, kv, d)
    v = _mm(x, p["Wv"], precision).reshape(t, kv, d)
    q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
    return (rotate(q, theta, rows, sections),
            rotate(k, theta, rows, sections), v)


def indexer_inputs(cfg, p, x, precision="highest", fault=None):
    """x (T, hidden), normed -> qI (T, 16, 64), kI (T, 64), w (T, 16), on
    the DETACHED input."""
    x = lax.stop_gradient(x)
    t = x.shape[0]
    ih, idim, _ = _indexer(cfg)
    theta, pos = float(cfg["rope_theta"]), jnp.arange(t)
    qi = rotate(_mm(x, p["Wq"], precision).reshape(t, ih, idim), theta, pos)
    ki = _layer_norm(_mm(x, p["Wk"], precision), p["k_gamma"], p["k_beta"],
                     cfg["rms_norm_eps"])
    ki = rotate(ki[:, None, :], theta, pos)[:, 0, :]
    w = _mm(x, p["Ww"], precision) * (ih ** -0.5 * idim ** -0.5)
    if fault == "no_head_weights":
        w = jnp.ones_like(w) * (ih ** -0.5 * idim ** -0.5)
    return qi, ki, w


def index_scores(qi, ki, w, precision="highest", fault=None):
    """I (Tq, T) of query rows qi (Tq, 16, 64), w (Tq, 16) against all
    keys ki (T, 64)."""
    if precision == "fp8":
        qi, ki = _fp8(qi), _fp8(ki)
    a = jnp.einsum("qhd,kd->qhk", qi, ki, precision=HIGHEST)
    if fault != "no_relu":
        a = jnp.where(a > 0, a, 0.0)     # slope 0 at exactly 0
    return jnp.einsum("qh,qhk->qk", w, a, precision=HIGHEST)


def visible(q0, tq, t, fault=None):
    """(Tq, T) bool: key s is visible to query q0 + i iff s <= t."""
    reach = 1 if fault == "sees_next" else 0
    return jnp.arange(t)[None, :] <= (q0 + jnp.arange(tq))[:, None] + reach


def select_ids(cfg, scores, seen, fault=None):
    """The ids ``lax.top_k`` picks in rows of ``scores`` (Tq, T) among the
    visible keys: (Tq, topk); a row with fewer visible keys than topk
    fills up with ids of keys it does not see (`selected` drops them)."""
    topk = _indexer(cfg)[2] // (2 if fault == "half_topk" else 1)
    masked = jnp.where(seen, scores, -jnp.inf)
    return lax.top_k(masked, min(topk, scores.shape[1]))[1]


def selected(ids, seen):
    """The dense (Tq, T) mask of the keys kept: scattered from the ids."""
    rows = jnp.arange(ids.shape[0])[:, None]
    return jnp.zeros(seen.shape, bool).at[rows, ids].set(True) & seen


def sparse_attention_block(cfg, q, k, v, qi, ki, w, ids, q0,
                           precision="highest", fault=None):
    """One block of queries (rows q0 ..) against the keys k (a prefix of
    the sequence's that holds every key the block sees): (weighted
    values (Tq, 32, 128), the indexer's loss summed over the rows)."""
    tq, t = q.shape[0], k.shape[0]
    mh, kv, d = _heads(cfg)
    # query head h reads key head h // 8: the heads as (key head, the
    # eight of its group); the planted fault reads h % 4: (eight, key head)
    split, to_s, to_o = ((mh // kv, kv), "qjgd,kgd->gjqk", "gjqk,kgd->qjgd") \
        if fault == "kv_head_mod" else \
        ((kv, mh // kv), "qgjd,kgd->gjqk", "gjqk,kgd->qgjd")
    seen = visible(q0, tq, t, fault)
    kept = selected(ids, seen)
    if precision == "fp8":
        q, k, v = _fp8(q), _fp8(k), _fp8(v)
    s = jnp.einsum(to_s, q.reshape((tq,) + split + (d,)), k,
                   precision=HIGHEST) * d ** -0.5
    p = jax.nn.softmax(jnp.where(kept, s, -jnp.inf), axis=-1)
    out = jnp.einsum(to_o, _fp8(p) if precision == "fp8" else p, v,
                     precision=HIGHEST).reshape(tq, mh, d)
    target = lax.stop_gradient(p[0, 0] if fault == "kl_head0"
                               else jnp.mean(p, axis=(0, 1)))
    scores = index_scores(qi, ki, w, precision, fault)
    log_pi = jax.nn.log_softmax(jnp.where(kept, scores, -jnp.inf), axis=-1)
    kl = jnp.sum(jnp.where(
        kept, target * (jnp.log(jnp.maximum(target, 1e-37)) - log_pi), 0.0))
    return out, kl


def attention(cfg, p, x, precision="highest", fault=None):
    """x (T, hidden), normed -> (Attn (T, hidden), L_I): queries in blocks
    of `Q_BLOCK`, each against the keys up to the end of its quarter of
    the sequence (`KEY_PREFIXES`: the keys behind it are invisible to
    every query of the quarter, so leaving them out changes no number),
    the selection's ids made once and kept (they are not differentiated),
    each block rematerialised in the backward pass."""
    t = x.shape[0]
    blk = min(Q_BLOCK, t)
    assert t % blk == 0
    q, k, v = attention_inputs(cfg, p, x, precision)
    qi, ki, w = indexer_inputs(cfg, p["indexer"], x, precision, fault)
    one = jax.checkpoint(functools.partial(
        sparse_attention_block, cfg, precision=precision, fault=fault))
    parts = math.gcd(t // blk, KEY_PREFIXES)
    outs, kl = [], 0.0
    for lo in range(0, t, t // parts):
        hi = lo + t // parts
        # the planted fault's queries see one key more
        end = min(hi + (blk if fault == "sees_next" else 0), t)
        k_, v_, ki_ = k[:end], v[:end], ki[:end]
        rows = lambda a: a[lo:hi].reshape(((hi - lo) // blk, blk)
                                          + a.shape[1:])
        starts = jnp.arange(lo, hi, blk)

        def pick(args):
            q0, qib, wb = args
            return select_ids(
                cfg, index_scores(qib, ki_, wb, precision, fault),
                visible(q0, blk, end, fault), fault)

        ids = checkpoint_name(lax.stop_gradient(lax.map(
            pick, (starts, rows(lax.stop_gradient(qi)),
                   rows(lax.stop_gradient(w))))), "kept")
        out, kls = lax.map(
            lambda a: one(a[0], k_, v_, a[1], ki_, a[2], a[3], a[4]),
            (rows(q), rows(qi), rows(w), ids, starts))
        outs.append(out.reshape(hi - lo, -1))
        kl = kl + jnp.sum(kls)
    loss = kl / t
    if fault == "no_indexer_loss":
        loss = lax.stop_gradient(loss)
    return _mm(jnp.concatenate(outs), p["Wo"], precision), loss


def _gated(x, wg, wu, wd, precision):
    return _mm(jax.nn.silu(_mm(x, wg, precision)) * _mm(x, wu, precision),
               wd, precision)


def routing(cfg, p, x, precision="highest", fault=None):
    """(experts chosen (N, 8), their weights (N, 8)) for x (N, hidden);
    the logits in float32 whatever the precision of the products."""
    r = jax.nn.softmax(_mm(x, p["Wr"], precision), axis=-1)
    _, idx = lax.top_k(lax.stop_gradient(r), cfg["num_experts_per_tok"])
    kept = jnp.take_along_axis(r, idx, axis=-1)
    if fault != "no_renorm":
        assert cfg["norm_topk_prob"]
        kept = kept / jnp.sum(kept, axis=-1, keepdims=True)
    return idx, kept


def experts(cfg, p, x, precision="highest", fault=None):
    """The held experts' part for x (N, hidden); nothing else: a token
    with no held expert gets exactly zero. Each expert's weighted result
    is rematerialised in the backward pass (sixteen results of 32,768 x
    2,048 float32 held for the weights' gradient would be 4.3 GB)."""
    lo, hi = _held(cfg)
    idx, w = routing(cfg, p, x, precision, fault)

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
    """One decoder layer on h (T, hidden) -> (h, L_I). ``held`` overrides
    the configuration's range of experts (the shares-add-up test)."""
    if held is not None:
        cfg = {**cfg, "experts_held": list(held),
               "num_experts": held[1] - held[0]}
    eps = cfg["rms_norm_eps"]
    a, kl = attention(cfg, p["attn"], _rms(h, p["ln1"]["gamma"], eps),
                      precision, fault)
    h = h + a
    return h + experts(cfg, p["ffn"], _rms(h, p["ln2"]["gamma"], eps),
                       precision, fault), kl


def _cross_entropy(x, head, y, keep, precision):
    """Mean over the kept positions of the cross-entropy of x (N, hidden)
    through the head against y (N,), in blocks of positions."""
    blk = min(LOSS_BLOCK, x.shape[0])

    @jax.checkpoint
    def block(w, xb, yb, kb):
        z = _mm(xb, w, precision)
        return jnp.sum(kb * (jax.nn.logsumexp(z, axis=-1)
                             - jnp.take_along_axis(z, yb[:, None],
                                                   axis=-1)[:, 0]))

    total = 0.0
    for s in range(0, x.shape[0], blk):
        total = total + block(head, x[s:s + blk], y[s:s + blk],
                              keep[s:s + blk])
    return total / jnp.sum(keep)


def hidden(cfg, params, ids, precision="highest", fault=None):
    """ids (T,) -> (the stream after the final norm (T, hidden), L_I of
    each layer (layers,))."""
    x = params["embed"]["W"][jnp.asarray(ids)]
    kls = []
    for i in range(cfg["num_hidden_layers"]):
        # the selection's ids are kept from the forward pass: they are
        # not differentiated, and a sort of T x T scores need not be made
        # a second and a third time
        x, kl = jax.checkpoint(
            functools.partial(layer, cfg, precision=precision, fault=fault),
            policy=jax.checkpoint_policies.save_only_these_names("kept"))(
                params[f"layer{i}"], x)
        kls.append(kl)
    return _rms(x, params["norm"]["gamma"], cfg["rms_norm_eps"]), \
        jnp.stack(kls)


def logits(cfg, params, ids, precision="highest"):
    """(T, vocab) over the held slice (tests' sizes only)."""
    return _mm(hidden(cfg, params, ids, precision)[0], params["head"]["W"],
               precision)


def losses(cfg, params, ids, precision="highest", fault=None):
    """(CE, L_I by layer) of ONE sequence ids (T,) int32, float32."""
    ids = jnp.asarray(ids)
    t = ids.shape[0]
    x, kls = hidden(cfg, params, ids, precision, fault)
    keep = (jnp.arange(t) < t - 1).astype(jnp.float32)
    ce = _cross_entropy(x, params["head"]["W"], jnp.roll(ids, -1), keep,
                        precision)
    return ce, kls


def loss_fn(cfg, params, ids, precision="highest", fault=None):
    """What the step differentiates, and beside it what it reports:
    (CE + INDEXER_LOSS_COEF * sum_layers L_I, (CE, L_I by layer))."""
    ce, kls = losses(cfg, params, ids, precision, fault)
    return ce + INDEXER_LOSS_COEF * jnp.sum(kls), (ce, kls)


def train_steps(cfg, params, batches, precision="highest", devices=None,
                fault=None):
    """Follow AdamW through ``batches`` (the harness's (uint8 rows,
    one-hot) pairs; the one-hot is ignored). Returns (losses, first
    moment, final params), all float32, the trees on the host; the losses
    are the cross-entropies, as ``fit()`` reports them. Weight decay on
    the leaves of two or more dimensions. One chip: ``devices`` is taken
    for the interface's sake. ``fault``: one of `FAULTS`, for the tests of
    the limits only.

    The gradient is taken ONE sequence at a time (every sequence has the
    same number of kept positions and of queries, so the batch's losses
    are the means of the sequences' and the gradient the mean of theirs)
    and the update is applied one top-level entry of the parameters after
    another with AdamW's two moments kept on the HOST in between."""
    lr, b1, b2 = cfg["learning_rate"], cfg["beta1"], cfg["beta2"]
    eps, wd = cfg["epsilon"], cfg["weight_decay"]

    @jax.jit
    def gradient(params, ids):
        (_, (ce, _)), g = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, ids, precision, fault),
            has_aux=True)(params)
        return ce, g

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
        loss, g = 0.0, None
        for seq in ids:             # one sequence of the batch at a time
            l1, g1 = gradient(params, jnp.asarray(seq))
            loss, g = loss + float(l1), g1 if g is None else add(g, g1)
        out.append(loss / len(ids))
        n = jnp.asarray(len(ids), jnp.float32)
        for stage in list(params):
            params[stage], m_new, v_new = update(
                params[stage], g.pop(stage), jax.device_put(m[stage]),
                jax.device_put(v[stage]), jnp.asarray(count, jnp.int32), n)
            m[stage], v[stage] = jax.device_get((m_new, v_new))
    return out, m, jax.device_get(params)
