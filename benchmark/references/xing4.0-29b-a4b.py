"""Plain reference for ``xing4.0-29b-a4b``: the decoder layers of XingChen's
Xing4.0-29B-A4B (config.json, ``model_type`` ``xing4_0``), the loss, its
gradients and the AdamW step in straightforward float32 ``jax.numpy`` at
``highest`` matmul precision: a residual path of ``hc_mult`` = 4 streams
whose three mappings are made one token at a time with a loop of
``hc_sinkhorn_iters`` = 20 Sinkhorn steps, YaRN-rotated latent attention
under a dense T x T mask one (sequence, head) at a time, one held expert
after another, no kernel, no dispatch, AdamW written out, stage by stage,
with its moments on the host between steps (which is what lets it fit the
chip). It imports nothing of the program and takes nothing the program
made: weights come from the configuration's ``weights_seed``, batches from
the benchmark's seed; leaves are named as the zoo model's graph names them.

The stream is X (T x 4 x 3584). In: ``X[t, j] = Emb(id_t)`` for each of the
4 streams. One layer is two sub-layers, the attention and then the MLP or
the expert layer, each with mapping parameters of its OWN (``phi`` 14336 x
24, ``bias`` 24, ``alpha`` 3; the 24 columns lie [pre 4 | post 4 | res 16,
row-major]). A sub-layer F, for one token's X (4 x 3584):
  v = vec(X) (14336: stream 0 first); r = sqrt(mean(v^2) + 1e-6)
  [p_pre, p_post, p_res] = (v / r) phi                       (no gain)
  H_pre  = sigmoid(a_pre p_pre + b_pre)                      (4)
  H_post = 2 sigmoid(a_post p_post + b_post)                 (4)
  M = exp(clip(a_res mat(p_res) + b_res, -30, 30))           (4 x 4)
  20 times: M <- M / (column sums + 1e-6); M <- M / (row sums + 1e-6)
  H_res = M                                  (all but doubly stochastic)
  u = sum_j H_pre[j] X[j];  y = F(RMSNorm(u))      (F's own pre-norm gain)
  X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y
Out: ``h = sum_j X[j]``, a final RMSNorm, an untied head, mean next-token
cross-entropy over the held slice of the vocabulary. RMSNorm is ``x /
sqrt(mean(x^2) + 1e-6) * gamma``.

Attention (every layer; the heads HELD, 4 of the 32), x the normed u:
  c_q = RMSNorm(x W_qa) (768); q = c_q W_qb, split 128 (nope) + 64 (rope)
  a head; [c_kv; k_r] = x W_kva (512 + 64); c_kv <- RMSNorm(c_kv);
  [k_nope; v] = c_kv W_kvb (128 + 128 a head);
  q_r, k_r <- rotated at the token's position, pairs (2j, 2j+1), by YaRN's
  blended frequencies (`yarn`): f_j = theta^(-2j/64), low = floor(dim(32))
  = 10, high = ceil(dim(1)) = 23 with dim(b) = 64 ln(4096 / (2 pi b)) / (2
  ln theta), ramp_j = clip((j - low) / (high - low), 0, 1), the frequency
  used f_j / 64 * ramp_j + f_j (1 - ramp_j); cos and sin times
  mscale(64, 1) / mscale(64, 1) = 1; k = [k_nope; k_r], k_r shared by the
  heads; out = causal softmax(q k^T * 192^-1/2 * m^2) v W_o with m = 0.1
  ln 64 + 1 (mscale_all_dim 1)
The first layer run (published layer 1, the second of the two leading dense
layers) has the MLP W_down(SiLU(x W_gate) * (x W_up)) at width 9216.
Experts (the four layers behind it, published 2-5), x the normed u of the second sub-layer:
  s = sigmoid(x W_r) over all 64; S = the 4 largest of s + b (b = 0, not
  trained); w_i = s_i / sum_{j in S} s_j * 2;
  y = sum_{i in S, i held} w_i W_down,i(SiLU(x W_gate,i) * (x W_up,i))
      + W_down,s(SiLU(x W_gate,s) * (x W_up,s))          the shared expert

Departures from the published model, each also under ``assumed`` in the
configuration's file: the model's own modelling code is not in the
repository, so the ends (replicate in, sum out), the place of ``hc_eps``
and of the clamp, the RMS without a gain and YaRN's layout are the cited
papers' and the DeepSeek family's; ``e_score_correction_bias`` is zero and
never updated; the multi-token-prediction module is not run; the chip's
share is the experts ``experts_held`` of the 64 routed over, the 4 heads
held and the first ``vocab_size`` ids; what the other chips' experts and
heads would add is left out, here as in the program.

``precision="fp8"`` is the control, not a reference: the same mathematics
with the operands of every matrix product (the 24-column one among them)
rounded to float8 (e4m3, one scale a tensor), the step below the bf16 the
configuration states.
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
#: positions whose logits the loss holds at once
LOSS_BLOCK = 2048
#: the faults `train_steps` can plant; the cell's limits have to catch each
#: (benchmark/tools/plant_faults.py)
#: (the streams AVERAGED at the way out instead of summed is no fault that
#: anything can see: the final RMSNorm divides the factor of 4 out again,
#: to ``rms_norm_eps``; `hidden`'s ``streams_averaged`` is kept for the test
#: that shows it, and the way out's fault is ``out_first_stream``)
FAULTS = ("half_batch", "static_mappings", "no_sinkhorn", "one_iteration",
          "rows_first", "post_without_2", "out_first_stream",
          "no_yarn_temperature", "plain_frequencies")


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
    """(next token ids, their 0/1 weights): position i predicts token
    i + 1; the last position of a sequence has no next token."""
    ids = np.asarray(ids)
    keep = np.ones(ids.shape, np.float32)
    keep[:, -1:] = 0.0
    return np.roll(ids, -1, axis=1), keep


# ----------------------------------------------------------------- shapes
def _held(cfg):
    lo, hi = cfg["experts_held"]
    assert hi - lo == cfg["n_routed_experts"]
    return lo, hi


def _dense_layer(cfg, i: int) -> bool:
    """Whether the i-th layer RUN (published layer ``first_layer + i``) is
    one of the leading dense ones."""
    return cfg["first_layer"] + i < cfg["first_k_dense_replace"]


def dense_layers(cfg) -> int:
    """Leading dense layers among the layers run."""
    return sum(_dense_layer(cfg, i) for i in range(cfg["num_hidden_layers"]))


def _mapping_columns(cfg) -> int:
    n = cfg["hc_mult"]
    return n + n + n * n


def _block_shapes(cfg, dense: bool) -> dict:
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    mh, rq, rkv = cfg["num_attention_heads"], cfg["q_lora_rank"], \
        cfg["kv_lora_rank"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    lo, hi = _held(cfg)
    e, sh = hi - lo, cfg["n_shared_experts"] * f
    cols = _mapping_columns(cfg)
    hc = {"phi": (cfg["hc_mult"] * h, cols), "bias": (cols,), "alpha": (3,)}
    attn = {"Wqa": (h, rq), "q_norm": (rq,), "Wqb": (rq, mh * (nope + rope)),
            "Wkva": (h, rkv + rope), "kv_norm": (rkv,),
            "Wkvb": (rkv, mh * (nope + dv)), "Wo": (mh * dv, h)}
    if dense:
        w = cfg["intermediate_size"]
        ffn = {"Wgate": (h, w), "Wup": (h, w), "Wdown": (w, h)}
    else:
        ffn = {"Wr": (h, cfg["router_experts"]), "Wgate": (e, h, f),
               "Wup": (e, h, f), "Wdown": (e, f, h), "Wgate_s": (h, sh),
               "Wup_s": (h, sh), "Wdown_s": (sh, h)}
    return {"attn": attn, "ffn": ffn, "ln1": {"gamma": (h,)},
            "ln2": {"gamma": (h,)}, "hc_attn": dict(hc), "hc_ffn": dict(hc)}


def param_shapes(cfg) -> dict:
    assert cfg["num_nextn_predict_layers"] == 0    # the module is not run
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    out = {"embed": {"W": (v, h)}}
    for i in range(cfg["num_hidden_layers"]):
        out[f"layer{i}"] = _block_shapes(cfg, _dense_layer(cfg, i))
    out["norm"] = {"gamma": (h,)}
    out["head"] = {"W": (h, v)}
    return out


def parameters(cfg) -> int:
    """Trained parameters at the configuration's sizes."""
    return sum(math.prod(s) for s in jax.tree_util.tree_leaves(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))


def stage_of(cfg, leaf: str) -> str:
    """The stage a parameter leaf (by its path,
    ``['layer2']['attn']['Wqa']``) belongs to: ``embed``, ``layer0`` ..
    ``layer4``, or ``head`` for the final norm and the output matrix."""
    top = leaf.split("'")[1]
    return "head" if top in ("norm", "head") else top


_OUT_PROJECTIONS = ("Wo", "Wdown", "Wdown_s")


def make_params(cfg, seed: int = 0):
    """Seeded float32 weights on the device, ALL from the configuration's
    ``weights_seed`` (``seed``, the run's, draws the token ids only: the
    weights decide which experts a token draws, so how many rows the held
    experts multiply, and a run's seed is not to move the amount of work).
    Embedding rows N(0, embedding_std^2); the matrices that write into the
    stream (attention, MLP, experts) N(0, out_proj_std^2); every other
    matrix N(0, matrix_std^2); gains 1. The mappings: ``phi`` N(0,
    hc_phi_std^2), ``alpha`` the three scalars ``hc_alpha``, ``bias`` zero
    but for the diagonal of its 4 x 4 part, ``hc_res_diagonal``."""
    root = jax.random.PRNGKey(int(cfg["weights_seed"]))
    flat, tree = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    n = cfg["hc_mult"]
    out = []
    for i, (path, shape) in enumerate(flat):
        name = path[-1].key
        if name == "alpha":
            out.append(jnp.asarray(cfg["hc_alpha"], jnp.float32))
        elif name == "bias":
            out.append(jnp.concatenate([
                jnp.zeros((2 * n,), jnp.float32),
                (float(cfg["hc_res_diagonal"])
                 * jnp.eye(n, dtype=jnp.float32)).reshape(-1)]))
        elif len(shape) == 1:
            out.append(jnp.ones(shape, jnp.float32))
        else:
            std = cfg["hc_phi_std"] if name == "phi" else (
                cfg["embedding_std"] if path[0].key == "embed" else (
                    cfg["out_proj_std"] if name in _OUT_PROJECTIONS
                    else cfg["matrix_std"]))
            out.append(_normal(jax.random.fold_in(root, i), shape,
                               float(std)))
    return jax.tree_util.tree_unflatten(tree, out)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _normal(key, shape, std):
    return std * jax.random.normal(key, shape, jnp.float32)


# ------------------------------------------------------------------ counts
def _attn_proj_macs(cfg) -> float:
    h, mh = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    return float(h * rq + rq * mh * (nope + rope) + h * (rkv + rope)
                 + rkv * mh * (nope + dv) + mh * dv * h)


def _experts_macs(cfg) -> float:
    """Router, shared expert and the held experts' EXPECTED rows."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    lo, hi = _held(cfg)
    return float(h * cfg["router_experts"]
                 + 3 * h * f * cfg["n_shared_experts"]
                 + 3 * h * f * cfg["num_experts_per_tok"] * (hi - lo)
                 / cfg["router_experts"])


def _mapping_macs(cfg) -> float:
    """One sub-layer's residual mappings: the 24-column product and the
    weighted sums over the streams (u, H_res X, H_post y)."""
    n, h = cfg["hc_mult"], cfg["hidden_size"]
    return float(n * h * _mapping_columns(cfg) + (n + n * n + n) * h)


def _per_token_macs(cfg) -> float:
    """Multiply-adds a token in the matrix products of the layers run and
    of the head and in the two residual mappings of every layer; the
    attentions' own token-mixing is counted apart."""
    h = cfg["hidden_size"]
    macs = 0.0
    for i in range(cfg["num_hidden_layers"]):
        macs += _attn_proj_macs(cfg) + 2 * _mapping_macs(cfg)
        macs += 3 * h * cfg["intermediate_size"] if _dense_layer(cfg, i) \
            else _experts_macs(cfg)
    return macs + h * cfg["vocab_size"]


def _causal_pairs(t):
    return t * (t + 1) / 2.0


def train_flops_per_example(cfg) -> float:
    """Model FLOPs of one sequence in a training step for the share held
    here: 2 per multiply-add forward and twice that again backward, in the
    projections, the latent attentions' scores and weighted values inside
    the causal mask, the router, the held experts' three products for the
    rows they are EXPECTED to draw, the shared expert, the dense MLP, the
    residual mappings (the 24-column product and the sums over the
    streams: 430,080 multiply-adds a sub-layer, 1.6 % of a token's) and
    the head. Recomputation, norms, softmax, rotation, the Sinkhorn steps,
    the embedding gather and the optimizer are left out, as MFU's
    convention has it."""
    t = seq_length(cfg)
    macs = _per_token_macs(cfg) * t
    macs += cfg["num_hidden_layers"] * _causal_pairs(t) \
        * cfg["num_attention_heads"] * (
            cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
            + cfg["v_head_dim"])
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


def mla_attn_min_seconds(cfg, peaks, batch: int) -> dict:
    """The least time the latent attentions of all the layers run can take
    in a training step of ``batch`` sequences (the scope ``mla/attn``:
    from expanded q, k, v to the weighted values): scores and weighted
    values INSIDE the causal mask, two products forward and four backward
    (a block computed and then masked is a loss), against reading q, k, v
    and the output and their gradients once, bf16."""
    t, mh = seq_length(cfg), cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    layers = cfg["num_hidden_layers"]
    tf = layers * batch * 3 * 2.0 * _causal_pairs(t) * mh * (qk + dv) \
        / peaks["flops_bf16"]
    tb = layers * batch * 2 * t * mh * (2 * qk + 2 * dv) * 2 \
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


def yarn(cfg, fault=None):
    """(the ``rope_dim / 2`` frequencies the rotation uses, the factor on
    cos and sin, the factor on the softmax scale) from the configuration's
    ``rope_theta`` and ``rope_scaling`` (type ``yarn``), as the DeepSeek
    family's code has them."""
    d, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    rs = cfg["rope_scaling"]
    assert rs["type"] == "yarn"
    plain = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    where = lambda turns: d * math.log(
        rs["original_max_position_embeddings"] / (turns * 2 * math.pi)) \
        / (2 * math.log(theta))
    low = max(math.floor(where(rs["beta_fast"])), 0)
    high = min(math.ceil(where(rs["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / max(high - low, 0.001), 0.0, 1.0)
    blended = plain / rs["factor"] * ramp + plain * (1.0 - ramp)
    mscale = lambda m: 0.1 * m * math.log(rs["factor"]) + 1.0 \
        if rs["factor"] > 1 else 1.0
    freqs = plain if fault == "plain_frequencies" else blended
    temperature = 1.0 if fault == "no_yarn_temperature" \
        else mscale(rs["mscale_all_dim"]) ** 2
    return (jnp.asarray(freqs, jnp.float32),
            mscale(rs["mscale"]) / mscale(rs["mscale_all_dim"]), temperature)


def rotate(x, freqs, amplitude=1.0):
    """x (B, T, H, D) at positions 0 .. T-1: the pair (x[2j], x[2j+1])
    turned by the angle ``t * freqs[j]``, cos and sin times
    ``amplitude``."""
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos = amplitude * jnp.cos(ang)[:, None, :]
    sin = amplitude * jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def attention_inputs(cfg, p, x, precision="highest", fault=None):
    """x (B, T, hidden), normed -> q, k (B, T, H, 192), v (B, T, H, 128)
    of the softmax attention, and its scale."""
    b, t, _ = x.shape
    mh, rank = p["Wo"].shape[0] // cfg["v_head_dim"], cfg["kv_lora_rank"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    eps = cfg["rms_norm_eps"]
    freqs, amplitude, temperature = yarn(cfg, fault)
    c_q = _rms(_mm(x, p["Wqa"], precision), p["q_norm"], eps)
    q = _mm(c_q, p["Wqb"], precision).reshape(b, t, mh, nope + rope)
    ckr = _mm(x, p["Wkva"], precision)
    c = _rms(ckr[..., :rank], p["kv_norm"], eps)
    kv = _mm(c, p["Wkvb"], precision).reshape(b, t, mh, nope + dv)
    q_r = rotate(q[..., nope:], freqs, amplitude)
    k_r = rotate(ckr[:, :, None, rank:], freqs, amplitude)
    q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_r, (b, t, mh, rope))], axis=-1)
    return q, k, kv[..., nope:], (nope + rope) ** -0.5 * temperature


def attention(cfg, p, x, precision="highest", fault=None):
    """x (B, T, hidden), normed -> (B, T, hidden). One (sequence, head) at
    a time under a dense T x T mask; as many heads as ``p`` holds."""
    b, t, _ = x.shape
    q, k, v, scale = attention_inputs(cfg, p, x, precision, fault)
    mh, dv = q.shape[2], v.shape[-1]
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    rows = lambda a: a.transpose(0, 2, 1, 3).reshape(b * mh, t, -1)

    @jax.checkpoint
    def one(q1, k1, v1):
        s = _mm(q1, k1.T, precision) * scale
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _mm(w, v1, precision)

    out = lax.map(lambda a: one(*a), (rows(q), rows(k), rows(v)))
    out = out.reshape(b, mh, t, dv).transpose(0, 2, 1, 3)
    return _mm(out.reshape(b, t, mh * dv), p["Wo"], precision)


def _gated(x, wg, wu, wd, precision):
    return _mm(jax.nn.silu(_mm(x, wg, precision)) * _mm(x, wu, precision),
               wd, precision)


def routing(cfg, p, x, precision="highest"):
    """(experts chosen (N, 4), their weights (N, 4)) for x (N, hidden);
    the scores in float32 whatever the precision of the products."""
    s = jax.nn.sigmoid(_mm(x, p["Wr"], precision))
    _, idx = lax.top_k(lax.stop_gradient(s), cfg["num_experts_per_tok"])
    kept = jnp.take_along_axis(s, idx, axis=-1)
    kept = kept / jnp.sum(kept, axis=-1, keepdims=True)
    return idx, kept * cfg["routed_scaling_factor"]


def experts(cfg, p, x, precision="highest", shared=True):
    """The held experts' part for x (N, hidden), plus the shared expert."""
    lo, hi = _held(cfg)
    idx, w = routing(cfg, p, x, precision)
    expert = jax.checkpoint(functools.partial(_gated, precision=precision))

    def add_one(y, held):           # one expert after another
        e, wg, wu, wd = held
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        return y + w_e[:, None] * expert(x, wg, wu, wd), None

    y, _ = lax.scan(add_one, jnp.zeros_like(x),
                    (jnp.arange(lo, hi), p["Wgate"], p["Wup"], p["Wdown"]))
    if shared:
        y = y + expert(x, p["Wgate_s"], p["Wup_s"], p["Wdown_s"])
    return y


def sinkhorn(cfg, m, fault=None):
    """``hc_sinkhorn_iters`` times: m (4 x 4, positive) over its column
    sums, then over its row sums, each sum plus ``hc_eps``."""
    eps = cfg["hc_eps"]
    over_columns = lambda m: m / (jnp.sum(m, axis=0, keepdims=True) + eps)
    over_rows = lambda m: m / (jnp.sum(m, axis=1, keepdims=True) + eps)
    first, then = (over_rows, over_columns) if fault == "rows_first" \
        else (over_columns, over_rows)
    iters = {"no_sinkhorn": 0, "one_iteration": 1}.get(
        fault, cfg["hc_sinkhorn_iters"])
    # a loop of `iters` steps (rolled: unrolled, twenty steps of ten
    # sub-layers three times over are most of the program's compile)
    return lax.fori_loop(0, iters, lambda _, m: then(first(m)), m)


def mappings(cfg, hc, x, precision="highest", fault=None):
    """ONE token's three mappings from its streams x (4, hidden):
    ``(H_pre (4), H_post (4), H_res (4, 4))``."""
    n = cfg["hc_mult"]
    v = x.reshape(-1)
    p = _mm(v * lax.rsqrt(jnp.mean(v * v) + cfg["rms_norm_eps"]),
            hc["phi"], precision)
    if fault == "static_mappings":
        p = jnp.zeros_like(p)
    a_pre, a_post, a_res = hc["alpha"]
    z = a_res * p[2 * n:].reshape(n, n) + hc["bias"][2 * n:].reshape(n, n)
    z = jnp.clip(z, cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"])
    post = 1.0 if fault == "post_without_2" else 2.0
    return (jax.nn.sigmoid(a_pre * p[:n] + hc["bias"][:n]),
            post * jax.nn.sigmoid(a_post * p[n:2 * n] + hc["bias"][n:2 * n]),
            sinkhorn(cfg, jnp.exp(z), fault))


def sub_layer(cfg, hc, gamma, f, x, precision="highest", fault=None):
    """One sub-layer ``f`` ((B, T, hidden) -> (B, T, hidden)) on the
    streams x (B, T, 4, hidden), its mappings made token by token."""
    of_token = functools.partial(mappings, cfg, hc, precision=precision,
                                 fault=fault)
    h_pre, h_post, h_res = jax.vmap(jax.vmap(of_token))(x)
    u = jnp.einsum("btj,btjc->btc", h_pre, x, precision=HIGHEST)
    y = f(_rms(u, gamma, cfg["rms_norm_eps"]))
    return jnp.einsum("btij,btjc->btic", h_res, x, precision=HIGHEST) \
        + h_post[..., None] * y[:, :, None, :]


def layer(cfg, p, x, dense, precision="highest", held=None, fault=None,
          shared=True):
    """One decoder layer on the streams x (B, T, 4, hidden); ``dense``:
    the SwiGLU MLP in place of the experts. ``held`` overrides the
    configuration's range of experts and ``shared`` leaves the shared
    expert out (the shares-add-up test)."""
    if held is not None:
        cfg = {**cfg, "experts_held": list(held),
               "n_routed_experts": held[1] - held[0]}
    x = sub_layer(cfg, p["hc_attn"], p["ln1"]["gamma"],
                  lambda h: attention(cfg, p["attn"], h, precision, fault),
                  x, precision, fault)

    def ffn(h):
        b, t, f = h.shape
        if dense:
            return _gated(h, p["ffn"]["Wgate"], p["ffn"]["Wup"],
                          p["ffn"]["Wdown"], precision)
        return experts(cfg, p["ffn"], h.reshape(b * t, f), precision,
                       shared).reshape(b, t, f)

    return sub_layer(cfg, p["hc_ffn"], p["ln2"]["gamma"], ffn, x, precision,
                     fault)


def _cross_entropy(x, w_out, y, keep, precision):
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
        total = total + block(w_out, x[s:s + blk], y[s:s + blk],
                              keep[s:s + blk])
    return total / jnp.sum(keep)


def hidden(cfg, params, ids, precision="highest", fault=None):
    """ids (B, T) int32 -> the final norm's output (B, T, hidden)."""
    n = cfg["hc_mult"]
    block = lambda i_dense: jax.checkpoint(functools.partial(
        layer, cfg, dense=i_dense, precision=precision, fault=fault))
    e = params["embed"]["W"][jnp.asarray(ids)]
    x = jnp.broadcast_to(e[:, :, None, :], e.shape[:2] + (n, e.shape[-1]))
    for i in range(cfg["num_hidden_layers"]):
        x = block(_dense_layer(cfg, i))(params[f"layer{i}"], x)
    h = {"streams_averaged": jnp.mean(x, axis=2),
         "out_first_stream": x[:, :, 0]}.get(fault, jnp.sum(x, axis=2))
    return _rms(h, params["norm"]["gamma"], cfg["rms_norm_eps"])


def logits(cfg, params, ids, precision="highest"):
    return _mm(hidden(cfg, params, ids, precision), params["head"]["W"],
               precision)


def loss_fn(cfg, params, ids, precision="highest", fault=None):
    """What a step minimises: the mean next-token cross-entropy of ids
    (B, T) int32 over the kept positions, float32."""
    ids = jnp.asarray(ids)
    b, t = ids.shape
    x = hidden(cfg, params, ids, precision, fault)
    keep = jnp.broadcast_to(jnp.arange(t)[None, :] < t - 1,
                            (b, t)).astype(jnp.float32)
    return _cross_entropy(x.reshape(b * t, -1), params["head"]["W"],
                          jnp.roll(ids, -1, axis=1).reshape(-1),
                          keep.reshape(-1), precision)


def train_steps(cfg, params, batches, precision="highest", devices=None,
                fault=None):
    """Follow AdamW through ``batches`` (the harness's (uint8 rows,
    one-hot) pairs; the one-hot is ignored). Returns (losses, first
    moment, final params), all float32, the trees on the host. Weight
    decay on the leaves of two or more dimensions (matrices, expert
    stacks, ``phi``), none on gains nor on the mappings' ``bias`` and
    ``alpha``. One chip: ``devices`` is taken for the interface's sake.
    ``fault``: one of `FAULTS`, for the tests of the limits only.

    The gradient is taken ONE sequence at a time (every sequence has the
    same number of kept positions, so the batch's loss is the mean of the
    sequences' and its gradient the mean of theirs) and the update is
    applied one top-level entry of the parameters after another with
    AdamW's two moments kept on the HOST in between: 656 M parameters with
    their gradient, both moments AND a layer's float32 temporaries (a
    sequence's four streams are 470 MB a copy) do not fit one chip's 16
    GB together."""
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
            # half of a batch of one sequence: the sequence's first half
            ids = ids[:len(ids) // 2] if len(ids) > 1 \
                else ids[:, :ids.shape[1] // 2]
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
