"""Plain reference for ``glm-4.7-flash``: the decoder layers and the
multi-token-prediction module of Zhipu's GLM-4.7-Flash (config.json,
``model_type`` ``glm4_moe_lite``), both losses, gradients and the AdamW step
in straightforward float32 ``jax.numpy`` at ``highest`` matmul precision:
latent attention under a dense T x T mask one (sequence, head) at a time, a
Python loop over the experts held, no kernel, no dispatch, AdamW written
out, stage by stage, with its moments on the host between steps (which is
what lets it fit the chip). It imports nothing of the program and takes nothing the program made:
weights come from the configuration's ``weights_seed``, batches from the
benchmark's seed; leaves are named as the zoo model's graph names them.

One block (h: T x 2048): ``h += Attn(RMSNorm(h))``; ``h += FFN(RMSNorm(h))``;
RMSNorm ``x / sqrt(mean(x^2) + 1e-5) * gamma``. After the last block a final
RMSNorm, an untied head, mean next-token cross-entropy over the held slice
of the vocabulary.

Attention (every layer; 20 heads), x = the normed stream:
  c_q = RMSNorm(x W_qa) (768); q = c_q W_qb, split 192 (nope) + 64 (rope)
  a head; [c_kv; k_r] = x W_kva (512 + 64); c_kv <- RMSNorm(c_kv);
  [k_nope; v] = c_kv W_kvb (192 + 256 a head);
  q_r, k_r <- RoPE(theta 1e6, all 64 dims, pairs (2j, 2j+1)) at the token's
  position; k = [k_nope; k_r], k_r shared by the heads;
  out = causal softmax(q k^T / sqrt(256)) v W_o
Layer 0's FFN: W_down(SiLU(x W_gate) * (x W_up)) at width 10240.
Experts (layers 1-4 and the MTP block), x = the normed stream after the
attention:
  s = sigmoid(x W_r) over all 64; S = the 4 largest of s + b (b = 0, not
  trained); w_i = s_i / sum_{j in S} s_j * 1.8;
  y = sum_{i in S, i held} w_i W_down,i(SiLU(x W_gate,i) * (x W_up,i))
      + W_down,s(SiLU(x W_gate,s) * (x W_up,s))          the shared expert
The MTP module (DeepSeek-V3, section 2.2), h_i the last block's output
BEFORE the final norm, Emb and the head the main model's own:
  h'_i = [RMSNorm_e(Emb(t_{i+1})); RMSNorm_h(h_i)] W_eh     (4096 -> 2048)
  one more block (the attention and the expert layer above); RMSNorm;
  the head; mean cross-entropy against t_{i+2} (the last two positions of
  a sequence have none).  The step minimises L_main + mtp_loss_weight L_mtp.

Departures from the published model, each also under ``assumed`` in the
configuration's file: ``e_score_correction_bias`` is zero and never
updated; the chip's share is the experts ``experts_held`` of the 64 routed
over and the first ``vocab_size`` ids; what the other chips' experts would
add is left out, here as in the program.

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
FAULTS = ("half_batch", "no_rope", "mtp_unshifted", "no_renorm")


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
    """((next, next-next) token ids, (their 0/1 weights)): position i
    predicts token i + 1 in the main model and token i + 2 in the MTP
    module; the last position of a sequence has no next token, the last
    two no next-next one."""
    ids = np.asarray(ids)
    keep1 = np.ones(ids.shape, np.float32)
    keep1[:, -1:] = 0.0
    keep2 = np.ones(ids.shape, np.float32)
    keep2[:, -2:] = 0.0
    return ((np.roll(ids, -1, axis=1), np.roll(ids, -2, axis=1)),
            (keep1, keep2))


# ----------------------------------------------------------------- shapes
def _held(cfg):
    lo, hi = cfg["experts_held"]
    assert hi - lo == cfg["n_routed_experts"]
    return lo, hi


def _dense_layer(cfg, i: int) -> bool:
    return i < cfg["first_k_dense_replace"]


def _block_shapes(cfg, dense: bool) -> dict:
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    mh, rq, rkv = cfg["num_attention_heads"], cfg["q_lora_rank"], \
        cfg["kv_lora_rank"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    lo, hi = _held(cfg)
    e, sh = hi - lo, cfg["n_shared_experts"] * f
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
            "ln2": {"gamma": (h,)}}


def param_shapes(cfg) -> dict:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    out = {"embed": {"W": (v, h)}}
    for i in range(cfg["num_hidden_layers"]):
        out[f"layer{i}"] = _block_shapes(cfg, _dense_layer(cfg, i))
    out["norm"] = {"gamma": (h,)}
    out["head"] = {"W": (h, v)}
    if cfg["num_nextn_predict_layers"]:
        out["mtp_enorm"] = {"gamma": (h,)}
        out["mtp_hnorm"] = {"gamma": (h,)}
        out["mtp_proj"] = {"W": (2 * h, h)}
        out["mtp_block"] = _block_shapes(cfg, False)
        out["mtp_norm"] = {"gamma": (h,)}
    return out


def stage_of(cfg, leaf: str) -> str:
    """The stage a parameter leaf (by its path,
    ``['layer2']['attn']['Wqa']``) belongs to: ``embed``, ``layer0`` ..
    ``layer4``, ``mtp`` for the module's own leaves, or ``head`` for the
    final norm and the output matrix (which the module shares)."""
    top = leaf.split("'")[1]
    if top.startswith("mtp_"):
        return "mtp"
    return "head" if top in ("norm", "head") else top


_OUT_PROJECTIONS = ("Wo", "Wdown", "Wdown_s")


def make_params(cfg, seed: int = 0):
    """Seeded float32 weights on the device, ALL from the configuration's
    ``weights_seed`` (``seed``, the run's, draws the token ids only: the
    weights decide which experts a token draws, so how many rows the held
    experts multiply, and a run's seed is not to move the amount of work).
    Embedding rows N(0, embedding_std^2); the output projections of
    attention, MLP and experts N(0, out_proj_std^2); every other matrix
    N(0, matrix_std^2); gains 1."""
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
def _attn_layers(cfg) -> int:
    """Latent attentions a step runs: one a layer, one in the module."""
    return cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]


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


def _per_token_macs(cfg) -> float:
    """Multiply-adds a token in the matrix products of the layers run, of
    the MTP module (its merge, its block and the head once more) and of
    the head; the attentions' own token-mixing is counted apart."""
    h = cfg["hidden_size"]
    macs = 0.0
    for i in range(cfg["num_hidden_layers"]):
        macs += _attn_proj_macs(cfg)
        macs += 3 * h * cfg["intermediate_size"] if _dense_layer(cfg, i) \
            else _experts_macs(cfg)
    macs += h * cfg["vocab_size"]
    if cfg["num_nextn_predict_layers"]:
        macs += 2 * h * h + _attn_proj_macs(cfg) + _experts_macs(cfg) \
            + h * cfg["vocab_size"]
    return macs


def _causal_pairs(t):
    return t * (t + 1) / 2.0


def train_flops_per_example(cfg) -> float:
    """Model FLOPs of one sequence in a training step for the share held
    here: 2 per multiply-add forward and twice that again backward, in the
    projections, the latent attentions' scores and weighted values inside
    the causal mask, the router, the held experts' three products for the
    rows they are EXPECTED to draw, the shared expert, the dense MLP, the
    MTP module (merge, block, the head a second time) and the head.
    Recomputation, norms, softmax, rotation, the embedding gathers and the
    optimizer are left out, as MFU's convention has it."""
    t = seq_length(cfg)
    macs = _per_token_macs(cfg) * t
    macs += _attn_layers(cfg) * _causal_pairs(t) \
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
    """The least time the latent attentions of all the layers run and of
    the MTP block can take in a training step of ``batch`` sequences (the
    scope ``mla/attn``: from expanded q, k, v to the weighted values):
    scores and weighted values INSIDE the causal mask, two products
    forward and four backward (a block computed and then masked is a
    loss), against reading q, k, v and the output and their gradients
    once, bf16."""
    t, mh = seq_length(cfg), cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    layers = _attn_layers(cfg)
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


def rotate(x, theta):
    """RoPE on x (B, T, H, D) at positions 0 .. T-1 over all D dims: the
    pair (x[2j], x[2j+1]) turned by the angle ``t * theta^(-2j/D)``."""
    t, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv      # (T, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def attention_inputs(cfg, p, x, precision="highest", fault=None):
    """x (B, T, hidden), normed -> q, k (B, T, H, 256), v (B, T, H, 256)
    of the softmax attention."""
    b, t, _ = x.shape
    mh, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    c_q = _rms(_mm(x, p["Wqa"], precision), p["q_norm"], eps)
    q = _mm(c_q, p["Wqb"], precision).reshape(b, t, mh, nope + rope)
    ckr = _mm(x, p["Wkva"], precision)
    c = _rms(ckr[..., :rank], p["kv_norm"], eps)
    kv = _mm(c, p["Wkvb"], precision).reshape(b, t, mh, nope + dv)
    q_r, k_r = q[..., nope:], ckr[:, :, None, rank:]
    if fault != "no_rope":
        q_r, k_r = rotate(q_r, theta), rotate(k_r, theta)
    q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_r, (b, t, mh, rope))], axis=-1)
    return q, k, kv[..., nope:]


def _attention(cfg, p, x, precision, fault=None):
    """x (B, T, hidden), normed -> (B, T, hidden). One (sequence, head) at
    a time under a dense T x T mask."""
    b, t, _ = x.shape
    mh, dv = cfg["num_attention_heads"], cfg["v_head_dim"]
    q, k, v = attention_inputs(cfg, p, x, precision, fault)
    scale = q.shape[-1] ** -0.5
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


def routing(cfg, p, x, precision="highest", fault=None):
    """(experts chosen (N, 4), their weights (N, 4)) for x (N, hidden);
    the scores in float32 whatever the precision of the products."""
    s = jax.nn.sigmoid(_mm(x, p["Wr"], precision))
    _, idx = lax.top_k(lax.stop_gradient(s), cfg["num_experts_per_tok"])
    kept = jnp.take_along_axis(s, idx, axis=-1)
    if fault != "no_renorm":
        kept = kept / jnp.sum(kept, axis=-1, keepdims=True)
    return idx, kept * cfg["routed_scaling_factor"]


def _experts(cfg, p, x, precision, fault=None, shared=True):
    """The held experts' part for x (N, hidden), plus the shared expert."""
    lo, hi = _held(cfg)
    idx, w = routing(cfg, p, x, precision, fault)
    expert = jax.checkpoint(functools.partial(_gated, precision=precision))
    y = jnp.zeros_like(x)
    for e in range(lo, hi):         # a plain loop over the experts held
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        y = y + w_e[:, None] * expert(x, p["Wgate"][e - lo],
                                      p["Wup"][e - lo], p["Wdown"][e - lo])
    if shared:
        y = y + expert(x, p["Wgate_s"], p["Wup_s"], p["Wdown_s"])
    return y


def layer(cfg, p, h, dense, precision="highest", held=None, fault=None,
          shared=True):
    """One decoder layer on h (B, T, hidden); ``dense``: the SwiGLU MLP in
    place of the experts. ``held`` overrides the configuration's range of
    experts and ``shared`` leaves the shared expert out (the
    shares-add-up test)."""
    if held is not None:
        cfg = {**cfg, "experts_held": list(held),
               "n_routed_experts": held[1] - held[0]}
    eps = cfg["rms_norm_eps"]
    h = h + _attention(cfg, p["attn"],
                       _rms(h, p["ln1"]["gamma"], eps), precision, fault)
    x = _rms(h, p["ln2"]["gamma"], eps)
    b, t, f = x.shape
    if dense:
        return h + _gated(x, p["ffn"]["Wgate"], p["ffn"]["Wup"],
                          p["ffn"]["Wdown"], precision)
    return h + _experts(cfg, p["ffn"], x.reshape(b * t, f), precision,
                        fault, shared).reshape(b, t, f)


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


def losses(cfg, params, ids, precision="highest", fault=None):
    """(L_main, L_mtp) of ids (B, T) int32, float32 each; L_mtp is 0
    without the module."""
    ids = jnp.asarray(ids)
    b, t = ids.shape
    eps = cfg["rms_norm_eps"]
    block = lambda i_dense: jax.checkpoint(functools.partial(
        layer, cfg, dense=i_dense, precision=precision, fault=fault))
    x = params["embed"]["W"][ids]
    for i in range(cfg["num_hidden_layers"]):
        x = block(_dense_layer(cfg, i))(params[f"layer{i}"], x)
    w_out = params["head"]["W"]
    flat = lambda a: a.reshape(b * t, -1)
    at = jnp.arange(t)[None, :]
    keep1 = jnp.broadcast_to(at < t - 1, (b, t)).astype(jnp.float32)
    main = _cross_entropy(
        flat(_rms(x, params["norm"]["gamma"], eps)), w_out,
        jnp.roll(ids, -1, axis=1).reshape(-1), keep1.reshape(-1), precision)
    if not cfg["num_nextn_predict_layers"]:
        return main, jnp.zeros((), jnp.float32)
    # the token after the one the trunk saw; a sequence's last position
    # has none (id 0 there: it reaches no kept position, the mask is causal)
    ahead = ids if fault == "mtp_unshifted" else jnp.where(
        at < t - 1, jnp.roll(ids, -1, axis=1), 0)
    merged = jnp.concatenate(
        [_rms(params["embed"]["W"][ahead], params["mtp_enorm"]["gamma"], eps),
         _rms(x, params["mtp_hnorm"]["gamma"], eps)], axis=-1)
    y = block(False)(params["mtp_block"],
                     _mm(merged, params["mtp_proj"]["W"], precision))
    keep2 = jnp.broadcast_to(at < t - 2, (b, t)).astype(jnp.float32)
    mtp = _cross_entropy(
        flat(_rms(y, params["mtp_norm"]["gamma"], eps)), w_out,
        jnp.roll(ids, -2, axis=1).reshape(-1), keep2.reshape(-1), precision)
    return main, mtp


def loss_fn(cfg, params, ids, precision="highest", fault=None):
    """What a step minimises: ``L_main + mtp_loss_weight * L_mtp``."""
    main, mtp = losses(cfg, params, ids, precision, fault)
    return main + cfg["mtp_loss_weight"] * mtp


def train_steps(cfg, params, batches, precision="highest", devices=None,
                fault=None):
    """Follow AdamW through ``batches`` (the harness's (uint8 rows,
    one-hot) pairs; the one-hot is ignored). Returns (losses, first
    moment, final params), all float32, the trees on the host. Weight
    decay on the leaves of two or more dimensions. One chip: ``devices``
    is taken for the interface's sake. ``fault``: one of `FAULTS`, for the
    tests of the limits only.

    The gradient is one program and the update another, applied one
    top-level entry of the parameters (a layer, the embedding, ...) after
    another with AdamW's two moments kept on the HOST in between: 706 M
    parameters with their gradient, both moments AND a layer's float32
    temporaries do not fit one chip's 16 GB together."""
    lr, b1, b2 = cfg["learning_rate"], cfg["beta1"], cfg["beta2"]
    eps, wd = cfg["epsilon"], cfg["weight_decay"]

    @jax.jit
    def gradient(params, ids):
        return jax.value_and_grad(
            lambda p: loss_fn(cfg, p, ids, precision, fault))(params)

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def update(params, g, m, v, count):
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
        loss, g = gradient(params, jnp.asarray(ids))
        out.append(float(loss))
        for stage in list(params):
            params[stage], m_new, v_new = update(
                params[stage], g.pop(stage), jax.device_put(m[stage]),
                jax.device_put(v[stage]), jnp.asarray(count, jnp.int32))
            m[stage], v[stage] = jax.device_get((m_new, v_new))
    return out, m, jax.device_get(params)
