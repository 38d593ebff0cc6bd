"""Plain reference for ``kimi-linear-48b-a3b``: the decoder layers of
Moonshot's Kimi-Linear-48B-A3B-Instruct (config.json), the next-token loss,
gradients and AdamW step in straightforward float32 ``jax.numpy`` at
``highest`` matmul precision: the KDA recurrence token by token, latent
attention under a dense T x T mask, a Python loop over the experts held, no
kernel, no chunk algebra, no dispatch, AdamW written out. It imports nothing
of the program and takes nothing the program made: weights come from the
configuration's ``weights_seed``, batches from the benchmark's seed; leaves
are named as the zoo model names them.

One block (h: T x 2304): ``h += Attn(RMSNorm(h))``; ``h += FFN(RMSNorm(h))``;
RMSNorm ``x / sqrt(mean(x^2) + 1e-5) * gamma``. After the last block a final
RMSNorm, an untied head, mean next-token cross-entropy over the held slice
of the vocabulary.

KDA (layers 1, 2, 3, 5; 32 heads, d_k = d_v = 128), x = the normed stream:
  q, k, v = SiLU(conv4(x W_{q,k,v}))     causal depth-wise convolution,
                                         y_t = sum_j taps[j] x_{t-3+j}
  q, k <- x / sqrt(sum(x^2) + 1e-6) per head; q <- q * 128^-1/2
  log a_t = -exp(A_log) * softplus((x W_a_down) W_a_up + dt_bias)
                                         per channel; A_log per head
  b_t = sigmoid(x W_b)                   per head
  S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T;  o_t = S_t^T q_t
  out = (RMSNorm_head(o_t) * sigmoid((x W_g_down) W_g_up)) W_o
MLA (layer 4; 32 heads), no rotation (``mla_use_nope``):
  q = x W_q split 128 + 64 a head; [c; k_r] = x W_kva (512 + 64);
  c <- RMSNorm(c); [k_nope; v] = c W_kvb (128 + 128 a head);
  k = [k_nope; k_r], k_r shared by the heads;
  out = causal softmax(q k^T / sqrt(192)) v W_o
Experts (layers 2-5), x = the normed stream after the attention:
  s = sigmoid(x W_r) over all 256; S = the 8 largest of s + b (b = 0, not
  trained); w_i = s_i / sum_{j in S} s_j * 2.446;
  y = sum_{i in S, i held} w_i W_down,i(SiLU(x W_gate,i) * (x W_up,i))
      + W_down,s(SiLU(x W_gate,s) * (x W_up,s))          the shared expert
Layer 1's FFN is the same gated form at width 9216, no routing.

Departures from the published model, each also under ``assumed`` in the
configuration's file: the output gate's up-projection has no bias; the
top-level ``head_dim: 72`` of config.json is used by neither attention;
``e_score_correction_bias`` is zero and never updated; the chip's share is
the experts ``experts_held`` of the 256 routed over and the first
``vocab_size`` ids; what the other chips' experts would add is left out,
here as in the program.

``precision="fp8"`` is the control, not a reference: the same mathematics
with the operands of every matrix product rounded to float8 (e4m3, one
scale a tensor), the step below the bf16 the configuration states.
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
FAULTS = ("half_batch", "kda_no_decay", "router_no_renorm")


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
    keep = np.ones(ids.shape, np.float32)
    keep[:, -1] = 0.0
    return np.roll(ids, -1, axis=1), keep


# ----------------------------------------------------------------- shapes
def layer_kinds(cfg):
    """(attention, ffn) of each layer run, layers numbered from 1 as
    config.json numbers them: ("kda" | "mla", "dense" | "experts")."""
    la = cfg["linear_attn_config"]
    out = []
    for layer in range(1, cfg["num_hidden_layers"] + 1):
        assert (layer in la["kda_layers"]) != (layer in la["full_attn_layers"])
        out.append(("kda" if layer in la["kda_layers"] else "mla",
                    "dense" if layer <= cfg["first_k_dense_replace"]
                    else "experts"))
    return out


def _held(cfg):
    lo, hi = cfg["experts_held"]
    assert hi - lo == cfg["num_experts"]
    return lo, hi


def _kda_dims(cfg):
    la = cfg["linear_attn_config"]
    return la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]


def param_shapes(cfg) -> dict:
    h, f, v = cfg["hidden_size"], cfg["moe_intermediate_size"], \
        cfg["vocab_size"]
    heads, d, taps = _kda_dims(cfg)
    hd, r = heads * d, cfg["kda_low_rank"]
    mh = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    lo, hi = _held(cfg)
    e, sh = hi - lo, cfg["num_shared_experts"] * f
    kda = {"Wq": (h, hd), "Wk": (h, hd), "Wv": (h, hd),
           "conv_q": (taps, hd), "conv_k": (taps, hd), "conv_v": (taps, hd),
           "Wa_down": (h, r), "Wa_up": (r, hd), "A_log": (heads,),
           "dt_bias": (hd,), "Wb": (h, heads), "Wg_down": (h, r),
           "Wg_up": (r, hd), "o_norm": (d,), "Wo": (hd, h)}
    mla = {"Wq": (h, mh * qk),
           "Wkva": (h, cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]),
           "kv_norm": (cfg["kv_lora_rank"],),
           "Wkvb": (cfg["kv_lora_rank"],
                    mh * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])),
           "Wo": (mh * cfg["v_head_dim"], h)}
    dense = {"Wgate": (h, cfg["intermediate_size"]),
             "Wup": (h, cfg["intermediate_size"]),
             "Wdown": (cfg["intermediate_size"], h)}
    experts = {"Wr": (h, cfg["router_experts"]), "Wgate": (e, h, f),
               "Wup": (e, h, f), "Wdown": (e, f, h), "Wgate_s": (h, sh),
               "Wup_s": (h, sh), "Wdown_s": (sh, h)}
    out = {"0": {"W": (v, h)}}
    for i, (attn, ffn) in enumerate(layer_kinds(cfg)):
        out[str(i + 1)] = {
            "attn": dict(kda if attn == "kda" else mla),
            "ffn": dict(dense if ffn == "dense" else experts),
            "ln1": {"gamma": (h,)}, "ln2": {"gamma": (h,)}}
    n = cfg["num_hidden_layers"]
    out[str(n + 1)] = {"gamma": (h,)}
    out[str(n + 2)] = {"W": (h, v)}
    return out


def stage_of(cfg, leaf: str) -> str:
    """The stage a parameter leaf (by its path, ``['2']['attn']['Wq']``)
    belongs to: ``embed``, ``layer1`` .. ``layer5``, or ``head`` for the
    final norm and the output matrix."""
    i = int(leaf.split("'")[1])
    if i == 0:
        return "embed"
    return f"layer{i}" if i <= cfg["num_hidden_layers"] else "head"


_OUT_PROJECTIONS = ("Wo", "Wdown", "Wdown_s")


def make_params(cfg, seed: int = 0):
    """Seeded float32 weights on the device, ALL from the configuration's
    ``weights_seed`` (``seed``, the run's, draws the token ids only: the
    weights decide which experts a token draws, so how many rows the held
    experts multiply, and a run's seed is not to move the amount of work).
    Embedding rows N(0, embedding_std^2); the output projections of
    attention, MLP and experts N(0, out_proj_std^2); every other matrix
    N(0, matrix_std^2); gains 1; KDA's own by the family's convention
    (fla's KimiDeltaAttention): conv taps U(-K^-1/2, K^-1/2), A_log =
    log U(1, 16), dt_bias = softplus^-1 of dt log-uniform in [1e-3,
    1e-1]."""
    root = jax.random.PRNGKey(int(cfg["weights_seed"]))
    flat, tree = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    taps = _kda_dims(cfg)[2]
    out = []
    for i, (path, shape) in enumerate(flat):
        key, name = jax.random.fold_in(root, i), path[-1].key
        if name in ("gamma", "o_norm", "kv_norm"):
            out.append(jnp.ones(shape, jnp.float32))
        elif name.startswith("conv_"):
            out.append(_uniform(key, shape, -taps ** -0.5, taps ** -0.5))
        elif name == "A_log":
            out.append(jnp.log(_uniform(key, shape, 1.0, 16.0)))
        elif name == "dt_bias":
            dt = jnp.exp(_uniform(key, shape, math.log(1e-3),
                                  math.log(1e-1)))
            out.append(dt + jnp.log(-jnp.expm1(-dt)))
        else:
            std = cfg["embedding_std"] if path[0].key == "0" else (
                cfg["out_proj_std"] if name in _OUT_PROJECTIONS
                else cfg["matrix_std"])
            out.append(_normal(key, shape, float(std)))
    return jax.tree_util.tree_unflatten(tree, out)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _normal(key, shape, std):
    return std * jax.random.normal(key, shape, jnp.float32)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _uniform(key, shape, lo, hi):
    return jax.random.uniform(key, shape, jnp.float32, lo, hi)


# ------------------------------------------------------------------ counts
def _per_token_macs(cfg):
    """Multiply-adds a token in the matrix products of each layer run
    (projections, router, the held experts' EXPECTED rows, the shared
    expert, the dense MLP) and of the head; the attentions' own
    token-mixing is counted apart."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    heads, d, _ = _kda_dims(cfg)
    hd, r = heads * d, cfg["kda_low_rank"]
    mh = cfg["num_attention_heads"]
    lo, hi = _held(cfg)
    macs = 0.0
    for attn, ffn in layer_kinds(cfg):
        if attn == "kda":
            macs += 4 * h * hd + 2 * (h * r + r * hd) + h * heads
        else:
            macs += h * mh * (cfg["qk_nope_head_dim"]
                              + cfg["qk_rope_head_dim"])
            macs += h * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            macs += cfg["kv_lora_rank"] * mh * (cfg["qk_nope_head_dim"]
                                                + cfg["v_head_dim"])
            macs += mh * cfg["v_head_dim"] * h
        if ffn == "dense":
            macs += 3 * h * cfg["intermediate_size"]
        else:
            macs += h * cfg["router_experts"]
            macs += 3 * h * f * cfg["num_shared_experts"]
            macs += 3 * h * f * cfg["num_experts_per_token"] * (hi - lo) \
                / cfg["router_experts"]
    return macs + h * cfg["vocab_size"]


def _kda_macs_per_token(cfg):
    """The recurrence's own multiply-adds a token, all heads: decay the
    state, read it with k, the rank-one update, read it with q: four
    passes over d_k x d_v."""
    heads, d, _ = _kda_dims(cfg)
    return 4.0 * heads * d * d


def _mla_pairs(t):
    return t * (t + 1) / 2.0


def train_flops_per_example(cfg) -> float:
    """Model FLOPs of one sequence in a training step for the share held
    here: 2 per multiply-add forward and twice that again backward, in the
    projections, the KDA recurrence token by token (its chunked form does
    more: that shows as a loss), the latent attention's scores and
    weighted values inside the causal mask, the router, the held experts'
    three products for the rows they are EXPECTED to draw, the shared
    expert, the dense MLP and the head. Recomputation, norms, softmax,
    convolutions, the embedding gather and the optimizer are left out, as
    MFU's convention has it."""
    t = seq_length(cfg)
    macs = _per_token_macs(cfg) * t
    for attn, _ in layer_kinds(cfg):
        if attn == "kda":
            macs += _kda_macs_per_token(cfg) * t
        else:
            macs += _mla_pairs(t) * cfg["num_attention_heads"] * (
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


def kda_scan_min_seconds(cfg, peaks, batch: int) -> dict:
    """The least time the KDA recurrences of all the KDA layers run can
    take in a training step of ``batch`` sequences (what the scope
    ``kda/scan`` holds: from normalised q, k, v, decay and beta to o): the
    token-by-token multiply-adds, once forward and twice backward, against
    reading q, k, v, the decay (float32) and beta and writing o once
    forward, and reading them with o's gradient and writing their
    gradients once backward."""
    t = seq_length(cfg)
    heads, d, _ = _kda_dims(cfg)
    layers = sum(a == "kda" for a, _ in layer_kinds(cfg))
    flops = 3 * 2.0 * _kda_macs_per_token(cfg) * t * batch * layers
    per_token = heads * d * (3 * 2 + 4 + 2) + heads * 4
    tf = flops / peaks["flops_bf16"]
    tb = 3.0 * per_token * t * batch * layers / peaks["hbm_bytes_per_s"]
    return {"least_s": max(tf, tb), "flops_s": tf, "bytes_s": tb}


def mla_attn_min_seconds(cfg, peaks, batch: int) -> dict:
    """The least time the latent attention of all the MLA layers run can
    take in a training step of ``batch`` sequences (the scope ``mla/attn``:
    from expanded q, k, v to the weighted values): scores and weighted
    values INSIDE the causal mask, two products forward and four backward
    (a block computed and then masked is a loss), against reading q, k, v
    and the output and their gradients once, bf16."""
    t, mh = seq_length(cfg), cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    layers = sum(a == "mla" for a, _ in layer_kinds(cfg))
    tf = layers * batch * 3 * 2.0 * _mla_pairs(t) * mh * (qk + dv) \
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


def _conv(x, taps):
    """x (B, T, C), taps (K, C): y_t = sum_j taps[j] x_{t-(K-1)+j}."""
    k, t = taps.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, j:j + t] * taps[j] for j in range(k))


def kda_recurrence(q, k, v, log_a, beta, segment=64, no_decay=False):
    """The recurrence as written, one token at a time: q, k, log_a
    (B, T, H, d_k), v (B, T, H, d_v), beta (B, T, H) -> (o (B, T, H, d_v),
    final state (B, H, d_k, d_v)). A scan over segments of ``segment``
    tokens, each rematerialised, so that 8,192 positions keep 128 states
    and not 8,192. ``no_decay``: the planted fault (a_t = 1)."""
    b, t, h, dk = k.shape
    dv = v.shape[-1]

    def token(s, x):
        q_t, k_t, v_t, la_t, b_t = x              # (B, H, d) / (B, H)
        if not no_decay:
            s = jnp.exp(la_t)[..., None] * s
        u = b_t[..., None] * (v_t - jnp.einsum(
            "bhkv,bhk->bhv", s, k_t, precision=HIGHEST))
        s = s + k_t[..., None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=HIGHEST)

    @jax.checkpoint
    def run(s, xs):
        return lax.scan(token, s, xs)

    pad = (-t) % segment
    xs = []
    for x in (q, k, v, log_a, beta):
        x = jnp.moveaxis(x, 1, 0)
        if pad:     # k = 0, beta = 0, no decay: the state stays as it is
            x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        xs.append(x.reshape((-1, segment) + x.shape[1:]))
    s, o = lax.scan(run, jnp.zeros((b, h, dk, dv), jnp.float32), tuple(xs))
    o = o.reshape((-1,) + o.shape[2:])[:t]
    return jnp.moveaxis(o, 0, 1), s


def kda_inputs(cfg, p, x, precision="highest"):
    """x (B, T, hidden), normed -> (q, k, v, log_a, beta, gate) of the
    recurrence and the output gate."""
    b, t, _ = x.shape
    heads, d, _ = _kda_dims(cfg)
    split = lambda a: a.reshape(b, t, heads, d)
    branch = lambda w, taps: split(jax.nn.silu(
        _conv(_mm(x, p[w], precision), p[taps])))
    unit = lambda a: a * lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)
    q = unit(branch("Wq", "conv_q")) * d ** -0.5
    k = unit(branch("Wk", "conv_k"))
    v = branch("Wv", "conv_v")
    z = _mm(_mm(x, p["Wa_down"], precision), p["Wa_up"], precision)
    log_a = -jnp.exp(p["A_log"])[:, None] * split(
        jax.nn.softplus(z + p["dt_bias"]))
    beta = jax.nn.sigmoid(_mm(x, p["Wb"], precision))
    gate = jax.nn.sigmoid(
        _mm(_mm(x, p["Wg_down"], precision), p["Wg_up"], precision))
    return q, k, v, log_a, beta, split(gate)


def _kda(cfg, p, x, precision, fault=None):
    b, t, _ = x.shape
    q, k, v, log_a, beta, gate = kda_inputs(cfg, p, x, precision)
    o, _ = kda_recurrence(q, k, v, log_a, beta,
                          no_decay=fault == "kda_no_decay")
    o = _rms(o, p["o_norm"], cfg["rms_norm_eps"]) * gate
    return _mm(o.reshape(b, t, -1), p["Wo"], precision)


def _mla(cfg, p, x, precision):
    """x (B, T, hidden), normed -> (B, T, hidden). One (sequence, head) at
    a time under a dense T x T mask."""
    b, t, _ = x.shape
    mh, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    q = _mm(x, p["Wq"], precision).reshape(b, t, mh, nope + rope)
    ckr = _mm(x, p["Wkva"], precision)
    c = _rms(ckr[..., :rank], p["kv_norm"], cfg["rms_norm_eps"])
    kv = _mm(c, p["Wkvb"], precision).reshape(b, t, mh, nope + dv)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(ckr[:, :, None, rank:],
                                          (b, t, mh, rope))], axis=-1)
    v = kv[..., nope:]
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    rows = lambda a: a.transpose(0, 2, 1, 3).reshape(b * mh, t, -1)

    @jax.checkpoint
    def one(q1, k1, v1):
        s = _mm(q1, k1.T, precision) * (nope + rope) ** -0.5
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _mm(w, v1, precision)

    out = lax.map(lambda a: one(*a), (rows(q), rows(k), rows(v)))
    out = out.reshape(b, mh, t, dv).transpose(0, 2, 1, 3)
    return _mm(out.reshape(b, t, mh * dv), p["Wo"], precision)


def _gated(x, wg, wu, wd, precision):
    return _mm(jax.nn.silu(_mm(x, wg, precision)) * _mm(x, wu, precision),
               wd, precision)


def routing(cfg, p, x, precision="highest", fault=None):
    """(experts chosen (N, 8), their weights (N, 8)) for x (N, hidden)."""
    s = jax.nn.sigmoid(_mm(x, p["Wr"], precision))
    _, idx = lax.top_k(lax.stop_gradient(s), cfg["num_experts_per_token"])
    kept = jnp.take_along_axis(s, idx, axis=-1)
    if fault != "router_no_renorm":
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


def layer(cfg, p, h, kinds, precision="highest", held=None, fault=None,
          shared=True):
    """One decoder layer on h (B, T, hidden); ``kinds`` = (attention,
    ffn). ``held`` overrides the configuration's range of experts and
    ``shared`` leaves the shared expert out (the shares-add-up test)."""
    if held is not None:
        cfg = {**cfg, "experts_held": list(held),
               "num_experts": held[1] - held[0]}
    attn, ffn = kinds
    eps = cfg["rms_norm_eps"]
    x = _rms(h, p["ln1"]["gamma"], eps)
    h = h + (_kda(cfg, p["attn"], x, precision, fault) if attn == "kda"
             else _mla(cfg, p["attn"], x, precision))
    x = _rms(h, p["ln2"]["gamma"], eps)
    b, t, f = x.shape
    if ffn == "dense":
        return h + _gated(x, p["ffn"]["Wgate"], p["ffn"]["Wup"],
                          p["ffn"]["Wdown"], precision)
    return h + _experts(cfg, p["ffn"], x.reshape(b * t, f), precision,
                        fault, shared).reshape(b, t, f)


def loss_fn(cfg, params, ids, precision="highest", fault=None):
    """Mean next-token cross-entropy of ids (B, T) int32, float32."""
    ids = jnp.asarray(ids)
    x = params["0"]["W"][ids]
    for i, kinds in enumerate(layer_kinds(cfg)):
        x = jax.checkpoint(functools.partial(
            layer, cfg, kinds=kinds, precision=precision, fault=fault))(
                params[str(i + 1)], x)
    n = cfg["num_hidden_layers"]
    x = _rms(x, params[str(n + 1)]["gamma"], cfg["rms_norm_eps"])
    b, t, h = x.shape
    x, y = x[:, :-1].reshape(-1, h), ids[:, 1:].reshape(-1)
    blk = min(LOSS_BLOCK, x.shape[0])
    w_out = params[str(n + 2)]["W"]

    @jax.checkpoint
    def block(w, xb, yb):
        z = _mm(xb, w, precision)
        return jnp.sum(jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(
            z, yb[:, None], axis=-1)[:, 0])

    total = 0.0
    for s in range(0, x.shape[0], blk):
        total = total + block(w_out, x[s:s + blk], y[s:s + blk])
    return total / x.shape[0]


def train_steps(cfg, params, batches, precision="highest", devices=None,
                fault=None):
    """Follow AdamW through ``batches`` (the harness's (uint8 rows,
    one-hot) pairs; the one-hot is ignored). Returns (losses, first
    moment, final params), all float32, the trees on the host. Weight
    decay on the leaves of two or more dimensions. One chip: ``devices``
    is taken for the interface's sake. ``fault``: one of `FAULTS`, for the
    tests of the limits only."""
    lr, b1, b2 = cfg["learning_rate"], cfg["beta1"], cfg["beta2"]
    eps, wd = cfg["epsilon"], cfg["weight_decay"]

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, m, v, count, ids):
        loss, g = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, ids, precision, fault))(params)
        count = count + 1
        m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = jax.tree_util.tree_map(
            lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        c1 = 1 - b1 ** count.astype(jnp.float32)
        c2 = 1 - b2 ** count.astype(jnp.float32)
        params = jax.tree_util.tree_map(
            lambda w, m, v: w - lr * (
                (m / c1) / (jnp.sqrt(v / c2) + eps)
                + (wd * w if w.ndim >= 2 else 0.0)), params, m, v)
        return params, m, v, count, loss

    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    count = jnp.zeros((), jnp.int32)
    losses = []
    for rows, _ in batches:
        ids = decode_tokens(cfg, rows)
        if fault == "half_batch":
            ids = ids[:max(len(ids) // 2, 1)]
        params, m, v, count, loss = step(params, m, v, count,
                                         jnp.asarray(ids))
        losses.append(float(loss))
    # on the host: the control follows the reference twice in one process,
    # and what the first pass leaves on the device the second pass's step
    # program no longer finds free
    del v
    return losses, jax.device_get(m), jax.device_get(params)
