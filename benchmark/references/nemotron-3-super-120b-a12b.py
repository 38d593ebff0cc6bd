"""Plain reference for ``nemotron-3-super-120b-a12b``: the blocks of NVIDIA's
Nemotron-3-Super-120B-A12B (config.json, ``model_type`` ``nemotron_h``), the
loss, its gradients and the AdamW step in straightforward float32
``jax.numpy`` at ``highest`` matmul precision: the Mamba-2 recurrence TOKEN
BY TOKEN (no chunk algebra), attention in blocks of queries against all the
keys under a dense mask, a Python loop over the experts held, no kernel, no
dispatch, AdamW written out, ONE sequence of a batch at a time (the loss is
the mean over sequences of equal weight, so the gradient is the mean of
theirs) and AdamW's moments on the host between steps, which is what lets it
fit the chip; the five repeats of the unit ``EM`` run as a `lax.scan` over
their leaves stacked (`by_stretch`), which is what lets its executable lie
in the benchmark's compile cache beside the step's. It imports nothing of the program and takes nothing the
program made: weights come from the configuration's ``weights_seed``,
batches from the benchmark's seed; leaves are named as the zoo model's graph
names them.

A block is ONE mixer behind a pre-norm (h: T x 4096), no biases but the
convolution's, eps 1e-5: ``h += Mixer_l(RMSNorm_l(h))``, the mixer by
``hybrid_override_pattern[first_layer + l]``. After the last block a final
RMSNorm, an untied head over the held slice of the vocabulary, mean
next-token cross-entropy in float32.

``M``, Mamba-2 (H heads of P = 64 in G groups of state N = 128; d_inner =
H P), x = the normed stream:
  [z ; u ; dt] = x W_in, z of d_inner, u = [x' ; B ; C] of d_inner + 2 G N,
  dt of H; u <- silu(conv(u) + b), depth-wise causal taps of 4 (w_j of
  c_t = sum_j w_j u_{t-j} is row K-1-j of the leaf ``conv``), zeros before
  position 0; D_t = softplus(dt_t + dt_bias) (H; no clamp); a_t =
  exp(D_t A), A = -exp(A_log) one scalar a head. Head h of group
  h // (H / G), state S (P x N):
      S_t = a_t S_{t-1} + D_t x'_t B_t^T;   y_t = S_t C_t + D_h x'_t.
  o = RMSNorm_group(y * silu(z)) * gamma, the mean of squares over each
  GROUP's d_inner / G channels (the gate BEFORE the norm); o W_out.
``*``, attention (mh query heads of 128 on kv key/value heads):
  q = x W_q, k = x W_k, v = x W_v; query head h reads key head
  h // (mh / kv); causal softmax(q k^T / sqrt(128)) v; W_o. NO rotation and
  no q/k norm.
``E``, LatentMoE:
  s = sigmoid(x W_r) over all 512; S = the 22 largest of s + b (b = 0, not
  trained); w_e = 5 s_e / sum_{j in S} s_j; l = x W_dn (4096 -> 1024);
  f_e(l) = relu(l W1_e)^2 W2_e (1024 -> 2688 -> 1024);
  out = (sum_{e in S, e held} w_e f_e(l)) W_up + relu(x V1)^2 V2 (the shared
  expert 4096 -> 5376 -> 4096 on the stream, every token).

Departures from the published model, each also under ``assumed`` in the
configuration's file: the expert bias is zero and never updated; the chip's
share is the experts ``experts_held`` of the 512 routed over, 16 of the 128
Mamba-2 heads with 1 of the 8 groups, 4 of the 32 query heads on 1 of the 2
key/value heads and the first ``vocab_size`` ids; what the other chips'
experts and heads would add is left out, here as in the program; the
multi-token-prediction module is not run.

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
#: queries whose scores against all the keys attention holds at once
QUERY_BLOCK = 1024
#: tokens of the recurrence between two states that are kept: 8,192
#: positions keep 64 states of 16 x 64 x 128 a sequence (34 MB), not 8,192
STRETCH = 128
#: the faults `train_steps` can plant; the cell's limits have to catch each
#: (benchmark/tools/plant_faults.py). ``kv_head_mod`` shows only where more
#: than one key/value head is held (the tests' sizes: the cut holds one)
FAULTS = ("half_batch", "no_decay", "no_dt_input", "norm_over_head",
          "gate_after_norm", "no_latent_down", "relu_not_squared",
          "no_scaling", "kv_head_mod")


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
    """The mixer of each block run, ``M``, ``*`` or ``E``:
    ``hybrid_override_pattern`` is the published string of all 88; the
    blocks run are the ``num_hidden_layers`` from ``first_layer`` on."""
    first, n = cfg["first_layer"], cfg["num_hidden_layers"]
    kinds = cfg["hybrid_override_pattern"][first:first + n]
    assert len(kinds) == n and set(kinds) <= set("M*E")
    return list(kinds)


def _held(cfg):
    lo, hi = cfg["experts_held"]
    assert hi - lo == cfg["n_routed_experts"]
    return lo, hi


def _mamba(cfg):
    """(heads, head width, groups, state width, d_inner, convolved width)
    of the Mamba-2 slice held."""
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    return h, p, g, n, h * p, h * p + 2 * g * n


def _heads(cfg):
    """(query heads, key/value heads, head width) held."""
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"])


def _mixer_shapes(cfg, kind: str) -> dict:
    hid = cfg["hidden_size"]
    if kind == "M":
        h, _, _, _, inner, conv = _mamba(cfg)
        return {"Win": (hid, inner + conv + h),
                "conv": (cfg["conv_kernel"], conv), "conv_b": (conv,),
                "dt_bias": (h,), "A_log": (h,), "D": (h,),
                "norm": (inner,), "Wout": (inner, hid)}
    if kind == "*":
        mh, kv, d = _heads(cfg)
        return {"Wq": (hid, mh * d), "Wk": (hid, kv * d),
                "Wv": (hid, kv * d), "Wo": (mh * d, hid)}
    lo, hi = _held(cfg)
    e, lat = hi - lo, cfg["moe_latent_size"]
    f, fs = cfg["moe_intermediate_size"], \
        cfg["moe_shared_expert_intermediate_size"]
    return {"Wr": (hid, cfg["router_experts"]), "W1": (e, lat, f),
            "W2": (e, f, lat), "Wl_down": (hid, lat), "Wl_up": (lat, hid),
            "W1_s": (hid, fs), "W2_s": (fs, hid)}


def param_shapes(cfg) -> dict:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    out = {"embed": {"W": (v, h)}}
    for i, kind in enumerate(layer_kinds(cfg)):
        out[f"layer{i}"] = {"ln": {"gamma": (h,)},
                            "mixer": _mixer_shapes(cfg, kind)}
    out["norm"] = {"gamma": (h,)}
    out["head"] = {"W": (h, v)}
    return out


def stage_of(cfg, leaf: str) -> str:
    """The stage a parameter leaf (by its path,
    ``['layer2']['mixer']['Win']``) belongs to: ``embed``, ``layer0`` ..
    ``layer10``, or ``head`` for the final norm and the head's matrix."""
    top = leaf.split("'")[1]
    return "head" if top in ("norm", "head") else top


#: the matrices that write into the stream
_OUT_PROJECTIONS = ("Wout", "Wo", "Wl_up", "W2_s")


def make_params(cfg, seed: int = 0):
    """Seeded float32 weights on the device, ALL from the configuration's
    ``weights_seed`` (``seed``, the run's, draws the token ids only: the
    weights decide which experts a token draws, so how many rows the held
    experts multiply, and a run's seed is not to move the amount of work).
    The embedding N(0, embedding_std^2); the taps and their bias N(0,
    conv_std^2); the matrices that write into the stream N(0,
    out_proj_std^2); every other matrix N(0, matrix_std^2); gains and D 1;
    dt_bias the inverse softplus of a step size log-uniform in
    [time_step_min, time_step_max] floored at time_step_floor; A_log the
    log of U(1, 16)."""
    root = jax.random.PRNGKey(int(cfg["weights_seed"]))
    flat, tree = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(flat):
        name, key = path[-1].key, jax.random.fold_in(root, i)
        if name in ("gamma", "norm", "D"):
            out.append(jnp.ones(shape, jnp.float32))
        elif name == "dt_bias":
            lo, hi = cfg["time_step_min"], cfg["time_step_max"]
            dt = jnp.maximum(jnp.exp(
                jax.random.uniform(key, shape, jnp.float32)
                * (np.log(hi) - np.log(lo)) + np.log(lo)),
                cfg["time_step_floor"])
            out.append(dt + jnp.log(-jnp.expm1(-dt)))
        elif name == "A_log":
            out.append(jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                                  1.0, 16.0)))
        else:
            std = cfg["embedding_std"] if path[0].key == "embed" else (
                cfg["conv_std"] if name in ("conv", "conv_b") else
                cfg["out_proj_std"] if name in _OUT_PROJECTIONS
                else cfg["matrix_std"])
            out.append(_normal(key, shape, float(std)))
    return jax.tree_util.tree_unflatten(tree, out)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _normal(key, shape, std):
    return std * jax.random.normal(key, shape, jnp.float32)


# ------------------------------------------------------------------ counts
def _count(cfg, kind: str) -> int:
    return layer_kinds(cfg).count(kind)


def _experts_macs(cfg) -> float:
    """The held experts' EXPECTED rows, a token: two products an expert."""
    lo, hi = _held(cfg)
    return float(2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]
                 * cfg["num_experts_per_tok"] * (hi - lo)
                 / cfg["router_experts"])


def _scan_macs(cfg) -> float:
    """The recurrence's multiply-adds a token, token by token: the state's
    update (P x N a head) and its read-out (P x N a head)."""
    h, p, _, n, _, _ = _mamba(cfg)
    return float(2 * h * p * n)


def _per_token_macs(cfg) -> dict:
    """Multiply-adds a token in the matrix products of the blocks run, by
    what they belong to; attention's own token-mixing is counted apart."""
    hid = cfg["hidden_size"]
    h, _, _, _, inner, conv = _mamba(cfg)
    mh, kv, d = _heads(cfg)
    experts = _count(cfg, "E")
    return {
        "shared expert": float(
            experts * 2 * hid * cfg["moe_shared_expert_intermediate_size"]),
        "head": float(hid * cfg["vocab_size"]),
        "Mamba-2 projections": float(
            _count(cfg, "M") * hid * (2 * inner + conv + h)),
        "latent projections": float(
            experts * 2 * hid * cfg["moe_latent_size"]),
        "router": float(experts * hid * cfg["router_experts"]),
        "attention projections": float(
            _count(cfg, "*") * hid * (2 * mh + 2 * kv) * d),
        "held experts": experts * _experts_macs(cfg),
        "state-space scan": _count(cfg, "M") * _scan_macs(cfg),
    }


def _causal_pairs(t):
    return t * (t + 1) / 2.0


def flops_shares(cfg) -> dict:
    """Share of `train_flops_per_example` by part, for the cell's ``why``."""
    t = seq_length(cfg)
    mh, _, d = _heads(cfg)
    parts = {k: v * t for k, v in _per_token_macs(cfg).items()}
    parts["attention scores"] = _count(cfg, "*") * _causal_pairs(t) \
        * mh * 2 * d
    total = sum(parts.values())
    return {k: v / total for k, v in parts.items()}


def train_flops_per_example(cfg) -> float:
    """Model FLOPs of one sequence in a training step for the share held
    here: 2 per multiply-add forward and twice that again backward, in
    every projection, the router, the held experts' two products for the
    rows they are EXPECTED to draw, the shared expert, the recurrence token
    by token, attention's scores and weighted values inside the causal
    mask at the held query heads, and the head. Recomputation, norms,
    softmax, the taps and gates (element-wise), the embedding gather and
    the optimizer are left out, as MFU's convention has it."""
    t = seq_length(cfg)
    mh, _, d = _heads(cfg)
    macs = sum(_per_token_macs(cfg).values()) * t
    macs += _count(cfg, "*") * _causal_pairs(t) * mh * 2 * d
    return 2.0 * macs * 3


def experts_min_seconds(cfg, peaks, rows: float) -> dict:
    """The least time the held experts' TWO products of ONE layer (1024 ->
    2688 -> 1024, un-gated) can take in a training step, forward and
    backward (each product once forward and twice backward: the input's and
    the weight's gradient), for ``rows`` token rows routed to them: the
    larger of FLOPs/peak and bytes/peak, bf16 operands read once and
    results written once."""
    lat, f = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    lo, hi = _held(cfg)
    flops = 2.0 * rows * lat * f
    tf = tb = 0.0
    for cin, cout in ((lat, f), (f, lat)):
        w = (hi - lo) * cin * cout * 2
        x, y = rows * cin * 2, rows * cout * 2
        tf += 3 * flops / peaks["flops_bf16"]
        tb += 3 * (x + y + w) / peaks["hbm_bytes_per_s"]
    return {"least_s": max(tf, tb), "flops_s": tf, "bytes_s": tb}


def gqa_attn_min_seconds(cfg, peaks, batch: int) -> dict:
    """The least time the attention of the blocks run can take in a
    training step of ``batch`` sequences (the scope ``mha/attn``: from q,
    k, v to the weighted values): scores and weighted values INSIDE the
    causal mask at the held query heads, two products forward and four
    backward, against reading q and the output at their heads and k and v
    at their own (once a group, not once a query head) and their gradients
    once, bf16."""
    t = seq_length(cfg)
    mh, kv, d = _heads(cfg)
    layers = _count(cfg, "*")
    tf = layers * batch * 3 * 2.0 * _causal_pairs(t) * mh * 2 * d \
        / peaks["flops_bf16"]
    tb = layers * batch * 2 * t * (2 * mh + 2 * kv) * d * 2 \
        / peaks["hbm_bytes_per_s"]
    return {"least_s": max(tf, tb), "flops_s": tf, "bytes_s": tb}


def ssd_scan_min_seconds(cfg, peaks, batch: int) -> dict:
    """The least time the Mamba-2 recurrences of the blocks run can take
    in a training step of ``batch`` sequences (what the scope ``ssd/scan``
    holds: from x', B, C and the raw step size to y), whatever implements
    them: the recurrence's multiply-adds token by token (`_scan_macs`) and
    the decay of the state (one multiply an element), once forward and
    twice backward, against reading x', B and C (bf16) and the step size
    (float32) and writing y (float32) once forward, and reading them with
    y's gradient and writing their gradients once backward. The gate z is
    read under ``ssd/out`` and is not counted here."""
    t = seq_length(cfg)
    h, p, g, n, _, _ = _mamba(cfg)
    layers = _count(cfg, "M")
    flops = 3 * (2.0 * _scan_macs(cfg) + h * p * n) * t * batch * layers
    per_token = (h * p + 2 * g * n) * 2 + h * 4 + h * p * 4
    tf = flops / peaks["flops_bf16"]
    tb = 3.0 * per_token * t * batch * layers / peaks["hbm_bytes_per_s"]
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


def recurrence(x, delta, a_log, b, c, state=None, fault=None):
    """The state-space recurrence as written, one token at a time: x (B,
    T, H, P), delta (B, T, H) the step sizes, a_log (H,), b and c (B, T, G,
    N) -> (y (B, T, H, P) WITHOUT the D skip, final state (B, H, P, N)).
    A scan over stretches of `STRETCH` tokens, each rematerialised, so that
    its gradient keeps one state a stretch. ``state``: the state before
    position 0 (None: zeros)."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    of_head = lambda v: jnp.repeat(v, h // g, axis=2)      # (B, T, H, N)
    decay = jnp.ones_like(delta) if fault == "no_decay" \
        else jnp.exp(delta * -jnp.exp(a_log))
    into = x if fault == "no_dt_input" else delta[..., None] * x

    def token(s, xs):
        a_t, u_t, b_t, c_t = xs           # (B, H), (B, H, P), (B, H, N) x 2
        s = a_t[..., None, None] * s + u_t[..., None] * b_t[:, :, None, :]
        return s, jnp.sum(s * c_t[:, :, None, :], axis=-1)

    @jax.checkpoint
    def run(s, xs):
        return lax.scan(token, s, xs)

    pad = (-t) % STRETCH
    xs = []
    for v, fill in ((decay, 1.0), (into, 0.0), (of_head(b), 0.0),
                    (of_head(c), 0.0)):
        v = jnp.moveaxis(v, 1, 0)
        if pad:      # a_t = 1 and no input: the state stays as it is
            v = jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1),
                        constant_values=fill)
        xs.append(v.reshape((-1, STRETCH) + v.shape[1:]))
    s0 = jnp.zeros((bsz, h, p, n), jnp.float32) if state is None else state
    s, y = lax.scan(run, s0, tuple(xs))
    y = y.reshape((-1,) + y.shape[2:])[:t]
    return jnp.moveaxis(y, 0, 1), s


def mamba_inputs(cfg, p, x, precision="highest"):
    """x (B, T, hidden), normed -> (z (B, T, d_inner), x' (B, T, H, P), B
    and C (B, T, G, N), delta (B, T, H)): the projection, the taps with
    their bias, SiLU, the step size."""
    bsz, t, _ = x.shape
    h, hp, g, n, inner, conv = _mamba(cfg)
    taps = cfg["conv_kernel"]
    zud = _mm(x, p["Win"], precision)
    z, u, dt = zud[..., :inner], zud[..., inner:inner + conv], \
        zud[..., inner + conv:]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    w = p["conv"][::-1]               # w_j meets u_{t-j}: row K-1-j
    u = jax.nn.silu(sum(w[j] * padded[:, taps - 1 - j:taps - 1 - j + t]
                        for j in range(taps)) + p["conv_b"])
    return (z, u[..., :inner].reshape(bsz, t, h, hp),
            u[..., inner:inner + g * n].reshape(bsz, t, g, n),
            u[..., inner + g * n:].reshape(bsz, t, g, n),
            jax.nn.softplus(dt + p["dt_bias"]))


def gated_group_norm(cfg, p, y, z, fault=None):
    """y, z (B, T, d_inner) -> RMSNorm over each GROUP's channels of
    y * silu(z), times the gain."""
    h, hp, g, _, inner, _ = _mamba(cfg)
    groups = h if fault == "norm_over_head" else g
    eps = cfg["layer_norm_epsilon"]
    norm = lambda v: (lambda r: r * lax.rsqrt(
        jnp.mean(r * r, axis=-1, keepdims=True) + eps))(
        v.reshape(v.shape[:-1] + (groups, inner // groups))).reshape(v.shape)
    if fault == "gate_after_norm":
        return norm(y) * p["norm"] * jax.nn.silu(z)
    return norm(y * jax.nn.silu(z)) * p["norm"]


def mamba2(cfg, p, x, precision="highest", fault=None):
    """The Mamba-2 mixer on x (B, T, hidden), normed."""
    bsz, t, _ = x.shape
    z, xs, b, c, delta = mamba_inputs(cfg, p, x, precision)
    y, _ = recurrence(xs, delta, p["A_log"], b, c, fault=fault)
    y = y + p["D"][:, None] * xs
    o = gated_group_norm(cfg, p, y.reshape(bsz, t, -1), z, fault)
    return _mm(o, p["Wout"], precision)


def attention(cfg, p, x, precision="highest", fault=None):
    """x (B, T, hidden), normed -> (B, T, hidden). One (sequence, query
    head, block of queries) at a time against all the keys of the head's
    group under a dense mask; no rotation."""
    bsz, t, _ = x.shape
    mh, kv, d = _heads(cfg)
    q = _mm(x, p["Wq"], precision).reshape(bsz, t, mh, d)
    k = _mm(x, p["Wk"], precision).reshape(bsz, t, kv, d)
    v = _mm(x, p["Wv"], precision).reshape(bsz, t, kv, d)
    of_query = jnp.arange(mh) % kv if fault == "kv_head_mod" \
        else jnp.arange(mh) // (mh // kv)
    rows = lambda a: a.transpose(0, 2, 1, 3).reshape(-1, t, d)
    k, v = rows(k[:, :, of_query]), rows(v[:, :, of_query])
    blk = min(QUERY_BLOCK, t)
    assert t % blk == 0
    at = jnp.arange(t)

    @jax.checkpoint
    def one(q1, first, k1, v1):
        seen = at[None, :] <= (first + jnp.arange(blk))[:, None]
        s = _mm(q1, k1.T, precision) * d ** -0.5
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _mm(w, v1, precision)

    def head(args):
        q1, k1, v1 = args
        return lax.map(lambda a: one(a[0], a[1], k1, v1),
                       (q1.reshape(-1, blk, d), jnp.arange(0, t, blk))
                       ).reshape(t, d)

    out = lax.map(head, (rows(q), k, v))
    out = out.reshape(bsz, mh, t, d).transpose(0, 2, 1, 3)
    return _mm(out.reshape(bsz, t, mh * d), p["Wo"], precision)


def _relu2(x, fault=None):
    r = jax.nn.relu(x)
    return r if fault == "relu_not_squared" else r * r


def routing(cfg, p, x, precision="highest", fault=None):
    """(experts chosen (N, 22), their weights (N, 22)) for x (N, hidden);
    the scores in float32 whatever the precision of the products."""
    s = jax.nn.sigmoid(_mm(x, p["Wr"], precision))
    _, idx = lax.top_k(lax.stop_gradient(s), cfg["num_experts_per_tok"])
    kept = jnp.take_along_axis(s, idx, axis=-1)
    kept = kept / jnp.sum(kept, axis=-1, keepdims=True)
    return idx, kept if fault == "no_scaling" \
        else kept * cfg["routed_scaling_factor"]


def experts(cfg, p, x, precision="highest", fault=None):
    """The held experts' part for x (N, hidden), through the latent; the
    shared expert is not in it: a token none of whose experts is held gets
    exactly zero."""
    lo, hi = _held(cfg)
    lat = cfg["moe_latent_size"]
    idx, w = routing(cfg, p, x, precision, fault)
    rows = x[:, :lat] if fault == "no_latent_down" \
        else _mm(x, p["Wl_down"], precision)

    @jax.checkpoint
    def expert(y, held):
        # every row through the expert, under its weight (zero for a row
        # that did not choose it)
        e, w1, w2 = held
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        return y + w_e[:, None] * _mm(
            _relu2(_mm(rows, w1, precision), fault), w2, precision), None

    # a plain loop over the experts held, one after another
    y, _ = lax.scan(expert, jnp.zeros_like(rows),
                    (jnp.arange(lo, hi), p["W1"], p["W2"]))
    return _mm(y, p["Wl_up"], precision)


def shared_expert(cfg, p, x, precision="highest", fault=None):
    """The shared expert's part for x (N, hidden): every token."""
    return _mm(_relu2(_mm(x, p["W1_s"], precision), fault), p["W2_s"],
               precision)


def latent_moe(cfg, p, x, precision="highest", fault=None):
    """The expert layer on x (B, T, hidden), normed."""
    flat = x.reshape(-1, x.shape[-1])
    return (experts(cfg, p, flat, precision, fault)
            + shared_expert(cfg, p, flat, precision, fault)).reshape(x.shape)


_MIXERS = {"M": mamba2, "*": attention, "E": latent_moe}


def layer(cfg, p, h, kind, precision="highest", fault=None):
    """One block on h (B, T, hidden): ``h + Mixer(RMSNorm(h))``."""
    x = _rms(h, p["ln"]["gamma"], cfg["layer_norm_epsilon"])
    return h + _MIXERS[kind](cfg, p["mixer"], x, precision, fault)


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


def _periods(kinds):
    """The blocks run as stretches ``(first block, unit, repeats)``: where a
    unit of one to three kinds repeats (``EMEMEMEMEM`` is ``EM`` five
    times) the stretch is the longest such repetition, else one block."""
    out, i = [], 0
    while i < len(kinds):
        best = (1, 1)
        for u in (1, 2, 3):
            r = 1
            while kinds[i + r * u:i + (r + 1) * u] == kinds[i:i + u]:
                r += 1
            if r > 1 and u * r > best[0] * best[1]:
                best = (u, r)
        out.append((i, kinds[i:i + best[0]], best[1]))
        i += best[0] * best[1]
    return out


def by_stretch(cfg, params, stack=jnp.stack):
    """The parameters with the blocks of every repeating stretch
    (`_periods`) STACKED: ``layers<first>`` holds, for each block of the
    unit, its leaves with the repeats on a new leading axis. `hidden` runs
    such a stretch as a `lax.scan` over the repeats: the same numbers as
    block after block, in a program a third the size (it compiles in two
    thirds of the time and its executable is a quarter: the benchmark's
    compile cache holds it beside the step's)."""
    out = {"embed": params["embed"]}
    for first, unit, repeats in _periods(layer_kinds(cfg)):
        if repeats == 1:
            out[f"layer{first}"] = params[f"layer{first}"]
            continue
        out[f"layers{first}"] = jax.tree_util.tree_map(
            lambda *a: stack(a),
            *[[params[f"layer{first + r * len(unit) + j}"]
               for j in range(len(unit))] for r in range(repeats)])
    return {**out, "norm": params["norm"], "head": params["head"]}


def by_block(cfg, stacked):
    """`by_stretch` undone, on the host: the leaves under the zoo's names."""
    out = {"embed": stacked["embed"]}
    for first, unit, repeats in _periods(layer_kinds(cfg)):
        if repeats == 1:
            out[f"layer{first}"] = stacked[f"layer{first}"]
            continue
        for r in range(repeats):
            for j in range(len(unit)):
                out[f"layer{first + r * len(unit) + j}"] = \
                    jax.tree_util.tree_map(lambda a: np.asarray(a)[r],
                                           stacked[f"layers{first}"][j])
    return {**out, "norm": stacked["norm"], "head": stacked["head"]}


def hidden(cfg, params, ids, precision="highest", fault=None):
    """ids (B, T) -> the stream after the final norm (B, T, hidden), from
    the parameters `by_stretch`; every block rematerialised."""
    x = params["embed"]["W"][jnp.asarray(ids)]
    block = lambda kind: jax.checkpoint(functools.partial(
        layer, cfg, kind=kind, precision=precision, fault=fault))
    for first, unit, repeats in _periods(layer_kinds(cfg)):
        if repeats == 1:
            x = block(unit[0])(params[f"layer{first}"], x)
            continue

        def run(x, of_unit):
            for kind, p in zip(unit, of_unit):
                x = block(kind)(p, x)
            return x, None

        x, _ = lax.scan(run, x, params[f"layers{first}"])
    return _rms(x, params["norm"]["gamma"], cfg["layer_norm_epsilon"])


def logits(cfg, params, ids, precision="highest"):
    """(B, T, vocab) over the held slice (tests' sizes only)."""
    return _mm(hidden(cfg, by_stretch(cfg, params), ids, precision),
               params["head"]["W"], precision)


def loss_of_stretches(cfg, params, ids, precision="highest", fault=None):
    """Mean next-token cross-entropy of ids (B, T) int32, float32, from
    the parameters `by_stretch` (what `train_steps` differentiates)."""
    ids = jnp.asarray(ids)
    b, t = ids.shape
    x = hidden(cfg, params, ids, precision, fault)
    keep = jnp.broadcast_to(jnp.arange(t)[None, :] < t - 1,
                            (b, t)).astype(jnp.float32)
    return _cross_entropy(
        x.reshape(b * t, -1), params["head"]["W"],
        jnp.roll(ids, -1, axis=1).reshape(-1), keep.reshape(-1), precision)


def loss_fn(cfg, params, ids, precision="highest", fault=None):
    """`loss_of_stretches` from the parameters under the zoo's names (the
    stacking is then part of the program: tests' sizes only)."""
    return loss_of_stretches(cfg, by_stretch(cfg, params), ids, precision,
                             fault)


def train_steps(cfg, params, batches, precision="highest", devices=None,
                fault=None):
    """Follow AdamW through ``batches`` (the harness's (uint8 rows,
    one-hot) pairs; the one-hot is ignored). Returns (losses, first
    moment, final params), all float32, the trees on the host. Weight
    decay on the leaves of two or more dimensions of their own (matrices,
    expert stacks, the taps), none on gains, biases and the per-head
    scalars. One
    chip: ``devices`` is taken for the interface's sake. ``fault``: one of
    `FAULTS`, for the tests of the limits only.

    The gradient is taken ONE sequence at a time (every sequence has the
    same number of kept positions, so the batch's loss is the mean of the
    sequences' and its gradient the mean of theirs) and the update is
    applied one top-level entry of the parameters after another with
    AdamW's two moments kept on the HOST in between: 701 M parameters with
    their gradient, both moments AND the float32 temporaries of a sequence
    do not fit one chip's 16 GB together."""
    lr, b1, b2 = cfg["learning_rate"], cfg["beta1"], cfg["beta2"]
    eps, wd = cfg["epsilon"], cfg["weight_decay"]

    @jax.jit
    def gradient(params, ids):
        return jax.value_and_grad(
            lambda p: loss_of_stretches(cfg, p, ids, precision, fault))(
            params)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def add(total, g):
        return jax.tree_util.tree_map(jnp.add, total, g)

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3), static_argnums=6)
    def update(params, g, m, v, count, n, stacked):
        g = jax.tree_util.tree_map(lambda g: g / n, g)
        m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = jax.tree_util.tree_map(
            lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        c1 = 1 - b1 ** count.astype(jnp.float32)
        c2 = 1 - b2 ** count.astype(jnp.float32)
        params = jax.tree_util.tree_map(
            lambda w, m, v: w - lr * (
                (m / c1) / (jnp.sqrt(v / c2) + eps)
                + (wd * w if w.ndim - stacked >= 2 else 0.0)), params, m, v)
        return params, m, v

    zeros = lambda t: jax.tree_util.tree_map(
        lambda a: np.zeros(a.shape, np.float32), t)
    # the repeating stretches' leaves stacked, here and in the moments:
    # the stacked copy is made once, outside the gradient's program
    params = by_stretch(cfg, params)
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
                jax.device_put(v[stage]), jnp.asarray(count, jnp.int32), n,
                stage.startswith("layers"))     # a leaf's own dimensions
            m[stage], v[stage] = jax.device_get((m_new, v_new))
    return out, by_block(cfg, m), by_block(cfg, jax.device_get(params))
