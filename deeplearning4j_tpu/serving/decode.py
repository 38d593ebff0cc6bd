"""Token-level continuous batching — the LLM decode runtime.

The shape-bucketed predict batcher (`serving/batcher.py`) assumes one
request = one forward. Autoregressive decode breaks that: a request is a
*sequence* of forwards with state (the KV cache), lengths vary per
request, and batching at request granularity (wait for the whole batch to
finish, then admit the next) idles slots behind the longest sequence.
This module implements the Orca/vLLM answer — iteration-level scheduling
over a paged KV cache — under this tree's serving invariants:

- **Fixed shapes, AOT-warmed.** Decode runs as ONE compiled program over
  ``slots`` fixed batch positions with an active mask; prefill compiles
  per bucket of a page-aligned ladder (`kvcache.default_prefill_buckets`).
  Every program is executed at load/swap time by `DecodeEngine.warm()`,
  and `serving_decode_compiles_total == serving_decode_warmup_runs_total`
  on /metrics is the ledger proof that no request ever waited on XLA —
  the exact contract `serving/batcher.py` established for predict.
- **Continuous batching.** `DecodeScheduler` admits queued requests into
  free slots *between token steps*: a late-joining request's first token
  (its prefill) lands while other sequences keep decoding — it never
  waits for the running batch to drain. Finished sequences free their
  slot and pages at the same granularity.
- **Prefill/decode phase split.** Prefill (compute-bound, whole prompt)
  and decode (memory-bound, one token) are separate compiled programs
  with separate metric families, so the roofline ledger sees each phase's
  real arithmetic intensity.
- **Sampling in-graph.** Greedy / temperature / top-k run inside the
  decode program (per-slot temperature and k operands), so the host sees
  only one int32 per slot per step.
- **Rolling hot swap.** A swap warms a complete replacement engine off
  the request path, then new admissions go to the new engine while
  in-flight sequences finish on the old one (their KV pages are only
  meaningful under the params that wrote them); the old engine retires
  when its last sequence ends. Zero 5xx, zero request-path compiles,
  bounded double-residency documented in docs/SERVING.md.

`ServedLM` packages an engine + scheduler + version history behind the
same servable surface `ServedModel` exposes (status / describe / swap /
rollback / shutdown), so the registry, HTTP server, fleet supervisor and
router treat LM servables like any other — per-variant routing of the
quantized servables (`quantize.py`) falls out of plain model naming.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import queue
import threading
import time
from collections import OrderedDict, deque
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.monitor import flight
from deeplearning4j_tpu.nn.activations import get_activation
from deeplearning4j_tpu.nn.layers.attention import (
    EmbeddingSequenceLayer, LayerNormLayer, MoEFeedForward,
    MultiHeadAttention, PositionalEmbeddingLayer, TransformerBlock,
    _merge_heads, _split_heads, dot_product_attention, rope,
)
from deeplearning4j_tpu.nn.layers.recurrent import RnnOutputLayer
from deeplearning4j_tpu.serving import kvcache, kvfabric
from deeplearning4j_tpu.serving.batcher import (
    DeadlineExceededError, ServerDrainingError, ServerOverloadedError,
)
from deeplearning4j_tpu.serving.quantize import (
    QUANT_MODES, is_spec_variant, parse_variant, qdot, qtake,
    quantize_params,
)
from deeplearning4j_tpu.util.params import own_tree
from deeplearning4j_tpu.util.locks import DiagnosedLock

log = logging.getLogger("deeplearning4j_tpu")

_LN = LayerNormLayer()          # the block-internal LN (default epsilon)

#: static ceiling for the in-graph top-k gate (per-request k is clipped)
TOP_K_MAX = 64

_TTFT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10)
_ITL_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 1)


@dataclasses.dataclass(frozen=True)
class DecodeConfig:
    """Decode-runtime sizing, fixed at servable load time (every knob
    here shapes a compiled program or the page pool)."""
    slots: int = 4                       # fixed decode batch positions
    page_size: int = 16                  # tokens per KV page
    max_context: Optional[int] = None    # default: the model's seq_length
    pool_pages: Optional[int] = None     # default: no oversubscription
    prefill_buckets: Optional[Sequence[int]] = None
    quantize: Optional[str] = None       # None | "int8" | "bf16"
    queue_limit: int = 64                # pending-join bound (full -> 429)
    max_new_tokens_cap: int = 1024       # server-side generation ceiling
    seed: int = 0                        # sampling PRNG stream
    #: share KV pages across requests with a common prompt prefix
    #: (radix-indexed, copy-on-write; released prefixes retained LRU)
    prefix_cache: bool = True
    #: per-scheduler-tick prefill-token budget: long uncached suffixes
    #: split into chunks of at most this many tokens, executed BETWEEN
    #: decode steps so one long prompt cannot stall every in-flight
    #: stream's inter-token latency. None = auto (4 pages); 0 = off
    #: (whole suffix in one program call, the pre-chunking behavior)
    prefill_chunk_tokens: Optional[int] = None
    #: speculative decoding (draft-verify): None = off. "int8"/"bf16"
    #: self-draft the target through a quantized variant of its own
    #: params; any other string is loaded as a servable source (it must
    #: serve the SAME vocab — mismatch is a loud ModelLoadError). The
    #: ``@spec[:draft=...,k=...]`` source suffix sets these per servable.
    spec_draft: Optional[str] = None
    spec_k: int = 4                      # draft tokens per verify round
    #: rolling acceptance-rate floor: over the last `spec_window` rounds
    #: of a stream, accepted/proposed below this turns speculation OFF
    #: for that stream (it plain-decodes to completion)
    spec_accept_floor: float = 0.4
    spec_window: int = 8                 # rounds in the acceptance window
    #: draft engine's page pool (its own second pool); None = derived
    #: like the target's (no oversubscription)
    spec_draft_pool_pages: Optional[int] = None
    #: host-RAM spill tier size in pages: zero-ref retained prefix pages
    #: demote here under HBM pool pressure and promote back on a hit, so
    #: the effective prefix cache is host-RAM sized. None/0 = off. Only
    #: the TARGET engine spills (the draft's cache is derivative)
    spill_pages: Optional[int] = None


def apply_variant(cfg: DecodeConfig, variant: Optional[str]) -> DecodeConfig:
    """Apply a parsed ``@<variant>`` source suffix to a DecodeConfig:
    ``int8``/``bf16`` select quantized weights, ``spec[:k=...,draft=...,
    floor=...,window=...,pool_pages=...]`` turns on speculative decoding
    (unset options keep the config's defaults)."""
    if variant is None:
        return cfg
    if variant in QUANT_MODES:
        return dataclasses.replace(cfg, quantize=variant)
    if is_spec_variant(variant):
        updates = {"spec_draft": cfg.spec_draft or "int8"}
        if variant.startswith("spec:"):
            for item in variant[len("spec:"):].split(","):
                if not item:
                    continue
                key, sep, val = item.partition("=")
                if not sep:
                    raise ValueError(
                        f"@spec option {item!r} is not key=value")
                if key == "draft":
                    updates["spec_draft"] = val
                elif key == "k":
                    updates["spec_k"] = int(val)
                elif key == "floor":
                    updates["spec_accept_floor"] = float(val)
                elif key == "window":
                    updates["spec_window"] = int(val)
                elif key == "pool_pages":
                    updates["spec_draft_pool_pages"] = int(val)
                else:
                    raise ValueError(
                        f"unknown @spec option {key!r}; known: draft, k, "
                        "floor, window, pool_pages")
        return dataclasses.replace(cfg, **updates)
    raise ValueError(f"unknown servable variant {variant!r}; known: "
                     f"{QUANT_MODES} or spec[:...]")


class GenerateRequest:
    """One in-flight generation: token events stream out through a queue
    (("token", id) / ("done", info) / ("error", exc))."""

    def __init__(self, prompt, max_new_tokens: int = 32,
                 temperature: float = 0.0, top_k: int = 0,
                 eos_id: Optional[int] = None,
                 deadline: Optional[float] = None):
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.eos_id = None if eos_id is None else int(eos_id)
        #: absolute time.monotonic() budget for the WHOLE generation
        self.deadline = deadline
        self.events: "queue.Queue" = queue.Queue()
        self.enqueued = time.monotonic()
        self.first_token_at: Optional[float] = None
        self.last_emit_at: Optional[float] = None
        self.n_emitted = 0
        self.version: Optional[int] = None
        self.finish_reason: Optional[str] = None
        #: prompt positions served from the shared prefix cache (set at
        #: admission) and prefill program executions it took to cover
        #: the uncached suffix (set when prefill completes)
        self.cached_tokens = 0
        self.prefill_chunks = 0
        #: speculative-decoding accounting: draft tokens proposed to /
        #: accepted by the verifier, and verify rounds run, for THIS
        #: stream (0/0/0 on plain decode) — ride the done event so one
        #: loadgen compares speculative and plain runs
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_rounds = 0
        self.cancelled = threading.Event()
        self.done = threading.Event()
        # the submitting thread's trace context (the HTTP handler binds
        # the request's ctx around generate()); the scheduler thread
        # records this stream's spans under it
        self.ctx = monitor.current_context()
        self.t0_pc = time.perf_counter()
        self._last_pc: Optional[float] = None

    # ------------------------------------------------------------- events
    def emit(self, token: int):
        self.n_emitted += 1
        now = time.monotonic()
        if self.first_token_at is None:
            self.first_token_at = now
        self.last_emit_at = now
        self._last_pc = time.perf_counter()
        self.events.put(("token", int(token)))

    def finish(self, reason: str):
        if self.done.is_set():
            return
        self.finish_reason = reason
        self.done.set()
        self.events.put(("done", {
            "finish_reason": reason,
            "tokens": self.n_emitted,
            "version": self.version,
            "cached_tokens": self.cached_tokens,
            "prefill_chunks": self.prefill_chunks,
            "spec_proposed": self.spec_proposed,
            "spec_accepted": self.spec_accepted,
            "spec_rounds": self.spec_rounds,
        }))

    def fail(self, exc: Exception):
        if self.done.is_set():
            return
        self.finish_reason = "error"
        self.done.set()
        self.events.put(("error", exc))

    def cancel(self):
        """Client went away: the scheduler frees the slot at the next
        token boundary."""
        self.cancelled.set()


# ==========================================================================
# The engine: compiled prefill / decode / scoring programs + cache state
# ==========================================================================
class DecodeEngine:
    """Paged-KV decode runtime for one model version.

    Builds fixed-shape jitted programs from a MultiLayerNetwork whose
    stack is an LM the runtime understands (EmbeddingSequenceLayer,
    TransformerBlock / MoEFeedForward / LayerNormLayer /
    PositionalEmbeddingLayer bodies, RnnOutputLayer head — i.e. the
    models/transformer.py family). Params are laundered through
    `own_tree` at build (they may be numpy-backed from a checkpoint
    restore and the KV pools ARE donated alongside them every step) and
    optionally quantized (`quantize.py`).
    """

    def __init__(self, model, cfg: DecodeConfig, name: str = "lm"):
        from deeplearning4j_tpu.serving.registry import ModelLoadError
        self.cfg = cfg
        self.name = name
        conf = model.conf
        it = getattr(conf, "input_type", None)
        if it is None or not model.layers:
            raise ModelLoadError(
                f"decode[{name}]: model has no recurrent input_type; not "
                "an LM this runtime can drive")
        self.max_context = int(cfg.max_context or it.shape[0])
        if cfg.page_size < 1 or self.max_context % cfg.page_size:
            raise ModelLoadError(
                f"decode[{name}]: max_context {self.max_context} must be "
                f"a positive multiple of page_size {cfg.page_size}")
        # ---------------------------------------------- layer extraction
        self._plan: List[Tuple[str, object, str]] = []
        self._block_index: Dict[str, int] = {}
        self.vocab: Optional[int] = None
        self.n_heads = self.head_dim = None
        for i, layer in enumerate(model.layers):
            key = str(i)
            last = i == len(model.layers) - 1
            if isinstance(layer, EmbeddingSequenceLayer):
                self._plan.append(("embed", layer, key))
                self.vocab = int(layer.n_in)
            elif isinstance(layer, PositionalEmbeddingLayer):
                if layer.max_length < self.max_context:
                    raise ModelLoadError(
                        f"decode[{name}]: positional table "
                        f"({layer.max_length}) shorter than max_context "
                        f"({self.max_context})")
                self._plan.append(("posembed", layer, key))
            elif isinstance(layer, TransformerBlock):
                if not layer.causal:
                    raise ModelLoadError(
                        f"decode[{name}]: layer {i} is a non-causal "
                        "TransformerBlock; autoregressive decode needs "
                        "causal attention")
                unserved = [what for what, on in (
                    (f"a {type(layer.attn).__name__} attention",
                     layer.attn is not None),
                    (f"a {type(layer.ffn).__name__} feed-forward"
                     + (f" holding experts {layer.ffn.experts_held}"
                        if getattr(layer.ffn, "experts_held", None) else ""),
                     layer.ffn is not None),
                    (f"norm={layer.norm!r}", layer.norm != "layer"),
                    ("a bias-free MLP", not layer.has_bias),
                ) if on]
                if unserved:
                    # the KV pool has no latent cache and no recurrent
                    # state, the block programs one norm and a biased dense
                    # MLP: refuse by name rather than serve it wrong
                    raise ModelLoadError(
                        f"decode[{name}]: layer {i} is a TransformerBlock "
                        f"with {'; '.join(unserved)}, which this runtime "
                        "cannot serve yet (it trains through fit())")
                h = layer.n_heads
                d = layer.n_out // layer.n_heads
                if self.n_heads not in (None, h) or \
                        self.head_dim not in (None, d):
                    raise ModelLoadError(
                        f"decode[{name}]: non-uniform head geometry "
                        "across blocks is not supported")
                self.n_heads, self.head_dim = h, d
                self._block_index[key] = len(self._block_index)
                self._plan.append(("block", layer, key))
            elif isinstance(layer, (LayerNormLayer, MoEFeedForward)):
                self._plan.append(("pertoken", layer, key))
            elif isinstance(layer, RnnOutputLayer) and last:
                self._plan.append(("head", layer, key))
                if self.vocab is None:
                    self.vocab = int(layer.n_out)
            elif isinstance(layer, MultiHeadAttention):
                raise ModelLoadError(
                    f"decode[{name}]: bare MultiHeadAttention at layer "
                    f"{i}; wrap it in a TransformerBlock for decode")
            else:
                raise ModelLoadError(
                    f"decode[{name}]: layer {i} "
                    f"({type(layer).__name__}) has no incremental decode "
                    "path")
        if not self._block_index or self.vocab is None:
            raise ModelLoadError(
                f"decode[{name}]: need at least one TransformerBlock and "
                "a vocabulary head")
        self.n_layers = len(self._block_index)
        # ------------------------------------------------------- buffers
        # laundered: restored checkpoints hand us numpy-backed leaves and
        # these params ride in every donating step call (PR-3 contract)
        params = own_tree(model.params)
        self._params = quantize_params(params, cfg.quantize)
        self._dtype = jnp.bfloat16 if cfg.quantize == "bf16" \
            else jnp.float32
        self.cache = kvcache.KVCacheState(
            cfg.slots, cfg.page_size, self.max_context,
            pool_pages=cfg.pool_pages, name=name,
            prefix_cache=cfg.prefix_cache)
        # per-tick prefill-token budget (page-aligned, rounded up): None
        # = auto (4 pages), <= 0 = chunking off
        if cfg.prefill_chunk_tokens is None:
            self.prefill_chunk_tokens = min(4 * cfg.page_size,
                                            self.max_context)
        elif cfg.prefill_chunk_tokens <= 0:
            self.prefill_chunk_tokens = 0
        else:
            self.prefill_chunk_tokens = min(
                self.max_context,
                ((int(cfg.prefill_chunk_tokens) + cfg.page_size - 1)
                 // cfg.page_size) * cfg.page_size)
        pool_shape = (self.n_layers, self.cache.pool_pages,
                      cfg.page_size, self.n_heads, self.head_dim)
        self._kpool = jnp.zeros(pool_shape, self._dtype)
        self._vpool = jnp.zeros(pool_shape, self._dtype)
        self.prefill_buckets = tuple(sorted(set(
            int(b) for b in (cfg.prefill_buckets
                             or kvcache.default_prefill_buckets(
                                 cfg.page_size, self.max_context)))))
        for b in self.prefill_buckets:
            if b < 1 or b % cfg.page_size or b > self.max_context:
                raise ModelLoadError(
                    f"decode[{name}]: prefill bucket {b} must be a "
                    f"page-aligned size <= max_context")
        # per-slot host state
        self._temps = np.zeros((cfg.slots,), np.float32)
        self._topks = np.zeros((cfg.slots,), np.int32)
        self._last_tokens = np.zeros((cfg.slots,), np.int32)
        self._counter = 0
        self._base_key = jax.random.PRNGKey(cfg.seed)
        self._compiled: set = set()
        self._closed = False
        self._decode_jit = jax.jit(self._decode_fn, donate_argnums=(1, 2))
        self._prefill_jit = jax.jit(self._prefill_fn, donate_argnums=(1, 2))
        self._chunk_jit = jax.jit(self._chunk_fn, donate_argnums=(1, 2))
        self._copy_jit = jax.jit(kvcache.copy_page, donate_argnums=(0, 1))
        self._logits_jit = jax.jit(self._logits_fn)
        # ---------------------------------------------- tiered KV fabric
        # page extract/land programs: extract reads one physical page
        # WITHOUT donating (the pools stay live), land scatters one page
        # back donating as every other pool writer does. Both take the
        # page id as a traced operand — ONE compile each serves every
        # page. They back the host-RAM spill tier and the disaggregated
        # prefill transfer path, so they exist (and warm) regardless of
        # whether spill is configured.
        self._extract_jit = jax.jit(self._extract_fn)
        self._land_jit = jax.jit(self._land_fn, donate_argnums=(0, 1))
        self.spill: Optional[kvfabric.HostPageStore] = None
        if cfg.spill_pages and int(cfg.spill_pages) > 0 \
                and cfg.prefix_cache:
            self.spill = kvfabric.HostPageStore(
                int(cfg.spill_pages),
                kvfabric.frame_capacity(self.n_layers, cfg.page_size,
                                        self.n_heads, self.head_dim,
                                        np.dtype(self._dtype)),
                name=name)
            self.cache.attach_spill(self.spill, self._demote_page,
                                    self._land_frame)
        # ---------------------------------------- speculative decoding
        # the draft is a full second engine (own params, own smaller
        # page pool, own compiled programs under "<name>.draft"); the
        # target keeps per-slot speculation state and the slot mapping
        self.draft: Optional["DecodeEngine"] = None
        self._verify_jit = None
        self._draft_slots: Dict[int, Optional[int]] = {}
        self._draft_origin: Dict[int, int] = {}
        self._spec_on = np.ones((cfg.slots,), bool)
        self._spec_hist = [deque(maxlen=max(1, int(cfg.spec_window)))
                           for _ in range(cfg.slots)]
        # host-side rejection/residual sampling stream (the draft's
        # in-graph Gumbel stream provides q; acceptance runs on the host)
        self._spec_rng = np.random.RandomState((cfg.seed ^ 0x5EC5) &
                                               0x7FFFFFFF)
        if cfg.spec_draft is not None:
            self._build_draft(model)

    def _build_draft(self, model):
        """Construct the speculative draft engine. ``spec_draft`` is a
        quantize mode (self-draft: the target's own params, int8/bf16) or
        any servable source with the SAME vocabulary — a mismatched draft
        would run every acceptance test over a different symbol set, so
        it is rejected loudly here, at deploy/swap time (the PR-11 vocab
        swap-rejection policy)."""
        from deeplearning4j_tpu.serving.registry import ModelLoadError
        cfg = self.cfg
        k = int(cfg.spec_k)
        if k < 1:
            raise ModelLoadError(
                f"decode[{self.name}]: spec_k must be >= 1 (got {k})")
        src = str(cfg.spec_draft)
        if src in QUANT_MODES:
            draft_model, dquant, dsrc = model, src, f"self@{src}"
        else:
            from deeplearning4j_tpu.serving.registry import load_servable
            base, dquant = parse_variant(src)
            draft_model, dsrc = load_servable(base), src
        dcfg = dataclasses.replace(
            cfg, quantize=dquant, max_context=self.max_context,
            pool_pages=cfg.spec_draft_pool_pages, spec_draft=None,
            spill_pages=None, seed=cfg.seed + 1)
        draft = DecodeEngine(draft_model, dcfg, name=f"{self.name}.draft")
        if draft.vocab != self.vocab:
            dvocab = draft.vocab
            draft.close()
            raise ModelLoadError(
                f"decode[{self.name}]: speculative draft {dsrc!r} has "
                f"vocab {dvocab}, target serves {self.vocab} — rejection "
                "sampling needs one symbol set (deploy a matching-vocab "
                "draft, or fix the tokenizer mismatch upstream)")
        self.draft = draft
        self._verify_jit = jax.jit(self._verify_fn, donate_argnums=(1, 2))
        draft._propose_jit = jax.jit(
            functools.partial(draft._spec_propose_fn, k),
            donate_argnums=(1, 2))

    @property
    def spec_enabled(self) -> bool:
        return self.draft is not None

    # --------------------------------------------------------- the forward
    def _forward_tokens(self, params, tokens, mask):
        """(B, T) ids -> ((B, T, V) pre-softmax logits, per-block roped
        (K, V) lists). The same primitive calls as the stock layers'
        apply() so full-sequence logits are bitwise those of
        net.output() at valid positions."""
        x = None
        kvs = []
        t = tokens.shape[1]
        pos = jnp.arange(t)[None]
        for kind, layer, key in self._plan:
            p = params[key]
            if kind == "embed":
                x = qtake(p["W"], tokens)
                if mask is not None:
                    x = x * mask[..., None].astype(x.dtype)
            elif kind == "posembed":
                x = x + p["P"][:t][None]
            elif kind == "pertoken":
                x, _ = layer.apply(p, {}, x, train=False, rng=None,
                                   mask=mask)
            elif kind == "block":
                x, k, v = self._block_full(layer, p, x, mask, pos)
                kvs.append((k, v))
            else:                                           # head
                z = qdot(x, p["W"])
                if "b" in p:
                    z = z + p["b"]
                x = z
        return x, kvs

    def _block_full(self, conf, p, x, mask, pos):
        """TransformerBlock full-sequence forward, returning the roped
        K / raw V the cache stores. Mirrors TransformerBlock.apply's
        dense path operation-for-operation."""
        h, _ = _LN.apply(p["ln1"], {}, x)
        a = p["attn"]
        q = _split_heads(qdot(h, a["Wq"]), conf.n_heads)
        k = _split_heads(qdot(h, a["Wk"]), conf.n_heads)
        v = _split_heads(qdot(h, a["Wv"]), conf.n_heads)
        if conf.use_rope:
            q = rope(q, pos)
            k = rope(k, pos)
        out = dot_product_attention(q, k, v, mask=mask, causal=conf.causal)
        y = qdot(_merge_heads(out), a["Wo"])
        if mask is not None:
            y = y * mask[..., None].astype(y.dtype)
        x = x + y
        h, _ = _LN.apply(p["ln2"], {}, x)
        h = get_activation(conf.activation)(qdot(h, p["W1"]) + p["b1"])
        h = qdot(h, p["W2"]) + p["b2"]
        y = x + h
        if mask is not None:
            y = y * mask[..., None].astype(y.dtype)
        return y, k, v

    def _block_decode(self, conf, p, li, x, kpool, vpool, page_table,
                      seq_lens, active, pos):
        """One-token incremental block forward against the paged cache."""
        s = x.shape[0]
        h, _ = _LN.apply(p["ln1"], {}, x)
        a = p["attn"]
        q = _split_heads(qdot(h, a["Wq"]), conf.n_heads)
        k = _split_heads(qdot(h, a["Wk"]), conf.n_heads)
        v = _split_heads(qdot(h, a["Wv"]), conf.n_heads)
        if conf.use_rope:
            q = rope(q, pos)
            k = rope(k, pos)
        ps = self.cfg.page_size
        page_idx = seq_lens // ps
        phys = page_table[jnp.arange(s), page_idx]
        # inactive slots write their garbage row to the dump page
        phys = jnp.where(active, phys, kvcache.DUMP_PAGE)
        kpool, vpool = kvcache.append_token_kv(
            kpool, vpool, li, k[:, 0], v[:, 0], phys, seq_lens % ps)
        keys, vals = kvcache.gather_kv(kpool, vpool, li, page_table,
                                       self.max_context)
        # validity: cached positions 0..seq_len INCLUSIVE (the row this
        # step just appended is position seq_len)
        mask = (jnp.arange(self.max_context)[None, :]
                <= seq_lens[:, None]).astype(jnp.float32)
        out = dot_product_attention(q, keys, vals, mask=mask, causal=False)
        y = qdot(_merge_heads(out), a["Wo"])
        x = x + y
        h, _ = _LN.apply(p["ln2"], {}, x)
        h = get_activation(conf.activation)(qdot(h, p["W1"]) + p["b1"])
        h = qdot(h, p["W2"]) + p["b2"]
        return x + h, kpool, vpool

    def _block_chunk(self, conf, p, li, x, kpool, vpool, page_row, pos,
                     valid, start, mask):
        """Incremental block forward for a prefill CHUNK: Tb suffix
        tokens of ONE slot at absolute positions `pos` (= start +
        arange), attending the paged cache — cached prefix pages AND the
        chunk's own rows, written first. Chunks may start mid-page (the
        COW divergence recompute does), so rows scatter by absolute
        (page, offset), padding rows steered to the dump page."""
        h, _ = _LN.apply(p["ln1"], {}, x)
        a = p["attn"]
        q = _split_heads(qdot(h, a["Wq"]), conf.n_heads)
        k = _split_heads(qdot(h, a["Wk"]), conf.n_heads)
        v = _split_heads(qdot(h, a["Wv"]), conf.n_heads)
        if conf.use_rope:
            q = rope(q, pos[None])
            k = rope(k, pos[None])
        ps = self.cfg.page_size
        page_idx = jnp.clip(pos // ps, 0, page_row.shape[0] - 1)
        phys = jnp.where(valid, page_row[page_idx], kvcache.DUMP_PAGE)
        kpool, vpool = kvcache.write_chunk_kv(
            kpool, vpool, li, k[0], v[0], phys, pos % ps)
        keys, vals = kvcache.gather_kv(kpool, vpool, li, page_row[None],
                                       self.max_context)
        # validity is pure causality: every cached position < a query's
        # absolute position was written (by a donor prefill, an earlier
        # chunk, or this chunk's own scatter above); positions >= end sit
        # past every valid query and the causal mask excludes them
        out = dot_product_attention(q, keys, vals, mask=None, causal=True,
                                    q_offset=start)
        y = qdot(_merge_heads(out), a["Wo"])
        y = y * mask[..., None].astype(y.dtype)
        x = x + y
        h, _ = _LN.apply(p["ln2"], {}, x)
        h = get_activation(conf.activation)(qdot(h, p["W1"]) + p["b1"])
        h = qdot(h, p["W2"]) + p["b2"]
        y = x + h
        return y * mask[..., None].astype(y.dtype), kpool, vpool

    # ----------------------------------------------------------- sampling
    def _sample(self, logits, temps, topks, counter):
        """Greedy / temperature / top-k, per slot, in-graph (Gumbel-max:
        one argmax regardless of temperature)."""
        lg = logits.astype(jnp.float32)
        s, v = lg.shape
        greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        kmax = min(TOP_K_MAX, v)
        top_vals, _ = jax.lax.top_k(lg, kmax)
        kth = top_vals[jnp.arange(s), jnp.clip(topks, 1, kmax) - 1]
        keep = (topks <= 0)[:, None] | (lg >= kth[:, None])
        filt = jnp.where(keep, lg, -jnp.inf)
        g = jax.random.gumbel(jax.random.fold_in(self._base_key, counter),
                              lg.shape, jnp.float32)
        safe_t = jnp.where(temps > 0, temps, 1.0)[:, None]
        sampled = jnp.argmax(filt / safe_t + g, axis=-1).astype(jnp.int32)
        return jnp.where(temps > 0, sampled, greedy)

    # ------------------------------------------------------- jitted bodies
    def _prefill_fn(self, params, kpool, vpool, tokens, length, page_row,
                    temp, topk, counter):
        """tokens (1, Tb); length (); page_row (pages_per_slot,). Returns
        (kpool, vpool, first sampled token (), last-position logits (V,))."""
        tb = tokens.shape[1]
        mask = (jnp.arange(tb)[None] < length).astype(jnp.float32)
        logits, kvs = self._forward_tokens(params, tokens, mask)
        for li, (k, v) in enumerate(kvs):
            kpool, vpool = kvcache.write_prompt_kv(
                kpool, vpool, li, k[0], v[0], page_row, self.cfg.page_size)
        last = jnp.take(logits[0], length - 1, axis=0)
        tok = self._sample(last[None], temp[None], topk[None], counter)[0]
        return kpool, vpool, tok, last

    def _chunk_fn(self, params, kpool, vpool, tokens, start, end, page_row,
                  temp, topk, counter):
        """Suffix-chunk prefill: tokens (1, Tb) are prompt positions
        [start, end) of one slot (bucket-padded past end - start), with
        everything before `start` already cached in the slot's pages
        (shared prefix and/or earlier chunks). start/end are traced
        scalars — ONE compiled program per bucket serves every cache-hit
        length and every chunk of the ladder. Returns (kpool, vpool,
        sampled token (), last-valid-position logits (V,)) — the sample
        is only meaningful on the final chunk (end == prompt length)."""
        tb = tokens.shape[1]
        pos = start + jnp.arange(tb)
        valid = pos < end
        mask = valid.astype(jnp.float32)[None]          # (1, Tb)
        x = None
        for kind, layer, key in self._plan:
            p = params[key]
            if kind == "embed":
                x = qtake(p["W"], tokens)
                x = x * mask[..., None].astype(x.dtype)
            elif kind == "posembed":
                idx = jnp.clip(pos, 0, layer.max_length - 1)
                x = x + jnp.take(p["P"], idx, axis=0)[None]
            elif kind == "pertoken":
                x, _ = layer.apply(p, {}, x, train=False, rng=None,
                                   mask=mask)
            elif kind == "block":
                x, kpool, vpool = self._block_chunk(
                    layer, p, self._block_index[key], x, kpool, vpool,
                    page_row, pos, valid, start, mask)
            else:                                       # head
                z = qdot(x, p["W"])
                if "b" in p:
                    z = z + p["b"]
                x = z
        last = jnp.take(x[0], jnp.clip(end - 1 - start, 0, tb - 1), axis=0)
        tok = self._sample(last[None], temp[None], topk[None], counter)[0]
        return kpool, vpool, tok, last

    def _step_body(self, params, kpool, vpool, page_table, seq_lens,
                   tokens, active):
        """The one-token decode forward shared — primitive call for
        primitive call — by the decode step AND each unrolled position of
        the speculative verify/propose programs: identical subgraphs are
        what makes verify logits bitwise-equal to sequential decode steps
        (the oracle greedy spec-parity rests on). Returns (kpool, vpool,
        logits (S, V))."""
        pos = seq_lens[:, None]
        x = None
        for kind, layer, key in self._plan:
            p = params[key]
            if kind == "embed":
                x = qtake(p["W"], tokens)[:, None, :]
            elif kind == "posembed":
                idx = jnp.clip(seq_lens, 0, layer.max_length - 1)
                x = x + jnp.take(p["P"], idx, axis=0)[:, None, :]
            elif kind == "pertoken":
                x, _ = layer.apply(p, {}, x, train=False, rng=None,
                                   mask=None)
            elif kind == "block":
                x, kpool, vpool = self._block_decode(
                    layer, p, self._block_index[key], x, kpool, vpool,
                    page_table, seq_lens, active, pos)
            else:
                z = qdot(x, p["W"])
                if "b" in p:
                    z = z + p["b"]
                x = z
        return kpool, vpool, x[:, 0, :]

    def _decode_fn(self, params, kpool, vpool, page_table, seq_lens,
                   tokens, active, temps, topks, counter):
        """One token for every slot (inactive slots compute masked
        garbage into the dump page). Returns (kpool, vpool, sampled (S,),
        logits (S, V))."""
        kpool, vpool, logits = self._step_body(
            params, kpool, vpool, page_table, seq_lens, tokens, active)
        toks = self._sample(logits, temps, topks, counter)
        return kpool, vpool, toks, logits

    def _verify_fn(self, params, kpool, vpool, page_table, seq_lens,
                   tokens, drafted, active):
        """The speculative verify: score k+1 positions per slot in ONE
        fixed-shape program — position 0 consumes the stream's last
        sampled token, positions 1..k consume the draft's proposals —
        writing each position's KV as it goes (rejected-tail rows land
        past the post-acceptance seq_len; the validity mask hides them
        until the next round overwrites). k+1 unrolled `_step_body`
        calls, NOT a chunked-attention reformulation: per-position logits
        must be bitwise those of k+1 sequential decode steps. Returns
        (kpool, vpool, logits (S, k+1, V))."""
        k = drafted.shape[1]
        outs = []
        tok = tokens
        for i in range(k + 1):
            kpool, vpool, logits = self._step_body(
                params, kpool, vpool, page_table, seq_lens + i, tok,
                active)
            outs.append(logits)
            if i < k:
                tok = drafted[:, i]
        return kpool, vpool, jnp.stack(outs, axis=1)

    def _spec_propose_fn(self, k, params, kpool, vpool, page_table,
                         seq_lens, tokens, active, temps, topks, counter):
        """The draft's fused propose program: k autoregressive tokens per
        slot in ONE dispatch (sampled in-graph, each fed to the next
        position), plus one extra body that consumes the k-th sample so
        the draft cache covers every token the target may accept — the
        next round then always resumes from exactly one new token
        regardless of where acceptance stopped. Returns (kpool, vpool,
        drafted (S, k), draft logits (S, k, V)); the logits give the
        host-side rejection sampler its q distribution."""
        drafted = []
        qlogits = []
        tok = tokens
        for i in range(k):
            kpool, vpool, logits = self._step_body(
                params, kpool, vpool, page_table, seq_lens + i, tok,
                active)
            tok = self._sample(logits, temps, topks, counter + i)
            drafted.append(tok)
            qlogits.append(logits)
        kpool, vpool, _ = self._step_body(
            params, kpool, vpool, page_table, seq_lens + k, tok, active)
        return (kpool, vpool, jnp.stack(drafted, axis=1),
                jnp.stack(qlogits, axis=1))

    def _logits_fn(self, params, tokens):
        """(B, T) -> (B, T, V) full-sequence pre-softmax logits (parity /
        quality scoring; never on the request path)."""
        return self._forward_tokens(params, tokens, None)[0]

    # ------------------------------------------------- tiered KV fabric
    def _extract_fn(self, kpool, vpool, page):
        """Read one physical page across every layer -> (K, V) each
        shaped (L, page_size, H, D). `page` is a traced scalar; the
        pools are NOT donated (the page must survive its own export)."""
        return (jax.lax.dynamic_index_in_dim(kpool, page, axis=1,
                                             keepdims=False),
                jax.lax.dynamic_index_in_dim(vpool, page, axis=1,
                                             keepdims=False))

    def _land_fn(self, kpool, vpool, page, kpage, vpage):
        """Write one (L, page_size, H, D) K/V pair into physical page
        `page` (traced scalar), donating the pools like every writer."""
        kpool = kpool.at[:, page].set(kpage)
        vpool = vpool.at[:, page].set(vpage)
        return kpool, vpool

    def _demote_page(self, page: int, digest: bytes) -> bytes:
        """Spill-extract callback: one HBM page -> a packed, sealed
        frame. SCHEDULER THREAD ONLY (the pools are donated buffers)."""
        self._meter_program("kv_extract", warmup=False)
        with monitor.span("serving/kv_extract", model=self.name):
            k, v = self._extract_jit(self._kpool, self._vpool,
                                     np.int32(page))
        return kvfabric.pack_page(np.asarray(k), np.asarray(v), digest)

    def _land_frame(self, page: int, payload: bytes, digest: bytes):
        """Spill-land callback: verify + write one frame into physical
        page `page`. Raises kvfabric.FrameError on corruption or a
        geometry that does not fit this pool — a clean rejection the
        caller degrades from. SCHEDULER THREAD ONLY."""
        k, v, _ = kvfabric.unpack_page(payload, expect_digest=digest)
        shape = (self.n_layers, self.cfg.page_size, self.n_heads,
                 self.head_dim)
        want = np.dtype(self._dtype)
        if k.shape != shape or k.dtype != want or v.dtype != want:
            raise kvfabric.FrameError(
                f"frame geometry {k.shape}/{k.dtype} does not fit pool "
                f"{shape}/{want} (mismatched model or quantize mode)")
        self._meter_program("kv_land", warmup=False)
        with monitor.span("serving/kv_land", model=self.name):
            self._kpool, self._vpool = self._land_jit(
                self._kpool, self._vpool, np.int32(page),
                jnp.asarray(k), jnp.asarray(v))

    def export_pages(self, tokens) -> List[bytes]:
        """Serialize the cached pages covering `tokens`' full blocks
        (which must all be radix-indexed — the caller prefills first)
        into sealed frames for a disaggregated transfer. SCHEDULER
        THREAD ONLY (runs as a fabric job)."""
        _, keys = self.cache._blocks(tokens)
        with self.cache._lock:
            node, pages = self.cache._walk_locked(keys)
            if len(pages) < len(keys):
                raise RuntimeError(
                    f"decode[{self.name}]: prefix fell out of the cache "
                    f"mid-export ({len(pages)}/{len(keys)} blocks "
                    "indexed); retry after re-prefilling")
            digests = kvfabric.chain_digests(keys)
        frames = []
        for page, dig in zip(pages, digests):
            self._meter_program("kv_extract", warmup=False)
            with monitor.span("serving/kv_extract", model=self.name):
                k, v = self._extract_jit(self._kpool, self._vpool,
                                         np.int32(page))
            frames.append(kvfabric.pack_page(np.asarray(k),
                                             np.asarray(v), dig))
        return frames

    def import_pages(self, tokens, frames: List[bytes]) -> int:
        """Adopt a shipment of sealed frames as this cache's retained
        prefix pages (the disaggregated-prefill landing). Frame i lands
        for block i via the verified land program; corruption raises
        kvfabric.FrameError cleanly. SCHEDULER THREAD ONLY."""
        _, keys = self.cache._blocks(tokens)
        if len(frames) != len(keys):
            raise kvfabric.FrameError(
                f"shipment has {len(frames)} frames for {len(keys)} "
                "full token blocks")
        digests = kvfabric.chain_digests(keys)

        def land(i: int, page: int):
            self._land_frame(page, frames[i], digests[i])

        return self.cache.adopt_pages(tokens, land)

    # ----------------------------------------------------- compile ledger
    def _meter_program(self, program: str, warmup: bool):
        if program in self._compiled:
            return
        self._compiled.add(program)
        monitor.counter(
            "serving_decode_compiles_total",
            "First executions of a decode-runtime program per engine "
            "generation (each implies one XLA compile)",
            labels=("model", "program")).inc(model=self.name,
                                             program=program)
        if not warmup:
            log.warning(
                "decode[%s]: program %s first executed on the REQUEST "
                "path (compile latency hit a live stream) — warm() was "
                "skipped or the ladder changed", self.name, program)

    def warm(self):
        """AOT-execute every prefill bucket and the decode step so no
        live stream ever waits on XLA. Installed counters satisfy
        compiles == warmups on /metrics (the generation ledger)."""
        t0 = time.perf_counter()
        dump_row = np.full((self.cache.pages_per_slot,),
                           kvcache.DUMP_PAGE, np.int32)
        # one handle, one help string: the registry is first-caller-wins
        # on help text, so retyping it per warmup site invites the
        # /metrics-vs-docs drift this family's ledger exists to prevent
        warmups = monitor.counter(
            "serving_decode_warmup_runs_total",
            "AOT decode-runtime warmup executions (one per program per "
            "engine generation)", labels=("model",))
        for tb in self.prefill_buckets:
            self._meter_program(f"prefill_{tb}", warmup=True)
            with monitor.span("serving/prefill", model=self.name,
                              bucket=tb, warmup=1):
                self._kpool, self._vpool, _, _ = self._prefill_jit(
                    self._params, self._kpool, self._vpool,
                    np.zeros((1, tb), np.int32), np.int32(1), dump_row,
                    np.float32(0), np.int32(0), np.uint32(0))
            warmups.inc(model=self.name)
        # the chunk ladder: suffix prefill after a cache hit and budgeted
        # chunks of a long prompt run through these — same buckets, one
        # extra program each (start/end are operands, not shapes)
        for tb in self.prefill_buckets:
            self._meter_program(f"chunk_{tb}", warmup=True)
            with monitor.span("serving/prefill_chunk", model=self.name,
                              bucket=tb, warmup=1):
                self._kpool, self._vpool, _, _ = self._chunk_jit(
                    self._params, self._kpool, self._vpool,
                    np.zeros((1, tb), np.int32), np.int32(0), np.int32(1),
                    dump_row, np.float32(0), np.int32(0), np.uint32(0))
            warmups.inc(model=self.name)
        # the COW page copy (dump -> dump during warmup: page 0 is
        # garbage by contract, so the no-op-shaped copy is safe)
        self._meter_program("cow_copy", warmup=True)
        with monitor.span("serving/kv_cow", model=self.name, warmup=1):
            self._kpool, self._vpool = self._copy_jit(
                self._kpool, self._vpool, np.int32(kvcache.DUMP_PAGE),
                np.int32(kvcache.DUMP_PAGE))
        warmups.inc(model=self.name)
        # the KV-fabric page programs (spill demote/promote + the
        # disaggregated transfer path): extract reads the dump page,
        # land writes the extracted garbage straight back to it
        self._meter_program("kv_extract", warmup=True)
        with monitor.span("serving/kv_extract", model=self.name, warmup=1):
            kx, vx = self._extract_jit(self._kpool, self._vpool,
                                       np.int32(kvcache.DUMP_PAGE))
        warmups.inc(model=self.name)
        self._meter_program("kv_land", warmup=True)
        with monitor.span("serving/kv_land", model=self.name, warmup=1):
            self._kpool, self._vpool = self._land_jit(
                self._kpool, self._vpool, np.int32(kvcache.DUMP_PAGE),
                kx, vx)
        warmups.inc(model=self.name)
        self._meter_program("decode", warmup=True)
        with monitor.span("serving/decode_step", model=self.name, warmup=1):
            s = self.cfg.slots
            self._kpool, self._vpool, _, _ = self._decode_jit(
                self._params, self._kpool, self._vpool,
                np.asarray(self.cache.page_table),
                np.zeros((s,), np.int32), np.zeros((s,), np.int32),
                np.zeros((s,), bool), np.zeros((s,), np.float32),
                np.zeros((s,), np.int32), np.uint32(0))
        warmups.inc(model=self.name)
        if self.draft is not None:
            # the draft engine warms its own ledger (programs metered
            # under "<name>.draft"), then the two speculative programs:
            # the fused k-token propose (draft's) and the k+1-position
            # verify (target's) — zero request-path compiles with
            # speculation live is part of the compiles==warmups contract
            d = self.draft
            d.warm()
            k = int(self.cfg.spec_k)
            ds = d.cfg.slots
            d._meter_program(f"draft_{k}", warmup=True)
            with monitor.span("serving/spec_draft", model=self.name,
                              warmup=1):
                d._kpool, d._vpool, _, _ = d._propose_jit(
                    d._params, d._kpool, d._vpool,
                    np.asarray(d.cache.page_table),
                    np.zeros((ds,), np.int32), np.zeros((ds,), np.int32),
                    np.zeros((ds,), bool), np.zeros((ds,), np.float32),
                    np.zeros((ds,), np.int32), np.uint32(0))
            warmups.inc(model=d.name)
            self._meter_program(f"verify_{k + 1}", warmup=True)
            with monitor.span("serving/spec_verify", model=self.name,
                              warmup=1):
                self._kpool, self._vpool, _ = self._verify_jit(
                    self._params, self._kpool, self._vpool,
                    np.asarray(self.cache.page_table),
                    np.zeros((s,), np.int32), np.zeros((s,), np.int32),
                    np.zeros((s, k), np.int32), np.zeros((s,), bool))
            warmups.inc(model=self.name)
        monitor.histogram(
            "serving_decode_warmup_seconds",
            "Full decode-runtime warmup duration (buckets + step)",
            labels=("model",),
            buckets=(0.1, 0.5, 1, 2.5, 5, 10, 30, 60, 120)).observe(
            time.perf_counter() - t0, model=self.name)

    # ------------------------------------------------------------ host API
    def bucket_for(self, n: int) -> int:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        return self.prefill_buckets[-1]

    def admit_prompt(self, prompt: np.ndarray
                     ) -> Optional[kvcache.AdmitInfo]:
        """Token-aware admission: claim a slot, map the longest cached
        prefix read-shared, and resolve any copy-on-write divergence
        on-device (the forced last-token recompute of a fully-cached
        page-aligned prompt writes into a private page copy, never into
        the shared one). None when slots/pages are exhausted."""
        info = self.cache.admit_prompt(prompt)
        if info is None:
            return None
        if info.cow_src is not None:
            try:
                self._meter_program("cow_copy", warmup=False)
                with monitor.span("serving/kv_cow", model=self.name):
                    self._kpool, self._vpool = self._copy_jit(
                        self._kpool, self._vpool, np.int32(info.cow_src),
                        np.int32(info.cow_dst))
            except Exception:
                # a failed copy must not leak the slot or the pinned
                # source page — undo the admission before surfacing
                self.cache.release(info.slot)
                self.cache.unref_page(info.cow_src)
                raise
            self.cache.unref_page(info.cow_src)
        if self.draft is not None:
            self._admit_draft(info.slot, prompt)
        return info

    def _admit_draft(self, slot: int, prompt: np.ndarray):
        """Mirror a successful target admission into the draft's (own,
        typically smaller) pool. A dry draft pool never blocks the
        stream — it just decodes plain (speculation off, metered as a
        fallback)."""
        self._spec_on[slot] = True
        self._spec_hist[slot].clear()
        dinfo = None
        try:
            dinfo = self.draft.admit_prompt(
                np.asarray(prompt, np.int32))
        except Exception:   # noqa: BLE001 — draft trouble must never
            # take down an admission the target already accepted
            log.exception("decode[%s]: draft admission failed; stream "
                          "decodes plain", self.name)
        if dinfo is None:
            self.spec_disable(slot, "draft_admit")
        else:
            self._draft_slots[slot] = int(dinfo.slot)
            self._draft_origin[slot] = int(dinfo.cached_len)

    def spec_disable(self, slot: int, reason: str):
        """Turn speculation off for ONE stream (it plain-decodes to
        completion) and free its draft pages for the streams still
        speculating. Metered per reason: draft_admit / draft_prefill /
        draft_pages / acceptance_floor."""
        self._spec_on[slot] = False
        ds = self._draft_slots.get(slot)
        self._draft_slots[slot] = None
        if ds is not None and self.draft is not None:
            self.draft.cache.release(ds)
        monitor.counter(
            "serving_decode_spec_fallbacks_total",
            "Streams whose speculation turned off (draft pool dry, "
            "draft prefill failure, or rolling acceptance under the "
            "floor)", labels=("model", "reason")).inc(
            model=self.name, reason=reason)

    def release_slot(self, slot: int):
        """Release a finished stream's target slot AND its draft mirror
        (scheduler call sites use this, never cache.release directly)."""
        self.cache.release(slot)
        if self.draft is not None:
            ds = self._draft_slots.pop(slot, None)
            self._draft_origin.pop(slot, None)
            if ds is not None:
                self.draft.cache.release(ds)
            self._spec_on[slot] = True
            self._spec_hist[slot].clear()

    def draft_prefill_origin(self, slot: int) -> Optional[int]:
        """Where the draft's prefill starts for this stream (its own
        cached-prefix length), or None when the stream speculates not."""
        if self.draft is None or self._draft_slots.get(slot) is None:
            return None
        return self._draft_origin.get(slot, 0)

    def draft_prefill(self, slot: int, prompt: np.ndarray, start: int,
                      n: int, temperature: float, top_k: int):
        """Advance the draft's prefill for `slot` by prompt positions
        [start, start+n) — same dense-vs-chunk split as the target's
        path; the sampled token is discarded (the stream's first token
        comes from the TARGET's prefill)."""
        ds = self._draft_slots[slot]
        if start == 0 and n == len(prompt):
            self.draft.prefill(ds, prompt, temperature, top_k)
        else:
            self.draft.prefill_chunk(ds, prompt, start, n, temperature,
                                     top_k)

    def draft_prefill_done(self, slot: int, prompt: np.ndarray):
        """Draft prefill complete: index the draft's prompt pages so the
        NEXT admission of this prefix is a draft-side cache hit too."""
        ds = self._draft_slots.get(slot)
        if ds is not None:
            self.draft.cache.register_prefix(ds, prompt)

    def prefill_chunk(self, slot: int, prompt: np.ndarray, start: int,
                      n: int, temperature: float, top_k: int) -> int:
        """Run prompt positions [start, start+n) through the paged-cache
        chunk program into `slot`'s pages (everything before `start` is
        already cached there). Returns the sampled token — meaningful
        only when this was the final chunk (start+n == len(prompt))."""
        tb = self.bucket_for(n)
        toks = np.zeros((1, tb), np.int32)
        toks[0, :n] = prompt[start:start + n]
        self._temps[slot] = temperature
        self._topks[slot] = top_k
        self._counter += 1
        self._meter_program(f"chunk_{tb}", warmup=False)
        with monitor.span("serving/prefill_chunk", model=self.name,
                          bucket=tb, tokens=n):
            self._kpool, self._vpool, tok, _ = self._chunk_jit(
                self._params, self._kpool, self._vpool, toks,
                np.int32(start), np.int32(start + n),
                self.cache.page_table[slot].copy(),
                np.float32(temperature), np.int32(top_k),
                np.uint32(self._counter & 0xFFFFFFFF))
        monitor.counter("serving_decode_prefills_total",
                        "Prefill program executions by bucket size "
                        "(chunk_* buckets are suffix/chunked prefills)",
                        labels=("model", "bucket")).inc(
            model=self.name, bucket=f"chunk_{tb}")
        tok = int(tok)
        self._last_tokens[slot] = tok
        return tok

    def prefill(self, slot: int, prompt: np.ndarray, temperature: float,
                top_k: int) -> Tuple[int, np.ndarray]:
        """Run the prompt through a bucket-padded prefill into `slot`'s
        pages; returns (first sampled token, last-position logits)."""
        n = int(len(prompt))
        tb = self.bucket_for(n)
        toks = np.zeros((1, tb), np.int32)
        toks[0, :n] = prompt
        self._temps[slot] = temperature
        self._topks[slot] = top_k
        self._counter += 1
        self._meter_program(f"prefill_{tb}", warmup=False)
        with monitor.span("serving/prefill", model=self.name, bucket=tb):
            self._kpool, self._vpool, tok, logits = self._prefill_jit(
                self._params, self._kpool, self._vpool, toks,
                np.int32(n), self.cache.page_table[slot].copy(),
                np.float32(temperature), np.int32(top_k),
                np.uint32(self._counter & 0xFFFFFFFF))
        monitor.counter("serving_decode_prefills_total",
                        "Prefill program executions by bucket size "
                        "(chunk_* buckets are suffix/chunked prefills)",
                        labels=("model", "bucket")).inc(
            model=self.name, bucket=str(tb))
        tok = int(tok)
        self._last_tokens[slot] = tok
        return tok, np.asarray(logits, np.float32)

    def step(self, exclude=()) -> Tuple[np.ndarray, np.ndarray,
                                        np.ndarray]:
        """One decode iteration over every runnable slot. Returns
        (sampled tokens (S,), runnable mask (S,), logits (S, V)); slots
        not in the mask were inactive, excluded (mid-prefill), page-
        stalled, or at the context cap and produced garbage."""
        act = np.zeros((self.cfg.slots,), bool)
        excl = frozenset(int(s) for s in exclude)
        n_runnable = 0
        for s in self.cache.active_slots():
            if s in excl:
                continue                # prefill still in flight
            if self.cache.ensure_page(s):
                act[s] = True
                n_runnable += 1
        self._counter += 1
        self._meter_program("decode", warmup=False)
        with monitor.span("serving/decode_step", model=self.name,
                          active=n_runnable):
            self._kpool, self._vpool, toks, logits = self._decode_jit(
                self._params, self._kpool, self._vpool,
                np.asarray(self.cache.page_table),
                np.asarray(self.cache.seq_lens), self._last_tokens.copy(),
                act, self._temps.copy(), self._topks.copy(),
                np.uint32(self._counter & 0xFFFFFFFF))
        toks_np = np.asarray(toks)
        for s in np.nonzero(act)[0]:
            self.cache.advance(int(s))
            self._last_tokens[s] = toks_np[s]
        monitor.counter("serving_decode_steps_total",
                        "Compiled decode iterations executed",
                        labels=("model",)).inc(model=self.name)
        return toks_np, act, np.asarray(logits, np.float32)

    # ------------------------------------------------- speculative decoding
    def _spec_dist(self, logits, temp: float, topk: int) -> np.ndarray:
        """The sampling distribution `_sample` draws from, recomputed on
        the host (float64): top-k filtering with the SAME clip against
        TOP_K_MAX, then temperature softmax. Rejection sampling is only
        exact when this q/p matches the in-graph Gumbel-max sampler's
        distribution term for term."""
        lg = np.asarray(logits, np.float64)
        v = lg.shape[-1]
        if topk > 0:
            kk = min(max(int(topk), 1), min(TOP_K_MAX, v))
            kth = np.sort(lg)[-kk]
            lg = np.where(lg >= kth, lg, -np.inf)
        z = lg / max(float(temp), 1e-30)
        z = z - z.max()
        p = np.exp(z)
        return p / p.sum()

    def _spec_accept(self, drafted, vlog, qlog, temp: float, topk: int
                     ) -> Tuple[int, int]:
        """Accept/reject one stream's k draft proposals against the
        target's k+1 verify logits. Returns (accepted count a, the one
        extra token): greedy is exact prefix-match on argmax with the
        target's own argmax at the first mismatch (bitwise the
        non-speculative stream); temperature is true rejection sampling
        — accept d_i with prob min(1, p(d_i)/q(d_i)), resample the first
        rejection from the residual max(p - q, 0), and on full
        acceptance sample the bonus token from the target's (k+1)-th
        distribution."""
        k = len(drafted)
        if temp <= 0:
            a = 0
            for i in range(k):
                if int(np.argmax(vlog[i])) == int(drafted[i]):
                    a += 1
                else:
                    break
            return a, int(np.argmax(vlog[a]))
        for i in range(k):
            d = int(drafted[i])
            p = self._spec_dist(vlog[i], temp, topk)
            q = self._spec_dist(qlog[i], temp, topk)
            if q[d] > 0.0 and self._spec_rng.random_sample() \
                    < min(1.0, float(p[d]) / float(q[d])):
                continue
            res = np.maximum(p - q, 0.0)
            tot = float(res.sum())
            if tot <= 0.0:
                res, tot = p, float(p.sum())    # p == q: any sample of
                # p is already correctly distributed
            return i, int(self._spec_rng.choice(len(res), p=res / tot))
        p = self._spec_dist(vlog[k], temp, topk)
        return k, int(self._spec_rng.choice(len(p), p=p / p.sum()))

    def spec_step(self, exclude=()) -> Dict[int, dict]:
        """One speculative round over every eligible stream: the draft
        proposes k tokens for all of them in ONE dispatch, the target
        scores all k+1 positions in ONE dispatch, and the host accepts
        per slot. Both caches advance by accepted+1 (the draft's propose
        program already consumed its own k-th sample, so whatever prefix
        survives, the next round resumes from exactly one new token).

        Returns {slot: {"tokens": [...], "proposed": k, "accepted": a}}
        for every slot handled this round — the scheduler emits those
        bursts and excludes the slots from the plain step. Slots under
        page/context pressure are simply left for the plain path this
        round; a dry DRAFT pool or a collapsed acceptance window turns
        speculation off for that stream (`spec_disable`)."""
        if self.draft is None:
            return {}
        k = int(self.cfg.spec_k)
        excl = frozenset(int(s) for s in exclude)
        pairs = []
        for s in self.cache.active_slots():
            if s in excl or not self._spec_on[s]:
                continue
            ds = self._draft_slots.get(s)
            if ds is None:
                continue
            if not self.cache.ensure_capacity(s, k + 1):
                # target page stall or context cap: the plain step's
                # per-token path copes (and finishes length_cap streams)
                continue
            if not self.draft.cache.ensure_capacity(ds, k + 1):
                self.spec_disable(s, "draft_pages")
                continue
            pairs.append((s, ds))
        if not pairs:
            return {}
        d = self.draft
        dact = np.zeros((d.cfg.slots,), bool)
        dtok = d._last_tokens.copy()
        for s, ds in pairs:
            dact[ds] = True
            # the draft extends the TARGET's stream: it consumes the
            # target's last sampled token, not its own prefill sample
            dtok[ds] = self._last_tokens[s]
        d._counter += k
        d._meter_program(f"draft_{k}", warmup=False)
        with monitor.span("serving/spec_draft", model=self.name,
                          active=len(pairs)):
            d._kpool, d._vpool, drafted, qlog = d._propose_jit(
                d._params, d._kpool, d._vpool,
                np.asarray(d.cache.page_table),
                np.asarray(d.cache.seq_lens), dtok, dact,
                d._temps.copy(), d._topks.copy(),
                np.uint32((d._counter - k + 1) & 0xFFFFFFFF))
        drafted = np.asarray(drafted)
        qlog = np.asarray(qlog, np.float32)
        tact = np.zeros((self.cfg.slots,), bool)
        vdraft = np.zeros((self.cfg.slots, k), np.int32)
        for s, ds in pairs:
            tact[s] = True
            vdraft[s] = drafted[ds]
        self._meter_program(f"verify_{k + 1}", warmup=False)
        with monitor.span("serving/spec_verify", model=self.name,
                          active=len(pairs)):
            self._kpool, self._vpool, vlog = self._verify_jit(
                self._params, self._kpool, self._vpool,
                np.asarray(self.cache.page_table),
                np.asarray(self.cache.seq_lens),
                self._last_tokens.copy(), vdraft, tact)
        vlog = np.asarray(vlog, np.float32)
        out: Dict[int, dict] = {}
        n_prop = n_acc = 0
        ratio = monitor.histogram(
            "serving_decode_spec_acceptance_ratio",
            "Per-stream-per-round fraction of draft proposals the "
            "verifier accepted (accepted / k)", labels=("model",),
            buckets=(0.0, 0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875,
                     1.0))
        floor = float(self.cfg.spec_accept_floor)
        for s, ds in pairs:
            a, extra = self._spec_accept(
                vdraft[s], vlog[s], qlog[ds], float(self._temps[s]),
                int(self._topks[s]))
            for _ in range(a + 1):
                self.cache.advance(s)
                d.cache.advance(ds)
            self._last_tokens[s] = extra
            d._last_tokens[ds] = extra
            n_prop += k
            n_acc += a
            out[s] = {"tokens": [int(t) for t in vdraft[s][:a]]
                      + [int(extra)],
                      "proposed": k, "accepted": a}
            ratio.observe(a / k, model=self.name)
            hist = self._spec_hist[s]
            hist.append((k, a))
            if len(hist) == hist.maxlen:
                pw = sum(p for p, _ in hist)
                aw = sum(acc for _, acc in hist)
                if pw > 0 and aw / pw < floor:
                    self.spec_disable(s, "acceptance_floor")
                    out[s]["fallback"] = "acceptance_floor"
        monitor.counter(
            "serving_decode_spec_proposed_total",
            "Draft tokens proposed to the verifier",
            labels=("model",)).inc(n_prop, model=self.name)
        monitor.counter(
            "serving_decode_spec_accepted_total",
            "Draft tokens the verifier accepted (the speedup is "
            "accepted + rounds extra tokens for 2 dispatches per round)",
            labels=("model",)).inc(n_acc, model=self.name)
        monitor.counter(
            "serving_decode_spec_rounds_total",
            "Speculative draft+verify rounds executed (2 dispatches "
            "each, emitting accepted+1 tokens per handled stream)",
            labels=("model",)).inc(model=self.name)
        return out

    def logits_full(self, tokens) -> np.ndarray:
        """(B, T) -> (B, T, V) float32 logits by full-sequence recompute
        (the parity oracle and the quantization-quality probe)."""
        out = self._logits_jit(self._params,
                               jnp.asarray(np.asarray(tokens, np.int32)))
        return np.asarray(out, np.float32)

    def close(self):
        """Release the page pools (the engine is retired; ~2 * L * P *
        page_size * H * D * dtype bytes come back)."""
        self._closed = True
        self._kpool = self._vpool = None
        self._params = None
        if self.spill is not None:
            self.spill.close()
        if self.draft is not None:
            self.draft.close()

    def describe(self) -> dict:
        d = self.cache.describe()
        d.update({"prefill_buckets": list(self.prefill_buckets),
                  "quantize": self.cfg.quantize,
                  "vocab_size": self.vocab,
                  "n_layers": self.n_layers,
                  "prefill_chunk_tokens": self.prefill_chunk_tokens})
        if self.draft is not None:
            d["spec"] = {"draft": self.cfg.spec_draft,
                         "k": int(self.cfg.spec_k),
                         "accept_floor": float(self.cfg.spec_accept_floor),
                         "window": int(self.cfg.spec_window),
                         "draft_pool": self.draft.cache.describe()}
        return d


# ==========================================================================
# The scheduler: iteration-level admission over one or more engines
# ==========================================================================
class _PrefillJob:
    """Admission-to-first-token state for one slot: the uncached suffix
    [pos, len(prompt)) still to prefill, executed in budgeted chunks
    between decode steps (head-of-line-free prefill)."""

    __slots__ = ("req", "pos", "chunks", "dpos", "tok")

    def __init__(self, req: GenerateRequest, pos: int,
                 dpos: Optional[int] = None):
        self.req = req
        self.pos = pos
        self.chunks = 0
        #: the speculative draft mirror's prefill cursor (None: stream
        #: has no draft slot); the job completes only when BOTH caches
        #: cover the prompt
        self.dpos = dpos
        #: the target's sampled first token, held until the draft mirror
        #: catches up (speculation needs both KV states at the prompt
        #: boundary before the stream's first round)
        self.tok: Optional[int] = None


class _EngineRun:
    """A live engine + the requests bound to its slots. `admitting` is
    True only for the newest engine; older runs drain and retire.
    `prefill` holds slots whose suffix prefill is still chunking (FIFO:
    insertion order is admission order)."""

    __slots__ = ("engine", "version", "admitting", "slot_req", "prefill")

    def __init__(self, engine: DecodeEngine, version: int):
        self.engine = engine
        self.version = version
        self.admitting = True
        self.slot_req: Dict[int, GenerateRequest] = {}
        self.prefill: "OrderedDict[int, _PrefillJob]" = OrderedDict()


class DecodeScheduler:
    """The continuous-batching loop: admit between steps, step every
    engine with live slots, retire drained engines. One daemon thread;
    every device interaction happens on it."""

    def __init__(self, name: str, queue_limit: int = 64):
        self.name = name
        self.queue_limit = int(queue_limit)
        self._pending: deque = deque()
        self._plock = DiagnosedLock(
            "deeplearning4j_tpu.serving.decode.DecodeScheduler._plock")
        self._runs: List[_EngineRun] = []
        self._rlock = DiagnosedLock(
            "deeplearning4j_tpu.serving.decode.DecodeScheduler._rlock")
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._draining = False
        #: KV-fabric jobs (page export/import) marshalled onto the
        #: scheduler thread — the ONLY thread allowed to touch the
        #: donated device pools. Guarded by _plock; (fn, done, box)
        self._fabric: deque = deque()
        # goodput accounting: page-stall slot-seconds apportioned out of
        # the step window by _step_all (stalled/considered share of each
        # step's wall) — read by _loop, only meaningful under the ledger
        self._stall_s = 0.0
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name=f"DecodeScheduler-{name}")
        self._started = False

    # -------------------------------------------------------------- control
    def install(self, engine: DecodeEngine, version: int):
        """Make `engine` the admitting engine; older runs stop admitting
        and retire once their in-flight sequences finish."""
        with self._rlock:
            for run in self._runs:
                run.admitting = False
            self._runs.append(_EngineRun(engine, version))
        if not self._started:
            self._started = True
            self._thread.start()
        self._wake.set()

    def submit(self, req: GenerateRequest):
        if self._draining or self._stop.is_set():
            raise ServerDrainingError(
                f"decode[{self.name}] is shutting down")
        with self._plock:
            if len(self._pending) >= self.queue_limit:
                monitor.counter("serving_decode_rejected_total",
                                "Generation requests rejected by "
                                "admission control",
                                labels=("model", "reason")).inc(
                    model=self.name, reason="queue_full")
                raise ServerOverloadedError(
                    f"decode[{self.name}]: join queue full "
                    f"({self.queue_limit} pending)")
            self._pending.append(req)
            depth = len(self._pending)
        monitor.gauge("serving_decode_queue_depth",
                      "Generation requests waiting for a decode slot",
                      labels=("model",)).set(depth, model=self.name)
        flight.note(req.ctx, "queued", depth=depth, model=self.name)
        self._wake.set()

    def run_fabric(self, fn, timeout: float = 30.0):
        """Run ``fn(engine)`` on the scheduler thread against the
        admitting engine and return its result. The device pools are
        donated by every compiled step, so any HTTP-thread work that
        reads or writes them (page export for a disaggregated transfer,
        shipment import) MUST marshal through here — the job executes
        between ticks, never concurrently with a step. Raises the job's
        own exception, or DeadlineExceededError if the loop never got
        to it within `timeout`."""
        if self._stop.is_set() or self._draining:
            raise ServerDrainingError(
                f"decode[{self.name}] is shutting down")
        box: dict = {}
        done = threading.Event()
        with self._plock:
            self._fabric.append((fn, done, box))
        self._wake.set()
        if not done.wait(timeout):
            raise DeadlineExceededError(
                f"decode[{self.name}]: fabric job did not run within "
                f"{timeout}s (scheduler saturated or stopped)")
        if "exc" in box:
            raise box["exc"]
        return box.get("res")

    def _fabric_tick(self) -> bool:
        """Drain queued fabric jobs on the scheduler thread. A job's
        failure belongs to its submitting thread (delivered through the
        box), never to the loop."""
        if not self._fabric:
            # unlocked empty-check on the common per-pass path: deque
            # reads are atomic under the GIL, and a submit racing this
            # pass sets _wake — the NEXT pass drains it. Skipping the
            # lock keeps the fabric free for the two hot schedulers of
            # an interference pair (no extra GIL handoff per pass)
            return False
        worked = False
        while True:
            with self._plock:
                if not self._fabric:
                    return worked
                fn, done, box = self._fabric.popleft()
            with self._rlock:
                engine = self._runs[-1].engine \
                    if self._runs and self._runs[-1].admitting else None
            try:
                if engine is None:
                    raise ServerDrainingError(
                        f"decode[{self.name}]: no admitting engine for "
                        "fabric job")
                box["res"] = fn(engine)
            except Exception as e:  # noqa: BLE001 — surfaced to the
                # submitting thread via the box; the scheduler loop
                # must outlive any single job's corrupt shipment
                box["exc"] = e
            done.set()
            worked = True

    def queue_state(self) -> Tuple[int, int]:
        with self._plock:
            return len(self._pending), self.queue_limit

    def inflight(self) -> int:
        with self._rlock:
            return sum(len(r.slot_req) + len(r.prefill)
                       for r in self._runs)

    def admitting_engine(self) -> Optional[DecodeEngine]:
        with self._rlock:
            if self._runs and self._runs[-1].admitting:
                return self._runs[-1].engine
            return None

    # --------------------------------------------------------------- loop
    def _loop(self):
        from deeplearning4j_tpu.monitor import goodput
        crash: Optional[Exception] = None
        while not self._stop.is_set():
            # goodput split of the scheduler pass: admission vs the
            # compute window (prefill + step + retire) with the step's
            # page-stall share apportioned out, vs idle wait below.
            # Zero-cost while the ledger is off: one flag check per pass
            gp = goodput.goodput_enabled()
            t_pass = time.perf_counter() if gp else 0.0
            try:
                worked = self._admit()
                worked = self._fabric_tick() or worked
                t_admitted = time.perf_counter() if gp else 0.0
                stall0 = self._stall_s
                worked = self._prefill_tick() or worked
                worked = self._step_all() or worked
                self._retire()
            except Exception as e:      # noqa: BLE001 — the scheduler
                # thread is the only place slots are reclaimed: an
                # unguarded exception here would strand every stream
                # forever while the servable still reported "ready".
                # Fail everything loudly and stop instead.
                crash = e
                log.exception("decode[%s]: scheduler crashed; failing "
                              "all streams", self.name)
                self._stop.set()
                break
            if gp:
                t_end = time.perf_counter()
                stall = max(self._stall_s - stall0, 0.0)
                goodput.decode_note(self.name, "admission",
                                    t_admitted - t_pass)
                goodput.decode_note(self.name, "page_stall", stall)
                goodput.decode_note(
                    self.name, "step_compute",
                    max(t_end - t_admitted - stall, 0.0))
            if not worked:
                idle0 = time.perf_counter() if gp else 0.0
                self._wake.wait(0.005)
                self._wake.clear()
                if gp:
                    goodput.decode_note(self.name, "idle",
                                        time.perf_counter() - idle0)
        # teardown: everything still live gets a terminal error
        exc = crash if crash is not None else ServerDrainingError(
            f"decode[{self.name}] shut down mid-stream")
        with self._rlock:
            runs = list(self._runs)
            self._runs.clear()
        for run in runs:
            for slot, job in run.prefill.items():
                run.engine.release_slot(slot)
                job.req.fail(exc)
            for slot, req in run.slot_req.items():
                run.engine.release_slot(slot)
                req.fail(exc)
            run.engine.close()
        self._fail_pending(crash if crash is not None
                           else ServerDrainingError(
                               f"decode[{self.name}] shut down"))
        self._fail_fabric(exc)

    def _fail_fabric(self, exc: Exception):
        while True:
            with self._plock:
                if not self._fabric:
                    return
                _fn, done, box = self._fabric.popleft()
            box["exc"] = exc
            done.set()

    def _fail_pending(self, exc: Exception):
        while True:
            with self._plock:
                if not self._pending:
                    return
                req = self._pending.popleft()
            req.fail(exc)

    def _admit(self) -> bool:
        with self._rlock:
            run = self._runs[-1] if self._runs and self._runs[-1].admitting \
                else None
        if run is None:
            return False
        worked = False
        while True:
            with self._plock:
                req = self._pending[0] if self._pending else None
            if req is None:
                break
            if req.cancelled.is_set():
                self._pop(req)
                req.finish("cancelled")
                continue
            if req.deadline is not None \
                    and time.monotonic() > req.deadline:
                self._pop(req)
                monitor.counter("serving_decode_rejected_total",
                                "Generation requests rejected by "
                                "admission control",
                                labels=("model", "reason")).inc(
                    model=self.name, reason="deadline")
                req.fail(DeadlineExceededError(
                    f"decode[{self.name}]: deadline expired after "
                    f"{time.monotonic() - req.enqueued:.3f}s in queue"))
                continue
            if len(req.prompt) >= run.engine.max_context:
                # the admitting engine changed under the request (a swap
                # to a shorter-context model raced generate()'s check):
                # fail it cleanly, never let admit() overrun a page table
                self._pop(req)
                req.fail(ValueError(
                    f"decode[{self.name}]: prompt length "
                    f"{len(req.prompt)} leaves no room to generate "
                    f"(live max_context {run.engine.max_context})"))
                continue
            try:
                info = run.engine.admit_prompt(req.prompt)
            except Exception as e:          # noqa: BLE001 — surfaced to req
                self._pop(req)
                log.exception("decode[%s]: admission failed", self.name)
                req.fail(e)
                continue
            if info is None:
                break                       # no slot/pages; retry next tick
            self._pop(req)
            # admission is now CHEAP (page-table writes + at most one COW
            # page copy; the suffix prefill runs in budgeted chunks on
            # the next _prefill_tick), so this loop keeps draining the
            # join queue until slots, pages or the queue are exhausted —
            # when a token step frees several slots at once, a burst of
            # queued joins lands in ONE tick, not one per step
            slot = info.slot
            req.cached_tokens = int(info.cached_len)
            # "joined a RUNNING batch" counts decoding streams only —
            # same-burst admissions still mid-prefill are not a batch
            # this request preempted into (inflight() would count them
            # and let the smoke's joins>0 gate pass on a workload where
            # continuous batching never engaged)
            with self._rlock:
                joined_running = any(r.slot_req for r in self._runs)
            if flight.enabled():
                # admission wait + the engine generation whose params
                # will write this stream's KV (the swap-generation fact
                # a postmortem needs) + how much prefill the prefix
                # cache just made free
                flight.note(req.ctx, "admitted", slot=slot,
                            engine_version=run.version,
                            wait_ms=round(
                                (time.monotonic() - req.enqueued) * 1e3,
                                3),
                            joined_running=joined_running,
                            cached_tokens=int(info.cached_len),
                            cow=info.cow_src is not None,
                            model=self.name)
            req.version = run.version
            run.prefill[slot] = _PrefillJob(
                req, int(info.cached_len),
                run.engine.draft_prefill_origin(slot))
            if joined_running:
                monitor.counter(
                    "serving_decode_preempted_joins_total",
                    "Requests admitted into an already-running batch "
                    "between token steps (continuous batching)",
                    labels=("model",)).inc(model=self.name)
            worked = True
        with self._plock:
            depth = len(self._pending)
        monitor.gauge("serving_decode_queue_depth",
                      "Generation requests waiting for a decode slot",
                      labels=("model",)).set(depth, model=self.name)
        return worked

    def _pop(self, req: GenerateRequest):
        with self._plock:
            if self._pending and self._pending[0] is req:
                self._pending.popleft()

    def _prefill_tick(self) -> bool:
        """Advance every in-flight prefill by at most the engine's
        per-tick token budget (FIFO across that engine's jobs), then
        return to the loop so a decode step can interleave — a long
        prompt costs the running streams one bounded chunk of ITL, never
        its whole prefill. Chunking off (budget 0) completes each job in
        a single program call. The final chunk yields the first token."""
        with self._rlock:
            runs = [r for r in self._runs if r.prefill]
        worked = False
        for run in runs:
            budget = run.engine.prefill_chunk_tokens
            spent = 0
            for slot in list(run.prefill.keys()):
                job = run.prefill.get(slot)
                if job is None:
                    continue
                req = job.req
                if req.cancelled.is_set():
                    run.prefill.pop(slot, None)
                    self._finish(run, slot, req, "cancelled")
                    worked = True
                    continue
                if req.deadline is not None \
                        and time.monotonic() > req.deadline:
                    run.prefill.pop(slot, None)
                    self._finish(run, slot, req, "deadline")
                    worked = True
                    continue
                total = len(req.prompt)
                try:
                    # bind the stream's context so prefill spans (and any
                    # first-compile ledger capture inside) carry its
                    # trace_id
                    with monitor.bind_context(req.ctx):
                        while job.pos < total:
                            if budget > 0 and spent >= budget:
                                break
                            n = total - job.pos if budget <= 0 \
                                else min(total - job.pos, budget - spent)
                            if job.pos == 0 and n == total:
                                # cold, whole prompt within budget: the
                                # dense program (bitwise the pre-cache
                                # path; also what cache-off runs)
                                tok, _ = run.engine.prefill(
                                    slot, req.prompt, req.temperature,
                                    req.top_k)
                            else:
                                tok = run.engine.prefill_chunk(
                                    slot, req.prompt, job.pos, n,
                                    req.temperature, req.top_k)
                            job.pos += n
                            job.chunks += 1
                            spent += n
                            worked = True
                            if job.pos >= total:
                                job.tok = tok
                except Exception as e:  # noqa: BLE001 — surfaced to req
                    run.prefill.pop(slot, None)
                    run.engine.release_slot(slot)
                    log.exception("decode[%s]: prefill failed", self.name)
                    req.fail(e)
                    continue
                # the speculative draft mirror prefills under the same
                # per-tick budget; its failure never fails the stream —
                # speculation just turns off and the stream decodes plain
                try:
                    with monitor.bind_context(req.ctx):
                        while job.dpos is not None and job.dpos < total:
                            if budget > 0 and spent >= budget:
                                break
                            n = total - job.dpos if budget <= 0 \
                                else min(total - job.dpos,
                                         budget - spent)
                            run.engine.draft_prefill(
                                slot, req.prompt, job.dpos, n,
                                req.temperature, req.top_k)
                            job.dpos += n
                            spent += n
                            worked = True
                except Exception:  # noqa: BLE001 — draft is optional
                    log.exception("decode[%s]: draft prefill failed; "
                                  "stream decodes plain", self.name)
                    run.engine.spec_disable(slot, "draft_prefill")
                    job.dpos = None
                if job.pos >= total and (job.dpos is None
                                         or job.dpos >= total):
                    run.prefill.pop(slot, None)
                    req.prefill_chunks = job.chunks
                    # prefill complete: every mapped prompt page holds
                    # final K/V — only now may the prefix index share it
                    run.engine.cache.register_prefix(slot, req.prompt)
                    run.engine.draft_prefill_done(slot, req.prompt)
                    run.slot_req[slot] = req
                    monitor.histogram(
                        "serving_decode_prefill_chunks",
                        "Prefill program executions per admission "
                        "(1 = unchunked; higher = budgeted chunking "
                        "interleaved with decode steps)",
                        labels=("model",),
                        buckets=(1, 2, 3, 4, 6, 8, 12, 16, 24, 32)
                    ).observe(job.chunks, model=self.name)
                    flight.note(req.ctx, "prefill_done",
                                chunks=job.chunks,
                                cached_tokens=req.cached_tokens,
                                model=self.name)
                    self._emit(run, slot, req, job.tok)
        return worked

    def _emit(self, run: _EngineRun, slot: int, req: GenerateRequest,
              tok: int):
        """Deliver one sampled token; finish/free the slot on EOS, the
        token budget, cancellation or the deadline."""
        if req.cancelled.is_set():
            self._finish(run, slot, req, "cancelled")
            return
        if req.deadline is not None and time.monotonic() > req.deadline:
            self._finish(run, slot, req, "deadline")
            return
        if req.eos_id is not None and tok == req.eos_id:
            self._finish(run, slot, req, "eos")
            return
        exemplar = None if req.ctx is None else req.ctx.trace_id
        if req.last_emit_at is not None:
            if monitor.tracing_enabled() and req._last_pc is not None:
                # one span per inter-token gap, under the stream's ctx:
                # the merged trace shows every ITL stall of a slow p99
                # stream (the runbook's page-stall walk)
                monitor.add_span("decode/itl_gap", req._last_pc,
                                 time.perf_counter(), ctx=req.ctx,
                                 model=self.name, index=req.n_emitted)
            monitor.histogram(
                "serving_decode_inter_token_seconds",
                "Gap between consecutive streamed tokens of one request",
                labels=("model",), buckets=_ITL_BUCKETS).observe(
                time.monotonic() - req.last_emit_at, model=self.name,
                exemplar=exemplar)
        elif req.n_emitted == 0:
            # TTFT observed only for generations that actually deliver a
            # first token — cancelled/deadline admissions (checked above)
            # must not pollute the gated decode_ttft_p99_ms series
            monitor.histogram(
                "serving_decode_ttft_seconds",
                "Time from request arrival to its first generated token",
                labels=("model",), buckets=_TTFT_BUCKETS).observe(
                time.monotonic() - req.enqueued, model=self.name,
                exemplar=exemplar)
        req.emit(tok)
        monitor.counter("serving_decode_tokens_total",
                        "Generated tokens streamed to clients",
                        labels=("model",)).inc(model=self.name)
        if req.n_emitted >= req.max_new_tokens:
            self._finish(run, slot, req, "length")

    def _finish(self, run: _EngineRun, slot: int, req: GenerateRequest,
                reason: str):
        run.engine.release_slot(slot)
        run.slot_req.pop(slot, None)
        req.finish(reason)
        if monitor.tracing_enabled():
            # the whole stream as one span on the scheduler track, under
            # the stream's trace_id — queue wait + prefill + every token
            monitor.add_span("serving/stream", req.t0_pc,
                             time.perf_counter(), ctx=req.ctx,
                             model=self.name, reason=reason,
                             tokens=req.n_emitted,
                             engine_version=run.version)
        flight.note(req.ctx, "finish", reason=reason,
                    tokens=req.n_emitted,
                    spec_proposed=req.spec_proposed,
                    spec_accepted=req.spec_accepted, model=self.name)
        monitor.counter("serving_decode_finished_total",
                        "Finished generations by reason",
                        labels=("model", "reason")).inc(
            model=self.name, reason=reason)

    def _step_all(self) -> bool:
        from deeplearning4j_tpu.monitor import goodput
        gp = goodput.goodput_enabled()
        with self._rlock:
            runs = [r for r in self._runs if r.slot_req]
        worked = False
        for run in runs:
            # speculation first: eligible streams get an accepted burst
            # (draft propose + target verify, two dispatches for up to
            # k+1 tokens each); everything speculation did not handle
            # falls through to the plain one-token step below
            spec = run.engine.spec_step(exclude=run.prefill.keys()) \
                if run.engine.spec_enabled else {}
            for slot, res in spec.items():
                req = run.slot_req.get(slot)
                if req is None:
                    continue
                req.spec_rounds += 1
                req.spec_proposed += res["proposed"]
                req.spec_accepted += res["accepted"]
                if res.get("fallback") and flight.enabled():
                    flight.note(req.ctx, "spec_fallback",
                                reason=res["fallback"], slot=slot,
                                proposed=req.spec_proposed,
                                accepted=req.spec_accepted,
                                model=self.name)
                for tok in res["tokens"]:
                    self._emit(run, slot, req, tok)
                    if req.done.is_set():
                        break
            if spec:
                worked = True
            handled = set(spec)
            if not any(s not in handled for s in run.slot_req):
                continue
            step_t0 = time.perf_counter() if gp else 0.0
            toks, act, _ = run.engine.step(
                exclude=set(run.prefill.keys()) | handled)
            considered = stalled = 0
            for slot, req in list(run.slot_req.items()):
                if slot in handled:
                    continue
                considered += 1
                if act[slot]:
                    self._emit(run, slot, req, int(toks[slot]))
                elif int(run.engine.cache.seq_lens[slot]) \
                        >= run.engine.max_context:
                    self._finish(run, slot, req, "length_cap")
                elif req.cancelled.is_set():
                    # a page-stalled slot must still honor cancellation/
                    # deadline: releasing it is what refills the pool —
                    # otherwise an oversubscribed pool where EVERY slot
                    # stalls deadlocks forever with all pages leaked
                    self._finish(run, slot, req, "cancelled")
                elif req.deadline is not None \
                        and time.monotonic() > req.deadline:
                    self._finish(run, slot, req, "deadline")
                else:
                    # page-stalled this step (metered by the cache); the
                    # per-stream timeline needs the stall itself — it is
                    # THE explanation for an ITL-gap span in the trace
                    stalled += 1
                    if flight.enabled():
                        flight.note(req.ctx, "page_stall", slot=slot,
                                    seq_len=int(
                                        run.engine.cache.seq_lens[slot]),
                                    model=self.name)
            if gp and considered:
                # the stalled slots' share of this step's wall is page-
                # stall time, not compute — _loop bills it separately
                self._stall_s += (time.perf_counter() - step_t0) \
                    * (stalled / considered)
            worked = True
        return worked

    def _retire(self):
        with self._rlock:
            keep = []
            for run in self._runs:
                if not run.admitting and not run.slot_req \
                        and not run.prefill:
                    run.engine.close()
                    log.info("decode[%s]: retired engine v%d (drained)",
                             self.name, run.version)
                else:
                    keep.append(run)
            self._runs = keep

    # -------------------------------------------------------------- drain
    def drain(self, timeout: float = 30.0) -> bool:
        """Stop admitting, let in-flight sequences finish (bounded), then
        stop the loop. Queued joins fail with a draining error."""
        self._draining = True
        self._fail_pending(ServerDrainingError(
            f"decode[{self.name}] is draining"))
        deadline = time.monotonic() + timeout
        while self.inflight() and time.monotonic() < deadline:
            time.sleep(0.01)
        flushed = self.inflight() == 0
        self._stop.set()
        self._wake.set()
        if self._started:
            self._thread.join(timeout=max(0.1,
                                          deadline - time.monotonic() + 5))
        return flushed


# ==========================================================================
# The servable: versions + engine lifecycle behind the registry surface
# ==========================================================================
class ServedLM:
    """One named decode servable: version history + engine + scheduler.

    The LM sibling of registry.ServedModel — same lifecycle surface
    (status/describe/swap/rollback/shutdown), so ModelRegistry, the HTTP
    server, the fleet supervisor and the router drive both kinds without
    caring which is which."""

    kind = "lm"

    def __init__(self, name: str, model, source: str,
                 decode: Optional[DecodeConfig] = None):
        from deeplearning4j_tpu.serving.registry import ServableVersion
        self.name = name
        self.cfg = decode if decode is not None else DecodeConfig()
        self.status = "loading"
        self._swap_lock = DiagnosedLock(
            "deeplearning4j_tpu.serving.decode.ServedLM._swap_lock")
        self._state_lock = DiagnosedLock(
            "deeplearning4j_tpu.serving.decode.ServedLM._state_lock")
        engine = DecodeEngine(model, self.cfg, name=name)
        engine.warm()
        self.vocab = engine.vocab
        self.max_context = engine.max_context
        self.scheduler = DecodeScheduler(name,
                                         queue_limit=self.cfg.queue_limit)
        self.scheduler.install(engine, version=1)
        self.versions: List[ServableVersion] = [
            ServableVersion(1, str(source), model)]
        self.active = 0
        self.active_info = self.versions[0].describe()
        self._engines: Dict[int, DecodeEngine] = {1: engine}
        self.status = "ready"
        monitor.gauge("serving_model_ready",
                      "1 while the servable is warmed and live",
                      labels=("model",)).set(1, model=name)

    # ---------------------------------------------------------- generation
    def generate(self, prompt, max_new_tokens: int = 32,
                 temperature: float = 0.0, top_k: int = 0,
                 eos_id: Optional[int] = None,
                 deadline: Optional[float] = None) -> GenerateRequest:
        """Validate + enqueue one generation; returns the live request
        whose `events` queue streams tokens. Raises ValueError (400),
        ServerOverloadedError (429) or ServerDrainingError (503)."""
        if self.status == "stopping":
            raise ServerDrainingError(
                f"decode[{self.name}] is draining")
        prompt = np.asarray(prompt, np.int64).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must contain at least one token id")
        if prompt.size >= self.max_context:
            raise ValueError(
                f"prompt length {prompt.size} leaves no room to generate "
                f"(max_context {self.max_context})")
        if prompt.min() < 0 or prompt.max() >= self.vocab:
            raise ValueError(
                f"prompt ids must be in [0, {self.vocab}); got "
                f"[{int(prompt.min())}, {int(prompt.max())}]")
        if max_new_tokens < 1:
            raise ValueError("max_tokens must be >= 1")
        max_new = min(int(max_new_tokens), self.cfg.max_new_tokens_cap,
                      self.max_context - int(prompt.size))
        req = GenerateRequest(
            prompt, max_new_tokens=max_new, temperature=temperature,
            top_k=top_k, eos_id=eos_id,
            deadline=None if deadline is None
            else time.monotonic() + float(deadline))
        self.scheduler.submit(req)
        return req

    # ------------------------------------------------------------ kv fabric
    def export_prefix(self, prompt, timeout: float = 30.0) -> bytes:
        """Serialize the KV pages covering `prompt`'s full blocks into a
        framed transfer blob (the prefill half of disaggregation). If the
        prefix isn't cached yet, a one-token greedy generation prefills
        and retains it first; the page reads are marshalled onto the
        scheduler thread via run_fabric."""
        if self.status == "stopping":
            raise ServerDrainingError(f"decode[{self.name}] is draining")
        prompt = np.asarray(prompt, np.int64).reshape(-1)
        engine = self.scheduler.admitting_engine()
        if engine is None:
            raise ServerDrainingError(
                f"decode[{self.name}]: no admitting engine")
        if not engine.cfg.prefix_cache:
            raise ValueError(
                f"decode[{self.name}]: prefix cache disabled; nothing "
                "to export")
        ps = engine.cfg.page_size
        full = (int(prompt.size) // ps) * ps
        if full < ps:
            raise ValueError(
                f"prompt too short to export: {prompt.size} tokens "
                f"< one {ps}-token page")
        head = prompt[:full]
        if engine.cache.cached_prefix_len(head) < full:
            req = self.generate(head, max_new_tokens=1, temperature=0.0,
                                deadline=timeout)
            while True:
                kind, payload = req.events.get(timeout=timeout)
                if kind == "done":
                    break
                if kind == "error":
                    raise payload
        frames = self.scheduler.run_fabric(
            lambda eng: eng.export_pages(head), timeout=timeout)
        return kvfabric.pack_transfer(np.asarray(head, np.int32), frames,
                                      ps)

    def import_prefix(self, payload: bytes, timeout: float = 30.0) -> dict:
        """Land a framed page transfer (produced by a prefill replica's
        export_prefix) into this servable's prefix cache. Frame integrity
        and geometry are verified before any pool write; a bad shipment
        raises kvfabric.FrameError and leaves the cache untouched."""
        if self.status == "stopping":
            raise ServerDrainingError(f"decode[{self.name}] is draining")
        tokens, frames, hdr = kvfabric.unpack_transfer(payload)
        engine = self.scheduler.admitting_engine()
        if engine is None:
            raise ServerDrainingError(
                f"decode[{self.name}]: no admitting engine")
        if int(hdr["page_size"]) != int(engine.cfg.page_size):
            raise kvfabric.FrameError(
                f"transfer page_size {hdr['page_size']} != "
                f"{engine.cfg.page_size} on decode[{self.name}]")
        if not engine.cfg.prefix_cache:
            raise ValueError(
                f"decode[{self.name}]: prefix cache disabled; cannot "
                "adopt pages")
        adopted = self.scheduler.run_fabric(
            lambda eng: eng.import_pages(tokens, frames), timeout=timeout)
        return {"adopted": int(adopted), "pages": len(frames),
                "tokens": int(np.asarray(tokens).size)}

    # ------------------------------------------------------------ lifecycle
    def _activate(self, sv, variant: Optional[str]):
        """Warm a full replacement engine off-path, then roll admissions
        onto it; in-flight sequences finish on their own engine (KV pages
        are only meaningful under the params that wrote them). `variant`
        is the source's parsed ``@`` suffix (quantize mode or ``spec``
        options); None keeps the servable's config as deployed."""
        from deeplearning4j_tpu.serving.registry import ModelLoadError
        cfg = apply_variant(self.cfg, variant) \
            if variant is not None else self.cfg
        t0 = time.perf_counter()
        engine = DecodeEngine(sv.model, cfg, name=self.name)
        if engine.vocab != self.vocab:
            engine.close()
            raise ModelLoadError(
                f"swap rejected: {sv.source!r} has vocab "
                f"{engine.vocab}, live servable {self.name!r} serves "
                f"{self.vocab} (deploy under a new name)")
        with monitor.span("serving/swap", model=self.name,
                          version=sv.version):
            engine.warm()
            self.scheduler.install(engine, version=sv.version)
        self._engines[sv.version] = engine
        if engine.max_context != self.max_context:
            # a swap may change KV capacity (cfg.max_context=None derives
            # it from the model); generate() must validate against the
            # LIVE admitting engine, and the scheduler re-checks at
            # admission for requests that raced this update
            log.warning("decode[%s]: max_context %d -> %d across swap",
                        self.name, self.max_context, engine.max_context)
            self.max_context = engine.max_context
        monitor.histogram("serving_swap_seconds",
                          "Load+warm+swap duration (off the request path)",
                          labels=("model",),
                          buckets=(0.1, 0.5, 1, 2.5, 5, 10, 30, 60, 120)
                          ).observe(time.perf_counter() - t0,
                                    model=self.name)

    def swap(self, source, keep_versions: int = 3) -> dict:
        from deeplearning4j_tpu.serving.registry import (
            ServableVersion, load_servable,
        )
        base, variant = parse_variant(str(source))
        model = load_servable(base)
        with self._swap_lock:
            if self.status == "stopping":
                raise ServerDrainingError(
                    f"decode[{self.name}] is draining; swap rejected")
            with self._state_lock:
                next_version = self.versions[-1].version + 1
            sv = ServableVersion(next_version, str(source), model)
            self._activate(sv, variant)
            with self._state_lock:
                self.versions.append(sv)
                self.active = len(self.versions) - 1
                while len(self.versions) > keep_versions:
                    dropped = self.versions.pop(0)
                    self.active -= 1
                    self._engines.pop(dropped.version, None)
                    log.info("decode[%s]: retired v%d (%s) from memory",
                             self.name, dropped.version, dropped.source)
                self.active_info = sv.describe()
            monitor.counter("serving_swaps_total",
                            "Zero-downtime model hot-swaps",
                            labels=("model",)).inc(model=self.name)
        log.info("decode[%s]: now admitting on v%d (%s); older versions "
                 "drain in place", self.name, sv.version, sv.source)
        return sv.describe()

    def rollback(self) -> dict:
        from deeplearning4j_tpu.serving.registry import ModelLoadError
        with self._swap_lock:
            if self.status == "stopping":
                raise ServerDrainingError(
                    f"decode[{self.name}] is draining; rollback rejected")
            with self._state_lock:
                if self.active == 0:
                    raise ModelLoadError(
                        f"decode[{self.name}]: no previous version in "
                        "memory to roll back to")
                sv = self.versions[self.active - 1]
            # the rolled-back-to version gets a FRESH warmed engine (its
            # old one may already be retired); the same rolling handoff
            base, variant = parse_variant(str(sv.source))
            self._activate(sv, variant)
            with self._state_lock:
                self.active -= 1
                self.active_info = sv.describe()
            monitor.counter("serving_rollbacks_total",
                            "One-step version rollbacks",
                            labels=("model",)).inc(model=self.name)
        log.warning("decode[%s]: rolled back to v%d (%s)", self.name,
                    sv.version, sv.source)
        return sv.describe()

    # --------------------------------------------------------------- admin
    def queue_state(self) -> Tuple[int, int]:
        """(depth, limit) of the join queue — the Retry-After input."""
        return self.scheduler.queue_state()

    def describe(self) -> dict:
        with self._state_lock:
            newest = self.scheduler.admitting_engine()
            d = {
                "name": self.name,
                "kind": self.kind,
                "status": self.status,
                "vocab_size": self.vocab,
                "max_context": self.max_context,
                "active_version": self.versions[self.active].version,
                "versions": [v.describe() for v in self.versions],
                "pending": self.scheduler.queue_state()[0],
                "inflight": self.scheduler.inflight(),
            }
            if newest is not None:
                d["decode"] = newest.describe()
            return d

    def shutdown(self, drain: bool = True, timeout: float = 30.0):
        self.status = "stopping"
        monitor.gauge("serving_model_ready",
                      "1 while the servable is warmed and live",
                      labels=("model",)).set(0, model=self.name)
        self.scheduler.drain(timeout=timeout if drain else 0.1)
