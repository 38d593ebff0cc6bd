"""Continuous-batching decode subsystem tests (serving/decode.py,
serving/kvcache.py, serving/quantize.py + the HTTP/router surfaces).

The load-bearing one is test_late_join_streams_before_batch_drains: the
continuous-batching acceptance criterion is proven by the SCHEDULER (a
late request's first token lands while an earlier generation is still
streaming), not inferred from throughput.
"""
import json
import os
import queue
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.models.transformer import TransformerLM
from deeplearning4j_tpu.serving import (
    ModelRegistry, ModelServer, ServerOverloadedError,
)
from deeplearning4j_tpu.serving.decode import (
    DecodeConfig, DecodeEngine, DecodeScheduler, GenerateRequest, ServedLM,
)
from deeplearning4j_tpu.serving.kvcache import DUMP_PAGE, KVCacheState
from deeplearning4j_tpu.serving.quantize import (
    QTensor, quality_delta, quantize_leaf,
)
from deeplearning4j_tpu.serving.registry import (
    ModelLoadError, load_servable, parse_zoo_source,
)

ZOO_SRC = ("zoo:TransformerLM?vocab_size=48&n_layers=1&n_embd=32"
           "&n_heads=4&seq_length=32")


def drain_events(req, timeout=30.0):
    """Collect ((kind, payload, t_monotonic)) until done/error."""
    out = []
    deadline = time.monotonic() + timeout
    while True:
        ev = req.events.get(timeout=max(0.1, deadline - time.monotonic()))
        out.append((ev[0], ev[1], time.monotonic()))
        if ev[0] in ("done", "error"):
            return out


# ------------------------------------------------------------- kv cache
def test_kvcache_alloc_release_and_dump_page():
    c = KVCacheState(slots=2, page_size=4, max_context=16, name="kvt")
    assert c.pool_pages == 1 + 2 * 4          # page 0 is the dump page
    s = c.admit(6)                            # needs ceil(6/4) = 2 pages
    assert s is not None
    assert c.describe()["pages_used"] == 2
    assert (c.page_table[s, :2] > 0).all()    # never the dump page
    assert (c.page_table[s, 2:] == 0).all()
    # position 6 lives inside page 1 (already allocated); 8 needs page 2
    assert c.ensure_page(s)
    c.seq_lens[s] = 8
    assert c.ensure_page(s)
    assert c.describe()["pages_used"] == 3
    c.release(s)
    assert c.describe()["pages_used"] == 0
    assert not c.active[s]


def test_kvcache_exhaustion_blocks_admission_and_growth():
    # pool sized for exactly one max-context sequence
    c = KVCacheState(slots=2, page_size=4, max_context=16, pool_pages=5,
                     name="kvx")
    a = c.admit(16 - 4)
    assert a is not None                      # took 3 of 4 pages
    assert c.admit(8) is None                 # 2 pages wanted, 1 free
    b = c.admit(3)                            # 1 page still fits
    assert b is not None
    c.seq_lens[b] = 4
    assert not c.ensure_page(b)               # pool dry -> stall, no crash
    c.release(a)
    assert c.ensure_page(b)                   # freed pages recycle


def test_kvcache_rejects_unaligned_context():
    with pytest.raises(ValueError):
        KVCacheState(slots=1, page_size=8, max_context=20)


# ------------------------------------------------- kv prefix cache (CoW)
def test_kvcache_prefix_reuse_refcount_lifecycle():
    """Full-block prefix sharing: a second prompt with a common prefix
    maps the SAME physical pages (ref 2), release retains indexed pages
    instead of freeing, and a later identical prefix still hits."""
    c = KVCacheState(slots=4, page_size=4, max_context=16, name="kvp")
    t = np.arange(12, dtype=np.int32)             # 3 full blocks
    a = c.admit_prompt(t)
    assert a.cached_len == 0 and a.cow_src is None  # cold
    c.register_prefix(a.slot, t)
    # shares the first 2 blocks, diverges in the third
    b = c.admit_prompt(np.concatenate([t[:8], [99, 98]]).astype(np.int32))
    assert b.cached_len == 8
    assert (c.page_table[b.slot, :2] == c.page_table[a.slot, :2]).all()
    shared_page = int(c.page_table[a.slot, 0])
    assert c.ref_count(shared_page) == 2
    c.release(a.slot)
    assert c.ref_count(shared_page) == 1          # b still maps it
    c.release(b.slot)
    assert c.ref_count(shared_page) == 0
    # indexed pages went to the retained set, not the free list (b's
    # partial third page was never indexed and freed immediately): the
    # prefix is still hot for the next admission
    assert c.retained_pages() == 3                # a's 3 indexed blocks
    assert c.cached_prefix_len(t) == 12
    d = c.admit_prompt(np.concatenate([t, [7]]).astype(np.int32))
    assert d.cached_len == 12                     # full retained chain hit
    c.release(d.slot)
    hits = monitor.counter("serving_decode_kv_cache_hits_total", "x",
                           labels=("model",)).value(model="kvp")
    misses = monitor.counter("serving_decode_kv_cache_misses_total", "x",
                             labels=("model",)).value(model="kvp")
    assert hits == 2 and misses == 1


def test_kvcache_cow_on_full_prefix_and_dump_page_never_shared():
    """A page-aligned prompt whose every block is cached still must
    recompute its last token — admit hands back a copy-on-write pair so
    the recompute writes a private copy, never the shared page. The dump
    page is never indexed, shared, or a COW endpoint."""
    c = KVCacheState(slots=4, page_size=4, max_context=16, name="kvcow")
    t = np.arange(8, dtype=np.int32)              # exactly 2 blocks
    a = c.admit_prompt(t)
    c.register_prefix(a.slot, t)
    b = c.admit_prompt(t)                         # identical, fully cached
    assert b.cached_len == 7                      # forced last-token redo
    assert b.cow_src == int(c.page_table[a.slot, 1])
    assert b.cow_dst == int(c.page_table[b.slot, 1])
    assert b.cow_dst not in (b.cow_src, DUMP_PAGE)
    # block 1 shared read-only; block 2 diverged onto the private copy
    assert c.page_table[b.slot, 0] == c.page_table[a.slot, 0]
    assert c.page_table[b.slot, 1] != c.page_table[a.slot, 1]
    # the source is pinned until the engine's on-device copy completes
    assert c.ref_count(b.cow_src) == 2            # a's mapping + the pin
    c.unref_page(b.cow_src)
    assert c.ref_count(b.cow_src) == 1
    c.release(a.slot)
    c.release(b.slot)
    assert c.ref_count(DUMP_PAGE) == 0
    assert DUMP_PAGE not in c._by_page            # never indexed
    # no live table maps the dump page as an allocated entry
    assert all(c._pages_per_slot_live[s] == 0 for s in range(c.slots))


def test_kvcache_lru_eviction_under_pool_pressure():
    """Retained prefixes are cache, not working memory: when the free
    list runs dry, the LRU chain is evicted (and unindexed) to satisfy
    new admissions; fresher chains survive."""
    c = KVCacheState(slots=2, page_size=4, max_context=8, pool_pages=5,
                     name="kvev")                 # 4 usable pages
    a_t = np.arange(8, dtype=np.int32)
    b_t = np.arange(8, dtype=np.int32) + 100
    c_t = np.arange(8, dtype=np.int32) + 200
    a = c.admit_prompt(a_t)
    c.register_prefix(a.slot, a_t)
    c.release(a.slot)
    b = c.admit_prompt(b_t)
    c.register_prefix(b.slot, b_t)
    c.release(b.slot)
    assert c.retained_pages() == 4 and c.free_pages() == 4
    ev0 = monitor.counter("serving_decode_kv_cache_evictions_total", "x",
                          labels=("model",)).value(model="kvev")
    d = c.admit_prompt(c_t)                       # needs 2 fresh pages
    assert d is not None and d.cached_len == 0
    ev1 = monitor.counter("serving_decode_kv_cache_evictions_total", "x",
                          labels=("model",)).value(model="kvev")
    assert ev1 - ev0 == 2                         # a's chain went, LRU
    assert c.cached_prefix_len(a_t) == 0          # evicted
    assert c.cached_prefix_len(b_t) == 8          # fresher chain survived
    c.release(d.slot)


def test_kvcache_tokenless_admit_keeps_legacy_semantics():
    """admit(int) (no tokens) must neither share nor retain: release
    frees everything immediately, exactly the pre-cache behavior."""
    c = KVCacheState(slots=2, page_size=4, max_context=16, name="kvleg")
    s = c.admit(10)
    assert s is not None
    c.release(s)
    assert c.retained_pages() == 0
    assert c.free_pages() == c.pool_pages - 1


# ------------------------------------------------------------ zoo kwargs
def test_zoo_source_constructor_kwargs():
    arch, kwargs = parse_zoo_source(
        "TransformerLM?n_layers=2&vocab_size=512&dropout=0.1"
        "&use_rope=false")
    assert arch == "TransformerLM"
    assert kwargs == {"n_layers": 2, "vocab_size": 512, "dropout": 0.1,
                      "use_rope": False}
    net = load_servable(ZOO_SRC)
    # layer 0 embedding table reflects the requested sizing
    assert net.params["0"]["W"].shape == (48, 32)
    # tuple coercion for shape-valued fields
    lenet = load_servable("zoo:LeNet?num_classes=5&input_shape=28,28,1")
    assert lenet.layers[-1].n_out == 5


def test_zoo_source_bad_kwarg_is_clean_error():
    with pytest.raises(ModelLoadError):
        load_servable("zoo:TransformerLM?definitely_not_a_field=3")
    with pytest.raises(ModelLoadError):
        load_servable("zoo:NoSuchArch?x=1")


# ------------------------------------------------------------- quantize
def test_quantize_leaf_roundtrip_and_pytree():
    rs = np.random.RandomState(0)
    w = rs.randn(32, 16).astype(np.float32)
    q = quantize_leaf(w)
    assert isinstance(q, QTensor) and q.q.dtype == np.int8
    deq = np.asarray(q.dequant())
    # per-channel symmetric int8: worst-case error is half a step
    step = np.abs(w).max(axis=0) / 127.0
    assert (np.abs(deq - w) <= step[None, :] * 0.5 + 1e-7).all()
    # QTensor flows through jax pytrees (jit params)
    import jax
    leaves, treedef = jax.tree_util.tree_flatten({"w": q})
    assert len(leaves) == 2
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(back["w"], QTensor)


@pytest.fixture(scope="module")
def quant_engines():
    net = TransformerLM(vocab_size=48, seq_length=32, n_layers=1,
                        n_embd=32, n_heads=4, seed=21).init()
    cfg = DecodeConfig(slots=2, page_size=8)
    base = DecodeEngine(net, cfg, name="q-base")
    i8 = DecodeEngine(net, DecodeConfig(slots=2, page_size=8,
                                        quantize="int8"), name="q-int8")
    b16 = DecodeEngine(net, DecodeConfig(slots=2, page_size=8,
                                         quantize="bf16"), name="q-bf16")
    return base, i8, b16


def test_quantized_variants_measured_quality(quant_engines):
    base, i8, b16 = quant_engines
    rs = np.random.RandomState(3)
    toks = rs.randint(0, 48, (4, 24))
    for eng in (i8, b16):
        d = quality_delta(base, eng, toks)
        assert np.isfinite(d["ppl_variant"]) and np.isfinite(d["logit_mae"])
        # weight-only PTQ of a small model: quality moves by percents,
        # not orders of magnitude
        assert abs(d["ppl_delta_pct"]) < 25.0, d
    # int8 really stores int8
    p = i8._params
    assert isinstance(p["1"]["attn"]["Wq"], QTensor)
    assert isinstance(p["0"]["W"], QTensor)


def test_quantized_engine_generates(quant_engines):
    _, i8, b16 = quant_engines
    for eng in (i8, b16):
        eng.warm()
        slot = eng.cache.admit(3)
        tok, _ = eng.prefill(slot, np.array([1, 2, 3], np.int32), 0.0, 0)
        assert 0 <= tok < 48
        toks, act, _ = eng.step()
        assert act[slot] and 0 <= int(toks[slot]) < 48
        eng.cache.release(slot)


# --------------------------------------------------- continuous batching
@pytest.fixture(scope="module")
def served_lm():
    lm = ServedLM("cb-lm", load_servable(ZOO_SRC), ZOO_SRC,
                  decode=DecodeConfig(slots=2, page_size=8,
                                      queue_limit=8))
    yield lm
    lm.shutdown(drain=False, timeout=5)


def test_late_join_streams_before_batch_drains(served_lm):
    """THE continuous-batching proof: request B, submitted while A is
    mid-generation, gets its first token before A finishes — token-level
    join, not request-level batching."""
    joins_before = monitor.counter(
        "serving_decode_preempted_joins_total", "x",
        labels=("model",)).value(model="cb-lm")
    a = served_lm.generate([1, 2, 3], max_new_tokens=24,
                           temperature=0.7, top_k=8)
    # wait until A is genuinely mid-stream
    first_a = a.events.get(timeout=30)
    assert first_a[0] == "token"
    b = served_lm.generate([4, 5], max_new_tokens=4)
    b_events = drain_events(b)
    a_events = drain_events(a)
    assert b_events[-1][0] == "done" and a_events[-1][0] == "done"
    b_first_token_t = b_events[0][2]
    a_done_t = a_events[-1][2]
    assert b_first_token_t < a_done_t, \
        "late join waited for the running batch to drain"
    # and the scheduler metered the mid-flight join
    joins_after = monitor.counter(
        "serving_decode_preempted_joins_total", "x",
        labels=("model",)).value(model="cb-lm")
    assert joins_after > joins_before


def test_eos_and_temperature_sampling(served_lm):
    # greedy run to learn the deterministic tokens, then take for eos the
    # first one that no earlier token is (the seeded model's greedy tokens
    # repeat: the third is the first again, and an eos that stood earlier
    # would end the run there)
    r = served_lm.generate([7, 8, 9], max_new_tokens=6)
    toks = [p for k, p, _ in drain_events(r) if k == "token"]
    assert len(toks) == 6
    at = next(i for i in range(1, 6) if toks[i] not in toks[:i])
    r = served_lm.generate([7, 8, 9], max_new_tokens=6, eos_id=toks[at])
    evs = drain_events(r)
    assert evs[-1][1]["finish_reason"] == "eos"
    assert [p for k, p, _ in evs if k == "token"] == toks[:at]
    # sampled run stays in-vocab and honors the token budget
    r = served_lm.generate([7, 8, 9], max_new_tokens=5, temperature=1.3,
                           top_k=5)
    toks = [p for k, p, _ in drain_events(r) if k == "token"]
    assert len(toks) == 5 and all(0 <= t < 48 for t in toks)


def test_generation_caps_at_max_context(served_lm):
    """max_tokens beyond the KV capacity is clamped server-side; the
    stream ends cleanly at the context cap, never a crash."""
    prompt = list(range(28))                  # 28 + budget vs ctx 32
    r = served_lm.generate(prompt, max_new_tokens=500)
    evs = drain_events(r)
    toks = [p for k, p, _ in evs if k == "token"]
    assert evs[-1][0] == "done"
    assert len(toks) == 32 - 28               # clamped to remaining room


def test_join_queue_overload_raises_429_shape(served_lm):
    """Saturate both slots with long generations, then overfill the join
    queue — admission control must answer ServerOverloadedError, not
    queue unboundedly."""
    live = [served_lm.generate([1], max_new_tokens=40, temperature=0.5)
            for _ in range(2)]
    with pytest.raises(ServerOverloadedError):
        for _ in range(16):                   # queue_limit is 8
            live.append(served_lm.generate([1], max_new_tokens=40))
    for r in live:
        r.cancel()
        drain_events(r, timeout=60)


def test_invalid_prompts_rejected(served_lm):
    with pytest.raises(ValueError):
        served_lm.generate([], max_new_tokens=2)
    with pytest.raises(ValueError):
        served_lm.generate([999], max_new_tokens=2)
    with pytest.raises(ValueError):
        served_lm.generate(list(range(32)), max_new_tokens=2)  # no room


def test_oversubscribed_pool_stall_releases_on_cancel():
    """All slots page-stalled on a dry pool must still honor
    cancellation — releasing a stalled slot is what refills the pool, so
    ignoring cancel here would deadlock the servable forever."""
    lm = ServedLM("stall-lm", load_servable(ZOO_SRC), ZOO_SRC,
                  decode=DecodeConfig(slots=2, page_size=8,
                                      pool_pages=5))   # 4 usable pages
    try:
        reqs = [lm.generate([1] * 8, max_new_tokens=500, temperature=0.5)
                for _ in range(2)]
        # both sequences grow until the pool is dry and every slot stalls
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and \
                lm.scheduler.admitting_engine().cache.free_pages() > 0:
            time.sleep(0.02)
        assert lm.scheduler.admitting_engine().cache.free_pages() == 0
        stalls = monitor.counter("serving_decode_page_stalls_total", "x",
                                 labels=("model",))
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and \
                stalls.value(model="stall-lm") == 0:
            time.sleep(0.02)
        assert stalls.value(model="stall-lm") > 0
        for r in reqs:
            r.cancel()
        evs = [drain_events(r, timeout=30) for r in reqs]
        assert all(e[-1][0] == "done" for e in evs)
        # slots and pages all came back — the pool is usable again
        assert lm.scheduler.admitting_engine().cache.free_pages() == 4
        r = lm.generate([1, 2], max_new_tokens=2)
        assert drain_events(r)[-1][0] == "done"
    finally:
        lm.shutdown(drain=False, timeout=5)


def test_swap_to_shorter_context_stays_safe():
    """A swap that shrinks KV capacity (cfg.max_context derives from the
    model) must update validation, not strand the scheduler."""
    lm = ServedLM("shrink-lm", load_servable(ZOO_SRC), ZOO_SRC,
                  decode=DecodeConfig(slots=2, page_size=8))
    try:
        assert lm.max_context == 32
        lm.swap(ZOO_SRC.replace("seq_length=32", "seq_length=16"))
        assert lm.max_context == 16
        with pytest.raises(ValueError):
            lm.generate(list(range(20)), max_new_tokens=2)
        r = lm.generate([1, 2, 3], max_new_tokens=3)
        evs = drain_events(r)
        assert evs[-1][0] == "done" and evs[-1][1]["version"] == 2
    finally:
        lm.shutdown(drain=False, timeout=5)


def test_deploy_kind_collision_is_loud():
    registry = ModelRegistry()
    registry.deploy_lm("m", ZOO_SRC,
                       decode=DecodeConfig(slots=2, page_size=8))
    with pytest.raises(ModelLoadError):
        registry.deploy("m", "zoo:LeNet", buckets=(1,))
    registry.undeploy("m", drain=False)
    registry.deploy("m", "zoo:LeNet", buckets=(1,))
    with pytest.raises(ModelLoadError):
        registry.deploy_lm("m", ZOO_SRC)
    registry.shutdown(drain=False)


# -------------------------------------- prefix cache + chunked prefill
def _greedy(lm, prompt, n=8):
    """(tokens, done-info) for one greedy generation."""
    req = lm.generate(prompt, max_new_tokens=n)
    evs = drain_events(req)
    assert evs[-1][0] == "done", evs[-1]
    return [p for k, p, _ in evs if k == "token"], evs[-1][1]


@pytest.fixture(scope="module")
def parity_lms():
    """The same model behind three decode configs: prefix cache on
    (default), prefix cache off, and cache off + tiny chunk budget."""
    net_src = ZOO_SRC
    lms = {
        "on": ServedLM("par-on", load_servable(net_src), net_src,
                       decode=DecodeConfig(slots=2, page_size=8)),
        "off": ServedLM("par-off", load_servable(net_src), net_src,
                        decode=DecodeConfig(slots=2, page_size=8,
                                            prefix_cache=False)),
        "chunk": ServedLM("par-chunk", load_servable(net_src), net_src,
                          decode=DecodeConfig(slots=2, page_size=8,
                                              prefix_cache=False,
                                              prefill_chunk_tokens=8)),
    }
    yield lms
    for lm in lms.values():
        lm.shutdown(drain=False, timeout=5)


def test_prefix_cache_greedy_parity_cold_hot_and_cow(parity_lms):
    """THE parity contract: greedy tokens bitwise-identical with the
    prefix cache on vs off — cold (miss), hot (shared-prefix hit), and
    the copy-on-write divergence case (page-aligned fully-cached
    prompt). The cache may only change WHERE KV comes from, never what
    gets sampled."""
    on, off = parity_lms["on"], parity_lms["off"]
    prefix = list(range(16))                      # 2 full pages
    # cold: identical programs either way, nothing cached yet
    t_on, i_on = _greedy(on, prefix + [17, 18, 19])
    t_off, i_off = _greedy(off, prefix + [17, 18, 19])
    assert t_on == t_off
    assert i_on["cached_tokens"] == 0 and i_off["cached_tokens"] == 0
    # hot: same prefix, divergent suffix -> 16 tokens of KV reused
    t_on, i_on = _greedy(on, prefix + [20, 21])
    t_off, _ = _greedy(off, prefix + [20, 21])
    assert t_on == t_off, (t_on, t_off)
    assert i_on["cached_tokens"] == 16
    # COW: page-aligned fully-cached prompt — the forced last-token
    # recompute diverges onto a private page copy
    t_on, i_on = _greedy(on, prefix)
    t_off, _ = _greedy(off, prefix)
    assert t_on == t_off, (t_on, t_off)
    assert i_on["cached_tokens"] == 15            # prompt_len - 1


def test_chunked_prefill_parity_and_chunk_accounting(parity_lms):
    """Chunking on vs off: identical greedy tokens, and the done event
    reports the budgeted chunk count (20-token prompt / 8-token budget
    -> 3 chunks)."""
    off, chunk = parity_lms["off"], parity_lms["chunk"]
    prompt = list(np.random.RandomState(5).randint(0, 48, 20))
    t_c, i_c = _greedy(chunk, prompt)
    t_o, i_o = _greedy(off, prompt)
    assert t_c == t_o, (t_c, t_o)
    assert i_c["prefill_chunks"] == 3             # 8 + 8 + 4
    assert i_o["prefill_chunks"] == 1             # whole prompt, one shot


def test_chunked_and_cow_traffic_never_compiles_on_request_path(
        parity_lms):
    """compiles == warmups per model AFTER hot/COW/chunked traffic: the
    chunk ladder and the COW copy were all AOT-warmed, so none of the
    new code paths paid for XLA on a live stream."""
    def fam_sum(family, model):
        total = 0.0
        for line in monitor.prometheus_text().splitlines():
            if line.startswith(family + "{") and f'model="{model}"' in line:
                total += float(line.rsplit(" ", 1)[1])
        return total

    for model in ("par-on", "par-off", "par-chunk"):
        csum = fam_sum("serving_decode_compiles_total", model)
        wsum = fam_sum("serving_decode_warmup_runs_total", model)
        assert csum == wsum and csum > 0, (model, csum, wsum)


def test_burst_admissions_drain_queue_in_one_tick():
    """When several slots free in one token step, the next admission
    pass must drain the join queue until slots or queue are exhausted —
    not trickle one admission per step. Driven tick-by-tick (no
    scheduler thread) so the assertion is on a single _admit pass."""
    eng = DecodeEngine(load_servable(ZOO_SRC),
                       DecodeConfig(slots=4, page_size=8), name="burst")
    eng.warm()
    sched = DecodeScheduler("burst", queue_limit=16)
    sched._started = True                 # keep the loop thread off
    sched.install(eng, version=1)
    reqs = [GenerateRequest([1, 2, 3], max_new_tokens=1)
            for _ in range(6)]
    for r in reqs:
        sched.submit(r)
    run = sched._runs[-1]
    assert sched._admit() is True
    # ONE pass filled every free slot from the queue
    assert len(run.prefill) == 4
    assert sched.queue_state()[0] == 2
    # prefill completes all four; max_new_tokens=1 finishes them at the
    # first token, freeing all four slots within the same tick
    assert sched._prefill_tick() is True
    assert len(run.prefill) == 0 and len(run.slot_req) == 0
    # the next pass admits the whole remainder at once
    assert sched._admit() is True
    assert len(run.prefill) == 2 and sched.queue_state()[0] == 0
    sched._prefill_tick()
    for r in reqs:
        assert r.done.is_set() and r.finish_reason == "length"
    sched._stop.set()
    eng.close()


def test_prefill_budget_caps_tokens_per_tick():
    """The per-tick prefill budget bounds how much prefill runs between
    decode steps: a 24-token prompt under an 8-token budget takes three
    ticks, one page-aligned chunk each — the head-of-line guarantee an
    in-flight stream's ITL rests on."""
    eng = DecodeEngine(load_servable(ZOO_SRC),
                       DecodeConfig(slots=2, page_size=8,
                                    prefill_chunk_tokens=8),
                       name="budget")
    eng.warm()
    sched = DecodeScheduler("budget", queue_limit=4)
    sched._started = True
    sched.install(eng, version=1)
    req = GenerateRequest(list(range(24)), max_new_tokens=2)
    sched.submit(req)
    assert sched._admit() is True
    run = sched._runs[-1]
    job = next(iter(run.prefill.values()))
    for expect_pos in (8, 16, 24):
        sched._prefill_tick()
        assert job.pos == expect_pos
    assert not run.prefill and len(run.slot_req) == 1
    assert req.n_emitted == 1                     # first token delivered
    sched._step_all()
    assert req.done.is_set()
    sched._stop.set()
    eng.close()


# ----------------------------------------------------------- HTTP + swap
@pytest.fixture(scope="module")
def lm_server():
    registry = ModelRegistry()
    registry.deploy_lm("lm", ZOO_SRC,
                       decode=DecodeConfig(slots=2, page_size=8))
    server = ModelServer(registry, port=0, default_deadline_s=60.0)
    yield server, registry
    server.drain(timeout=10)


def _gen(url, payload, headers=None, timeout=60):
    h = {"Content-Type": "application/json"}
    h.update(headers or {})
    return urllib.request.urlopen(urllib.request.Request(
        url + "/v1/models/lm/generate", data=json.dumps(payload).encode(),
        headers=h), timeout=timeout)


def test_http_sse_stream_and_json(lm_server):
    server, _ = lm_server
    r = _gen(server.url, {"prompt": [1, 2, 3], "max_tokens": 5})
    assert r.status == 200
    assert r.headers.get("Content-Type") == "text/event-stream"
    events = [json.loads(line[6:]) for line in r
              if line.startswith(b"data: ")]
    toks = [e["token"] for e in events if "token" in e]
    assert len(toks) == 5
    assert events[-1]["done"] and events[-1]["finish_reason"] == "length"
    # buffered JSON answer carries the same tokens (greedy = determinism)
    r = _gen(server.url, {"prompt": [1, 2, 3], "max_tokens": 5,
                          "stream": False})
    doc = json.loads(r.read())
    assert doc["tokens"] == toks
    assert doc["finish_reason"] == "length"
    assert doc["ttft_ms"] is not None


def test_http_generate_error_mapping(lm_server):
    server, registry = lm_server
    # bad prompt -> 400
    with pytest.raises(urllib.error.HTTPError) as e:
        _gen(server.url, {"prompt": [9999]})
    assert e.value.code == 400
    # unknown model -> 404
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(urllib.request.Request(
            server.url + "/v1/models/nope/generate", data=b"{}",
            headers={"Content-Type": "application/json"}), timeout=10)
    assert e.value.code == 404
    # generate against a predict servable -> 400 with a pointed message
    registry.deploy("lenet", "zoo:LeNet", buckets=(1,))
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(urllib.request.Request(
            server.url + "/v1/models/lenet/generate",
            data=json.dumps({"prompt": [1]}).encode(),
            headers={"Content-Type": "application/json"}), timeout=30)
    assert e.value.code == 400
    assert "predict servable" in json.loads(e.value.read())["error"]


def test_http_rolling_swap_mid_stream(lm_server):
    """A stream started on v1 finishes on v1 while the swap warms and
    flips admissions to v2; the next stream answers v2. Compile ledger
    stays balanced across the swap."""
    server, _ = lm_server
    r1 = _gen(server.url, {"prompt": [2, 4], "max_tokens": 30,
                           "temperature": 0.5})
    assert r1.headers.get("X-Model-Version") == "1"
    first = r1.readline()                     # stream is live
    assert first.startswith(b"data: ")
    swap = urllib.request.urlopen(urllib.request.Request(
        server.url + "/v1/models/lm/swap",
        data=json.dumps({"source": ZOO_SRC + "&seed=99"}).encode(),
        headers={"Content-Type": "application/json"}), timeout=300)
    assert swap.status == 200
    # v1 stream still completes cleanly after the swap
    tail = [json.loads(line[6:]) for line in r1
            if line.startswith(b"data: ")]
    assert tail[-1].get("done"), tail[-1]
    r2 = _gen(server.url, {"prompt": [2, 4], "max_tokens": 3})
    assert r2.headers.get("X-Model-Version") == "2"
    [_ for _ in r2]

    def fam_sum(family):
        total = 0.0
        for line in monitor.prometheus_text().splitlines():
            if line.startswith(family + "{") and 'model="lm"' in line:
                total += float(line.rsplit(" ", 1)[1])
        return total

    csum = fam_sum("serving_decode_compiles_total")
    wsum = fam_sum("serving_decode_warmup_runs_total")
    assert csum == wsum and csum > 0


def test_vocab_mismatch_swap_rejected(lm_server):
    server, _ = lm_server
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(urllib.request.Request(
            server.url + "/v1/models/lm/swap",
            data=json.dumps({"source": ZOO_SRC.replace(
                "vocab_size=48", "vocab_size=64")}).encode(),
            headers={"Content-Type": "application/json"}), timeout=300)
    assert e.value.code == 400


def test_http_concurrent_streams_zero_errors(lm_server):
    server, _ = lm_server
    errors, tokens = [], []

    def worker(i):
        try:
            r = _gen(server.url, {"prompt": [i % 48, 1], "max_tokens": 6,
                                  "temperature": 0.9, "top_k": 4})
            evs = [json.loads(line[6:]) for line in r
                   if line.startswith(b"data: ")]
            if not evs or not evs[-1].get("done"):
                errors.append((i, "truncated"))
            tokens.append(sum(1 for e in evs if "token" in e))
        except Exception as e:              # noqa: BLE001
            errors.append((i, repr(e)))

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors
    assert all(n == 6 for n in tokens), tokens


# ---------------------------------------------------------- fleet/router
@pytest.mark.slow
def test_router_streams_through_inprocess_fleet():
    from deeplearning4j_tpu.serving.fleet import (
        InProcessReplica, ReplicaSpec, ReplicaSupervisor,
    )
    from deeplearning4j_tpu.serving.router import (
        ResilientRouter, RouterServer,
    )
    spec = ReplicaSpec([], lms=[("lm", ZOO_SRC)],
                       decode=DecodeConfig(slots=2, page_size=8))
    sup = ReplicaSupervisor(
        lambda i: InProcessReplica(f"replica-{i}", spec), 2)
    sup.start()
    router = ResilientRouter(sup.healthy)
    server = RouterServer(router, supervisor=sup)
    try:
        r = _gen(server.url, {"prompt": [1, 2], "max_tokens": 4},
                 headers={"X-Priority": "interactive"})
        assert r.status == 200
        assert r.headers.get("X-Served-By", "").startswith("replica-")
        evs = [json.loads(line[6:]) for line in r
               if line.startswith(b"data: ")]
        assert sum(1 for e in evs if "token" in e) == 4
        assert evs[-1].get("done")
        # stream metering is its own family
        streams = monitor.counter(
            "serving_router_stream_requests_total", "x",
            labels=("model", "code", "cls"))
        assert streams.value(model="lm", code="200",
                             cls="standard") >= 1 \
            or streams.value(model="lm", code="200",
                             cls="interactive") >= 1
    finally:
        sup.stop()
        server.stop()


# ------------------------------------------------------ the smoke (slow)
@pytest.mark.slow
def test_decode_smoke_gate(tmp_path):
    """tools/decode_smoke.py end-to-end: N concurrent streams through a
    mid-traffic hot-swap, zero 5xx, ledger equality, variant quality —
    asserted by the tool itself (exit 0 == contract held)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = tmp_path / "DECODE_test.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join("tools", "decode_smoke.py"),
         "--streams", "3", "--requests", "9", "--max-new-tokens", "12",
         "--n-layers", "1", "--n-embd", "64", "--seq-length", "64",
         "--vocab", "128", "--out", str(out)],
        cwd=repo, env=env, capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-2000:]
    doc = json.loads(out.read_text())
    assert doc["ok"] and doc["sweep"][0]["zero_5xx"]
    assert doc["sweep"][0]["decode_tokens_sec"] > 0
    # prefix-cache + chunked-prefill acceptance, re-asserted here so the
    # gate fails loudly even if the tool's own failure list regresses:
    # the cache engaged, compiles==warmups held WITH chunking enabled,
    # hot TTFT >= 2x better than cold, chunking improved interferer ITL
    assert doc["prefix_loadgen"]["prefix"]["cache_hit_rate"] > 0
    assert doc["kv_cache"]["hits"] > 0
    assert doc["ledger"]["compiles"] == doc["ledger"]["warmups"] > 0
    assert doc["prefix_ttft"]["hot_p99_ms"] * 2 \
        <= doc["prefix_ttft"]["cold_p99_ms"]
    assert doc["interferer_itl"]["chunked_p99_ms"] \
        < doc["interferer_itl"]["nochunk_p99_ms"]
