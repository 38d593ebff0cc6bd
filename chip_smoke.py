#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

Drives the main path once on a TPU, through the entry points a user
calls, and checks what comes out:

- train : zoo ResNet-50 (1000 classes, 224x224, batch 128, bf16) through
          ``ComputationGraph.fit()`` over fresh host uint8 batches with an
          ``ImagePreProcessingScaler`` — the TPU-default scan-of-10
          program AND the per-call program — then save_model ->
          load_model -> identical ``output()``.
- kernel: the Pallas flash-attention kernels compiled (``interpret=
          False``), forward and ``jax.grad``, against the dense path; then
          ``fit()`` steps of ``TransformerLM(attention_impl="flash")`` whose
          compiled step must contain the Pallas custom calls.
- serve : the serving CLI as its own process, concurrent streaming
          generate requests from a JAX-free client, compiles == warm-up
          runs on /metrics, SIGTERM, exit 0.
- mesh  : with >= 4 TPU devices, the train fits again under
          ``fit(plan=ShardingPlan(data=-1))``; with fewer it says that it
          did not run.

One process per chip: this process never imports JAX. Each phase is its
own child, one after another, each exiting before the next starts, all
sharing one compile cache ($JAX_COMPILATION_CACHE_DIR, else
<checkout>/.jaxcache). The serve phase stays JAX-free itself, so the
server it starts is the only process that holds the chip.

No chip means a non-zero exit within seconds and no result line; nothing
is run on the CPU instead. ``--rehearse-cpu`` asks, explicitly, for a tiny
CPU rehearsal of the same control flow (interpreted kernel, toy widths);
its result line says ``"rehearsal": true`` and is not a chip result.

The last line of stdout on success is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
PHASES = ("train", "kernel", "serve", "mesh")
NO_TPU_RC = 3           # a phase child's "jax found no TPU" exit code
BUDGET_S = 1150.0       # the whole run, compilation included

# Nesterov(0.1), the zoo's ImageNet recipe, needs a warm-up: from a random
# init it diverges within 30 steps (loss 12 -> 60 on repeated batches at
# 64x64, batch 16, on the CPU). A smoke that asserts a falling loss needs
# a step size that falls without one (12.4 -> 6.8 in 24 steps there).
TRAIN_LR = 1e-3

LM_FULL = dict(vocab_size=8192, seq_length=2048, n_layers=2, n_embd=1024,
               n_heads=8)                           # head dim 128
LM_TINY = dict(vocab_size=64, seq_length=64, n_layers=2, n_embd=32,
               n_heads=4)

FULL = dict(
    train=dict(hw=224, classes=1000, batch=128, scan_steps=None, scan_k=10,
               per_call=4),
    kernel=dict(parity=((4, 2048, 8, 64), (4, 2048, 8, 128)),
                masked=(2, 512, 4, 64), lm=LM_FULL, lm_batch=2, lm_steps=3),
    serve=dict(lm=LM_FULL, slots=16, page=16,
               first_wave=(700, 12, 200), late_wave=(12, 40, 1500),
               first_tokens=96, late_tokens=24, ready_s=900.0),
)
# the same control flow at sizes a CPU finishes in seconds; scan_steps is
# explicit because fit()'s default off-TPU is the per-call program
REHEARSAL = dict(
    # (not smaller: at 32x32, batch 4, batch norm sees 4 values a channel
    # and the loss rises at any step size)
    train=dict(hw=64, classes=1000, batch=8, scan_steps=2, scan_k=2,
               per_call=2),
    kernel=dict(parity=((2, 64, 2, 16),), masked=(2, 64, 2, 16),
                lm=LM_TINY, lm_batch=2, lm_steps=2),
    serve=dict(lm=LM_TINY, slots=4, page=8,
               first_wave=(30, 6, 20), late_wave=(6, 12, 40),
               first_tokens=12, late_tokens=6, ready_s=300.0),
)


def say(phase, msg):
    print(f"[{phase}] {msg}", flush=True)


# ------------------------------------------------------------ JAX phases
def _device(phase, rehearse):
    """The device as JAX reports it; without a TPU (and without an
    explicit rehearsal) the phase ends here, before anything runs."""
    from deeplearning4j_tpu.util.platform import device_info
    info = device_info()
    say(phase, f"device: platform={info['platform']} "
               f"device_kind={info['kind']!r} count={info['count']}")
    if info["platform"] != "tpu" and not rehearse:
        sys.stderr.write(f"chip_smoke: no TPU — jax found {info}; nothing "
                         "was run\n")
        raise SystemExit(NO_TPU_RC)
    return info


def _check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def phase_train(sz, rehearse=False, mesh=False):
    """ResNet-50 through ComputationGraph.fit(): scan-of-K, then
    per-call, then the checkpoint round trip. `mesh`: the same fits under
    a data-parallel ShardingPlan over every visible device."""
    phase = "mesh" if mesh else "train"
    info = _device(phase, rehearse)
    if mesh and info["count"] < 4:
        say(phase, f"NOT RUN: {info['count']} device(s) visible, the "
                   "data-parallel mesh check needs >= 4")
        return {"device": info, "ran": False}

    import dataclasses
    import tempfile

    import jax
    import numpy as np

    from deeplearning4j_tpu import monitor, native
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterator import ExistingDataSetIterator
    from deeplearning4j_tpu.data.normalization import (
        ImagePreProcessingScaler)
    from deeplearning4j_tpu.models import ResNet50
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.updaters import Nesterovs
    from deeplearning4j_tpu.train.listeners import (
        CollectScoresIterationListener)
    from deeplearning4j_tpu.util.serialization import load_model, save_model

    say(phase, "native host library: "
        + ("built (g++)" if native.available() else "numpy fallback"))
    hw, classes, batch = sz["hw"], sz["classes"], sz["batch"]
    plan = None
    if mesh:
        from deeplearning4j_tpu.parallel.plan import ShardingPlan
        plan = ShardingPlan(data=-1)
    monitor.xla.enable_ledger()

    conf = dataclasses.replace(
        ResNet50(num_classes=classes, input_shape=(hw, hw, 3)).conf(),
        compute_dtype="bfloat16", updater=Nesterovs(TRAIN_LR, momentum=0.9))
    net = ComputationGraph(conf).init()
    scores = CollectScoresIterationListener(1)
    net.set_listeners(scores)

    # fresh host uint8 batches from a seed. Four distinct ones, repeated,
    # with labels from 16 classes: a loss that must fall needs batches
    # the run sees again
    rng = np.random.default_rng(21)
    distinct = [DataSet(
        rng.integers(0, 256, (batch, hw, hw, 3), dtype=np.uint8),
        np.eye(classes, dtype="float32")[
            rng.integers(0, min(16, classes), batch)])
        for _ in range(4)]

    def batches(n):
        return ExistingDataSetIterator(
            [distinct[i % 4] for i in range(n)]).set_pre_processor(
            ImagePreProcessingScaler())

    def timed_fit(n, **kw):
        t0 = time.perf_counter()
        net.fit(batches(n), plan=plan, **kw)
        return time.perf_counter() - t0

    # scan-of-K: fit()'s own default on a TPU (scan_steps=None there)
    n_scan = 2 * sz["scan_k"]
    scan_first = timed_fit(n_scan, scan_steps=sz["scan_steps"])
    scan_again = timed_fit(n_scan, scan_steps=sz["scan_steps"])
    call_first = timed_fit(sz["per_call"], scan_steps=1)
    call_again = timed_fit(sz["per_call"], scan_steps=1)
    say(phase, f"scan-of-{sz['scan_k']} fit, {n_scan} steps: first call "
               f"{scan_first:.1f}s (compile ~{scan_first - scan_again:.1f}s)"
               f", again {scan_again:.2f}s")
    say(phase, f"per-call fit, {sz['per_call']} steps: first call "
               f"{call_first:.1f}s (compile ~{call_first - call_again:.1f}s)"
               f", again {call_again:.2f}s")

    losses = [s for _, s in scores.scores]
    _check(len(losses) == 2 * n_scan + 2 * sz["per_call"],
           f"expected {2 * n_scan + 2 * sz['per_call']} steps, the "
           f"listener saw {len(losses)}")
    _check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    tail = float(np.mean(losses[-5:]))
    say(phase, f"loss: first {losses[0]:.3f}, mean of last 5 {tail:.3f} "
               f"({len(losses)} steps)")
    _check(tail < losses[0], f"loss did not fall on repeated batches: "
                             f"{[round(x, 3) for x in losses]}")

    on = {d.platform for leaf in jax.tree_util.tree_leaves(net.params)
          for d in leaf.devices()}
    _check(on == {info["platform"]}, f"param leaves live on {on}")

    names = {r.name for r in monitor.xla.records()}
    _check({"graph/train_step", "graph/scan_step"} <= names,
           f"one fit path silently took the other: ledger has {names}")
    mfu = monitor.xla.last_mfu("train")
    _check(mfu is not None, "train_mfu_pct was never set: the peak table "
                            "did not know this device")
    say(phase, f"train_mfu_pct gauge set (last step, smoke-sized window: "
               f"{mfu:.1f}%"
               + (" against a NOMINAL peak" if rehearse else "") + ")")

    report = {"device": info, "ran": True,
              "compile_s": round(scan_first - scan_again
                                 + call_first - call_again, 1),
              "run_s": round(scan_again + call_again, 2)}
    if mesh:
        shards = plan.shard_batch(distinct[0].features).addressable_shards
        shapes = sorted({tuple(s.data.shape) for s in shards})
        say(phase, f"batch {distinct[0].features.shape} over "
                   f"{len({s.device for s in shards})} devices, shard "
                   f"shape {shapes}")
        _check(len({s.device for s in shards}) == info["count"]
               and shapes == [(batch // info["count"], hw, hw, 3)],
               f"batch is not split {info['count']} ways: {shapes}")
        step = next(r for r in monitor.xla.records()
                    if r.name == "graph/train_step")
        _check(step.is_sharded, "the compiled train step's arguments "
                                f"are not mesh-sharded: {step.arg_shardings}")
        stats = {str(d.id): d.memory_stats() for d in jax.devices()}
        in_use = {}
        if None in stats.values():      # the CPU backend reports none
            say(phase, "memory_stats: not reported by this backend")
        else:
            in_use = {i: s["bytes_in_use"] for i, s in stats.items()}
            say(phase, f"bytes_in_use per device: {in_use}")
            _check(all(v > 0 for v in in_use.values()),
                   f"a device holds nothing: {in_use}")
        report.update(shard_shape=shapes[0], bytes_in_use=in_use)
        say(phase, "checkpoint round trip: covered by phase train")
        return report

    x = distinct[0].features[:8].astype("float32") / 255.0
    before = np.asarray(net.output(x))
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "resnet50.zip")
        save_model(net, path)
        size_mb = os.path.getsize(path) / 2 ** 20
        after = np.asarray(load_model(path).output(x))
    _check(before.shape == (8, classes) and np.isfinite(before).all(),
           f"output() shape {before.shape} / non-finite")
    _check(np.array_equal(before, after),
           "output() differs after save_model -> load_model")
    say(phase, f"save_model ({size_mb:.0f} MiB) -> load_model -> output() "
               "identical")
    return report


def _attention_parity(phase, shape, dtype, causal, masked, interpret):
    """flash vs dense, forward (+ lse when masked) and gradients. Errors
    are normalised by the reference's largest magnitude: both paths round
    to bf16, so one ulp at the top of the range is the floor."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.nn.layers.attention import dot_product_attention
    from deeplearning4j_tpu.ops import flash_attention

    b, t, h, d = shape
    rs = np.random.RandomState(t + d)
    q, k, v = (jnp.asarray(rs.randn(b, t, h, d), dtype) for _ in range(3))
    mask = None
    if masked:
        m = np.ones((b, t), np.float32)
        m[:, t - t // 4:] = 0.0
        mask = jnp.asarray(m)

    def flash(q, k, v):
        return flash_attention(q, k, v, mask=mask, causal=causal,
                               interpret=interpret, return_lse=masked)

    def dense(q, k, v):
        return dot_product_attention(q, k, v, mask=mask, causal=causal)

    def nerr(a, ref):
        a, ref = (np.asarray(x, np.float32) for x in (a, ref))
        return float(np.max(np.abs(a - ref)) / max(1.0, np.max(np.abs(ref))))

    name = (f"{shape} {jnp.dtype(dtype).name} "
            f"{'causal' if causal else 'full'}{' masked+lse' if masked else ''}")
    t0 = time.perf_counter()
    out = jax.block_until_ready(jax.jit(flash)(q, k, v))
    first = time.perf_counter() - t0
    # the f32 reference multiplies in f32 ("highest"; no effect on bf16
    # operands), so an f32 error measures the kernel alone
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(dense)(q, k, v)
        errs = {}
        if masked:
            out, lse = out
            s = jnp.einsum("bqhd,bkhd->bqhk", q, k,
                           preferred_element_type=jnp.float32) / np.sqrt(d)
            s = jnp.where(mask[:, None, None, :] > 0, s, -jnp.inf)
            errs["lse"] = nerr(lse, jax.nn.logsumexp(s, axis=-1))
    errs["fwd"] = nerr(out, ref)

    def loss(fn):
        def go(q, k, v):
            return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)
        return jax.jit(jax.grad(go, argnums=(0, 1, 2)))

    t0 = time.perf_counter()
    got = jax.block_until_ready(loss(
        (lambda q, k, v: flash(q, k, v)[0]) if masked else flash)(q, k, v))
    first += time.perf_counter() - t0
    with jax.default_matmul_precision("highest"):
        want = loss(dense)(q, k, v)
    for n, a, w in zip(("dq", "dk", "dv"), got, want):
        errs[n] = nerr(a, w)
    tol = {"fwd": 2e-2, "lse": 2e-2, "dq": 6e-2, "dk": 6e-2, "dv": 6e-2}
    ok = all(np.isfinite(e) and e <= tol[n] for n, e in errs.items())
    say(phase, f"flash vs dense {name}: "
        + " ".join(f"{n}={e:.1e}" for n, e in errs.items())
        + f" (tol fwd/lse 2e-2, grads 6e-2) first calls {first:.1f}s "
        + ("OK" if ok else "FAIL"))
    return ok


def phase_kernel(sz, rehearse=False):
    """The hand-written kernels: parity with the dense path, then the
    flash layer inside a real fit()."""
    phase = "kernel"
    info = _device(phase, rehearse)
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.data.iterator import ArrayDataSetIterator
    from deeplearning4j_tpu.models import TransformerLM
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    interpret = info["platform"] != "tpu"       # rehearsal only
    t0 = time.perf_counter()
    oks = [_attention_parity(phase, s, jnp.bfloat16, True, False, interpret)
           for s in sz["parity"]]
    oks.append(_attention_parity(phase, sz["masked"], jnp.float32, False,
                                 True, interpret))
    _check(all(oks), "flash attention does not match the dense path")
    parity_s = time.perf_counter() - t0

    lm = sz["lm"]
    conf = dataclasses.replace(
        TransformerLM(attention_impl="flash", **lm).conf(),
        compute_dtype="bfloat16")
    net = MultiLayerNetwork(conf).init()
    n = sz["lm_batch"] * sz["lm_steps"]
    rs = np.random.RandomState(5)
    ids = rs.randint(0, lm["vocab_size"], (n, lm["seq_length"]))
    x = ids.astype("int32")     # integer ids: a bf16 cast would round them
    y = np.zeros(ids.shape + (lm["vocab_size"],), "float32")
    np.put_along_axis(y, ((ids + 1) % lm["vocab_size"])[..., None], 1.0,
                      axis=-1)

    def fit():
        t0 = time.perf_counter()
        net.fit(ArrayDataSetIterator(x, y, batch_size=sz["lm_batch"]),
                scan_steps=1)
        return time.perf_counter() - t0

    first, again = fit(), fit()
    _check(np.isfinite(net.score()), f"LM loss {net.score()}")
    say(phase, f"TransformerLM(attention_impl='flash', {lm}) fit: "
               f"{sz['lm_steps']} steps first call {first:.1f}s (compile "
               f"~{first - again:.1f}s), again {again:.2f}s, loss "
               f"{net.score():.3f}")

    if interpret:
        say(phase, "Pallas custom call in the compiled step: NOT CHECKED "
                   "(off-TPU the layer takes the lax blockwise path)")
    else:
        # the step fit() just ran, lowered again at the same shapes (the
        # ledger's own method; a compile-cache hit)
        from deeplearning4j_tpu.nn.fit_loop import compiled_step
        step = compiled_step(net, "step")
        xs = jnp.asarray(x[:sz["lm_batch"]])
        ys = jnp.asarray(y[:sz["lm_batch"]], jnp.bfloat16)
        hlo = step.lower(net.params, net.opt_state, net.state, xs, ys,
                         None, None, jax.random.PRNGKey(0),
                         None).compile().as_text()
        # (the kernel names alone prove nothing: an interpreted kernel
        # leaves them in the op metadata too)
        calls = hlo.count('custom_call_target="tpu_custom_call"')
        kernels = [k for k in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
                   if k in hlo]
        say(phase, f"compiled train step: {calls} tpu_custom_call(s), "
                   f"kernels {kernels}")
        _check(calls == 2 * lm["n_layers"]
               and kernels == ["flash_fwd", "flash_bwd_dq"],
               "the compiled LM step does not hold a forward and ONE fused "
               "backward Pallas call (named flash_bwd_dq) for every layer")
    return {"device": info, "ran": True,
            "compile_s": round(first - again, 1),
            "run_s": round(parity_s + again, 2)}


# ----------------------------------------------------- serve (JAX-free)
def _get(url, timeout=10.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read().decode()


def _metric_sum(text, family):
    total = 0.0
    for line in text.splitlines():
        if line.startswith(family) and line[len(family)] in " {":
            total += float(line.rsplit(" ", 1)[1])
    return total


def _stream(url, prompt, max_tokens, on_first=None):
    """One greedy streaming generation: (tokens, done event or None)."""
    body = json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                       "temperature": 0.0, "stream": True}).encode()
    req = urllib.request.Request(
        url + "?deadline_ms=120000", data=body,
        headers={"Content-Type": "application/json"})
    tokens, done = [], None
    with urllib.request.urlopen(req, timeout=300) as r:
        for line in r:
            if not line.startswith(b"data: "):
                continue
            ev = json.loads(line[6:])
            if "token" in ev:
                tokens.append(ev["token"])
                if on_first is not None and len(tokens) == 1:
                    on_first()
            elif ev.get("done"):
                done = ev
            elif "error" in ev:
                raise RuntimeError(f"stream error: {ev['error']}")
    return tokens, done


def phase_serve(sz, rehearse=False):
    """The serving CLI as its own process; this one never imports JAX."""
    phase = "serve"
    lm = sz["lm"]
    spec = ("chat=zoo:TransformerLM?"
            + "&".join(f"{k}={v}" for k, v in lm.items())
            + "@bf16")
    argv = [sys.executable, "-m", "deeplearning4j_tpu.serving",
            "--lm", spec, "--port", "0",
            "--decode-slots", str(sz["slots"]),
            "--decode-page-size", str(sz["page"]),
            "--decode-max-context", str(lm["seq_length"]),
            "--drain-timeout-s", "60"]
    say(phase, "starting: " + " ".join(argv[1:]))
    t0 = time.perf_counter()
    server = subprocess.Popen(argv, cwd=HERE, stdout=subprocess.PIPE,
                              text=True)
    try:
        url = None
        for line in server.stdout:          # EOF when the child dies
            try:
                url = json.loads(line).get("serving")
            except ValueError:
                continue
            if url:
                break
        _check(url, f"the server exited (rc={server.poll()}) before "
                    "announcing its URL")
        # keep draining the child's stdout so it can never block on it
        threading.Thread(target=lambda: [None for _ in server.stdout],
                         daemon=True, name="server-stdout").start()
        ready = None
        while time.perf_counter() - t0 < sz["ready_s"]:
            _check(server.poll() is None,
                   f"the server died while warming (rc={server.poll()})")
            try:
                ready = json.loads(_get(url + "/readyz")[1])
                break
            except (urllib.error.URLError, OSError):
                time.sleep(1.0)
        _check(ready, f"/readyz not 200 within {sz['ready_s']:.0f}s")
        startup = time.perf_counter() - t0
        info = ready["device"]
        say(phase, f"device (the server's /readyz): platform="
                   f"{info['platform']} device_kind={info['kind']!r} "
                   f"count={info['count']}")
        if info["platform"] != "tpu" and not rehearse:
            sys.stderr.write(f"chip_smoke: the server runs on {info}, not "
                             "a TPU\n")
            raise SystemExit(NO_TPU_RC)
        say(phase, f"ready after {startup:.1f}s (start-up, load and the "
                   "AOT warm-up compiles)")

        # prompts of different lengths -> more than one prefill bucket.
        # The late wave starts when the first wave's first token arrives,
        # so it joins a running batch. Prompt 0 of each wave is the SAME
        # short prompt (under one KV page, so both take the same
        # programs): greedy output must repeat.
        import random
        rnd = random.Random(9)
        vocab = lm["vocab_size"]

        def prompt(n):
            return [rnd.randrange(vocab) for _ in range(n)]

        first_wave = [prompt(n) for n in sz["first_wave"]]
        late_wave = [prompt(n) for n in sz["late_wave"]]
        late_wave[0] = first_wave[1]
        gen = url + "/v1/models/chat/generate"
        results, errors = {}, []
        started = threading.Event()

        def run(key, p, n, on_first=None):
            try:
                results[key] = _stream(gen, p, n, on_first)
            except Exception as e:      # noqa: BLE001 — reported below
                errors.append(f"{key}: {type(e).__name__}: {e}")
                started.set()

        t1 = time.perf_counter()
        threads = [threading.Thread(
            target=run, name=f"first-{i}",
            args=(("first", i), p, sz["first_tokens"], started.set))
            for i, p in enumerate(first_wave)]
        for th in threads:
            th.start()
        _check(started.wait(180), "no first token within 180 s")
        late = [threading.Thread(
            target=run, name=f"late-{i}",
            args=(("late", i), p, sz["late_tokens"]))
            for i, p in enumerate(late_wave)]
        for th in late:
            th.start()
        for th in threads + late:
            th.join(300)
        run_s = time.perf_counter() - t1
        _check(not errors, f"streams failed: {errors}")
        n_req = len(first_wave) + len(late_wave)
        _check(len(results) == n_req, f"{len(results)}/{n_req} streams "
                                      "came back")
        for (wave, i), (tokens, done) in sorted(results.items()):
            want = sz["first_tokens" if wave == "first" else "late_tokens"]
            _check(done is not None and done.get("finish_reason"),
                   f"{wave}[{i}] ended without a finish reason: {done}")
            _check(len(tokens) == want
                   and all(0 <= t < vocab for t in tokens),
                   f"{wave}[{i}]: {len(tokens)} tokens, wanted {want}")
        say(phase, f"{n_req} concurrent streams finished in {run_s:.2f}s, "
                   "prompt lengths "
                   f"{[len(p) for p in first_wave + late_wave]}, finish "
                   f"reasons {sorted({d['finish_reason'] for _, d in results.values()})}")
        a, b = results[("first", 1)][0], results[("late", 0)][0]
        _check(a[:len(b)] == b, f"greedy output differs for the repeated "
                                f"prompt: {a[:len(b)]} vs {b}")
        say(phase, f"greedy output identical for the repeated prompt "
                   f"({len(b)} tokens)")
        # one more, alone: a finished prompt extended past whole pages
        # (an observation of the default-on prefix cache, not a check)
        _, done = _stream(gen, first_wave[2] + prompt(15), 4)
        say(phase, f"a finished prompt, extended: cached_tokens="
                   f"{done.get('cached_tokens')} of {len(first_wave[2])}")

        metrics = _get(url + "/metrics")[1]
        compiles = _metric_sum(metrics, "serving_decode_compiles_total")
        warmups = _metric_sum(metrics, "serving_decode_warmup_runs_total")
        joins = _metric_sum(metrics, "serving_decode_preempted_joins_total")
        say(phase, f"/metrics: compiles={compiles:.0f} "
                   f"warmup_runs={warmups:.0f} joins_into_running_batch="
                   f"{joins:.0f}")
        _check(compiles == warmups and compiles > 0,
               "a live stream paid for a compile: compiles != warm-up runs")
        _check(joins > 0, "no request joined a running batch")

        server.send_signal(signal.SIGTERM)
        rc = server.wait(90)
        _check(rc == 0, f"the server exited {rc} after SIGTERM")
        say(phase, "SIGTERM -> drained, exit 0")
        return {"device": info, "ran": True,
                "compile_s": round(startup, 1), "run_s": round(run_s, 2)}
    finally:
        if server.poll() is None:
            server.kill()
            server.wait(30)


# ------------------------------------------------------------- one phase
def run_phase(name, rehearse):
    """Child entry: run one phase in this process, print its report as
    the last line of stdout."""
    sizes = REHEARSAL if rehearse else FULL
    if rehearse:
        # a rehearsal never reaches for a chip; the mesh phase needs
        # devices to shard over; CPU has no tabulated peak, and a nominal
        # one keeps the MFU gauge's control flow in the rehearsal
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()
        os.environ.setdefault("DL4J_TPU_PEAK_FLOPS", "1e12")
    if name == "serve":             # stays JAX-free: no package import
        report = phase_serve(sizes["serve"], rehearse)
    else:
        from deeplearning4j_tpu.util.platform import enable_compile_cache
        say(name, f"compile cache: {enable_compile_cache()}")
        if name == "probe":
            report = {"device": _device(name, rehearse), "ran": True}
        elif name == "kernel":
            report = phase_kernel(sizes["kernel"], rehearse)
        else:
            report = phase_train(sizes["train"], rehearse,
                                 mesh=(name == "mesh"))
    print(json.dumps({"phase": name, "ok": True, **report}), flush=True)


# ---------------------------------------------------------------- parent
def _kill_group(child):
    if child.poll() is None:
        try:
            os.killpg(child.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        child.wait()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma-separated subset, run in the given order")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny CPU rehearsal of the control flow; never "
                         "chosen by the program, not a chip result")
    ap.add_argument("--phase", choices=PHASES + ("probe",),
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.phase:                      # a phase child
        run_phase(args.phase, args.rehearse_cpu)
        return 0
    names = [p for p in args.phases.split(",") if p]
    unknown = [p for p in names if p not in PHASES]
    if unknown or not names:
        ap.error(f"--phases: unknown {unknown}; known: {PHASES}")
    if names[0] == "serve":
        # the serve phase is JAX-free and learns the device only from a
        # warmed server: find out that there is no chip in seconds
        names.insert(0, "probe")

    def on_term(signum, frame):
        raise SystemExit(128 + signum)

    signal.signal(signal.SIGTERM, on_term)
    deadline = time.monotonic() + BUDGET_S
    reports, failed = {}, []
    for name in names:
        cmd = [sys.executable, os.path.abspath(__file__), "--phase", name]
        if args.rehearse_cpu:
            cmd.append("--rehearse-cpu")
        t0 = time.monotonic()
        # own session: one killpg stops the phase and whatever it started
        child = subprocess.Popen(cmd, cwd=HERE, stdout=subprocess.PIPE,
                                 text=True, start_new_session=True)
        timer = threading.Timer(max(1.0, deadline - t0), _kill_group,
                                (child,))
        timer.start()
        last = ""
        try:
            for line in child.stdout:
                print(line, end="", flush=True)
                last = line.strip() or last
            rc = child.wait()
        finally:
            timer.cancel()
            _kill_group(child)
        wall = time.monotonic() - t0
        if rc == NO_TPU_RC:
            sys.stderr.write("chip_smoke: FAILED — no TPU\n")
            return NO_TPU_RC
        try:
            report = json.loads(last) if rc == 0 else None
        except ValueError:
            report = None
        if not report or not report.get("ok"):
            sys.stderr.write(f"chip_smoke: phase {name} FAILED (rc={rc}, "
                             f"{wall:.0f}s)\n")
            failed.append(name)
            continue
        reports[name] = report
        print(f"[{name}] PASS in {wall:.0f}s", flush=True)
    if failed:
        sys.stderr.write(f"chip_smoke: FAILED phases: {failed}\n")
        return 1
    devices = [r["device"] for r in reports.values()]
    if any(d != devices[0] for d in devices):
        sys.stderr.write(f"chip_smoke: FAILED — the phases saw different "
                         f"devices: {devices}\n")
        return 1
    result = {"ok": True, "device": devices[0]}
    if args.rehearse_cpu:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
