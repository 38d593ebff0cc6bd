"""A serving cell: the model behind the program's HTTP front end in THIS
process (only the holder of the chip can trace it), load offered by a
JAX-free child (``lib/loadgen.py``) from a schedule that ``lib/traffic.py``
made from the traffic file and the seed.

Time line of a run: set-up (weights on the device from the seed, the
engine and its warm-up, the server), then the lead-in the traffic file
states (steady state at the window's opening; charged to ``setup_s``),
then the window, then a short tail in which requests due inside the window
may still get their first token. Once the load has stopped the program's
state is freed and a seeded sample of the requests it finished, the
longest among them, is followed by the plain reference: ``correct`` is the
widest gap by which a served token's logit lies below the reference's
best.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

from benchmark.lib import checks, device, serve_stats, xplane
from benchmark.lib import traffic as gen
from benchmark.lib.manifest import BENCH_DIR, load_module


def _sleep_until(t):
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(0.02, left))


def pick_checked(records, seed: int, n: int):
    """The requests to follow in the reference: the longest finished one
    and ``n - 1`` more drawn from the seed."""
    done = sorted((r for r in records if r.get("finish") == "length"
                   and len(r["tokens"]) == r["max_tokens"]),
                  key=lambda r: r["k"])
    if not done:
        return []
    longest = max(done, key=lambda r: r["prompt_tokens"] + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 21])
    more = [rest[i] for i in rng.permutation(len(rest))[:max(n - 1, 0)]]
    return [longest] + more


def check_served(ref, cfg, seed, sched, picked, precision="highest"):
    """The gaps of every served token of the picked requests under the
    reference: (widest, mean, tokens compared)."""
    prompts = {r["k"]: r["prompt"] for r in sched["requests"]}
    gaps = []
    for r in picked:
        prompt, served = prompts[r["k"]], r["tokens"]
        seq = list(prompt) + list(served[:-1])
        logits = ref.forward_logits(cfg, seed, seq, len(prompt) - 1,
                                    len(served), precision="highest")
        if precision == "highest":
            tokens = served
        else:
            # the control: the token the lower precision puts first at
            # each position of the same prompt and tokens
            tokens = ref.forward_logits(
                cfg, seed, seq, len(prompt) - 1, len(served),
                precision=precision).argmax(axis=-1)
        gaps.append(checks.served_gaps(logits, tokens))
    gaps = np.concatenate(gaps)
    return float(gaps.max()), float(gaps.mean()), len(gaps)


def offer(url, sched, out_dir, system, tracer=None, traffic=None):
    """Offer one schedule to a live server through the load generator's
    child process. Returns (records, window open, window close, what was
    read at the window's edges)."""
    sched_path = os.path.join(out_dir, "schedule.json")
    rec_path = os.path.join(out_dir, "records.json")
    with open(sched_path, "w") as f:
        json.dump(sched, f)
    if os.path.exists(rec_path):
        os.remove(rec_path)
    t0 = time.monotonic() + 0.5
    w0 = t0 + sched["lead_in_s"]
    w1 = w0 + sched["seconds"]
    mid = {}
    child = subprocess.Popen(
        [sys.executable, os.path.join(BENCH_DIR, "lib", "loadgen.py"),
         sched_path, url, repr(t0), rec_path])
    try:
        _sleep_until(w0)
        mid["split0"] = system.decode_time_totals()
        if tracer:
            # the last seconds of the window: stopping the profiler takes
            # a minute, and it does so after the window has closed
            _sleep_until(w1 - float(traffic["trace_s"]))
            tracer.start()
            t_tr0 = time.monotonic()
        _sleep_until(w1)
        mid["split1"] = system.decode_time_totals()
        if tracer:
            mid["traced"] = (t_tr0, time.monotonic())
            tracer.stop()
        rc = child.wait(timeout=sched["ttft_limit_s"] + 60)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    if rc != 0 or not os.path.exists(rec_path):
        raise SystemExit(f"benchmark: the load generator exited {rc}")
    with open(rec_path) as f:
        return json.load(f), w0, w1, mid


def run(cell, seed, seconds, trace, out_dir, t_start):
    info = device.require(cell.chips)
    device.enable_compile_cache()
    cfg, traffic = cell.config, cell.traffic
    ref = load_module("references", cell.config_name)
    system = load_module("systems", cfg["system"])
    from deeplearning4j_tpu import monitor

    serving = system.serve(cfg, ref.make_weights(cfg, seed))
    sched = gen.schedule(traffic, seed, seconds, cfg["vocab_size"])
    if trace:
        # the program's spans (with their profiler annotations) and the
        # scheduler loop's time split only in the traced run
        monitor.goodput.enable_goodput()
        monitor.enable_tracing(jax_annotations=True)
    tracer = xplane.Session(out_dir, cell.chips) if trace else None
    records, w0, w1, mid = offer(serving.url, sched, out_dir, system,
                                 tracer, traffic)
    setup_s = w0 - t_start
    print(f"[setup] {setup_s:.3f} s to the window's opening, "
          f"{sched['lead_in_s']:.1f} s of it lead-in", flush=True)
    peak = device.memory_peak_bytes(cell.chips)
    spans = [e for e in monitor.trace_events()
             if e.get("ph") == "X"] if trace else []
    slots = serving.slots
    serving.close()
    del serving

    st = serve_stats.reduce(records, w0, w1, sched["ttft_limit_s"])
    print("[window] " + json.dumps(st), flush=True)
    with open(os.path.join(out_dir, "stats.json"), "w") as f:
        json.dump(st, f)

    t_ref = time.monotonic()
    picked = pick_checked(records, seed, int(traffic["check_requests"]))
    rows = [("requests_finished_to_check", float(not picked), 0.0)]
    if picked:
        widest, mean, n_tok = check_served(ref, cfg, seed, sched, picked)
        rows.append(("served_logit_gap", widest,
                     cfg["limits"]["served_logit_gap"]))
        rows.append(("served_logit_gap_mean", mean,
                     cfg["limits"]["served_logit_gap_mean"]))
        print(f"[check] reference followed {len(picked)} requests "
              f"({n_tok} served tokens, longest "
              f"{picked[0]['prompt_tokens']}+{len(picked[0]['tokens'])}) "
              f"in {time.monotonic() - t_ref:.1f} s", flush=True)
    correct = checks.verdict(rows)

    result = {"correct": correct, "attempted": st["attempted"],
              "failed": st["failed"],
              "device": {**info, "memory_peak_bytes": peak}}
    e2e = {"serve_ttft_mean_ms": st.get("ttft_mean_ms"),
           "serve_itl_p95_ms": st["itl_p95_ms"],
           "setup_s": setup_s}
    if not trace:
        return result, e2e, None
    split = {k: mid["split1"].get(k, 0.0) - mid["split0"].get(k, 0.0)
             for k in mid["split1"]}
    ctx = {"cell": cell, "trace": tracer.reduce(), "spans": spans,
           "window": (w0, w1), "stats": st, "e2e": e2e, "split": split,
           "load": serve_stats.live_load(records, *mid["traced"]),
           "slots": slots, "reference": ref, "system": system,
           "peaks": device.PEAKS.get(info["kind"])}
    return result, e2e, ctx
