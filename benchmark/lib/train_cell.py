"""A training cell: ONE ``fit()`` driven for the whole window.

Set-up builds ONE network with the seeded weights and drives it through its
first chunk of optimizer steps by the window's own call (``fit()`` on the
window's own feed); that call compiles the step, and what it leaves behind
(the losses, the momentum, the change of the parameters) is what the plain
reference is compared with after the window. The same object then runs the
window.

The window is one ``fit()`` call, as a user's epoch is: the feed hands out
host batches until ``--seconds`` have passed (it ends on a whole chunk, so
nothing but the compiled chunk program runs) and the window closes when
``fit()`` has returned and the parameters are ready. The end-to-end rate is
all the window's examples over all its time, the pipeline's fill at the
start and its drain at the end included. A listener stamps the moment each
chunk's losses reach the host; from those stamps the window is cut into
segments of ``segment_steps`` steps, whose rates are printed, kept in the
run's output directory, and reported beside the end-to-end rate as
per-layer statistics (``fit_segment_median_rate``,
``fit_window_rate_ratio``), so that a stall shows where it fell.

A traced run keeps the profiler off for most of the window (the per-layer
rates and shares are taken over that part) and switches it on inside the
same ``fit()`` call, in steady state, for the window's last seconds; see
``_trace_inside``.
"""
from __future__ import annotations

import json
import os
import statistics
import threading
import time

import numpy as np

from benchmark.lib import checks, device, xplane
from benchmark.lib.manifest import load_module


def make_batches(seed: int, n: int, batch: int, cfg: dict):
    """``n`` host batches from the seed: uint8 pixels whose rows all
    differ, one-hot float32 labels."""
    rng = np.random.default_rng([seed, 11])
    size, ch, classes = cfg["image_size"], cfg["channels"], cfg["num_classes"]
    out = []
    for _ in range(n):
        x = np.frombuffer(rng.bytes(batch * size * size * ch),
                          np.uint8).reshape(batch, size, size, ch)
        y = np.zeros((batch, classes), np.float32)
        y[np.arange(batch), rng.integers(0, classes, batch)] = 1.0
        out.append((x, y))
    return out


def cycle_until(pool, chunk: int, done):
    """The pool's batches over and over, ending at the first whole chunk
    at which ``done()`` holds."""
    i = 0
    while i % chunk or not done():
        yield pool[i % len(pool)]
        i += 1


def cut_segments(stamps, t_open: float, chunk: int, seg_steps: int,
                 batch: int):
    """Segments of ``seg_steps`` steps from the listener's stamps (one per
    step; a chunk's steps are stamped together when the chunk is done).
    The first segment starts when the window opens and so holds the
    pipeline's fill."""
    done = [stamps[i] for i in range(chunk - 1, len(stamps), chunk)]
    per = seg_steps // chunk
    out, t0 = [], t_open
    for i in range(per - 1, len(done), per):
        out.append({"i": len(out), "t0": t0 - t_open,
                    "t1": done[i] - t_open,
                    "examples_per_s": seg_steps * batch / (done[i] - t0)})
        t0 = done[i]
    return out


def _trace_inside(plan, t_start, out_dir, chips, before, traced):
    """The traced run's helper thread: one profiler session of ``read_s``
    seconds inside the window's one ``fit()`` call, in steady state.
    ``before()`` is called just before the profiler starts; ``traced`` is
    set when the stretch that is read is over and the profiler is told to
    stop. It goes on recording for 2-4 s more and then takes a minute or
    three to write the trace (66 s after 30 steps, 168 s after 56), the
    window going on meanwhile at 99 % of its rate: hence seconds of trace,
    not the window, and early enough that the run ends inside its limit.
    ``read_s`` holds one whole run of the chunk program even when the
    launch after the profiler's start comes late (it did once, by 1.65 s).
    One session only: a second one in the same process held the next
    launch back for 2.4 s and took 183 s to stop (my chip runs, PR 23).
    The session comes back as ``plan["session"]``."""
    try:
        time.sleep(max(t_start - time.monotonic(), 0.0))
        before()
        session = xplane.Session(out_dir, chips)
        session.start()
        time.sleep(plan["read_s"])
        traced.set()
        session.stop()
        plan["session"] = session
    except BaseException as e:       # the window must end either way
        plan["error"] = e
    finally:
        traced.set()


def _launches(tr, program):
    """Start (from the traced window's opening) and length of each launch
    of ``program``, in seconds, chip 0."""
    lo = tr.window[0]
    return [(round(s - lo, 3), round(e - s, 3))
            for n, s, e in tr.devices[0]["modules"] if n == program]


def run(cell, seed, seconds, trace, out_dir, t_start):
    import jax
    info = device.require(cell.chips)
    device.enable_compile_cache()
    cfg, traffic = cell.config, cell.traffic
    ref = load_module("references", cell.config_name)
    system = load_module("systems", cfg["system"])
    from deeplearning4j_tpu import monitor

    batch, seg_steps = int(traffic["batch"]), int(traffic["segment_steps"])
    chunk = int(traffic["check_steps"])      # one scan chunk of fit()
    assert seg_steps % chunk == 0
    pool = make_batches(seed, int(traffic["pool_batches"]), batch, cfg)
    assert len(pool) >= chunk
    plan = system.make_plan(traffic["plan"])
    net = system.build(cfg, ref.make_params(cfg, seed))
    stamps = system.stamp_listener()
    net.set_listeners(stamps)
    if trace:
        monitor.enable_tracing(jax_annotations=True)
        monitor.goodput.enable_goodput()

    def fit(batches):
        net.fit(system.feed(batches, plan), plan=plan,
                scan_steps=traffic["scan_steps"])
        jax.block_until_ready(net.params)

    # the first chunk: compiles the step, and is what `correct` compares
    fit(pool[:chunk])
    prog = {"losses": [loss for _, loss in stamps.rows][:chunk],
            "momentum": checks.leaf_norms(
                system.trained(system.momentum(net))),
            "update": checks.leaf_norms(jax.tree_util.tree_map(
                lambda a, b: a - b, system.trained(net.params),
                ref.make_params(cfg, seed)))}
    fit(pool[:chunk])                   # one untimed chunk: all warm
    warm = len(stamps.rows)

    t_open = time.monotonic()
    setup_s = t_open - t_start
    print(f"[setup] {setup_s:.3f} s to the window's opening", flush=True)
    traced, cut, tracing, helper = threading.Event(), {}, None, None
    if trace:
        tracing = dict(traffic["trace"])

        def before():
            cut["t"] = time.monotonic()
            cut["fit_s"] = system.fit_seconds_by_category()

        fit_s0 = system.fit_seconds_by_category()
        helper = threading.Thread(target=_trace_inside, args=(
            tracing, t_open + tracing["untraced_share"] * seconds, out_dir,
            cell.chips, before, traced), daemon=True)
        helper.start()
    else:
        traced.set()
    fit(cycle_until(pool, chunk, lambda: traced.is_set() and
                    time.monotonic() - t_open >= seconds))
    t_close = time.monotonic()
    if helper:
        helper.join()
        if "error" in tracing:
            raise tracing["error"]
    rows = stamps.rows[warm:]
    losses = [loss for _, loss in stamps.rows]
    peak = device.memory_peak_bytes(cell.chips)
    steps = len(rows)
    assert steps and steps % chunk == 0, steps
    whole = steps * batch / (t_close - t_open)
    segments = cut_segments([t for t, _ in rows], t_open, chunk, seg_steps,
                            batch)
    for s in segments:
        print(f"[segment] {s['i']:3d} {s['t0']:8.3f}-{s['t1']:8.3f} s "
              f"{s['examples_per_s']:.3f} examples/s", flush=True)
    # a traced run's rates and shares are those of the part before the
    # profiler: whole chunks, from the opening to the last one done by then
    clean = [s for s in segments
             if not cut or s["t1"] <= cut["t"] - t_open]
    rates = [s["examples_per_s"] for s in clean]
    clean_rate = len(clean) * seg_steps * batch / clean[-1]["t1"] \
        if clean else float("nan")
    print(f"[window] {steps} steps in {t_close - t_open:.3f} s, "
          f"{whole:.3f} examples/s; {len(segments)} segments of "
          f"{seg_steps}, before the profiler {len(clean)}: median "
          f"{statistics.median(rates) if rates else float('nan'):.3f}, "
          f"slowest {min(rates, default=float('nan')):.3f}, together "
          f"{clean_rate:.3f} examples/s", flush=True)
    with open(os.path.join(out_dir, "segments.json"), "w") as f:
        json.dump({"segments": segments, "steps": steps,
                   "window_s": t_close - t_open, "whole_window": whole,
                   "profiler_from": cut.get("t", t_close) - t_open}, f)

    # free the program's state, then follow the first chunk in the reference
    net.set_listeners()
    del net
    t_ref = time.monotonic()
    devs = jax.devices()[:cell.chips]
    r_losses, r_trace, r_params = ref.train_steps(
        cfg, ref.make_params(cfg, seed), pool[:chunk], devices=devs)
    r_update = checks.leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, r_params, ref.make_params(cfg, seed)))
    reference = {"losses": r_losses, "update": r_update,
                 "momentum": checks.leaf_norms(r_trace)}
    for what in ("momentum", "update"):
        print(f"[check] widest {what} leaves, not judged leaf by leaf "
              f"(gap, leaf, program, reference): "
              f"{checks.worst_leaves(prog[what], reference[what], 2)}",
              flush=True)
    with open(os.path.join(out_dir, "check.json"), "w") as f:
        json.dump({"program": prog, "reference": reference}, f)
    judged = checks.training_rows(
        prog, reference, lambda leaf: ref.stage_of(cfg, leaf),
        cfg["limits"])
    judged.append(("nonfinite_losses",
                   float(sum(not np.isfinite(x) for x in losses)), 0.0))
    print(f"[check] reference followed {chunk} steps in "
          f"{time.monotonic() - t_ref:.1f} s; program losses "
          f"{prog['losses'][:3]}, reference {r_losses[:3]}", flush=True)
    correct = checks.verdict(judged)

    result = {"correct": correct, "attempted": steps,
              "failed": 0 if correct else steps,
              "device": {**info, "memory_peak_bytes": peak}}
    e2e = {"train_examples_per_s": whole, "setup_s": setup_s}
    if not trace:
        return result, e2e, None
    tr = tracing["session"].reduce()
    if tr is not None and tr.devices:
        print(f"[trace] {tr.window_s:.2f} s read, idle share "
              f"{tr.idle_share():.4f}, launches of the step program "
              f"(start, length) {_launches(tr, system.STEP_PROGRAM)}",
              flush=True)
    ctx = {"cell": cell, "trace": tr, "segment_rates": rates,
           "window_rate": clean_rate, "reference": ref, "system": system,
           "fit_s": {"wall": cut["t"] - t_open,
                     "by_category": {k: cut["fit_s"][k] - fit_s0[k]
                                     for k in fit_s0 if k != "other"}},
           "peaks": device.PEAKS.get(info["kind"]), "batch": batch,
           "steps_per_call": chunk}
    return result, e2e, ctx
