"""Read the control of a cell on the chip: the plain reference put in the
program's place and computed one precision below the one the configuration
states (float8 operands for a bf16 system), compared with the reference
exactly as a run compares the program. Its numbers have to FAIL the limits
in the configuration's file; the limits are set between the sound runs'
largest and the smallest these runs give (PERF.md section 2).

    python3 benchmark/tools/read_control.py --workload NAME --seeds 1 2 3
        [--seconds S] [--out DIR]

A training cell needs no window: the reference and the control follow the
first chunk of the seed's batches. A serving cell runs a short window at
the cell's own load (the control compares the same prompts and served
tokens that the run's own check followed), so the sound reading of each
seed is printed beside the control's, from one process.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.monotonic()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import checks, device, manifest  # noqa: E402


def fit_control(cell, seed, out_dir):
    import jax
    from benchmark.lib import train_cell
    cfg, traffic = cell.config, cell.traffic
    ref = manifest.load_module("references", cell.config_name)
    steps = int(traffic["check_steps"])
    pool = train_cell.make_batches(seed, steps, int(traffic["batch"]), cfg)
    devs = jax.devices()[:cell.chips]
    out = {}
    for precision in ("highest", "fp8"):
        losses, trace, params = ref.train_steps(
            cfg, ref.make_params(cfg, seed), pool, precision=precision,
            devices=devs)
        out[precision] = (losses, checks.leaf_norms(trace), checks.leaf_norms(
            jax.tree_util.tree_map(lambda a, b: a - b, params,
                                   ref.make_params(cfg, seed))))
    # every leaf's norms, for a look at which leaves a precision moves
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"control-{cell.name}-{seed}.json"),
              "w") as f:
        json.dump(out, f)
    as_dict = lambda t: {"losses": t[0], "momentum": t[1], "update": t[2]}
    rows = checks.training_rows(
        as_dict(out["fp8"]), as_dict(out["highest"]),
        lambda leaf: ref.stage_of(cfg, leaf), cfg["limits"])
    return {name: value for name, value, _ in rows}


def serve_control(cell, seed, seconds, out):
    from benchmark.lib import serve_cell
    out_dir = os.path.join(out, f"control-{cell.name}-{seed}")
    os.makedirs(out_dir, exist_ok=True)
    serve_cell.run(cell, seed, seconds, False, out_dir, time.monotonic())
    ref = manifest.load_module("references", cell.config_name)
    sched = json.load(open(os.path.join(out_dir, "schedule.json")))
    records = json.load(open(os.path.join(out_dir, "records.json")))
    picked = serve_cell.pick_checked(records, seed,
                                     int(cell.traffic["check_requests"]))
    widest, mean, n = serve_cell.check_served(
        ref, cell.config, seed, sched, picked, precision="fp8")
    return {"served_logit_gap": widest, "served_logit_gap_mean": mean,
            "tokens": n}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--out", default=os.path.join(manifest.ROOT,
                                                  "benchmark_out"))
    ap.add_argument("--manifest", default=None,
                    help="a manifest other than BENCHMARK.json, for a cell "
                         "that is not in it yet")
    args = ap.parse_args(argv)
    cell = manifest.Cell(manifest.load_manifest(args.manifest),
                         args.workload)
    if device.rehearsing():
        cell.rehearsal()
    device.prepare_environment(cell.chips)
    device.require(cell.chips)
    device.enable_compile_cache()
    for seed in args.seeds:
        t = time.monotonic()
        if cell.traffic["driver"] == "fit":
            row = fit_control(cell, seed, args.out)
        else:
            row = serve_control(cell, seed, args.seconds, args.out)
        print("[control] " + json.dumps(
            {"workload": cell.name, "seed": seed, **row,
             "limits": cell.config["limits"],
             "seconds": round(time.monotonic() - t, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
