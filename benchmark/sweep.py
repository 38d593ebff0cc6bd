#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell once, on the chip: the
highest rate the system sustains. Builds the cell's system once and offers
the cell's traffic at each of a list of rates for a short window; prints
one line per rate. The rate the traffic file then fixes is about four
fifths of the knee. A builder's tool: the driver never runs it.

    python3 benchmark/sweep.py --workload rpj3b-chat-steady --seed 5 \\
        --rates 0.6 0.8 1.0 1.2 1.4 --seconds 30 \\
        [--manifest benchmark/tests/data/withheld-serving.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import device, manifest, serve_stats  # noqa: E402
from benchmark.lib import traffic as gen  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
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
    from benchmark.lib import serve_cell
    cfg = cell.config
    ref = manifest.load_module("references", cell.config_name)
    system = manifest.load_module("systems", cfg["system"])
    serving = system.serve(cfg, ref.make_weights(cfg, args.seed))
    out_dir = os.path.join(args.out, "sweep-" + cell.name)
    os.makedirs(out_dir, exist_ok=True)
    try:
        for rate in args.rates:
            traffic = {**cell.traffic, "rate_per_s": rate}
            sched = gen.schedule(traffic, args.seed, args.seconds,
                                 cfg["vocab_size"])
            records, w0, w1, _ = serve_cell.offer(serving.url, sched,
                                                  out_dir, system)
            st = serve_stats.reduce(records, w0, w1, sched["ttft_limit_s"])
            half = (w0 + w1) / 2
            late = serve_stats.reduce(records, half, w1,
                                      sched["ttft_limit_s"])
            load = serve_stats.live_load(records, w0, w1)
            print("[sweep] " + json.dumps({
                "rate_per_s": rate, "arrivals": st["attempted"],
                "failed": st["failed"],
                "ttft_mean_ms": round(st["ttft_mean_ms"], 1),
                "ttft_p90_ms": round(st["ttft_p90_ms"], 1),
                "ttft_mean_2nd_half_ms": round(late["ttft_mean_ms"], 1),
                "itl_p99_ms": round(st["itl_p99_ms"], 1),
                "live_slots": round(load["live_slots"], 2)}), flush=True)
            time.sleep(8.0)     # let the queue drain between rates
    finally:
        serving.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
