#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload NAME --seed N --seconds S --trace 0|1

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of its standard output, one JSON object: whether
what the timed path produced was correct, how much was attempted and
failed, the metrics (end-to-end with ``--trace 0``, per-layer with
``--trace 1``) and the device. Without a TPU holding the chips the cell
asks for it exits non-zero and prints no result; ``JAX_PLATFORMS=cpu`` asks
for a rehearsal of the control flow at tiny sizes, which prints counts and
no metric.

The cell's files decide everything else: ``traffic/<traffic>.json`` names
the driver (``fit`` or ``serve``) and its parameters, ``configs/<config>
.json`` the sizes and the system adapter, ``references/<config>.py`` the
plain reference, ``metrics/<metric>.py`` the reader of each per-layer
metric.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.monotonic()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import device, manifest  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=None,
                    help="directory for the run's files (default: "
                         "benchmark_out/ in the checkout)")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(manifest.ROOT, "deeplearning4j_tpu")):
        sys.stderr.write("benchmark: the system under test is not in this "
                         "checkout; nothing was run\n")
        return 2
    cell = manifest.Cell(manifest.load_manifest(), args.workload)
    rehearsal = device.rehearsing()
    if rehearsal:
        cell.rehearsal()
    device.prepare_environment(cell.chips)
    out_dir = os.path.join(
        args.out or os.path.join(manifest.ROOT, "benchmark_out"),
        cell.name, f"seed-{args.seed}-trace-{args.trace}")
    os.makedirs(out_dir, exist_ok=True)

    driver = cell.traffic["driver"]
    if driver == "fit":
        from benchmark.lib import train_cell as drv
    elif driver == "serve":
        from benchmark.lib import serve_cell as drv
    else:
        raise SystemExit(f"unknown driver {driver!r} in the traffic file")
    result, e2e, ctx = drv.run(cell, args.seed, args.seconds,
                               bool(args.trace), out_dir, T_START)

    units = {m["name"]: m["unit"]
             for m in cell.end_to_end + cell.per_layer}
    if args.trace:
        values = {}
        for m in cell.per_layer:
            reader = manifest.load_module("metrics", m["name"])
            if reader is None:
                raise SystemExit(f"no reader benchmark/metrics/"
                                 f"{m['name']}.py")
            v = reader.read(ctx)
            if v is not None:
                values[m["name"]] = v
        tr = ctx["trace"]
        if tr is not None and tr.devices:
            result["device"]["busy_s"] = tr.busy_s()
            result["device"]["window_s"] = tr.window_s
            result["breakdown"] = tr.breakdown()
            with open(os.path.join(out_dir, "ops.json"), "w") as f:
                json.dump({"op_seconds": tr.op_seconds(),
                           "idle_gaps": tr.idle_gaps()}, f)
    else:
        values = {m["name"]: e2e[m["name"]] for m in cell.end_to_end}
    bad = [k for k, v in values.items() if v is None or v != v]
    if bad:
        # a window that gave a metric nothing to read is not a result
        sys.stderr.write(f"benchmark: no value for {bad}; no result\n")
        return 4
    if rehearsal:
        # a CPU run proves counts and control flow, never a device metric
        result["rehearsal"] = True
        result["counts"] = {"metrics_read": sorted(values)}
        values = {}
    result["metrics"] = {k: {"value": v, "unit": units[k]}
                         for k, v in values.items()}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
