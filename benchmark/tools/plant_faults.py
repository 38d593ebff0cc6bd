"""Plant the faults that a training cell's limits have to catch, on the
chip at the cell's sizes: the plain reference with one of its ``FAULTS``
(half a batch, a layer without its decay, a router without the
renormalisation, ...) put in the program's place and compared with the
sound reference exactly as a run compares the program
(``checks.training_rows`` and ``checks.verdict`` under the limits in the
configuration's file). Every fault has to come out as NOT correct; the
exit code is 1 if one passes.

    python3 benchmark/tools/plant_faults.py --workload NAME --seeds 1 2 3
        [--faults half_batch ...]

A reference whose ``train_steps`` takes no ``fault`` has none to plant.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import checks, device, manifest  # noqa: E402


def followed(ref, cfg, pool, seed, fault):
    """What a run hands the comparison, from the reference itself."""
    import jax
    losses, moment, params = ref.train_steps(
        cfg, ref.make_params(cfg, seed), pool, fault=fault)
    update = jax.tree_util.tree_map(
        lambda a, b: a - b, params, jax.device_get(ref.make_params(cfg, seed)))
    return {"losses": losses, "momentum": checks.leaf_norms(moment),
            "update": checks.leaf_norms(update)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", nargs="+", default=None)
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
    from benchmark.lib import train_cell
    cfg, traffic = cell.config, cell.traffic
    ref = manifest.load_module("references", cell.config_name)
    missed = 0
    for seed in args.seeds:
        pool = train_cell.make_batches(seed, int(traffic["check_steps"]),
                                       int(traffic["batch"]), cfg)
        sound = followed(ref, cfg, pool, seed, None)
        for fault in args.faults or ref.FAULTS:
            t = time.monotonic()
            rows = checks.training_rows(
                followed(ref, cfg, pool, seed, fault), sound,
                lambda leaf: ref.stage_of(cfg, leaf), cfg["limits"])
            caught = not checks.verdict(rows)
            missed += not caught
            print("[fault] " + json.dumps(
                {"workload": cell.name, "seed": seed, "fault": fault,
                 "caught": caught,
                 "failed": [n for n, v, lim in rows if not v <= lim],
                 **{n: v for n, v, _ in rows},
                 "seconds": round(time.monotonic() - t, 1)}), flush=True)
    return 1 if missed else 0


if __name__ == "__main__":
    raise SystemExit(main())
