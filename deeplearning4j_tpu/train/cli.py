"""CLI training entry point.

Parity: DL4J `deeplearning4j-scaleout-parallelwrapper/.../main/
ParallelWrapperMain.java` (143 LoC): args-driven launcher — model zip in,
worker/averaging knobs, fit over a data source, save the trained model.

Usage:
    python -m deeplearning4j_tpu.train \
        --model model.zip --output trained.zip \
        --dataset mnist --epochs 2 --batch-size 64 \
        --mode sync --averaging-frequency 5 --ui-port 9001
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m deeplearning4j_tpu.train",
        description="Train a serialized model with the ParallelWrapper "
                    "data-parallel trainer (ParallelWrapperMain analog)")
    p.add_argument("--model", required=True,
                   help="input model zip (save_model format)")
    p.add_argument("--output", required=True,
                   help="where to write the trained model zip")
    p.add_argument("--dataset", required=True,
                   help="mnist | emnist | cifar10 | iris | path to .npz "
                        "with 'features' and 'labels' arrays")
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--mode", choices=["sync", "averaging", "single"],
                   default="sync",
                   help="sync = compiled all-reduce DP; averaging = DL4J "
                        "AVERAGING semantics; single = plain net.fit")
    p.add_argument("--averaging-frequency", type=int, default=5)
    p.add_argument("--no-average-updaters", action="store_true",
                   help="skip averaging optimizer state (saveUpdater=false)")
    p.add_argument("--ui-port", type=int, default=None,
                   help="serve the training dashboard on this port")
    p.add_argument("--score-every", type=int, default=10,
                   help="ScoreIterationListener frequency")
    p.add_argument("--synthetic-data", action="store_true",
                   help="substitute deterministic synthetic data when the "
                        "dataset cache is missing (pipeline testing only); "
                        "without this flag a missing cache is an error")
    p.add_argument("--checkpoint-dir", default=None,
                   help="enable fault-tolerant training (ResilientTrainer): "
                        "atomic manifest-tracked checkpoints in this "
                        "directory, SIGTERM/SIGINT preemption handling, "
                        "per-step fault policy (docs/FAULT_TOLERANCE.md)")
    p.add_argument("--resume", action="store_true",
                   help="auto-resume from the newest valid checkpoint in "
                        "--checkpoint-dir (bitwise-identical continuation); "
                        "--epochs is then the TOTAL epoch target")
    p.add_argument("--save-every-iterations", type=int, default=50,
                   help="checkpoint cadence for --checkpoint-dir runs")
    p.add_argument("--keep-last", type=int, default=3,
                   help="checkpoints retained by manifest pruning")
    p.add_argument("--metrics", action="store_true",
                   help="print the final telemetry summary "
                        "(monitor.summary()) as JSON to stderr; with "
                        "--ui-port the live Prometheus exposition is "
                        "also served at /metrics (docs/OBSERVABILITY.md)")
    p.add_argument("--trace-out", default=None, metavar="PATH",
                   help="record telemetry spans and write a Chrome "
                        "trace-event JSON to PATH on exit (load in "
                        "Perfetto / chrome://tracing)")
    p.add_argument("--perf-ledger", default=None, metavar="PATH",
                   help="enable the compiled-program ledger (monitor.xla: "
                        "per-program fingerprint, compile time, flops, "
                        "bytes accessed, HBM peak; live train_mfu_pct) and "
                        "write the ledger JSON to PATH on exit; defaults "
                        "to perf_ledger.json alongside --trace-out when "
                        "tracing is on (docs/OBSERVABILITY.md, gate it "
                        "with tools/perf_report.py)")
    p.add_argument("--serve-port", type=int, default=None,
                   help="after a successful fit, serve the trained model "
                        "over HTTP on this port (shape-bucketed batching, "
                        "warmed; docs/SERVING.md) until SIGTERM/SIGINT, "
                        "then drain gracefully")
    p.add_argument("--serve-buckets", default="1,8,32,128",
                   help="batch-size bucket ladder for --serve-port")
    p.add_argument("--mesh", default=None, metavar="SPEC",
                   help="GSPMD sharding plan for the whole run, e.g. "
                        "'data=8' or 'data=4,model=2,rules=megatron,"
                        "zero=1' — the plan compiles into the default "
                        "fit() (DP all-reduce, Megatron TP, ZeRO "
                        "reduce-scatter/all-gather as jit-inserted "
                        "collectives; docs/PARALLELISM.md). Applies to "
                        "--mode single|sync and the resilient path")
    return p


def _serve_trained(net, args) -> None:
    """train -> serve handoff: publish the just-trained model on
    --serve-port and block until a signal requests a graceful drain."""
    import signal
    import threading

    from deeplearning4j_tpu.serving import ModelRegistry, ModelServer
    registry = ModelRegistry()
    registry.deploy("model", net, buckets=args.serve_buckets)
    server = ModelServer(registry, port=args.serve_port)
    print(json.dumps({"serving": server.url,
                      "predict": "/v1/models/model/predict"}),
          file=sys.stderr)
    stop = threading.Event()
    for s in (signal.SIGTERM, signal.SIGINT):
        signal.signal(s, lambda *_: stop.set())
    stop.wait()
    server.drain()


def _load_data(name: str, batch_size: int, allow_synthetic: bool = False):
    from deeplearning4j_tpu.data.fetchers import (
        Cifar10DataSetIterator, EmnistDataSetIterator, IrisDataSetIterator,
        MnistDataSetIterator,
    )
    from deeplearning4j_tpu.data.iterator import ArrayDataSetIterator
    # a real training CLI must not silently train on synthetic noise: the
    # fetchers' lenient default is overridden to fail loudly unless the
    # user opted in with --synthetic-data
    syn = None if allow_synthetic else False
    builtin = {
        "mnist": lambda: MnistDataSetIterator(batch_size=batch_size,
                                              synthetic=syn),
        "emnist": lambda: EmnistDataSetIterator(batch_size=batch_size,
                                                synthetic=syn),
        "cifar10": lambda: Cifar10DataSetIterator(batch_size=batch_size,
                                                  synthetic=syn),
        "iris": lambda: IrisDataSetIterator(batch_size=batch_size),
    }
    if name.lower() in builtin:
        return builtin[name.lower()]()
    data = np.load(name)
    if "features" not in data or "labels" not in data:
        raise SystemExit(f"{name}: npz must contain 'features' and 'labels'")
    return ArrayDataSetIterator(data["features"], data["labels"],
                                batch_size=batch_size)


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    # validate BEFORE the (possibly hours-long) fit: a typo'd ladder must
    # not surface only when the post-training serve handoff starts
    try:
        args.serve_buckets = tuple(
            int(b) for b in args.serve_buckets.split(",") if b)
    except ValueError:
        raise SystemExit(f"--serve-buckets must be comma-separated ints, "
                         f"got {args.serve_buckets!r}")
    import os

    from deeplearning4j_tpu.util.platform import enable_compile_cache
    # restarts and --resume runs load their programs from disk; so do
    # the perf ledger's AOT lower+compile captures, which bypass the jit
    # __call__ cache
    enable_compile_cache()
    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.parallel import ParallelWrapper, TrainingMode
    from deeplearning4j_tpu.train.listeners import (
        PerformanceListener, ScoreIterationListener,
    )
    from deeplearning4j_tpu.util.serialization import load_model, save_model

    if args.trace_out:
        monitor.enable_tracing()
    if args.perf_ledger is None and args.trace_out:
        # "alongside --trace-out": tracing runs double as perf-ledger runs
        # unless the user points the ledger elsewhere
        args.perf_ledger = os.path.join(
            os.path.dirname(os.path.abspath(args.trace_out)),
            "perf_ledger.json")
    if args.perf_ledger:
        monitor.xla.enable_ledger(args.perf_ledger)

    def emit_telemetry():
        # runs in a finally: a bad --trace-out path (unwritable dir, full
        # disk) must not fail an otherwise-successful run or mask the
        # fit's real exception
        if args.trace_out:
            try:
                n = monitor.save_trace(args.trace_out)
                print(f"trace: {args.trace_out} ({n} events)",
                      file=sys.stderr)
            except OSError as e:
                print(f"trace not written to {args.trace_out}: {e}",
                      file=sys.stderr)
        if args.perf_ledger:
            try:
                n = monitor.xla.save_ledger(args.perf_ledger)
                print(f"perf ledger: {args.perf_ledger} ({n} programs)",
                      file=sys.stderr)
            except OSError as e:
                print(f"perf ledger not written to {args.perf_ledger}: {e}",
                      file=sys.stderr)
        if args.metrics:
            print(json.dumps({"metrics": monitor.summary()}),
                  file=sys.stderr)

    net = load_model(args.model)
    iterator = _load_data(args.dataset, args.batch_size,
                          allow_synthetic=args.synthetic_data)
    listeners = [ScoreIterationListener(args.score_every),
                 PerformanceListener(args.score_every)]
    ui_server = None
    if args.ui_port is not None:
        from deeplearning4j_tpu.ui import (
            InMemoryStatsStorage, StatsListener, UIServer,
        )
        storage = InMemoryStatsStorage()
        listeners.append(StatsListener(storage, frequency=args.score_every))
        ui_server = UIServer(port=args.ui_port)   # serves once constructed
        ui_server.attach(storage)
        print(f"dashboard: {ui_server.url}", file=sys.stderr)
    net.set_listeners(*listeners)

    if args.resume and not args.checkpoint_dir:
        raise SystemExit("--resume requires --checkpoint-dir")
    # --mesh: the whole training section runs under use_mesh so plain
    # fit(), ParallelWrapper and ResilientTrainer all resolve the plan
    # with zero further wiring (parallel/plan.active_plan)
    mesh_ctx = None
    if args.mesh:
        from deeplearning4j_tpu.parallel.plan import parse_plan, use_mesh
        try:
            mesh_plan = parse_plan(args.mesh)
            mesh_plan.mesh()    # validate extents against the REAL device
            # count now — "data=16 on an 8-chip host" must be a clean
            # SystemExit before the (possibly hours-long) fit, not a raw
            # traceback mid-run
        except ValueError as e:
            raise SystemExit(f"--mesh: {e}")
        if args.mode == "averaging":
            raise SystemExit("--mesh applies to --mode single|sync "
                             "(AVERAGING keeps per-worker replicas by "
                             "definition)")
        mesh_ctx = use_mesh(mesh_plan)
        mesh_ctx.__enter__()        # exited in the finally below
        print(f"mesh plan: {mesh_plan.describe()}", file=sys.stderr)
    # telemetry emits in a finally: a fit that dies mid-run (bad data,
    # retries exhausted, OOM) still leaves the trace/metrics record —
    # the crash case is exactly when it is most needed
    try:
        if args.checkpoint_dir:
            # resilient path: atomic checkpoint/auto-resume + fault policy;
            # wraps the plain net (single) or the sync-mode ParallelWrapper
            from deeplearning4j_tpu.train.resilience import ResilientTrainer
            target = net
            if args.mode == "sync":
                target = ParallelWrapper(net,
                                         mode=TrainingMode.SYNC_GRADIENTS)
            elif args.mode == "averaging":
                raise SystemExit("--checkpoint-dir supports --mode "
                                 "single|sync (AVERAGING replica state is "
                                 "not resumable)")
            trainer = ResilientTrainer(
                target, args.checkpoint_dir,
                save_every_n_iterations=args.save_every_iterations,
                keep_last=args.keep_last, resume=args.resume)
            report = trainer.fit(iterator, epochs=args.epochs,
                                 batch_size=args.batch_size)
            if report.preempted or report.diverged:
                # incomplete run (preempted, or diverged and rolled back
                # to an older checkpoint): no output model, no success
                # JSON, distinct exit code so callers can't mistake it
                # for a finished job
                print(json.dumps({"preempted": report.preempted,
                                  "diverged": report.diverged,
                                  "iterations": net.iteration_count,
                                  "resume_with": "--resume"}),
                      file=sys.stderr)
                if ui_server is not None:
                    ui_server.stop()
                return 3 if report.preempted else 4
        elif args.mode == "single":
            net.fit(iterator, epochs=args.epochs)
        else:
            wrapper = ParallelWrapper(
                net,
                mode=(TrainingMode.SYNC_GRADIENTS if args.mode == "sync"
                      else TrainingMode.AVERAGING),
                averaging_frequency=args.averaging_frequency,
                average_updaters=not args.no_average_updaters)
            wrapper.fit(iterator, epochs=args.epochs)

        save_model(net, args.output)
        print(json.dumps({"output": args.output,
                          "final_score": net.score(),
                          "iterations": net.iteration_count,
                          "epochs": net.epoch_count}))
        if args.serve_port is not None:
            _serve_trained(net, args)
        if ui_server is not None:
            ui_server.stop()
        return 0
    finally:
        if mesh_ctx is not None:
            mesh_ctx.__exit__(None, None, None)
        emit_telemetry()


if __name__ == "__main__":
    raise SystemExit(main())
