"""`python -m deeplearning4j_tpu.serving` — the serve CLI entrypoint.

Single-replica mode (default): stands up a ModelServer over one or more
servables and runs until SIGTERM/SIGINT, then drains gracefully (stop
admitting, flush in-flight, clean exit 0) — the deploy surface a process
supervisor or container runtime manages.

Fleet mode (``--replicas N``, N >= 2): stands up a ReplicaSupervisor over
N serving replicas (subprocess by default — each its own crash domain —
or ``--replica-mode inprocess``) behind a ResilientRouter front end with
per-(replica, model) circuit breakers, priority-class shedding
(``--priority-classes``, ``X-Priority`` request header), and hedged
retries. ``--port`` is then the ROUTER's port; replicas bind ephemeral
ports on localhost.

Usage:
    python -m deeplearning4j_tpu.serving \
        --model lenet=zoo:LeNet --port 8500 \
        --buckets 1,8,32,128 --max-delay-ms 5 --deadline-s 30

    # serve a training run's newest verified checkpoint, fleet of 3:
    python -m deeplearning4j_tpu.serving --model prod=/ckpts/run17 \
        --replicas 3 --priority-classes interactive,standard,batch

See docs/SERVING.md for the API, bucket-ladder tuning, the swap/rollback
runbook, and the "Fleet operations" section for supervisor/router knobs.
"""
from __future__ import annotations

import argparse
import json
import signal
import sys
import threading
import time


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m deeplearning4j_tpu.serving",
        description="Production model server: versioned registry, "
                    "shape-bucketed AOT-warmed batching, admission "
                    "control, zero-downtime hot-swap (docs/SERVING.md)")
    p.add_argument("--model", action="append", default=[],
                   metavar="NAME=SOURCE",
                   help="predict servable to deploy; SOURCE is a "
                        "checkpoint dir (manifest.json), a model zip, a "
                        "Keras .h5, or zoo:<Arch> (constructor kwargs "
                        "ride a query string: zoo:LeNet?num_classes=10). "
                        "Repeatable.")
    # ----------------------------------------------------- decode (LM) mode
    dec = p.add_argument_group(
        "LM decode servables (docs/SERVING.md 'LLM decode')")
    dec.add_argument("--lm", action="append", default=[],
                     metavar="NAME=SOURCE",
                     help="decode servable (continuous-batching token "
                          "generation, POST .../generate). Same SOURCE "
                          "forms as --model; an @int8 / @bf16 suffix "
                          "serves a post-training-quantized variant "
                          "(e.g. zoo:TransformerLM?n_layers=2@int8) and "
                          "@spec[:draft=...,k=...] serves with "
                          "speculative decoding (draft-verify; greedy "
                          "output is unchanged). Repeatable.")
    dec.add_argument("--decode-slots", type=int, default=4,
                     help="fixed in-flight decode batch positions")
    dec.add_argument("--decode-page-size", type=int, default=16,
                     help="tokens per KV-cache page")
    dec.add_argument("--decode-max-context", type=int, default=None,
                     help="KV capacity per sequence (default: the "
                          "model's seq_length)")
    dec.add_argument("--decode-pool-pages", type=int, default=None,
                     help="physical KV pages in the pool (default "
                          "slots*max_context/page_size: no "
                          "oversubscription)")
    dec.add_argument("--decode-queue-limit", type=int, default=64,
                     help="pending-join bound (full -> 429)")
    dec.add_argument("--prefill-buckets", default=None,
                     help="prefill sequence-length ladder (comma ints, "
                          "page-aligned; default: geometric up to "
                          "max_context)")
    dec.add_argument("--prefill-chunk-tokens", type=int, default=None,
                     help="per-scheduler-tick prefill-token budget: long "
                          "uncached prompt suffixes run in chunks of at "
                          "most this many tokens BETWEEN decode steps, so "
                          "one long prompt cannot stall every stream's "
                          "inter-token latency (default: 4 pages; 0 "
                          "disables chunking)")
    dec.add_argument("--spec-draft", default=None, metavar="SRC",
                     help="turn on speculative decoding for every --lm "
                          "servable: 'int8'/'bf16' self-draft the target "
                          "through a quantized variant of its own "
                          "params; any other value loads a servable "
                          "source with the SAME vocab (mismatch is a "
                          "deploy-time error). Per-servable override: "
                          "the @spec source suffix")
    dec.add_argument("--spec-k", type=int, default=4,
                     help="draft tokens proposed per verify round")
    dec.add_argument("--spec-accept-floor", type=float, default=0.4,
                     help="rolling acceptance-rate floor below which a "
                          "stream stops speculating (plain decode)")
    dec.add_argument("--spec-window", type=int, default=8,
                     help="rounds in the per-stream acceptance window")
    dec.add_argument("--spec-draft-pool-pages", type=int, default=None,
                     help="KV pages in the draft engine's own pool "
                          "(default: sized like the target's)")
    dec.add_argument("--no-prefix-cache", action="store_true",
                     help="disable copy-on-write KV prefix sharing "
                          "(radix-indexed page reuse across requests "
                          "with a common prompt prefix; on by default — "
                          "greedy outputs are identical either way)")
    dec.add_argument("--kv-spill-pages", type=int, default=0,
                     help="host-RAM KV spill-tier capacity in pages (0 "
                          "disables): zero-ref retained prefix pages "
                          "demote into pinned host memory instead of "
                          "being dropped, and promote back into HBM on a "
                          "prefix hit (docs/SERVING.md 'Tiered KV "
                          "fabric')")
    dec.add_argument("--kv-role", choices=("prefill", "decode", "mixed"),
                     default="mixed",
                     help="disaggregation role this server advertises on "
                          "/readyz: 'prefill' computes KV and ships "
                          "pages, 'decode' streams tokens, 'mixed' does "
                          "both (single-replica mode; fleet mode assigns "
                          "roles with --kv-roles)")
    p.add_argument("--host", default="127.0.0.1",
                   help="bind address (0.0.0.0 behind a load balancer)")
    p.add_argument("--port", type=int, default=8500)
    p.add_argument("--buckets", default="1,8,32,128",
                   help="batch-size bucket ladder (comma-separated)")
    p.add_argument("--max-delay-ms", type=float, default=5.0,
                   help="batching coalescing deadline")
    p.add_argument("--queue-limit", type=int, default=256,
                   help="admission-control queue bound (full -> 429)")
    p.add_argument("--deadline-s", type=float, default=30.0,
                   help="default per-request deadline (expired -> 504)")
    p.add_argument("--drain-timeout-s", type=float, default=30.0,
                   help="max time to flush in-flight work on SIGTERM")
    p.add_argument("--enable-fault-injection", action="store_true",
                   help="expose POST /v1/faults (chaos testing; wedge "
                        "probes / predicts of THIS process) and honor "
                        "$DL4J_TPU_SERVING_FAULTS. Never on by default.")
    # -------------------------------------------- observability (tracing)
    obs = p.add_argument_group(
        "observability (docs/OBSERVABILITY.md 'Tracing a single request')")
    obs.add_argument("--trace-out", default=None, metavar="PATH",
                     help="enable span tracing and save the Chrome/"
                          "Perfetto trace here on drain. In fleet mode "
                          "the router writes PATH and each subprocess "
                          "replica writes PATH-stem.<replica>.json — "
                          "merge them with tools/trace_report.py")
    obs.add_argument("--postmortem-dir", default=None, metavar="DIR",
                     help="flight-recorder SLO postmortems (5xx, breaker "
                          "open, wedge, p99 breach) are auto-dumped here "
                          "as JSON")
    obs.add_argument("--flight-records", type=int, default=512,
                     help="per-request flight-recorder ring capacity")
    obs.add_argument("--no-flight", action="store_true",
                     help="disable the flight recorder (on by default "
                          "for served processes; the ring is bounded "
                          "host memory, never on the compiled path)")
    obs.add_argument("--slo-p99-ms", type=float, default=None,
                     help="latency SLO: 99%% of requests must finish "
                          "under this; breaches fire the SLO engine's "
                          "burn-rate alert (reason p99_breach) and an "
                          "automatic postmortem")
    obs.add_argument("--slo-availability", type=float, default=None,
                     metavar="TARGET",
                     help="availability SLO target (e.g. 0.999). Any "
                          "--slo-* flag enables the in-process time-"
                          "series ring + multi-window burn-rate "
                          "alerting; verdicts on GET /v1/slo, firings "
                          "trip flight postmortems")
    obs.add_argument("--slo-sample-interval-s", type=float, default=5.0,
                     help="time-series sampling interval while an "
                          "--slo-* objective is active")
    obs.add_argument("--slo-windows", default=None, metavar="FL,FS,SL,SS",
                     help="override the burn-rate windows (seconds): "
                          "fast-long,fast-short,slow-long,slow-short "
                          "(default 3600,300,21600,1800)")
    # ------------------------------------------------------ fleet mode
    fleet = p.add_argument_group(
        "fleet mode (docs/SERVING.md 'Fleet operations')")
    fleet.add_argument("--replicas", type=int, default=1,
                       help="N >= 2 supervises N replicas behind the "
                            "resilient router; 1 = plain single server")
    fleet.add_argument("--replica-mode", choices=("subprocess", "inprocess"),
                       default="subprocess",
                       help="subprocess = own crash domain per replica "
                            "(production); inprocess = threads (tests)")
    fleet.add_argument("--priority-classes",
                       default="interactive,standard,batch",
                       help="ordered priority ladder, highest first; "
                            "requests select via the X-Priority header")
    fleet.add_argument("--shed-floor", type=float, default=0.7,
                       help="fleet utilization at which the LOWEST class "
                            "starts shedding (higher classes shed at "
                            "evenly spaced higher thresholds)")
    fleet.add_argument("--per-replica-inflight", type=int, default=8,
                       help="router-side in-flight cap per replica (the "
                            "capacity unit behind shedding)")
    fleet.add_argument("--probe-interval-s", type=float, default=1.0)
    fleet.add_argument("--probe-timeout-s", type=float, default=2.0)
    fleet.add_argument("--unhealthy-after", type=int, default=3,
                       help="consecutive failed probes before a live "
                            "replica is presumed wedged and replaced")
    fleet.add_argument("--restart-budget", type=int, default=5,
                       help="restarts allowed per replica per 10 min "
                            "before it is marked dead (crash loop)")
    fleet.add_argument("--no-hedge", action="store_true",
                       help="disable hedged retries for straggler "
                            "predicts")
    fleet.add_argument("--kv-roles", default=None, metavar="R0,R1,...",
                       help="per-replica disaggregation roles (comma "
                            "list of prefill|decode|mixed, indexed by "
                            "replica); replicas beyond the list — "
                            "autoscaled ones included — serve 'mixed'. "
                            "At least one replica must be able to decode")
    fleet.add_argument("--no-affinity", action="store_true",
                       help="disable prefix-affinity routing (steering "
                            "same-prefix streams to the replica whose "
                            "heartbeat advertises ownership of the "
                            "prompt's leading KV block)")
    fleet.add_argument("--disagg-min-tokens", type=int, default=None,
                       help="prompts at least this many tokens long are "
                            "prefilled on a prefill-role replica and "
                            "their KV pages shipped to the decode "
                            "replica before the stream is routed "
                            "(default: disabled)")
    fleet.add_argument("--disagg-timeout-s", type=float, default=30.0,
                       help="per-leg timeout for the kv export/import "
                            "transfer; a missed deadline fails over to "
                            "local prefill on the decode replica")
    # ----------------------------------------------- continuous rollout
    ro = p.add_argument_group(
        "continuous rollout (docs/SERVING.md 'Continuous rollout')")
    ro.add_argument("--rollout-watch", default=None, metavar="DIR",
                    help="checkpoint directory to tail for new versions; "
                         "enables the RolloutController (fleet mode only)")
    ro.add_argument("--rollout-model", default=None,
                    help="served model name the rollout swaps (default: "
                         "the first --model/--lm name)")
    ro.add_argument("--rollout-mode", choices=("blessed", "latest"),
                    default="blessed",
                    help="tail the eval-gated blessed.json manifest "
                         "(default) or the raw newest manifest entry")
    ro.add_argument("--rollout-observe-s", type=float, default=30.0,
                    help="canary observation window before the verdict")
    ro.add_argument("--rollout-poll-s", type=float, default=5.0,
                    help="how often the watcher re-reads the manifest")
    ro.add_argument("--rollout-canary-fraction", type=float, default=0.1,
                    help="bounded share of live traffic routed to the "
                         "canary replica (0 < f <= 0.5)")
    ro.add_argument("--rollout-min-requests", type=int, default=20,
                    help="minimum canary requests before a promote "
                         "verdict (insufficient traffic rejects)")
    ro.add_argument("--rollout-p99-floor-ms", type=float, default=10.0,
                    help="p99 regressions below this floor are noise, "
                         "not a verdict; raise it where the canary's "
                         "first requests pay a compile (cold swap)")
    # ------------------------------------------------------- autoscaling
    asc = p.add_argument_group(
        "load-signal autoscaling (docs/SERVING.md 'Autoscaling')")
    asc.add_argument("--autoscale-max", type=int, default=None,
                     metavar="N",
                     help="enable autoscaling up to N replicas "
                          "(--replicas is the floor); scale signal is "
                          "router in-flight vs healthy capacity "
                          "(--per-replica-inflight)")
    asc.add_argument("--autoscale-high", type=float, default=0.8,
                     help="utilization above this for consecutive ticks "
                          "scales up")
    asc.add_argument("--autoscale-low", type=float, default=0.25,
                     help="utilization below this for consecutive ticks "
                          "drains one replica (readyz-confirmed drain, "
                          "never a kill)")
    asc.add_argument("--autoscale-cooldown-s", type=float, default=10.0,
                     help="minimum seconds between scaling decisions")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    from deeplearning4j_tpu.util.platform import enable_compile_cache
    # a restart (and every fleet replica after the first) loads the AOT
    # bucket ladder from disk instead of recompiling it
    enable_compile_cache()
    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.monitor import flight as flight_recorder
    from deeplearning4j_tpu.serving.registry import (
        ModelLoadError, ModelRegistry,
    )
    from deeplearning4j_tpu.serving.server import ModelServer

    # observability defaults for served processes: the flight recorder is
    # ON (bounded host-side ring; the zero-cost contract only governs the
    # library default), span tracing only when --trace-out asks for it
    if not args.no_flight:
        flight_recorder.enable_flight(capacity=args.flight_records,
                                      dump_dir=args.postmortem_dir)
    if args.trace_out:
        monitor.enable_tracing()

    try:
        buckets = tuple(int(b) for b in args.buckets.split(",") if b)
    except ValueError:
        raise SystemExit(f"--buckets must be comma-separated ints, got "
                         f"{args.buckets!r}")

    def parse_specs(values, flag):
        out = []
        for spec in values:
            name, sep, source = spec.partition("=")
            if not sep or not name or not source:
                raise SystemExit(f"{flag} expects NAME=SOURCE, got "
                                 f"{spec!r}")
            out.append((name, source))
        return out

    specs = parse_specs(args.model, "--model")
    lm_specs = parse_specs(args.lm, "--lm")
    if not specs and not lm_specs:
        raise SystemExit("deploy at least one servable (--model/--lm)")
    seen = set()
    for name, _ in specs + lm_specs:
        if name in seen:
            raise SystemExit(f"duplicate servable name {name!r}")
        seen.add(name)
    decode_cfg = _decode_config(args)

    if args.replicas > 1:
        return _main_fleet(args, specs, lm_specs, buckets, decode_cfg)

    registry = ModelRegistry()
    for name, source in specs:
        try:
            served = registry.deploy(name, source, buckets=buckets,
                                     max_delay_ms=args.max_delay_ms,
                                     queue_limit=args.queue_limit)
        except ModelLoadError as e:
            raise SystemExit(f"cannot deploy {name!r}: {e}")
        print(json.dumps({"deployed": name,
                          "input_shape": list(served.input_shape),
                          "buckets": list(served.batcher.buckets)}),
              file=sys.stderr)
    for name, source in lm_specs:
        try:
            served = registry.deploy_lm(name, source, decode=decode_cfg)
        except ModelLoadError as e:
            raise SystemExit(f"cannot deploy LM {name!r}: {e}")
        print(json.dumps({"deployed": name, "kind": "lm",
                          "vocab_size": served.vocab,
                          "max_context": served.max_context}),
              file=sys.stderr)

    from deeplearning4j_tpu.monitor import slo as slo_mod
    slo_engine = _slo_setup(args, slo_mod.server_objectives(
        slo_p99_ms=args.slo_p99_ms,
        availability_target=args.slo_availability))
    server = ModelServer(registry, host=args.host, port=args.port,
                         default_deadline_s=args.deadline_s,
                         enable_faults=args.enable_fault_injection,
                         slo_engine=slo_engine, kv_role=args.kv_role)
    endpoints = ["/v1/models", "/healthz", "/readyz", "/metrics"]
    if slo_engine is not None:
        endpoints += ["/v1/slo", "/v1/timeseries"]
    print(json.dumps({"serving": server.url,
                      "models": registry.names(),
                      "endpoints": endpoints}))
    sys.stdout.flush()

    stop = threading.Event()

    def _on_signal(signum, frame):
        print(json.dumps({"signal": signum, "action": "drain"}),
              file=sys.stderr)
        stop.set()

    for s in (signal.SIGTERM, signal.SIGINT):
        signal.signal(s, _on_signal)
    stop.wait()
    server.drain(timeout=args.drain_timeout_s)
    if args.trace_out:
        n = monitor.save_trace(args.trace_out)
        print(json.dumps({"trace_out": args.trace_out, "events": n}),
              file=sys.stderr)
    return 0


def _slo_enabled(args) -> bool:
    return (args.slo_availability is not None
            or args.slo_p99_ms is not None)


def _slo_setup(args, objectives):
    """Enable the time-series ring + SLO engine from --slo-* flags.
    Returns the engine (None when no --slo-* flag was given)."""
    if not objectives:
        return None
    from deeplearning4j_tpu.monitor import slo, timeseries
    rules = slo.DEFAULT_RULES
    if args.slo_windows:
        try:
            fl, fs, sl, ss = (float(x)
                              for x in args.slo_windows.split(","))
        except ValueError:
            raise SystemExit("--slo-windows expects 4 comma-separated "
                             f"seconds, got {args.slo_windows!r}")
        # keep the workbook burn thresholds, scale the flap-suppression
        # hold with the short windows
        rules = (slo.BurnRule("page", fl, fs, 14.4,
                              keep_firing_s=max(2.0, fs / 2)),
                 slo.BurnRule("ticket", sl, ss, 6.0,
                              keep_firing_s=max(2.0, ss / 2)))
    timeseries.enable_timeseries(interval_s=args.slo_sample_interval_s)
    return slo.enable_slo(objectives, rules=rules)


def _decode_config(args):
    """CLI decode knobs -> DecodeConfig (shared by all --lm servables)."""
    from deeplearning4j_tpu.serving.decode import DecodeConfig
    prefill = None
    if args.prefill_buckets:
        try:
            prefill = tuple(int(b) for b in args.prefill_buckets.split(",")
                            if b)
        except ValueError:
            raise SystemExit("--prefill-buckets must be comma-separated "
                             f"ints, got {args.prefill_buckets!r}")
    return DecodeConfig(slots=args.decode_slots,
                        page_size=args.decode_page_size,
                        max_context=args.decode_max_context,
                        pool_pages=args.decode_pool_pages,
                        prefill_buckets=prefill,
                        queue_limit=args.decode_queue_limit,
                        prefix_cache=not args.no_prefix_cache,
                        prefill_chunk_tokens=args.prefill_chunk_tokens,
                        spec_draft=args.spec_draft,
                        spec_k=args.spec_k,
                        spec_accept_floor=args.spec_accept_floor,
                        spec_window=args.spec_window,
                        spec_draft_pool_pages=args.spec_draft_pool_pages,
                        spill_pages=args.kv_spill_pages)


def _main_fleet(args, specs, lm_specs, buckets, decode_cfg) -> int:
    """--replicas N: supervisor + router. --port is the router's port."""
    import os

    from deeplearning4j_tpu.serving.fleet import (
        AutoscaleConfig, InProcessReplica, ReplicaSpec, ReplicaSupervisor,
        SubprocessReplica,
    )
    from deeplearning4j_tpu.serving.quantize import parse_variant
    from deeplearning4j_tpu.serving.router import (
        ResilientRouter, RouterServer,
    )

    classes = tuple(c.strip() for c in args.priority_classes.split(",")
                    if c.strip())
    if not classes:
        raise SystemExit("--priority-classes must name at least one class")
    roles: tuple = ()
    if args.kv_roles:
        roles = tuple(r.strip() for r in args.kv_roles.split(",")
                      if r.strip())
        bad = sorted({r for r in roles
                      if r not in ("prefill", "decode", "mixed")})
        if bad:
            raise SystemExit(f"--kv-roles: unknown role(s) {bad} "
                             "(expected prefill|decode|mixed)")
        if (len(roles) >= args.replicas
                and all(r == "prefill" for r in roles[:args.replicas])):
            raise SystemExit("--kv-roles: every replica is 'prefill' — "
                             "at least one must be able to decode")
        if roles and not lm_specs:
            raise SystemExit("--kv-roles only applies to --lm servables")

    def _role(i: int) -> str:
        # replicas past the list (autoscaled growth included) serve mixed
        return roles[i] if i < len(roles) else "mixed"

    def _spec(i: int) -> ReplicaSpec:
        return ReplicaSpec(specs, buckets=buckets,
                           max_delay_ms=args.max_delay_ms,
                           queue_limit=args.queue_limit,
                           default_deadline_s=args.deadline_s,
                           enable_faults=args.enable_fault_injection,
                           lms=lm_specs, decode=decode_cfg,
                           trace_out=args.trace_out,
                           postmortem_dir=args.postmortem_dir,
                           flight=not args.no_flight,
                           flight_records=args.flight_records,
                           slo_availability=args.slo_availability,
                           slo_p99_ms=args.slo_p99_ms,
                           slo_sample_interval_s=args.slo_sample_interval_s,
                           slo_windows=args.slo_windows,
                           kv_role=_role(i))
    if args.replica_mode == "subprocess":
        for _, source in specs + lm_specs:
            base, _variant = parse_variant(source)
            if base.startswith("zoo:") or os.path.exists(base):
                continue
            raise SystemExit(f"fleet replicas cannot serve {source!r} "
                             "(need a path or zoo: name)")

        def factory(i):
            return SubprocessReplica(f"replica-{i}", _spec(i),
                                     env=dict(os.environ))
    else:
        def factory(i):
            return InProcessReplica(f"replica-{i}", _spec(i))

    autoscale = None
    if args.autoscale_max is not None:
        try:
            autoscale = AutoscaleConfig(
                min_replicas=args.replicas,
                max_replicas=args.autoscale_max,
                capacity_per_replica=args.per_replica_inflight,
                high_watermark=args.autoscale_high,
                low_watermark=args.autoscale_low,
                cooldown_s=args.autoscale_cooldown_s,
                drain_timeout_s=args.drain_timeout_s)
        except ValueError as e:
            raise SystemExit(f"--autoscale-*: {e}")
    supervisor = ReplicaSupervisor(
        factory, args.replicas,
        probe_interval_s=args.probe_interval_s,
        probe_timeout_s=args.probe_timeout_s,
        unhealthy_after=args.unhealthy_after,
        restart_budget=args.restart_budget,
        autoscale=autoscale)
    try:
        supervisor.start()
    except Exception as e:                    # noqa: BLE001
        raise SystemExit(f"fleet launch failed: {e}")
    router = ResilientRouter(
        supervisor.healthy, classes=classes,
        shed_floor=args.shed_floor,
        per_replica_inflight=args.per_replica_inflight,
        hedge=not args.no_hedge, timeout_s=args.deadline_s,
        slo_p99_ms=args.slo_p99_ms,
        canary_fraction=args.rollout_canary_fraction,
        affinity=not args.no_affinity,
        disagg_min_tokens=args.disagg_min_tokens,
        disagg_timeout_s=args.disagg_timeout_s)
    from deeplearning4j_tpu.monitor import slo as slo_mod
    slo_engine = _slo_setup(args, slo_mod.router_objectives(
        slo_p99_ms=args.slo_p99_ms,
        availability_target=args.slo_availability))
    server = RouterServer(router, supervisor=supervisor,
                          host=args.host, port=args.port,
                          slo_engine=slo_engine)
    rollout = None
    if args.rollout_watch is not None:
        from deeplearning4j_tpu.serving.rollout import RolloutController
        model_names = [n for n, _ in specs + lm_specs]
        rollout_model = args.rollout_model or (
            model_names[0] if model_names else None)
        if rollout_model is None:
            raise SystemExit("--rollout-watch needs a model "
                             "(--rollout-model or at least one --model)")
        rollout = RolloutController(
            supervisor, router, args.rollout_watch, rollout_model,
            watch=args.rollout_mode,
            poll_interval_s=args.rollout_poll_s,
            observe_s=args.rollout_observe_s,
            min_canary_requests=args.rollout_min_requests,
            p99_floor_ms=args.rollout_p99_floor_ms)
        server.rollout = rollout
        rollout.start()
    endpoints = ["/v1/models", "/v1/fleet", "/healthz", "/readyz",
                 "/metrics"]
    if slo_engine is not None:
        endpoints += ["/v1/slo", "/v1/timeseries"]
    print(json.dumps({"serving": server.url, "role": "router",
                      "replicas": [r.describe() for r in
                                   supervisor.replicas],
                      "priority_classes": list(classes),
                      "endpoints": endpoints,
                      "rollout": (rollout.describe()
                                  if rollout is not None else None),
                      "autoscale": (None if autoscale is None else
                                    {"min": autoscale.min_replicas,
                                     "max": autoscale.max_replicas})}))
    sys.stdout.flush()

    stop = threading.Event()

    def _on_signal(signum, frame):
        print(json.dumps({"signal": signum, "action": "fleet drain"}),
              file=sys.stderr)
        stop.set()

    for s in (signal.SIGTERM, signal.SIGINT):
        signal.signal(s, _on_signal)
    stop.wait()
    # graceful fleet drain, same contract as single-replica mode: flip
    # /readyz to 503 FIRST so the balancer stops sending, give it a
    # moment to observe, let router-tracked in-flight work finish, and
    # only then tear the replicas down (their own SIGTERM drain flushes
    # whatever is still inside them)
    server.draining = True
    if rollout is not None:
        # settle the control loop first: a rollout mid-promotion must
        # not race the teardown's replica stops
        rollout.stop()
    grace = min(2.0, args.drain_timeout_s)
    time.sleep(grace)
    deadline = time.monotonic() + max(0.0, args.drain_timeout_s - grace)
    while time.monotonic() < deadline and any(
            r.inflight() for r in supervisor.replicas):
        time.sleep(0.1)
    supervisor.stop()
    server.stop()
    if args.trace_out:
        # supervisor.stop() SIGTERMed the replicas: each drained and
        # saved its own segment next to ours — trace_report merges them
        from deeplearning4j_tpu import monitor
        n = monitor.save_trace(args.trace_out)
        print(json.dumps({"trace_out": args.trace_out, "events": n,
                          "merge_hint": "tools/trace_report.py "
                                        f"{args.trace_out} "
                                        "<stem>.replica-*.json"}),
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
