"""Training listeners — the observability callback bus.

Parity with DL4J's TrainingListener/IterationListener framework
(deeplearning4j-nn/.../optimize/api/ + optimize/listeners/):
- ScoreIterationListener          (prints score every N iterations)
- PerformanceListener             (samples/sec, batches/sec, ETL time;
                                   PerformanceListener.java:22-87)
- CollectScoresIterationListener  (score history collection)
- TimeIterationListener           (ETA logging)
- EvaluativeListener              (periodic held-out evaluation)
- CheckpointListener              (periodic checkpoints w/ keepLast(n);
                                   checkpoint/CheckpointListener.java:72-144)
- ExpertLoadListener              (expert layers' routed tokens and walked
                                   rows as counters)
"""
from __future__ import annotations

import logging
import os
import re
import time
from typing import Callable, List, Optional

log = logging.getLogger("deeplearning4j_tpu")


class TrainingListener:
    #: True for listeners whose iteration_done inspects the MODEL (params,
    #: opt state) rather than just the scalar score stream. The
    #: input-pipelined fit path (fit(scan_steps=K)) delivers iteration_done
    #: up to 2K-1 steps after the params have advanced, so such listeners
    #: force a fallback to the per-call path where model state and
    #: iteration number are always in sync.
    reads_model = False

    def on_epoch_start(self, model, epoch: int):
        pass

    def on_epoch_end(self, model, epoch: int):
        pass

    def iteration_done(self, model, iteration: int, epoch: int,
                       score: float, etl_ms: float = 0.0,
                       batch_size: int = 0):
        pass


class ScoreIterationListener(TrainingListener):
    def __init__(self, print_iterations: int = 10):
        self.n = max(int(print_iterations), 1)

    def iteration_done(self, model, iteration, epoch, score, etl_ms=0.0,
                       batch_size=0):
        if iteration % self.n == 0:
            log.info("Score at iteration %d is %s", iteration, score)


class PerformanceListener(TrainingListener):
    """Reports throughput per iteration (DL4J PerformanceListener.java:22-87).

    Every reported record carries the SAME four numbers in the history
    dict, the log line, and the telemetry registry (monitor/metrics.py:
    train_examples_per_sec / train_batches_per_sec gauges and the
    train_etl_seconds histogram) — one source of truth for throughput,
    whether you read logs, listener history, or a /metrics scrape."""

    def __init__(self, frequency: int = 1, report: bool = True):
        self.frequency = max(int(frequency), 1)
        self.report = report
        self._last_time: Optional[float] = None
        self._compiled_logged: set = set()   # ledger fingerprints reported
        self.history: List[dict] = []

    def _report_compiled(self):
        """Once per distinct compiled program (first iteration after its
        compile): log HBM peak and MFU, sourced from the monitor.xla
        ledger — no re-lowering, just a dict read. No-op while the ledger
        is disabled."""
        from deeplearning4j_tpu.monitor import xla as xla_ledger
        if not xla_ledger.enabled():
            return
        rec = xla_ledger.latest_record("train")
        if rec is None or rec.fingerprint in self._compiled_logged:
            return
        mfu = xla_ledger.last_mfu("train")
        if mfu is None and rec.flops and xla_ledger.device_peak_flops():
            # debut iteration: its wall time included the compile, so no
            # MFU sample exists yet — log on the next (steady) iteration
            return
        self._compiled_logged.add(rec.fingerprint)
        peak = rec.hbm_peak_bytes
        log.info(
            "compiled step %s (fingerprint %s): %s GFLOP/call, HBM peak "
            "%s, compile %.2f s, mfu %s",
            rec.name, rec.fingerprint,
            "n/a" if not rec.flops else f"{rec.flops / 1e9:.2f}",
            "n/a" if peak is None else f"{peak / 2**20:.1f} MiB",
            rec.compile_seconds,
            "n/a" if mfu is None else f"{mfu:.1f}%")

    def iteration_done(self, model, iteration, epoch, score, etl_ms=0.0,
                       batch_size=0):
        from deeplearning4j_tpu import monitor
        if self.report:
            self._report_compiled()
        now = time.perf_counter()
        if self._last_time is not None and iteration % self.frequency == 0:
            dt = now - self._last_time
            rec = {
                "iteration": iteration,
                "batches_per_sec": 1.0 / dt if dt > 0 else float("inf"),
                "examples_per_sec": batch_size / dt if dt > 0 else float("inf"),
                "etl_ms": etl_ms,
                "iteration_ms": dt * 1e3,
            }
            # historical key kept so existing consumers don't break
            rec["samples_per_sec"] = rec["examples_per_sec"]
            self.history.append(rec)
            if dt > 0:
                monitor.gauge("train_examples_per_sec",
                              "Training throughput, examples/sec "
                              "(PerformanceListener)").set(
                    rec["examples_per_sec"])
                monitor.gauge("train_batches_per_sec",
                              "Training throughput, batches/sec "
                              "(PerformanceListener)").set(
                    rec["batches_per_sec"])
            monitor.histogram("train_etl_seconds",
                              "Host ETL time per reported iteration "
                              "(PerformanceListener)").observe(etl_ms / 1e3)
            # goodput beside throughput, sourced from the ledger's live
            # session (the same accumulators /metrics scrapes, so the
            # log line and the gauge cannot disagree); absent while the
            # ledger is off
            from deeplearning4j_tpu.monitor import goodput
            gp = goodput.live_stats()
            if gp is not None:
                rec["goodput_pct"] = gp["goodput_pct"]
                rec["dominant_stall"] = gp["dominant_stall"]
            if self.report:
                suffix = ""
                if gp is not None:
                    suffix = (f"; goodput: {gp['goodput_pct']:.1f}%% "
                              f"(top stall: {gp['dominant_stall']})")
                log.info("ETL: %.0f ms; iteration %d; iteration time: %.1f ms; "
                         "examples/sec: %.1f; batches/sec: %.2f" + suffix,
                         etl_ms, iteration, rec["iteration_ms"],
                         rec["examples_per_sec"], rec["batches_per_sec"])
        self._last_time = now


class CollectScoresIterationListener(TrainingListener):
    def __init__(self, frequency: int = 1):
        self.frequency = max(int(frequency), 1)
        self.scores: List[tuple] = []

    def iteration_done(self, model, iteration, epoch, score, etl_ms=0.0,
                       batch_size=0):
        if iteration % self.frequency == 0:
            self.scores.append((iteration, float(score)))


class TimeIterationListener(TrainingListener):
    """Logs remaining-time estimate (DL4J TimeIterationListener)."""

    def __init__(self, total_iterations: int, frequency: int = 50):
        self.total = total_iterations
        self.frequency = max(int(frequency), 1)
        self._start: Optional[float] = None

    def iteration_done(self, model, iteration, epoch, score, etl_ms=0.0,
                       batch_size=0):
        if self._start is None:
            self._start = time.perf_counter()
            return
        if iteration % self.frequency == 0 and iteration > 0:
            elapsed = time.perf_counter() - self._start
            rate = elapsed / iteration
            remaining = (self.total - iteration) * rate
            log.info("Remaining time estimate: %.1f s (iteration %d/%d)",
                     remaining, iteration, self.total)


class EvaluativeListener(TrainingListener):
    """Periodic evaluation on a held-out iterator (DL4J EvaluativeListener)."""

    reads_model = True

    def __init__(self, iterator, frequency: int = 1, unit: str = "epoch"):
        self.iterator = iterator
        self.frequency = max(int(frequency), 1)
        self.unit = unit
        self.results: List[tuple] = []

    def iteration_done(self, model, iteration, epoch, score, etl_ms=0.0,
                       batch_size=0):
        if self.unit == "iteration" and iteration % self.frequency == 0:
            self._evaluate(model, iteration)

    def on_epoch_end(self, model, epoch):
        if self.unit == "epoch" and (epoch + 1) % self.frequency == 0:
            self._evaluate(model, epoch)

    def _evaluate(self, model, at):
        ev = model.evaluate(self.iterator)
        self.results.append((at, ev))
        log.info("Evaluation at %s %d: accuracy=%.4f", self.unit, at, ev.accuracy())


class CheckpointListener(TrainingListener):
    """Periodic checkpoint saver with retention policy
    (DL4J checkpoint/CheckpointListener.java:46-144: saveEveryNIterations /
    saveEveryNEpochs + keepLast).

    `async_save=True` moves the zip serialization off the training thread
    (the device array snapshot is taken synchronously — params are copied
    to host before the step loop continues mutating them — but compression
    and file IO happen in a background worker, so checkpointing does not
    stall the accelerator). Call `flush()` (or let the listener be used as
    a context manager) to wait for pending saves; errors from background
    saves surface on the next save or flush."""

    reads_model = True      # snapshots params: scan-mode fit falls back

    def __init__(self, directory: str, save_every_n_iterations: Optional[int] = None,
                 save_every_n_epochs: Optional[int] = None, keep_last: int = 3,
                 async_save: bool = False):
        self.dir = directory
        self.every_iter = save_every_n_iterations
        self.every_epoch = save_every_n_epochs
        self.keep_last = keep_last
        self.async_save = async_save
        self._saved: List[str] = []
        self._executor = None
        self._pending: List = []
        os.makedirs(directory, exist_ok=True)

    def _prune(self):
        """keep_last retention by directory scan: only files matching the
        tag kinds THIS listener writes (checkpoint_iter_* and/or
        checkpoint_epoch_*) count and get deleted — foreign files in the
        checkpoint directory (exports, notes, resilience manifests, a
        sibling listener's other-kind checkpoints) are ignored. Scanning
        (vs. an in-memory list) also retires leftovers from a previous
        run of the same job."""
        kinds = [k for k, on in (("iter", self.every_iter),
                                 ("epoch", self.every_epoch)) if on]
        if not kinds:
            return
        pat = re.compile(rf"^checkpoint_({'|'.join(kinds)})_(\d+)\.zip$")
        try:
            names = os.listdir(self.dir)
        except OSError:
            return
        # order by the monotone counter in the filename, NOT mtime —
        # coarse-granularity or copied-file mtimes would make ties
        # arbitrary and could delete the newest checkpoint. Iteration and
        # epoch counters are not comparable to each other, so retention
        # applies per kind (keep_last of each).
        for kind in kinds:
            own = sorted((int(m.group(2)), n) for n in names
                         for m in [pat.match(n)] if m and m.group(1) == kind)
            while len(own) > self.keep_last:
                try:
                    os.remove(os.path.join(self.dir, own.pop(0)[1]))
                except OSError:
                    pass

    def _save(self, model, tag: str):
        # save_model's default atomic mode (tmp + os.replace) means a kill
        # mid-save can never leave a truncated checkpoint zip at `path`
        from deeplearning4j_tpu.util.serialization import save_model
        path = os.path.join(self.dir, f"checkpoint_{tag}.zip")
        if self.async_save:
            import concurrent.futures

            import numpy as np

            import jax
            if self._executor is None:
                self._executor = concurrent.futures.ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="ckpt")
            self._raise_pending_errors(block=False)
            # host snapshot NOW: copy() materializes independent device
            # buffers (the live ones are donated by the next step), the
            # counters ride along, and the optimizer state gets its own
            # forced host copies (np.asarray could alias the soon-donated
            # originals on CPU backends)
            snap = model.copy()
            snap.iteration_count = model.iteration_count
            snap.epoch_count = model.epoch_count
            snap.params = jax.tree_util.tree_map(np.asarray, snap.params)
            snap.state = jax.tree_util.tree_map(np.asarray, snap.state)
            snap.opt_state = jax.tree_util.tree_map(
                lambda a: np.array(a, copy=True), model.opt_state)

            def job():
                save_model(snap, path)
                # retention runs AFTER the file lands; the single-worker
                # executor serializes these mutations
                self._saved.append(path)
                self._prune()

            self._pending.append(self._executor.submit(job))
        else:
            save_model(model, path)
            self._saved.append(path)
            self._prune()

    def _raise_pending_errors(self, block: bool):
        still = []
        for f in self._pending:
            if f.done() or block:
                f.result()          # re-raises background failures
            else:
                still.append(f)
        self._pending = still

    def flush(self):
        """Block until all background saves land (async_save mode)."""
        self._raise_pending_errors(block=True)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        try:
            self.flush()
        finally:                    # never leak the worker thread
            if self._executor is not None:
                self._executor.shutdown(wait=True)

    def iteration_done(self, model, iteration, epoch, score, etl_ms=0.0,
                       batch_size=0):
        if self.every_iter and iteration > 0 and iteration % self.every_iter == 0:
            self._save(model, f"iter_{iteration}")

    def on_epoch_end(self, model, epoch):
        if self.every_epoch and (epoch + 1) % self.every_epoch == 0:
            self._save(model, f"epoch_{epoch}")


class ProfilerListener(TrainingListener):
    """Captures an XLA device trace with jax.profiler for a window of
    iterations (SURVEY.md §5.1: the reference's op-level profiling lives in
    external ND4J; the TPU equivalent is the XLA profiler, exposed here as
    an ordinary listener).

    Usage:
        net.set_listeners(ProfilerListener("/tmp/trace", start_iteration=5,
                                           num_iterations=3))
        net.fit(...)          # iterations [5, 8) are traced
        # inspect with tensorboard or xprof on the written trace dir
    """

    reads_model = True      # brackets live device work: needs per-call fit

    def __init__(self, log_dir: str, start_iteration: int = 5,
                 num_iterations: int = 3):
        self.log_dir = log_dir
        self.start_iteration = start_iteration
        self.stop_iteration = start_iteration + num_iterations
        self._active = False
        self.trace_dir: Optional[str] = None

    def iteration_done(self, model, iteration, epoch, score, etl_ms,
                       batch_size):
        import jax
        if iteration + 1 == self.start_iteration and not self._active:
            jax.profiler.start_trace(self.log_dir)
            self._active = True
        elif iteration + 1 >= self.stop_iteration and self._active:
            jax.profiler.stop_trace()
            self._active = False
            self.trace_dir = self.log_dir
            log.info("profiler trace written to %s", self.log_dir)

    def on_epoch_end(self, model, epoch):
        if self._active:        # epoch ended inside the window: close out
            import jax
            jax.profiler.stop_trace()
            self._active = False
            self.trace_dir = self.log_dir


class ExpertLoadListener(TrainingListener):
    """What a model's expert layers (`MoEFeedForward`, alone, as a
    `TransformerBlock`'s FFN or as a `MixerBlock`'s mixer) routed and
    walked, as counters. The layers
    count in their own STATE, so every fit path of both containers counts
    alike; this listener reads the state where ``fit()`` holds a finished
    one, at the start and the end of an epoch (inside an epoch the
    pipelined paths have the next chunk in flight, and reading its state
    would wait for it), and publishes the difference:
    ``moe_tokens_routed_total{layer,held}`` ((token, expert) pairs, by
    whether the expert is held here),
    ``moe_expert_load_max_over_mean{layer}`` (the busiest expert's tokens
    over the mean of all, last step) and, for a layer that holds a share
    of its experts, ``moe_dispatch_tier_total{layer,tier}`` (dispatches by
    the row tier they walked, named by its share of the dispatch's pairs)
    and ``moe_rows_walked_total{layer}`` (rows those tiers had) and
    ``moe_tokens_with_held_pair_total{layer}`` (tokens with at least one
    of their experts held here; over the tokens routed it is the share of
    tokens the layer adds anything to). The state's totals are uint32 and
    wrap; the difference is taken modulo 2**32.

    A sparse attention (`MultiHeadAttention(indexer=)`, alone or as a
    block's attention) counts in its state too, in two uint32 words a
    total (a step of long sequences passes 2**32 pairs within a few
    steps): ``dsa_pairs_selected_total{layer}`` (the (query, key) pairs its
    selection kept), ``dsa_pairs_causal_total{layer}`` (the pairs it chose
    among: an exact top-k keeps ``sum_t min(t + 1, topk)`` of ``T (T + 1)
    / 2`` a sequence, and a selection that lets a tie in or drops a key
    shows here) and the gauge ``dsa_indexer_kl{layer}`` (the indexer's
    loss at the last step).

    A block on several residual streams (`HyperConnectedBlock`) keeps two
    gauges of its last step in its state: ``mhc_res_gap{layer}`` (how far
    a row or column sum of its stream-to-stream mapping is from 1 after
    the Sinkhorn steps, the largest over tokens and sub-layers) and
    ``mhc_pre_entropy{layer}`` (the mean entropy, nats, of the weights a
    sub-layer reads the streams with)."""

    _TOTALS = ("tokens_routed_total", "tier_hits", "rows_walked_total",
               "tokens_with_held_pair_total")

    def __init__(self):
        self._at_start = {}
        self._pairs_at_start = {}

    @staticmethod
    def _layers(model):
        """(state key, expert layer, its state) of every expert layer."""
        from deeplearning4j_tpu.nn.regularization import constraint_map
        for key, layer in constraint_map(model).items():
            state = model.state.get(key) or {}
            if getattr(layer, "ffn", None) is not None:
                layer, state = layer.ffn, state.get("ffn", {})
            elif getattr(layer, "mixer", None) is not None:
                layer = layer.mixer      # a MixerBlock's state is its mixer's
            if "tokens_routed_total" in state:
                yield key, layer, state

    @classmethod
    def _totals(cls, state):
        import numpy as np
        return {name: np.asarray(state[name], np.int64)
                for name in cls._TOTALS if name in state}

    _PAIRS = ("pairs_selected_total", "pairs_causal_total")

    @classmethod
    def _sparse_layers(cls, model):
        """(state key, state) of every attention that selects its keys."""
        from deeplearning4j_tpu.nn.regularization import constraint_map
        for key, layer in constraint_map(model).items():
            state = model.state.get(key) or {}
            if getattr(layer, "attn", None) is not None:
                state = state.get("attn", {})
            if cls._PAIRS[0] in state:
                yield key, state

    @classmethod
    def _pairs(cls, state):
        import numpy as np
        words = {name: [int(w) for w in np.asarray(state[name], np.uint32)]
                 for name in cls._PAIRS}
        return {name: lo + (hi << 32) for name, (lo, hi) in words.items()}

    def on_epoch_start(self, model, epoch):
        self._at_start = {key: self._totals(state)
                          for key, _, state in self._layers(model)}
        self._pairs_at_start = {key: self._pairs(state)
                                for key, state in self._sparse_layers(model)}

    def _publish_sparse(self, model):
        import numpy as np
        from deeplearning4j_tpu import monitor
        counters = {
            "pairs_selected_total": monitor.counter(
                "dsa_pairs_selected_total",
                "(query, key) pairs a sparse attention's selection kept",
                labels=("layer",)),
            "pairs_causal_total": monitor.counter(
                "dsa_pairs_causal_total",
                "(query, key <= query) pairs a sparse attention's selection "
                "chose among", labels=("layer",))}
        kl = monitor.gauge(
            "dsa_indexer_kl",
            "a sparse attention's indexer loss (Kullback-Leibler divergence "
            "from the attention's mean probabilities over the kept keys), "
            "last step", labels=("layer",))
        for key, state in self._sparse_layers(model):
            now = self._pairs(state)
            before = self._pairs_at_start.get(key, {})
            for name, counter in counters.items():
                counter.inc((now[name] - before.get(name, 0)) % 2 ** 64,
                            layer=key)
            kl.set(float(np.asarray(state["indexer_kl"])), layer=key)
            self._pairs_at_start[key] = now

    @staticmethod
    def _publish_streams(model):
        import numpy as np
        from deeplearning4j_tpu import monitor
        gauges = {
            "res_gap": monitor.gauge(
                "mhc_res_gap",
                "largest |row sum - 1| or |column sum - 1| of a "
                "multi-stream block's stream-to-stream mapping after its "
                "Sinkhorn steps, over tokens and sub-layers, last step",
                labels=("layer",)),
            "pre_entropy": monitor.gauge(
                "mhc_pre_entropy",
                "mean entropy (nats) of the weights a multi-stream block's "
                "sub-layers read the streams with, over their sum, last "
                "step", labels=("layer",))}
        for key, state in (model.state or {}).items():
            if isinstance(state, dict) and "mhc" in state:
                for name, gauge in gauges.items():
                    gauge.set(float(np.asarray(state["mhc"][name])),
                              layer=key)

    def on_epoch_end(self, model, epoch):
        import numpy as np
        from deeplearning4j_tpu import monitor
        self._publish_sparse(model)
        self._publish_streams(model)
        routed = monitor.counter(
            "moe_tokens_routed_total",
            "(token, expert) pairs routed by an expert layer, by whether "
            "the expert is held here", labels=("layer", "held"))
        load = monitor.gauge(
            "moe_expert_load_max_over_mean",
            "tokens of the busiest expert over the mean of all experts, "
            "last step", labels=("layer",))
        tiers = monitor.counter(
            "moe_dispatch_tier_total",
            "dispatches of an expert layer by the row tier they walked "
            "(its share of the dispatch's token x top_k pairs)",
            labels=("layer", "tier"))
        walked = monitor.counter(
            "moe_rows_walked_total",
            "rows the dispatches of an expert layer gathered, multiplied "
            "and summed back: those of the tiers they walked",
            labels=("layer",))
        with_held = monitor.counter(
            "moe_tokens_with_held_pair_total",
            "tokens of an expert layer with at least one of their top_k "
            "experts held here", labels=("layer",))
        for key, layer, state in self._layers(model):
            now = self._totals(state)
            before = self._at_start.get(key, {})
            gained = {name: (total - before.get(name, 0)) % 2 ** 32
                      for name, total in now.items()}
            drew = gained["tokens_routed_total"]
            lo, hi = layer.experts_held or (0, layer.n_experts)
            held = int(drew[lo:hi].sum())
            routed.inc(held, layer=key, held="yes")
            routed.inc(int(drew.sum()) - held, layer=key, held="no")
            last = np.asarray(state["tokens_routed"], np.float64)
            load.set(float(last.max() / max(last.mean(), 1e-9)), layer=key)
            if "tier_hits" in gained:
                for name, hits in zip(layer.tier_names(),
                                      gained["tier_hits"]):
                    tiers.inc(int(hits), layer=key, tier=name)
                walked.inc(int(gained["rows_walked_total"]), layer=key)
            if "tokens_with_held_pair_total" in gained:
                with_held.inc(int(gained["tokens_with_held_pair_total"]),
                              layer=key)
            self._at_start[key] = now


class DivergenceListener(TrainingListener):
    """Training failure detection (SURVEY.md §5.2/5.3: the reference has no
    in-tree sanitizer; its closest analog is cuDNN helpers counting
    failures). Watches the score stream for NaN/Inf or a sustained
    explosion and either raises TrainingDivergedError (default — fail the
    job before it burns more TPU hours) or invokes a callback (alerting /
    checkpoint-and-restart policies).

    Usage:
        net.set_listeners(DivergenceListener())                  # raise
        net.set_listeners(DivergenceListener(on_divergence=cb))  # custom
    """

    def __init__(self, explosion_factor: float = 1e4,
                 window: int = 20, on_divergence: Optional[Callable] = None):
        self.explosion_factor = explosion_factor
        self.window = window
        self.on_divergence = on_divergence
        # a custom callback receives the model; the default raise path only
        # reads the score stream and stays scan-compatible
        self.reads_model = on_divergence is not None
        self._recent: List[float] = []

    def iteration_done(self, model, iteration, epoch, score, etl_ms,
                       batch_size):
        import math
        bad = None
        if not math.isfinite(score):
            bad = f"non-finite score {score} at iteration {iteration}"
        else:
            self._recent.append(score)
            if len(self._recent) > self.window:
                self._recent.pop(0)
            baseline = min(self._recent)
            if baseline > 0 and score > baseline * self.explosion_factor:
                bad = (f"score exploded: {score:.4g} > "
                       f"{self.explosion_factor:g} x recent best "
                       f"{baseline:.4g} at iteration {iteration}")
        if bad:
            if self.on_divergence is not None:
                self.on_divergence(model, iteration, bad)
            else:
                raise TrainingDivergedError(bad)


class TrainingDivergedError(RuntimeError):
    """Raised by DivergenceListener when the loss goes NaN/Inf/explodes."""
