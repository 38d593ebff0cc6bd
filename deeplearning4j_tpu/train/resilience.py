"""Fault-tolerant training: resilient fit loop with atomic checkpoints,
auto-resume, preemption handling, and a per-step fault policy.

The north-star deployment is a preemptible TPU fleet where jobs are
killed routinely (spot preemption, maintenance, transport flaps) and a
single NaN step must not burn the run. `parallel/distributed.py`
declares the stance — "failure handling = checkpoint + restart; we layer
checkpoint/resume on top" — and this module is that layer:

- **Atomic, manifest-tracked checkpoints.** Every checkpoint zip is
  written tmp-then-`os.replace` (util/serialization.save_model atomic
  mode) and recorded in a `manifest.json` (itself atomically replaced)
  with a SHA-256 integrity hash. A kill at ANY instant leaves either the
  previous complete manifest/checkpoint set or the new one — never a
  truncated zip that a resume would trip over. Checkpoints carry params,
  updater (optimizer) state, layer state, iteration/epoch counters, the
  live RNG key, the position in the data stream, and the fitted data
  normalizer. `keep_last` pruning removes only manifest-tracked files —
  foreign files in the directory are never touched.

- **Auto-resume.** `fit()` restores the newest manifest entry whose hash
  verifies (corrupted/missing files fall back to the next-newest),
  fast-forwards the data iterator to the recorded epoch/step, and
  continues the RNG stream from the stored key — a killed-and-resumed
  run reaches bitwise-identical parameters (and updater state) to an
  uninterrupted one, provided the data source is deterministic.

- **Preemption.** SIGTERM/SIGINT set a flag; at the next step boundary
  the trainer writes a final checkpoint and shuts down cleanly
  (`FitReport.preempted=True`). Re-running the same command resumes.

- **Per-step fault policy** (`FaultPolicy`): transient errors retry with
  jittered exponential backoff from a pre-step host snapshot (a retried
  step is bitwise-identical to an unfaulted one — same RNG, same batch);
  non-finite losses skip the step (snapshot restore) with a
  consecutive-skip abort threshold; score explosions are detected by an
  integrated `DivergenceListener`. Unrecoverable divergence restores the
  newest good checkpoint instead of leaving NaN params behind.

`util/faults.py` injects deterministic faults through the same step
boundaries, so every path above is testable (tests/test_resilience.py,
tools/chaos_fit.py). See docs/FAULT_TOLERANCE.md for the operational
guide.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import logging
import math
import os
import random
import signal
import threading
import time
import weakref
import zipfile
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.monitor import goodput
from deeplearning4j_tpu.train.listeners import (
    DivergenceListener, TrainingDivergedError,
)
from deeplearning4j_tpu.util.faults import FaultInjector, TransientFaultError

log = logging.getLogger("deeplearning4j_tpu")


# --------------------------------------------------------------------- policy
@dataclasses.dataclass
class FaultPolicy:
    """Per-step fault handling knobs (docs/FAULT_TOLERANCE.md)."""

    #: transient-error retry: attempts beyond the first, with jittered
    #: exponential backoff in [backoff_base, backoff_max] seconds.
    max_retries: int = 3
    backoff_base: float = 0.05
    backoff_max: float = 2.0
    #: exception classes treated as retryable. Everything else propagates.
    transient_errors: Tuple = (TransientFaultError, ConnectionError,
                               TimeoutError, OSError)
    #: NaN/Inf loss -> restore the pre-step snapshot and skip the batch.
    skip_nonfinite: bool = True
    #: consecutive skipped steps beyond which training is declared
    #: unrecoverable (the last good checkpoint is restored).
    max_consecutive_skips: int = 3
    #: "restore": restore newest good checkpoint, log, stop the fit.
    #: "raise": restore, then raise TrainingDivergedError.
    on_unrecoverable: str = "restore"
    #: score-explosion detection via DivergenceListener (None disables).
    explosion_factor: Optional[float] = 1e4
    explosion_window: int = 20
    #: seed for the backoff jitter stream (determinism in tests).
    seed: int = 0

    @property
    def guards_steps(self) -> bool:
        """True when a pre-step host snapshot is kept (needed to undo a
        faulted step). Costs one host copy of params/opt/state per step —
        disable both knobs for maximum-throughput unguarded fits."""
        return self.skip_nonfinite or self.max_retries > 0


@dataclasses.dataclass
class FitReport:
    """What happened during a resilient fit (returned by
    ResilientTrainer.fit; the trained model lives on the network)."""

    applied_steps: int = 0
    skipped_steps: int = 0
    retries: int = 0
    checkpoints_written: int = 0
    checkpoints_blessed: int = 0
    resumed_from: Optional[str] = None
    preempted: bool = False
    diverged: bool = False
    restored_checkpoint: Optional[str] = None
    final_score: Optional[float] = None
    #: goodput-ledger session summary, when `monitor.goodput` is enabled:
    #: the fit's wall-clock split over the closed category set (so a
    #: preempt->resume run accounts its replay as overhead, not compute)
    goodput_pct: Optional[float] = None
    time_by_category: Optional[dict] = None


class _Unrecoverable(Exception):
    """Internal control flow: divergence beyond the fault policy's
    tolerance; fit() translates it into restore-last-good semantics."""


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except OSError:     # EPERM etc.: exists but not ours
        return True
    return True


# --------------------------------------------------------- checkpoint manager
class CheckpointManager:
    """Atomic, manifest-tracked checkpoint directory.

    Layout:
        <dir>/manifest.json          atomic (tmp + os.replace), hash index
        <dir>/ckpt_000042.zip        save_model zip + resilience extras

    The manifest is the source of truth: `latest_valid()` walks it
    newest-first and SHA-256-verifies each candidate, so a truncated or
    bit-rotted file is skipped with a warning instead of crashing the
    resume. Pruning removes only manifest-tracked files — anything else
    in the directory (foreign checkpoints, notes, exports) is preserved.
    """

    MANIFEST = "manifest.json"
    BLESSED = "blessed.json"

    def __init__(self, directory: str, keep_last: int = 3,
                 prefix: str = "ckpt"):
        self.dir = directory
        self.keep_last = max(1, int(keep_last))
        self.prefix = prefix
        os.makedirs(directory, exist_ok=True)
        # a kill mid-save leaves a *.zip.tmp.<pid> partial. Sweep only
        # leftovers whose writing process is gone — on a shared checkpoint
        # dir another live process may be mid-save right now, and deleting
        # its tmp file would break its os.replace.
        for name in os.listdir(directory):
            if not (name.startswith(prefix) and ".zip.tmp." in name):
                continue
            try:
                pid = int(name.rsplit(".", 1)[-1])
            except ValueError:
                continue
            if pid != os.getpid() and _pid_alive(pid):
                continue
            try:
                os.remove(os.path.join(directory, name))
            except OSError:
                pass

    # ------------------------------------------------------------- manifest
    def _manifest_path(self) -> str:
        return os.path.join(self.dir, self.MANIFEST)

    def _read_manifest(self) -> dict:
        try:
            with open(self._manifest_path()) as f:
                return json.load(f)
        except (OSError, ValueError):
            return {"version": 1, "next_ordinal": 0, "checkpoints": []}

    def _write_manifest(self, manifest: dict):
        tmp = self._manifest_path() + f".tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(manifest, f, indent=1)
        os.replace(tmp, self._manifest_path())

    @staticmethod
    def _sha256(path: str) -> str:
        h = hashlib.sha256()
        with open(path, "rb") as f:
            for chunk in iter(lambda: f.read(1 << 20), b""):
                h.update(chunk)
        return h.hexdigest()

    # ----------------------------------------------------------------- save
    def save(self, model, extra: dict) -> str:
        """Write one checkpoint atomically and record it in the manifest.
        `extra` (JSON-serializable) lands in the zip as resilience.json —
        the RNG key / stream position / normalizer the resume needs."""
        from deeplearning4j_tpu.util.serialization import save_model
        manifest = self._read_manifest()
        ordinal = int(manifest.get("next_ordinal", 0))
        fname = f"{self.prefix}_{ordinal:06d}.zip"
        path = os.path.join(self.dir, fname)
        save_model(model, path, atomic=True,
                   extra_entries={"resilience.json": json.dumps(extra)})
        manifest["checkpoints"].append({
            "file": fname,
            "sha256": self._sha256(path),
            "iteration": int(model.iteration_count),
            "epoch": int(model.epoch_count),
            "step_in_epoch": int(extra.get("step_in_epoch", 0)),
            "time": time.time(),
        })
        manifest["next_ordinal"] = ordinal + 1
        # keep_last pruning: drop only files THIS manifest tracks — and
        # never the blessed (serving-eligible) one: the rollout watcher
        # may not have deployed it yet, and pruning it would leave
        # blessed.json pointing at nothing
        blessed = self._blessed_file()
        while len(manifest["checkpoints"]) > self.keep_last:
            prunable = [e for e in manifest["checkpoints"][:-self.keep_last]
                        if e["file"] != blessed]
            if not prunable:
                break
            old = prunable[0]
            manifest["checkpoints"].remove(old)
            try:
                os.remove(os.path.join(self.dir, old["file"]))
            except OSError:
                pass
        self._write_manifest(manifest)
        return path

    # ---------------------------------------------------------------- bless
    def _blessed_path(self) -> str:
        return os.path.join(self.dir, self.BLESSED)

    def _blessed_file(self) -> Optional[str]:
        try:
            with open(self._blessed_path()) as f:
                return json.load(f).get("file")
        except (OSError, ValueError):
            return None

    def bless(self, path: str, metrics: Optional[dict] = None) -> str:
        """Mark a checkpoint serving-eligible: atomically (re)write
        <dir>/blessed.json naming the file, its SHA-256, and the eval
        metrics that justified the blessing. serving/rollout.py tails
        this manifest — blessing is the eval gate between "the trainer
        wrote a checkpoint" and "the fleet may canary it"."""
        fname = os.path.basename(path)
        doc = {
            "version": 1,
            "file": fname,
            "path": os.path.abspath(path),
            "sha256": self._sha256(path),
            "blessed_at": time.time(),
            "metrics": dict(metrics or {}),
        }
        for entry in self._read_manifest().get("checkpoints", []):
            if entry["file"] == fname:
                doc["iteration"] = entry["iteration"]
                doc["epoch"] = entry["epoch"]
                break
        tmp = self._blessed_path() + f".tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1)
        os.replace(tmp, self._blessed_path())
        monitor.counter("resilience_checkpoints_blessed_total",
                        "Checkpoints marked serving-eligible "
                        "(blessed.json writes)").inc()
        log.info("checkpoint blessed for serving: %s (metrics %s)",
                 fname, doc["metrics"])
        return self._blessed_path()

    # --------------------------------------------------------------- resume
    def latest_valid(self) -> Optional[dict]:
        """Newest manifest entry whose file exists and hash verifies;
        invalid entries are skipped (fall back to the next-newest)."""
        manifest = self._read_manifest()
        for entry in reversed(manifest.get("checkpoints", [])):
            path = os.path.join(self.dir, entry["file"])
            if not os.path.exists(path):
                log.warning("checkpoint %s missing; falling back", path)
                continue
            try:
                if self._sha256(path) != entry["sha256"]:
                    log.warning("checkpoint %s failed integrity check; "
                                "falling back", path)
                    continue
            except OSError as e:
                log.warning("checkpoint %s unreadable (%s); falling back",
                            path, e)
                continue
            return {**entry, "path": path}
        return None

    def restore_into(self, model, path: str) -> dict:
        """Load a checkpoint INTO an existing (initialized) model and
        return the resilience extras dict ({} for plain save_model zips)."""
        from deeplearning4j_tpu.util.serialization import (
            _npz_bytes_to_tree, _restore_like,
        )
        if model.params is None:
            model.init()
        with zipfile.ZipFile(path, "r") as zf:
            meta = json.loads(zf.read("metadata.json"))
            conf_json = zf.read("configuration.json").decode()
            if conf_json != model.conf.to_json():
                log.warning("resuming %s into a model whose configuration "
                            "differs from the checkpoint's", path)
            model.params = _restore_like(
                model.params, _npz_bytes_to_tree(zf.read("coefficients.npz")))
            model.state = _restore_like(
                model.state, _npz_bytes_to_tree(zf.read("state.npz")))
            model.iteration_count = int(meta.get("iteration_count", 0))
            model.epoch_count = int(meta.get("epoch_count", 0))
            names = zf.namelist()
            if "updaterState.bin" in names:
                from flax import serialization as fser
                from deeplearning4j_tpu.util.params import own_tree
                # owned copies: from_bytes yields numpy leaves which the
                # donated train step must never alias (owned_leaf)
                model.opt_state = own_tree(fser.from_bytes(
                    model.opt_state, zf.read("updaterState.bin")))
            extra = json.loads(zf.read("resilience.json")) \
                if "resilience.json" in names else {}
        return extra


# ----------------------------------------------------------------- preemption
class PreemptionGuard:
    """SIGTERM/SIGINT -> request a clean stop at the next step boundary.

    Installed only on the main thread (signal.signal requires it); the
    previous handlers are restored on exit. A second SIGINT while the
    final checkpoint is being written still raises KeyboardInterrupt —
    the guard chains to the previous handler after the first delivery —
    so an operator can always force-quit."""

    def __init__(self, signals=(signal.SIGTERM, signal.SIGINT)):
        self.signals = signals
        self.requested = False
        self.signum: Optional[int] = None
        self._old: dict = {}

    def _handler(self, signum, frame):
        if self.requested:
            old = self._old.get(signum)
            if callable(old):
                old(signum, frame)
            return
        self.requested = True
        self.signum = signum
        log.warning("received signal %d: checkpointing and shutting down "
                    "at the next step boundary", signum)

    def __enter__(self):
        if threading.current_thread() is threading.main_thread():
            for s in self.signals:
                try:
                    self._old[s] = signal.signal(s, self._handler)
                except (ValueError, OSError):  # non-main thread / exotic os
                    pass
        return self

    def __exit__(self, *exc):
        for s, old in self._old.items():
            try:
                signal.signal(s, old)
            except (ValueError, OSError):
                pass
        self._old = {}
        return False


# ------------------------------------------------------------------- drivers
def _host_copy(tree):
    # forced host copies: the live arrays are donated by the next step —
    # np.asarray could alias the soon-deleted buffers on CPU backends
    return jax.tree_util.tree_map(lambda a: np.array(a, copy=True), tree)


class _NetDriver:
    """Per-call step execution for MultiLayerNetwork — the same compiled
    step, staging, and RNG stream as fit()'s per-call path
    (nn/fit_loop.py)."""

    rng_mult = 7919

    #: ledger name of the compiled step this driver executes
    ledger_program = "mln/train_step"

    #: whether this driver resolves the process-wide GSPMD plan
    #: (parallel/plan.use_mesh) onto the net — the _WrapperDriver turns
    #: this off because ParallelWrapper manages its own plan/placement
    _uses_plan = True

    def __init__(self, net):
        self.net = net
        self._ledger_rec = None        # latest monitor.xla program record
        self._ledger_fresh = False     # last capture was a debut
        self._ledger_pending = None    # deferred capture args (see below)

    def capture_ledger(self):
        """Run the capture step() deferred, OUTSIDE the caller's attempt
        clock — the first sight of a program pays an AOT lower+compile,
        which must not inflate step_secs / train_step_seconds. Dict-hit
        after the first call per program. Marks _ledger_fresh so the
        caller can skip feeding the debut step's compile-inflated wall
        time to the MFU accountant."""
        from deeplearning4j_tpu.monitor import xla as xla_ledger
        pending, self._ledger_pending = self._ledger_pending, None
        if pending is None:
            return
        cache, key, name, fn, args, bs = pending
        self._ledger_fresh = key not in cache
        self._ledger_rec = xla_ledger.capture_cached(
            cache, key, name, fn, args, examples_per_call=bs)

    def prepare(self):
        from deeplearning4j_tpu.util import params as param_util
        if self.net.params is None:
            self.net.init()
        # donated-buffer safety for the initial state too (a model fresh
        # from keras/dl4j import may hold numpy-aliased leaves). With a
        # process-wide GSPMD plan active (parallel/plan.use_mesh), the
        # laundering is sharding-aware: the owned copies land on the
        # plan placements and the net's compiled step compiles the
        # plan's collectives — the same zero-code-change pickup fit()
        # has.
        if self._uses_plan:
            from deeplearning4j_tpu.nn.fit_loop import _engage_plan_impl
            from deeplearning4j_tpu.parallel.plan import active_plan
            _engage_plan_impl(self.net, active_plan())
        else:
            self.net.params = param_util.own_tree(self.net.params)
            self.net.state = param_util.own_tree(self.net.state)
            self.net.opt_state = param_util.own_tree(self.net.opt_state)
        if getattr(self.net.conf, "backprop_type", None) == "tbptt":
            raise NotImplementedError(
                "ResilientTrainer does not support tbptt fits yet (chunk "
                "carries would have to be checkpointed mid-batch)")

    def finish(self):
        pass

    def plan_describe(self):
        """JSON descriptor of the active sharding plan (checkpoint
        extras), or None."""
        plan = getattr(self.net, "_plan", None)
        return None if plan is None else plan.describe()

    def post_restore(self):
        """Called after a checkpoint was restored into the net (the
        restored arrays live unsharded on the default device). Under an
        active plan, re-launder them onto the plan placements — the
        PR-3 own_tree contract, now sharding-aware — so a resumed step
        never donates misplaced (or heap-aliased) restored leaves."""
        if self._uses_plan and getattr(self.net, "_plan", None) is not None:
            from deeplearning4j_tpu.nn.fit_loop import _engage_plan_impl
            _engage_plan_impl(self.net, self.net._plan)

    def make_source(self, data, batch_size):
        return self.net._as_iterator(data, batch_size)

    def batches(self, source):
        return iter(source)

    @staticmethod
    def reset(source):
        if hasattr(source, "reset"):
            source.reset()

    def epoch_key(self, epoch: int):
        return jax.random.PRNGKey(self.net.conf.seed
                                  + self.rng_mult * (epoch + 1))

    def snapshot(self):
        n = self.net
        return (_host_copy(n.params), _host_copy(n.opt_state),
                _host_copy(n.state))

    def restore(self, snap):
        from deeplearning4j_tpu.util.params import own_tree
        n = self.net
        # owned copies, NOT jnp.asarray: the snapshot's numpy buffers must
        # survive the restored params being donated into the retried step
        n.params = own_tree(snap[0])
        n.opt_state = own_tree(snap[1])
        n.state = own_tree(snap[2])

    def step(self, batch, sub):
        from deeplearning4j_tpu.monitor import xla as xla_ledger
        from deeplearning4j_tpu.nn.fit_loop import compiled_step
        n = self.net
        fn = compiled_step(n, "step")
        # staged and, under a GSPMD plan, sharded over the mesh "data"
        # axis exactly like fit()'s per-call path
        operands = n._operands(batch)
        n.params, n.opt_state, n.state, loss, _ = fn(
            n.params, n.opt_state, n.state, *operands, sub, None)
        bs = n._batch_examples(batch)
        if xla_ledger.enabled():
            self._ledger_pending = (
                n._ledger_cache,
                (id(fn), xla_ledger.shape_key(operands)),
                self.ledger_program, fn,
                (n.params, n.opt_state, n.state, *operands, sub, None), bs)
        return loss, bs


class _GraphDriver(_NetDriver):
    """ComputationGraph per-call step (the same step; per-epoch RNG
    reseed for resumability)."""

    rng_mult = 331

    ledger_program = "graph/train_step"

    def make_source(self, data, batch_size):
        return data

    def batches(self, source):
        return self.net._iter_data(source)


class _WrapperDriver(_NetDriver):
    """ParallelWrapper SYNC_GRADIENTS step: the wrapper's compiled
    all-reduce step with its mesh-sharded batch placement."""

    rng_mult = 65537

    _uses_plan = False      # the wrapper manages its own plan/placement

    def __init__(self, wrapper):
        from deeplearning4j_tpu.parallel.wrapper import TrainingMode
        if wrapper.mode != TrainingMode.SYNC_GRADIENTS:
            raise NotImplementedError(
                "ResilientTrainer drives ParallelWrapper in SYNC_GRADIENTS "
                "mode only (AVERAGING keeps per-worker replica state that "
                "is not checkpointable step-by-step yet)")
        super().__init__(wrapper.model)
        self.wrapper = wrapper

    def prepare(self):
        super().prepare()
        w = self.wrapper
        if w._step_fn is None:
            w._step_fn = w._build_sync_step()
        if w._needs_placement():
            w._zero_place()
        from jax.sharding import NamedSharding, PartitionSpec as P
        from deeplearning4j_tpu.parallel.mesh import DATA_AXIS
        self._shard = NamedSharding(w.mesh, P(DATA_AXIS))

    def finish(self):
        if self.wrapper.zero_stage == 3:
            self.wrapper._zero_gather()

    def plan_describe(self):
        return self.wrapper.plan.describe()

    def post_restore(self):
        # restore_into left unsharded default-device arrays; re-establish
        # the plan layout or a stage-3/TP resume would run unsharded
        # (OOM on models that only fit sharded)
        if self.wrapper._needs_placement():
            self.wrapper._zero_place()

    def make_source(self, data, batch_size):
        if self.wrapper._is_graph:
            return data
        from deeplearning4j_tpu.data.iterator import DataSetIterator
        return data if isinstance(data, DataSetIterator) \
            else self.net._as_iterator(data, batch_size)

    def batches(self, source):
        return self.wrapper._batches(source)

    def step(self, batch, sub):
        w, n = self.wrapper, self.net
        x, y, fm, lm = batch
        bs = w._batch_count(x)
        x, y, fm, lm = w._device_batch(x, y, fm, lm, self._shard)
        n.params, n.opt_state, n.state, loss = w._step_fn(
            n.params, n.opt_state, n.state, x, y, fm, lm, sub)
        return loss, bs


# ------------------------------------------------------------------- trainer
class ResilientTrainer:
    """Fault-tolerant fit loop around MultiLayerNetwork / ComputationGraph
    / ParallelWrapper(SYNC_GRADIENTS).

    Usage:
        trainer = ResilientTrainer(net, "/ckpts", save_every_n_iterations=50)
        report = trainer.fit(iterator, epochs=10)     # auto-resumes

    `epochs` is the TOTAL target (unlike net.fit's "additional epochs"):
    a resumed run passes the same value and trains only the remainder.
    The trained model lives on the wrapped network; `fit` returns a
    FitReport describing what happened (resume source, skips, retries,
    preemption).

    Multi-host: only the coordinator process writes checkpoints (every
    process restores), override with `write_checkpoints=`.
    """

    def __init__(self, model, checkpoint_dir: str,
                 save_every_n_iterations: int = 50,
                 save_every_n_epochs: int = 1,
                 keep_last: int = 3,
                 policy: Optional[FaultPolicy] = None,
                 injector: Optional[FaultInjector] = None,
                 normalizer=None,
                 resume: bool = True,
                 write_checkpoints: Optional[bool] = None,
                 eval_gate=None):
        from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
        if isinstance(model, ParallelWrapper):
            self._driver = _WrapperDriver(model)
        else:
            from deeplearning4j_tpu.nn.graph import ComputationGraph
            self._driver = _GraphDriver(model) \
                if isinstance(model, ComputationGraph) else _NetDriver(model)
        self.net = self._driver.net
        self.ckpt = CheckpointManager(checkpoint_dir, keep_last=keep_last)
        self.save_every_n_iterations = save_every_n_iterations
        self.save_every_n_epochs = save_every_n_epochs
        self.policy = policy or FaultPolicy()
        self.injector = injector if injector is not None \
            else FaultInjector.from_env()
        self.normalizer = normalizer
        self.resume = resume
        self.write_checkpoints = write_checkpoints
        # eval gate for continuous rollout: called after every checkpoint
        # save with the live network; return a metrics dict to bless the
        # checkpoint (CheckpointManager.bless -> blessed.json, which
        # serving/rollout.py tails) or None to withhold it from serving
        self.eval_gate = eval_gate
        self._jitter = random.Random(self.policy.seed)
        self._rng = None
        self._dispatch_idx = 0          # batches consumed, fit-global
        self._consecutive_skips = 0

    # ------------------------------------------------------------- plumbing
    def _writes_enabled(self) -> bool:
        if self.write_checkpoints is not None:
            return self.write_checkpoints
        try:
            from deeplearning4j_tpu.parallel.distributed import is_coordinator
            return is_coordinator()
        except Exception:
            return True

    def _normalizer_extra(self) -> Optional[dict]:
        nz = self.normalizer
        if nz is None or not hasattr(nz, "save"):
            return None
        import tempfile
        fd, path = tempfile.mkstemp(suffix=".json")
        os.close(fd)
        try:
            nz.save(path)
            with open(path) as f:
                return json.load(f)
        except Exception as e:          # unfitted normalizer etc.
            log.warning("normalizer not checkpointed: %s", e)
            return None
        finally:
            try:
                os.remove(path)
            except OSError:
                pass

    def _restore_normalizer(self, blob: dict):
        from deeplearning4j_tpu.data import normalization
        kind = blob.get("kind")
        cls = getattr(normalization, kind, None)
        if cls is None or not hasattr(cls, "restore"):
            log.warning("checkpoint normalizer kind %r unknown; ignored",
                        kind)
            return None
        import tempfile
        with tempfile.NamedTemporaryFile("w", suffix=".json",
                                         delete=False) as tf:
            json.dump(blob, tf)
            path = tf.name
        try:
            return cls.restore(path)
        finally:
            try:
                os.remove(path)
            except OSError:
                pass

    def _save(self, report: FitReport, step_in_epoch: int):
        if not self._writes_enabled():
            return None
        extra = {
            "rng": np.asarray(self._rng).tolist(),
            "step_in_epoch": int(step_in_epoch),
            "dispatch_idx": int(self._dispatch_idx),
        }
        plan_desc = self._driver.plan_describe()
        if plan_desc is not None:
            # bank the GSPMD plan the run trained under, so a resume
            # onto a different mesh/zero_stage is detected and logged —
            # never silently misplaced (placements are re-derived by
            # post_restore either way)
            extra["plan"] = plan_desc
        src = getattr(self, "_source", None)
        src = src() if src is not None else None
        if src is not None and hasattr(src, "stream_state"):
            # the exact shard file/offset the next batch starts at —
            # step_in_epoch implies it (deterministic epoch order), but
            # the explicit position makes checkpoints auditable and
            # resumable by offset without replaying the order rule
            try:
                extra["stream"] = src.stream_state()
            except Exception:
                # checkpoint still lands (position replay covers resume)
                # but the missing offset must be visible in the log, not
                # silently absent from an "auditable" manifest
                log.warning("checkpoint: stream_state() unavailable — "
                            "banking position-replay resume only",
                            exc_info=True)
        if self.net._score is not None:
            extra["score"] = float(self.net._score)
        nz = self._normalizer_extra()
        if nz is not None:
            extra["normalizer"] = nz
        t0 = time.perf_counter()
        with monitor.span("resilience/checkpoint_save",
                          iteration=self.net.iteration_count):
            path = self.ckpt.save(self.net, extra)
        monitor.histogram("resilience_checkpoint_save_seconds",
                          "Checkpoint zip write + hash + manifest update"
                          ).observe(time.perf_counter() - t0)
        monitor.counter("resilience_checkpoints_written_total",
                        "Checkpoints written by ResilientTrainer").inc()
        report.checkpoints_written += 1
        log.info("checkpoint written: %s (iteration %d, epoch %d, step %d)",
                 path, self.net.iteration_count, self.net.epoch_count,
                 step_in_epoch)
        if self.eval_gate is not None:
            try:
                with monitor.span("resilience/eval_gate",
                                  iteration=self.net.iteration_count):
                    metrics = self.eval_gate(self.net)
            except Exception:           # noqa: BLE001 — a broken eval gate
                # must not kill training; it only withholds the blessing,
                # and loudly: an unblessed stream starves the rollout
                log.warning("eval gate raised; checkpoint NOT blessed",
                            exc_info=True)
                metrics = None
            if metrics is not None:
                if not isinstance(metrics, dict):
                    metrics = {"score": float(metrics)}
                self.ckpt.bless(path, metrics)
                report.checkpoints_blessed += 1
        return path

    # ------------------------------------------------------------ stepping
    def _run_step(self, batch, sub, step_idx: int, report: FitReport):
        """One guarded optimizer step. Returns (status, loss, batch_size)
        with status in {"applied", "skipped"}; raises _Unrecoverable when
        the consecutive-skip threshold trips."""
        from deeplearning4j_tpu.nn.fit_loop import score_of
        policy = self.policy
        snap = self._driver.snapshot() if policy.guards_steps else None
        attempt = 0
        while True:
            # per-attempt clock: train_step_seconds and the train/step
            # span must time ONLY the attempt that landed — backoff
            # sleeps and failed attempts would otherwise make retried
            # steps read as slow compute
            attempt_start = time.perf_counter()
            try:
                if self.injector is not None:
                    self.injector.before_step(step_idx)
                loss, bs = self._driver.step(batch, sub)
                wait_start = time.perf_counter()
                # block for device completion FIRST (goodput:
                # step_compute; banks per-shard barrier wait under a
                # plan), so host_sync covers only the narrow D2H fetch
                goodput.device_wait(loss)
                fetch_start = time.perf_counter()
                monitor.add_span("train/device_wait", wait_start,
                                 fetch_start)
                loss_f = score_of(self._driver.net, loss)
                step_end = time.perf_counter()
                step_secs = step_end - attempt_start
                monitor.add_span("train/host_sync", fetch_start, step_end)
                monitor.add_span("train/step", attempt_start,
                                 step_end, step=step_idx)
                # capture AFTER the attempt clock stops: the first sight
                # of a program pays an AOT lower+compile that must not
                # read as compute time
                self._driver.capture_ledger()
                break
            except policy.transient_errors as e:
                attempt += 1
                monitor.counter("resilience_retries_total",
                                "Transient-error step retries").inc()
                monitor.add_span("resilience/step_retry", attempt_start,
                                 time.perf_counter(), step=step_idx,
                                 attempt=attempt, error=str(e))
                if snap is not None:
                    self._driver.restore(snap)
                if attempt > policy.max_retries:
                    log.error("step %d failed after %d retries: %s — "
                              "checkpointing last good state and raising",
                              step_idx, policy.max_retries, e)
                    raise
                delay = min(policy.backoff_base * (2 ** (attempt - 1)),
                            policy.backoff_max)
                delay *= 0.5 + self._jitter.random()     # jitter in [.5, 1.5)
                log.warning("transient error at step %d (attempt %d/%d): "
                            "%s — retrying in %.3fs", step_idx, attempt,
                            policy.max_retries, e, delay)
                report.retries += 1
                time.sleep(delay)
        if self.injector is not None:
            loss_f = self.injector.corrupt_loss(step_idx, loss_f)
        if not math.isfinite(loss_f) and policy.skip_nonfinite:
            if snap is not None:
                self._driver.restore(snap)
            self._consecutive_skips += 1
            report.skipped_steps += 1
            monitor.counter("resilience_steps_skipped_total",
                            "Steps skipped on non-finite loss").inc()
            monitor.instant("resilience/nan_skip", step=step_idx)
            log.warning("non-finite loss %s at step %d: skipping batch "
                        "(%d consecutive skips, threshold %d)", loss_f,
                        step_idx, self._consecutive_skips,
                        policy.max_consecutive_skips)
            if self._consecutive_skips > policy.max_consecutive_skips:
                raise _Unrecoverable(
                    f"{self._consecutive_skips} consecutive non-finite "
                    f"steps (threshold {policy.max_consecutive_skips}) "
                    f"at step {step_idx}")
            return "skipped", loss_f, bs
        self._consecutive_skips = 0
        from deeplearning4j_tpu.nn.fit_loop import _record_iteration
        from deeplearning4j_tpu.monitor import xla as xla_ledger
        _record_iteration(loss_f, bs, step_seconds=step_secs)
        if xla_ledger.enabled() and not self._driver._ledger_fresh:
            # feed the MFU accountant the attempt-that-landed wall time
            # against the program the driver captured for this step; the
            # debut step (fresh capture) is skipped — its wall time
            # includes the jit compile
            xla_ledger.observe_step(self._driver._ledger_rec, step_secs)
        return "applied", loss_f, bs

    # ------------------------------------------------------------------ fit
    def fit(self, data, epochs: int = 1, batch_size: int = 32) -> FitReport:
        report = FitReport()
        # the goodput session owns the WHOLE resilient fit wall-clock —
        # prepare, restore, replay, every epoch, the final save — so the
        # report's categories sum to what an outside stopwatch measures
        # (the exclusivity contract telemetry_smoke enforces)
        gp_session = goodput.fit_begin("resilient/fit")
        try:
            return self._fit_guarded(data, epochs, batch_size, report)
        finally:
            gp = goodput.fit_end(gp_session)
            if gp is not None:
                report.goodput_pct = gp["goodput_pct"]
                report.time_by_category = gp["categories"]

    def _fit_guarded(self, data, epochs: int, batch_size: int,
                     report: FitReport) -> FitReport:
        net = self.net
        policy = self.policy
        self._driver.prepare()

        # -------- auto-resume from the newest valid checkpoint
        step_in_epoch = 0
        resumed_mid_epoch = False
        if self.resume:
            entry = self.ckpt.latest_valid()
            if entry is not None:
                t0 = time.perf_counter()
                with monitor.span("resilience/checkpoint_restore",
                                  path=entry["path"]):
                    extra = self.ckpt.restore_into(net, entry["path"])
                monitor.histogram("resilience_checkpoint_restore_seconds",
                                  "Checkpoint verify + load into the model"
                                  ).observe(time.perf_counter() - t0)
                monitor.counter("resilience_resumes_total",
                                "Auto-resumes from a checkpoint").inc()
                report.resumed_from = entry["path"]
                step_in_epoch = int(extra.get("step_in_epoch", 0))
                self._dispatch_idx = int(extra.get("dispatch_idx", 0))
                if step_in_epoch > 0 and "rng" in extra:
                    self._rng = jnp.asarray(
                        np.asarray(extra["rng"], dtype=np.uint32))
                    resumed_mid_epoch = True
                if "score" in extra:
                    net._score = float(extra["score"])
                if "normalizer" in extra and self.normalizer is None:
                    self.normalizer = self._restore_normalizer(
                        extra["normalizer"])
                live_plan = self._driver.plan_describe()
                if extra.get("plan") != live_plan:
                    # resuming onto a different mesh layout is SUPPORTED
                    # (checkpoints store whole host arrays; post_restore
                    # re-launders them onto the live plan's placements)
                    # but must be loud — a silent layout change is how
                    # misplaced-restore bugs ship
                    log.warning(
                        "resuming onto a different sharding plan: "
                        "checkpoint trained under %s, live plan is %s — "
                        "placements re-derived from the live plan",
                        extra.get("plan"), live_plan)
                self._driver.post_restore()
                log.info("resumed from %s (iteration %d, epoch %d, "
                         "step-in-epoch %d)", entry["path"],
                         net.iteration_count, net.epoch_count, step_in_epoch)

        source = self._driver.make_source(data, batch_size)
        # weakly held: _save banks the seekable stream position while the
        # local `source` keeps it alive for the fit; a strong ref would
        # pin a multi-process ETL pipeline (workers + shared-memory ring)
        # to the trainer's lifetime after fit() returns
        try:
            self._source = weakref.ref(source)
        except TypeError:
            self._source = None     # plain list/array: no stream_state
        if self.normalizer is not None \
                and getattr(source, "pre_processor", False) is None \
                and hasattr(source, "set_pre_processor"):
            source.set_pre_processor(self.normalizer)

        if any(getattr(lst, "wants_gradients", False)
               for lst in net.listeners):
            log.warning("gradient-capturing listeners (wants_gradients) are "
                        "not fed by the resilient fit loop — gradient/update "
                        "capture will be empty; use the plain fit() for "
                        "capture runs")

        div_guard = None
        if policy.explosion_factor:
            def _diverged(model, iteration, msg):
                raise TrainingDivergedError(msg)
            div_guard = DivergenceListener(
                explosion_factor=policy.explosion_factor,
                window=policy.explosion_window, on_divergence=_diverged)

        steps_since_save = 0
        rng_at_step_start = None    # pre-split carry of the in-flight step
        with PreemptionGuard() as guard, \
                monitor.span("resilience/fit", epochs=epochs):
            # the uninterrupted run resets the source once per completed
            # epoch — replay those resets so epoch-dependent shuffles
            # match. A LIVE streaming source re-fit in the same process
            # (preempt -> fit again on the same pipeline) already
            # consumed its in-fit resets; stream_state names its current
            # epoch, so replay only the difference — blind replay would
            # double-advance the shuffle permutation the seek below
            # resumes into.
            src_epoch = 0
            state_fn = getattr(source, "stream_state", None)
            if callable(state_fn):
                src_epoch = int(state_fn().get("epoch") or 0)
            for _ in range(max(0, net.epoch_count - src_epoch)):
                self._driver.reset(source)
            try:
                while net.epoch_count < epochs:
                    epoch = net.epoch_count
                    if not resumed_mid_epoch:
                        self._rng = self._driver.epoch_key(epoch)
                        step_in_epoch = 0
                        for lst in net.listeners:
                            lst.on_epoch_start(net, epoch)
                    resumed_mid_epoch = False
                    consumed = 0
                    if step_in_epoch > 0 \
                            and getattr(source, "supports_seek", False):
                        # streaming sources (ShardDataSetIterator) land on
                        # the exact next shard offset instead of replaying
                        # — decoding the whole stream prefix just to throw
                        # it away is the resume tax this skips
                        seek_start = time.perf_counter()
                        source.seek(step_in_epoch)
                        consumed = step_in_epoch
                        monitor.add_span("train/resume_replay", seek_start,
                                         time.perf_counter(),
                                         seeked=step_in_epoch)
                        if hasattr(source, "stream_state"):
                            log.info("resume: seeked stream to %s",
                                     source.stream_state())
                    it = self._driver.batches(source)
                    while True:
                        if guard.requested or (
                                self.injector is not None
                                and self.injector.should_preempt(
                                    self._dispatch_idx)):
                            self._save(report, step_in_epoch)
                            report.preempted = True
                            report.final_score = net._score
                            monitor.counter(
                                "resilience_preemptions_total",
                                "Preemption-triggered clean stops").inc()
                            monitor.instant("resilience/preempted",
                                            iteration=net.iteration_count)
                            log.warning("preempted: checkpointed at "
                                        "iteration %d; re-run to resume",
                                        net.iteration_count)
                            return report
                        etl_start = time.perf_counter()
                        if self.injector is not None:
                            # inside the ETL window: an injected stall
                            # must read as data_wait, like a real one
                            self.injector.before_fetch(self._dispatch_idx)
                        try:
                            batch = next(it)
                        except StopIteration:
                            break
                        etl_end = time.perf_counter()
                        if consumed < step_in_epoch:    # resume fast-forward
                            consumed += 1
                            # replayed batches are resume overhead, not
                            # data_wait: the goodput ledger bills them to
                            # resume_replay
                            monitor.add_span("train/resume_replay",
                                             etl_start, etl_end,
                                             step=consumed)
                            continue
                        consumed += 1
                        etl_ms = (etl_end - etl_start) * 1e3
                        monitor.add_span("train/etl", etl_start, etl_end,
                                         step=self._dispatch_idx)
                        rng_at_step_start = self._rng
                        self._rng, sub = jax.random.split(self._rng)
                        step_idx = self._dispatch_idx
                        self._dispatch_idx += 1
                        status, loss_f, bs = self._run_step(
                            batch, sub, step_idx, report)
                        rng_at_step_start = None    # step landed: no rewind
                        step_in_epoch = consumed
                        if status == "skipped":
                            continue
                        net._score = loss_f
                        report.applied_steps += 1
                        for lst in net.listeners:
                            lst.iteration_done(net, net.iteration_count,
                                               epoch, loss_f, etl_ms, bs)
                        if div_guard is not None:
                            div_guard.iteration_done(net,
                                                     net.iteration_count,
                                                     epoch, loss_f, 0.0, bs)
                        net.iteration_count += 1
                        steps_since_save += 1
                        if self.save_every_n_iterations and \
                                steps_since_save >= \
                                self.save_every_n_iterations:
                            self._save(report, step_in_epoch)
                            steps_since_save = 0
                    for lst in net.listeners:
                        lst.on_epoch_end(net, epoch)
                    net.epoch_count += 1
                    self._driver.reset(source)
                    step_in_epoch = 0
                    if self.save_every_n_epochs and \
                            net.epoch_count % self.save_every_n_epochs == 0 \
                            and net.epoch_count < epochs:
                        self._rng = self._driver.epoch_key(net.epoch_count)
                        self._save(report, 0)
                        steps_since_save = 0
            except (_Unrecoverable, TrainingDivergedError) as e:
                return self._handle_unrecoverable(report, str(e))
            except policy.transient_errors:
                # retries exhausted: state is at the last good step —
                # checkpoint it so the operator can resume, then surface
                # the original error (a failing emergency save must not
                # mask it). The RNG carry was already split for the failed
                # step while step_in_epoch was not advanced — rewind it so
                # the resumed run re-derives the SAME subkey for that step
                # (bitwise resume parity holds across the failure)
                if rng_at_step_start is not None:
                    self._rng = rng_at_step_start
                    self._dispatch_idx = max(0, self._dispatch_idx - 1)
                try:
                    self._save(report, step_in_epoch)
                except Exception as save_err:
                    log.error("emergency checkpoint failed: %s", save_err)
                raise
            self._driver.finish()
            # final checkpoint: a re-run of the same command sees
            # epoch_count == epochs and returns without retraining. A
            # no-op rerun (resumed, nothing trained) must NOT save again —
            # duplicate finals would rotate real history out of keep_last.
            if report.applied_steps > 0 or report.resumed_from is None:
                self._rng = self._driver.epoch_key(net.epoch_count)
                self._save(report, 0)
        report.final_score = net._score
        return report

    def _handle_unrecoverable(self, report: FitReport, reason: str):
        """Graceful degradation: restore the newest good checkpoint so the
        model is left usable, then stop (or raise, per policy)."""
        report.diverged = True
        monitor.counter("resilience_divergence_rollbacks_total",
                        "Unrecoverable divergences rolled back to the "
                        "last good checkpoint").inc()
        entry = self.ckpt.latest_valid()
        if entry is not None:
            self.ckpt.restore_into(self.net, entry["path"])
            self._driver.post_restore()
            report.restored_checkpoint = entry["path"]
            log.error("unrecoverable divergence (%s); restored last good "
                      "checkpoint %s", reason, entry["path"])
        else:
            log.error("unrecoverable divergence (%s) and no valid "
                      "checkpoint to restore", reason)
        report.final_score = self.net._score
        if self.policy.on_unrecoverable == "raise":
            raise TrainingDivergedError(
                f"{reason}; model restored to "
                f"{entry['path'] if entry else 'initial state'}")
        return report
