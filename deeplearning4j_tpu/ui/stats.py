"""StatsListener — per-iteration training stats capture.

Parity: DL4J `deeplearning4j-ui-model/.../stats/BaseStatsListener.java:229-304`
(iterationDone: score, timing, memory, parameter/gradient/update histograms
and mean magnitudes, hooked via onGradientCalculation/onBackwardPass) plus
the static-info record (session start, model info, hardware).

TPU-native design: gradients/updates come from a dedicated jit variant of
the train step that returns the raw pytrees only on capture iterations
(nn/fit_loop.build_step with_stats=True) — the fast path
transfers nothing extra. Histograms/norms are computed host-side from the
fetched arrays; device memory comes from jax's per-device memory_stats().
"""
from __future__ import annotations

import json
import time
from typing import Dict, List, Optional

import numpy as np

from deeplearning4j_tpu.train.listeners import TrainingListener
from deeplearning4j_tpu.ui.storage import (
    StatsRecord, StatsStorageRouter, new_session_id,
)

TYPE_ID = "StatsListener"        # DL4J uses the listener class name


def _leaf_paths(tree, prefix="") -> Dict[str, np.ndarray]:
    """Flatten a {layer: {param: array}} pytree into {"0/W": array} paths."""
    out = {}
    if isinstance(tree, dict):
        for k in sorted(tree):
            out.update(_leaf_paths(tree[k], f"{prefix}{k}/"))
    elif tree is not None:
        arr = np.asarray(tree)
        if arr.size:
            out[prefix[:-1]] = arr
    return out


def _summarize(arrays: Dict[str, np.ndarray], n_bins: int,
               histograms: bool) -> Dict[str, dict]:
    summary = {}
    for path, a in arrays.items():
        a = a.astype("float64", copy=False).ravel()
        finite = a[np.isfinite(a)]
        entry = {
            "norm": float(np.linalg.norm(finite)),
            "mean_mag": float(np.abs(finite).mean()) if finite.size else 0.0,
            "n_non_finite": int(a.size - finite.size),
        }
        if histograms:
            # histogram over finite values only — a diverged run (NaN/Inf
            # grads) must not crash the fit loop; surfacing n_non_finite is
            # exactly what the dashboard needs at that moment
            if finite.size:
                lo, hi = float(finite.min()), float(finite.max())
                if lo == hi:
                    hi = lo + 1e-12
                counts, _ = np.histogram(finite, bins=n_bins,
                                         range=(lo, hi))
            else:
                lo, hi = 0.0, 0.0
                counts = np.zeros(n_bins, dtype=int)
            entry["hist"] = counts.tolist()
            entry["lo"], entry["hi"] = lo, hi
        summary[path] = entry
    return summary


def _device_memory() -> dict:
    mem = {}
    try:
        import jax
        stats = jax.local_devices()[0].memory_stats()
        if stats:
            mem["device_bytes_in_use"] = int(stats.get("bytes_in_use", 0))
            mem["device_bytes_limit"] = int(stats.get("bytes_limit", 0))
    except Exception:
        pass
    try:
        import resource
        mem["host_max_rss_kb"] = int(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss)
    except Exception:
        pass
    return mem


class StatsListener(TrainingListener):
    """Captures score/timing/memory/param/grad/update stats into a
    StatsStorageRouter every `frequency` iterations.

    Usage (mirrors the reference's UIServer quickstart):
        storage = InMemoryStatsStorage()
        UIServer.get_instance().attach(storage)
        net.set_listeners(StatsListener(storage))
    """

    wants_gradients = True       # ask fit() for the stats train-step variant

    def __init__(self, router: StatsStorageRouter, frequency: int = 1,
                 session_id: Optional[str] = None,
                 worker_id: str = "worker-0", histograms: bool = True,
                 n_bins: int = 20):
        self.router = router
        self.frequency = max(int(frequency), 1)
        self.session_id = session_id or new_session_id()
        self.worker_id = worker_id
        self.histograms = histograms
        self.n_bins = int(n_bins)
        self._static_sent = False
        self._last_time: Optional[float] = None
        self._pending: Optional[dict] = None

    # -------------------------------------------------------------- hooks
    def should_capture(self, iteration: int) -> bool:
        return iteration % self.frequency == 0

    def on_gradients(self, model, iteration, epoch, grads, updates):
        """Receives the raw grad/update pytrees on capture iterations."""
        self._pending = {
            "gradients": _summarize(_leaf_paths(grads), self.n_bins,
                                    self.histograms),
            "updates": _summarize(_leaf_paths(updates), self.n_bins,
                                  self.histograms),
        }

    def iteration_done(self, model, iteration, epoch, score, etl_ms=0.0,
                       batch_size=0):
        if not self._static_sent:
            self._send_static(model)
        now = time.perf_counter()
        iter_ms = (now - self._last_time) * 1e3 if self._last_time else 0.0
        self._last_time = now
        if not self.should_capture(iteration):
            self._pending = None
            return
        data = {
            "iteration": int(iteration),
            "epoch": int(epoch),
            "score": float(score),
            "iter_ms": iter_ms,
            "etl_ms": float(etl_ms),
            "batch_size": int(batch_size),
            "samples_sec": (batch_size / (iter_ms / 1e3)
                            if iter_ms > 0 else 0.0),
            "memory": _device_memory(),
            "params": _summarize(_leaf_paths(model.params), self.n_bins,
                                 self.histograms),
        }
        if self._pending is not None:
            data.update(self._pending)
            self._pending = None
        self.router.put_update(StatsRecord(
            session_id=self.session_id, type_id=TYPE_ID,
            worker_id=self.worker_id, timestamp=time.time(), data=data))

    # ------------------------------------------------------------- static
    def _send_static(self, model):
        self._static_sent = True
        try:
            import jax
            devices = [f"{d.platform}:{d.id}" for d in jax.local_devices()]
        except Exception:
            devices = []
        layers: List[str] = [type(l).__name__
                             for l in getattr(model, "layers", [])]
        # per-layer drill-down table (TrainModule model tab): name, type,
        # param count and shapes, keyed the same way the update records
        # key their params ("0/W", "conv1/b", ...)
        params = getattr(model, "params", None) or {}
        detail = []
        if layers:
            named = [(str(i), type(l).__name__)
                     for i, l in enumerate(getattr(model, "layers", []))]
        else:       # ComputationGraph: vertices in topological order
            conf = getattr(model, "conf", None)
            vertices = getattr(conf, "vertices", {}) or {}
            named = [(name, type(vd.vertex).__name__)
                     for name, vd in vertices.items()]
        for key, ltype in named:
            # _leaf_paths handles nested trees (Bidirectional fwd/bwd etc.)
            # with the same path keys the update records use
            leaves = _leaf_paths(params.get(key, {}) or {})
            detail.append({
                "name": key,
                "type": ltype,
                "n_params": int(sum(a.size for a in leaves.values())),
                "shapes": {k: list(a.shape) for k, a in leaves.items()},
            })
        info = {
            "start_time": time.time(),
            "model_class": type(model).__name__,
            "num_params": int(model.num_params()),
            "num_layers": len(detail) if detail else len(layers),
            "layer_names": layers,
            "layers": detail,
            "devices": devices,
        }
        try:
            info["config_json"] = model.conf.to_json()
        except Exception:
            info["config_json"] = json.dumps({"error": "unserializable"})
        try:                      # layer table for the dashboard info card
            info["summary"] = model.summary()
        except Exception:
            pass
        self.router.put_static_info(StatsRecord(
            session_id=self.session_id, type_id=TYPE_ID,
            worker_id=self.worker_id, timestamp=time.time(), data=info))
