"""Asynchronous prefetching iterator.

Parity with DL4J AsyncDataSetIterator
(deeplearning4j-data/deeplearning4j-utility-iterators/.../AsyncDataSetIterator.java),
which every fit() wraps by default (MultiLayerNetwork.java:1272-1274): a
background thread pulls batches from the source iterator into a bounded queue
so host ETL overlaps device compute. On TPU this additionally starts the
host->HBM transfer (jax.device_put) from the worker thread, so the next
batch's DMA overlaps the current step — the role DL4J's device-aware
buffering plays for CUDA. The default depth of 2 is DOUBLE BUFFERING:
batch i+1 is staged (cast + device_put) while batch i computes.

Environment knobs of the default data plane — the one reference list
(mirrored in docs/DATA_PIPELINE.md); every switch follows the same
``=="0"``-disables kill-switch contract:

- ``DL4J_TPU_PREFETCH_DEPTH``: device-prefetch queue depth for the
  default fit() wrap and prefetch_iterable (default 2 =
  double-buffered); ``0`` disables the background thread entirely
  (batches are staged synchronously — placement contract still holds).
- ``DL4J_TPU_FIT_PREFETCH``: ``0`` skips the fit() async wrap
  altogether (the legacy switch; prefer PREFETCH_DEPTH=0).
- ``DL4J_TPU_HOST_CAST``: ``0`` restores transfer-then-cast for 16-bit
  compute dtypes (see `host_cast`).
- ``DL4J_TPU_DEVICE_NORM``: ``0`` keeps normalization on host instead
  of the on-device affine + raw-uint8-over-the-wire path
  (data/normalization.engaged_device_affine).
- ``DL4J_TPU_ETL_WORKERS`` / ``DL4J_TPU_ETL_RING_SLOTS`` /
  ``DL4J_TPU_ETL_MP_START``: the multi-process shared-memory ETL ring
  (data/pipeline.py); ``DL4J_TPU_ETL_WORKERS=0`` disables.
"""
from __future__ import annotations

import itertools
import queue
import threading
import time
from typing import Optional

import jax
import numpy as np

from deeplearning4j_tpu.util.env import env_flag, env_int

from deeplearning4j_tpu.data.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.data.iterator import DataSetIterator

_SENTINEL = object()
#: monotonically numbered prefetch workers: the trace viewer needs a
#: STABLE per-worker track name, not Python's default "Thread-N"
_prefetch_seq = itertools.count()


def prefetch_depth(default: int = 2) -> int:
    """Resolve DL4J_TPU_PREFETCH_DEPTH (default 2: double-buffered).
    0 disables prefetching — the same kill-switch contract as
    DL4J_TPU_HOST_CAST / DL4J_TPU_DEVICE_NORM (module docstring)."""
    return max(0, env_int("DL4J_TPU_PREFETCH_DEPTH", default))


def fit_prefetch_enabled() -> bool:
    """DL4J_TPU_FIT_PREFETCH resolved under the one kill-switch contract
    of the module docstring: ONLY ``"0"`` disables; unset/empty/anything
    else leaves the default fit() async wrap on. The single rule for
    both fit gates (nn/multilayer.py, nn/graph.py)."""
    return env_flag("DL4J_TPU_FIT_PREFETCH")


def host_cast(a, dtype):
    """Cast a float32 host array to a 16-bit compute dtype BEFORE the
    device transfer: ml_dtypes' round-to-nearest-even matches XLA's device
    cast bit-for-bit, and the H2D copy ships half the bytes (the single
    shared implementation of the rule — nn/fit_loop._as_jnp and the
    prefetch workers both route through here). DL4J_TPU_HOST_CAST=0
    restores the transfer-then-cast path."""
    if (dtype is not None and isinstance(a, np.ndarray)
            and a.dtype == np.float32
            and np.dtype(dtype).itemsize == 2
            and env_flag("DL4J_TPU_HOST_CAST")):
        return a.astype(dtype)
    return a


def prefetch_iterable(source, transform=None, queue_size: Optional[int] = None):
    """Generic bounded background-thread pump: pull items from `source`,
    apply `transform` on the worker thread (host cast + async device_put
    live there), yield in order. The device-side analog of DL4J's
    prefetch buffer for arbitrary item types (the graph container's
    MultiDataSet stream uses this; DataSet streams use
    AsyncDataSetIterator).

    `queue_size` defaults to DL4J_TPU_PREFETCH_DEPTH (2 =
    double-buffered); 0 degrades to a synchronous generator that still
    applies `transform` per item, so the device-placement contract holds
    with the background thread disabled.

    Telemetry (monitor/): `etl_queue_depth` tracks the prefetch buffer
    fill, `etl_fetch_wait_seconds` how long the consumer (the train
    loop) blocked on it — a consistently empty queue + large waits means
    the fit is ETL-bound, not compute-bound. With tracing on, each batch
    leaves `etl/source_next`, `etl/stage` and `etl/queue_put` spans on
    the prefetch thread's track and an `etl/queue_wait` span on the
    consumer's, all carrying the batch's `seq`."""
    if queue_size is None:
        queue_size = prefetch_depth()
    if int(queue_size) <= 0:
        return (item if transform is None else transform(item)
                for item in source)
    return _prefetch_pump(source, transform, int(queue_size))


def _prefetch_pump(source, transform, queue_size: int):
    """The background-thread pump half of prefetch_iterable (split out so
    the depth-0 sync degrade can be a plain return, not a dead generator
    branch)."""
    from deeplearning4j_tpu import monitor
    q: "queue.Queue" = queue.Queue(maxsize=int(queue_size))
    stop = threading.Event()
    m_depth = monitor.gauge("etl_queue_depth",
                            "Prefetch queue fill (async ETL)")
    m_wait = monitor.histogram("etl_fetch_wait_seconds",
                               "Consumer wait on the prefetch queue")
    m_batches = monitor.counter("etl_batches_prefetched_total",
                                "Batches staged by prefetch workers")
    m_stage = monitor.histogram("etl_stage_seconds",
                                "Worker-side batch staging (cast + "
                                "device_put + callback)")

    def put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                m_depth.set(q.qsize())
                return True
            except queue.Full:
                continue
        return False

    # Spans of one batch share its `seq` (its number since the pump
    # started): `etl/source_next`, `etl/stage` and `etl/queue_put` on the
    # worker thread, `etl/queue_wait` on the consumer's. A worker blocked
    # in `etl/queue_put` is the healthy state (the queue is full), so
    # that span stays out of the profiler's host plane, where it is about
    # as long as a chunk and would be taken for what the device waited
    # on; a consumer blocked in `etl/queue_wait` is work waiting for the
    # feed.
    def worker():
        try:
            it = iter(source)
            seq = 0
            while True:
                if stop.is_set():
                    return
                with monitor.span("etl/source_next", seq=seq):
                    try:
                        item = next(it)
                    except StopIteration:
                        break
                if transform is not None:
                    t0 = time.perf_counter()
                    with monitor.span("etl/stage", seq=seq):
                        item = transform(item)
                    m_stage.observe(time.perf_counter() - t0)
                m_batches.inc()
                with monitor.span("etl/queue_put", annotate=False, seq=seq):
                    if not put(item):
                        return
                seq += 1
        except BaseException as e:    # surface worker errors to the consumer
            put(e)
            return
        put(_SENTINEL)

    t = threading.Thread(target=worker, daemon=True,
                         name=f"etl-prefetch-{next(_prefetch_seq)}")
    t.start()
    try:
        seq = 0
        while True:
            t0 = time.perf_counter()
            with monitor.span("etl/queue_wait", seq=seq):
                item = q.get()
            m_wait.observe(time.perf_counter() - t0)
            m_depth.set(q.qsize())
            if item is _SENTINEL:
                break
            if isinstance(item, BaseException):
                raise item
            seq += 1
            yield item
    finally:
        stop.set()
        try:
            while True:
                q.get_nowait()
        except queue.Empty:
            pass
        t.join(timeout=5)


class AsyncDataSetIterator(DataSetIterator):
    def __init__(self, source: DataSetIterator,
                 queue_size: Optional[int] = None,
                 device_put: bool = True, device=None, callback=None,
                 cast_dtype=None, cast_features: bool = True):
        """`callback` is a DataSetCallback (data/utility_iterators.py)
        applied to each batch on the prefetch thread AFTER the default
        device_put — the reference's DataSetCallback seam
        (AsyncDataSetIterator.java callback ctor arg); pass
        InterleavedDataSetCallback to round-robin batches over devices
        (set device_put=False so the callback owns placement).

        `cast_dtype`: 16-bit compute dtype to host-cast float32
        features/labels to on the worker thread before the transfer
        (see `host_cast`; masks keep their dtype). `cast_features=False`
        restricts the cast to labels — fit() uses it when device-side
        normalization is engaged, where RAW features must reach the
        device uncast (normalize-then-cast preserves the f32 signal a
        premature bf16 cast would quantize away).

        `queue_size` defaults to DL4J_TPU_PREFETCH_DEPTH (2 =
        double-buffered: the next batch stages while the current one
        computes); 0 disables the prefetch thread but keeps per-batch
        staging (cast + placement) synchronous."""
        if queue_size is None:
            queue_size = prefetch_depth()
        if getattr(source, "async_supported", True) is False:
            # AsyncShieldDataSetIterator semantics: pass through unwrapped
            self._passthrough = source
        else:
            self._passthrough = None
        self._source = source
        self._queue_size = int(queue_size)
        self._device_put = device_put
        self._device = device
        self._callback = callback
        self._cast_dtype = cast_dtype
        self._cast_features = cast_features

    def reset(self):
        self._source.reset()

    def batch_size(self):
        return self._source.batch_size()

    def set_pre_processor(self, pre_processor):
        # DL4J AsyncDataSetIterator delegates to the backing iterator:
        # the source routes every batch, a `MultiDataSet` like a
        # `DataSet`, through it as the prefetch thread pulls it
        self._source.set_pre_processor(pre_processor)
        return self

    def _stage(self, ds):
        """Per-batch worker-thread transform: 16-bit host cast, async H2D
        transfer, then the DataSetCallback seam. A `MultiDataSet` (a
        graph's several inputs, labels and masks) is cast and placed
        array by array, the same way."""
        if isinstance(ds, MultiDataSet):
            parts = [self._place(DataSet(*arrays))
                     for arrays in itertools.zip_longest(
                         ds.features, ds.labels, ds.features_masks or (),
                         ds.labels_masks or ())]
            pick = lambda name, like: None if like is None else tuple(
                getattr(part, name) for part in parts[:len(like)])
            ds = MultiDataSet(
                pick("features", ds.features), pick("labels", ds.labels),
                pick("features_mask", ds.features_masks),
                pick("labels_mask", ds.labels_masks))
        else:
            ds = self._place(ds)
        if self._callback is not None:
            out = self._callback.call(ds)
            ds = ds if out is None else out
        return ds

    def _place(self, ds: DataSet) -> DataSet:
        """One DataSet's arrays cast on the host and put on the device."""
        if self._cast_dtype is not None:
            ds = DataSet(
                host_cast(ds.features, self._cast_dtype)
                if self._cast_features else ds.features,
                None if ds.labels is None
                else host_cast(ds.labels, self._cast_dtype),
                ds.features_mask, ds.labels_mask,
            )
        if self._device_put:
            dev = self._device or jax.local_devices()[0]
            if isinstance(dev, jax.sharding.Sharding):
                # mesh placement (GSPMD-plan fit): the shared ragged-tail
                # fallback (parallel/plan.put_batch) keeps a
                # non-divisible batch from killing the prefetch thread
                from deeplearning4j_tpu.parallel.plan import put_batch
                put = lambda a: None if a is None else put_batch(a, dev)
            else:
                put = lambda a: None if a is None \
                    else jax.device_put(a, dev)
            ds = DataSet(put(ds.features), put(ds.labels),
                         put(ds.features_mask), put(ds.labels_mask))
        return ds

    def __iter__(self):
        if self._passthrough is not None:
            # shielded sources skip the prefetch thread, but the callback
            # contract (device placement) must still hold
            if self._callback is None:
                return iter(self._passthrough)
            return self._iter_passthrough()
        return self._iter_async()

    def _iter_passthrough(self):
        for ds in self._passthrough:
            out = self._callback.call(ds)
            yield ds if out is None else out

    def _iter_async(self):
        # the one shared thread pump (bounded queue, sentinel, exception
        # smuggling, drain-and-join teardown) lives in prefetch_iterable;
        # queue_size 0 degrades it to synchronous per-batch staging
        yield from prefetch_iterable(self._source, self._stage,
                                     self._queue_size)
