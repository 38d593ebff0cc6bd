"""MultiLayerNetwork — the sequential network container.

Parity target: DL4J nn/multilayer/MultiLayerNetwork.java (3545 LoC):
- init()                    :549   -> init(): per-layer param init via InputType chain
- fit(DataSetIterator)      :1268  -> fit(): jit-compiled train step (autodiff
                                     replaces calcBackpropGradients :1378)
- feedForward               :885   -> feed_forward(): all layer activations
- output                    :2012  -> output(): jitted inference
- computeGradientAndScore   :2360  -> the value_and_grad inside the train step
- doTruncatedBPTT           :1315  -> tBPTT chunking with carried RNN state
- rnnTimeStep               :2806  -> rnn_time_step(): stateful streaming step
- score includes l1/l2 regularization (BaseLayer.calcRegularizationScore)

TPU-native design: the whole training step (forward, backward, updater apply)
is ONE jit-compiled XLA program with donated params/opt-state buffers (the
analog of DL4J's workspace arena reuse, MultiLayerNetwork.java:1284-1292).
Parameters are a pytree; the canonical flat view (util/params.py) replaces
DL4J's flattenedParams single buffer (:114,603-627).
"""
from __future__ import annotations

import logging
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from deeplearning4j_tpu.data.async_iterator import (
    AsyncDataSetIterator, host_cast,
)
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterator import ArrayDataSetIterator, DataSetIterator
from deeplearning4j_tpu.nn.conf.base import (
    InputType, Kind, LayerConf, preprocess_forward, preprocessed_type,
)
from deeplearning4j_tpu.nn.conf.network import MultiLayerConfiguration
from deeplearning4j_tpu.nn.updaters import NoOp, apply_update, build_optimizer
from deeplearning4j_tpu.util import params as param_util
from deeplearning4j_tpu.util.env import env_int
from deeplearning4j_tpu.util.platform import is_tpu_backend

log = logging.getLogger("deeplearning4j_tpu")

# layer-kind requirements for automatic preprocessor insertion
# (the analog of MultiLayerConfiguration.Builder#setInputType auto-adding
#  InputPreProcessors). None = accepts anything (elementwise layers).
_KIND_BY_CLASS = {
    "DenseLayer": Kind.FF, "EmbeddingLayer": Kind.FF, "OutputLayer": Kind.FF,
    "AutoEncoder": Kind.FF, "VariationalAutoencoder": Kind.FF,
    "ConvolutionLayer": Kind.CNN, "Deconvolution2D": Kind.CNN,
    "SeparableConvolution2D": Kind.CNN, "DepthwiseConvolution2D": Kind.CNN,
    "SubsamplingLayer": Kind.CNN, "Upsampling2D": Kind.CNN,
    "ZeroPaddingLayer": Kind.CNN, "Cropping2D": Kind.CNN,
    "SpaceToDepthLayer": Kind.CNN, "SpaceToBatchLayer": Kind.CNN,
    "Yolo2OutputLayer": Kind.CNN,
    "MultiHeadAttention": Kind.RNN, "TransformerBlock": Kind.RNN,
    "MoEFeedForward": Kind.RNN,
    "PositionalEmbeddingLayer": Kind.RNN, "EmbeddingSequenceLayer": Kind.RNN,
    "LocalResponseNormalization": Kind.CNN, "CnnLossLayer": Kind.CNN,
    "LSTM": Kind.RNN, "GravesLSTM": Kind.RNN, "SimpleRnn": Kind.RNN,
    "GRU": Kind.RNN,
    "Bidirectional": Kind.RNN, "GravesBidirectionalLSTM": Kind.RNN,
    "RnnOutputLayer": Kind.RNN, "RnnLossLayer": Kind.RNN,
    "LastTimeStep": Kind.RNN, "MaskZeroLayer": Kind.RNN,
    "Convolution1DLayer": Kind.RNN, "Subsampling1DLayer": Kind.RNN,
}

_RECURRENT_CLASSES = {"LSTM", "GravesLSTM", "SimpleRnn", "GRU"}


def _is_stateful_recurrent(layer) -> bool:
    """Recurrent-carry dispatch, unwrapping FrozenLayerWrapper so a
    frozen LSTM keeps its rnn_time_step/tbptt state semantics."""
    inner = getattr(layer, "layer", None)
    name = type(inner if inner is not None
                and type(layer).__name__ == "FrozenLayerWrapper"
                else layer).__name__
    return name in _RECURRENT_CLASSES


def _scan_incompatible_listeners(listeners) -> bool:
    """Listeners that inspect the model (params/opt state) or capture
    gradients need iteration_done in lockstep with the params — the
    pipelined scan fit delivers it up to 2K-1 steps late, so their
    presence forces the per-call path."""
    return any(getattr(lst, "wants_gradients", False)
               or getattr(lst, "reads_model", False)
               for lst in listeners)


def _record_iteration(score: float, batch_size: int,
                      step_seconds: Optional[float] = None,
                      sync_seconds: Optional[float] = None):
    """One optimizer step's worth of telemetry (monitor/metrics.py) —
    shared by every fit path of both containers and the resilient
    trainer, so `train_*` series mean the same thing everywhere. Only
    host scalars are touched: no device sync is introduced."""
    from deeplearning4j_tpu import monitor
    monitor.counter("train_iterations_total",
                    "Optimizer steps applied").inc()
    monitor.counter("train_examples_total",
                    "Training examples consumed").inc(batch_size)
    monitor.gauge("train_score", "Last training loss/score").set(score)
    if step_seconds is not None:
        monitor.histogram("train_step_seconds",
                          "Train step wall time (dispatch + host sync)"
                          ).observe(step_seconds)
    if sync_seconds is not None:
        monitor.histogram("train_host_sync_seconds",
                          "Blocking device->host loss fetch per step"
                          ).observe(sync_seconds)


def _ds_examples(ds) -> int:
    """Rows of one DataSet batch (the `examples=` of a train/chunk)."""
    return int(np.shape(ds.features)[0])


def _run_scan_pipeline(batches, K, *, sig_of, examples_of, stage, launch,
                       fetch, notify, defer=True, first_chunk=0):
    """Shared chunking/deferral loop of the input-pipelined fit paths
    (the `_fit_epoch_scan` / `_fit_epoch_accum` of both containers).

    One turn pulls consecutive batches with identical shape signature
    `sig_of(b)` into a chunk of at most K, stages and launches it
    (`stage(group)` -> staged device inputs, `launch(staged, etl_ms)` ->
    an opaque pending record whose device values are still futures), and
    only then syncs the chunk launched one turn EARLIER
    (`fetch(pending)` blocks on its losses, `notify(pending, fetched)`
    runs the per-step bookkeeping and listeners and returns the number
    of optimizer steps it reported) — so staging and launching chunk i
    overlap the device compute of chunk i-1, and the one blocking loss
    fetch per chunk happens while the device is busy (on a TPU the
    staging's own enqueues can block first: PERF.md section 5). The last turn
    pulls nothing and drains. defer=False syncs each chunk in the turn
    that launched it (model-reading listeners must observe the params
    as of the step they're told about).

    Every phase is an ENTERED span, so with
    `enable_tracing(jax_annotations=True)` the whole tree is on the
    profiler's host plane (docs/OBSERVABILITY.md "Tracing"):

        train/chunk                 chunk=i batches= examples= steps=
          train/etl                 batches=    (etl/queue_wait inside)
          train/dispatch            chunk=i
            train/stage / train/launch
          train/chunk_sync          chunk=i-1
            train/loss_fetch / train/listeners steps=

    `chunk` counts from `first_chunk` (the container keeps it running
    over the epochs of one fit()); returns the next chunk's number."""
    from deeplearning4j_tpu import monitor
    span = monitor.span
    it = iter(batches)
    chunk = first_chunk
    held = None          # the batch whose shape change closed the last group
    pending = None       # (chunk, record) launched and not yet synced
    exhausted = False
    while not (exhausted and held is None and pending is None):
        with span("train/chunk", chunk=chunk) as turn:
            etl_start = time.perf_counter()
            with span("train/etl") as etl:
                group, held = ([] if held is None else [held]), None
                gsig = sig_of(group[0]) if group else None
                while len(group) < K and not exhausted:
                    try:
                        b = next(it)
                    except StopIteration:
                        exhausted = True
                        break
                    s = sig_of(b)
                    if group and s != gsig:
                        held = b
                        break
                    group.append(b)
                    gsig = s
                etl.set(batches=len(group))
            etl_ms = (time.perf_counter() - etl_start) * 1e3
            fresh = None
            if group:
                with span("train/dispatch", chunk=chunk):
                    with span("train/stage"):
                        staged = stage(group)
                    with span("train/launch"):
                        fresh = (chunk, launch(staged, etl_ms))
                    # the launched program alone keeps its inputs from
                    # here: a reference held into the next turn's stage()
                    # adds a whole chunk of device memory to the peak
                    # (PERF.md section 6, PR 24)
                    del staged
            due, pending = (pending, fresh) if defer else (fresh, None)
            steps = 0
            if due is not None:
                with span("train/chunk_sync", chunk=due[0]):
                    with span("train/loss_fetch"):
                        fetched = fetch(due[1])
                    with span("train/listeners") as told:
                        steps = notify(due[1], fetched)
                        told.set(steps=steps)
            turn.set(batches=len(group), steps=steps,
                     examples=len(group) * examples_of(group[0])
                     if group else 0)
        if not group:
            break
        chunk += 1
    return chunk


def _required_kind(layer: LayerConf) -> Optional[Kind]:
    name = type(layer).__name__
    if name == "FrozenLayerWrapper":
        return _required_kind(layer.layer)
    return _KIND_BY_CLASS.get(name)


def _layer_call(layer, *, seq, train, remat, params, x, state=None,
                carry=None, rng=None, mask=None, cast=None):
    """Invoke layer.apply (seq=False) or layer.apply_seq (seq=True), with
    jax.checkpoint rematerialization when remat is on: every traced value
    (params/state/carry/input/rng/mask) is a checkpoint ARGUMENT, only the
    static layer conf and train flag are closed over. ``cast`` (params ->
    params in the compute dtype) is applied INSIDE the rematerialised
    region, so that the cast copy of a layer's weights lives only while
    the layer runs and is not held from the forward pass to the backward.
    Shared by both containers so the two forward passes can't drift."""
    cast = cast or (lambda lp: lp)
    if seq:
        def fn(lp, xx, cc, rr, mm, _l=layer):
            return _l.apply_seq(cast(lp), xx, cc, train=train, rng=rr,
                                mask=mm)
        args = (params, x, carry, rng, mask)
    else:
        def fn(lp, st, xx, rr, mm, _l=layer):
            return _l.apply(cast(lp), st, xx, train=train, rng=rr, mask=mm)
        args = (params, state, x, rng, mask)
    if remat:
        # nothing is kept but what a layer names "remat_keep" (a result
        # far dearer to make again than to hold: a recurrence's output)
        fn = jax.checkpoint(
            fn, policy=jax.checkpoint_policies.save_only_these_names(
                "remat_keep"))
    return fn(*args)


def _default_scan_steps() -> int:
    """Production fit() pipelining default, decided from the round-5
    hardware measurement (PERF.md): on the TPU v5e the scan-of-10 fused
    step measured +6.5% over per-call (2377 vs 2231 imgs/s, ResNet-50
    bf16 batch 128) and removes all per-step dispatch; on CPU XLA
    pessimizes convolutions inside scan (10.9x slower, PERF.md
    "mechanism check"), so per-call stays the CPU default.
    DL4J_TPU_SCAN_STEPS overrides either way."""
    env = env_int("DL4J_TPU_SCAN_STEPS")
    if env is not None:
        return env
    # TPU only — GPU/other backends are unmeasured, and the CPU
    # mechanism check shows conv-in-scan can regress badly off-TPU
    return 10 if is_tpu_backend() else 1


def _engage_plan_impl(net, plan):
    """Shared by MultiLayerNetwork/ComputationGraph (and the resilience
    drivers): activate a GSPMD ShardingPlan for a net's compiled steps —
    or plain single-device training when None. Either way
    params/opt/state are laundered into XLA-owned buffers
    (donated-buffer safety, util/params.owned_leaf); under a plan the
    laundered copies additionally land on the plan's placements
    (sharding-aware own_tree), and a plan CHANGE drops the compiled-step
    caches so the next step re-lowers against the new layout instead of
    silently running the old one."""
    prior = net._plan
    if plan != prior:
        net._plan = plan
        net._train_step = None
        net._scan_step = {}
        net._output_fn = None
        # the ledger cache keys on id(step_fn): with the old jitted fns
        # dropped above, CPython may reuse their ids for the NEW steps —
        # a stale hit would misattribute the re-compiled (sharded)
        # program's timings to the old record
        net._ledger_cache = {}
    if plan is None:
        if prior is not None:
            # leaving a plan: gather mesh-committed leaves back to the
            # default device FIRST — the owned copy below preserves
            # committed shardings, and a plain fit stages its batches
            # single-device (incompatible-devices error otherwise)
            dev = jax.local_devices()[0]
            gather = lambda t: jax.tree_util.tree_map(
                lambda a: jax.device_put(a, dev), t)
            net.params = gather(net.params)
            net.state = gather(net.state)
            net.opt_state = gather(net.opt_state)
        net.params = param_util.own_tree(net.params)
        net.state = param_util.own_tree(net.state)
        net.opt_state = param_util.own_tree(net.opt_state)
    else:
        net.params = param_util.own_tree(
            net.params, plan.param_shardings(net.params))
        net.state = param_util.own_tree(
            net.state, plan.replicated_shardings(net.state))
        net.opt_state = param_util.own_tree(
            net.opt_state, plan.opt_shardings(net.opt_state, net.params))


def _stage_with_affine(net, a):
    """Features -> device, shared by MultiLayerNetwork._stage_x and
    ComputationGraph._stage_x. With a device affine engaged (fit through
    a `device_affine()` pre-processor), RAW features ship over the
    host->HBM link (uint8 pixels stay uint8: 4x fewer bytes than
    float32, 2x fewer than the bf16 host cast) and the normalization
    runs on device in one fused jit; otherwise plain _as_jnp."""
    if net._input_affine is None:
        return _as_jnp(a, net._compute_dtype)
    if net._affine_fn is None:
        from deeplearning4j_tpu.data.normalization import make_affine_fn
        net._affine_fn = make_affine_fn(net._compute_dtype)
    shift, scale = net._input_affine
    return net._affine_fn(jnp.asarray(a), shift, scale)


def _as_jnp(a, dtype=None):
    if a is None:
        return None
    # 16-bit compute dtypes (bfloat16 training): cast float32 host arrays
    # BEFORE the device transfer (bit-identical to the device cast; f64 is
    # excluded — its old path double-rounds via f32 with x64 disabled).
    # Shared rule: data/async_iterator.host_cast (DL4J_TPU_HOST_CAST=0
    # restores transfer-then-cast).
    a = host_cast(a, dtype)
    arr = jnp.asarray(a)
    # floats cast to the compute dtype; so do raw uint8 image bytes
    # (ImageRecordReader reference parity) used WITHOUT a normalizer.
    # Wider int dtypes stay integer — they are embedding/sparse-label
    # token ids, not pixels.
    if dtype is not None and (jnp.issubdtype(arr.dtype, jnp.floating)
                              or arr.dtype == jnp.uint8):
        arr = arr.astype(dtype)
    return arr


def _masked_eval_pair(labels, preds, labels_mask):
    """Normalize (labels, preds) for the eval accumulators: drop
    mask-padded entries (mask reshaped to the labels' leading dims, so
    (B,T), (B,T,1) and (B,) layouts all work) and flatten remaining
    rank>=3 sequences to (N, C) so per-class accumulators see the class
    axis."""
    if labels_mask is not None:
        m = np.asarray(labels_mask).astype(bool).reshape(labels.shape[:-1])
        labels, preds = labels[m], preds[m]
    if labels.ndim >= 3:
        labels = labels.reshape(-1, labels.shape[-1])
        preds = preds.reshape(-1, preds.shape[-1])
    return labels, preds


def validate_layer_conf(layer: LayerConf):
    """Fail fast on unresolvable names at init time (typos in activation /
    weight_init / loss would otherwise only surface at first forward)."""
    from deeplearning4j_tpu.nn.activations import get_activation
    from deeplearning4j_tpu.nn.initializers import get_initializer
    from deeplearning4j_tpu.nn.losses import get_loss
    for field, resolver in (("activation", get_activation),
                            ("gate_activation", get_activation),
                            ("weight_init", get_initializer),
                            ("loss", get_loss)):
        v = getattr(layer, field, None)
        if v is not None:
            resolver(v)
    inner = getattr(layer, "layer", None)
    if isinstance(inner, LayerConf):
        validate_layer_conf(inner)


class MultiLayerNetwork:
    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layers = conf.layers
        self.params: Optional[dict] = None
        self.state: Optional[dict] = None
        self.opt_state = None
        self.listeners: List = []
        self.iteration_count = 0
        self.epoch_count = 0
        self._score: Optional[float] = None
        self._rnn_carries: Dict[str, Any] = {}
        self._param_dtype = jnp.dtype(conf.dtype)
        self._compute_dtype = jnp.dtype(conf.compute_dtype or conf.dtype)
        self._input_types: Optional[List[InputType]] = None
        self._tx = None
        self._train_step = None
        self._scan_step: Dict[Any, Any] = {}
        self._output_fn = None
        self._input_affine = None   # (shift, scale) during device-norm fit
        self._affine_fn = None
        self._ledger_cache: Dict[Any, Any] = {}   # monitor.xla programs
        self._plan = None           # active GSPMD ShardingPlan (parallel/plan)

    # ------------------------------------------------------------ plumbing
    def _stage_x(self, a):
        return _stage_with_affine(self, a)

    def _engage_plan(self, plan):
        """Activate a GSPMD ShardingPlan (parallel/plan.py) for this
        net's compiled steps — or plain single-device training when
        None (the shared `_engage_plan_impl`; also used by
        ComputationGraph and the ResilientTrainer drivers)."""
        _engage_plan_impl(self, plan)

    def _shard_batch(self, *arrs, stacked: bool = False):
        """Place staged batch operands per the active plan — dim 0 (dim
        1 for host-stacked scan/accum chunks) split over the mesh "data"
        axis. Identity without a plan."""
        plan = self._plan
        if plan is None:
            return arrs
        return tuple(plan.shard_batch(a, stacked=stacked) for a in arrs)

    def _stage_stacked(self, group):
        """K same-shape host batches -> (xs, ys, fms, lms) stacked on a
        new leading axis, on the device, per the active plan: the ONE
        staging rule of the scan and accumulation chunks."""
        ds0 = group[0]
        stack = lambda get, dt=None: (
            None if get(ds0) is None else
            _as_jnp(np.stack([np.asarray(get(d)) for d in group]), dt))
        xs = None if ds0.features is None else self._stage_x(
            np.stack([np.asarray(d.features) for d in group]))
        return self._shard_batch(
            xs, stack(lambda d: d.labels, self._compute_dtype),
            stack(lambda d: d.features_mask),
            stack(lambda d: d.labels_mask), stacked=True)

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def add_listeners(self, *listeners):
        self.listeners.extend(listeners)
        return self

    def _resolve_types(self) -> List[InputType]:
        """Per-layer input InputTypes (pre-preprocessor), following DL4J's
        setInputType chain."""
        if self.conf.input_type is None:
            raise ValueError("MultiLayerConfiguration.input_type must be set "
                             "(InputType.feed_forward/convolutional/recurrent)")
        types = []
        cur = self.conf.input_type
        for layer in self.layers:
            need = _required_kind(layer)
            if need is not None and cur.kind != need:
                cur = preprocessed_type(cur, need)
            types.append(cur)
            cur = layer.output_type(cur)
        self._output_type = cur
        return types

    def init(self, seed: Optional[int] = None):
        """Initialize parameters and optimizer state (DL4J init(), :549)."""
        seed = self.conf.seed if seed is None else seed
        key = jax.random.PRNGKey(seed)
        for layer in self.layers:
            validate_layer_conf(layer)
        self._input_types = self._resolve_types()
        params: Dict[str, dict] = {}
        state: Dict[str, dict] = {}
        for i, layer in enumerate(self.layers):
            key, sub = jax.random.split(key)
            p, s = layer.init(sub, self._input_types[i], self._param_dtype)
            params[str(i)] = p
            state[str(i)] = s
        self.params = params
        self.state = state
        self._build_optimizer()
        return self

    def _label_params(self):
        """Per-layer updater labels for optax.multi_transform (per-layer
        updater overrides + FrozenLayer -> NoOp, DL4J UpdaterBlock grouping)."""
        labels = {}
        transforms = {"__global__": build_optimizer(
            self.conf.updater, self.conf.grad_clip_norm, self.conf.grad_clip_value)}
        any_override = False
        for i, layer in enumerate(self.layers):
            lab = "__global__"
            if layer.frozen or type(layer).__name__ == "FrozenLayerWrapper":
                lab = "__noop__"
                transforms.setdefault("__noop__", NoOp().to_optax())
                any_override = True
            elif layer.updater is not None:
                lab = f"layer_{i}"
                transforms[lab] = build_optimizer(
                    layer.updater, self.conf.grad_clip_norm, self.conf.grad_clip_value)
                any_override = True
            labels[str(i)] = jax.tree_util.tree_map(lambda _: lab, self.params[str(i)])
        return any_override, labels, transforms

    def _build_optimizer(self):
        any_override, labels, transforms = self._label_params()
        if any_override:
            self._tx = optax.multi_transform(transforms, labels)
        else:
            self._tx = transforms["__global__"]
        self.opt_state = self._tx.init(self.params)
        self._train_step = None     # force re-trace
        self._scan_step = {}

    # ------------------------------------------------------------- forward
    def _cast_params(self, params):
        if self._compute_dtype == self._param_dtype:
            return params
        def cast(a):
            if jnp.issubdtype(a.dtype, jnp.floating):
                return a.astype(self._compute_dtype)
            return a
        return jax.tree_util.tree_map(cast, params)

    def _forward(self, params, state, x, train, rng, fmask=None,
                 carries=None, collect=False, upto: Optional[int] = None):
        """Forward through layers [0, upto) with auto preprocessors
        (upto=None -> all layers).

        When `upto` cuts before the output head, the returned activation is
        additionally preprocessed into the head's required kind, ready for
        head.score(). Returns (activations list if collect else final
        activation, new_state, new_carries)."""
        if self._input_types is None:
            self._input_types = self._resolve_types()
        # under gradient checkpointing a layer casts its own weights
        # inside its rematerialised region (`_layer_call`)
        remat = train and self.conf.gradient_checkpointing
        late_cast = self._cast_params if remat else None
        if not remat:
            params = self._cast_params(params)
        x = _as_jnp(x, self._compute_dtype)
        cur_type = self.conf.input_type
        n = len(self.layers) if upto is None else upto
        new_state = dict(state)
        new_carries = {}
        acts = []
        for i, layer in enumerate(self.layers[:n]):
            need = _required_kind(layer)
            if need is not None and cur_type.kind != need:
                x = preprocess_forward(cur_type, need, x)
                cur_type = preprocessed_type(cur_type, need)
            sub_rng = None
            if rng is not None:
                rng, sub_rng = jax.random.split(rng)
            mask = fmask if cur_type.kind == Kind.RNN else None
            key = str(i)
            layer_params = params[key]
            if remat and layer.weight_noise is not None:
                layer_params = self._cast_params(layer_params)
            if train and sub_rng is not None and layer.weight_noise is not None:
                from deeplearning4j_tpu.nn.regularization import (
                    apply_weight_noise,
                )
                sub_rng, noise_rng = jax.random.split(sub_rng)
                layer_params = apply_weight_noise(layer, layer_params, train,
                                                  noise_rng)
            # gradient checkpointing: rematerialize this layer's
            # activations in the backward pass instead of storing them —
            # HBM for recompute FLOPs (jax.checkpoint). Only the training
            # forward pays for a backward, so inference is untouched.
            if carries is not None and _is_stateful_recurrent(layer):
                y, carry = _layer_call(
                    layer, seq=True, train=train, remat=remat,
                    params=layer_params, x=x, carry=carries.get(key),
                    rng=sub_rng, mask=mask, cast=late_cast)
                new_carries[key] = carry
                new_state[key] = state[key]
            else:
                y, s = _layer_call(
                    layer, seq=False, train=train, remat=remat,
                    params=layer_params, x=x, state=state[key],
                    rng=sub_rng, mask=mask, cast=late_cast)
                new_state[key] = s
            x = y
            cur_type = layer.output_type(cur_type)
            if collect:
                acts.append(x)
        if upto is not None and upto < len(self.layers):
            head = self.layers[upto]
            need = _required_kind(head)
            if need is not None and cur_type.kind != need:
                x = preprocess_forward(cur_type, need, x)
        return (acts if collect else x), new_state, new_carries

    def _score_fn(self, params, state, x, y, fmask, lmask, train, rng,
                  carries=None):
        """Loss on a batch: last-layer score + regularization
        (computeGradientAndScore, MultiLayerNetwork.java:2360)."""
        if not self.layers or not hasattr(self.layers[-1], "score"):
            raise ValueError("Last layer must be an output/loss layer with a "
                             "score() method to compute training loss")
        params_c = self._cast_params(params)
        # forward up to (but excluding) the output layer; it casts the
        # weights itself, a layer at a time under gradient checkpointing
        head = self.layers[-1]
        feat, new_state, new_carries = self._forward(
            params, state, x, train, rng, fmask, carries,
            upto=len(self.layers) - 1)
        out_mask = lmask if lmask is not None else (
            fmask if _required_kind(head) == Kind.RNN else None)
        loss = head.score(params_c[str(len(self.layers) - 1)], feat,
                          _as_jnp(y, self._compute_dtype), train=train,
                          rng=None, mask=out_mask)
        reg = jnp.asarray(0.0, jnp.float32)
        for i, layer in enumerate(self.layers):
            reg = reg + layer.regularization_score(params[str(i)])
        # score accumulates in f32 (bf16 compute) but must stay f64 under
        # float64 gradient checking — don't down-cast a wider loss
        score_dtype = jnp.promote_types(jnp.float32, loss.dtype)
        return loss.astype(score_dtype) + reg, (new_state, new_carries)

    # -------------------------------------------------------------- output
    def output(self, x, train: bool = False):
        """Inference (DL4J output(), :2012-2112). jit-compiled and cached."""
        if self.params is None:
            raise RuntimeError("Network is not initialized — call init() first")
        if self._output_fn is None:
            @jax.jit
            def _out(params, state, x):
                y, _, _ = self._forward(params, state, x, False, None)
                return y
            self._output_fn = _out
        return self._output_fn(self.params, self.state, _as_jnp(x, self._compute_dtype))

    def feed_forward(self, x, train: bool = False, rng=None):
        """All layer activations (DL4J feedForward(), :885-1071).
        With train=True and no rng given, a fresh dropout key is drawn per
        call (so repeated calls do not reuse one mask)."""
        if train and rng is None:
            self._ff_counter = getattr(self, "_ff_counter", 0) + 1
            rng = jax.random.fold_in(
                jax.random.PRNGKey(self.conf.seed + 15485863), self._ff_counter)
        acts, _, _ = self._forward(self.params, self.state, x, train,
                                   rng if train else None, collect=True)
        return acts

    # ----------------------------------------------------------------- fit
    def _make_train_step(self, with_fmask, with_lmask, with_carries,
                         with_stats=False):
        from deeplearning4j_tpu.nn.regularization import (
            apply_constraints, constraint_map, has_constraints,
        )
        tx = self._tx
        constrained = has_constraints(self.layers)
        layer_map = constraint_map(self)
        plan = self._plan   # GSPMD plan: sharding constraints in-jit

        def step(params, opt_state, state, x, y, fmask, lmask, rng, carries):
            def loss_fn(p):
                return self._score_fn(p, state, x, y, fmask, lmask, True, rng,
                                      carries=carries)
            (loss, (new_state, new_carries)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            if plan is not None:
                # pin grads to the ZeRO/TP compute layout: this single
                # hint makes XLA derive reduce-scatter -> sharded update
                # -> all-gather (parallel/plan.py)
                grads = plan.constrain_grads(grads)
            new_params, new_opt, updates = apply_update(
                tx, grads, opt_state, params, plan)
            if constrained:     # post-update projection (DL4J applyConstraints)
                new_params = apply_constraints(layer_map, new_params)
            if plan is not None:
                new_params = plan.constrain_params(new_params)
                new_opt = plan.constrain_opt(new_opt, new_params)
                new_state = plan.constrain_replicated(new_state)
            if with_stats:
                # StatsListener capture iterations also return the raw
                # gradient and update pytrees (DL4J onGradientCalculation /
                # onBackwardPass hooks); a separate jit variant so the fast
                # path transfers nothing extra
                return (new_params, new_opt, new_state, loss, new_carries,
                        grads, updates)
            return new_params, new_opt, new_state, loss, new_carries

        return jax.jit(step, donate_argnums=(0, 1, 2))

    def _get_train_step(self, fmask, lmask, carries, with_stats=False):
        sig = (fmask is not None, lmask is not None, carries is not None,
               with_stats)
        if self._train_step is None:
            self._train_step = {}
        if sig not in self._train_step:
            self._train_step[sig] = self._make_train_step(*sig)
        return self._train_step[sig]

    def fit(self, data, epochs: int = 1, batch_size: int = 32,
            scan_steps: Optional[int] = None,
            prefetch: Optional[bool] = None,
            accumulate_steps: int = 1,
            plan=None):
        """Train (DL4J fit(DataSetIterator), :1268). Accepts a DataSetIterator,
        a DataSet, or (features, labels) arrays.

        accumulate_steps > 1: gradient accumulation — K micro-batch
        gradients averaged into ONE optimizer step inside one jit, for
        effective batch sizes beyond what HBM fits in a single forward
        (see _make_accum_step; mutually exclusive with scan_steps > 1,
        not applicable to tbptt). Accumulation groups only CONSECUTIVE
        same-shape micro-batches: a shape change (e.g. a non-drop-last
        partial tail) cuts the group short, and the short group takes one
        full-learning-rate step with the mean of however many gradients
        it holds — use drop_last/padded iterators for uniform shapes if K
        must be honored exactly (a warning fires once otherwise).

        scan_steps > 1 fuses that many optimizer steps into ONE jit call via
        lax.scan (input-pipelined fit): batches are stacked host-side while
        the previous chunk computes on device, and the per-step loss fetch is
        deferred one chunk, so the dispatch pipeline never blocks on a
        device→host sync. The RNG stream, update math and listener calls are
        identical to the per-call path (bit-for-bit, tested) — only the
        host/device overlap changes. Default: 10 on TPU (measured +6.5%
        over per-call, PERF.md), 1 on CPU; $DL4J_TPU_SCAN_STEPS overrides.

        Intended for dispatch-bound TPU loops. Caveat (PERF.md "mechanism
        check"): XLA:CPU pessimizes convolutions inside scan, so conv nets
        on CPU should keep scan_steps=1.

        `prefetch` (default on, kill switches DL4J_TPU_FIT_PREFETCH=0 /
        DL4J_TPU_PREFETCH_DEPTH=0): wrap plain sources in
        AsyncDataSetIterator, like the reference wraps every fit in an
        async iterator by default (MultiLayerNetwork.java:1272-1274) — a
        worker thread overlaps host ETL, the bf16 host cast, and the H2D
        transfer with device compute, DL4J_TPU_PREFETCH_DEPTH batches
        deep (default 2: double-buffered H2D). Already-async and
        async_supported=False sources pass through. Multi-process
        sources (data/pipeline.MultiProcessDataSetIterator, or the hot
        image path's automatic delegation in data/records.py) compose:
        the wrap's prefetch thread is the ring consumer, so worker
        decode, device DMA, and the compiled step all overlap — see
        docs/DATA_PIPELINE.md.

        `plan` (or an enclosing `parallel.use_mesh(plan)` context): a
        GSPMD ShardingPlan (parallel/plan.py) — the SAME compiled step
        runs SPMD over the plan's ("data", "model") mesh with DP
        all-reduce, tensor-parallel matmuls, and ZeRO reduce-scatter/
        all-gather as jit-inserted collectives. See docs/PARALLELISM.md."""
        if self.params is None:
            self.init()
        # donated-buffer safety: params from ANY host source (checkpoint,
        # keras/dl4j import, set_params_flat) may alias numpy memory that
        # the donating train step must not free (util/params.owned_leaf);
        # under a plan the laundered copies land on the plan placements
        from deeplearning4j_tpu.parallel.plan import active_plan
        if plan is None:
            plan = active_plan()
        self._engage_plan(plan)
        if accumulate_steps > 1:
            if self.conf.backprop_type == "tbptt":
                raise ValueError("accumulate_steps does not apply to "
                                 "tbptt (chunked-time) training")
            if scan_steps is not None and scan_steps > 1:
                raise ValueError("accumulate_steps and scan_steps are "
                                 "mutually exclusive (one fuses K "
                                 "optimizer steps, the other folds K "
                                 "micro-batches into one step)")
            scan_steps = 1
        if scan_steps is None:
            scan_steps = _default_scan_steps()
        iterator = self._as_iterator(data, batch_size)
        if prefetch is None:
            from deeplearning4j_tpu.data.async_iterator import (
                fit_prefetch_enabled,
            )
            prefetch = fit_prefetch_enabled()
        # device-side normalization (data/normalization.py
        # engaged_device_affine — env gate, listener gate, detach/restore,
        # feature-cast pause): an affine-representable pre-processor is
        # applied on device instead of host (_stage_x), so raw uint8
        # pixels ship over the link. Engaged BEFORE the async wrap so
        # the wrap skips the 16-bit FEATURE host cast — normalize-then-
        # cast preserves the f32 signal a premature bf16 cast would
        # quantize away (labels still ship 16-bit).
        from deeplearning4j_tpu.data.normalization import (
            engaged_device_affine)
        with engaged_device_affine(iterator, self.listeners) as aff:
            if aff is not None:
                self._input_affine = (jnp.asarray(aff[0]),
                                      jnp.asarray(aff[1]))
            # scan-fit and accumulation STACK K host batches before one
            # transfer — the wrap must not device_put per batch there (a
            # device array would round-trip back through the host). The
            # scan path falls back to per-call under model-reading
            # listeners and tbptt never scans, so match the path that
            # will actually run.
            stacking = accumulate_steps > 1 or (
                scan_steps > 1
                and self.conf.backprop_type != "tbptt"
                and not _scan_incompatible_listeners(self.listeners))
            copy_marked = []
            if stacking:
                # stacking holds K live batches before ONE transfer —
                # shared-memory ring iterators must yield copies for it
                # (their normal view batches are recycled on the next
                # pull; data/pipeline.mark_copy_for_stacking)
                from deeplearning4j_tpu.data.pipeline import (
                    mark_copy_for_stacking)
                copy_marked = mark_copy_for_stacking(iterator)
            if prefetch and not isinstance(iterator, AsyncDataSetIterator) \
                    and getattr(iterator, "async_supported", True):
                iterator = AsyncDataSetIterator(
                    iterator, device_put=not stacking,
                    # under a plan the worker thread stages straight onto
                    # the mesh (device arg accepts a Sharding), so the
                    # double-buffered H2D lands already batch-sharded
                    device=(self._plan.batch_sharding()
                            if self._plan is not None else None),
                    cast_dtype=self._compute_dtype
                    if np.dtype(self._compute_dtype).itemsize == 2
                    else None,
                    cast_features=self._input_affine is None)
            from deeplearning4j_tpu.monitor import goodput
            gp_session = goodput.fit_begin("mln/fit")
            self._fit_chunk = 0     # train/chunk numbers run over epochs
            try:
                from deeplearning4j_tpu import monitor
                for _ in range(epochs):
                    for lst in self.listeners:
                        lst.on_epoch_start(self, self.epoch_count)
                    with monitor.span("train/epoch",
                                      epoch=self.epoch_count):
                        if self.conf.backprop_type == "tbptt":
                            self._fit_epoch_tbptt(iterator)
                        elif accumulate_steps > 1:
                            self._fit_epoch_accum(iterator, accumulate_steps)
                        elif scan_steps > 1:
                            self._fit_epoch_scan(iterator, scan_steps)
                        else:
                            self._fit_epoch(iterator)
                    for lst in self.listeners:
                        lst.on_epoch_end(self, self.epoch_count)
                    self.epoch_count += 1
                    iterator.reset()
            finally:
                goodput.fit_end(gp_session)
                self._input_affine = None
                for it_ in copy_marked:
                    it_._copy = False
        return self

    def fit_pretrain(self, data, epochs: int = 1, batch_size: int = 32):
        """Greedy layerwise unsupervised pretraining (the `pretrain` branch
        of DL4J MultiLayerNetwork.fit, MultiLayerNetwork.java:1344-1346 over
        nn/layers/BasePretrainNetwork.java).

        For each layer exposing `pretrain_score` (AutoEncoder, VAE), in
        order: features are computed through the already-(pre)trained layers
        below in eval mode, and only that layer's params are optimized on
        its unsupervised objective. Supervised layers are skipped — follow
        with fit() to fine-tune end-to-end."""
        if self.params is None:
            self.init()
        iterator = self._as_iterator(data, batch_size)
        rng = jax.random.PRNGKey(self.conf.seed + 52711)
        for i, layer in enumerate(self.layers):
            if not hasattr(layer, "pretrain_score"):
                continue
            tx = build_optimizer(layer.updater or self.conf.updater,
                                 self.conf.grad_clip_norm,
                                 self.conf.grad_clip_value)
            lp = self.params[str(i)]
            opt_state = tx.init(lp)

            @jax.jit
            def feats_fn(params, state, x, _i=i):
                f, _, _ = self._forward(params, state, x, False, None,
                                        upto=_i)
                return f

            @jax.jit
            def pretrain_step(lp, opt_state, x, sub, _layer=layer, _tx=tx):
                loss, grads = jax.value_and_grad(
                    lambda p: _layer.pretrain_score(p, x, sub))(lp)
                updates, new_opt = _tx.update(grads, opt_state, lp)
                return optax.apply_updates(lp, updates), new_opt, loss

            for _ in range(epochs):
                for ds in iterator:
                    feats = feats_fn(self.params, self.state,
                                     _as_jnp(ds.features,
                                             self._compute_dtype))
                    rng, sub = jax.random.split(rng)
                    lp, opt_state, loss = pretrain_step(lp, opt_state,
                                                        feats, sub)
                iterator.reset()
            self.params[str(i)] = lp
            self._score = float(loss)
            log.info("pretrained layer %d (%s): score %.5f", i,
                     type(layer).__name__, self._score)
        self._build_optimizer()     # fresh opt state for supervised fit()
        return self

    def _as_iterator(self, data, batch_size) -> DataSetIterator:
        if isinstance(data, DataSetIterator):
            return data
        if isinstance(data, DataSet):
            from deeplearning4j_tpu.data.iterator import ExistingDataSetIterator
            return ExistingDataSetIterator([data])
        if isinstance(data, (tuple, list)) and len(data) == 2:
            return ArrayDataSetIterator(data[0], data[1], batch_size=batch_size)
        raise ValueError(f"Cannot interpret training data: {type(data)}")

    def _fit_epoch(self, iterator):
        from deeplearning4j_tpu import monitor
        from deeplearning4j_tpu.monitor import goodput
        from deeplearning4j_tpu.monitor import xla as xla_ledger
        etl_start = time.perf_counter()
        rng = jax.random.PRNGKey(self.conf.seed + 7919 * (self.epoch_count + 1))
        grad_listeners = [lst for lst in self.listeners
                          if getattr(lst, "wants_gradients", False)]
        for ds in iterator:
            step_start = time.perf_counter()
            etl_ms = (step_start - etl_start) * 1e3
            monitor.add_span("train/etl", etl_start, step_start,
                             iteration=self.iteration_count)
            rng, sub = jax.random.split(rng)
            capture = [lst for lst in grad_listeners
                       if lst.should_capture(self.iteration_count)]
            step = self._get_train_step(ds.features_mask, ds.labels_mask,
                                        None, with_stats=bool(capture))
            xs = self._stage_x(ds.features)
            ys = _as_jnp(ds.labels, self._compute_dtype)
            fm = _as_jnp(ds.features_mask)
            lm = _as_jnp(ds.labels_mask)
            xs, ys, fm, lm = self._shard_batch(xs, ys, fm, lm)
            out = step(self.params, self.opt_state, self.state,
                       xs, ys, fm, lm, sub, None)
            grads = updates = None
            if capture:
                (self.params, self.opt_state, self.state, loss, _,
                 grads, updates) = out
            else:
                self.params, self.opt_state, self.state, loss, _ = out
            sync_start = time.perf_counter()
            # block for device completion FIRST (goodput: step_compute;
            # banks per-shard barrier wait under a plan), so the
            # host_sync span below covers only the narrow D2H fetch
            goodput.device_wait(loss)
            fetch_start = time.perf_counter()
            monitor.add_span("train/device_wait", sync_start, fetch_start)
            # graftlint: disable=host-sync-in-hot-path -- the step's ONE budgeted loss fetch (the deliberate per-iteration sync; PERF.md) — bracketed by the train/host_sync span
            self._score = float(loss)     # the step's one blocking fetch
            step_end = time.perf_counter()
            bs = int(np.shape(ds.features)[0])
            monitor.add_span("train/host_sync", fetch_start, step_end)
            monitor.add_span("train/step", step_start, step_end,
                             iteration=self.iteration_count,
                             score=self._score, batch_size=bs)
            if xla_ledger.enabled():
                key = (id(step), xla_ledger.shape_key((xs, ys, fm, lm)))
                fresh = key not in self._ledger_cache
                rec = xla_ledger.capture_cached(
                    self._ledger_cache, key, "mln/train_step", step,
                    (self.params, self.opt_state, self.state, xs, ys, fm,
                     lm, sub, None), examples_per_call=bs)
                if not fresh:
                    # the debut execution's wall time includes the jit
                    # compile — only steady-state steps feed the MFU gauge
                    xla_ledger.observe_step(rec, step_end - step_start)
            _record_iteration(self._score, bs,
                              step_seconds=step_end - step_start,
                              sync_seconds=step_end - fetch_start)
            for lst in capture:
                lst.on_gradients(self, self.iteration_count, self.epoch_count,
                                 grads, updates)
            for lst in self.listeners:
                lst.iteration_done(self, self.iteration_count,
                                   self.epoch_count, self._score, etl_ms, bs)
            self.iteration_count += 1
            etl_start = time.perf_counter()

    def _make_scan_step(self, with_fmask, with_lmask, K):
        """K optimizer steps fused into one jit via lax.scan. Same math as
        _make_train_step applied K times; returns the K per-step losses as a
        device array so the host never syncs inside the chunk."""
        from deeplearning4j_tpu.nn.regularization import (
            apply_constraints, constraint_map, has_constraints,
        )
        tx = self._tx
        constrained = has_constraints(self.layers)
        layer_map = constraint_map(self)
        plan = self._plan   # GSPMD plan: sharding constraints in-jit

        def kstep(params, opt_state, state, xs, ys, fms, lms, subs):
            def body(carry, batch):
                params, opt_state, state = carry
                x, y, fm, lm, sub = batch
                def loss_fn(p):
                    return self._score_fn(p, state, x, y, fm, lm, True, sub,
                                          carries=None)
                (loss, (new_state, _)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
                if plan is not None:
                    grads = plan.constrain_grads(grads)
                new_params, new_opt, _ = apply_update(
                    tx, grads, opt_state, params, plan)
                if constrained:
                    new_params = apply_constraints(layer_map, new_params)
                if plan is not None:
                    new_params = plan.constrain_params(new_params)
                    new_opt = plan.constrain_opt(new_opt, new_params)
                    new_state = plan.constrain_replicated(new_state)
                return (new_params, new_opt, new_state), loss

            (params, opt_state, state), losses = jax.lax.scan(
                body, (params, opt_state, state), (xs, ys, fms, lms, subs))
            return params, opt_state, state, losses

        return jax.jit(kstep, donate_argnums=(0, 1, 2))

    def _make_accum_step(self, with_stats):
        """Gradient accumulation: K micro-batch gradients averaged into
        ONE optimizer step, all inside one jit (TPU-native big-effective-
        batch training — the HBM cost is one extra gradient-sized
        accumulator, not a K-times batch). For equal micro-batch sizes
        and batch-independent layers the result is bit-comparable to one
        big-batch step (mean of equal-size micro means == full-batch
        mean; tested); BatchNormalization statistics remain per
        micro-batch, the same semantics every framework's accumulation
        has. with_stats additionally returns the averaged (grads,
        updates) for on_gradients listeners. One jit serves every
        chunk/mask shape (jax retraces per pytree structure)."""
        from deeplearning4j_tpu.nn.regularization import (
            apply_constraints, constraint_map, has_constraints,
        )
        tx = self._tx
        constrained = has_constraints(self.layers)
        layer_map = constraint_map(self)
        plan = self._plan   # GSPMD plan: sharding constraints in-jit

        def kaccum(params, opt_state, state, xs, ys, fms, lms, subs):
            def body(carry, batch):
                gsum, state = carry
                x, y, fm, lm, sub = batch
                def loss_fn(p):
                    return self._score_fn(p, state, x, y, fm, lm, True,
                                          sub, carries=None)
                (loss, (new_state, _)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
                gsum = jax.tree_util.tree_map(jnp.add, gsum, grads)
                if plan is not None:
                    # the accumulator carries in the ZeRO layout: micro-
                    # batch grads reduce-scatter into it instead of ever
                    # materializing whole per chip
                    gsum = plan.constrain_grads(gsum)
                return (gsum, new_state), loss

            zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
            (gsum, state), losses = jax.lax.scan(
                body, (zeros, state), (xs, ys, fms, lms, subs))
            grads = jax.tree_util.tree_map(
                lambda g: g / subs.shape[0], gsum)
            new_params, new_opt, updates = apply_update(
                tx, grads, opt_state, params, plan)
            if constrained:
                new_params = apply_constraints(layer_map, new_params)
            if plan is not None:
                new_params = plan.constrain_params(new_params)
                new_opt = plan.constrain_opt(new_opt, new_params)
                state = plan.constrain_replicated(state)
            if with_stats:
                return (new_params, new_opt, state, jnp.mean(losses),
                        grads, updates)
            return new_params, new_opt, state, jnp.mean(losses)

        return jax.jit(kaccum, donate_argnums=(0, 1, 2))

    def _get_accum_step(self, with_stats=False):
        sig = ("accum", with_stats)
        if sig not in self._scan_step:
            self._scan_step[sig] = self._make_accum_step(with_stats)
        return self._scan_step[sig]

    def _fit_epoch_accum(self, iterator, K):
        """One optimizer step per K micro-batches (gradient accumulation).
        Iteration counting follows DL4J's meaning (one iteration = one
        optimizer step); a ragged tail (< K same-shape batches) still
        accumulates into one step with the correct 1/len mean. Gradient
        listeners receive the AVERAGED per-step grads/updates (lockstep
        — wants_gradients forces defer=False below, so iteration_count
        at dispatch is the step being reported)."""
        from deeplearning4j_tpu.monitor import xla as xla_ledger
        rng = jax.random.PRNGKey(self.conf.seed
                                 + 7919 * (self.epoch_count + 1))
        grad_listeners = [lst for lst in self.listeners
                          if getattr(lst, "wants_gradients", False)]
        sigs_seen = set()
        warned_partial = [False]
        last_sync = [None]

        def fetch(p):
            return float(p[0])      # the chunk's one blocking fetch

        def notify(p, score):
            _, bs, etl_ms, capture, grads, updates, rec = p
            self._score = score
            if xla_ledger.enabled():
                now = time.perf_counter()
                if rec is not None and last_sync[0] is not None:
                    xla_ledger.observe_step(rec, now - last_sync[0])
                last_sync[0] = now
            _record_iteration(self._score, bs)
            for lst in capture:
                lst.on_gradients(self, self.iteration_count,
                                 self.epoch_count, grads, updates)
            for lst in self.listeners:
                lst.iteration_done(self, self.iteration_count,
                                   self.epoch_count, self._score, etl_ms,
                                   bs)
            self.iteration_count += 1
            return 1

        def stage(group):
            nonlocal rng
            if len(group) < K and not warned_partial[0]:
                # _run_scan_pipeline only groups CONSECUTIVE same-shape
                # batches: a shape change (e.g. a non-drop-last partial
                # tail) cuts the accumulation group short, and the short
                # group still takes ONE full-learning-rate optimizer step
                # with the mean of len(group) gradients — K is silently
                # not honored for it. Surface that once.
                warned_partial[0] = True
                cause = ("the micro-batch shape changed mid-epoch (use "
                         "drop_last or padded iterators for uniform "
                         "shapes)" if len(sigs_seen) > 1
                         else "the epoch ended mid-group")
                log.warning(
                    "fit(accumulate_steps=%d): dispatching an accumulation "
                    "group of only %d micro-batch(es) because %s; the "
                    "partial group takes one full-learning-rate step with "
                    "the 1/%d gradient mean", K, len(group), cause,
                    len(group))
            subs = []
            for _ in group:
                rng, sub = jax.random.split(rng)
                subs.append(sub)
            xs, ys, fms, lms = self._stage_stacked(group)
            bs = _ds_examples(group[0]) * len(group)
            return xs, ys, fms, lms, jnp.stack(subs), bs, len(group)

        def launch(staged, etl_ms):
            xs, ys, fms, lms, subs_d, bs, n = staged
            capture = [lst for lst in grad_listeners
                       if lst.should_capture(self.iteration_count)]
            kstep = self._get_accum_step(with_stats=bool(capture))
            out = kstep(self.params, self.opt_state, self.state, xs, ys,
                        fms, lms, subs_d)
            grads = updates = None
            if capture:
                (self.params, self.opt_state, self.state, loss, grads,
                 updates) = out
            else:
                self.params, self.opt_state, self.state, loss = out
            rec = None
            if xla_ledger.enabled():
                key = (id(kstep), xla_ledger.shape_key((xs, ys, fms, lms)))
                fresh = key not in self._ledger_cache
                rec = xla_ledger.capture_cached(
                    self._ledger_cache, key,
                    "mln/accum_step", kstep,
                    (self.params, self.opt_state, self.state, xs, ys, fms,
                     lms, subs_d), examples_per_call=bs,
                    steps_per_call=n)
                if fresh:
                    last_sync[0] = None   # exclude the AOT compile interval
            return loss, bs, etl_ms, capture, grads, updates, rec

        def sig_of(ds):
            s = (np.shape(ds.features), np.shape(ds.labels),
                 None if ds.features_mask is None
                 else np.shape(ds.features_mask),
                 None if ds.labels_mask is None
                 else np.shape(ds.labels_mask))
            sigs_seen.add(s)
            return s

        # unlike scan-fit, accumulation cannot fall back to per-call for
        # model-reading listeners (that would change the optimization) —
        # it drops the one-chunk deferral instead so each callback sees
        # the params of the step it reports
        self._fit_chunk = _run_scan_pipeline(
            iterator, K, sig_of=sig_of, examples_of=_ds_examples,
            stage=stage, launch=launch, fetch=fetch, notify=notify,
            defer=not _scan_incompatible_listeners(self.listeners),
            first_chunk=self._fit_chunk)

    def _get_scan_step(self, fmask, lmask, K):
        sig = (fmask is not None, lmask is not None, K)
        if sig not in self._scan_step:
            self._scan_step[sig] = self._make_scan_step(*sig)
        return self._scan_step[sig]

    def _fit_epoch_scan(self, iterator, K):
        """Input-pipelined epoch: group consecutive same-shape batches into
        chunks of K, stack host-side, run one scan-of-K jit per chunk, and
        defer the loss fetch by one chunk so stacking/dispatch of chunk i+1
        overlaps chunk i's device compute. Ragged tails (or a shape change
        mid-epoch) fall back to per-call steps for those batches."""
        if _scan_incompatible_listeners(self.listeners):
            return self._fit_epoch(iterator)
        from deeplearning4j_tpu.monitor import xla as xla_ledger
        rng = jax.random.PRNGKey(self.conf.seed + 7919 * (self.epoch_count + 1))
        last_sync = [None]   # previous chunk-sync stamp: chunk wall clock

        def fetch(p):
            return np.asarray(p[0])             # single blocking fetch/chunk

        def notify(p, arr):
            _, bs, etl_ms, rec = p
            if xla_ledger.enabled():
                # steady-state chunk wall time = spacing between chunk
                # syncs (the pipelined path has no un-overlapped "this
                # chunk only" interval to time; the first chunk is
                # skipped). The stamp advances on EVERY chunk — a ragged
                # tail (rec None) must not leak its wall time into the
                # next scan chunk's interval.
                now = time.perf_counter()
                if rec is not None and last_sync[0] is not None:
                    xla_ledger.observe_step(rec, now - last_sync[0])
                last_sync[0] = now
            for loss in arr:
                # graftlint: disable=host-sync-in-hot-path -- chunk losses are already host-resident (fetch() above IS the deferred chunk sync); this is per-iteration bookkeeping
                self._score = float(loss)
                _record_iteration(self._score, bs)
                for lst in self.listeners:
                    lst.iteration_done(self, self.iteration_count,
                                       self.epoch_count, self._score,
                                       etl_ms, bs)
                self.iteration_count += 1
                etl_ms = 0.0
            return len(arr)

        def stage(group):
            nonlocal rng
            subs = []
            for _ in group:
                rng, sub = jax.random.split(rng)
                subs.append(sub)
            ds0 = group[0]
            bs = _ds_examples(ds0)
            if len(group) < K:
                # ragged tail / shape-change remainder: reuse the already
                # compiled per-call step rather than compiling a one-off
                # scan-of-len(group) program
                tail = [self._shard_batch(
                    self._stage_x(ds.features),
                    _as_jnp(ds.labels, self._compute_dtype),
                    _as_jnp(ds.features_mask),
                    _as_jnp(ds.labels_mask)) for ds in group]
                return tail, subs, bs, ds0
            return self._stage_stacked(group), jnp.stack(subs), bs, None

        def launch(staged, etl_ms):
            inputs, subs, bs, tail_of = staged
            if tail_of is not None:
                step = self._get_train_step(tail_of.features_mask,
                                            tail_of.labels_mask, None)
                losses = []
                for (txs, tys, tfm, tlm), sub in zip(inputs, subs):
                    out = step(self.params, self.opt_state, self.state,
                               txs, tys, tfm, tlm, sub, None)
                    self.params, self.opt_state, self.state, loss, _ = out
                    losses.append(loss)
                return jnp.stack(losses), bs, etl_ms, None
            xs, ys, fms, lms = inputs
            n = int(subs.shape[0])
            kstep = self._get_scan_step(fms, lms, n)
            (self.params, self.opt_state, self.state,
             losses) = kstep(self.params, self.opt_state, self.state,
                             xs, ys, fms, lms, subs)
            rec = None
            if xla_ledger.enabled():
                key = (id(kstep),
                       xla_ledger.shape_key((xs, ys, fms, lms)))
                fresh = key not in self._ledger_cache
                rec = xla_ledger.capture_cached(
                    self._ledger_cache, key,
                    "mln/scan_step", kstep,
                    (self.params, self.opt_state, self.state, xs, ys,
                     fms, lms, subs),
                    examples_per_call=bs * n, steps_per_call=n)
                if fresh:
                    # the capture's AOT compile sat inside this
                    # inter-chunk interval — restart the MFU clock so
                    # it can't read as a slow chunk
                    last_sync[0] = None
            return losses, bs, etl_ms, rec

        def sig_of(ds):
            return (np.shape(ds.features), np.shape(ds.labels),
                    None if ds.features_mask is None
                    else np.shape(ds.features_mask),
                    None if ds.labels_mask is None
                    else np.shape(ds.labels_mask))

        self._fit_chunk = _run_scan_pipeline(
            iterator, K, sig_of=sig_of, examples_of=_ds_examples,
            stage=stage, launch=launch, fetch=fetch, notify=notify,
            first_chunk=self._fit_chunk)

    def _fit_epoch_tbptt(self, iterator):
        """Truncated BPTT: chunk the time axis, carry RNN state across chunks,
        stop gradients at chunk boundaries (doTruncatedBPTT, :1315-1317)."""
        fwd = self.conf.tbptt_fwd_length
        rng = jax.random.PRNGKey(self.conf.seed + 104729 * (self.epoch_count + 1))
        for ds in iterator:
            T = ds.features.shape[1]
            carries = {}
            for t0 in range(0, T, fwd):
                t1 = min(t0 + fwd, T)
                x = ds.features[:, t0:t1]
                y = ds.labels[:, t0:t1] if ds.labels is not None and ds.labels.ndim >= 3 else ds.labels
                fm = ds.features_mask[:, t0:t1] if ds.features_mask is not None else None
                lm = ds.labels_mask[:, t0:t1] if ds.labels_mask is not None else None
                rng, sub = jax.random.split(rng)
                step = self._get_train_step(fm, lm, carries)
                txs, tys, tfm, tlm = self._shard_batch(
                    self._stage_x(x), _as_jnp(y, self._compute_dtype),
                    _as_jnp(fm), _as_jnp(lm))
                self.params, self.opt_state, self.state, loss, new_carries = step(
                    self.params, self.opt_state, self.state,
                    txs, tys, tfm, tlm, sub, carries)
                # stop gradient across chunk boundary
                carries = jax.tree_util.tree_map(jax.lax.stop_gradient, new_carries)
                # graftlint: disable=host-sync-in-hot-path -- the tbptt chunk's one budgeted loss fetch
                self._score = float(loss)
                _record_iteration(self._score, int(np.shape(x)[0]))
                for lst in self.listeners:
                    lst.iteration_done(self, self.iteration_count,
                                       self.epoch_count, self._score, 0.0,
                                       int(np.shape(x)[0]))
                self.iteration_count += 1

    # ------------------------------------------------------------- scoring
    def score(self, dataset: Optional[DataSet] = None) -> float:
        """Last training score, or score on a given DataSet (DL4J score())."""
        if dataset is None:
            return self._score if self._score is not None else float("nan")
        loss, _ = self._score_fn(self.params, self.state,
                                 _as_jnp(dataset.features, self._compute_dtype),
                                 _as_jnp(dataset.labels, self._compute_dtype),
                                 _as_jnp(dataset.features_mask),
                                 _as_jnp(dataset.labels_mask), False, None)
        return float(loss)

    def evaluate(self, data, batch_size: int = 32):
        """Classification evaluation (DL4J evaluate(DataSetIterator))."""
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        return self._evaluate_with(Evaluation(), data, batch_size)

    def evaluate_roc(self, data, batch_size: int = 32):
        """Binary ROC evaluation (DL4J evaluateROC(DataSetIterator))."""
        from deeplearning4j_tpu.eval.roc import ROC
        return self._evaluate_with(ROC(), data, batch_size)

    def evaluate_roc_multi_class(self, data, batch_size: int = 32):
        """One-vs-all per-class ROC (DL4J evaluateROCMultiClass)."""
        from deeplearning4j_tpu.eval.roc import ROCMultiClass
        return self._evaluate_with(ROCMultiClass(), data, batch_size)

    def _evaluate_with(self, ev, data, batch_size: int = 32):
        iterator = self._as_iterator(data, batch_size)
        for ds in iterator:
            ev.eval(*_masked_eval_pair(
                np.asarray(ds.labels), np.asarray(self.output(ds.features)),
                ds.labels_mask))
        iterator.reset()
        return ev

    def evaluate_regression(self, data, batch_size: int = 32):
        from deeplearning4j_tpu.eval.regression import RegressionEvaluation
        ev = self._evaluate_with(RegressionEvaluation(), data, batch_size)
        return ev

    # ----------------------------------------------------- recurrent state
    def rnn_time_step(self, x):
        """Stateful single/multi-step streaming inference
        (DL4J rnnTimeStep, MultiLayerNetwork.java:2806). x: (B, F) one step or
        (B, T, F) several steps; recurrent layer state persists across calls."""
        x = _as_jnp(x, self._compute_dtype)
        single = x.ndim == 2
        if single:
            x = x[:, None, :]
        y, _, new_carries = self._forward(self.params, self.state, x, False,
                                          None, carries=self._rnn_carries)
        self._rnn_carries = new_carries
        return y[:, -1, :] if single and y.ndim == 3 else y

    def rnn_clear_previous_state(self):
        self._rnn_carries = {}

    # ------------------------------------------------------------ summary
    def summary(self) -> str:
        """Layer table: name, type, shapes, parameter count
        (MultiLayerNetwork.summary(), MultiLayerNetwork.java:3230)."""
        if self.params is None:
            raise RuntimeError("init() the network before summary()")
        types = self._input_types or self._resolve_types()
        rows = [("idx", "type", "in", "out", "params")]
        total = 0
        for i, layer in enumerate(self.layers):
            in_t = types[i]
            out_t = layer.output_type(in_t)
            n = param_util.num_params(self.params[str(i)])
            total += n
            rows.append((str(i), type(layer).__name__,
                         "x".join(map(str, in_t.shape)),
                         "x".join(map(str, out_t.shape)), f"{n:,}"))
        return param_util.format_param_table(rows, total)

    # ------------------------------------------------------------ memory
    def memory_report(self, batch_size: int = 32, with_compiled: bool = True):
        """Per-layer analytic memory estimate + exact XLA compiled-step HBM
        (DL4J LayerMemoryReport/NetworkMemoryReport analog, exceeded via
        jit(...).compile().memory_analysis())."""
        from deeplearning4j_tpu.util.memory import build_memory_report
        return build_memory_report(self, batch_size, with_compiled)

    # ------------------------------------------------------------ params
    def num_params(self) -> int:
        return param_util.num_params(self.params)

    def params_flat(self):
        """Canonical flat parameter vector (DL4J's flattenedParams view)."""
        return param_util.params_to_flat(self.params)

    def set_params_flat(self, flat):
        self.params = param_util.flat_to_params(flat, self.params)

    def copy(self) -> "MultiLayerNetwork":
        clone = MultiLayerNetwork(self.conf)
        if self.params is not None:
            clone._input_types = self._resolve_types()
            # materialize NEW buffers: the original's arrays are donated by
            # its train step and would be deleted out from under the clone
            clone.params = jax.tree_util.tree_map(
                lambda a: jnp.array(a, copy=True), self.params)
            clone.state = jax.tree_util.tree_map(
                lambda a: jnp.array(a, copy=True), self.state)
            clone._build_optimizer()
        return clone


