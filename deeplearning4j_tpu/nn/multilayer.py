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
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from deeplearning4j_tpu.data.async_iterator import AsyncDataSetIterator
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterator import ArrayDataSetIterator, DataSetIterator
from deeplearning4j_tpu.nn.conf.base import (
    InputType, Kind, LayerConf, preprocess_forward, preprocessed_type,
)
from deeplearning4j_tpu.monitor.scopes import layer_scope
from deeplearning4j_tpu.nn import fit_loop
from deeplearning4j_tpu.nn.conf.network import MultiLayerConfiguration
from deeplearning4j_tpu.nn.fit_loop import (
    _as_jnp, _fit_tbptt_batch, _stage_with_affine,
)
from deeplearning4j_tpu.nn.updaters import NoOp, build_optimizer
from deeplearning4j_tpu.ops import REMAT_KEEP
from deeplearning4j_tpu.util import params as param_util

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


def _required_kind(layer: LayerConf) -> Optional[Kind]:
    name = type(layer).__name__
    if name == "FrozenLayerWrapper":
        return _required_kind(layer.layer)
    return _KIND_BY_CLASS.get(name)


def _layer_call(layer, *, seq, train, remat, params, x, state=None,
                carry=None, rng=None, mask=None, cast=None, name=None):
    """Invoke layer.apply (seq=False) or layer.apply_seq (seq=True), with
    jax.checkpoint rematerialization when remat is on: every traced value
    (params/state/carry/input/rng/mask) is a checkpoint ARGUMENT, only the
    static layer conf and train flag are closed over. ``cast`` (params ->
    params in the compute dtype) is applied INSIDE the rematerialised
    region, so that the cast copy of a layer's weights lives only while
    the layer runs and is not held from the forward pass to the backward.
    The whole call stands under the layer's own scope (``name``: its index
    or its vertex; None: no scope), OUTSIDE the checkpoint, so that the
    forward pass, the forward made again and the backward pass all name
    their layer (`monitor/scopes.py`). Shared by both containers so the
    two forward passes can't drift."""
    def weights(lp):
        if cast is None:
            return lp
        with jax.named_scope("cast"):
            return cast(lp)

    if seq:
        def fn(lp, xx, cc, rr, mm, _l=layer):
            return _l.apply_seq(weights(lp), xx, cc, train=train, rng=rr,
                                mask=mm)
        args = (params, x, carry, rng, mask)
    else:
        def fn(lp, st, xx, rr, mm, _l=layer):
            return _l.apply(weights(lp), st, xx, train=train, rng=rr,
                            mask=mm)
        args = (params, state, x, rng, mask)
    if remat:
        # nothing is kept but what a layer or a kernel names REMAT_KEEP (a
        # result far dearer to make again than to hold: a recurrence's
        # output, the flash forward's output and log-sum-exp)
        fn = jax.checkpoint(
            fn, policy=jax.checkpoint_policies.save_only_these_names(
                REMAT_KEEP))
    if name is None:
        return fn(*args)
    with layer_scope(name):
        return fn(*args)


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
        self._steps: Dict[Any, Any] = {}   # compiled train steps (nn/fit_loop)
        self._output_fn = None
        self._input_affine = None   # (shift, scale) during device-norm fit
        self._affine_fn = None
        self._ledger_cache: Dict[Any, Any] = {}   # monitor.xla programs
        self._plan = None           # active GSPMD ShardingPlan (parallel/plan)

    # ---------------------------------------- what nn/fit_loop.py asks for
    _LEDGER_PREFIX = "mln"
    # the RNG stream: re-keyed every epoch, its own multiplier under tBPTT
    _RNG_MULT, _RNG_MULT_TBPTT, _RNG_PER_EPOCH = 7919, 104729, True

    def _shard_batch(self, *arrs, stacked: bool = False):
        """Place staged batch operands per the active plan — dim 0 (dim
        1 for host-stacked scan/accum chunks) split over the mesh "data"
        axis. Identity without a plan."""
        plan = self._plan
        if plan is None:
            return arrs
        return tuple(plan.shard_batch(a, stacked=stacked) for a in arrs)

    def _operands(self, ds):
        """One DataSet -> (x, y, fmask, lmask) on the device, per the
        active plan: the ONE staging rule of the per-call steps."""
        return self._shard_batch(
            _stage_with_affine(self, ds.features),
            _as_jnp(ds.labels, self._compute_dtype),
            _as_jnp(ds.features_mask), _as_jnp(ds.labels_mask))

    def _stage_stacked(self, group):
        """K same-shape host batches -> (xs, ys, fms, lms) stacked on a
        new leading axis, on the device, per the active plan: the ONE
        staging rule of the scan and accumulation chunks."""
        ds0 = group[0]
        stack = lambda get, dt=None: (
            None if get(ds0) is None else
            _as_jnp(np.stack([np.asarray(get(d)) for d in group]), dt))
        xs = None if ds0.features is None else _stage_with_affine(
            self, np.stack([np.asarray(d.features) for d in group]))
        return self._shard_batch(
            xs, stack(lambda d: d.labels, self._compute_dtype),
            stack(lambda d: d.features_mask),
            stack(lambda d: d.labels_mask), stacked=True)

    @staticmethod
    def _batch_sig(ds):
        shape = lambda a: None if a is None else np.shape(a)
        return (np.shape(ds.features), np.shape(ds.labels),
                shape(ds.features_mask), shape(ds.labels_mask))

    @staticmethod
    def _batch_examples(ds) -> int:
        """Rows of one DataSet batch (the `examples=` of a train/chunk)."""
        return int(np.shape(ds.features)[0])

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
        self._steps = {}     # force re-trace

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
        # a layer casts its own weights, under its own scope; under
        # gradient checkpointing inside its rematerialised region
        # (`_layer_call`)
        remat = train and self.conf.gradient_checkpointing
        x = _as_jnp(x, self._compute_dtype)
        cur_type = self.conf.input_type
        n = len(self.layers) if upto is None else upto
        new_state = dict(state)
        new_carries = {}
        acts = []
        for i, layer in enumerate(self.layers[:n]):
            need = _required_kind(layer)
            key = str(i)
            if need is not None and cur_type.kind != need:
                with layer_scope(key), jax.named_scope("layout"):
                    x = preprocess_forward(cur_type, need, x)
                cur_type = preprocessed_type(cur_type, need)
            sub_rng = None
            if rng is not None:
                rng, sub_rng = jax.random.split(rng)
            mask = fmask if cur_type.kind == Kind.RNN else None
            layer_params = params[key]
            if layer.weight_noise is not None:
                # the noise is drawn in the compute dtype
                with layer_scope(key), jax.named_scope("cast"):
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
                    layer, name=key, seq=True, train=train, remat=remat,
                    params=layer_params, x=x, carry=carries.get(key),
                    rng=sub_rng, mask=mask, cast=self._cast_params)
                new_carries[key] = carry
                new_state[key] = state[key]
            else:
                y, s = _layer_call(
                    layer, name=key, seq=False, train=train, remat=remat,
                    params=layer_params, x=x, state=state[key],
                    rng=sub_rng, mask=mask, cast=self._cast_params)
                new_state[key] = s
            x = y
            cur_type = layer.output_type(cur_type)
            if collect:
                acts.append(x)
        if upto is not None and upto < len(self.layers):
            head = self.layers[upto]
            need = _required_kind(head)
            if need is not None and cur_type.kind != need:
                with layer_scope(upto), jax.named_scope("layout"):
                    x = preprocess_forward(cur_type, need, x)
        return (acts if collect else x), new_state, new_carries

    def _score_fn(self, params, state, x, y, fmask, lmask, train, rng,
                  carries=None):
        """Loss on a batch: last-layer score + regularization
        (computeGradientAndScore, MultiLayerNetwork.java:2360)."""
        if not self.layers or not hasattr(self.layers[-1], "score"):
            raise ValueError("Last layer must be an output/loss layer with a "
                             "score() method to compute training loss")
        # forward up to (but excluding) the output layer; it casts the
        # weights itself, a layer at a time
        head, last = self.layers[-1], str(len(self.layers) - 1)
        feat, new_state, new_carries = self._forward(
            params, state, x, train, rng, fmask, carries,
            upto=len(self.layers) - 1)
        out_mask = lmask if lmask is not None else (
            fmask if _required_kind(head) == Kind.RNN else None)
        with layer_scope(last):
            with jax.named_scope("cast"):
                head_params = self._cast_params(params[last])
            loss = head.score(head_params, feat,
                              _as_jnp(y, self._compute_dtype), train=train,
                              rng=None, mask=out_mask)
        reg = jnp.asarray(0.0, jnp.float32)
        for i, layer in enumerate(self.layers):
            with layer_scope(i), jax.named_scope("reg"):
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
    def _make_scan_step(self):
        """The scan-of-K compiled step fit() runs (nn/fit_loop.py)."""
        return fit_loop.build_step(self, "kstep")

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
        (see nn/fit_loop.build_step; mutually exclusive with scan_steps > 1,
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
        host/device overlap changes. Default: 10 on TPU (the path both
        cells of the benchmark run: fit_window_rate_ratio 99.5 % and
        99.7 %, ledger PR 28; scan against per-call has no cell yet,
        ROADMAP W5), 1 on CPU; $DL4J_TPU_SCAN_STEPS overrides.

        Intended for dispatch-bound TPU loops. Caveat: XLA:CPU pessimizes
        convolutions inside scan, so conv nets on CPU should keep
        scan_steps=1.

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
        return fit_loop.fit(self, self._as_iterator(data, batch_size),
                            epochs, scan_steps, accumulate_steps, plan,
                            prefetch=prefetch)

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

    def _fit_source(self, iterator, stacking, prefetch=None):
        """The source policy of fit(): a plain source goes behind an
        AsyncDataSetIterator, like the reference wraps every fit in an
        async iterator by default (MultiLayerNetwork.java:1272-1274);
        already-async and async_supported=False sources pass through.
        Scan-fit and accumulation STACK K host batches before one
        transfer — the wrap must not device_put per batch there (a
        device array would round-trip back through the host)."""
        if prefetch is None:
            from deeplearning4j_tpu.data.async_iterator import (
                fit_prefetch_enabled,
            )
            prefetch = fit_prefetch_enabled()
        if not prefetch or isinstance(iterator, AsyncDataSetIterator) \
                or not getattr(iterator, "async_supported", True):
            return iterator
        return AsyncDataSetIterator(
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

    def _epoch_batches(self, source, stacking):
        return source

    def _fit_epoch_tbptt(self, batches, rng):
        """Truncated BPTT: chunk the time axis (sliced BEFORE staging),
        carry RNN state across chunks, stop gradients at chunk boundaries
        (doTruncatedBPTT, :1315-1317)."""
        fwd = self.conf.tbptt_fwd_length
        for ds in batches:
            T = ds.features.shape[1]

            def chunks(ds=ds, T=T):
                cut_labels = ds.labels is not None and ds.labels.ndim >= 3
                for t0 in range(0, T, fwd):
                    cut = lambda a: None if a is None else a[:, t0:t0 + fwd]
                    yield self._operands(DataSet(
                        cut(ds.features),
                        cut(ds.labels) if cut_labels else ds.labels,
                        cut(ds.features_mask), cut(ds.labels_mask)))

            rng = _fit_tbptt_batch(self, chunks(), rng, 0.0,
                                   self._batch_examples(ds))
        return rng

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


