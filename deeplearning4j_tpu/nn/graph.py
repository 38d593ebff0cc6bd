"""ComputationGraph — the DAG network container.

Parity target: DL4J nn/graph/ComputationGraph.java (3904 LoC):
- topological order        :152,401 -> ComputationGraphConfiguration.topological_order()
- fit(MultiDataSetIterator):1015    -> fit(): jitted train step over the DAG
- feedForward              :1409    -> feed_forward(): dict of all activations
- output                   :1759    -> output()
- multi-input / multi-output with per-output losses summed into one score

The DAG executes inside ONE jit trace — XLA sees the whole graph and fuses
across vertices (DL4J walks GraphVertex objects at runtime instead).
"""
from __future__ import annotations

import contextlib
import logging
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from deeplearning4j_tpu.data.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.monitor.scopes import layer_scope
from deeplearning4j_tpu.nn.conf.base import (
    InputType, Kind, LayerConf, preprocess_forward, preprocessed_type,
)
from deeplearning4j_tpu.nn.conf.graph_vertices import GraphVertexConf
from deeplearning4j_tpu.nn.conf.network import ComputationGraphConfiguration
from deeplearning4j_tpu.nn import fit_loop
from deeplearning4j_tpu.nn.fit_loop import (
    _as_jnp, _fit_tbptt_batch, _stage_with_affine,
)
from deeplearning4j_tpu.nn.multilayer import _required_kind
from deeplearning4j_tpu.nn.updaters import NoOp, build_optimizer
from deeplearning4j_tpu.util import params as param_util

log = logging.getLogger("deeplearning4j_tpu")


def _scoped(scope: Optional[str]):
    """The `jax.named_scope` a vertex asked for, or nothing. It stands
    OUTSIDE the vertex's own layer scope (`monitor/scopes.py`)."""
    return contextlib.nullcontext() if scope is None \
        else jax.named_scope(scope)


class ComputationGraph:
    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        self.params: Optional[dict] = None
        self.state: Optional[dict] = None
        self.opt_state = None
        self.listeners: List = []
        self.iteration_count = 0
        self.epoch_count = 0
        self._score: Optional[float] = None
        self._param_dtype = jnp.dtype(conf.dtype)
        self._compute_dtype = jnp.dtype(conf.compute_dtype or conf.dtype)
        self._topo = conf.topological_order()
        self._vertex_types: Optional[Dict[str, InputType]] = None
        self._tx = None
        self._steps: Dict[Any, Any] = {}   # compiled train steps (nn/fit_loop)
        self._output_fn = None
        self._input_affine = None   # (shift, scale) during device-norm fit
        self._affine_fn = None
        self._ledger_cache: Dict[Any, Any] = {}   # monitor.xla programs
        self._plan = None           # active GSPMD ShardingPlan (parallel/plan)

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    # ----------------------------------------------------------- init/types
    def _resolve_types(self) -> Dict[str, InputType]:
        """InputType for every vertex output (DL4J getLayerActivationTypes)."""
        if len(self.conf.input_types) != len(self.conf.network_inputs):
            raise ValueError("ComputationGraphConfiguration.input_types must "
                             "match network_inputs")
        types: Dict[str, InputType] = dict(zip(self.conf.network_inputs,
                                               self.conf.input_types))
        self._pre_kind: Dict[str, Optional[Kind]] = {}
        for name in self._topo:
            vd = self.conf.vertices[name]
            in_types = [types[i] for i in vd.inputs]
            if isinstance(vd.vertex, GraphVertexConf):
                self._pre_kind[name] = None
                types[name] = vd.vertex.output_type(*in_types)
            else:
                need = _required_kind(vd.vertex)
                self._pre_kind[name] = need
                t = in_types[0]
                if need is not None and t.kind != need:
                    t = preprocessed_type(t, need)
                types[name] = vd.vertex.output_type(t)
        return types

    def init(self, seed: Optional[int] = None):
        seed = self.conf.seed if seed is None else seed
        key = jax.random.PRNGKey(seed)
        for name in self.conf.network_outputs:
            if name not in self.conf.vertices:
                raise ValueError(f"Unknown output vertex '{name}'")
            v = self.conf.vertices[name].vertex
            if not hasattr(v, "score"):
                raise ValueError(
                    f"Output vertex '{name}' ({type(v).__name__}) must be an "
                    "output/loss layer with a score() method")
        from deeplearning4j_tpu.nn.multilayer import validate_layer_conf
        for vd in self.conf.vertices.values():
            if isinstance(vd.vertex, LayerConf):
                validate_layer_conf(vd.vertex)
        self._vertex_types = self._resolve_types()
        params: Dict[str, dict] = {}
        state: Dict[str, dict] = {}
        for name in self._topo:
            vd = self.conf.vertices[name]
            if isinstance(vd.vertex, GraphVertexConf):
                continue
            key, sub = jax.random.split(key)
            in_t = self._vertex_types[vd.inputs[0]]
            need = self._pre_kind[name]
            if need is not None and in_t.kind != need:
                in_t = preprocessed_type(in_t, need)
            if vd.params_of is not None:
                # a sharer holds no parameters: its owner's leaf is the one
                # leaf (of what a sharer's init makes only the state is kept)
                init = lambda k, v=vd.vertex, t=in_t: v.init(
                    k, t, self._param_dtype)
                shapes = lambda t: jax.tree_util.tree_map(
                    lambda a: (a.shape, a.dtype), t)
                if shapes(jax.eval_shape(init, sub)[0]) \
                        != shapes(params[vd.params_of]):
                    raise ValueError(
                        f"vertex '{name}' cannot share the parameters of "
                        f"'{vd.params_of}': the shapes differ")
                state[name] = init(sub)[1]
                continue
            p, s = vd.vertex.init(sub, in_t, self._param_dtype)
            params[name] = p
            state[name] = s
        self.params = params
        self.state = state
        self._build_optimizer()
        return self

    def _build_optimizer(self):
        transforms = {"__global__": build_optimizer(
            self.conf.updater, self.conf.grad_clip_norm, self.conf.grad_clip_value)}
        labels = {}
        any_override = False
        for name, p in self.params.items():
            vd = self.conf.vertices[name]
            lab = "__global__"
            if getattr(vd.vertex, "frozen", False) or \
                    type(vd.vertex).__name__ == "FrozenLayerWrapper":
                lab = "__noop__"
                transforms.setdefault("__noop__", NoOp().to_optax())
                any_override = True
            elif getattr(vd.vertex, "updater", None) is not None:
                lab = f"v_{name}"
                transforms[lab] = build_optimizer(
                    vd.vertex.updater, self.conf.grad_clip_norm,
                    self.conf.grad_clip_value)
                any_override = True
            labels[name] = jax.tree_util.tree_map(lambda _: lab, p)
        if any_override:
            self._tx = optax.multi_transform(transforms, labels)
        else:
            self._tx = transforms["__global__"]
        self.opt_state = self._tx.init(self.params)
        self._steps = {}

    # -------------------------------------------------------------- forward
    def _cast_params(self, params):
        if self._compute_dtype == self._param_dtype:
            return params
        def cast(a):
            if jnp.issubdtype(a.dtype, jnp.floating):
                return a.astype(self._compute_dtype)
            return a
        return jax.tree_util.tree_map(cast, params)

    @staticmethod
    def _vertex_out_mask(vertex, in_masks, xs, out_type):
        """Mask propagation through a non-layer graph vertex (the analog of
        DL4J GraphVertex.feedForwardMaskArrays): time-collapsing vertices
        drop the mask, DuplicateToTimeSeries adopts its reference input's
        mask, Reverse flips it, Stack/Unstack concat/slice along batch,
        everything else forwards the first non-None input mask."""
        if out_type.kind != Kind.RNN:
            return None
        vname = type(vertex).__name__
        if vname == "DuplicateToTimeSeriesVertex":
            return in_masks[1]
        if vname == "ReverseTimeSeriesVertex":
            m = in_masks[0]
            return None if m is None else jnp.flip(m, axis=1)
        if vname == "StackVertex":
            # output batch is the concat of input batches; so is its mask
            # (DL4J StackVertex.feedForwardMaskArrays). All-None stays
            # None; a mixed case substitutes all-ones for unmasked inputs.
            if all(m is None for m in in_masks):
                return None
            return jnp.concatenate(
                [jnp.ones(x.shape[:2], jnp.float32) if m is None else m
                 for m, x in zip(in_masks, xs)], axis=0)
        if vname == "UnstackVertex":
            m = in_masks[0]
            if m is None:
                return None
            n = m.shape[0] // vertex.stack_size
            return m[vertex.from_idx * n:(vertex.from_idx + 1) * n]
        if vname == "TimeSliceVertex":
            m = in_masks[0]
            return None if m is None else m[:, :vertex.steps]
        return next((m for m in in_masks if m is not None), None)

    def _forward(self, params, state, inputs: Sequence, train, rng,
                 fmasks: Optional[Sequence] = None, stash_pre: bool = False,
                 carries: Optional[dict] = None):
        """Execute the DAG. Returns (activations dict, new_state,
        new_carries, per-vertex mask dict).

        Masks are routed per input path (ComputationGraph.setLayerMaskArrays
        semantics): each vertex sees the mask propagated from ITS inputs,
        not a globally shared one — a multi-input graph with differently
        masked sequence inputs applies each mask where it belongs.

        With `carries` (a dict, possibly empty), recurrent layer vertices
        run stateful via apply_seq and their final carry is returned — the
        graph analogs of rnnTimeStep / tBPTT stored state
        (ComputationGraph.java:2720, :2894).

        With stash_pre=True, the pre-head activation of each output vertex is
        stored under '__pre__<name>' so score() sees features, not
        post-activation output (the analog of DL4J output layers keeping
        preOutput for computeScore)."""
        from deeplearning4j_tpu.nn.multilayer import (
            _is_stateful_recurrent, _layer_call,
        )
        if self._vertex_types is None:
            self._vertex_types = self._resolve_types()
        # a layer casts its own weights, under its own scope; under
        # gradient checkpointing inside its rematerialised region
        # (`_layer_call`)
        remat = train and self.conf.gradient_checkpointing
        acts: Dict[str, Any] = {}
        masks: Dict[str, Any] = {}
        for i, name in enumerate(self.conf.network_inputs):
            acts[name] = _as_jnp(inputs[i], self._compute_dtype)
            masks[name] = (None if fmasks is None or i >= len(fmasks)
                           else fmasks[i])
        new_state = {}
        new_carries = {}
        out_set = set(self.conf.network_outputs) if stash_pre else ()
        for name in self._topo:
            vd = self.conf.vertices[name]
            xs = [acts[i] for i in vd.inputs]
            in_masks = [masks[i] for i in vd.inputs]
            if isinstance(vd.vertex, GraphVertexConf):
                with _scoped(vd.scope), layer_scope(name):
                    acts[name] = vd.vertex.apply(*xs)
                masks[name] = self._vertex_out_mask(
                    vd.vertex, in_masks, xs, self._vertex_types[name])
                continue
            x = xs[0]
            need = self._pre_kind[name]
            src_t = self._input_type_of(vd.inputs[0])
            if need is not None and src_t.kind != need:
                with _scoped(vd.scope), layer_scope(name), \
                        jax.named_scope("layout"):
                    x = preprocess_forward(src_t, need, x)
            sub_rng = None
            if rng is not None:
                rng, sub_rng = jax.random.split(rng)
            m = in_masks[0] if need == Kind.RNN else None
            if name in out_set:
                acts["__pre__" + name] = x
            layer_params = params.get(vd.params_of or name, {})
            if getattr(vd.vertex, "weight_noise", None) is not None:
                # the noise is drawn in the compute dtype
                with _scoped(vd.scope), layer_scope(name), \
                        jax.named_scope("cast"):
                    layer_params = self._cast_params(layer_params)
            if train and sub_rng is not None and \
                    getattr(vd.vertex, "weight_noise", None) is not None:
                from deeplearning4j_tpu.nn.regularization import (
                    apply_weight_noise,
                )
                sub_rng, noise_rng = jax.random.split(sub_rng)
                layer_params = apply_weight_noise(vd.vertex, layer_params,
                                                  train, noise_rng)
            # per-vertex jax.checkpoint under gradient_checkpointing:
            # backward recomputes this vertex's activations (HBM for
            # FLOPs); inference forwards are untouched (train only)
            if carries is not None and _is_stateful_recurrent(vd.vertex):
                with _scoped(vd.scope):
                    y, carry = _layer_call(
                        vd.vertex, name=name, seq=True, train=train,
                        remat=remat, params=layer_params, x=x,
                        carry=carries.get(name), rng=sub_rng, mask=m,
                        cast=self._cast_params)
                new_carries[name] = carry
                new_state[name] = state.get(name, {})
            else:
                with _scoped(vd.scope):
                    y, s = _layer_call(
                        vd.vertex, name=name, seq=False, train=train,
                        remat=remat, params=layer_params, x=x,
                        state=state.get(name, {}), rng=sub_rng, mask=m,
                        cast=self._cast_params)
                new_state[name] = s
            acts[name] = y
            masks[name] = (in_masks[0]
                           if self._vertex_types[name].kind == Kind.RNN
                           else None)
        return acts, new_state, new_carries, masks

    def _input_type_of(self, name: str) -> InputType:
        return self._vertex_types[name]

    # --------------------------------------------------------------- output
    def output(self, *inputs, train: bool = False):
        """Multi-output inference (ComputationGraph.output, :1759-1810)."""
        if self.params is None:
            raise RuntimeError(
                "Network is not initialized — call init() first")
        if self._output_fn is None:
            @jax.jit
            def _out(params, state, inputs):
                acts, _, _, _ = self._forward(params, state, inputs, False,
                                              None)
                return tuple(acts[o] for o in self.conf.network_outputs)
            self._output_fn = _out
        outs = self._output_fn(self.params, self.state,
                               tuple(_as_jnp(x, self._compute_dtype) for x in inputs))
        return outs[0] if len(outs) == 1 else outs

    def feed_forward(self, *inputs, train: bool = False):
        acts, _, _, _ = self._forward(self.params, self.state, inputs, train,
                                      None)
        return acts

    # ------------------------------------------------------------------ fit
    def _score_fn(self, params, state, inputs, labels, fmasks, lmasks, train,
                  rng, carries=None):
        total, aux, _ = self._score_parts(params, state, inputs, labels,
                                          fmasks, lmasks, train, rng, carries)
        return total, aux

    def _score_parts(self, params, state, inputs, labels, fmasks, lmasks,
                     train, rng, carries=None):
        """`_score_fn` and, third, each output's own loss (unweighted, in
        `network_outputs`' order): the score is their sum under the
        configuration's ``output_weights`` plus the regularization."""
        # the forward casts the weights itself, a vertex at a time
        acts, new_state, new_carries, masks = self._forward(
            params, state, inputs, train, rng, fmasks, stash_pre=True,
            carries=carries)
        weights = self.conf.output_weights
        total = jnp.asarray(0.0, jnp.float32)
        parts = []
        for i, out_name in enumerate(self.conf.network_outputs):
            vd = self.conf.vertices[out_name]
            feat = acts["__pre__" + out_name]
            lmask = None
            if lmasks is not None and lmasks[i] is not None:
                lmask = lmasks[i]
            elif self._vertex_types[out_name].kind == Kind.RNN:
                # RNN output with no label mask: fall back to the feature
                # mask propagated along THIS output's input path
                lmask = masks[vd.inputs[0]]
            lab = _as_jnp(labels[i], self._compute_dtype)
            with _scoped(vd.scope), layer_scope(out_name):
                with jax.named_scope("cast"):
                    head_params = self._cast_params(
                        params.get(vd.params_of or out_name, {}))
                s = vd.vertex.score(head_params, feat, lab, train=train,
                                    rng=None, mask=lmask)
            # keep f64 under float64 gradient checking; f32 otherwise
            s = s.astype(jnp.promote_types(jnp.float32, s.dtype))
            parts.append(s)
            total = total + (s * weights[i] if weights else s)
        for name, p in params.items():
            vd = self.conf.vertices[name]
            if isinstance(vd.vertex, LayerConf):
                with layer_scope(name), jax.named_scope("reg"):
                    total = total + vd.vertex.regularization_score(p)
        return total, (new_state, new_carries), tuple(parts)

    def _make_scan_step(self):
        """The scan-of-K compiled step fit() runs (nn/fit_loop.py)."""
        return fit_loop.build_step(self, "kstep")

    def fit(self, data, epochs: int = 1, scan_steps: Optional[int] = None,
            accumulate_steps: int = 1, plan=None):
        """Train on a MultiDataSet / DataSet / iterator of either
        (ComputationGraph.fit, :1015).

        scan_steps > 1 fuses that many optimizer steps into one jit via
        lax.scan with a one-chunk-deferred loss fetch (input-pipelined fit;
        see MultiLayerNetwork.fit) — bit-identical math/RNG to the per-call
        path. Default: 10 on TPU, 1 on CPU (see MultiLayerNetwork.fit);
        $DL4J_TPU_SCAN_STEPS overrides.

        accumulate_steps > 1: gradient accumulation — K micro-batch
        gradients averaged into ONE optimizer step inside one jit (see
        MultiLayerNetwork.fit; mutually exclusive with scan_steps > 1,
        not applicable to tbptt)."""
        return fit_loop.fit(self, data, epochs, scan_steps,
                            accumulate_steps, plan)

    # ---------------------------------------- what nn/fit_loop.py asks for
    _LEDGER_PREFIX = "graph"
    # the RNG stream: keyed once a fit() and carried over its epochs
    _RNG_MULT, _RNG_MULT_TBPTT, _RNG_PER_EPOCH = 331, 331, False

    def _fit_source(self, data, stacking):
        return data

    def _epoch_batches(self, data, stacking):
        """The source policy of fit(): what it is given, batch by batch;
        behind the prefetch thread on the per-call path only (stage()
        of a chunk stacks K host batches into ONE transfer; the prefetch
        stream's per-batch device_put would round-trip each through the
        host)."""
        return self._iter_data(data) if stacking else self._mds_stream(data)

    def _mds_stream(self, data):
        """MultiDataSet stream for one epoch: a prefetch worker thread
        overlaps host ETL + the bf16 host cast + the H2D transfer with
        device compute (the reference wraps every fit in an async iterator
        by default — MultiLayerNetwork.java:1272-1274, same contract for
        graphs at ComputationGraph.java:1015), DL4J_TPU_PREFETCH_DEPTH
        batches deep (default 2: double-buffered H2D).
        DL4J_TPU_FIT_PREFETCH=0 or DL4J_TPU_PREFETCH_DEPTH=0 disables
        the thread (the latter keeps synchronous staging)."""
        from deeplearning4j_tpu.data.async_iterator import (
            fit_prefetch_enabled, host_cast, prefetch_iterable,
        )
        if not fit_prefetch_enabled() \
                or getattr(data, "async_supported", True) is False:
            return self._iter_data(data)
        cast = self._compute_dtype \
            if np.dtype(self._compute_dtype).itemsize == 2 else None
        # device-norm engaged: features reach the device UNCAST so the
        # affine normalizes the full-precision values (normalize-then-
        # cast); labels still ship 16-bit
        fcast = None if self._input_affine is not None else cast
        # under a GSPMD plan the worker thread stages straight onto the
        # mesh (batch dim over "data"); ragged tails degrade to the
        # default device via the shared fallback (parallel/plan.put_batch)
        # instead of killing the prefetch thread
        if self._plan is not None:
            from deeplearning4j_tpu.parallel.plan import put_batch
            dev = self._plan.batch_sharding()
            put_fn = put_batch
        else:
            dev = jax.local_devices()[0]
            put_fn = jax.device_put

        def stage(mds):
            def put(a):
                return None if a is None else put_fn(a, dev)
            return MultiDataSet(
                tuple(put(host_cast(f, fcast)) for f in mds.features),
                tuple(put(host_cast(l, cast)) for l in mds.labels),
                None if mds.features_masks is None
                else tuple(put(m) for m in mds.features_masks),
                None if mds.labels_masks is None
                else tuple(put(m) for m in mds.labels_masks))

        return prefetch_iterable(self._iter_data(data), stage)

    def _shard_tuple(self, t, stacked: bool = False):
        """Place one tuple of staged batch operands (graph inputs/labels/
        masks) per the active plan; identity without one."""
        plan = self._plan
        if plan is None or t is None:
            return t
        return tuple(None if a is None else plan.shard_batch(a, stacked=stacked)
                     for a in t)

    def _mds_to_dev(self, mds):
        """MultiDataSet -> device operand tuples; the ONE staging rule
        the per-call, scan and accumulation fit paths share."""
        return (tuple(_stage_with_affine(self, f) for f in mds.features),
                tuple(_as_jnp(l, self._compute_dtype) for l in mds.labels),
                None if mds.features_masks is None else tuple(
                    _as_jnp(m) for m in mds.features_masks),
                None if mds.labels_masks is None else tuple(
                    _as_jnp(m) for m in mds.labels_masks))

    def _operands(self, mds):
        """One MultiDataSet -> (inputs, labels, fmasks, lmasks) on the
        device, per the active plan."""
        return tuple(self._shard_tuple(t) for t in self._mds_to_dev(mds))

    def _stage_stacked(self, group):
        """K same-shape MultiDataSets -> (inputs, labels, fmasks, lmasks)
        stacked on a new leading axis, on the device, per the active
        plan: the ONE staging rule of the scan and accumulation chunks."""
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[self._mds_to_dev(m) for m in group])
        return tuple(self._shard_tuple(t, stacked=True) for t in stacked)

    @staticmethod
    def _batch_sig(mds):
        shapes = lambda t: None if t is None else tuple(
            np.shape(a) for a in t)
        return (shapes(mds.features), shapes(mds.labels),
                shapes(mds.features_masks), shapes(mds.labels_masks))

    @staticmethod
    def _batch_examples(mds) -> int:
        """Rows of one MultiDataSet batch (the `examples=` of a
        train/chunk)."""
        return int(np.shape(mds.features[0])[0])

    def _fit_epoch_tbptt(self, batches, rng):
        """Truncated BPTT: chunk the time axis of every staged sequence
        input/label/mask of a batch (ComputationGraph.java:2894
        doTruncatedBPTT); the chunk loop is nn/fit_loop's."""
        from deeplearning4j_tpu import monitor
        etl_start = time.perf_counter()
        for mds in batches:
            step_start = time.perf_counter()
            monitor.add_span("train/etl", etl_start, step_start,
                             iteration=self.iteration_count)
            rng = _fit_tbptt_batch(
                self, self._tbptt_chunks(*self._operands(mds)), rng,
                (step_start - etl_start) * 1e3, self._batch_examples(mds))
            etl_start = time.perf_counter()
        return rng

    def _tbptt_chunks(self, inputs, labels, fmasks, lmasks):
        """The time slices of one staged batch, tbptt_fwd_length long."""
        fwd = self.conf.tbptt_fwd_length
        in_types = [self._vertex_types[n] for n in self.conf.network_inputs]
        seq_lengths = [f.shape[1] for t, f in zip(in_types, inputs)
                       if t.kind == Kind.RNN]
        if not seq_lengths:
            raise ValueError("tbptt backprop requires at least one RNN "
                             "(B, T, F) network input")
        if len(set(seq_lengths)) > 1:
            raise ValueError(
                f"tbptt requires all RNN inputs to share one sequence "
                f"length, got {seq_lengths} — chunking cannot be aligned "
                f"across inputs of different T")
        T = seq_lengths[0]

        def slice_t(arr, t0, t1, is_mask=False):
            # sequences are rank-3 (B,T,F); masks are rank-2 (B,T). A rank-2
            # LABEL is per-example (B,C) and must not be time-sliced even if
            # C happens to equal T (DL4J slices by rank the same way).
            if arr is None:
                return arr
            if np.ndim(arr) >= 3 and arr.shape[1] == T:
                return arr[:, t0:t1]
            if is_mask and np.ndim(arr) == 2 and arr.shape[1] == T:
                return arr[:, t0:t1]
            return arr

        for t0 in range(0, T, fwd):
            t1 = min(t0 + fwd, T)
            yield (tuple(slice_t(f, t0, t1) for f in inputs),
                   tuple(slice_t(l, t0, t1) for l in labels),
                   None if fmasks is None else tuple(
                       slice_t(m, t0, t1, is_mask=True) for m in fmasks),
                   None if lmasks is None else tuple(
                       slice_t(m, t0, t1, is_mask=True) for m in lmasks))


    def _iter_data(self, data):
        if isinstance(data, (tuple, list)) and len(data) == 2 \
                and all(hasattr(d, "shape") for d in data):
            # (features, labels) ARRAY pair convenience, as
            # MultiLayerNetwork.fit; anything else 2-long (a batch list,
            # tuples of per-input arrays) iterates normally. Arrays pass
            # through as-is — no host round-trip for device-resident data.
            data = MultiDataSet((data[0],), (data[1],), None, None)
        if isinstance(data, MultiDataSet):
            yield data
        elif isinstance(data, DataSet):
            yield MultiDataSet((data.features,), (data.labels,),
                               None if data.features_mask is None else (data.features_mask,),
                               None if data.labels_mask is None else (data.labels_mask,))
        else:
            for item in data:
                yield from self._iter_data(item)

    # -------------------------------------------------------------- scoring
    def score(self, mds: Optional[MultiDataSet] = None) -> float:
        if mds is None:
            return self._score if self._score is not None else float("nan")
        if isinstance(mds, DataSet):
            mds = MultiDataSet((mds.features,), (mds.labels,))
        loss, _ = self._score_fn(
            self.params, self.state,
            tuple(_as_jnp(f, self._compute_dtype) for f in mds.features),
            tuple(_as_jnp(l, self._compute_dtype) for l in mds.labels),
            None, None, False, None)
        return float(loss)

    def evaluate_roc(self, data, batch_size: int = 32):
        """Binary ROC on the (single-output) graph (DL4J evaluateROC)."""
        from deeplearning4j_tpu.eval.roc import ROC
        return self._evaluate_with(ROC(), data, batch_size)

    def evaluate_roc_multi_class(self, data, batch_size: int = 32):
        """One-vs-all per-class ROC (DL4J evaluateROCMultiClass)."""
        from deeplearning4j_tpu.eval.roc import ROCMultiClass
        return self._evaluate_with(ROCMultiClass(), data, batch_size)

    def _evaluate_with(self, ev, data, batch_size: int = 32):
        """Feed an eval accumulator from the first output, chunked by
        batch_size and excluding mask-padded entries."""
        from deeplearning4j_tpu.nn.multilayer import _masked_eval_pair
        for mds in self._iter_data(data):
            labels = np.asarray(mds.labels[0])
            lm = None if mds.labels_masks is None else mds.labels_masks[0]
            n = labels.shape[0]
            for i in range(0, n, batch_size):
                out = self.output(*(f[i:i + batch_size]
                                    for f in mds.features))
                out = out[0] if isinstance(out, (tuple, list)) else out
                ev.eval(*_masked_eval_pair(
                    labels[i:i + batch_size], np.asarray(out),
                    None if lm is None else lm[i:i + batch_size]))
        if hasattr(data, "reset"):
            data.reset()
        return ev

    def evaluate(self, data, batch_size: int = 32):
        """First-output classification evaluation (DL4J evaluate);
        mask-padded steps excluded, chunked by batch_size."""
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        return self._evaluate_with(Evaluation(), data, batch_size)

    # ----------------------------------------------------- recurrent state
    def rnn_time_step(self, *inputs):
        """Stateful streaming inference over the DAG (ComputationGraph
        rnnTimeStep, ComputationGraph.java:2720). Each input is (B, F) for
        one step or (B, T, F) for several; recurrent vertex state persists
        across calls until rnn_clear_previous_state()."""
        if not hasattr(self, "_rnn_carries"):
            self._rnn_carries = {}
        if self._vertex_types is None:
            self._vertex_types = self._resolve_types()
        in_types = [self._vertex_types[n] for n in self.conf.network_inputs]
        singles = []
        prep = []
        for t, x in zip(in_types, inputs):
            x = _as_jnp(x, self._compute_dtype)
            single = t.kind == Kind.RNN and x.ndim == 2
            singles.append(single)
            prep.append(x[:, None, :] if single else x)
        if getattr(self, "_rnn_step_fn", None) is None:
            # jitted once; jax re-traces automatically when the carry
            # pytree structure changes (first call: empty dict)
            @jax.jit
            def _stepfn(params, state, prep, carries):
                acts, _, new_carries, _ = self._forward(
                    params, state, prep, False, None, carries=carries)
                return ({o: acts[o] for o in self.conf.network_outputs},
                        new_carries)
            self._rnn_step_fn = _stepfn
        out_acts, new_carries = self._rnn_step_fn(
            self.params, self.state, tuple(prep), self._rnn_carries)
        acts = out_acts
        self._rnn_carries = new_carries
        outs = []
        for o in self.conf.network_outputs:
            y = acts[o]
            if any(singles) and y.ndim == 3:
                y = y[:, -1, :]
            outs.append(y)
        return outs[0] if len(outs) == 1 else tuple(outs)

    def rnn_clear_previous_state(self):
        self._rnn_carries = {}

    # -------------------------------------------------------------- summary
    def summary(self) -> str:
        """Vertex table in topological order: name, type, inputs, output
        shape, parameter count (ComputationGraph summary analog)."""
        if self.params is None:
            raise RuntimeError("init() the network before summary()")
        types = self._vertex_types or self._resolve_types()
        self._vertex_types = types
        rows = [("vertex", "type", "inputs", "out", "params")]
        total = 0
        for name in self._topo:
            vd = self.conf.vertices[name]
            n = param_util.num_params(self.params.get(name, {}))
            total += n
            rows.append((name, type(vd.vertex).__name__,
                         ",".join(vd.inputs),
                         "x".join(map(str, types[name].shape)), f"{n:,}"))
        return param_util.format_param_table(rows, total)

    # --------------------------------------------------------------- memory
    def memory_report(self, batch_size: int = 32, with_compiled: bool = True):
        """Per-vertex analytic memory estimate + exact XLA compiled-step HBM
        (DL4J NetworkMemoryReport analog — see util/memory.py)."""
        from deeplearning4j_tpu.util.memory import build_memory_report
        return build_memory_report(self, batch_size, with_compiled)

    def copy(self) -> "ComputationGraph":
        """Clone with copied parameter/state pytrees (MultiLayerNetwork.copy
        analog for graphs)."""
        clone = ComputationGraph(self.conf)
        if self.params is not None:
            clone._vertex_types = self._vertex_types or self._resolve_types()
            clone._pre_kind = self._pre_kind
            # materialize NEW buffers: the original's arrays are donated by
            # its train step and would be deleted out from under the clone
            clone.params = jax.tree_util.tree_map(
                lambda a: jnp.array(a, copy=True), self.params)
            clone.state = jax.tree_util.tree_map(
                lambda a: jnp.array(a, copy=True), self.state)
            clone._build_optimizer()
        return clone

    # --------------------------------------------------------------- params
    def num_params(self) -> int:
        return param_util.num_params(self.params)

    def params_flat(self):
        return param_util.params_to_flat(self.params)

    def set_params_flat(self, flat):
        self.params = param_util.flat_to_params(flat, self.params)
