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

import logging
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from deeplearning4j_tpu.data.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.nn.conf.base import (
    InputType, Kind, LayerConf, preprocess_forward, preprocessed_type,
)
from deeplearning4j_tpu.nn.conf.graph_vertices import GraphVertexConf
from deeplearning4j_tpu.nn.conf.network import ComputationGraphConfiguration
from deeplearning4j_tpu.nn.multilayer import (
    _as_jnp, _default_scan_steps, _record_iteration, _required_kind,
    _run_scan_pipeline, _scan_incompatible_listeners,
)
from deeplearning4j_tpu.nn.updaters import NoOp, apply_update, build_optimizer
from deeplearning4j_tpu.util import params as param_util

log = logging.getLogger("deeplearning4j_tpu")


def _mds_examples(mds) -> int:
    """Rows of one MultiDataSet batch (the `examples=` of a train/chunk)."""
    return int(np.shape(mds.features[0])[0])


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
        self._train_step = None
        self._scan_step: Dict[Any, Any] = {}
        self._output_fn = None
        self._input_affine = None   # (shift, scale) during device-norm fit
        self._affine_fn = None
        self._ledger_cache: Dict[Any, Any] = {}   # monitor.xla programs
        self._plan = None           # active GSPMD ShardingPlan (parallel/plan)

    def _engage_plan(self, plan):
        """Activate a GSPMD ShardingPlan for this graph's compiled steps
        (the shared MultiLayerNetwork._engage_plan_impl contract)."""
        from deeplearning4j_tpu.nn.multilayer import _engage_plan_impl
        _engage_plan_impl(self, plan)

    def _shard_tuple(self, t, stacked: bool = False):
        """Place one tuple of staged batch operands (graph inputs/labels/
        masks) per the active plan; identity without one."""
        plan = self._plan
        if plan is None or t is None:
            return t
        return tuple(None if a is None else plan.shard_batch(a, stacked=stacked)
                     for a in t)

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def _stage_x(self, a):
        from deeplearning4j_tpu.nn.multilayer import _stage_with_affine
        return _stage_with_affine(self, a)

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
        self._train_step = None
        self._scan_step = {}

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
        params = self._cast_params(params)
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
                acts[name] = vd.vertex.apply(*xs)
                masks[name] = self._vertex_out_mask(
                    vd.vertex, in_masks, xs, self._vertex_types[name])
                continue
            x = xs[0]
            need = self._pre_kind[name]
            src_t = self._input_type_of(vd.inputs[0])
            if need is not None and src_t.kind != need:
                x = preprocess_forward(src_t, need, x)
            sub_rng = None
            if rng is not None:
                rng, sub_rng = jax.random.split(rng)
            m = in_masks[0] if need == Kind.RNN else None
            if name in out_set:
                acts["__pre__" + name] = x
            layer_params = params.get(name, {})
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
            remat = train and self.conf.gradient_checkpointing
            if carries is not None and _is_stateful_recurrent(vd.vertex):
                y, carry = _layer_call(
                    vd.vertex, seq=True, train=train, remat=remat,
                    params=layer_params, x=x, carry=carries.get(name),
                    rng=sub_rng, mask=m)
                new_carries[name] = carry
                new_state[name] = state.get(name, {})
            else:
                y, s = _layer_call(
                    vd.vertex, seq=False, train=train, remat=remat,
                    params=layer_params, x=x, state=state.get(name, {}),
                    rng=sub_rng, mask=m)
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
        params_c = self._cast_params(params)
        acts, new_state, new_carries, masks = self._forward(
            params_c, state, inputs, train, rng, fmasks, stash_pre=True,
            carries=carries)
        total = jnp.asarray(0.0, jnp.float32)
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
            s = vd.vertex.score(params_c.get(out_name, {}), feat, lab,
                                train=train, rng=None, mask=lmask)
            # keep f64 under float64 gradient checking; f32 otherwise
            total = total + s.astype(jnp.promote_types(jnp.float32, s.dtype))
        for name, p in params.items():
            vd = self.conf.vertices[name]
            if isinstance(vd.vertex, LayerConf):
                total = total + vd.vertex.regularization_score(p)
        return total, (new_state, new_carries)

    def _make_train_step(self):
        from deeplearning4j_tpu.nn.regularization import (
            apply_constraints, constraint_map, has_constraints,
        )
        tx = self._tx
        layer_map = constraint_map(self)
        constrained = has_constraints(layer_map.values())
        plan = self._plan   # GSPMD plan: sharding constraints in-jit

        def step(params, opt_state, state, inputs, labels, fmasks, lmasks,
                 rng, carries):
            def loss_fn(p):
                return self._score_fn(p, state, inputs, labels, fmasks,
                                      lmasks, True, rng, carries=carries)
            (loss, (new_state, new_carries)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            if plan is not None:
                # pin grads to the ZeRO/TP compute layout: the single
                # hint from which XLA derives reduce-scatter -> sharded
                # update -> all-gather (parallel/plan.py)
                grads = plan.constrain_grads(grads)
            new_params, new_opt, _ = apply_update(
                tx, grads, opt_state, params, plan)
            if constrained:     # post-update projection (DL4J applyConstraints)
                new_params = apply_constraints(layer_map, new_params)
            if plan is not None:
                new_params = plan.constrain_params(new_params)
                new_opt = plan.constrain_opt(new_opt, new_params)
                new_state = plan.constrain_replicated(new_state)
            return new_params, new_opt, new_state, loss, new_carries

        return jax.jit(step, donate_argnums=(0, 1, 2))

    def fit(self, data, epochs: int = 1, scan_steps: Optional[int] = None,
            accumulate_steps: int = 1, plan=None):
        """Train on a MultiDataSet / DataSet / iterator of either
        (ComputationGraph.fit, :1015).

        scan_steps > 1 fuses that many optimizer steps into one jit via
        lax.scan with a one-chunk-deferred loss fetch (input-pipelined fit;
        see MultiLayerNetwork.fit) — bit-identical math/RNG to the per-call
        path. Default: 10 on TPU, 1 on CPU (measured, PERF.md);
        $DL4J_TPU_SCAN_STEPS overrides.

        accumulate_steps > 1: gradient accumulation — K micro-batch
        gradients averaged into ONE optimizer step inside one jit (see
        MultiLayerNetwork.fit; mutually exclusive with scan_steps > 1,
        not applicable to tbptt)."""
        if self.params is None:
            self.init()
        # donated-buffer safety: see util/params.owned_leaf (params from a
        # checkpoint or import may alias numpy memory the donating step
        # would otherwise free); under a GSPMD plan the laundered copies
        # additionally land on the plan placements (docs/PARALLELISM.md)
        from deeplearning4j_tpu.parallel.plan import active_plan
        if plan is None:
            plan = active_plan()
        if plan is None and self._plan is None:
            # deliberately inlined (mirrors _engage_plan_impl's no-plan
            # branch): the donated-aliasing lint contract requires the
            # own_tree laundering to live IN the module that builds the
            # donating steps, not only behind the shared impl — keep in
            # sync with nn/multilayer._engage_plan_impl
            self.params = param_util.own_tree(self.params)
            self.state = param_util.own_tree(self.state)
            self.opt_state = param_util.own_tree(self.opt_state)
        else:
            self._engage_plan(plan)
        if self._train_step is None:
            self._train_step = self._make_train_step()
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
        rng = jax.random.PRNGKey(self.conf.seed + 331 * (self.epoch_count + 1))
        tbptt = self.conf.backprop_type == "tbptt"
        # device-side normalization (data/normalization.py
        # engaged_device_affine; see MultiLayerNetwork.fit): the affine
        # pre-processor is applied on device, raw (uint8) features ship
        # over the link
        from deeplearning4j_tpu.data.normalization import (
            engaged_device_affine)
        with engaged_device_affine(data, self.listeners) as aff:
            if aff is not None:
                self._input_affine = (jnp.asarray(aff[0]),
                                      jnp.asarray(aff[1]))
            copy_marked = []
            if not tbptt and (accumulate_steps > 1 or (
                    scan_steps > 1
                    and not _scan_incompatible_listeners(self.listeners))):
                # the stacking fits hold K live batches before one
                # transfer — shared-memory ring sources must yield copies
                # (data/pipeline.mark_copy_for_stacking)
                from deeplearning4j_tpu.data.pipeline import (
                    mark_copy_for_stacking)
                copy_marked = mark_copy_for_stacking(data)
            from deeplearning4j_tpu.monitor import goodput
            gp_session = goodput.fit_begin("graph/fit")
            self._fit_chunk = 0     # train/chunk numbers run over epochs
            try:
                from deeplearning4j_tpu import monitor
                for _ in range(epochs):
                    for lst in self.listeners:
                        lst.on_epoch_start(self, self.epoch_count)
                    with monitor.span("train/epoch",
                                      epoch=self.epoch_count):
                        if not tbptt and accumulate_steps > 1:
                            rng = self._fit_epoch_accum(data, rng,
                                                        accumulate_steps)
                        elif not tbptt and scan_steps > 1:
                            rng = self._fit_epoch_scan(data, rng, scan_steps)
                        else:
                            rng = self._fit_epoch_per_call(data, rng, tbptt)
                    for lst in self.listeners:
                        lst.on_epoch_end(self, self.epoch_count)
                    self.epoch_count += 1
                    if hasattr(data, "reset"):
                        data.reset()
            finally:
                goodput.fit_end(gp_session)
                self._input_affine = None
                for it_ in copy_marked:
                    it_._copy = False
        return self

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

    def _fit_epoch_per_call(self, data, rng, tbptt):
        from deeplearning4j_tpu import monitor
        from deeplearning4j_tpu.monitor import goodput
        from deeplearning4j_tpu.monitor import xla as xla_ledger
        etl_start = time.perf_counter()
        for mds in self._mds_stream(data):
            step_start = time.perf_counter()
            etl_ms = (step_start - etl_start) * 1e3
            monitor.add_span("train/etl", etl_start, step_start,
                             iteration=self.iteration_count)
            inputs = self._shard_tuple(
                tuple(self._stage_x(f) for f in mds.features))
            labels = self._shard_tuple(
                tuple(_as_jnp(l, self._compute_dtype) for l in mds.labels))
            fmasks = self._shard_tuple(
                None if mds.features_masks is None else tuple(
                    _as_jnp(m) for m in mds.features_masks))
            lmasks = self._shard_tuple(
                None if mds.labels_masks is None else tuple(
                    _as_jnp(m) for m in mds.labels_masks))
            bs = int(np.shape(mds.features[0])[0])
            if tbptt:
                rng = self._fit_tbptt_batch(inputs, labels, fmasks,
                                            lmasks, rng, etl_ms, bs)
            else:
                rng, sub = jax.random.split(rng)
                (self.params, self.opt_state, self.state, loss,
                 _) = self._train_step(
                    self.params, self.opt_state, self.state, inputs,
                    labels, fmasks, lmasks, sub, None)
                sync_start = time.perf_counter()
                # block for device completion FIRST (goodput:
                # step_compute; banks per-shard barrier wait under a
                # plan), so the host_sync span below covers only the
                # narrow D2H fetch
                goodput.device_wait(loss)
                fetch_start = time.perf_counter()
                monitor.add_span("train/device_wait", sync_start,
                                 fetch_start)
                # graftlint: disable=host-sync-in-hot-path -- the step's ONE budgeted loss fetch (the deliberate per-iteration sync; PERF.md) — bracketed by the train/host_sync span
                self._score = float(loss)
                step_end = time.perf_counter()
                monitor.add_span("train/host_sync", fetch_start, step_end)
                monitor.add_span("train/step", step_start, step_end,
                                 iteration=self.iteration_count,
                                 score=self._score, batch_size=bs)
                if xla_ledger.enabled():
                    key = (id(self._train_step), xla_ledger.shape_key(
                        (inputs, labels, fmasks, lmasks)))
                    fresh = key not in self._ledger_cache
                    rec = xla_ledger.capture_cached(
                        self._ledger_cache, key,
                        "graph/train_step", self._train_step,
                        (self.params, self.opt_state, self.state, inputs,
                         labels, fmasks, lmasks, sub, None),
                        examples_per_call=bs)
                    if not fresh:
                        # debut wall time includes the jit compile —
                        # only steady-state steps feed the MFU gauge
                        xla_ledger.observe_step(rec,
                                                step_end - step_start)
                _record_iteration(self._score, bs,
                                  step_seconds=step_end - step_start,
                                  sync_seconds=step_end - fetch_start)
                for lst in self.listeners:
                    lst.iteration_done(self, self.iteration_count,
                                       self.epoch_count, self._score,
                                       etl_ms, bs)
                self.iteration_count += 1
            etl_start = time.perf_counter()
        return rng

    def _make_scan_step(self):
        from deeplearning4j_tpu.nn.regularization import (
            apply_constraints, constraint_map, has_constraints,
        )
        tx = self._tx
        layer_map = constraint_map(self)
        constrained = has_constraints(layer_map.values())

        plan = self._plan   # GSPMD plan: sharding constraints in-jit

        def kstep(params, opt_state, state, inputs, labels, fmasks, lmasks,
                  subs):
            def body(carry, batch):
                params, opt_state, state = carry
                cin, clab, cfm, clm, sub = batch
                def loss_fn(p):
                    return self._score_fn(p, state, cin, clab, cfm, clm,
                                          True, sub, carries=None)
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
                body, (params, opt_state, state),
                (inputs, labels, fmasks, lmasks, subs))
            return params, opt_state, state, losses

        return jax.jit(kstep, donate_argnums=(0, 1, 2))

    def _mds_to_dev(self, mds):
        """MultiDataSet -> device operand tuples; the ONE staging rule
        the per-call, scan and accumulation fit paths share."""
        return (tuple(self._stage_x(f) for f in mds.features),
                tuple(_as_jnp(l, self._compute_dtype) for l in mds.labels),
                None if mds.features_masks is None else tuple(
                    _as_jnp(m) for m in mds.features_masks),
                None if mds.labels_masks is None else tuple(
                    _as_jnp(m) for m in mds.labels_masks))

    def _stage_stacked(self, group):
        """K same-shape MultiDataSets -> (inputs, labels, fmasks, lmasks)
        stacked on a new leading axis, on the device, per the active
        plan: the ONE staging rule of the scan and accumulation chunks."""
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[self._mds_to_dev(m) for m in group])
        return tuple(self._shard_tuple(t, stacked=True) for t in stacked)

    @staticmethod
    def _mds_sig(mds):
        shapes = lambda t: None if t is None else tuple(
            np.shape(a) for a in t)
        return (shapes(mds.features), shapes(mds.labels),
                shapes(mds.features_masks), shapes(mds.labels_masks))

    def _make_accum_step(self):
        """K micro-batch gradients averaged into ONE optimizer step (see
        MultiLayerNetwork._make_accum_step)."""
        from deeplearning4j_tpu.nn.regularization import (
            apply_constraints, constraint_map, has_constraints,
        )
        tx = self._tx
        layer_map = constraint_map(self)
        constrained = has_constraints(layer_map.values())

        plan = self._plan   # GSPMD plan: sharding constraints in-jit

        def kaccum(params, opt_state, state, inputs, labels, fmasks,
                   lmasks, subs):
            k = subs.shape[0]

            def body(carry, batch):
                gsum, state = carry
                cin, clab, cfm, clm, sub = batch
                def loss_fn(p):
                    return self._score_fn(p, state, cin, clab, cfm, clm,
                                          True, sub, carries=None)
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
                body, (zeros, state), (inputs, labels, fmasks, lmasks,
                                       subs))
            grads = jax.tree_util.tree_map(lambda g: g / k, gsum)
            new_params, new_opt, _ = apply_update(
                tx, grads, opt_state, params, plan)
            if constrained:
                new_params = apply_constraints(layer_map, new_params)
            if plan is not None:
                new_params = plan.constrain_params(new_params)
                new_opt = plan.constrain_opt(new_opt, new_params)
                state = plan.constrain_replicated(state)
            return new_params, new_opt, state, jnp.mean(losses)

        return jax.jit(kaccum, donate_argnums=(0, 1, 2))

    def _fit_epoch_accum(self, data, rng, K):
        """One optimizer step per K stacked micro-batches; chunking and
        ragged-tail handling as in _fit_epoch_scan, lockstep listener
        callbacks when a model-reading listener is attached."""
        from deeplearning4j_tpu.monitor import xla as xla_ledger
        last_sync = [None]

        def fetch(p):
            return float(p[0])      # the chunk's one blocking fetch

        def notify(p, score):
            _, bs, etl_ms, rec = p
            self._score = score
            if xla_ledger.enabled():
                now = time.perf_counter()
                if rec is not None and last_sync[0] is not None:
                    xla_ledger.observe_step(rec, now - last_sync[0])
                last_sync[0] = now
            _record_iteration(self._score, bs)
            for lst in self.listeners:
                lst.iteration_done(self, self.iteration_count,
                                   self.epoch_count, self._score, etl_ms,
                                   bs)
            self.iteration_count += 1
            return 1

        def stage(group):
            nonlocal rng
            subs = []
            for _ in group:
                rng, sub = jax.random.split(rng)
                subs.append(sub)
            inputs, labels, fmasks, lmasks = self._stage_stacked(group)
            bs = _mds_examples(group[0]) * len(group)
            return (inputs, labels, fmasks, lmasks, jnp.stack(subs), bs,
                    len(group))

        def launch(staged, etl_ms):
            inputs, labels, fmasks, lmasks, subs_d, bs, n = staged
            sig = ("accum", fmasks is not None, lmasks is not None)
            if sig not in self._scan_step:
                self._scan_step[sig] = self._make_accum_step()
            kstep = self._scan_step[sig]
            (self.params, self.opt_state, self.state,
             loss) = kstep(
                self.params, self.opt_state, self.state, inputs, labels,
                fmasks, lmasks, subs_d)
            rec = None
            if xla_ledger.enabled():
                key = (id(kstep), xla_ledger.shape_key(
                    (inputs, labels, fmasks, lmasks)))
                fresh = key not in self._ledger_cache
                rec = xla_ledger.capture_cached(
                    self._ledger_cache, key,
                    "graph/accum_step", kstep,
                    (self.params, self.opt_state, self.state, inputs,
                     labels, fmasks, lmasks, subs_d),
                    examples_per_call=bs, steps_per_call=n)
                if fresh:
                    last_sync[0] = None   # exclude the AOT compile interval
            return (loss, bs, etl_ms, rec)

        # _iter_data, not _mds_stream: stage() stacks K host batches
        # into ONE transfer; the prefetch stream's per-batch device_put
        # would round-trip each micro-batch through the host (same rule
        # as _fit_epoch_scan)
        self._fit_chunk = _run_scan_pipeline(
            self._iter_data(data), K, sig_of=self._mds_sig,
            examples_of=_mds_examples, stage=stage, launch=launch,
            fetch=fetch, notify=notify,
            defer=not _scan_incompatible_listeners(self.listeners),
            first_chunk=self._fit_chunk)
        return rng

    def _fit_epoch_scan(self, data, rng, K):
        """Input-pipelined epoch over MultiDataSets: consecutive same-shape
        batches are stacked and run as one scan-of-K jit; the loss fetch is
        deferred one chunk so host stacking overlaps device compute. Ragged
        tails fall back to the per-call step."""
        if _scan_incompatible_listeners(self.listeners):
            return self._fit_epoch_per_call(data, rng, False)
        from deeplearning4j_tpu.monitor import xla as xla_ledger
        last_sync = [None]

        def fetch(p):
            return np.asarray(p[0])             # single blocking fetch/chunk

        def notify(p, arr):
            _, bs, etl_ms, rec = p
            if xla_ledger.enabled():
                # steady-state chunk wall = spacing between chunk syncs;
                # the stamp advances on EVERY chunk so a ragged tail
                # can't leak into the next interval (see
                # MultiLayerNetwork._fit_epoch_scan)
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
            bs = _mds_examples(group[0])
            if len(group) < K:
                # ragged tail / shape-change remainder: staged batch by
                # batch for the compiled per-call step instead of a
                # one-off scan-of-len(group)
                return ([tuple(self._shard_tuple(t)
                               for t in self._mds_to_dev(m))
                         for m in group], subs, bs, True)
            return self._stage_stacked(group), jnp.stack(subs), bs, False

        def launch(staged, etl_ms):
            parts, subs, bs, tail = staged
            if tail:
                losses = []
                for (inputs, labels, fmasks, lmasks), sub in zip(parts,
                                                                 subs):
                    (self.params, self.opt_state, self.state, loss,
                     _) = self._train_step(
                        self.params, self.opt_state, self.state, inputs,
                        labels, fmasks, lmasks, sub, None)
                    losses.append(loss)
                return (jnp.stack(losses), bs, etl_ms, None)
            inputs, labels, fmasks, lmasks = parts
            n = int(subs.shape[0])
            sig = (n, fmasks is not None, lmasks is not None)
            if sig not in self._scan_step:
                self._scan_step[sig] = self._make_scan_step()
            kstep = self._scan_step[sig]
            (self.params, self.opt_state, self.state,
             losses) = kstep(
                self.params, self.opt_state, self.state, inputs, labels,
                fmasks, lmasks, subs)
            rec = None
            if xla_ledger.enabled():
                key = (id(kstep), xla_ledger.shape_key(
                    (inputs, labels, fmasks, lmasks)))
                fresh = key not in self._ledger_cache
                rec = xla_ledger.capture_cached(
                    self._ledger_cache, key,
                    "graph/scan_step", kstep,
                    (self.params, self.opt_state, self.state, inputs,
                     labels, fmasks, lmasks, subs),
                    examples_per_call=bs * n, steps_per_call=n)
                if fresh:
                    last_sync[0] = None   # exclude the AOT compile interval
            return (losses, bs, etl_ms, rec)

        self._fit_chunk = _run_scan_pipeline(
            self._iter_data(data), K, sig_of=self._mds_sig,
            examples_of=_mds_examples, stage=stage, launch=launch,
            fetch=fetch, notify=notify, first_chunk=self._fit_chunk)
        return rng

    def _fit_tbptt_batch(self, inputs, labels, fmasks, lmasks, rng, etl_ms,
                         bs):
        """Truncated BPTT over one batch: chunk the time axis of every
        sequence input/label/mask, carry RNN state across chunks with
        stop_gradient at the boundaries (ComputationGraph.java:2894
        doTruncatedBPTT)."""
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

        carries = {}
        for t0 in range(0, T, fwd):
            t1 = min(t0 + fwd, T)
            cin = tuple(slice_t(f, t0, t1) for f in inputs)
            clab = tuple(slice_t(l, t0, t1) for l in labels)
            cfm = None if fmasks is None else tuple(
                slice_t(m, t0, t1, is_mask=True) for m in fmasks)
            clm = None if lmasks is None else tuple(
                slice_t(m, t0, t1, is_mask=True) for m in lmasks)
            rng, sub = jax.random.split(rng)
            (self.params, self.opt_state, self.state, loss,
             new_carries) = self._train_step(
                self.params, self.opt_state, self.state, cin, clab, cfm,
                clm, sub, carries)
            carries = jax.tree_util.tree_map(jax.lax.stop_gradient,
                                             new_carries)
            # graftlint: disable=host-sync-in-hot-path -- the tbptt chunk's one budgeted loss fetch
            self._score = float(loss)
            _record_iteration(self._score, bs)
            for lst in self.listeners:
                lst.iteration_done(self, self.iteration_count,
                                   self.epoch_count, self._score, etl_ms, bs)
            self.iteration_count += 1
            etl_ms = 0.0
        return rng

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
