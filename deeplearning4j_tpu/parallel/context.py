"""Context-parallel training — the sequence axis sharded over the mesh.

No DL4J analog (SURVEY.md §5.7): the reference bounds sequence memory only
via truncated BPTT. Here the FULL training step runs under `shard_map` with
activations sharded on the time axis over the mesh "seq" axis:

- pointwise layers (embeddings, layer norm, MLP, MoE) run unchanged on
  their local sequence shard;
- `MultiHeadAttention` detects context-parallel mode (attention.py
  `context_parallel`) and switches to ring attention — K/V blocks rotate
  over ICI with online-softmax accumulation (`parallel/ring.py`);
- position-dependent layers (RoPE, learned positions) offset by the
  shard's global start;
- the loss is averaged across shards with `pmean`, and parameter gradients
  are `pmean`-ed so every shard applies the identical update to its
  replicated parameter copy.

Memory per device scales O(T / seq_degree) — sequences the reference could
never touch fit a pod. Combine with the "data" axis for dp x sp.

Restrictions (checked at build): standard backprop only (no tBPTT), every
layer must be sequence-local (recurrent scan layers like LSTM are NOT —
their hidden state crosses shard boundaries; use attention stacks).
"""
from __future__ import annotations

import logging
from typing import Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning4j_tpu.nn.layers.attention import context_parallel
from deeplearning4j_tpu.parallel.mesh import (
    DATA_AXIS, SEQ_AXIS, build_mesh, MeshConfig,
)

log = logging.getLogger("deeplearning4j_tpu")

# layers/vertices whose state/computation crosses sequence-shard boundaries
_SEQ_CROSSING = {"LSTM", "GravesLSTM", "SimpleRnn", "GRU", "Bidirectional",
                 "GravesBidirectionalLSTM", "Convolution1DLayer",
                 "Subsampling1DLayer", "LastTimeStep",
                 # graph vertices that read/reorder the global time axis:
                 # per-shard last-step / flip / length-broadcast are all
                 # silently wrong on a local sequence chunk
                 "LastTimeStepVertex", "ReverseTimeSeriesVertex",
                 "DuplicateToTimeSeriesVertex", "TimeSliceVertex"}


class ContextParallelTrainer:
    """Data x sequence parallel trainer for attention-based
    MultiLayerNetworks.

    Usage:
        mesh = build_mesh(MeshConfig(data=2, seq=4))
        trainer = ContextParallelTrainer(net, mesh)
        trainer.fit(iterator, epochs=1)
    """

    def __init__(self, model, mesh: Optional[Mesh] = None):
        if model.params is None:
            model.init()
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        self._is_graph = isinstance(model, ComputationGraph)
        if self._is_graph:
            if len(model.conf.network_inputs) != 1 or \
                    len(model.conf.network_outputs) != 1:
                raise ValueError(
                    "context parallelism supports single-input/"
                    "single-output ComputationGraphs (one sequence axis "
                    "to shard)")
            units = [vd.vertex for vd in model.conf.vertices.values()]
        else:
            units = list(model.layers)
        for layer in units:
            # check every level of the wrapper chain: both a crossing
            # wrapper (LastTimeStep, Bidirectional) and a crossing wrapped
            # layer (FrozenLayerWrapper(LSTM)) are rejected
            inner = layer
            while inner is not None:
                if type(inner).__name__ in _SEQ_CROSSING:
                    raise ValueError(
                        f"{type(inner).__name__} carries state across "
                        "sequence shards and cannot run context-parallel; "
                        "use attention/transformer layers")
                inner = getattr(inner, "layer", None)
        if model.conf.backprop_type != "standard":
            raise ValueError("context parallelism requires standard backprop")
        self.model = model
        if mesh is None:
            # default: every device on the sequence axis (pure CP)
            mesh = build_mesh(MeshConfig(data=1, model=1,
                                         seq=len(jax.devices())))
        self.mesh = mesh
        self.seq_degree = self.mesh.shape[SEQ_AXIS]
        self.data_degree = self.mesh.shape[DATA_AXIS]
        self._step = None

    # ---------------------------------------------------------------- build
    def _build_step(self, with_fmask, with_lmask):
        from deeplearning4j_tpu.nn.regularization import (
            apply_constraints, constraint_map, has_constraints,
        )
        net = self.model
        tx = net._tx
        mesh = self.mesh
        layer_map = constraint_map(net)
        constrained = has_constraints(layer_map.values())

        def local_step(params, opt_state, state, x, y, fmask, lmask, rng):
            """Runs on one (data, seq) shard; params replicated."""
            # decorrelate dropout across shards
            rng = jax.random.fold_in(
                rng, jax.lax.axis_index(DATA_AXIS) * 8191 +
                jax.lax.axis_index(SEQ_AXIS))

            def loss_fn(p):
                with context_parallel(SEQ_AXIS):
                    if self._is_graph:
                        loss, (new_state, _) = net._score_fn(
                            p, state, (x,), (y,),
                            None if fmask is None else (fmask,),
                            None if lmask is None else (lmask,), True, rng)
                    else:
                        loss, (new_state, _) = net._score_fn(
                            p, state, x, y, fmask, lmask, True, rng)
                # the loss-weighting mask is the one the output layer used:
                # an explicit label mask wins, else the feature mask
                wmask = lmask if lmask is not None else fmask
                if wmask is not None:
                    # shards hold different numbers of VALID tokens: the
                    # global masked mean is psum(local_sum)/psum(count),
                    # where local_sum = local_masked_mean * local_count
                    # (fully-masked shards have loss 0, count 0). The
                    # replicated l1/l2 term passes through unchanged:
                    # psum(reg*cnt)/psum(cnt) == reg.
                    cnt = jnp.sum(wmask)
                    num = jax.lax.psum(loss * cnt, (DATA_AXIS, SEQ_AXIS))
                    den = jax.lax.psum(cnt, (DATA_AXIS, SEQ_AXIS))
                    loss = num / jnp.maximum(den, 1.0)
                else:
                    # uniform shards: mean of means is exact
                    loss = jax.lax.pmean(loss, DATA_AXIS)
                    loss = jax.lax.pmean(loss, SEQ_AXIS)
                return loss, new_state

            (loss, new_state), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            # grads of the pmean'd loss still need cross-shard reduction:
            # each shard saw only its slice of the batch/sequence
            grads = jax.lax.pmean(grads, DATA_AXIS)
            grads = jax.lax.pmean(grads, SEQ_AXIS)
            updates, new_opt = tx.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            if constrained:    # same post-update projection as net.fit
                new_params = apply_constraints(layer_map, new_params)
            return new_params, new_opt, new_state, loss

        repl = P()
        xspec = P(DATA_AXIS, SEQ_AXIS)          # (B, T, ...) batch+seq sharded
        out_specs = (repl, repl, repl, repl)
        # absent masks are closed over, not passed as None args
        def shard(f, *in_specs):
            return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False)

        if with_fmask and with_lmask:
            sm = shard(local_step, repl, repl, repl, xspec, xspec, xspec,
                       xspec, repl)
        elif with_fmask:
            def fm_step(params, opt_state, state, x, y, fmask, rng):
                return local_step(params, opt_state, state, x, y, fmask,
                                  None, rng)
            inner = shard(fm_step, repl, repl, repl, xspec, xspec, xspec,
                          repl)

            def sm(params, opt_state, state, x, y, fmask, lmask, rng):
                return inner(params, opt_state, state, x, y, fmask, rng)
        elif with_lmask:
            def lm_step(params, opt_state, state, x, y, lmask, rng):
                return local_step(params, opt_state, state, x, y, None,
                                  lmask, rng)
            inner = shard(lm_step, repl, repl, repl, xspec, xspec, xspec,
                          repl)

            def sm(params, opt_state, state, x, y, fmask, lmask, rng):
                return inner(params, opt_state, state, x, y, lmask, rng)
        else:
            def bare_step(params, opt_state, state, x, y, rng):
                return local_step(params, opt_state, state, x, y, None,
                                  None, rng)
            inner = shard(bare_step, repl, repl, repl, xspec, xspec, repl)

            def sm(params, opt_state, state, x, y, fmask, lmask, rng):
                return inner(params, opt_state, state, x, y, rng)

        return jax.jit(sm, donate_argnums=(0, 1, 2))

    # ------------------------------------------------------------------ fit
    def _iter_batches(self, data, batch_size):
        """Yield (x, y, fmask, lmask) for either container type."""
        net = self.model
        if self._is_graph:
            for mds in net._iter_data(data):
                fm = lm = None
                if mds.features_masks is not None and \
                        mds.features_masks[0] is not None:
                    fm = jnp.asarray(mds.features_masks[0])
                if mds.labels_masks is not None and \
                        mds.labels_masks[0] is not None:
                    lm = jnp.asarray(mds.labels_masks[0])
                yield (jnp.asarray(mds.features[0]),
                       jnp.asarray(mds.labels[0]), fm, lm)
            if hasattr(data, "reset"):
                data.reset()
        else:
            source = net._as_iterator(data, batch_size)
            for ds in source:
                yield (jnp.asarray(ds.features), jnp.asarray(ds.labels),
                       None if ds.features_mask is None
                       else jnp.asarray(ds.features_mask),
                       None if ds.labels_mask is None
                       else jnp.asarray(ds.labels_mask))
            source.reset()

    def fit(self, data, epochs: int = 1, batch_size: int = 32):
        net = self.model
        # donated-buffer safety (util/params.owned_leaf): the step below
        # donates params/opt_state/state, so leaves from ANY host source
        # (checkpoint restore, keras/dl4j import, user numpy) must be
        # copied into XLA-owned buffers first — same contract as
        # MultiLayerNetwork.fit; zero-copy numpy aliases donated into
        # XLA are the PR-3 serde-resume segfault
        from deeplearning4j_tpu.util import params as param_util
        net.params = param_util.own_tree(net.params)
        net.state = param_util.own_tree(net.state)
        net.opt_state = param_util.own_tree(net.opt_state)
        # vary by epoch_count so repeated fit() calls draw fresh dropout
        # masks (as fit()'s own keying, nn/fit_loop.py)
        rng = jax.random.fold_in(
            jax.random.PRNGKey(net.conf.seed + 524287), net.epoch_count)
        for _ in range(epochs):
            for lst in net.listeners:
                lst.on_epoch_start(net, net.epoch_count)
            for x, y, fm, lm in self._iter_batches(data, batch_size):
                self._check_divisible(x)
                sig = (fm is not None, lm is not None)
                if self._step is None:
                    self._step = {}
                if sig not in self._step:
                    self._step[sig] = self._build_step(*sig)
                rng, sub = jax.random.split(rng)
                net.params, net.opt_state, net.state, loss = \
                    self._step[sig](net.params, net.opt_state, net.state,
                                    x, y, fm, lm, sub)
                # graftlint: disable=host-sync-in-hot-path -- the step's ONE budgeted loss fetch (the deliberate per-iteration sync; PERF.md)
                net._score = float(loss)
                for lst in net.listeners:
                    lst.iteration_done(net, net.iteration_count,
                                       net.epoch_count, net._score, 0.0,
                                       int(x.shape[0]))
                net.iteration_count += 1
            for lst in net.listeners:
                lst.on_epoch_end(net, net.epoch_count)
            net.epoch_count += 1
        net._steps = {}
        net._output_fn = None
        return net

    def _check_divisible(self, x):
        if x.shape[0] % self.data_degree:
            raise ValueError(
                f"batch {x.shape[0]} not divisible by data degree "
                f"{self.data_degree}")
        if x.shape[1] % self.seq_degree:
            raise ValueError(
                f"sequence length {x.shape[1]} not divisible by seq degree "
                f"{self.seq_degree}")
