"""ComputationGraph — DAG model runtime (multi-input / multi-output).

Reference parity: `nn/graph/ComputationGraph.java` — `init():340` (toposort
`:357`), `fit(DataSetIterator):778`, forward loop over `topologicalOrder`
`:1313,1325`, backprop `:1200-1210` (reverse topo order with fan-in epsilon
accumulation — here `jax.grad` through the forward fold).

The runtime folds over the configuration's topological order; the whole
forward + losses for ALL outputs + backward + update is one jitted XLA
computation, with multi-output loss = sum of per-output-layer losses
(reference: ComputationGraph sums output layer scores).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.data.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.data.iterators import (
    DataSetIterator, DevicePrefetchIterator, as_iterator,
)
from deeplearning4j_tpu.optim.executor import LossTracker, TrainingExecutor
from deeplearning4j_tpu.optim.recovery import build_plan, run_with_recovery
from deeplearning4j_tpu.optim.step import (
    as_features, build_step, make_train_step, stack_step_args,
    with_counter_sums,
)
from deeplearning4j_tpu.nn.graph import (
    ComputationGraphConfiguration, GraphVertex, LayerVertex,
    resolve_output_type,
)
from deeplearning4j_tpu.nn.layers.convolution import (
    defers_to_pool, record_deferred_pairs,
)
from deeplearning4j_tpu.nn.layers.special import CenterLossOutputLayer
from deeplearning4j_tpu.models.multilayer import (
    _check_decode_budget, _checkpointed, _dtype_of, _is_recurrent,
    record_residuals_kept,
)
from deeplearning4j_tpu.observe.trace import span
from deeplearning4j_tpu.observe.watchdog import listen_for_compiles
from deeplearning4j_tpu.optim.listeners import TrainingListener
from deeplearning4j_tpu.optim.updaters import NoOp, Updater, resolve_updater
from deeplearning4j_tpu.models.decode_state import DecodeState
from deeplearning4j_tpu.parallel.ring_attention import (
    SeqCtxJitCache, SeqCtxSolverCache,
)
from deeplearning4j_tpu.utils.pytrees import (
    flatten_params, param_count, unflatten_params,
)

_tmap = jax.tree_util.tree_map


class ComputationGraph(SeqCtxJitCache, SeqCtxSolverCache):
    """DAG network runtime over a ComputationGraphConfiguration."""

    def __init__(self, conf: ComputationGraphConfiguration):
        # from here on every compile of the process leaves `xla.*` spans
        # (this net's `init()` and first `fit()` among them)
        listen_for_compiles()
        self.conf = conf
        self.dtype = _dtype_of(conf.dtype)
        self.params_tree: Optional[Dict[str, Any]] = None
        self.state_tree: Dict[str, Any] = {}
        self.updater_state: Optional[Dict[str, Any]] = None
        self.iteration = 0
        self.epoch = 0
        self.listeners: List[TrainingListener] = []
        self.last_batch_size: Optional[int] = None
        self._loss_tracker = LossTracker()
        self._rng = jax.random.PRNGKey(conf.seed)
        # rnnTimeStep statefulness, lock-guarded (ISSUE 7: the bare-attr
        # version was an unlocked shared-state mutation)
        self._decode_state = DecodeState()
        self._stateful: set = set()
        self._vertex_updaters: Dict[str, Updater] = {}
        self._jit_caches: Dict[Any, Dict[Any, Any]] = {}
        self._solvers: Dict[Any, Any] = {}      # full-batch solver cache

    @property
    def score_(self) -> Optional[float]:
        """Most recent training loss as a float — reading this materializes
        the deferred device loss (see MultiLayerNetwork.score_)."""
        return self._loss_tracker.value

    @score_.setter
    def score_(self, value) -> None:
        self._loss_tracker.set(value)

    # ------------------------------------------------------------- init
    def init(self) -> "ComputationGraph":
        timed = span("net.init", model=type(self).__name__,
                     layers=len(self.conf.topological_order))
        with timed:
            key = jax.random.PRNGKey(self.conf.seed)
            params, states = {}, {}
            known = dict(self.conf.input_types)
            for name in self.conf.topological_order:
                v = self.conf.vertices[name]
                in_types = [known[i] for i in self.conf.vertex_inputs[name]
                            if i in known]
                key, sub = jax.random.split(key)
                p, s = v.init_params(sub, in_types, self.dtype)
                params[name] = p
                states[name] = s
                if s:
                    self._stateful.add(name)
                resolve_output_type(name, v, in_types,
                                    len(self.conf.vertex_inputs[name]), known)
            self.params_tree = params
            self.state_tree = with_counter_sums(states)
            self._build_updaters()
            self.updater_state = {
                n: u.init(params[n]) for n, u in self._vertex_updaters.items()
            }
            timed.attrs["params"] = param_count(params)   # from shapes
        return self

    def _build_updaters(self):
        global_u = resolve_updater(self.conf.updater or "sgd")
        for name in self.conf.topological_order:
            v = self.conf.vertices[name]
            u = global_u
            if isinstance(v, LayerVertex):
                layer = v.layer
                if layer.updater is not None:
                    u = resolve_updater(layer.updater)
                if layer.learning_rate is not None and hasattr(u, "learning_rate"):
                    u = dataclasses.replace(u, learning_rate=layer.learning_rate)
                if layer.frozen:
                    u = NoOp()
            self._vertex_updaters[name] = u

    # ---------------------------------------------------------- forward
    @property
    def _rnn_vertex_names(self) -> List[str]:
        """Vertices that carry RNN state (tBPTT / rnnTimeStep persistence)."""
        if not hasattr(self, "_rnn_names_cache"):
            self._rnn_names_cache = [
                n for n, v in self.conf.vertices.items()
                if isinstance(v, LayerVertex) and _is_recurrent(v.layer)
            ]
        return self._rnn_names_cache

    @property
    def _decode_vertex_names(self) -> List[str]:
        """Vertices with KV-cache decode carries (attention stepping)."""
        if not hasattr(self, "_decode_names_cache"):
            self._decode_names_cache = [
                n for n, v in self.conf.vertices.items()
                if isinstance(v, LayerVertex)
                and hasattr(v.layer, "decode_carry")
            ]
        return self._decode_names_cache

    @property
    def _pool_after(self) -> Dict[str, str]:
        """Convolution vertex -> the max-pool vertex it defers its bias and
        activation to (`defers_to_pool`): the pool is its ONLY consumer and
        has no preprocessor, and the convolution is not a network output."""
        if not hasattr(self, "_pool_after_cache"):
            conf = self.conf
            consumers: Dict[str, List[str]] = {}
            for name, ins in conf.vertex_inputs.items():
                for i in ins:
                    consumers.setdefault(i, []).append(name)
            self._pool_after_cache = {
                name: cons[0] for name, cons in consumers.items()
                if len(cons) == 1 and name not in conf.network_outputs
                and isinstance(conf.vertices.get(name), LayerVertex)
                and isinstance(conf.vertices[cons[0]], LayerVertex)
                and conf.vertices[cons[0]].preprocessor is None
                and defers_to_pool(conf.vertices[name].layer,
                                   conf.vertices[cons[0]].layer)}
        return self._pool_after_cache

    def _forward(self, params, states, inputs: Dict[str, Any], *, train, rng,
                 fmasks: Optional[Dict[str, Any]] = None,
                 carries: Optional[Dict[str, Any]] = None,
                 stop_before: Optional[str] = None, collect: bool = False):
        """Fold over topological order. Returns (values, out_inputs, states)
        where out_inputs[name] is the input activation each output layer saw
        (needed for fused-loss score). `carries` override the stored state of
        recurrent vertices (tBPTT / rnnTimeStep statefulness — reference:
        `ComputationGraph.rnnTimeStep` / `rnnUpdateStateWithTBPTTState`).

        A convolution vertex whose only consumer is a max-pool
        (`_pool_after`) adds its bias and activates on the pool's output,
        under its own scope, and leaves no entry in `values`. Not with
        `collect` (every vertex's own activation, as `MultiLayerNetwork.
        feed_forward` gives) and not under `gradient_checkpointing`."""
        values: Dict[str, Any] = dict(inputs)
        out_inputs: Dict[str, Any] = {}
        new_states: Dict[str, Any] = {}
        remat = train and self.conf.gradient_checkpointing
        pool_after = {} if collect or remat else self._pool_after
        tails = {}      # pool vertex -> (convolution vertex, its tail)
        kept = np.zeros(2, int)     # named residuals that stay
        for idx, name in enumerate(self.conf.topological_order):
            if name == stop_before:
                break
            v = self.conf.vertices[name]
            ins = [values[i] for i in self.conf.vertex_inputs[name]]
            st = states.get(name) or None
            if carries is not None and name in carries:
                st = carries[name]
            lrng = None if rng is None else jax.random.fold_in(rng, idx)
            mask = None
            if fmasks:
                # A vertex may name the network input whose mask it wants
                # (CrossAttentionVertex.key_mask_input — the generic
                # first-match rule below would deliver the wrong stream's
                # mask to a two-input attention vertex).
                pref = getattr(v, "key_mask_input", None)
                if pref is not None:
                    # Named-input mask ONLY — falling back to first-match
                    # would hand a different stream's mask to a vertex
                    # that trusts whatever it receives as a key mask.
                    mask = fmasks.get(pref)
                else:
                    for i in self.conf.vertex_inputs[name]:
                        if i in fmasks:
                            mask = fmasks[i]
                            break
            # the vertex's name on its device ops (and, as
            # `transpose(jvp(<name>))`, on its backward ops): debug
            # locations only, the compiled program is the same
            with jax.named_scope(name):
                if isinstance(v, LayerVertex) and v.layer.is_output_layer:
                    x = ins[0]
                    if v.preprocessor is not None:
                        x = v.preprocessor.apply(x)
                    out_inputs[name] = x
                    y, new_st = v.layer.apply(
                        params[name], x, state=st, train=train, rng=lrng,
                        mask=mask)
                elif name in pool_after and pool_after[name] != stop_before:
                    x = ins[0]
                    if v.preprocessor is not None:
                        x = v.preprocessor.apply(x)
                    y, tail = v.layer.split(params[name], x, train=train,
                                            rng=lrng)
                    tails[pool_after[name]], new_st = (name, tail), st
                elif remat and isinstance(v, LayerVertex):
                    # remat this layer vertex in the backward pass; cheap
                    # parameterless vertices (merge/elementwise/...) are
                    # NOT wrapped — their outputs are checkpoint residuals
                    # anyway, so wrapping buys nothing and blocks CSE
                    (y, new_st), named = _checkpointed(v.apply, mask)(
                        params[name], ins, st, lrng)
                    kept += named
                else:
                    y, new_st = v.apply(
                        params[name], ins, state=st, train=train, rng=lrng,
                        mask=mask)
            if name in tails:
                conv, tail = tails[name]
                with jax.named_scope(conv):
                    y = tail(y)
                del values[conv]
            values[name] = y
            new_states[name] = new_st
        if not collect:
            record_deferred_pairs(self, len(tails))
            record_residuals_kept(self, kept)
        return values, out_inputs, new_states

    # ------------------------------------------------------------- loss
    def _loss(self, params, states, inputs, labels: Dict[str, Any],
              fmasks, lmasks, rng, train=True, carries=None):
        values, out_inputs, new_states = self._forward(
            params, states, inputs, train=train, rng=rng, fmasks=fmasks,
            carries=carries)
        total = jnp.asarray(0.0, jnp.float32)
        for name in self.conf.network_outputs:
            v = self.conf.vertices[name]
            if not (isinstance(v, LayerVertex) and v.layer.is_output_layer):
                continue
            lm = lmasks.get(name) if lmasks else None
            lab = labels[name]
            # the output layer's own work in a train step is its score
            with jax.named_scope(name), jax.named_scope("loss"):
                if isinstance(v.layer, CenterLossOutputLayer):
                    s, cstate = v.layer.score_and_state(
                        params[name], out_inputs[name], lab, states[name],
                        lm)
                    new_states[name] = cstate
                else:
                    s = v.layer.score(params[name], out_inputs[name], lab,
                                      lm)
                total = total + s
        with jax.named_scope("regularization"):
            for name, v in self.conf.vertices.items():
                if isinstance(v, LayerVertex):
                    total = total + v.layer.regularization(params[name])
        # Activity-dependent auxiliary losses (e.g. MoE load balancing)
        # reported via vertex state — differentiated with the score.
        for st in new_states.values():
            if isinstance(st, dict) and "aux_loss" in st:
                total = total + st["aux_loss"]
        return total, new_states

    # ------------------------------------------------------ train step
    def make_step_fn(self, tbptt: bool = False):
        """Pure (un-jitted) train-step fn for parallel trainers (see
        MultiLayerNetwork.make_step_fn)."""
        return make_train_step(
            functools.partial(self._loss, train=True), self._vertex_updaters,
            grad_norm=(self.conf.gradient_normalization,
                       self.conf.gradient_normalization_threshold),
            stateful=self._stateful,
            carry_names=self._rnn_vertex_names if tbptt else None)

    def _get_train_step(self, key, tbptt: bool = False):
        """The jitted step; `key` is (has_fmasks, has_lmasks)."""
        key = (key, tbptt)
        if key in self._jit_cache:
            return self._jit_cache[key]
        return build_step(
            functools.partial(self.make_step_fn, tbptt=tbptt),
            cache=self._jit_cache, key=key, name="ComputationGraph._step")

    # ---------------------------------------------------- data plumbing
    def _features(self, name: str, x, asarray=jnp.asarray):
        """Input `name`'s features as the forward pass takes them
        (`as_features`): ids into an embedding keep their dtype."""
        if not hasattr(self, "_id_inputs"):
            self._id_inputs = {
                i for v, ins in self.conf.vertex_inputs.items() for i in ins
                if getattr(getattr(self.conf.vertices[v], "layer", None),
                           "TAKES_IDS", False)}
        return as_features(x, self.dtype, asarray,
                           ids=name in self._id_inputs)

    def _batch_args(self, ds: Union[DataSet, MultiDataSet],
                    host: bool = False):
        """A DataSet/MultiDataSet as the step's batch arguments: dicts of
        named inputs/outputs by order, real features in the net's dtype and
        integer ids as they came.
        `host=True` keeps leaves as numpy (multi-controller feeding: the
        caller lifts them into global arrays in one upload)."""
        asarray = np.asarray if host else jnp.asarray
        ins = self.conf.network_inputs
        outs = self.conf.network_outputs
        if isinstance(ds, MultiDataSet):
            feats = {n: self._features(n, f, asarray)
                     for n, f in zip(ins, ds.features)}
            labs = {n: asarray(l) for n, l in zip(outs, ds.labels)}
            fmasks = {}
            if ds.features_masks:
                fmasks = {n: asarray(m) for n, m in
                          zip(ins, ds.features_masks) if m is not None}
            lmasks = {}
            if ds.labels_masks:
                lmasks = {n: asarray(m) for n, m in
                          zip(outs, ds.labels_masks) if m is not None}
            return feats, labs, fmasks or None, lmasks or None
        feats = {ins[0]: self._features(ins[0], ds.features, asarray)}
        labs = {outs[0]: asarray(ds.labels)} if ds.labels is not None else {}
        fmasks = ({ins[0]: asarray(ds.features_mask)}
                  if ds.features_mask is not None else None)
        lmasks = ({outs[0]: asarray(ds.labels_mask)}
                  if ds.labels_mask is not None else None)
        return feats, labs, fmasks, lmasks

    # ---------------------------------------------------------- fit API
    def fit(self, data, labels=None, *, epochs: int = 1, batch_size: int = 32,
            steps_per_dispatch: int = 1, device_prefetch: bool = True,
            sync_every: int = 0, checkpointer=None, checkpoint_every: int = 1,
            resume=None, stop_fn=None, preemption=None):
        """Reference: `ComputationGraph.fit(DataSetIterator):778` (also
        accepts MultiDataSet / arrays / iterator / iterable of batches).
        Pipelined per the async-dispatch contract — see
        `MultiLayerNetwork.fit` for the knob semantics, including the
        recovery knobs (``checkpointer``/``checkpoint_every``/``resume``/
        ``stop_fn``/``preemption`` — `optim/recovery.RecoveryPlan`). Each
        epoch re-iterates the source (`iter(...)` per epoch), so
        multi-epoch fit over a DataSetIterator or an iterable of DataSets
        replays every batch every epoch."""
        if self.params_tree is None:
            raise RuntimeError("Network not initialized — call init() first")
        plan = build_plan(self, checkpointer=checkpointer,
                          checkpoint_every=checkpoint_every, resume=resume,
                          stop_fn=stop_fn, preemption=preemption)
        if isinstance(data, MultiDataSet):
            iterable: Any = [data]
        else:
            iterable = as_iterator(data, labels, batch_size)
        if device_prefetch:
            iterable = DevicePrefetchIterator(
                iterable, depth=max(2, int(steps_per_dispatch)))
        self._loss_tracker.sync_every = int(sync_every)
        execu = TrainingExecutor(
            self,
            step=self._fit_batch,
            fused_step=self._fused_dispatch,
            can_fuse=self._can_fuse,
            steps_per_dispatch=steps_per_dispatch,
            before_batch=plan.before_batch if plan else None,
            after_dispatch=plan.after_dispatch if plan else None,
            epoch_start=plan.epoch_start if plan else None,
            epoch_end=plan.epoch_end if plan else None,
        )
        run_with_recovery(execu, plan, iterable, epochs)
        self.stopped_early = execu.stopped
        return self

    def _fit_batch(self, ds: Union[DataSet, MultiDataSet]):
        """One training step; returns the loss as a DEVICE array on the
        SGD path (deferred sync — see LossTracker)."""
        feats, labs, fmasks, lmasks = self._batch_args(ds)
        self.last_batch_size = next(iter(feats.values())).shape[0]
        if self.conf.optimization_algo != "stochastic_gradient_descent":
            from deeplearning4j_tpu.optim.solvers import fit_with_solver

            return fit_with_solver(self, feats, labs, fmasks, lmasks)
        if (self.conf.tbptt_fwd_length > 0
                and all(v.ndim == 3 for v in feats.values())):
            return self._fit_tbptt(feats, labs, fmasks, lmasks)
        key = (fmasks is not None, lmasks is not None)
        fn = self._get_train_step(key)
        self._rng, k = jax.random.split(self._rng)
        (self.params_tree, self.updater_state, self.state_tree,
         loss) = fn(self.params_tree, self.updater_state, self.state_tree,
                    jnp.asarray(self.iteration, jnp.int32),
                    feats, labs, fmasks, lmasks, k)
        return loss

    def _can_fuse(self, ds) -> bool:
        if self.conf.optimization_algo != "stochastic_gradient_descent":
            return False
        if self.conf.tbptt_fwd_length > 0:
            fs = ds.features if hasattr(ds, "features_masks") else [ds.features]
            if all(f.ndim == 3 for f in fs):
                return False
        return True

    def _get_fused_step(self, key, k: int):
        cache_key = ("fused", key, k)
        if cache_key in self._jit_cache:
            return self._jit_cache[cache_key]
        return build_step(self.make_step_fn, fused=True,
                          cache=self._jit_cache, key=cache_key,
                          name="ComputationGraph._fused_step")

    def _stacked_batch_args(self, batches: Sequence):
        """K same-shape batches as the fused step's arguments, stacked on
        a leading axis: on the host (numpy) when the batches are there, so
        the caller's placement is each tensor's one transfer; a prefetched
        (device-array) batch stacks on the device."""
        f0 = batches[0].features
        host = isinstance(
            f0[0] if hasattr(batches[0], "features_masks") else f0,
            np.ndarray)
        return stack_step_args(
            [self._batch_args(b, host=host) for b in batches])

    def _fused_dispatch(self, batches: Sequence):
        """K same-shape batches → one `lax.scan` dispatch → (K,) losses."""
        feats, labs, fms, lms = _tmap(
            jnp.asarray, self._stacked_batch_args(batches))
        self.last_batch_size = next(iter(feats.values())).shape[1]
        fn = self._get_fused_step((fms is not None, lms is not None),
                                  len(batches))
        (self.params_tree, self.updater_state, self.state_tree, self._rng,
         losses) = fn(self.params_tree, self.updater_state, self.state_tree,
                      np.int32(self.iteration), self._rng,
                      feats, labs, fms, lms)
        return losses

    def _fit_tbptt(self, feats, labs, fmasks, lmasks) -> float:
        """Truncated BPTT over every 3-D input/label dict entry; RNN vertex
        state carried across chunks with stop_gradient. Reference:
        `ComputationGraph.fit` tBPTT dispatch (`:778`) + doTruncatedBPTT."""
        L = self.conf.tbptt_fwd_length
        Lb = min(self.conf.tbptt_back_length or L, L)
        T = next(iter(feats.values())).shape[1]
        for name, lab in labs.items():
            if lab.ndim != 3:
                raise ValueError(
                    f"Truncated BPTT requires per-timestep 3-D labels; "
                    f"output {name!r} has shape {tuple(lab.shape)}")
        key = (fmasks is not None, lmasks is not None)
        fn = self._get_train_step(key, tbptt=True)
        carries = {}
        losses = []
        for lo in range(0, T, L):
            hi = min(lo + L, T)
            t_lo = lo

            def sl(d, a, b):
                return None if d is None else {
                    n: jnp.asarray(v[:, a:b]) for n, v in d.items()}

            if Lb < hi - lo:
                # fwd > back: advance carries over the prefix, no update.
                t_lo = hi - Lb
                carries = self._advance_carries(
                    sl(feats, lo, t_lo), sl(fmasks, lo, t_lo), carries)
            self._rng, k = jax.random.split(self._rng)
            (self.params_tree, self.updater_state, self.state_tree, loss,
             carries) = fn(
                self.params_tree, self.updater_state, self.state_tree,
                jnp.asarray(self.iteration, jnp.int32),
                sl(feats, t_lo, hi), sl(labs, t_lo, hi),
                sl(fmasks, t_lo, hi), sl(lmasks, t_lo, hi), k,
                carries if carries else None)
            losses.append(loss)
        # Mean on device — no per-chunk host syncs.
        return jnp.stack(losses).mean()

    def _advance_carries(self, feats, fmasks, carries):
        """Gradient-free forward that only advances RNN vertex carries."""
        key = ("advance", fmasks is not None, bool(carries))
        if key not in self._jit_cache:
            rnn_names = self._rnn_vertex_names

            def adv(params, states, inputs, fm, car):
                _, _, new_states = self._forward(
                    params, states, inputs, train=False, rng=None,
                    fmasks=fm, carries=car)
                return {n: new_states[n] for n in rnn_names}

            self._jit_cache[key] = jax.jit(adv)
        return self._jit_cache[key](
            self.params_tree, self.state_tree, feats, fmasks,
            carries if carries else None)

    # ----------------------------------------------------- rnn stepping
    @property
    def _rnn_carries(self):
        """Read view of the ambient stepping carries (mutations live in
        the lock-guarded `DecodeState`)."""
        return self._decode_state.carries

    @property
    def _decode_pos(self):
        return self._decode_state.pos

    def rnn_time_step(self, *xs):
        """Stateful single-step inference; RNN vertex carries persist across
        calls. Reference: `ComputationGraph.rnnTimeStep`. Attention
        vertices step the same way via their decode carries (KV cache),
        mirroring `MultiLayerNetwork.rnn_time_step`. The read-step-write
        runs under the decode-state lock so concurrent callers serialize
        instead of corrupting each other's carries."""
        inputs = {}
        for n, x in zip(self.conf.network_inputs, xs):
            x = self._features(n, x)
            if x.ndim == 2:
                x = x[:, None, :]
            inputs[n] = x
        decode_names = self._decode_vertex_names
        st = self._decode_state
        with st.lock():
            t_step = None
            if decode_names:
                # Host-side decode-length guard (under jit the layers'
                # eager overflow checks cannot fire — see
                # MultiLayerNetwork). Only meaningful when every input
                # steps by the same length; a multi-length graph (e.g.
                # full encoder context + one decoder token per call) has
                # no single counter, so the in-kernel NaN poison is the
                # remaining overflow signal there.
                lens = {v.shape[1] for v in inputs.values() if v.ndim >= 3}
                if len(lens) == 1:
                    t_step = lens.pop()
                    _check_decode_budget(
                        self,
                        (self.conf.vertices[n].layer for n in decode_names),
                        t_step)
            if not st.carries and decode_names:
                batch = next(iter(inputs.values())).shape[0]
                # validate ALL before seeding ANY: a mid-loop raise would
                # leave partial carries behind and disarm this guard
                for n in decode_names:
                    if not getattr(self.conf.vertices[n].layer,
                                   "causal", True):
                        raise ValueError(
                            f"rnn_time_step requires causal attention; "
                            f"vertex {n!r} is non-causal (stepped "
                            f"decoding cannot reproduce a bidirectional "
                            f"forward)")
                st.seed({n: self.conf.vertices[n].layer.decode_carry(
                    batch, self.dtype) for n in decode_names})
            stateful = set(self._rnn_vertex_names) | set(decode_names)
            carries = st.carries or None
            # One jitted program per (step shapes, carry presence) — see
            # MultiLayerNetwork.rnn_time_step for why eager per-op
            # dispatch is unacceptable in a per-token decode loop on TPU.
            key = ("rnn_step",
                   tuple(sorted((n, v.shape) for n, v in inputs.items())),
                   carries is not None)
            if key not in self._jit_cache:
                def step_fn(params, states, inputs_, carries_):
                    values, _, new_states = self._forward(
                        params, states, inputs_, train=False, rng=None,
                        carries=carries_)
                    return ({o: values[o]
                             for o in self.conf.network_outputs},
                            {n: new_states[n] for n in stateful})

                self._jit_cache[key] = jax.jit(step_fn)
            values, new_carries = self._jit_cache[key](
                self.params_tree, self.state_tree, inputs, carries)
            # advance only after a successful step
            st.update(new_carries,
                      advance=t_step if t_step is not None else 0)
        outs = [values[o] for o in self.conf.network_outputs]
        return outs[0] if len(outs) == 1 else outs

    def rnn_reorder_state(self, idx) -> None:
        """Reorder/expand decode carries along the batch dimension (see
        `MultiLayerNetwork.rnn_reorder_state` — the beam-search carry
        contract is identical for graph vertices)."""
        ix = jnp.asarray(np.asarray(idx))
        self._decode_state.reorder(lambda carries: jax.tree_util.tree_map(
            lambda a: a[ix] if getattr(a, "ndim", 0) >= 1 else a, carries))

    def rnn_clear_previous_state(self):
        """Reference: `ComputationGraph.rnnClearPreviousState`."""
        self._decode_state.clear()

    # -------------------------------------------------------- pretrain
    def pretrain(self, data, *, epochs: int = 1, batch_size: int = 32):
        """Greedy layerwise unsupervised pretraining of pretrainable layer
        vertices (AutoEncoder/RBM/VAE), in topological order. Reference:
        `ComputationGraph.pretrain(DataSetIterator)`."""
        it = as_iterator(data, None, batch_size)
        for name in self.conf.topological_order:
            v = self.conf.vertices[name]
            if not (isinstance(v, LayerVertex) and v.layer.is_pretrainable):
                continue
            layer, vertex = v.layer, v
            updater = self._vertex_updaters[name]
            opt = updater.init(self.params_tree[name])

            def featurize(params, states, feats):
                """This vertex's input activation under the current params:
                fold the DAG only up to (not including) this vertex."""
                values, _, _ = self._forward(
                    params, states, feats, train=False, rng=None,
                    stop_before=name)
                x = values[self.conf.vertex_inputs[name][0]]
                if vertex.preprocessor is not None:
                    x = vertex.preprocessor.apply(x)
                return x

            @jax.jit
            # graft: allow(GL103): one program per pretrained layer by
            # design — layerwise pretraining compiles each layer once
            def pre_step(params, lp, opt_state, step, feats, rng):
                x = featurize(params, self.state_tree, feats)

                def loss_fn(p):
                    return layer.reconstruction_score(p, x, rng=rng)

                loss, grads = jax.value_and_grad(loss_fn)(lp)
                new_lp, new_opt = updater.update_with_params(
                    grads, opt_state, lp, step)
                return new_lp, new_opt, loss

            step = 0
            for _ in range(epochs):
                for ds in it:
                    feats, _, _, _ = self._batch_args(ds)
                    self._rng, k = jax.random.split(self._rng)
                    lp, opt, _ = pre_step(
                        self.params_tree, self.params_tree[name], opt,
                        jnp.asarray(step, jnp.int32), feats, k)
                    self.params_tree[name] = lp
                    step += 1
        return self

    # -------------------------------------------------------- inference
    def output(self, *xs, train: bool = False):
        """Forward; returns a list of output arrays (single array if one
        output). Reference: `ComputationGraph.output(INDArray...)`."""
        if self.params_tree is None:
            raise RuntimeError("Network not initialized — call init() first")
        inputs = {n: self._features(n, x)
                  for n, x in zip(self.conf.network_inputs, xs)}
        key = ("output", train, tuple(sorted(inputs)))
        if key not in self._jit_cache:
            def out_fn(params, states, feats):
                values, _, _ = self._forward(
                    params, states, feats, train=train, rng=None)
                return [values[o] for o in self.conf.network_outputs]
            self._jit_cache[key] = jax.jit(out_fn)
        outs = self._jit_cache[key](self.params_tree, self.state_tree, inputs)
        return outs[0] if len(outs) == 1 else outs

    def score(self, ds: Union[DataSet, MultiDataSet]) -> float:
        feats, labs, fmasks, lmasks = self._batch_args(ds)
        loss, _ = self._loss(self.params_tree, self.state_tree, feats, labs,
                             fmasks, lmasks, rng=None, train=False)
        return float(loss)

    def predict(self, *xs) -> np.ndarray:
        out = self.output(*xs)
        if isinstance(out, list):
            return [np.asarray(jnp.argmax(o, -1)) for o in out]
        return np.asarray(jnp.argmax(out, -1))

    def evaluate(self, iterator: DataSetIterator,
                 output_name: Optional[str] = None):
        """Classification evaluation of one head (default: first output),
        with the device-side argmax fast path for plain per-example labels
        (only int32 indices cross to host) — matching
        MultiLayerNetwork.evaluate. `output_name` selects a specific head
        of a multi-output graph (beyond the reference, whose
        `ComputationGraph.evaluate(DataSetIterator)` is first-output-only).
        Accepts DataSet batches (labels belong to the selected head) or
        MultiDataSet batches (labels matched to outputs by position).
        RecordMetaData from a meta-collecting iterator flows into
        per-example Prediction records."""
        from deeplearning4j_tpu.eval.evaluation import Evaluation

        order = list(self.conf.network_outputs)
        idx = 0
        if output_name is not None:
            if output_name not in order:
                raise ValueError(
                    f"Unknown output {output_name!r}; graph outputs: {order}")
            idx = order.index(output_name)

        def head(out):
            return out[idx] if isinstance(out, list) else out

        ev = Evaluation()
        for ds in iterator:
            meta = getattr(iterator, "last_meta", None)
            if isinstance(ds, MultiDataSet):
                feats = list(ds.features)
                lab = np.asarray(ds.labels[idx])
                mask = ds.labels_masks[idx] if ds.labels_masks else None
            else:
                feats = [ds.features]
                lab = np.asarray(ds.labels)
                mask = ds.labels_mask
            if lab.ndim == 3 or mask is not None:
                ev.eval(lab, np.asarray(head(self.output(*feats))),
                        mask=mask,
                        record_meta=None if lab.ndim == 3 else meta)
                continue
            o = head(self.output(*feats))
            pred = jnp.argmax(o, axis=-1)       # argmax on device
            actual = (lab.argmax(-1) if lab.ndim == 2
                      else lab.astype(np.int64))
            n = lab.shape[-1] if lab.ndim == 2 else int(o.shape[-1])
            ev.eval_indices(actual, np.asarray(pred), num_classes=n,
                            record_meta=meta)
        return ev

    def evaluate_outputs(self, iterator,
                         output_names: Optional[Sequence[str]] = None
                         ) -> Dict[str, "Evaluation"]:
        """Per-output metrics for multi-output graphs in ONE forward pass
        per batch: returns {output_name: Evaluation}. Accepts DataSet
        (single-output graphs) or MultiDataSet iterators (labels matched
        to outputs by position, the _batch_args ordering). RecordMetaData
        from a meta-collecting iterator flows into every head's
        Prediction records. Reference: `nn/graph/ComputationGraph.java`
        evaluate family (single-output) — multi-output eval is a
        capability extension."""
        from deeplearning4j_tpu.data.dataset import MultiDataSet
        from deeplearning4j_tpu.eval.evaluation import Evaluation

        order = list(self.conf.network_outputs)
        names = list(output_names) if output_names is not None else order
        for n in names:
            if n not in order:
                raise ValueError(
                    f"Unknown output {n!r}; graph outputs: {order}")
        evals = {n: Evaluation() for n in names}
        for ds in iterator:
            if isinstance(ds, MultiDataSet):
                feats = [np.asarray(f) for f in ds.features]
                labels = {n: ds.labels[order.index(n)] for n in names}
                masks = ({n: ds.labels_masks[order.index(n)] for n in names}
                         if ds.labels_masks else {n: None for n in names})
            else:
                if len(order) > 1 and len(names) != 1:
                    raise ValueError(
                        "DataSet batches carry ONE labels array; evaluating "
                        f"{len(names)} heads of a multi-output graph needs "
                        "MultiDataSet batches (labels per output)")
                # single head requested: the DataSet's labels are its labels
                feats = [ds.features]
                labels = {n: ds.labels for n in names}
                masks = {n: ds.labels_mask for n in names}
            outs = self.output(*feats)
            if not isinstance(outs, list):
                outs = [outs]
            meta = getattr(iterator, "last_meta", None)
            for n in names:
                lab = np.asarray(labels[n])
                evals[n].eval(
                    lab, np.asarray(outs[order.index(n)]), mask=masks[n],
                    record_meta=None if lab.ndim == 3 else meta)
        return evals

    def evaluate_regression(self, iterator: DataSetIterator):
        """Reference: `ComputationGraph.evaluateRegression:2780`."""
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        from deeplearning4j_tpu.eval.regression import RegressionEvaluation

        return Evaluation.run_evaluation(
            RegressionEvaluation(), iterator, self.output)

    def evaluate_roc(self, iterator: DataSetIterator,
                     threshold_steps: int = 0):
        """Binary ROC over the (single) output. Reference: evaluateROC."""
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        from deeplearning4j_tpu.eval.roc import ROC

        return Evaluation.run_evaluation(
            ROC(threshold_steps), iterator, self.output)

    def evaluate_roc_multi_class(self, iterator: DataSetIterator):
        """Reference: evaluateROCMultiClass."""
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        from deeplearning4j_tpu.eval.roc import ROCMultiClass

        return Evaluation.run_evaluation(
            ROCMultiClass(), iterator, self.output)

    # ----------------------------------------------------- param views
    def params(self) -> np.ndarray:
        flat, _ = flatten_params(self.params_tree)
        return np.asarray(flat)

    def set_params(self, flat) -> None:
        self.params_tree = unflatten_params(jnp.asarray(flat), self.params_tree)

    def num_params(self) -> int:
        return param_count(self.params_tree)

    def set_listeners(self, *listeners: TrainingListener) -> None:
        self.listeners = list(listeners)

    def add_listener(self, l: TrainingListener) -> None:
        self.listeners.append(l)
