"""MultiLayerNetwork — sequential-stack model runtime.

Reference parity: `nn/multilayer/MultiLayerNetwork.java` — `init():446`,
`feedForward:752-858`, `fit(DataSetIterator):1046`, `backprop():1147`,
tBPTT `:1102-1104,1351`, `output:1716-1827`, `computeGradientAndScore():2047`,
pretrain `:214-301` — and the solver loop
(`optimize/solvers/StochasticGradientDescent.java:58-98`).

TPU-first redesign: the reference's OUTER HOT LOOP (SURVEY §3.1) ran dozens of
eager native ops per layer per step; here `fit()` compiles forward + backward
+ updater into ONE donated, jitted XLA computation. Parameters and optimizer
state are pytrees keyed by layer name (the reference's flattened view arrays
are available on demand via `params()` for serde/parity). Gradients come from
`jax.value_and_grad` — the reference's per-layer `backpropGradient` chain is
the autodiff transpose.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterators import (
    DataSetIterator, DevicePrefetchIterator, as_iterator,
)
from deeplearning4j_tpu.models.decode_state import DecodeState
from deeplearning4j_tpu.nn.config import MultiLayerConfiguration
from deeplearning4j_tpu.optim.executor import LossTracker, TrainingExecutor
from deeplearning4j_tpu.optim.recovery import build_plan, run_with_recovery
from deeplearning4j_tpu.optim.step import (
    as_features, build_step, make_train_step, stack_step_args,
    with_counter_sums,
)
from deeplearning4j_tpu.nn.layers.base import Layer
from deeplearning4j_tpu.nn.layers.convolution import (
    defers_to_pool, record_deferred_pairs,
)
from deeplearning4j_tpu.nn.layers.recurrent import (
    BaseRecurrentLayer, Bidirectional, GravesBidirectionalLSTM, LastTimeStep,
)
from deeplearning4j_tpu.nn.layers.special import LoopedStack
from deeplearning4j_tpu.observe.registry import get_registry
from deeplearning4j_tpu.observe.trace import span
from deeplearning4j_tpu.observe.watchdog import listen_for_compiles
from deeplearning4j_tpu.optim.listeners import TrainingListener
from deeplearning4j_tpu.optim.updaters import NoOp, Updater, resolve_updater
from deeplearning4j_tpu.parallel.ring_attention import (
    SeqCtxJitCache, SeqCtxSolverCache,
)
from deeplearning4j_tpu.utils.pytrees import (
    flatten_params, param_count, unflatten_params,
)

_tmap = jax.tree_util.tree_map


def _dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16, "float64": jnp.float64}[name]


def _is_recurrent(layer: Layer) -> bool:
    return isinstance(
        layer, (BaseRecurrentLayer, Bidirectional, GravesBidirectionalLSTM)
    )


def _decode_limit(decode_layers) -> Optional[int]:
    """Smallest KV-cache/position bound among decode-capable layers —
    the host-side decode-length guard's ceiling (under the jitted
    stepping path the layers' own eager overflow checks cannot fire).
    Rolling-cache layers stream in fixed memory, so their max_cache is
    a buffer size, not a length bound."""
    limits = []
    for l in decode_layers:
        if not getattr(l, "rolling_cache", False):
            mc = getattr(l, "max_cache", None)
            if mc is not None:
                limits.append(mc)
        ml = getattr(l, "max_length", None)
        if ml is not None:
            limits.append(ml)
    return min(limits) if limits else None


def _check_decode_budget(model, decode_layers, t_step: int) -> None:
    """The shared host-side decode-length guard: raises before a step
    that would run past the smallest cache/position limit. The caller
    advances `model._decode_pos` only after a successful step."""
    limit = _decode_limit(decode_layers)
    pos0 = getattr(model, "_decode_pos", 0)
    if limit is not None and pos0 + t_step > limit:
        raise ValueError(
            f"decode position {pos0} + step {t_step} exceeds the "
            f"smallest cache/position limit {limit}; raise "
            f"max_cache/max_length or rnn_clear_previous_state()")


def _checkpointed(apply_fn, mask):
    """Wrap one layer/vertex apply in jax.checkpoint for the TRAIN path
    (gradient_checkpointing): its activations are rematerialized in the
    backward pass instead of stored, all but the values named with one of
    `ops/attention.KEPT_NAMES`, which the policy keeps from the forward
    pass to the backward:

    - what a Pallas attention kernel's backward reads of its forward, the
      kernel's output and its rows' log-sum-exp (`RESIDUAL_NAMES`: one
      hidden-sized tensor and T floats a head), which only a second run of
      the kernel could remake;
    - what a block's recomputation would remake only to read it again
      (`BLOCK_RESIDUAL_NAMES`): both halves' outputs of a
      `SandwichTransformerBlock`, whose norms read them (two `[T, d]`
      tensors a block), the stream between the halves of a `PreNormBlock`
      (one), a sparse `MultiHeadAttention`'s block selection
      (`[B, KV heads, T, blocks]` bools), and an expert layer's schedule
      (five values a `parallel/moe.ExpertFeedForward`: the router's
      choice `[N, k]`, the pairs' order and places by expert, the
      experts' sizes, all int32, and the pairs' weights in row order, each
      N x k). Kept, what made each (`Wo`, the
      feed-forward's or the shared expert's down-projection, the routed
      experts' tier, the selection, the router's `top_k`, the schedule's
      sort and count) is dead in the recomputed forward and JAX leaves it
      out; the gradients are the same numbers.

    A layer that names nothing keeps nothing. Returns the apply's result
    and the pair (kernel calls whose pair was named, block values named).
    Shared by MultiLayerNetwork and ComputationGraph so the remat
    semantics can't drift."""
    from deeplearning4j_tpu.ops.attention import KEPT_NAMES

    remat = jax.checkpoint(
        lambda p, x, st, lr, _a=apply_fn:
        _a(p, x, state=st, train=True, rng=lr, mask=mask),
        policy=jax.checkpoint_policies.save_only_these_names(*KEPT_NAMES))

    def apply(p, x, st, lr):
        before = _named_so_far()
        out = remat(p, x, st, lr)
        return out, _named_so_far() - before

    return apply


def _named_so_far():
    """(kernel calls, block values) this thread has named while tracing."""
    from deeplearning4j_tpu.ops.attention import (
        block_residuals_named, residuals_named,
    )

    return np.array([residuals_named(), block_residuals_named()])


def record_residuals_kept(model, kept) -> None:
    """`kept` is the sum of `_checkpointed`'s pairs over the forward pass
    that `model` traced last. Gauge
    `attention_residuals_kept{model=<class>}`: how many attention kernel
    calls had their output and log-sum-exp named inside a checkpointed
    layer (5 for the benchmark's `trinity_large`, 0 without
    `gradient_checkpointing` or without a Pallas attention forward under
    differentiation). Gauge `block_residuals_kept{model=<class>}`: how
    many values the blocks named inside checkpointed layers
    (`ops/attention.name_block_residual`: two a sandwich block, the
    stream of a pre-norm block, a selection, and five an expert layer,
    its choice and its schedule: 30 for `trinity_large`, 25 for
    `deepseek_v2`, 5 for `minicpm_sala`; 0 without
    `gradient_checkpointing`). Both are set at trace time and read by no
    benchmark metric."""
    for name, value in zip(("attention_residuals_kept",
                            "block_residuals_kept"), kept):
        get_registry().gauge(name, model=type(model).__name__).set(
            int(value))


def record_loops(model, layers) -> None:
    """Gauges `loop_passes{model=<class>}` and
    `loop_block_applications{model=<class>}`: over the `LoopedStack`s of
    `layers`, the passes a step makes and the member applications they
    come to (4 and 24 for the benchmark's `ouro_2_6b`). Set at trace
    time, and only for a model that has such a layer."""
    loops = [l for l in layers if isinstance(l, LoopedStack)]
    if loops:
        for name, value in (
                ("loop_passes", sum(l.passes for l in loops)),
                ("loop_block_applications",
                 sum(l.passes * len(l.layers) for l in loops))):
            get_registry().gauge(name, model=type(model).__name__).set(value)


def record_sparse_dense(model, dense: int) -> None:
    """Gauge `sparse_layers_dense{model=<class>}`: how many layers with a
    block selection the forward pass that `model` traced last ran as plain
    causal attention because the sequence was no longer than their
    `dense_len` (0 for the benchmark's `minicpm_sala` at 16,384 tokens,
    and for a model with no such layer)."""
    get_registry().gauge("sparse_layers_dense",
                         model=type(model).__name__).set(dense)


class MultiLayerNetwork(SeqCtxJitCache, SeqCtxSolverCache):
    """Sequential network runtime over a MultiLayerConfiguration."""

    def __init__(self, conf: MultiLayerConfiguration):
        # from here on every compile of the process leaves `xla.*` spans
        # (this net's `init()` and first `fit()` among them)
        listen_for_compiles()
        self.conf = conf
        self.layers: Tuple[Layer, ...] = conf.layers
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
        self._stateful: set = set()           # layers with persistent state (BN)
        self._layer_updaters: Dict[str, Updater] = {}
        self._jit_caches: Dict[Any, Dict[Any, Any]] = {}
        # rnnTimeStep statefulness, lock-guarded (ISSUE 7: the bare-attr
        # version was an unlocked shared-state mutation)
        self._decode_state = DecodeState()
        self._solvers: Dict[Any, Any] = {}      # full-batch solver cache
        # a head with `tied_to`: its name -> the layer whose W it reads
        self._tied: Dict[str, str] = {
            l.name: self._tied_target(l) for l in self.layers
            if getattr(l, "tied_to", None) is not None}

    def _tied_target(self, layer) -> str:
        names = [l.name for l in self.layers]
        tied = layer.tied_to
        if isinstance(tied, int) and 0 <= tied < len(names):
            tied = names[tied]
        if tied not in names or tied == layer.name:
            raise ValueError(f"{layer.name}: tied_to {layer.tied_to!r} "
                             f"names no other layer of {names}")
        return tied

    def _params_of(self, params, layer):
        """The layer's own leaves and, for a head with `tied_to`, the
        named layer's `W` beside them: one leaf in the tree, read twice.
        Transposed and as the head's own `W`, or as the layer's `TIED_AS`
        = (key, transposed) says (a head that keeps a `W` of its own and
        looks its labels up in the embedding's table)."""
        own = params[layer.name]
        if layer.name not in self._tied:
            return own
        key, transposed = getattr(layer, "TIED_AS", ("W", True))
        w = params[self._tied[layer.name]]["W"]
        return {**own, key: w.T if transposed else w}

    @property
    def score_(self) -> Optional[float]:
        """Most recent training loss as a float. Reading this MATERIALIZES
        the deferred device loss (forces a host sync) — cheap after epoch
        end, a pipeline stall if polled every step mid-fit."""
        return self._loss_tracker.value

    @score_.setter
    def score_(self, value) -> None:
        self._loss_tracker.set(value)

    # ------------------------------------------------------------- init
    def init(self) -> "MultiLayerNetwork":
        """Initialize params/state. Reference: `MultiLayerNetwork.init():446`."""
        timed = span("net.init", model=type(self).__name__,
                     layers=len(self.layers))
        with timed:
            key = jax.random.PRNGKey(self.conf.seed)
            params, states = {}, {}
            it = self.conf.input_type
            for i, layer in enumerate(self.layers):
                if it is not None and i in self.conf.preprocessors:
                    it = self.conf.preprocessors[i].output_type(it)
                key, sub = jax.random.split(key)
                p, s = layer.init_params(sub, it, self.dtype)
                params[layer.name] = p
                states[layer.name] = s
                if s:
                    self._stateful.add(layer.name)
                if it is not None:
                    it = layer.output_type(it)
            for layer in self.layers:
                if layer.name not in self._tied:
                    continue
                source = self._tied[layer.name]
                w = params[source].get("W")
                # a head reads it transposed: [n_out, n_in]; one that
                # looks labels up in it (`TIED_AS`) needs its rows' width
                want = ((layer.n_out, layer.n_in)
                        if getattr(layer, "TIED_AS", ("W", True))[1]
                        else (None, layer.n_in))
                if w is None or w.ndim != 2 or any(
                        n is not None and n != m
                        for n, m in zip(want, w.shape)):
                    raise ValueError(
                        f"{layer.name} is tied to {source}, whose W is "
                        f"{None if w is None else w.shape}, not {want}")
            self.params_tree = params
            self.state_tree = with_counter_sums(states)
            self._build_updaters()
            self.updater_state = {
                name: u.init(params[name])
                for name, u in self._layer_updaters.items()
            }
            timed.attrs["params"] = param_count(params)   # from shapes
        return self

    def _build_updaters(self):
        """Per-layer updaters honoring per-layer overrides + freezing.
        Reference: `nn/updater/MultiLayerUpdater` / UpdaterBlock grouping."""
        global_u = resolve_updater(self.conf.updater or "sgd")
        for layer in self.layers:
            u = layer.updater if layer.updater is not None else global_u
            u = resolve_updater(u)
            if layer.learning_rate is not None and hasattr(u, "learning_rate"):
                u = dataclasses.replace(u, learning_rate=layer.learning_rate)
            if layer.frozen:
                u = NoOp()
            self._layer_updaters[layer.name] = u

    # ---------------------------------------------------------- forward
    def _forward(self, params, states, x, *, train: bool, rng, fmask=None,
                 carries: Optional[Dict[str, Any]] = None,
                 collect: bool = False):
        """Run the stack; returns (final_out, out_layer_input, new_states,
        activations?). Reference: `feedForward:752-858`.

        A convolution directly in front of a max-pool (`defers_to_pool`,
        no preprocessor between) adds its bias and activates on the pool's
        output, under its own scope. Not with `collect` (`feed_forward`
        gets every layer's own activation) and not under
        `gradient_checkpointing`, whose unit is one layer."""
        acts = []
        new_states = {}
        out_in = x
        n = len(self.layers)
        remat = train and self.conf.gradient_checkpointing
        tails = {}      # pool's index -> (convolution's name, its tail)
        kept = np.zeros(2, int)     # named residuals that stay
        from deeplearning4j_tpu.ops.sparse_attention import dense_runs

        dense_before = dense_runs()
        for i, layer in enumerate(self.layers):
            # the layer's name on its device ops (and, as
            # `transpose(jvp(<name>))`, on its backward ops): debug
            # locations only, the compiled program is the same
            with jax.named_scope(layer.name):
                if i in self.conf.preprocessors:
                    x = self.conf.preprocessors[i].apply(x, fmask)
                if layer.is_output_layer and i == n - 1:
                    out_in = x
                st = states.get(layer.name) or None
                if carries is not None and layer.name in carries:
                    st = carries[layer.name]
                lrng = None if rng is None else jax.random.fold_in(rng, i)
                if (not collect and not remat and i + 1 < n
                        and i + 1 not in self.conf.preprocessors
                        and defers_to_pool(layer, self.layers[i + 1])):
                    x, tail = layer.split(params[layer.name], x,
                                          train=train, rng=lrng)
                    tails[i + 1], new_st = (layer.name, tail), st
                elif remat and isinstance(layer, LoopedStack):
                    # the unit is one member's one application: the loop
                    # layer checkpoints each, and is not wrapped again.
                    # Its passes are one traced body, and a kernel's
                    # forward rule names its pair when the loop is
                    # differentiated, inside `run`: what was named by
                    # its end was named once for every pass
                    before = _named_so_far()
                    x, new_st = layer.run(
                        self._params_of(params, layer), x, train=True,
                        rng=lrng, mask=fmask, unit=_checkpointed), st
                    kept += (_named_so_far() - before) * layer.passes
                elif remat and not (layer.is_output_layer and i == n - 1):
                    # remat this layer's activations in the backward pass
                    # (memory ∝ depth → the layers' inputs and what they
                    # name, `_checkpointed`; +~33% FLOPs less what the
                    # names keep out); the output layer is skipped — its
                    # input is retained for the loss anyway
                    (x, new_st), named = _checkpointed(layer.apply, fmask)(
                        self._params_of(params, layer), x, st, lrng)
                    kept += named
                else:
                    x, new_st = layer.apply(
                        self._params_of(params, layer), x, state=st,
                        train=train, rng=lrng, mask=fmask)
            if i in tails:
                name, tail = tails[i]
                with jax.named_scope(name):
                    x = tail(x)
            new_states[layer.name] = new_st
            if collect:
                acts.append(x)
        if not collect:
            record_deferred_pairs(self, len(tails))
            record_residuals_kept(self, kept)
            record_sparse_dense(self, dense_runs() - dense_before)
            record_loops(self, self.layers)
        return x, out_in, new_states, acts

    # ------------------------------------------------------------- loss
    def _loss(self, params, states, features, labels, fmask, lmask, rng,
              train: bool = True, carries=None):
        """Score = output-layer loss + L1/L2 regularization.
        Reference: `computeGradientAndScore():2047` + calcL1/calcL2."""
        out, out_in, new_states, _ = self._forward(
            params, states, features, train=train, rng=rng, fmask=fmask,
            carries=carries,
        )
        out_layer = self.layers[-1]
        score_mask = lmask if lmask is not None else (
            fmask if labels is not None and labels.ndim == 3 else None
        )
        # the output layer's own work in a train step is its score
        with jax.named_scope(out_layer.name), jax.named_scope("loss"):
            if hasattr(out_layer, "score_and_state"):
                # a head that keeps state from its score (class centres,
                # the exit distribution's counters)
                score, new_states[out_layer.name] = (
                    out_layer.score_and_state(
                        self._params_of(params, out_layer), out_in, labels,
                        states[out_layer.name], score_mask))
            else:
                score = out_layer.score(self._params_of(params, out_layer),
                                        out_in, labels, score_mask)
        with jax.named_scope("regularization"):
            reg = sum(
                layer.regularization(params[layer.name])
                for layer in self.layers
            )
        # Activity-dependent auxiliary losses (e.g. MoE load balancing)
        # reported through layer state — added INSIDE the differentiated
        # closure so they contribute gradients.
        aux = sum(
            st["aux_loss"] for st in new_states.values()
            if isinstance(st, dict) and "aux_loss" in st
        )
        return score + reg + aux, new_states

    # ------------------------------------------------------ train step
    def make_step_fn(self, tbptt: bool = False):
        """The pure (un-jitted) train-step function — also consumed by the
        parallel trainers, which re-jit it with mesh shardings (DP/TP),
        the way the reference's ParallelWrapper wraps the same model fit."""
        return make_train_step(
            functools.partial(self._loss, train=True), self._layer_updaters,
            grad_norm=(self.conf.gradient_normalization,
                       self.conf.gradient_normalization_threshold),
            stateful=self._stateful,
            carry_names=self._rnn_layer_names if tbptt else None)

    def _get_train_step(self, key):
        """The jitted step; `key` is (has_fmask, has_lmask, tbptt)."""
        if key in self._jit_cache:
            return self._jit_cache[key]
        return build_step(
            functools.partial(self.make_step_fn, tbptt=key[2]),
            cache=self._jit_cache, key=key, name="MultiLayerNetwork._step")

    @property
    def _rnn_layer_names(self):
        """Layers that carry RNN state (tBPTT / rnnTimeStep persistence)."""
        if not hasattr(self, "_rnn_names_cache"):
            self._rnn_names_cache = [
                l.name for l in self.layers if _is_recurrent(l)]
        return self._rnn_names_cache

    @property
    def _decode_layer_names(self):
        """Layers with KV-cache decode carries (attention stepping)."""
        if not hasattr(self, "_decode_names_cache"):
            self._decode_names_cache = [
                l.name for l in self.layers if hasattr(l, "decode_carry")]
        return self._decode_names_cache

    # ---------------------------------------------------------- fit API
    def fit(self, data, labels=None, *, epochs: int = 1, batch_size: int = 32,
            steps_per_dispatch: int = 1, device_prefetch: bool = True,
            sync_every: int = 0, checkpointer=None, checkpoint_every: int = 1,
            resume=None, stop_fn=None, preemption=None):
        """Train. Accepts arrays, a DataSet, a DataSetIterator, or any
        iterable of DataSets. Reference: `fit(DataSetIterator):1046`
        (+ tBPTT dispatch `:1102`), pipelined per the async-dispatch
        contract (PERF_NOTES):

        - the loss stays on device; ``score_`` materializes it lazily
          (``sync_every=N`` forces a float every N steps for listeners)
        - ``device_prefetch`` double-buffers the host→device transfer of
          batch N+1 behind batch N's compute; features travel in the
          iterator's own dtype and are cast to the net's on the device
        - ``steps_per_dispatch=K`` (opt-in) fuses K same-shape batches
          into one `lax.scan` dispatch; tBPTT batches and non-SGD solvers
          fall back to per-step dispatch automatically

        Recovery knobs (see `optim/recovery.RecoveryPlan`): pass a
        ``checkpointer`` (`ShardedCheckpointer`) for continuous async
        checkpoints every ``checkpoint_every`` iterations; ``resume``
        (`"auto"` or a position dict) for exact mid-epoch resume;
        ``stop_fn`` / ``preemption=True`` to stop cleanly at a batch
        boundary with a final exact-position snapshot. None of these add
        a per-step host sync.
        """
        self._check_init()
        plan = build_plan(self, checkpointer=checkpointer,
                          checkpoint_every=checkpoint_every, resume=resume,
                          stop_fn=stop_fn, preemption=preemption)
        it = as_iterator(data, labels, batch_size)
        if device_prefetch:
            it = DevicePrefetchIterator(
                it, depth=max(2, int(steps_per_dispatch)))
        self._loss_tracker.sync_every = int(sync_every)
        execu = TrainingExecutor(
            self,
            step=self._dispatch_batch,
            fused_step=self._fused_dispatch,
            can_fuse=self._can_fuse,
            steps_per_dispatch=steps_per_dispatch,
            before_batch=plan.before_batch if plan else None,
            after_dispatch=plan.after_dispatch if plan else None,
            epoch_start=plan.epoch_start if plan else None,
            epoch_end=plan.epoch_end if plan else None,
        )
        run_with_recovery(execu, plan, it, epochs)
        self.stopped_early = execu.stopped
        return self

    def _dispatch_batch(self, ds: DataSet):
        if self.conf.tbptt_fwd_length > 0 and ds.features.ndim == 3:
            return self._fit_tbptt(ds)
        return self._fit_batch(ds)

    def _can_fuse(self, ds: DataSet) -> bool:
        """Fused dispatch needs the plain SGD step: tBPTT chunks and
        full-batch solvers require per-step host control flow."""
        return (self.conf.optimization_algo == "stochastic_gradient_descent"
                and not (self.conf.tbptt_fwd_length > 0
                         and ds.features.ndim == 3))

    def _get_fused_step(self, key, k: int):
        cache_key = ("fused", key, k)
        if cache_key in self._jit_cache:
            return self._jit_cache[cache_key]
        return build_step(self.make_step_fn, fused=True,
                          cache=self._jit_cache, key=cache_key,
                          name="MultiLayerNetwork._fused_step")

    def _features(self, x, asarray=jnp.asarray):
        """Features as the forward pass takes them (`as_features`): ids
        into a net that starts with an embedding keep their dtype."""
        return as_features(x, self.dtype, asarray,
                           ids=self.layers[0].TAKES_IDS)

    def _batch_args(self, ds: DataSet, host: bool = False):
        """A DataSet as the step's batch arguments `(features, labels,
        fmask, lmask)`, real features in the net's dtype (cast on the device
        when they are there already), integer ids as they came.
        `host=True` keeps leaves as numpy: the caller places them in one
        upload."""
        asarray = np.asarray if host else jnp.asarray
        opt = lambda a: None if a is None else asarray(a)
        return (self._features(ds.features, asarray), opt(ds.labels),
                opt(ds.features_mask), opt(ds.labels_mask))

    def _stacked_batch_args(self, batches: List[DataSet]):
        """K same-shape batches as the fused step's arguments, stacked on
        a leading axis: on the host (numpy) when the batches are there, so
        the caller's placement is each tensor's one transfer."""
        host = isinstance(batches[0].features, np.ndarray)
        return stack_step_args(
            [self._batch_args(b, host=host) for b in batches])

    def _fused_dispatch(self, batches: List[DataSet]):
        """Run K stacked same-shape batches as ONE `lax.scan` dispatch.
        Returns the (K,) per-step losses as a device array."""
        first = batches[0]
        self._check_input(first.features)
        self.last_batch_size = first.num_examples()
        self._last_features = batches[-1].features
        key = (first.features_mask is not None,
               first.labels_mask is not None, False)
        fn = self._get_fused_step(key, len(batches))
        (self.params_tree, self.updater_state, self.state_tree, self._rng,
         losses) = fn(self.params_tree, self.updater_state, self.state_tree,
                      np.int32(self.iteration), self._rng,
                      *_tmap(jnp.asarray, self._stacked_batch_args(batches)))
        return losses

    def _split_rng(self):
        self._rng, k = jax.random.split(self._rng)
        return k

    def _check_init(self):
        if self.params_tree is None:
            raise RuntimeError(
                "Network not initialized — call net.init() before "
                "fit()/output()/score() (reference: MultiLayerNetwork.init())"
            )

    def _check_input(self, x):
        it = self.conf.input_type
        if it is None:
            return
        expect = it.shape(int(x.shape[0]))
        if it.kind == "rnn" and it.size == 1 and x.ndim == 2:
            # token ids as [B, T] under InputType.recurrent(1, T)
            ok = it.timesteps in (None, x.shape[1])
        elif it.kind == "rnn" and it.timesteps is None:
            ok = x.ndim == 3 and x.shape[-1] == it.size
        else:
            ok = tuple(x.shape) == tuple(expect)
        if not ok:
            raise ValueError(
                f"Input shape {tuple(x.shape)} does not match configured "
                f"{it!r} (expected {tuple(expect)} for batch={x.shape[0]})"
            )

    def _fit_batch(self, ds: DataSet) -> float:
        self._check_input(ds.features)
        self.last_batch_size = ds.num_examples()
        self._last_features = ds.features   # for listener activation stats
        if self.conf.optimization_algo != "stochastic_gradient_descent":
            # Full-batch solver path (CG / LBFGS / line GD) — reference:
            # Solver.java builds the configured optimizer per fit call.
            from deeplearning4j_tpu.optim.solvers import fit_with_solver

            return fit_with_solver(self, *self._batch_args(ds))
        key = (ds.features_mask is not None, ds.labels_mask is not None, False)
        fn = self._get_train_step(key)
        (self.params_tree, self.updater_state, self.state_tree, loss, *_
         ) = fn(self.params_tree, self.updater_state, self.state_tree,
                jnp.asarray(self.iteration, jnp.int32),
                *self._batch_args(ds), self._split_rng(), None)
        # Deferred sync: the loss stays on device — LossTracker/score_
        # materializes it only on demand (async-dispatch contract).
        return loss

    def _fit_tbptt(self, ds: DataSet) -> float:
        """Truncated BPTT: slice time into fwd-length chunks, carry RNN
        state across chunks with stop_gradient. When tbptt_back_length <
        tbptt_fwd_length, the first (fwd - back) steps of each chunk only
        advance the carries (no gradient, no update) and the train step
        covers the last `back` steps — gradients flow at most back_length
        steps, the reference's fwd != back truncation
        (`MultiLayerNetwork.java:1102-1104,1351`)."""
        L = self.conf.tbptt_fwd_length
        Lb = min(self.conf.tbptt_back_length or L, L)
        T = ds.features.shape[1]
        if ds.labels is None or ds.labels.ndim != 3:
            raise ValueError(
                "Truncated BPTT requires per-timestep (3-D [batch, time, "
                "n_out]) labels, as the reference's doTruncatedBPTT does; for "
                "sequence-level labels use tbptt_fwd_length=0"
            )
        key = (ds.features_mask is not None, ds.labels_mask is not None, True)
        fn = self._get_train_step(key)
        carries = {}
        losses = []
        for lo in range(0, T, L):
            hi = min(lo + L, T)
            t_lo = lo
            if Lb < hi - lo:
                t_lo = hi - Lb
                carries = self._advance_carries(
                    self._features(ds.features[:, lo:t_lo]),
                    None if ds.features_mask is None
                    else jnp.asarray(ds.features_mask[:, lo:t_lo]),
                    carries)
            sl = lambda a: None if a is None else a[:, t_lo:hi]
            chunk = DataSet(sl(ds.features), sl(ds.labels),
                            sl(ds.features_mask), sl(ds.labels_mask))
            (self.params_tree, self.updater_state, self.state_tree, loss,
             carries) = fn(
                self.params_tree, self.updater_state, self.state_tree,
                jnp.asarray(self.iteration, jnp.int32),
                *self._batch_args(chunk), self._split_rng(),
                carries if carries else None)
            losses.append(loss)
        self.last_batch_size = ds.num_examples()
        # Mean on device — one divide instead of len(losses) host syncs.
        return jnp.stack(losses).mean()

    def _advance_carries(self, feats, fmask, carries):
        """Gradient-free forward that only moves the RNN carries along —
        the no-update prefix of a fwd>back tBPTT chunk."""
        key = ("advance", fmask is not None, bool(carries))
        if key not in self._jit_cache:
            rnn_names = self._rnn_layer_names

            def adv(params, states, x, fm, car):
                _, _, new_states, _ = self._forward(
                    params, states, x, train=False, rng=None, fmask=fm,
                    carries=car)
                return {n: new_states[n] for n in rnn_names}

            self._jit_cache[key] = jax.jit(adv)
        return self._jit_cache[key](
            self.params_tree, self.state_tree, feats, fmask,
            carries if carries else None)

    # -------------------------------------------------------- inference
    def output(self, x, train: bool = False):
        """Forward to final activations. Reference: `output:1716-1827`."""
        self._check_init()
        self._check_input(np.asarray(x) if not hasattr(x, "shape") else x)
        key = ("output", train)
        if key not in self._jit_cache:
            def out_fn(params, states, feats):
                y, _, _, _ = self._forward(
                    params, states, feats, train=train, rng=None)
                return y
            self._jit_cache[key] = jax.jit(out_fn)
        return self._jit_cache[key](
            self.params_tree, self.state_tree, self._features(x))

    def feed_forward(self, x, train: bool = False) -> List[jax.Array]:
        """All per-layer activations. Reference: `feedForward:752`."""
        _, _, _, acts = self._forward(
            self.params_tree, self.state_tree, self._features(x),
            train=train, rng=None, collect=True)
        return acts

    def score(self, data, labels=None) -> float:
        """Mean loss on data, as ONE jitted computation (an eager _loss
        call here would retrace per invocation). Reference:
        `score(DataSet)`."""
        ds = data if isinstance(data, DataSet) else DataSet(
            np.asarray(data), np.asarray(labels))
        key = ("score", ds.features_mask is not None,
               ds.labels_mask is not None)
        if key not in self._jit_cache:
            def score_fn(params, states, feats, labs, fm, lm):
                loss, _ = self._loss(params, states, feats, labs, fm, lm,
                                     None, train=False)
                return loss
            self._jit_cache[key] = jax.jit(score_fn)
        loss = self._jit_cache[key](
            self.params_tree, self.state_tree,
            self._features(ds.features),
            None if ds.labels is None else jnp.asarray(ds.labels),
            None if ds.features_mask is None else jnp.asarray(ds.features_mask),
            None if ds.labels_mask is None else jnp.asarray(ds.labels_mask))
        return float(loss)

    def predict(self, x) -> np.ndarray:
        """Argmax class predictions. Reference: `predict(INDArray)`."""
        return np.asarray(jnp.argmax(self.output(x), axis=-1))

    def evaluate(self, iterator: DataSetIterator):
        """Reference: `MultiLayerNetwork.evaluate(DataSetIterator)`.

        For plain per-example classification the argmax happens ON DEVICE
        and only int32 class indices cross to host (the full softmax
        round-trip only happens for masked/time-series labels, which the
        Evaluation flattens host-side)."""
        from deeplearning4j_tpu.eval.evaluation import Evaluation

        self._check_init()
        key = ("eval_argmax",)
        if key not in self._jit_cache:
            def pred_fn(params, states, feats):
                y, _, _, _ = self._forward(params, states, feats,
                                           train=False, rng=None)
                return jnp.argmax(y, axis=-1).astype(jnp.int32)
            self._jit_cache[key] = jax.jit(pred_fn)

        def predict_indices(feats):
            self._check_input(np.asarray(feats))
            idx = self._jit_cache[key](
                self.params_tree, self.state_tree,
                self._features(feats))
            return idx, getattr(self.layers[-1], "n_out", None)

        return Evaluation().evaluate_iterator(
            iterator, output_fn=self.output,
            predict_indices_fn=predict_indices)

    def evaluate_regression(self, iterator: DataSetIterator):
        """Reference: `MultiLayerNetwork.evaluateRegression:2668`."""
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        from deeplearning4j_tpu.eval.regression import RegressionEvaluation

        self._check_init()
        return Evaluation.run_evaluation(
            RegressionEvaluation(), iterator, self.output)

    def evaluate_roc(self, iterator: DataSetIterator,
                     threshold_steps: int = 0):
        """Binary ROC. Reference: `evaluateROC:2679`."""
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        from deeplearning4j_tpu.eval.roc import ROC

        self._check_init()
        return Evaluation.run_evaluation(
            ROC(threshold_steps), iterator, self.output)

    def evaluate_roc_multi_class(self, iterator: DataSetIterator):
        """One-vs-all ROC per class. Reference:
        `evaluateROCMultiClass:2690`."""
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        from deeplearning4j_tpu.eval.roc import ROCMultiClass

        self._check_init()
        return Evaluation.run_evaluation(
            ROCMultiClass(), iterator, self.output)

    # ----------------------------------------------------- rnn stepping
    @property
    def _rnn_carries(self):
        """Read view of the ambient stepping carries (the mutable path
        lives inside `DecodeState`, lock-guarded)."""
        return self._decode_state.carries

    @property
    def _decode_pos(self):
        return self._decode_state.pos

    def _validate_causal_decode(self, layers, what="rnn_time_step"):
        """Validate ALL before seeding ANY carries: a mid-loop raise
        would leave partial carries behind and disarm the guard."""
        for l in layers:
            if not getattr(l, "causal", True):
                raise ValueError(
                    f"{what} requires causal attention; layer "
                    f"{l.name!r} is non-causal (stepped decoding cannot "
                    f"see future tokens, so it cannot reproduce a "
                    f"bidirectional forward)")

    def rnn_time_step(self, x):
        """Stateful single-step inference; carries persist across calls.
        Reference: `rnnTimeStep` + `rnnClearPreviousState`. Attention
        stacks step the same way: layers exposing `decode_carry` (KV
        cache, position offset) are seeded on the first call, so a
        transformer generates token-by-token without re-running the
        prefix. The whole read-step-write runs under the decode-state
        lock, so concurrent callers serialize instead of corrupting each
        other's carries (serving threads its carries through
        `session_step` arguments instead and never touches this state)."""
        x = self._features(x)
        if x.ndim == 2:
            x = x[:, None, :]
        stateful = set(self._rnn_layer_names) | set(self._decode_layer_names)
        st = self._decode_state
        with st.lock():
            if not st.carries and self._decode_layer_names:
                decode = [l for l in self.layers
                          if hasattr(l, "decode_carry")]
                self._validate_causal_decode(decode)
                st.seed({l.name: l.decode_carry(x.shape[0], self.dtype)
                         for l in decode})
            if self._decode_layer_names:
                _check_decode_budget(
                    self,
                    (l for l in self.layers if hasattr(l, "decode_carry")),
                    x.shape[1])
            carries = st.carries or None
            # One jitted program per (step shape, carry presence): token-
            # by-token decoding is a fixed-shape loop, so eager per-op
            # dispatch (a device round-trip per op per token) would
            # dominate on TPU.
            key = ("rnn_step", x.shape, carries is not None)
            if key not in self._jit_cache:
                def step_fn(params, states, feats, carries_):
                    out, _, new_states, _ = self._forward(
                        params, states, feats, train=False, rng=None,
                        carries=carries_)
                    return out, {n: new_states[n] for n in stateful}

                self._jit_cache[key] = jax.jit(step_fn)
            out, new_carries = self._jit_cache[key](
                self.params_tree, self.state_tree, x, carries)
            # advance only after a successful step (a raise above or a
            # trace failure must not burn decode budget)
            st.update(new_carries,
                      advance=x.shape[1] if self._decode_layer_names else 0)
        return out

    def rnn_clear_previous_state(self):
        self._decode_state.clear()

    def rnn_reorder_state(self, idx) -> None:
        """Reorder (or expand) the stateful-decoding carries along the
        batch dimension — beam-search reselection gathers each beam's KV
        cache/h/c rows to follow its chosen parent. Every non-scalar
        carry leaf is batch-leading by the decode-carry contract
        (`decode_carry`/`initial_carry`); scalar leaves (decode
        positions) are shared across the batch and pass through."""
        ix = jnp.asarray(np.asarray(idx))
        self._decode_state.reorder(lambda carries: jax.tree_util.tree_map(
            lambda a: a[ix] if getattr(a, "ndim", 0) >= 1 else a, carries))

    # ------------------------------------------- slot-indexed sessions
    def decode_limit(self) -> Optional[int]:
        """Smallest non-rolling cache/position bound across decode
        layers (None = unbounded, e.g. a pure rolling-cache stack) — the
        serving session manager's host-side budget ceiling."""
        return _decode_limit(
            l for l in self.layers if hasattr(l, "decode_carry"))

    def session_carries(self, slots: int, kv_dtype: Optional[str] = None,
                        page_len: Optional[int] = None,
                        pages: Optional[int] = None):
        """Batched slot-indexed decode carries for `slots` independent
        sessions: attention layers get PER-SLOT position vectors
        (`decode_carry(per_slot=True)`), recurrent layers their h/c
        carries (mask-gated per step, so padded chunks hold them on pad
        tokens). This is the KVSlotPool's backing tree — pure data, no
        model-global state.

        `kv_dtype` ("native"/None, "int8", "fp8") selects the attention
        caches' storage dtype — quantized carries gain per-(token,
        kv-head) scale rows next to each cache (see
        `MultiHeadAttention.decode_carry`).

        `page_len` switches every attention cache to the PAGED layout
        (fixed [pages, page_len, Hkv, Dh] block pools + per-slot page
        tables — the prefix-cache storage; see `decode_carry`). One
        logical page id must mean the same physical row in EVERY layer's
        pool, so paged mode requires a uniform `max_cache` across decode
        layers (`prefix_cache_capable` checks the same). `pages`
        defaults to `slots * max_cache / page_len` per layer — the
        monolithic layout's exact memory."""
        self._check_init()
        decode = [l for l in self.layers if hasattr(l, "decode_carry")]
        rnn = [l for l in self.layers if _is_recurrent(l)]
        if not decode and not rnn:
            raise ValueError(
                "session_carries needs at least one stateful decode "
                "layer (attention decode_carry or recurrent carry)")
        for l in rnn:
            if isinstance(l, (Bidirectional, GravesBidirectionalLSTM,
                              LastTimeStep)):
                raise ValueError(
                    f"session decoding is causal left-to-right; layer "
                    f"{l.name!r} ({type(l).__name__}) cannot stream")
        self._validate_causal_decode(decode, what="session decoding")
        if page_len is not None:
            caches = {l.max_cache for l in decode
                      if hasattr(l, "max_cache")}
            if len(caches) > 1:
                raise ValueError(
                    f"paged session carries need a uniform max_cache "
                    f"across decode layers (one logical page id = one "
                    f"physical row in every layer's pool); got {sorted(caches)}")
            if pages is None and caches:
                pages = slots * (next(iter(caches)) // page_len)
        carries = {l.name: l.decode_carry(slots, self.dtype, per_slot=True,
                                          kv_dtype=kv_dtype,
                                          page_len=page_len, pages=pages)
                   if page_len is not None else
                   l.decode_carry(slots, self.dtype, per_slot=True,
                                  kv_dtype=kv_dtype)
                   for l in decode}
        for l in rnn:
            carries[l.name] = l.initial_carry(slots, self.dtype)
        return carries

    def spec_decode_capable(self) -> bool:
        """Can this net serve as a speculative-decode draft or target?
        The windows below un-write rejected tokens by REWINDING the
        per-slot positions — stale cache entries past `pos` are invisible
        (`k_ids <= pos`) and get overwritten by the next window. That
        trick needs every stateful carry to be position-addressed:
        recurrent h/c carries hold irreversible state, and rolling rings
        misattribute stale slots through their held-index arithmetic, so
        either disqualifies the net."""
        if self._rnn_layer_names:
            return False
        decode = [l for l in self.layers if hasattr(l, "decode_carry")]
        if not decode:
            return False
        return not any(getattr(l, "rolling_cache", False) for l in decode)

    def prefix_cache_capable(self) -> bool:
        """Can this net's session carries run PAGED (the prefix-cache
        storage)? Pages are position-addressed blocks, so the same
        rewind argument as `spec_decode_capable` applies (no recurrent
        carries, no rolling rings — both hold state a shared page cannot
        represent), plus one structural condition: every decode layer's
        `max_cache` must agree, because one logical page id must mean
        the same physical row in every layer's block pool."""
        if not self.spec_decode_capable():
            return False
        caches = {l.max_cache for l in self.layers
                  if hasattr(l, "decode_carry") and hasattr(l, "max_cache")}
        return len(caches) == 1

    _PAGE_POOL_KEYS = ("cache_k", "cache_v", "scale_k", "scale_v")

    @classmethod
    def _lane_merge(cls, old_tree, new_tree, act):
        """Revert inactive lanes' carry writes: slot-indexed leaves get
        a per-lane `where`. PAGED cache leaves (physical page pools —
        leading dim is pages, shared across slots) pass through
        untouched instead: a slot mask cannot address a page pool, and
        it does not need to — every paged write path is valid-masked at
        the scatter (invalid/inactive targets push out of range and
        `mode="drop"` discards them), so an inactive lane never dirtied
        a page in the first place."""
        paged = any(
            getattr(p[-1], "key", None) == "page_table"
            for p, _ in jax.tree_util.tree_leaves_with_path(new_tree))

        def lane(path, old, nw):
            if paged and getattr(path[-1], "key", None) \
                    in cls._PAGE_POOL_KEYS:
                return nw
            a = act.reshape(
                (-1,) + (1,) * (getattr(nw, "ndim", 1) - 1))
            return jnp.where(a, nw, old)

        return jax.tree_util.tree_map_with_path(lane, old_tree, new_tree)

    def session_step(self, x, carries, *, active=None, valid=None):
        """One slot-indexed decode step: carries and per-slot positions
        are ARGUMENTS threaded through the jitted program, not model
        state — any mix of sessions can ride one dispatch.

        `x` is [S, T, F] (S = slot count; T = the chunk bucket), `valid`
        an optional [S, T] prefix mask (1.0 = real token) letting short
        chunks and idle lanes share the padded bucket shape, `active` an
        optional [S] bool vector — inactive lanes' carries pass through
        unchanged (their lanes compute, their writes are masked, their
        outputs are garbage to be ignored). Returns (out, new_carries).

        One compiled program per (x.shape, active?, valid?) — the
        fixed-shape decode contract the recompile watchdog polices."""
        self._check_init()
        x = self._features(x)
        if x.ndim == 2:
            x = x[:, None, :]
        stateful = set(self._rnn_layer_names) | set(self._decode_layer_names)
        key = ("session_step", x.shape,
               active is not None, valid is not None)
        if key not in self._jit_cache:
            def step_fn(params, states, feats, carries_, active_, valid_):
                out, _, new_states, _ = self._forward(
                    params, states, feats, train=False, rng=None,
                    fmask=valid_, carries=carries_)
                new = {n: new_states[n] for n in stateful}
                if active_ is not None:
                    new = self._lane_merge(carries_, new, active_)
                return out, new

            self._jit_cache[key] = jax.jit(step_fn)
        return self._jit_cache[key](
            self.params_tree, self.state_tree, x, carries,
            None if active is None else jnp.asarray(active, bool),
            None if valid is None else jnp.asarray(valid, self.dtype))

    def session_decode_window(self, tokens, carries, *, active, k,
                              temperature, top_k, top_p, greedy,
                              keys, offsets, budgets, eos_ids):
        """K fused decode steps in ONE dispatch: a `lax.scan` that
        forwards each active lane's next token, samples on-device
        (utils/sampling.sample_token_lanes — greedy/temperature/top-k/
        top-p as lax ops), feeds the sample back in, and early-exits
        per lane on EOS or budget via the active mask — finished lanes
        stop writing carries without breaking the fixed shape. This is
        the decode twin of the training executor's fused-K machinery:
        one host round-trip buys K tokens.

        Arguments (S = slot count; everything per-lane so one compiled
        program serves any request mix — the zero-recompile contract):

        - ``tokens``   i32[S]    first input token per lane (the last
          prompt token on the first window, the previous window's last
          sample afterwards)
        - ``carries``  the KVSlotPool tree from :meth:`session_carries`
        - ``active``   bool[S]   lanes that decode this window
        - ``k``        python int, the window length (bucketed by the
          caller; part of the compile key)
        - ``temperature/top_k/top_p/greedy``  f32/i32/f32/bool [S]
        - ``keys``     u32[S, 2] per-lane base rng keys; token i of a
          lane always draws with fold_in(key, offsets+i), so streams
          are invariant to K and to how sessions share dispatches
        - ``offsets``  i32[S]    tokens already generated per lane
        - ``budgets``  i32[S]    remaining token budget per lane
        - ``eos_ids``  i32[S]    per-lane EOS id (-1 = none)

        Returns ``(tokens [S, k] i32, emitted [S, k] bool,
        new_carries)`` — positions where ``emitted`` is False carry -1
        and must be ignored (lane finished mid-window or was inactive).
        Greedy output is bit-exact against running the same program
        with k=1 K times: same per-step forwards, same carry merges —
        the parity contract tests/test_fused_decode.py pins."""
        from deeplearning4j_tpu.nn.layers.feedforward import (
            EmbeddingSequenceLayer,
        )
        from deeplearning4j_tpu.utils import sampling as _sampling

        self._check_init()
        k = int(k)
        if k < 1:
            raise ValueError(f"window length k must be >= 1, got {k}")
        tokens = jnp.asarray(tokens, jnp.int32)
        ids_input = isinstance(self.layers[0], EmbeddingSequenceLayer)
        feat = 1 if ids_input else int(self.layers[0].n_in)
        stateful = set(self._rnn_layer_names) | set(self._decode_layer_names)
        key = ("session_decode_window", k, tokens.shape, ids_input)
        if key not in self._jit_cache:
            def window_fn(params, states, tok0, carries_, active_, temps,
                          tks, tps, grdy, keys_, offs, buds, eos):
                dt = self.dtype

                def encode(tok):
                    if ids_input:
                        return tok[:, None, None].astype(dt)
                    return jax.nn.one_hot(tok, feat, dtype=dt)[:, None, :]

                def body(carry, _):
                    tok, c, act, n = carry
                    val = act.astype(dt)[:, None]
                    out, _, new_states, _ = self._forward(
                        params, states, encode(tok), train=False, rng=None,
                        fmask=val, carries=c)
                    new = {nm: new_states[nm] for nm in stateful}
                    new = self._lane_merge(c, new, act)
                    step_keys = jax.vmap(jax.random.fold_in)(keys_, offs + n)
                    nxt = _sampling.sample_token_lanes(
                        out[:, -1, :], temps, tks, tps, grdy, step_keys)
                    emit = act
                    n2 = n + emit.astype(jnp.int32)
                    finished = emit & ((nxt == eos) | (n2 >= buds))
                    return ((jnp.where(emit, nxt, tok), new,
                             act & jnp.logical_not(finished), n2),
                            (jnp.where(emit, nxt, -1), emit))

                init = (tok0, carries_, active_, jnp.zeros_like(offs))
                (_, cf, _, _), (toks, emits) = jax.lax.scan(
                    body, init, None, length=k)
                return (jnp.transpose(toks), jnp.transpose(emits), cf)

            self._jit_cache[key] = jax.jit(window_fn)
        return self._jit_cache[key](
            self.params_tree, self.state_tree, tokens, carries,
            jnp.asarray(active, bool), jnp.asarray(temperature, jnp.float32),
            jnp.asarray(top_k, jnp.int32), jnp.asarray(top_p, jnp.float32),
            jnp.asarray(greedy, bool), jnp.asarray(keys, jnp.uint32),
            jnp.asarray(offsets, jnp.int32), jnp.asarray(budgets, jnp.int32),
            jnp.asarray(eos_ids, jnp.int32))

    # ------------------------------------------- speculative decoding
    #
    # The draft/target window pair below shares one invariant: every
    # stateful carry is POSITION-ADDRESSED (linear caches + per-slot
    # positions — `spec_decode_capable` gates the rest out), so a
    # rejected token is un-written by rewinding `pos`: stale entries
    # past `pos` are invisible (`k_ids <= pos`) and the next window's
    # scatter overwrites them. Bookkeeping per window, for a lane that
    # accepted n_acc of k draft tokens and emitted n = n_acc + 1:
    #   target: verify writes k+1 entries, pos snaps back to old + n
    #   draft:  propose wrote k entries ([t0, d_1..d_{k-1}]); the next
    #           window enters with rewind = max(k - n, 0); on full
    #           acceptance (n = k+1) the draft lacks d_k's KV, so the
    #           next propose catch-up-writes it (pre_tokens/pre_valid)

    # rng stream salts: acceptance uniforms and residual/bonus draws
    # come from streams independent of both models' sampling draws
    # (fold_in(fold_in(base_key, SALT), position)) — the rejection
    # rule's correctness assumes the acceptance coin is independent of
    # the proposal.
    _SPEC_U_SALT = 0x5EC0DE
    _SPEC_R_SALT = 0xDEC0DE5

    @staticmethod
    def _pos_rewind(carries, delta):
        """Subtract `delta` [S] from every per-slot `pos` leaf (the
        decode-carry trees are nested dicts whose position leaves are
        always keyed "pos")."""
        def walk(node):
            if isinstance(node, dict):
                out = {}
                for kk, vv in node.items():
                    if kk == "pos":
                        out[kk] = vv - delta.astype(vv.dtype)
                    else:
                        out[kk] = walk(vv)
                return out
            return node
        return walk(carries)

    def session_propose_window(self, tokens, carries, *, active, k,
                               temperature, top_k, top_p, greedy, keys,
                               offsets, rewind, pre_tokens, pre_valid):
        """The DRAFT half of a speculative window: k sequential decode
        steps in one dispatch, sampling each proposal on-device and
        recording the warped distribution it was drawn from (the q the
        rejection rule needs). Entry bookkeeping per lane: `rewind` [S]
        is subtracted from the draft positions (un-writing proposals the
        target rejected last window) and, where `pre_valid`, one masked
        catch-up step writes `pre_tokens`' KV first (the fully-accepted
        d_k whose cache entry the draft never wrote). Proposal draws use
        the SAME stream as the non-speculative sampler
        (fold_in(base_key, offsets + i)); no EOS/budget early-exit — the
        target's verify applies the cuts.

        Returns ``(draft_tokens [S, k] i32, draft_probs [S, k, V] f32,
        new_carries)``."""
        from deeplearning4j_tpu.nn.layers.feedforward import (
            EmbeddingSequenceLayer,
        )
        from deeplearning4j_tpu.utils import sampling as _sampling

        self._check_init()
        k = int(k)
        if k < 1:
            raise ValueError(f"draft window k must be >= 1, got {k}")
        tokens = jnp.asarray(tokens, jnp.int32)
        ids_input = isinstance(self.layers[0], EmbeddingSequenceLayer)
        feat = 1 if ids_input else int(self.layers[0].n_in)
        stateful = set(self._rnn_layer_names) | set(self._decode_layer_names)
        key = ("session_propose_window", k, tokens.shape, ids_input)
        if key not in self._jit_cache:
            def propose_fn(params, states, tok0, carries_, active_, temps,
                           tks, tps, grdy, keys_, offs, rew, ptok, pval):
                dt = self.dtype

                def encode(tok):
                    if ids_input:
                        return tok[:, None, None].astype(dt)
                    return jax.nn.one_hot(tok, feat, dtype=dt)[:, None, :]

                def lane_merge(mask, old_tree, new_tree):
                    return self._lane_merge(old_tree, new_tree, mask)

                carries_ = self._pos_rewind(
                    carries_, jnp.where(active_, rew, 0))
                cu = active_ & pval
                _, _, cu_states, _ = self._forward(
                    params, states, encode(ptok), train=False, rng=None,
                    fmask=cu.astype(dt)[:, None], carries=carries_)
                carries_ = lane_merge(
                    cu, carries_, {nm: cu_states[nm] for nm in stateful})

                def body(carry, i):
                    tok, c = carry
                    out, _, new_states, _ = self._forward(
                        params, states, encode(tok), train=False, rng=None,
                        fmask=active_.astype(dt)[:, None], carries=c)
                    new = lane_merge(
                        active_, c, {nm: new_states[nm] for nm in stateful})
                    p = out[:, -1, :].astype(jnp.float32)
                    pw = _sampling.warp_probs_lanes(p, temps, tks, tps)
                    step_keys = jax.vmap(jax.random.fold_in)(keys_, offs + i)
                    logp = jnp.where(pw > 0.0, jnp.log(pw), -jnp.inf)
                    drawn = jax.vmap(jax.random.categorical)(
                        step_keys, logp).astype(jnp.int32)
                    g_tok = jnp.argmax(p, axis=-1).astype(jnp.int32)
                    nxt = jnp.where(grdy, g_tok, drawn)
                    return ((jnp.where(active_, nxt, tok), new), (nxt, pw))

                (_, cf), (toks, pws) = jax.lax.scan(
                    body, (tok0, carries_), jnp.arange(k))
                return (jnp.transpose(toks), jnp.moveaxis(pws, 0, 1), cf)

            self._jit_cache[key] = jax.jit(propose_fn)
        return self._jit_cache[key](
            self.params_tree, self.state_tree, tokens, carries,
            jnp.asarray(active, bool), jnp.asarray(temperature, jnp.float32),
            jnp.asarray(top_k, jnp.int32), jnp.asarray(top_p, jnp.float32),
            jnp.asarray(greedy, bool), jnp.asarray(keys, jnp.uint32),
            jnp.asarray(offsets, jnp.int32), jnp.asarray(rewind, jnp.int32),
            jnp.asarray(pre_tokens, jnp.int32),
            jnp.asarray(pre_valid, bool))

    def session_verify_window(self, tokens, carries, *, active, k,
                              draft_tokens, draft_probs, temperature,
                              top_k, top_p, greedy, keys, offsets,
                              budgets, eos_ids):
        """The TARGET half of a speculative window: ONE chunked forward
        over [t0, d_1..d_k] scores every draft position, accept/reject
        runs on device (utils/sampling.spec_accept_lanes — greedy
        longest-prefix fast path, standard rejection rule otherwise),
        EOS/budget prefix cuts apply, and the target positions snap back
        to old + n_emit so rejected entries are rewound. An alive lane
        always emits n_acc + 1 tokens (its accepted prefix plus the
        correction/bonus token), so the chain advances every window.

        Returns ``(packed [S, k+4] i32, new_carries)`` where packed rows
        are ``[n_emit, n_acc, last_draft, tok_0..tok_k]`` (-1 past
        n_emit) — one device array so the manager's single post-lock
        readback covers counts, catch-up token, and emissions together.
        `n_acc` (the acceptance verdict BEFORE the EOS/budget cuts) rides
        along so the manager can count exactly the accepted drafts that
        were actually emitted — ``min(n_acc, n_emit)`` — instead of
        inferring them from n_emit alone, which mis-counts when a fully
        verified window is truncated by the token budget."""
        from deeplearning4j_tpu.nn.layers.feedforward import (
            EmbeddingSequenceLayer,
        )
        from deeplearning4j_tpu.utils import sampling as _sampling

        self._check_init()
        k = int(k)
        if k < 1:
            raise ValueError(f"verify window k must be >= 1, got {k}")
        tokens = jnp.asarray(tokens, jnp.int32)
        draft_tokens = jnp.asarray(draft_tokens, jnp.int32)
        ids_input = isinstance(self.layers[0], EmbeddingSequenceLayer)
        feat = 1 if ids_input else int(self.layers[0].n_in)
        stateful = set(self._rnn_layer_names) | set(self._decode_layer_names)
        key = ("session_verify_window", k, tokens.shape, ids_input)
        if key not in self._jit_cache:
            def verify_fn(params, states, tok0, carries_, active_, d_toks,
                          q_pw, temps, tks, tps, grdy, keys_, offs, buds,
                          eos):
                dt = self.dtype
                chunk = jnp.concatenate([tok0[:, None], d_toks], axis=1)
                if ids_input:
                    x = chunk[:, :, None].astype(dt)
                else:
                    x = jax.nn.one_hot(chunk, feat, dtype=dt)
                val = active_.astype(dt)[:, None] * jnp.ones((1, k + 1), dt)
                out, _, new_states, _ = self._forward(
                    params, states, x, train=False, rng=None, fmask=val,
                    carries=carries_)
                p_raw = out.astype(jnp.float32)            # [S, k+1, V]
                pw = jax.vmap(
                    lambda pp: _sampling.warp_probs_lanes(
                        pp, temps, tks, tps),
                    in_axes=1, out_axes=1)(p_raw)

                def lane_u(key_, off):
                    sk = jax.random.fold_in(key_, self._SPEC_U_SALT)
                    return jax.vmap(
                        lambda i: jax.random.uniform(
                            jax.random.fold_in(sk, off + i)))(jnp.arange(k))

                u = jax.vmap(lane_u)(keys_, offs)          # [S, k]
                extra_keys = jax.vmap(
                    lambda key_, off: jax.random.fold_in(
                        jax.random.fold_in(key_, self._SPEC_R_SALT), off)
                )(keys_, offs)
                n_acc, extra = _sampling.spec_accept_lanes(
                    p_raw, pw, q_pw, d_toks, grdy, u, extra_keys)

                idx = jnp.arange(k + 1)[None, :]
                d_pad = jnp.concatenate(
                    [d_toks, jnp.zeros_like(d_toks[:, :1])], axis=1)
                cand = jnp.where(idx == n_acc[:, None], extra[:, None],
                                 d_pad)
                base = idx <= n_acc[:, None]
                eos_hit = base & (cand == eos[:, None]) & (eos[:, None] >= 0)
                prior_eos = jnp.cumsum(eos_hit, axis=1) - eos_hit
                emitted = (base & (prior_eos == 0)
                           & (idx < buds[:, None]) & active_[:, None])
                n_emit = emitted.sum(axis=1).astype(jnp.int32)
                toks_out = jnp.where(emitted, cand, -1)

                new = self._lane_merge(
                    carries_, {nm: new_states[nm] for nm in stateful},
                    active_)
                # position snap-back: the forward advanced active lanes
                # by k+1; the confirmed history is old + n_emit
                demit = jnp.where(active_, n_emit, 0)

                def fix(path, old_leaf, new_leaf):
                    # graft: allow(GL003): `path` is static pytree
                    # structure from tree_map_with_path, not a tracer
                    if getattr(path[-1], "key", None) == "pos":
                        return old_leaf + demit.astype(old_leaf.dtype)
                    return new_leaf

                new = jax.tree_util.tree_map_with_path(
                    fix, carries_, new)
                packed = jnp.concatenate(
                    [n_emit[:, None], n_acc[:, None].astype(jnp.int32),
                     d_toks[:, -1:], toks_out], axis=1)
                return packed.astype(jnp.int32), new

            self._jit_cache[key] = jax.jit(verify_fn)
        return self._jit_cache[key](
            self.params_tree, self.state_tree, tokens, carries,
            jnp.asarray(active, bool), draft_tokens,
            jnp.asarray(draft_probs, jnp.float32),
            jnp.asarray(temperature, jnp.float32),
            jnp.asarray(top_k, jnp.int32), jnp.asarray(top_p, jnp.float32),
            jnp.asarray(greedy, bool), jnp.asarray(keys, jnp.uint32),
            jnp.asarray(offsets, jnp.int32), jnp.asarray(budgets, jnp.int32),
            jnp.asarray(eos_ids, jnp.int32))

    # -------------------------------------------------------- pretrain
    def pretrain(self, data, *, epochs: int = 1, batch_size: int = 32):
        """Greedy layerwise unsupervised pretraining for pretrainable layers
        (AutoEncoder/RBM/VAE). Reference: `pretrain:214-301`."""
        it = as_iterator(data, None, batch_size)
        for idx, layer in enumerate(self.layers):
            if not layer.is_pretrainable:
                continue
            updater = self._layer_updaters[layer.name]
            opt = updater.init(self.params_tree[layer.name])

            def featurize(feats):
                x = feats
                for j in range(idx):
                    if j in self.conf.preprocessors:
                        x = self.conf.preprocessors[j].apply(x)
                    x, _ = self.layers[j].apply(
                        self.params_tree[self.layers[j].name], x,
                        state=self.state_tree.get(self.layers[j].name) or None,
                        train=False, rng=None)
                if idx in self.conf.preprocessors:
                    x = self.conf.preprocessors[idx].apply(x)
                return x

            @jax.jit
            # graft: allow(GL103): one program per pretrained layer by
            # design — layerwise pretraining compiles each layer once
            def pre_step(lp, opt_state, step, feats, rng):
                x = featurize(feats)

                def loss_fn(p):
                    return layer.reconstruction_score(p, x, rng=rng)

                loss, grads = jax.value_and_grad(loss_fn)(lp)
                new_lp, new_opt = updater.update_with_params(
                    grads, opt_state, lp, step)
                return new_lp, new_opt, loss

            step = 0
            for _ in range(epochs):
                for ds in it:
                    lp, opt, loss = pre_step(
                        self.params_tree[layer.name], opt,
                        jnp.asarray(step, jnp.int32),
                        self._features(ds.features), self._split_rng())
                    self.params_tree[layer.name] = lp
                    step += 1
        return self

    # ----------------------------------------------------- param views
    def params(self) -> np.ndarray:
        """Single flat parameter vector. Reference: `Model.params()`."""
        flat, _ = flatten_params(self.params_tree)
        return np.asarray(flat)

    def set_params(self, flat) -> None:
        self.params_tree = unflatten_params(
            jnp.asarray(flat), self.params_tree)

    def num_params(self) -> int:
        return param_count(self.params_tree)

    def set_listeners(self, *listeners: TrainingListener) -> None:
        self.listeners = list(listeners)

    def add_listener(self, l: TrainingListener) -> None:
        self.listeners.append(l)

    def clone(self) -> "MultiLayerNetwork":
        """Deep copy (new runtime, copied params). Reference: MLN.clone()."""
        other = MultiLayerNetwork(self.conf)
        other.init()
        if self.params_tree is not None:
            other.params_tree = _tmap(lambda a: a, self.params_tree)
            other.state_tree = jax.tree_util.tree_map(lambda a: a, self.state_tree)
        return other
