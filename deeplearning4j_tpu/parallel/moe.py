"""Mixture-of-Experts with expert parallelism over the `expert` mesh axis.

No reference counterpart: DL4J has no conditional-compute layers (SURVEY
§2.4/§5 — parallelism surface is data-parallel only); this is a green-field
TPU-scale extension required by SURVEY §7 step 7.

TPU-first design (GShard/Switch-style, MXU-friendly):
- Routing is expressed entirely as dense one-hot einsums over a FIXED
  per-expert capacity C — no dynamic shapes, no gather/scatter loops, so XLA
  tiles everything onto the MXU and the dispatch/combine contractions lower
  to all_to_all over ICI when the expert axis of the parameter leaves is
  sharded over the `expert` mesh axis (collectives are inserted by the
  partitioner from sharding constraints — the scaling-book recipe — rather
  than hand-written).
- Load balancing uses the standard auxiliary loss (mean gate fraction ×
  mean routed fraction, scaled by E); the layer reports it through the
  state pytree under "aux_loss" and the model runtimes add it to the score
  inside the differentiated loss closure.

Two layers live here. `MoEFeedForward` is the one above: every expert on
the mesh, a fixed capacity, tokens over it dropped; the `expert` mesh
axis and its all_to_all are its reason to stay. `ExpertFeedForward` is
one device's share of a large sparse model's expert layer: a router over
all `n_experts`, of which this device holds `held`, the (token, expert)
pairs sorted by expert, the rows of the experts held gathered, grouped
matrix products over them (`jax.lax.ragged_dot`; on one TPU device
`ops/grouped_matmul.grouped_dot`, which passes by the tiles that hold no
pair), and the results gathered back by token (on one TPU device both
gathers by `ops/row_gather`'s kernels, which move only the rows that
hold a pair).
No pair is dropped at any imbalance and no [N, E, C] tensor exists; what
the absent experts would add is left out.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.nn.activations import Activation
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer
from deeplearning4j_tpu.ops.attention import name_block_residual
from deeplearning4j_tpu.ops.kernel_defaults import kernels_run as _kernel_runs
from deeplearning4j_tpu.parallel.mesh import AXIS_EXPERT


_ACTIVE_MESH: List[Tuple[Mesh, str]] = []


@contextlib.contextmanager
def expert_mesh(mesh: Mesh, axis: str = AXIS_EXPERT):
    """Make `mesh` visible to MoEFeedForward layers traced inside the block.

    The layer API has no mesh parameter (layers are mesh-agnostic pure
    functions), so the sharding constraints that pin dispatch/combine to
    all_to_all need a side channel. Activate this context around the call
    that TRACES the train/inference step (fit(), make_step_fn() + jit, ...);
    the constraint is baked into the jaxpr at trace time.
    """
    _ACTIVE_MESH.append((mesh, axis))
    try:
        yield
    finally:
        _ACTIVE_MESH.pop()


def _active_expert_mesh() -> Tuple[Optional[Mesh], str]:
    return _ACTIVE_MESH[-1] if _ACTIVE_MESH else (None, AXIS_EXPERT)


def top_k_gating(logits, k: int, capacity: int, token_mask=None):
    """Top-k token→expert routing with fixed expert capacity.

    logits: [N, E]. Returns (combine [N, E, C], dispatch [N, E, C],
    aux_loss scalar). Tokens overflowing an expert's capacity are dropped
    (their combine weights are zero — residual connections carry them).
    token_mask: optional [N] 0/1 — masked (padding) tokens are excluded from
    routing entirely: they occupy no capacity and don't skew the aux loss.
    """
    n, e = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    if token_mask is not None:
        probs = probs * token_mask[:, None].astype(probs.dtype)
    denom = (jnp.maximum(jnp.sum(token_mask.astype(probs.dtype)), 1.0)
             if token_mask is not None else jnp.asarray(float(n), probs.dtype))
    combine = jnp.zeros((n, e, capacity), probs.dtype)
    dispatch = jnp.zeros((n, e, capacity), jnp.bool_)
    masked = probs
    # Occupancy accumulates across the k rounds so slot indices never collide.
    occupancy = jnp.zeros((e,), jnp.int32)
    fraction_routed = jnp.zeros((e,), probs.dtype)
    for _ in range(k):
        choice = jnp.argmax(masked, axis=-1)                     # [N]
        onehot_raw = jax.nn.one_hot(choice, e, dtype=jnp.int32)   # [N, E]
        # A token whose remaining probs are all zero (padding, or E < k) is
        # out of the round: no capacity slot, no routed-fraction credit.
        valid = jnp.max(masked, axis=-1) > 0                      # [N]
        onehot = onehot_raw * valid[:, None].astype(jnp.int32)
        pos = occupancy[None, :] + jnp.cumsum(onehot, axis=0) - onehot
        pos = jnp.sum(pos * onehot, axis=-1)                      # [N]
        keep = (pos < capacity) & valid
        occupancy = occupancy + jnp.sum(
            onehot * keep[:, None].astype(jnp.int32), axis=0)
        slot = jax.nn.one_hot(pos, capacity, dtype=probs.dtype)   # [N, C]
        gate = jnp.take_along_axis(probs, choice[:, None], axis=-1)[:, 0]
        route = (onehot.astype(probs.dtype) * keep[:, None]
                 )[:, :, None] * slot[:, None, :]                 # [N, E, C]
        combine = combine + gate[:, None, None] * route
        dispatch = dispatch | (route > 0)
        fraction_routed = fraction_routed + jnp.sum(
            onehot.astype(probs.dtype), axis=0) / denom
        masked = masked * (1.0 - onehot_raw.astype(probs.dtype))
    # Switch-transformer load-balance loss: E * <p_e> . <f_e> (per round,
    # averaged, over VALID tokens); pushes toward uniform expert utilisation.
    aux = e * jnp.sum(jnp.sum(probs, axis=0) / denom * fraction_routed / k)
    return combine, dispatch.astype(probs.dtype), aux


def moe_ffn(params: Dict[str, jax.Array], x, *, k: int = 2,
            capacity_factor: float = 1.25,
            activation: str = "gelu",
            mesh: Optional[Mesh] = None,
            axis: str = AXIS_EXPERT,
            token_mask=None,
            group_size: Optional[int] = None
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Expert-parallel feed-forward over tokens x: [N, d] -> [N, d].

    params: gate [d, E], w1 [E, d, h], b1 [E, h], w2 [E, h, d], b2 [E, d].
    token_mask: optional [N] 0/1 validity (padding excluded from routing).

    group_size=None routes all N tokens in one group — dispatch/combine are
    [N, E, C] with C = cf*k*N/E, i.e. O(N^2) memory; fine for small batches.
    group_size=S switches to GShard-style grouped dispatch ([G, S, E, C],
    C = cf*k*S/E): per-group capacity, memory linear in N, and the G (token)
    → E (expert) resharding of the dispatch einsum lowers to all_to_all over
    ICI when `mesh` is active. Use this at >4k-token scale.

    Returns (y, aux_loss, overflow_frac) — overflow_frac is the fraction of
    desired (token, expert) routes dropped because expert capacity filled up.
    """
    e = params["w1"].shape[0]
    n = x.shape[0]
    act = Activation.get(activation)

    if group_size is None or group_size >= n:
        capacity = max(1, int(capacity_factor * k * n / e))
        logits = x @ params["gate"].astype(x.dtype)
        combine, dispatch, aux = top_k_gating(
            logits.astype(jnp.float32), k, capacity, token_mask=token_mask)
        combine = combine.astype(x.dtype)
        dispatch = dispatch.astype(x.dtype)
        n_valid = (jnp.sum(token_mask) if token_mask is not None
                   else jnp.asarray(float(n), jnp.float32))

        ex_in = jnp.einsum("nec,nd->ecd", dispatch, x)
        if mesh is not None and axis in mesh.axis_names:
            # Pin the expert dim so the partitioner materialises the dispatch
            # as an all_to_all over ICI instead of replicating expert blocks.
            ex_in = jax.lax.with_sharding_constraint(
                ex_in, NamedSharding(mesh, P(axis)))
        h = act(jnp.einsum("ecd,edh->ech", ex_in, params["w1"])
                + params["b1"][:, None, :])
        ex_out = (jnp.einsum("ech,ehd->ecd", h, params["w2"])
                  + params["b2"][:, None, :])
        if mesh is not None and axis in mesh.axis_names:
            ex_out = jax.lax.with_sharding_constraint(
                ex_out, NamedSharding(mesh, P(axis)))
        y = jnp.einsum("nec,ecd->nd", combine, ex_out)
        routed = jnp.sum(dispatch)
    else:
        s = int(group_size)
        pad = (-n) % s
        if pad:
            x_p = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:],
                                                x.dtype)])
            tm = (jnp.concatenate([token_mask.astype(jnp.float32),
                                   jnp.zeros((pad,), jnp.float32)])
                  if token_mask is not None
                  else jnp.concatenate([jnp.ones((n,), jnp.float32),
                                        jnp.zeros((pad,), jnp.float32)]))
        else:
            x_p = x
            tm = (token_mask.astype(jnp.float32)
                  if token_mask is not None else None)
        g = x_p.shape[0] // s
        capacity = max(1, int(capacity_factor * k * s / e))
        x_g = x_p.reshape(g, s, -1)
        if mesh is not None and axis in mesh.axis_names:
            # Token groups data-parallel over the expert devices: the G→E
            # resharding in the dispatch einsum becomes the MoE all_to_all.
            x_g = jax.lax.with_sharding_constraint(
                x_g, NamedSharding(mesh, P(axis)))
        logits_g = (x_g @ params["gate"].astype(x.dtype)).astype(jnp.float32)
        if tm is not None:
            tm_g = tm.reshape(g, s)
            combine, dispatch, aux_g = jax.vmap(
                lambda lg, mg: top_k_gating(lg, k, capacity, token_mask=mg)
            )(logits_g, tm_g)
            n_valid = jnp.sum(tm)
            # Weight by per-group valid tokens: fully-masked groups report
            # aux=0 and must not dilute the load-balance gradient.
            valid_g = jnp.sum(tm_g, axis=1)
            aux = (jnp.sum(aux_g * valid_g)
                   / jnp.maximum(jnp.sum(valid_g), 1.0))
        else:
            combine, dispatch, aux_g = jax.vmap(
                lambda lg: top_k_gating(lg, k, capacity))(logits_g)
            n_valid = jnp.asarray(float(n), jnp.float32)
            aux = jnp.mean(aux_g)
        combine = combine.astype(x.dtype)
        dispatch = dispatch.astype(x.dtype)

        ex_in = jnp.einsum("gsec,gsd->egcd", dispatch, x_g)
        if mesh is not None and axis in mesh.axis_names:
            ex_in = jax.lax.with_sharding_constraint(
                ex_in, NamedSharding(mesh, P(axis)))
        h = act(jnp.einsum("egcd,edh->egch", ex_in, params["w1"])
                + params["b1"][:, None, None, :])
        ex_out = (jnp.einsum("egch,ehd->egcd", h, params["w2"])
                  + params["b2"][:, None, None, :])
        if mesh is not None and axis in mesh.axis_names:
            ex_out = jax.lax.with_sharding_constraint(
                ex_out, NamedSharding(mesh, P(axis)))
        y_g = jnp.einsum("gsec,egcd->gsd", combine, ex_out)
        if mesh is not None and axis in mesh.axis_names:
            y_g = jax.lax.with_sharding_constraint(
                y_g, NamedSharding(mesh, P(axis)))
        y = y_g.reshape(g * s, -1)[:n]
        routed = jnp.sum(dispatch)

    expected = jnp.maximum(n_valid * min(k, e), 1.0)
    overflow = jnp.maximum(0.0, 1.0 - routed / expected)
    return y, aux, overflow


def expert_sharding(params: Dict[str, Any], mesh: Mesh,
                    axis: str = AXIS_EXPERT):
    """NamedShardings: expert-indexed leaves sharded on their E axis, gate
    replicated."""
    return {
        k: NamedSharding(mesh, P() if k == "gate" else P(axis))
        for k in params
    }


@register_layer
@dataclasses.dataclass(frozen=True)
class MoEFeedForward(Layer):
    """Mixture-of-experts FFN layer (d -> d, residual inside).

    Pluggable into MultiLayerNetwork/ComputationGraph like any layer;
    reports its load-balancing auxiliary loss via state["aux_loss"], which
    the model loss closures fold into the score (weighted by aux_weight).
    Accepts [B, d] or RNN-format [B, T, d] activations.
    """

    # Consumes [B, d] or [B, T, d] natively — keep the config builder from
    # inserting an Rnn->FF (last-timestep) preprocessor in front of it.
    CONSUMES = "any"

    n_in: Optional[int] = None
    n_experts: int = 8
    hidden_mult: int = 4
    k: int = 2
    capacity_factor: float = 1.25
    aux_weight: float = 1e-2
    residual: bool = True
    # GShard-style grouped dispatch: None = single group (fine for small
    # batches); set to e.g. 512-1024 at >4k-token scale to keep the
    # dispatch/combine tensors linear in token count.
    group_size: Optional[int] = None

    def infer_n_in(self, input_type: InputType) -> "MoEFeedForward":
        if self.n_in is None:
            return dataclasses.replace(self, n_in=input_type.size)
        return self

    def init_params(self, key, input_type, dtype=jnp.float32):
        d = self.n_in or input_type.size
        h = self.hidden_mult * d
        e = self.n_experts
        ks = jax.random.split(key, 3)
        winit = self._winit()
        params = {
            "gate": winit(ks[0], (d, e), dtype),
            "w1": jnp.stack([winit(jax.random.fold_in(ks[1], i), (d, h), dtype)
                             for i in range(e)]),
            "b1": jnp.zeros((e, h), dtype),
            "w2": jnp.stack([winit(jax.random.fold_in(ks[2], i), (h, d), dtype)
                             for i in range(e)]),
            "b2": jnp.zeros((e, d), dtype),
        }
        # Non-empty init state marks the layer stateful, so the model
        # runtimes persist the per-step routing metrics into state_tree —
        # net.state_tree[name]["overflow_frac"] is user-visible after fit.
        state = {"aux_loss": jnp.zeros((), jnp.float32),
                 "overflow_frac": jnp.zeros((), jnp.float32)}
        return params, state

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None):
        x = self._maybe_dropout(x, train, rng)
        rnn = x.ndim == 3
        token_mask = None
        if rnn:  # [B, T, d] (framework RNN layout, recurrent.py) -> [B*T, d]
            b, t, d = x.shape
            tokens = x.reshape(b * t, d)
            if mask is not None:  # [B, T] timestep mask -> [B*T]
                token_mask = jnp.reshape(mask, (b * t,))
        else:
            tokens = x
        mesh, axis = _active_expert_mesh()
        y, aux, overflow = moe_ffn(
            params, tokens, k=self.k,
            capacity_factor=self.capacity_factor,
            activation=self.activation or "gelu",
            mesh=mesh, axis=axis, token_mask=token_mask,
            group_size=self.group_size)
        if self.residual:
            y = y + tokens
        if rnn:
            y = y.reshape(b, t, d)
        return y, {"aux_loss": self.aux_weight * aux,
                   "overflow_frac": overflow}


# ------------------------------------------- one device's share of experts
def _chosen(s, experts):
    """`take_along_axis(s, experts, -1)`, the scores [N, E] of each
    token's `k` distinct experts [N, k], as a sum under a one-hot over
    the experts' axis (a score or exact zeros), and its transpose the
    same: on the TPU XLA's gather of N x k scalars reads 1.84 ms where
    this reads 0.26 (8,192 x 22 of 512), and its scatter-add sorts all
    the pairs to place them (chip runs, PR 51)."""
    lanes = jnp.arange(s.shape[-1], dtype=experts.dtype)
    return jnp.sum(jnp.where(experts[:, :, None] == lanes, s[:, None, :], 0),
                   axis=-1)


def _by(key, v):
    return jax.lax.sort((key, v), num_keys=1, is_stable=False)[1]


@jax.custom_vjp
def _in_order(v, order, place):
    """`v[order]` for a permutation `order` [rows] whose inverse is
    `place`, and its transpose `g[place]`, each made by SORTING the
    values with the other permutation for key: on the TPU a two-operand
    sort of 180,224 distinct keys reads 0.11 ms where XLA's gather of as
    many scalars reads 1.44 and its scatter-add 1.53 with a sort of its
    own (chip runs, PR 51)."""
    return _by(place, v)


_in_order.defvjp(
    lambda v, order, place: (_by(place, v), order),
    lambda order, g: (_by(order, g), None, None))


def route(x, router, bias, *, k: int, score: str, route_norm: bool,
          route_scale: float, n_group: int = 1, topk_group: int = 1):
    """Each token's `k` experts and their weights: scores over the
    router's whole width in float32 (`score`: "sigmoid", each expert on
    its own, or "softmax"), the `k` largest of score + `bias` (which
    steers the choice only: no weight and no gradient comes of it), and
    the chosen scores themselves, normalised to sum to one where
    `route_norm`, times `route_scale`. With `n_group` > 1 the choice has
    two stages (group-limited greedy, under the scope `group_select`):
    the experts lie in `n_group` groups of consecutive indices, a token
    keeps the `topk_group` groups whose best expert scores highest (of
    equal ones the lower group) and chooses its `k` among those groups'
    experts alone (of equal ones the lower expert). Returns (experts
    [N, k] int32, weights [N, k] float32)."""
    logits = jnp.dot(x, router, preferred_element_type=jnp.float32)
    if score == "sigmoid":
        s = jax.nn.sigmoid(logits)
    elif score == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"score must be 'sigmoid' or 'softmax', "
                         f"got {score!r}")
    choice = s if bias is None else s + jax.lax.stop_gradient(
        bias.astype(jnp.float32))
    choice = jax.lax.stop_gradient(choice)
    if n_group > 1:
        n, e = choice.shape
        if e % n_group or not 1 <= topk_group <= n_group \
                or k > topk_group * (e // n_group):
            raise ValueError(f"{e} experts in {n_group} groups, "
                             f"{topk_group} kept, {k} chosen")
        with jax.named_scope("group_select"):
            best = jnp.max(choice.reshape(n, n_group, e // n_group), axis=-1)
            _, kept = jax.lax.top_k(best, topk_group)           # [N, kept]
            keep = jnp.any(kept[:, :, None] == jnp.arange(n_group), axis=1)
            choice = jnp.where(jnp.repeat(keep, e // n_group, axis=1),
                               choice, 0.0)
    _, experts = jax.lax.top_k(choice, k)
    # a choice with no backward: named, a checkpointed layer keeps it and
    # its recomputation has no `top_k` (and no `group_select`)
    experts = name_block_residual(experts.astype(jnp.int32),
                                  "expert_schedule")
    weights = _chosen(s, experts)
    if route_norm:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + 1e-20)
    return experts, weights * route_scale


@jax.custom_vjp
def _take_rows(x, index, back):
    """`x[index]`: the token rows of the first C sorted pairs (`index`
    [C]). Its transpose is `_sum_rows`, so the cotangent is gathered too
    and no scatter-add over rows is ever made."""
    return jnp.take(x, index, axis=0)


@jax.custom_vjp
def _sum_rows(x, index, back):
    """The sum over a token's k pairs of the pairs' rows: `sum_j
    x_[back[:, j]]` with `x_` = `x` [C, d] and one row of zeros after it,
    where `back` [N, k] holds each pair's place among the sorted pairs, C
    for a pair that is not among the first C. The transpose of
    `_take_rows`, a gather like it."""
    padded = jnp.concatenate([x, jnp.zeros((1,) + x.shape[1:], x.dtype)])
    return jnp.sum(jnp.take(padded, back, axis=0).astype(jnp.float32),
                   axis=1).astype(x.dtype)


_take_rows.defvjp(
    lambda x, index, back: (_take_rows(x, index, back), (index, back)),
    lambda res, g: (_sum_rows(g, *res), None, None))
_sum_rows.defvjp(
    lambda x, index, back: (_sum_rows(x, index, back), (index, back)),
    lambda res, g: (_take_rows(g, *res), None, None))


def _swiglu(x, w1, w3, w2, dot):
    return dot(jax.nn.silu(dot(x, w1)) * dot(x, w3), w2)


def _relu2(h):
    return jnp.square(jax.nn.relu(h))


# an expert's form by name: "swiglu" has `w3` beside `w1` and `w2`
EXPERT_FORMS = ("swiglu", "relu2")


def _row_tiers(rows: int, share: float,
               most: Optional[int] = None) -> Tuple[int, ...]:
    """The row counts the routed products are compiled for where XLA's
    `ragged_dot` and gathers run (the CPU, any mesh context): four times
    what uniform routing sends to the experts held (`share` of all `rows`
    pairs), twice and four times that, and `most`, all that can fall here
    (a token's pairs lie on distinct experts; `rows` where not given). A
    step runs the smallest that holds the pairs that fell here, so no
    pair is ever dropped and the work follows them, coarsely: on the chip
    a router's load on eight of 256 experts read 0.4 to 2.1 times the
    uniform share from seed to seed and batch to batch, and a tier that
    every other step crosses makes the step's time a matter of the seed.
    Where twice the first tier is `most` or more the ladder would have
    one rung to climb, a step on it runs the layer at twice the cost, and
    WHEN a training run's routers climb it differs from seed to seed: nine
    of 72 experts at ten a token crossed in the eighteenth step, one to
    three layers of ten apart, and a window's rate read 0.73% apart where
    the other cells read 0.03 (chip runs, PR 42). There the one tier is
    `most`. A layer with a ladder counts the rows past the pairs held to
    the last expert held, so each of its tiers costs the same whatever
    fell into it. Where the kernels run
    (`ops/kernel_defaults.kernels_run`) every layer has the LAST of these
    alone, `most`: a row past the pairs held is no row
    to the grouped products and the gathers round them, which follow the
    pairs with no threshold to cross, and what passes over the tier's
    rows (the SwiGLU's elementwise work between the products; and, over
    all pairs, `pair_schedule`'s one sort and count, made once a step:
    a checkpointed layer keeps them) costs the same every step
    (`held_experts`; the products since PR 43, six seeds' rates 0.04%
    apart by their quartiles where the two tiers' lay 0.73; the gathers
    since PR 44; the layers that have a ladder elsewhere since PR 47:
    chip runs)."""
    most = min(rows, -(-int(rows if most is None else most) // 128) * 128)
    up = lambda n: min(most, -(-int(n) // 128) * 128)
    first = up(4 * share * rows)
    if 2 * first >= most:
        return (most,)
    return tuple(sorted({first, up(2 * first), up(4 * first), most}))


_LANES = 128


def pair_schedule(key, count: int):
    """Which pair goes to which row: `key` [rows] int32 holds each pair's
    expert among the `count` held, `count` for a pair of none of them.
    Returns (`order`, `place`, `sizes`), all int32: `order` [rows] the
    pairs by expert and, of one expert, by index (`argsort(key,
    stable=True)` to the bit), `place` [rows] its inverse (the row of
    pair p) and `sizes` [count] the pairs of each expert held.

    `order` is ONE single-operand sort of the word `key << bits | p`
    (`bits` what the index takes: the words are distinct and ties fall
    in p's order). `place` is made by counting, with no second sort:
    `place[p] = start[key[p]] + (pairs before p with p's key)`, the
    pairs' axis on the lanes all through: the running count of each
    bucket inside a tile of 128 pairs, the tiles' totals summed along
    the tiles, and `start` the exclusive sum of the buckets' sizes, which
    are the same totals summed. Where the word does not fit 32 bits, a
    fact of the shapes (some 2**31 (bucket, pair) cells, which the count
    would have to write out), the two-operand stable sort and its
    inverse's."""
    rows = key.shape[0]
    key = key.astype(jnp.int32)
    i32 = dict(dtype=jnp.int32)
    bits = max(rows - 1, 1).bit_length()
    if (count + 1) << bits > 2 ** 31:
        order = jnp.argsort(key, stable=True)
        return (order.astype(jnp.int32), jnp.argsort(order).astype(jnp.int32),
                jnp.bincount(key, length=count + 1)[:count].astype(jnp.int32))
    word = (key << bits) | jnp.arange(rows, **i32)
    order = jnp.sort(word, stable=False) & ((1 << bits) - 1)
    # pairs past `rows` fill the last tile as pairs of no expert held:
    # they lie after every real pair, so no real pair counts them
    padded = -(-rows // _LANES) * _LANES
    tiles = jnp.pad(key, (0, padded - rows), constant_values=count)
    hot = (tiles.reshape(-1, _LANES)[None]
           == jnp.arange(count + 1, **i32)[:, None, None])
    within = jnp.cumsum(hot, axis=-1, **i32)    # [count + 1, tiles, 128]
    in_tile = within[..., -1]
    before_tile = jnp.cumsum(in_tile, axis=-1, **i32) - in_tile
    sizes = jnp.sum(in_tile, axis=-1, **i32)
    start = jnp.cumsum(sizes, **i32) - sizes
    place = jnp.sum(jnp.where(
        hot, within + (start[:, None] + before_tile)[..., None], 0),
        axis=0, **i32) - 1
    return order, place.reshape(padded)[:rows], sizes[:count]


def held_experts(x, experts, weights, w1, w3, w2, *, first: int,
                 n_experts: int):
    """sum over a token's pairs whose expert is held of weight x
    expert(token): x [N, d], experts and weights [N, k], the held experts'
    SwiGLU kernels w1, w3 [count, d, f] and w2 [count, f, d], `first` the
    published index of the first of them among the router's `n_experts`.
    With `w3` None the experts are two-matrix `relu(x w1)^2 w2` ("relu2"):
    two grouped products forward where a SwiGLU has three, and the rows
    gathered once. Returns (y [N, d], counters)."""
    n, k = experts.shape
    count, rows = w1.shape[0], n * k
    with jax.named_scope("dispatch"):
        local = experts.reshape(rows) - first
        is_held = (local >= 0) & (local < count)
        key = jnp.where(is_held, local, count)      # the rest sort last
        # integers with no backward, which a layer's recomputation would
        # make again only to hand them to the tier: named, a checkpointed
        # layer keeps them and sorts and counts once a step
        order, place, sizes = name_block_residual(
            pair_schedule(key, count), "expert_schedule")
        token = order // k                          # pair p is token p // k
        # the weights in row order have a backward, and are kept with the
        # schedule: the recomputation would sort them again
        pair_weight = name_block_residual(_in_order(
            jnp.where(is_held, weights.reshape(rows), 0.0), order, place),
            "expert_schedule")
        place = place.reshape(n, k)
        n_held = jnp.sum(sizes)
    # On the TPU (on one device: under a mesh the operands may be sharded)
    # every layer has ONE tier, all that can fall here: its grouped
    # products are `ops/grouped_matmul`'s kernel, whose grid visits only
    # the row tiles that hold a row of a group, and the rows past the
    # pairs held are left in NO group, so the kernel passes them by; and
    # its two gathers are `ops/row_gather`'s kernels, which move the rows
    # of the pairs held and no other, a row a DMA: the layer's time but
    # for the schedule above (one sort and one count over all pairs, once
    # a step: the recomputation reads the kept one) and the elementwise
    # work between the products follows the pairs, continuously, with no
    # tier to cross. On any other backend
    # and under a mesh a layer has `_row_tiers`' ladder, counts the rows
    # past the pairs held to the last expert held, gathers with XLA's
    # gather over the tier and runs XLA's `ragged_dot`: a tier then costs
    # the same whatever fell into it, its padding is bounded by the ladder
    # (four times the uniform share), and a step's time is not a matter
    # of the seed. (The kernel in each of a ladder's tiers was faster on
    # the TPU too, but four times the call sites to trace, lower and
    # compile, and set-up grew by a fifth: chip runs, PR 43.)
    kernel = _kernel_runs()
    tiers = _row_tiers(rows, count / n_experts, n * min(k, count))
    if kernel:
        tiers = tiers[-1:]
    from deeplearning4j_tpu.ops.grouped_matmul import (
        grouped_dot, rows_visited, schedule, tile_rows,
    )
    from deeplearning4j_tpu.ops.row_gather import sum_rows, take_rows

    def tier(c):
        # under `jax.checkpoint`: what a tier keeps for its backward pass
        # is its arguments, the same for every tier, so the one that runs
        # writes no residuals of the others' sizes. That holds inside a
        # checkpointed layer too, so this checkpoint stays there, and the
        # tier's backward runs its forward again: three grouped products
        # forward, three here, six backward. A layer's own recomputation
        # runs the switch once more only where something reads the
        # layer's OUTPUT as a value (a norm after it): the block that has
        # one names that output (`ops/attention.name_block_residual`), the
        # layer's policy keeps it, and JAX drops the dead run
        @jax.checkpoint
        def run(x, w1, w3, w2, token, place, pair_weight, sizes):
            held = jnp.sum(sizes)
            weight = pair_weight[:c].astype(x.dtype)
            if kernel:
                # Nothing reads a row past the pairs held: the gathers
                # move the live rows alone (`ops/row_gather`: a place at
                # or past `held` is no row), the products pass the rest
                # by, so such a row may hold anything, on both sides of
                # every product, and no mask is made over the tier. The
                # rows come back twice, one array: each product's
                # cotangent goes into the transposed gather on its own,
                # which adds them as it packs them, and it is the gather
                # that weighs a row as it packs it.
                rows = lambda v: v
                with jax.named_scope("dispatch"):
                    taken = take_rows(x, token[:c], place, held,
                                      1 if w3 is None else 2)
                    if w3 is None:
                        taken = (taken,)
                summed = lambda v: sum_rows(v, weight, token[:c], place,
                                            held)
                in_group = sizes
            else:
                # A row past the pairs held is no pair of ours. It is
                # counted to the last expert held, and what `ragged_dot`
                # reads must be real zeros, in its operands and in its
                # cotangents alike, so such rows are put at zero on both
                # sides of every product.
                back = jnp.where(place < c, place, c)
                live = (jnp.arange(c) < held)[:, None]
                rows = lambda v: jnp.where(live, v, 0)
                with jax.named_scope("dispatch"):
                    taken = (rows(_take_rows(x, token[:c], back)),) * 2
                summed = lambda v: _sum_rows(v * weight[:, None],
                                             token[:c], back)
                in_group = sizes.at[-1].add((c - held).astype(sizes.dtype))
            with jax.named_scope("experts_held"):
                if kernel:      # one schedule for the three products
                    plan = schedule(in_group, c)
                    product = lambda a, w: grouped_dot(a, w, plan)
                else:
                    product = lambda a, w: jax.lax.ragged_dot(
                        a, w, group_sizes=in_group)
                dot = lambda a, w: rows(product(a, w))
                if w3 is None:
                    out = dot(_relu2(dot(taken[0], w1)), w2)
                else:
                    out = dot(jax.nn.silu(dot(taken[0], w1))
                              * dot(taken[1], w3), w2)
            with jax.named_scope("combine"):
                return summed(out)
        return run

    which = jnp.sum(n_held > jnp.asarray(tiers[:-1], jnp.int32)) \
        if len(tiers) > 1 else 0
    args = (x, w1, w3, w2, token, place, pair_weight, sizes)
    y = (jax.lax.switch(which, [tier(c) for c in tiers], *args)
         if len(tiers) > 1 else tier(tiers[0])(*args))
    taken = jnp.asarray(tiers, jnp.int32)[which]
    visited = rows_visited(schedule(sizes, tiers[0])) if kernel else taken
    tile = tile_rows(tiers[0])
    gathered = (n_held + (tile - 1)) // tile * tile if kernel else taken
    return y, dict(zip(COUNTERS, (
        jnp.asarray(rows, jnp.int32), n_held,
        jnp.maximum(n_held - taken, 0), jnp.max(sizes), jnp.min(sizes),
        taken, visited.astype(jnp.int32), gathered.astype(jnp.int32))))


# a step's routing counters, in an expert layer's state: the pairs the
# router chose (tokens x k), those that fell on experts held, those of
# them not computed (0: there is no capacity), the largest and smallest
# load of an expert held, the rows of the tier the step ran, and the rows
# its grouped products multiplied: the row tiles their schedule visits
# times a tile's rows (`ops/grouped_matmul.schedule`), the tier's rows
# where every row is in a group, and the rows its gather moved: the row
# tiles that hold a pair times a tile's rows (`ops/row_gather.take_rows`),
# the tier's rows wherever XLA's gather runs
COUNTERS = ("moe_pairs_routed", "moe_pairs_held", "moe_pairs_dropped",
            "moe_load_max", "moe_load_min", "moe_rows_tier",
            "moe_rows_visited", "moe_rows_gathered")
# and, of a layer that routes by groups, the tokens with at least one pair
# on an expert held: what the exchange would send this device, which is
# what group limits exist to bound
TOKENS_HELD = "moe_tokens_held"


@register_layer
@dataclasses.dataclass(frozen=True)
class ExpertFeedForward(Layer):
    """One device's share of a sparse model's expert layer (d -> d, no
    residual inside): `shared(x) + sum over a token's k experts that are
    held here of weight x expert(x)`, every expert a bias-free SwiGLU of
    `width`. The router spans all `n_experts` (the published count);
    `held` = (first, count) names the ones whose kernels this device has,
    all of them where None. What the absent experts would add is left out:
    on a mesh their devices add it, here nothing stands in for them.

    With `n_group` > 1 a token chooses its `k` inside the `topk_group`
    groups of experts whose best score is highest (`route`).

    `expert_form` "relu2" makes every expert the two-matrix
    `relu(x w1)^2 w2` in the SwiGLU's place. With `latent` the routed
    experts live in a latent of that width (LatentMoE): the router still
    reads the token at the model's width, `u = x latent_down` goes to the
    experts ([latent -> width -> latent] each) and their weighted sum comes
    back through `latent_up`, both projections whole on every device;
    the shared expert stays at the model's width and has the experts'
    form. `shared_width` gives the shared expert a width of its own
    (`n_shared` x `width` where None). The defaults are the SwiGLU layer
    at the model's width, to the bit.

    Leaves: `router` [d, n_experts]; `bias` [n_experts] where
    `selection_bias` (added to the scores for the choice only, its
    gradient exactly zero: whoever balances load moves it between steps);
    `w1`, `w3` [count, d, width], `w2` [count, width, d] (no `w3` for
    "relu2"; `d` the latent's width where there is one); with a shared
    expert `shared_w1`, `shared_w3` [d, shared width] and `shared_w2`;
    with `latent`, `latent_down` [d, latent] and `latent_up` [latent, d].
    State: the last step's `COUNTERS` and, where it routes
    by groups, `TOKENS_HELD`, which `fit()` publishes as gauges
    `<name>{layer=}` where an epoch synchronises. Scopes: `router`,
    `latent_down`, `dispatch`, `experts_held`, `combine`, `latent_up`,
    `shared_expert`. Accepts [N, d] or [B, T, d]."""

    CONSUMES = "any"

    n_in: Optional[int] = None
    width: Optional[int] = None
    n_experts: int = 8
    held: Optional[Tuple[int, int]] = None
    k: int = 2
    score: str = "softmax"
    selection_bias: bool = False
    route_norm: bool = False
    route_scale: float = 1.0
    n_shared: int = 0
    n_group: int = 1
    topk_group: int = 1
    expert_form: str = "swiglu"
    latent: Optional[int] = None
    shared_width: Optional[int] = None

    def infer_n_in(self, input_type: InputType) -> "ExpertFeedForward":
        if self.n_in is None:
            return dataclasses.replace(self, n_in=input_type.size)
        return self

    @property
    def _held(self) -> Tuple[int, int]:
        first, count = self.held or (0, self.n_experts)
        if not (0 <= first and 1 <= count
                and first + count <= self.n_experts):
            raise ValueError(f"held {self.held} lies outside the "
                             f"{self.n_experts} experts")
        return int(first), int(count)

    def init_params(self, key, input_type, dtype=jnp.float32):
        d = self.n_in or input_type.size
        f = self.width or 4 * d
        if not 1 <= self.k <= self.n_experts:
            raise ValueError(f"k {self.k} of {self.n_experts} experts")
        if self.expert_form not in EXPERT_FORMS:
            raise ValueError(f"an expert is one of {EXPERT_FORMS}, "
                             f"not {self.expert_form!r}")
        gated = self.expert_form == "swiglu"
        first, count = self._held
        ks = jax.random.split(key, 7)
        winit = self._winit()
        inner = self.latent or d     # the width the routed experts read

        def stack(key, shape):      # an expert's kernel from its own index
            return jnp.stack([winit(jax.random.fold_in(key, first + i),
                                    shape, dtype) for i in range(count)])

        params = {"router": winit(ks[0], (d, self.n_experts), dtype),
                  "w1": stack(ks[1], (inner, f)),
                  "w2": stack(ks[3], (f, inner))}
        if gated:
            params["w3"] = stack(ks[2], (inner, f))
        if self.selection_bias:
            params["bias"] = jnp.zeros((self.n_experts,), dtype)
        fs = self.shared_width or self.n_shared * f
        if fs:
            params.update(shared_w1=winit(ks[4], (d, fs), dtype),
                          shared_w2=winit(ks[6], (fs, d), dtype))
            if gated:
                params["shared_w3"] = winit(ks[5], (d, fs), dtype)
        if self.latent:
            down, up = jax.random.split(jax.random.fold_in(key, 7))
            params.update(latent_down=winit(down, (d, inner), dtype),
                          latent_up=winit(up, (inner, d), dtype))
        names = COUNTERS + ((TOKENS_HELD,) if self.n_group > 1 else ())
        # a buffer each: the step donates its state
        return params, {n: jnp.zeros((), jnp.int32) for n in names}

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None):
        tokens = x.reshape(-1, x.shape[-1])
        with jax.named_scope("router"):
            experts, weights = route(
                tokens, params["router"], params.get("bias"), k=self.k,
                score=self.score, route_norm=self.route_norm,
                route_scale=self.route_scale, n_group=self.n_group,
                topk_group=self.topk_group)
        first, count = self._held
        routed = tokens
        if self.latent:
            with jax.named_scope("latent_down"):
                routed = tokens @ params["latent_down"]
        y, counters = held_experts(
            routed, experts, weights.astype(tokens.dtype), params["w1"],
            params.get("w3"), params["w2"], first=first,
            n_experts=self.n_experts)
        if self.latent:
            # `latent_up`'s own gradient reads the routed sum: named, a
            # checkpointed layer keeps it ([N, latent]) and its
            # recomputation leaves the routed path's products out
            y = name_block_residual(y, "sublayer_out")
            with jax.named_scope("latent_up"):
                y = y @ params["latent_up"]
        if self.n_group > 1:
            here = (experts >= first) & (experts < first + count)
            counters[TOKENS_HELD] = jnp.sum(jnp.any(here, axis=-1),
                                            dtype=jnp.int32)
        if "shared_w1" in params:
            with jax.named_scope("shared_expert"):
                if "shared_w3" in params:
                    y = y + _swiglu(tokens, params["shared_w1"],
                                    params["shared_w3"],
                                    params["shared_w2"], jnp.dot)
                else:
                    y = y + (_relu2(tokens @ params["shared_w1"])
                             @ params["shared_w2"])
        return y.reshape(x.shape), counters
