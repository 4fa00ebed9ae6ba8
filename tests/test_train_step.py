"""The one train step (`optim/step.py`) and the one updater path under it.

Three things no other tier-1 test holds: every update rule's whole step
(`update_with_params`) against `apply` plus the subtraction, with the
dtypes donation rests on; the step factory on a loss that is neither model
class (plain step, K-step window, tBPTT carries, pinned shardings); and the
benchmark's contract, a `_get_train_step` replaced on the class by a step
that returns its state unchanged.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.optim.step import (
    jit_step, make_fused_step, make_train_step, stack_step_args,
)
from deeplearning4j_tpu.parallel.mesh import make_mesh
from deeplearning4j_tpu.optim.updaters import (
    AdaDelta, AdaGrad, AdaMax, Adam, AMSGrad, Nadam, Nesterovs, NoOp,
    RmsProp, Sgd,
)

_tmap = jax.tree_util.tree_map


def _assert_trees_equal(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


# ------------------------------------------------- (a) the one updater path
RULES = {
    "sgd": Sgd(0.05),
    "nesterovs": Nesterovs(0.05, momentum=0.8),
    "adam": Adam(3e-3, beta1=0.8, beta2=0.95, epsilon=1e-6),
    "adamax": AdaMax(3e-3, beta1=0.8, beta2=0.95),
    "nadam": Nadam(3e-3, beta1=0.8, beta2=0.95),
    "amsgrad": AMSGrad(3e-3, beta1=0.8, beta2=0.95),
    "adagrad": AdaGrad(0.05),
    "adadelta": AdaDelta(rho=0.9),
    "rmsprop": RmsProp(0.05, rms_decay=0.9),
    "noop": NoOp(),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rule", sorted(RULES))
def test_whole_update_is_apply_then_subtract_in_the_dtypes_it_got(
        rule, dtype):
    """Two steps of every rule (the second over moments that are not
    zero): parameters and state come back in the dtypes they came in,
    whatever the float32 schedule arithmetic promoted to, and equal
    `apply` followed by the dtype-preserving subtraction."""
    u, dt = RULES[rule], jnp.dtype(dtype)
    rng = np.random.default_rng(11)
    params = {"W": jnp.asarray(rng.standard_normal((5, 7)), dt),
              "b": jnp.asarray(rng.standard_normal((7,)), dt)}
    state = u.init(params)
    for step in (0, 1):
        grads = _tmap(lambda p: jnp.asarray(
            rng.standard_normal(p.shape) * 0.1, dt), params)
        step = jnp.asarray(step, jnp.int32)
        upd, raw_state = u.apply(grads, state, params, step)
        want_p = _tmap(lambda p, d: p - d.astype(p.dtype), params, upd)
        want_s = _tmap(lambda n, o: n.astype(o.dtype), raw_state, state)
        got_p, got_s = u.update_with_params(grads, state, params, step)
        assert all(x.dtype == dt for x in jax.tree_util.tree_leaves(
            (got_p, got_s)))
        _assert_trees_equal(got_p, want_p)
        _assert_trees_equal(got_s, want_s)
        if rule != "noop":
            assert float(jnp.abs(got_p["W"].astype(jnp.float32)
                                 - params["W"].astype(jnp.float32)).max()) > 0
        params, state = got_p, got_s


# ------------------------------------- (b) the factory, on neither model class
def _toy_loss(params, states, x, y, fmask, lmask, rng, carries=None):
    """Two 'layers': `enc` keeps a state that persists and can be carried
    (its last hidden rows), `head` has none; `rng` is noise on the hidden
    rows, so a wrong key chain shows."""
    h = jnp.tanh(x["in"] @ params["enc"]["w"])
    if carries is not None:
        h = h + 0.5 * carries["enc"]["h"]
    h = h + 0.05 * jax.random.normal(rng, h.shape, h.dtype)
    err = h @ params["head"]["w"] - y["out"]
    if lmask is not None:
        err = err * lmask["out"][:, None]
    new_states = {"enc": {"h": h, "seen": states["enc"]["seen"] + 1},
                  "head": {"scratch": jnp.sum(h)}}
    return jnp.mean(jnp.square(err)), new_states


UPDATERS = {"enc": Nesterovs(0.1, 0.9), "head": Adam(1e-2)}


def _toy(batch=8, seed=0):
    rng = np.random.default_rng(seed)
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    params = {"enc": {"w": f32(8, 8)}, "head": {"w": f32(8, 3)}}
    opt = {n: u.init(params[n]) for n, u in UPDATERS.items()}
    states = {"enc": {"h": jnp.zeros((batch, 8), jnp.float32),
                      "seen": jnp.zeros((), jnp.int32)},
              "head": {"scratch": jnp.zeros((), jnp.float32)}}
    batches = [({"in": f32(batch, 8)}, {"out": f32(batch, 3)}, None, None)
               for _ in range(4)]
    return params, opt, states, batches


def _step(mode="none", threshold=1.0, carry_names=None):
    return make_train_step(_toy_loss, UPDATERS, grad_norm=(mode, threshold),
                           stateful={"enc"}, carry_names=carry_names)


@pytest.mark.parametrize("mode", ["none", "clip_elementwise_absolute_value"])
def test_plain_step_is_gradient_normalization_update_and_persist(mode):
    params, opt, states, batches = _toy()
    key, it = jax.random.PRNGKey(3), jnp.asarray(0, jnp.int32)
    thr = 1e-3
    step = jax.jit(_step(mode, thr))
    out = step(params, opt, states, it, *batches[0], key)
    assert len(out) == 4                       # no carries without tBPTT
    new_p, new_o, persist, loss = out

    (want_loss, new_states), g = jax.value_and_grad(
        lambda p: _toy_loss(p, states, *batches[0], key), has_aux=True)(
            params)
    if mode != "none":
        assert float(jnp.abs(g["enc"]["w"]).max()) > thr
        g = _tmap(lambda a: jnp.clip(a, -thr, thr), g)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    for name, u in UPDATERS.items():
        wp, wo = u.update_with_params(g[name], opt[name], params[name], it)
        np.testing.assert_allclose(new_p[name]["w"], wp["w"], rtol=1e-5,
                                   atol=1e-7)
        assert jax.tree_util.tree_structure(new_o[name]) == \
            jax.tree_util.tree_structure(wo)
    # a stateful layer's new state persists, any other keeps what it had
    np.testing.assert_allclose(persist["enc"]["h"], new_states["enc"]["h"],
                               rtol=1e-6)
    assert int(persist["enc"]["seen"]) == 1
    assert float(persist["head"]["scratch"]) == 0.0


def test_window_of_four_equals_four_single_steps_bit_for_bit():
    params, opt, states, batches = _toy()
    rng0, step = jax.random.PRNGKey(5), _step()

    single = jax.jit(step)
    p, o, s, rng, losses = params, opt, states, rng0, []
    for i, b in enumerate(batches):
        rng, sub = jax.random.split(rng)
        p, o, s, loss = single(p, o, s, jnp.asarray(i, jnp.int32), *b, sub)
        losses.append(loss)

    stacked = _tmap(jnp.asarray, stack_step_args(batches))
    window = jax.jit(make_fused_step(step))
    fp, fo, fs, frng, flosses = window(
        params, opt, states, np.int32(0), rng0, *stacked)
    _assert_trees_equal((fp, fo, fs), (p, o, s))
    np.testing.assert_array_equal(np.asarray(flosses), np.asarray(losses))
    np.testing.assert_array_equal(np.asarray(frng), np.asarray(rng))
    assert int(fs["enc"]["seen"]) == 4


def test_tbptt_step_hands_on_the_named_carries_with_the_gradient_stopped():
    params, opt, states, batches = _toy()
    key, it = jax.random.PRNGKey(3), jnp.asarray(0, jnp.int32)
    step = jax.jit(_step(carry_names=["enc"]))
    p1, o1, s1, loss1, carries = step(params, opt, states, it, *batches[0],
                                      key, None)
    assert set(carries) == {"enc"}
    np.testing.assert_array_equal(np.asarray(carries["enc"]["h"]),
                                  np.asarray(s1["enc"]["h"]))
    # the next chunk reads them: the same chunk without them scores apart
    with_c = step(p1, o1, s1, it + 1, *batches[1], key, carries)
    p1b, o1b, s1b, _, _ = step(params, opt, states, it, *batches[0], key,
                               None)
    without = step(p1b, o1b, s1b, it + 1, *batches[1], key, None)
    assert abs(float(with_c[3]) - float(without[3])) > 1e-4
    # ... as constants: nothing differentiates back through a carry, while
    # the same rows handed back as persisted state do carry a gradient
    def rows(p, which):
        return jnp.sum(_step(carry_names=["enc"])(
            p, opt, states, it, *batches[0], key)[which]["enc"]["h"])

    through_carry = jax.grad(rows)(params, 4)
    through_state = jax.grad(rows)(params, 2)
    assert float(jnp.abs(through_carry["enc"]["w"]).max()) == 0.0
    assert float(jnp.abs(through_state["enc"]["w"]).max()) > 0.0


class _ProbingCache(dict):
    """As the watchdog's cache does: what is read is not what was put."""

    def __setitem__(self, key, fn):
        calls = self.setdefault("calls", []) if key != "calls" else None
        if calls is None:
            return super().__setitem__(key, fn)

        @functools.wraps(fn)
        def probed(*args):
            calls.append(key)
            return fn(*args)
        super().__setitem__(key, probed)


def test_jit_step_pins_shardings_donates_and_returns_the_caches_entry(
        devices8):
    mesh = make_mesh({"data": 8}, devices=devices8[:8])
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    params, opt, states, batches = _toy(batch=16)
    # the moments of `enc` split over the replicas, all else whole
    opt_sh = _tmap(lambda _: rep, opt)
    opt_sh["enc"]["v"]["w"] = NamedSharding(mesh, P(None, "data"))
    par_sh, st_sh = _tmap(lambda _: rep, params), _tmap(lambda _: rep, states)
    st_sh["enc"]["h"] = rows
    batch_sh = tuple(_tmap(lambda _: rows, x) for x in batches[0])
    unplaced = jax.jit(_step())
    want = unplaced(params, opt, states, jnp.asarray(0, jnp.int32),
                    *batches[0], jax.random.PRNGKey(1))

    cache = _ProbingCache()
    fn = jit_step(_step(), cache=cache, key="k", name="Toy._step",
                  in_shardings=(par_sh, opt_sh, st_sh, rep, *batch_sh, rep),
                  out_shardings=(par_sh, opt_sh, st_sh, rep))
    assert fn is cache["k"]
    placed = jax.device_put((params, opt, states), (par_sh, opt_sh, st_sh))
    args = placed + (jax.device_put(jnp.asarray(0, jnp.int32), rep),) + \
        jax.device_put(batches[0], batch_sh) + \
        (jax.device_put(jax.random.PRNGKey(1), rep),)
    got = fn(*args)
    assert cache["calls"] == ["k"]             # the first dispatch is seen
    for x, y in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-6)
    assert got[1]["enc"]["v"]["w"].sharding.is_equivalent_to(
        opt_sh["enc"]["v"]["w"], 2)
    assert got[0]["enc"]["w"].sharding.is_equivalent_to(rep, 2)
    assert all(x.is_deleted()
               for x in jax.tree_util.tree_leaves(placed[:2]))


def test_stacked_arguments_stay_on_the_host_until_the_caller_places_them():
    host = [({"in": np.full((2, 3), i, np.float32)},
             {"out": np.full((2,), i, np.int32)}, None, None)
            for i in range(3)]
    feats, labs, fms, lms = stack_step_args(host)
    assert isinstance(feats["in"], np.ndarray) and feats["in"].shape == \
        (3, 2, 3) and labs["out"].dtype == np.int32
    assert fms is None and lms is None
    mixed = [(jnp.asarray(b[0]["in"]), b[1]["out"], None, None) for b in host]
    f, l, _, _ = stack_step_args(mixed)
    assert isinstance(f, jax.Array) and isinstance(l, np.ndarray)
    np.testing.assert_array_equal(np.asarray(f), feats["in"])


# ------------------------ (c) the benchmark's contract: a step replaced on
# the class (`benchmarks/tests/test_broken_path.py` does this with exactly
# these two, which tier-1 does not collect)
def _graph_double(self, key, tbptt=False):
    def step(params, opt_state, states, step, *batch_and_rng):
        return params, opt_state, states, jnp.float32(6.9)
    return step


def _multilayer_double(self, key):
    def step(params, opt_state, states, step, *batch_and_rng):
        return params, opt_state, states, jnp.float32(6.9), None
    return step


def _tiny_graph():
    from deeplearning4j_tpu.models import ComputationGraph
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer

    return ComputationGraph(
        NeuralNetConfiguration.builder().seed(0).updater(Nesterovs(0.1))
        .graph_builder().add_inputs("in")
        .add_layer("d1", DenseLayer(n_out=8), "in")
        .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                      loss="mcxent"), "d1")
        .set_outputs("out").set_input_types(InputType.feed_forward(6))
        .build()).init()


def _tiny_multilayer():
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer

    return MultiLayerNetwork(
        NeuralNetConfiguration.builder().seed(0).updater(Adam(1e-2))
        .list(DenseLayer(n_out=8, activation="relu"),
              OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
        .set_input_type(InputType.feed_forward(6)).build()).init()


@pytest.mark.parametrize("build,double", [
    (_tiny_graph, _graph_double), (_tiny_multilayer, _multilayer_double)],
    ids=["graph_four_results", "multilayer_five_results"])
def test_fit_runs_over_a_step_replaced_on_the_class(monkeypatch, build,
                                                    double):
    net = build()
    monkeypatch.setattr(type(net), "_get_train_step", double)
    before = _tmap(np.asarray, (net.params_tree, net.updater_state))
    rng = np.random.default_rng(0)
    x = rng.standard_normal((24, 6)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 24)]
    net.fit(x, y, epochs=1, batch_size=8)
    assert net.iteration == 3
    assert net.score_ == pytest.approx(6.9)
    _assert_trees_equal(_tmap(np.asarray, (net.params_tree,
                                           net.updater_state)), before)
