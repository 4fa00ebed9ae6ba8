"""`ops/embedding.py`: the lookup's gradient as a grouped product over the
ids sorted by vocabulary tile, against `jax.grad` of plain `jnp.take`. On
the CPU the kernel runs in the TPU interpret mode, whose buffers start as
NaN: a row of the table's gradient that nothing wrote would show."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu import InputType
from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers.feedforward import EmbeddingSequenceLayer
from deeplearning4j_tpu.nn.layers.normalization import RMSNormalization
from deeplearning4j_tpu.nn.layers.recurrent import RnnOutputLayer
from deeplearning4j_tpu.observe import get_registry
from deeplearning4j_tpu.ops import kernel_defaults as kd
from deeplearning4j_tpu.optim.updaters import Adam

em = importlib.import_module("deeplearning4j_tpu.ops.embedding")
gm = importlib.import_module("deeplearning4j_tpu.ops.grouped_matmul")

POISONED = pltpu.InterpretParams(uninitialized_memory="nan")


@pytest.fixture
def grouped(monkeypatch):
    """The backward a TPU device traces, here: the predicate says the
    kernels run and the kernel is interpreted over poisoned buffers."""
    monkeypatch.setattr(em, "kernels_run", lambda: True)
    monkeypatch.setattr(gm, "grouped_dot_drhs", functools.partial(
        gm.grouped_dot_drhs, interpret=POISONED))


def _dispatched(impl):
    return get_registry().counter("kernel_dispatch_total",
                                  op="embedding_backward", impl=impl).value


def _zipf(rng, shape, vocab):
    return np.minimum(rng.zipf(1.2, shape) - 1, vocab - 1)


# name -> (V, D, ids' shape, how the ids are drawn)
CASES = {
    "trinity_rows_off_the_tile": (
        25_024, 128, (2, 200), lambda rng, s, v: rng.integers(0, v, s)),
    "minicpm_rows_off_the_tile": (
        18_362, 128, (1, 300), lambda rng, s, v: rng.integers(0, v, s)),
    "tokens_off_the_row_tile": (
        1_000, 256, (3, 37), lambda rng, s, v: rng.integers(0, v, s)),
    "every_id_the_same_row": (
        1_000, 128, (2, 150), lambda rng, s, v: np.full(s, 617)),
    "zipfian_ids": (5_000, 128, (2, 256), _zipf),
    # ids in the first and the last tile alone: the tiles between get zeros
    "tiles_no_id_falls_in": (
        1_500, 128, (2, 64),
        lambda rng, s, v: rng.choice(np.r_[0:100, 1450:1500], s)),
    "negative_and_out_of_range_ids": (
        1_000, 128, (2, 64),
        lambda rng, s, v: rng.choice(
            np.r_[-1, -1000, -1001, -5000, 1000, 70_000, 0:1000], s)),
}


def _case(name, dtype):
    v, d, shape, draw = CASES[name]
    rng = np.random.default_rng(len(name))
    ids = jnp.asarray(draw(rng, shape, v), jnp.int32)
    table = jnp.asarray(rng.standard_normal((v, d)), dtype)
    ct = jnp.asarray(rng.standard_normal(shape + (d,)), dtype)
    return table, ids, ct


def _same_sums(got, want, ids, ct, table):
    """To 1e-6 of what a row's sum adds up in absolute value: the two
    forms add a row's terms in different orders."""
    mass, = jax.vjp(lambda w: jnp.take(w, ids, axis=0), table)[1](
        jnp.abs(ct))
    assert (np.abs(np.asarray(got) - np.asarray(want))
            <= 1e-6 * np.asarray(mass) + 1e-30).all()


@pytest.mark.parametrize("case", sorted(CASES))
def test_float32_gradient_is_jnp_takes(case, grouped):
    table, ids, ct = _case(case, jnp.float32)
    before = _dispatched("grouped")
    out, vjp = jax.vjp(lambda w: em.lookup(w, ids), table)
    got, = vjp(ct)
    want_out, vjp = jax.vjp(lambda w: jnp.take(w, ids, axis=0), table)
    want, = vjp(ct)
    assert _dispatched("grouped") == before + 1
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want_out))
    assert got.shape == table.shape and got.dtype == table.dtype
    assert np.isfinite(np.asarray(got)).all()
    _same_sums(got, want, ids, ct, table)
    # a row no id names is zeros, written and not left: the buffers began
    # as NaN
    named = np.unique(np.where(np.asarray(ids) < 0,
                               np.asarray(ids) + table.shape[0], ids))
    unnamed = np.setdiff1d(np.arange(table.shape[0]), named)
    assert not np.asarray(got)[unnamed].any()


@pytest.mark.parametrize("case", sorted(CASES))
def test_bfloat16_gradient_is_the_float32_sum_rounded_once(case, grouped):
    """Where the scatter adds bf16 to bf16 row after row: with every id
    the same row it is the one that strays."""
    table, ids, ct = _case(case, jnp.bfloat16)
    got, = jax.vjp(lambda w: em.lookup(w, ids), table)[1](ct)
    f32 = lambda a: np.asarray(a.astype(jnp.float32), np.float64)
    summed, = jax.vjp(lambda w: jnp.take(w, ids, axis=0),
                      table.astype(jnp.float32))[1](ct.astype(jnp.float32))
    assert got.dtype == jnp.bfloat16
    # one rounding to 8 bits of a float32 sum whose own order differs
    np.testing.assert_allclose(f32(got), f32(summed), rtol=2.0 ** -8,
                               atol=1e-5)
    if case == "every_id_the_same_row":
        scattered, = jax.vjp(lambda w: jnp.take(w, ids, axis=0), table)[1](ct)
        err = lambda a: np.abs(f32(a) - f32(summed)).max()
        assert err(got) < err(scattered)


@pytest.mark.parametrize("tile", [128, 256, 512])
def test_any_vocabulary_tile_gives_the_same_sums(tile, grouped):
    table, ids, ct = _case("zipfian_ids", jnp.float32)
    got = em.table_gradient(ids, ct, table.shape[0], tile)
    want, = jax.vjp(lambda w: jnp.take(w, ids, axis=0), table)[1](ct)
    _same_sums(got, want, ids, ct, table)


@pytest.mark.parametrize("ids_shape", [(3, 20), (3, 20, 1)],
                         ids=["ids_B_T", "ids_B_T_1"])
@pytest.mark.parametrize("scale", [None, 11.3], ids=["plain", "scaled"])
def test_the_layer_differentiates_through_the_lookup(ids_shape, scale,
                                                     grouped):
    layer = EmbeddingSequenceLayer(n_in=300, n_out=128, scale=scale,
                                   activation="identity")
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.standard_normal((300, 128)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 300, ids_shape), jnp.int32)
    ct = jnp.asarray(rng.standard_normal((3, 20, 128)), jnp.float32)
    out, vjp = jax.vjp(lambda w: layer.apply({"W": w}, ids)[0], w)
    flat = ids.reshape(3, 20)
    want_out, want_vjp = jax.vjp(
        lambda w: jnp.take(w, flat, axis=0) * (scale or 1.0), w)
    assert out.shape == (3, 20, 128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(vjp(ct)[0]),
                               np.asarray(want_vjp(ct)[0]),
                               rtol=1e-5, atol=1e-5)


def _tied_net():
    from deeplearning4j_tpu.models import MultiLayerNetwork

    return MultiLayerNetwork(
        NeuralNetConfiguration.builder().seed(1).updater(Adam(1e-2))
        .activation("identity").weight_init("xavier").list(
            EmbeddingSequenceLayer(n_in=40, n_out=128, activation="identity",
                                   scale=3.0),
            RMSNormalization(),
            RnnOutputLayer(n_out=40, has_bias=False, activation="softmax",
                           loss="sparse_mcxent", tied_to=0))
        .set_input_type(InputType.recurrent(1, 6)).build())


def test_a_tied_heads_gradient_adds_to_the_grouped_one(grouped):
    """One leaf read as rows and as the head: the two gradients add, the
    rows' by the grouped product, and it is the sum the scatter gave."""
    net = _tied_net().init()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 40, (4, 6)), jnp.int32)
    y = jnp.asarray(rng.integers(0, 40, (4, 6)), jnp.int32)
    loss = lambda p: net._loss(p, net.state_tree, x, y, None, None, None,
                               train=True)[0]
    name = "layer0_embeddingsequencelayer"
    before = _dispatched("grouped")
    got = jax.grad(loss)(net.params_tree)[name]["W"]
    assert _dispatched("grouped") == before + 1
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(em, "kernels_run", lambda: False)
        want = jax.grad(loss)(net.params_tree)[name]["W"]
    # both uses reach it: the rows no id names still hear from the head
    unnamed = np.setdiff1d(np.arange(40), np.asarray(x))
    assert unnamed.size and np.asarray(got)[unnamed].any()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("where", ["cpu", "tpu_under_a_mesh_context",
                                   "tpu_rows_off_the_lanes"])
def test_elsewhere_the_backward_is_xlas_scatter(where, monkeypatch):
    """The CPU, a mesh context (the table may be sharded over the
    vocabulary) and a row that is not whole lanes trace `jnp.take` and its
    scatter-add, no kernel; `record_dispatch` says which form a trace got."""
    from deeplearning4j_tpu.parallel.mesh import (
        MeshContext, make_mesh, use_mesh_context,
    )

    monkeypatch.setattr(gm, "grouped_dot_drhs", lambda *a, **kw: 1 / 0)
    width = 128
    if where != "cpu":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if where == "tpu_rows_off_the_lanes":
        width = 96
        assert kd.kernels_run()
    table = jnp.ones((300, width))
    ids = jnp.asarray([[1, 5, 5, 299]], jnp.int32)
    grad = jax.grad(lambda w: jnp.sum(em.lookup(w, ids) ** 2))
    before = _dispatched("scatter"), _dispatched("grouped")
    if where == "tpu_under_a_mesh_context":
        with use_mesh_context(MeshContext(make_mesh({"data": -1}))):
            assert not kd.kernels_run()
            text = str(jax.make_jaxpr(grad)(table))
    else:
        text = str(jax.make_jaxpr(grad)(table))
    assert (_dispatched("scatter"), _dispatched("grouped")) == (
        before[0] + 1, before[1])
    assert "scatter-add" in text and "pallas_call" not in text
    if where == "cpu":
        assert not kd.kernels_run()


def test_the_grouped_form_traces_no_scatter(grouped):
    table = jnp.ones((300, 128))
    ids = jnp.asarray([[1, 5, 5, 299]], jnp.int32)
    text = str(jax.make_jaxpr(jax.grad(
        lambda w: jnp.sum(em.lookup(w, ids) ** 2)))(table))
    assert "scatter" not in text and "pallas_call" in text
    assert "name=grouped_dot_drhs" in text


def test_the_expert_layers_ask_the_same_predicate():
    """One definition of where the one-device kernels run."""
    moe = importlib.import_module("deeplearning4j_tpu.parallel.moe")
    assert moe._kernel_runs is kd.kernels_run is em.kernels_run
