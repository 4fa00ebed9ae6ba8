"""`ops/row_gather.py` against `jnp.take`, on the CPU in interpret mode:
the rows taken, the rows summed, each kernel the other's transpose, and
that what stands in a row at or past `live` reaches nothing."""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

rg = importlib.import_module("deeplearning4j_tpu.ops.row_gather")

N, K, C, D = 128, 8, 768, 512     # 1,024 places, the first 768 of them rows
TILE = 256                         # `tile_rows(C)`
LIVE = {"none": 0, "one": 1, "a_tile_less_one": TILE - 1, "a_tile": TILE,
        "a_tile_plus_one": TILE + 1, "every_row": C}
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def _pairs(seed):
    """(index [C], back [N, K]): the places in a random order, row r the
    pair of token `index[r]`, so a token stands in `index` K times."""
    order = np.random.default_rng(seed).permutation(N * K)
    back = np.argsort(order).astype(np.int32).reshape(N, K)
    return jnp.asarray(order[:C] // K, jnp.int32), jnp.asarray(back)


def _normal(seed, shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(seed), shape,
                             jnp.float32).astype(dtype)


def _rows(live):
    return (jnp.arange(C) < live)[:, None]


def _f64(a):
    return np.asarray(a.astype(jnp.float32), np.float64)


def _summed(x, back, live):
    """`sum_rows` as XLA makes it: a row of zeros for every place that is
    no row, float32 sums in j's order, rounded once."""
    padded = jnp.concatenate([jnp.where(_rows(live), x, 0),
                              jnp.zeros((1, x.shape[1]), x.dtype)])
    total = jnp.zeros((back.shape[0], x.shape[1]), jnp.float32)
    for j in range(back.shape[1]):
        place = jnp.where(back[:, j] < live, back[:, j], C)
        total = total + jnp.take(padded, place, axis=0).astype(jnp.float32)
    return total.astype(x.dtype)


def test_the_tile_is_the_grouped_products_tile():
    assert rg.tile_rows(C) == TILE


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("live", sorted(LIVE))
def test_take_rows_is_take_over_the_live_rows(live, dtype):
    """`x[index[r]]` to the bit in the rows under `live`, zeros in the
    rest of the last tile that holds one; the tiles past it hold anything
    and are not looked at."""
    live, dtype = LIVE[live], DTYPES[dtype]
    index, back = _pairs(live)
    x = _normal(1, (N, D), dtype)
    got = rg.take_rows(x, index, back, jnp.int32(live), 1, True)
    assert got.shape == (C, D) and got.dtype == dtype
    np.testing.assert_array_equal(_f64(got[:live]),
                                  _f64(jnp.take(x, index[:live], axis=0)))
    upto = -(-live // TILE) * TILE
    assert not _f64(got[live:upto]).any()


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("live", sorted(LIVE))
def test_sum_rows_sums_the_live_places_and_reads_no_other_row(live, dtype):
    """Every row at or past `live` holds NaN: none reaches a sum, and the
    sums are XLA's, float32 in j's order and rounded once."""
    live, dtype = LIVE[live], DTYPES[dtype]
    index, back = _pairs(live + 1)
    x = _normal(2, (C, D), dtype)
    got = rg.sum_rows(jnp.where(_rows(live), x, jnp.nan), None, index, back,
                      jnp.int32(live), True)
    assert got.shape == (N, D) and got.dtype == dtype
    np.testing.assert_array_equal(_f64(got), _f64(_summed(x, back, live)))


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_one_token_in_every_row_is_taken_every_time(dtype):
    dtype = DTYPES[dtype]
    _, back = _pairs(3)
    x = _normal(4, (N, D), dtype)
    index = jnp.full((C,), 77, jnp.int32)
    got = rg.take_rows(x, index, back, jnp.int32(300), 1, True)
    np.testing.assert_array_equal(
        _f64(got[:300]), np.broadcast_to(_f64(x[77]), (300, D)))


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("live", ["a_tile_plus_one", "every_row"])
def test_each_kernel_is_the_others_transpose(live, dtype):
    """`jax.vjp` of `take_rows` is `sum_rows` of the cotangent and the
    other way round, a cotangent's rows at or past `live` NaN: they are
    no row of anything."""
    live, dtype = LIVE[live], DTYPES[dtype]
    index, back = _pairs(5)
    held = jnp.int32(live)
    x, ct = _normal(6, (N, D), dtype), _normal(7, (C, D), dtype)
    ct = jnp.where(_rows(live), ct, jnp.nan)
    _, vjp = jax.vjp(lambda v: rg.take_rows(v, index, back, held, 1, True), x)
    np.testing.assert_array_equal(
        _f64(vjp(ct)[0]), _f64(rg.sum_rows(ct, None, index, back, held, True)))
    _, vjp = jax.vjp(lambda v: rg.sum_rows(v, None, index, back, held, True), ct)
    dx, = vjp(x)
    np.testing.assert_array_equal(
        _f64(dx[:live]),
        _f64(rg.take_rows(x, index, back, held, 1, True)[:live]))
    # and the pairing is the mathematics': <take(x), ct> = <x, sum(ct)>
    taken = rg.take_rows(x, index, back, held, 1, True)
    lhs = np.sum(_f64(taken)[:live] * _f64(ct)[:live])
    rhs = np.sum(_f64(x) * _f64(_summed(jnp.where(_rows(live), ct, 0), back,
                                        live).astype(jnp.float32)))
    if dtype == jnp.float32:
        np.testing.assert_allclose(lhs, rhs, rtol=1e-4)


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("live", ["a_tile_plus_one", "every_row"])
def test_rows_are_weighed_as_they_are_packed(live, dtype):
    """`sum_rows` with a weight a row is `sum_rows` of `x * weight[:,
    None]`, rounded where XLA rounds it; its gradients are the taken
    cotangent times the weight and the row dots, over the live rows."""
    live, dtype = LIVE[live], DTYPES[dtype]
    index, back = _pairs(9)
    held = jnp.int32(live)
    x, ct = _normal(10, (C, D), dtype), _normal(11, (N, D), dtype)
    weight = _normal(12, (C,), dtype)
    x = jnp.where(_rows(live), x, jnp.nan)
    got, vjp = jax.vjp(
        lambda v, w: rg.sum_rows(v, w, index, back, held, True), x, weight)
    want = rg.sum_rows(x * weight[:, None], None, index, back, held, True)
    np.testing.assert_array_equal(_f64(got), _f64(want))
    assert np.isfinite(_f64(got)).all()
    dx, dw = vjp(ct)
    taken = rg.take_rows(ct, index, back, held, 1, True)
    np.testing.assert_array_equal(
        _f64(dx[:live]), _f64((taken * weight[:, None])[:live]))
    # bf16: every product is rounded before the sum, as XLA's are
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        _f64(dw[:live]), np.sum(_f64(x[:live]) * _f64(taken[:live]), axis=1),
        rtol=tol, atol=20 * tol)


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("copies", [2, 3])
def test_the_cotangents_of_the_copies_are_added_as_they_are_packed(
        copies, dtype):
    """`take_rows` handed out `copies` times over is one array; the
    copies' cotangents, NaN at and past `live`, are summed row by row in
    the transposed gather, rounded as XLA rounds their sum."""
    live, dtype = TILE + 1, DTYPES[dtype]
    index, back = _pairs(13)
    held = jnp.int32(live)
    x = _normal(14, (N, D), dtype)
    cts = tuple(jnp.where(_rows(live), _normal(15 + i, (C, D), dtype),
                          jnp.nan) for i in range(copies))
    got, vjp = jax.vjp(
        lambda v: rg.take_rows(v, index, back, held, copies, True), x)
    assert len(got) == copies and all(g is got[0] for g in got)
    np.testing.assert_array_equal(
        _f64(got[0][:live]), _f64(jnp.take(x, index[:live], axis=0)))
    total = cts[0]
    for ct in cts[1:][::-1] if copies == 2 else (cts[1] + cts[2],):
        total = total + ct
    np.testing.assert_array_equal(
        _f64(vjp(cts)[0]),
        _f64(rg.sum_rows(total, None, index, back, held, True)))


@pytest.mark.parametrize("d,dtype,words", [
    (4096, "bfloat16", (16, 128, jnp.uint32)),
    (4096, "float32", (32, 128, jnp.float32)),
    (32, "bfloat16", (1, 16, jnp.uint32)),
    (48, "float32", (1, 48, jnp.float32)),
])
def test_a_row_in_row_form_is_whole_lane_blocks_of_words(d, dtype, words):
    assert rg._form(d, dtype) == words


def test_rows_of_another_dtype_and_odd_bf16_rows_are_refused():
    with pytest.raises(ValueError, match="not gathered"):
        rg._form(64, jnp.int8)
    with pytest.raises(ValueError, match="word pairs"):
        rg._form(63, jnp.bfloat16)


def test_many_calls_trace_one_body_a_shape():
    """Each kernel is under a `jax.jit` of its own: a program with many
    gathers of one shape traces `take_rows`, `sum_rows` and the packing
    of each one's source once."""
    index, back = _pairs(8)
    index, back = index[:96], back[:16, :6]       # shapes no other test has
    held = jnp.int32(50)

    def loss(x):
        for _ in range(4):
            x = rg.sum_rows(rg.take_rows(x, index, back, held, 1, True),
                            None, index, back, held, True)
        return jnp.sum(x)

    text = str(jax.make_jaxpr(jax.grad(loss))(
        jnp.ones((16, 64), jnp.float32)))
    for name in ("_take", "_sum"):      # four calls forward, four backward
        bodies = re.findall(rf"jit\[name={name} jaxpr=(\w+)", text)
        assert len(bodies) == 8 and len(set(bodies)) <= 2, bodies
