"""`ops/grouped_matmul.py` against `jax.lax.ragged_dot`, on the CPU in
interpret mode: the product, both gradients, the schedule's length, and
that what stands in a row of no group reaches nothing."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

gm = importlib.import_module("deeplearning4j_tpu.ops.grouped_matmul")

ROWS = 16     # a tile's rows in these cases

# name -> (M, group sizes)
CASES = {
    "every_group_full": (64, [16, 16, 16, 16]),
    "all_rows_in_groups_off_the_tiles": (64, [5, 40, 3, 16]),
    "no_rows_first": (64, [0, 24, 24, 16]),
    "no_rows_in_the_middle": (64, [24, 0, 0, 30]),
    "no_rows_last": (64, [24, 24, 10, 0]),
    # group 0 ends inside tile 0; group 1 (rows 5 to 45) spans three tiles
    "ends_inside_a_tile_and_spans_three": (64, [5, 40, 3, 2]),
    "no_pair_at_all": (64, [0, 0, 0, 0]),
    "one_row": (64, [0, 1, 0, 0]),
    "one_row_in_the_last_group": (64, [0, 0, 0, 1]),
    "rows_that_do_not_fill_the_last_tile": (40, [7, 9, 20]),
    "one_group": (48, [33]),
}


def _visits(m, sizes, rows):
    """Sum over the groups of the tiles a group touches, by hand."""
    end = np.cumsum(sizes)
    return sum(int((e - 1) // rows - (e - s) // rows + 1)
               for s, e in zip(sizes, end) if s)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_grouped_dot_is_ragged_dot_over_the_rows_in_a_group(case, dtype):
    """Output, `d_lhs` and `d_rhs`; the rows in no group hold NaN in `lhs`
    and in the cotangent, which reaches no live row and no entry of
    `d_rhs`; a group of no rows gets zeros; and the schedule is as long as
    the counter's arithmetic says."""
    m, sizes = CASES[case]
    k, n, g = 32, 48, len(sizes)
    live = (np.arange(m) < sum(sizes))[:, None]
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    lhs = jax.random.normal(keys[0], (m, k), jnp.float32).astype(dtype)
    rhs = jax.random.normal(keys[1], (g, k, n), jnp.float32).astype(dtype)
    ct = jax.random.normal(keys[2], (m, n), jnp.float32).astype(dtype)
    group_sizes = jnp.asarray(sizes, jnp.int32)

    out, vjp = jax.vjp(
        lambda a, w: gm.grouped_dot(a, w, group_sizes, ROWS, True),
        jnp.where(live, lhs, jnp.nan), rhs)
    d_lhs, d_rhs = vjp(jnp.where(live, ct, jnp.nan))
    want, vjp = jax.vjp(
        lambda a, w: jax.lax.ragged_dot(a, w, group_sizes),
        jnp.where(live, lhs, 0), rhs)
    want_lhs, want_rhs = vjp(jnp.where(live, ct, 0))

    f64 = lambda a: np.asarray(a.astype(jnp.float32), np.float64)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for got, ref in ((out, want), (d_lhs, want_lhs)):
        assert got.dtype == dtype and got.shape == ref.shape
        got, ref = f64(got)[live[:, 0]], f64(ref)[live[:, 0]]
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, ref, rtol=tol, atol=tol)
    assert d_rhs.dtype == dtype and d_rhs.shape == rhs.shape
    assert np.isfinite(f64(d_rhs)).all()
    np.testing.assert_allclose(f64(d_rhs), f64(want_rhs), rtol=tol,
                               atol=8 * tol)
    for group, size in enumerate(sizes):
        if not size:
            assert not f64(d_rhs)[group].any()

    plan = gm.schedule(group_sizes, m, ROWS)
    visits = _visits(m, sizes, ROWS)
    assert int(plan.visits[0]) == visits <= -(-sum(sizes) // ROWS) + g - 1
    assert plan.tile.shape == (-(-m // ROWS) + g - 1,)
    assert int(gm.rows_visited(plan)) == visits * ROWS
    # the visits name a group's tiles in turn; after them a step for each
    # group of no rows, which `d_rhs` spends on that group's zeros
    tile, group = np.asarray(plan.tile), np.asarray(plan.group)
    start = np.cumsum(sizes) - sizes
    for v in range(visits):
        lo, hi = tile[v] * ROWS, (tile[v] + 1) * ROWS
        assert start[group[v]] < hi and start[group[v]] + sizes[group[v]] > lo
    no_rows = [i for i, size in enumerate(sizes) if not size]
    assert int(plan.empty) == len(no_rows)
    assert list(group[visits:visits + len(no_rows)]) == no_rows
    assert (tile[visits:] == (tile[visits - 1] if visits else 0)).all()


def test_a_tile_is_picked_from_the_shapes():
    """256 rows, and no more than the rows there are."""
    assert gm.tile_rows(73728) == 256       # granite_4_0_h_small's tier
    assert gm.tile_rows(9856) == 256        # deepseek_v2's first
    assert gm.tile_rows(4096) == 256        # trinity_large's first
    assert gm.tile_rows(40) == 48
    m, sizes = 40, jnp.asarray([7, 9, 20], jnp.int32)
    lhs, = (jax.random.normal(jax.random.PRNGKey(0), (m, 16)),)
    rhs = jax.random.normal(jax.random.PRNGKey(1), (3, 16, 8))
    got = gm.grouped_dot(lhs, rhs, sizes, None, True)
    np.testing.assert_allclose(got[:36], jax.lax.ragged_dot(
        lhs, rhs, sizes)[:36], rtol=1e-5, atol=1e-5)


def test_many_calls_trace_one_body_a_shape_and_share_a_schedule(monkeypatch):
    """Each kernel is under a `jax.jit` of its own: a program with many
    grouped products of one shape traces the product, `d_lhs` and `d_rhs`
    once each; and products given one `Schedule` in place of their group
    sizes make none of their own."""
    made = []
    schedule = gm.schedule
    monkeypatch.setattr(gm, "schedule",
                        lambda *a: made.append(a[1:]) or schedule(*a))
    sizes = jnp.asarray([10, 0, 21], jnp.int32)    # shapes no other test has
    rhs = jax.random.normal(jax.random.PRNGKey(1), (3, 24, 24))
    x = jnp.ones((32, 24))
    live = (jnp.arange(32) < 31)[:, None]

    def loss(x, rhs, groups):
        for _ in range(5):
            x = gm.grouped_dot(x, rhs, groups, 8, True)
        return jnp.sum(jnp.where(live, x, 0))

    grad = jax.grad(loss, (0, 1))
    text = str(jax.make_jaxpr(lambda x, rhs: grad(x, rhs, sizes))(x, rhs))
    assert made == [(32, 8)] * 5
    assert text.count("pallas_call") == 3
    assert (text.count("jit[name=_dot ") == 10
            and text.count("jit[name=grouped_dot_drhs ") == 5)
    del made[:]
    shared = jax.grad(lambda x, rhs: loss(x, rhs, gm.schedule(sizes, 32, 8)),
                      (0, 1))(x, rhs)
    assert made == [(32, 8)]
    want = grad(x, rhs, sizes)
    np.testing.assert_array_equal(jnp.where(live, shared[0], 0),
                                  jnp.where(live, want[0], 0))
    np.testing.assert_array_equal(shared[1], want[1])
