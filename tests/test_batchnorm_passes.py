"""`batch_norm_train`: one-pass statistics and a written-out backward pass
give what `jnp.mean` / `jnp.var` under autodiff give (the reference in
`batchnorm_reference.py`), in every rank, flag and dtype the layer takes.
The pass count itself needs the chip's compiler: `test_tpu_compile.py`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batchnorm_reference import two_pass_apply
from deeplearning4j_tpu.nn.layers import BatchNormalization
from deeplearning4j_tpu.nn.layers.normalization import batch_norm_train

SHAPES = {2: (24, 5), 3: (6, 7, 5), 4: (4, 6, 3, 5)}
FLAGS = {"learned": {}, "no_scale": {"scale": False},
         "no_center": {"center": False}, "locked": {"lock_gamma_beta": True}}


def _case(rank, activation, flags, dtype, seed=0):
    """(layer, params, state, x, w): off-centre inputs with a spread of
    their own per channel, parameters and state off their initial values,
    and the weights of the scalar the gradients are taken of."""
    rng = np.random.default_rng(seed)
    shape = SHAPES[rank]
    f = shape[-1]
    layer = BatchNormalization(n_out=f, activation=activation, **flags)
    x = rng.normal(size=shape) * rng.uniform(0.5, 2.0, f) \
        + rng.normal(size=f)
    params = {}
    if not layer.lock_gamma_beta:
        if layer.scale:
            params["gamma"] = jnp.asarray(rng.uniform(0.5, 1.5, f), dtype)
        if layer.center:
            params["beta"] = jnp.asarray(rng.normal(size=f), dtype)
    state = {"mean": jnp.asarray(rng.normal(size=f), dtype),
             "var": jnp.asarray(rng.uniform(0.5, 1.5, f), dtype)}
    return (layer, params, state, jnp.asarray(x, dtype),
            jnp.asarray(rng.normal(size=shape), dtype))


def _outputs(apply, params, state, x, w):
    """(y, new state, gradients of sum(w * y) by params and x)."""
    def scalar(p, x):
        y, new_state = apply(p, x, state)
        return jnp.sum(y * w), (y, new_state)

    (_, (y, new_state)), grads = jax.value_and_grad(
        scalar, argnums=(0, 1), has_aux=True)(params, x)
    return y, new_state, grads


def _both(rank, activation, flags, dtype):
    layer, params, state, x, w = _case(rank, activation, FLAGS[flags], dtype)
    new = _outputs(lambda p, x, s: layer.apply(p, x, state=s, train=True),
                   params, state, x, w)
    ref = _outputs(lambda p, x, s: two_pass_apply(layer, p, x, s),
                   params, state, x, w)
    return new, ref


@pytest.mark.parametrize("dtype,tol", [("float64", 1e-9), ("float32", 1e-5)])
@pytest.mark.parametrize("flags", sorted(FLAGS))
@pytest.mark.parametrize("activation", ["identity", "relu"])
@pytest.mark.parametrize("rank", sorted(SHAPES))
def test_same_function_as_two_pass_autodiff(rank, activation, flags, dtype,
                                            tol):
    new, ref = _both(rank, activation, flags, jnp.dtype(dtype))
    assert (jax.tree_util.tree_structure(new)
            == jax.tree_util.tree_structure(ref))
    for got, want in zip(jax.tree_util.tree_leaves(new),
                         jax.tree_util.tree_leaves(ref)):
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@pytest.mark.parametrize("activation", ["identity", "relu"])
@pytest.mark.parametrize("rank", sorted(SHAPES))
def test_bfloat16_no_further_from_float32_than_two_pass(rank, activation):
    """The old form rounds the mean and the centred values to bfloat16;
    the new one rounds once, at `y`."""
    exact = _both(rank, activation, "learned", jnp.float32)[1]
    new, old = _both(rank, activation, "learned", jnp.bfloat16)

    def distance(outs):
        # the float32 case's inputs, rounded to bfloat16, are this case's
        return [float(jnp.max(jnp.abs(a.astype(jnp.float32) - b)))
                for a, b in zip(jax.tree_util.tree_leaves(outs),
                                jax.tree_util.tree_leaves(exact))]

    for d_new, d_old, leaf in zip(distance(new), distance(old),
                                  jax.tree_util.tree_leaves(new)):
        assert leaf.dtype == jnp.bfloat16
        assert d_new <= d_old + 2.0 ** -8, (d_new, d_old)
    assert sum(distance(new)) <= sum(distance(old))


@pytest.mark.parametrize("mean_over_std", [0.0, 1.0, 1e1, 1e2, 1e3])
@pytest.mark.parametrize("shape", [(512, 8), (16, 12, 12, 8)])
def test_one_pass_variance_off_centre(shape, mean_over_std):
    """`E[x^2] - E[x]^2` in float32 loses `2^-24 * (1 + mean^2 / var)` of
    the variance for each rounding of the sums, which on the CPU grow with
    the square root of the count. At `mean = 1e3 * std` that is all of it
    (`2^-24 * 1e6` is 6% a rounding): there the clamp keeps the variance at
    0 or above and `y` finite, which is what one pass can promise."""
    rng = np.random.default_rng(3)
    f = shape[-1]
    std = rng.uniform(0.5, 2.0, f)
    x = jnp.asarray(rng.normal(size=shape) * std + mean_over_std * std,
                    jnp.float32)
    y, mean, var = batch_norm_train(x, jnp.ones((f,), x.dtype),
                                    jnp.zeros((f,), x.dtype), 1e-5)
    x64 = np.asarray(x, np.float64).reshape(-1, f)
    lost = 4 * np.sqrt(len(x64)) * 2.0 ** -24 * (1 + mean_over_std ** 2)
    assert np.all(np.asarray(var) >= 0) and np.all(np.isfinite(y))
    np.testing.assert_allclose(mean, x64.mean(axis=0), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(var, x64.var(axis=0), rtol=lost)
