"""What the runner's set-up holds on the device, of the model's size:
never more than the step's own arguments, the parameters and the
optimizer's state. The reference's float32 tree is gone before the net is
built, the net's `init()` makes no parameters of its own, and the copy of
the weights as placed that `delta_norm` is read against lives on the host.
Read on `tokens_tiny` (float32, Adam: p, m and v, three arrays of every
leaf's shape) from `jax.live_arrays()`: once the net's own `init()` has
run, from inside the first warm-up step's listener call, and after
`prepare()` has returned.
"""

import collections
import gc

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import harness
from benchmarks.tests.helpers import tiny_cell

follow = harness.load_module("reference", "follow.py")


def test_prepare_leaves_p_m_v_and_no_copy(monkeypatch):
    runner = harness.load_module("runners", "fit.py")
    cell = tiny_cell(1, "fit_stream", "tokens_tiny")
    config = cell["config_data"]
    ref = harness.load_module("reference", config["reference"] + ".py")
    # the leaves larger than a gradient sample: no sample has their shape
    large = collections.Counter(
        s.shape for s in jax.tree_util.tree_leaves(
            jax.eval_shape(lambda: ref.init_params(3, config)))
        if np.prod(s.shape) > follow.SAMPLE)
    assert large

    def alive():
        gc.collect()
        return collections.Counter(
            a.shape for a in jax.live_arrays() if a.shape in large)

    before, after_init, at_first_step = alive(), [], []
    heard = runner.FirstSteps.iteration_done
    init = harness.init_in_one_program

    def init_and_look(net):
        shapes = init(net)
        after_init.append(alive())
        return shapes

    def listen(self, model, iteration, epoch, score):
        heard(self, model, iteration, epoch, score)
        if len(self.losses) == 1:
            at_first_step.append(alive())

    monkeypatch.setattr(runner.FirstSteps, "iteration_done", listen)
    monkeypatch.setattr(harness, "init_in_one_program", init_and_look)
    ready = runner.prepare(cell, 3, jax.devices()[:1])
    p_m_v = collections.Counter({shape: 3 * n for shape, n in large.items()})
    # m and v, and neither the reference's float32 tree nor parameters
    assert after_init == [before + large + large]
    assert at_first_step == [before + p_m_v]
    assert alive() == before + p_m_v
    # and the pool is the host's: the batches reach the device in fit()
    assert all(isinstance(a, np.ndarray) for xy in ready["pool"] for a in xy)
    assert len(ready["program"]["delta_norm"]) == len(
        jax.tree_util.tree_leaves(ready["net"].params_tree))


def test_host_rounding_is_the_devices():
    """`_place_weights` rounds on the host what it used to round in a
    jitted cast: the same bits, ties and all."""
    rng = np.random.default_rng(5)
    x = rng.standard_normal(1 << 16).astype(np.float32)
    # exact ties between two neighbouring bfloat16 values, both parities
    ties = (np.arange(1, 1 << 12, dtype=np.uint32) << 16 | 0x3F808000)
    x = np.concatenate([x, ties.view(np.float32), -ties.view(np.float32)])
    on_device = np.asarray(jax.jit(lambda a: a.astype(jnp.bfloat16))(x))
    assert np.array_equal(x.astype(jnp.bfloat16).view(np.uint16),
                          on_device.view(np.uint16))
