"""The main path's Pallas kernels, compiled at real widths for a TPU v5e
that is described and not attached.

Interpret mode (every other kernel test here) never meets Mosaic: a slice
off the tiling, a 16-bit matmul accumulator or too much VMEM only shows
when the chip's compiler sees the kernel. The compiler is installed in the
sandbox and compiles for a described topology, so these cases guard every
later PR at no chip time. Nothing runs: a pass says the kernel compiles,
not that it is right or fast.

The chip runs with x64 off and refuses every one of these kernels with it
on, so the module turns off what `conftest.py` turned on; it also turns the
persistent compile cache off, because an entry compiled for a described
chip cannot be read back without one.
"""

import importlib
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

# by module path: `ops/__init__` re-exports functions under these names
lstm = importlib.import_module("deeplearning4j_tpu.ops.lstm")
flash = importlib.import_module("deeplearning4j_tpu.ops.attention")
banded = importlib.import_module("deeplearning4j_tpu.ops.banded_attention")
sparse = importlib.import_module("deeplearning4j_tpu.ops.sparse_attention")
latent = importlib.import_module("deeplearning4j_tpu.ops.latent_attention")
grouped = importlib.import_module("deeplearning4j_tpu.ops.grouped_matmul")
gather = importlib.import_module("deeplearning4j_tpu.ops.row_gather")

BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def chip():
    """Sharding on one described v5e device; x64 and the persistent
    compile cache are off while the module runs."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with jax.enable_x64(False):
            yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()


# --- cases: name -> (function, [(shape, dtype), ...]) at the widths the
# chip smoke and the policies use
def _lstm(dt, train, T=128, B=64, H=512):
    shapes = [((T, B, 4 * H), dt), ((H, 4 * H), dt), ((3, H), dt),
              ((B, H), dt), ((B, H), dt), ((T, B), dt)]

    def fwd(xw, rw, p, h0, c0, m):
        return lstm.fused_lstm(xw, rw, p, h0, c0, m, False)

    def loss(*args):
        return sum(o.astype(F32).sum() for o in fwd(*args))

    return (jax.grad(loss, argnums=(0, 1, 2, 3, 4)) if train else fwd), shapes


def _flash(train, T=8192, heads=8, d=64, block=512, kv_heads=None):
    # T 8192 is where the policy routes to flash for memory, with the
    # Pallas backward (ops/kernel_defaults.attention_policy); with
    # `kv_heads` the kernels read K/V by `h // G` in place
    shapes = [((1, T, heads, d), BF16)] \
        + [((1, T, kv_heads or heads, d), BF16)] * 2

    def fwd(q, k, v):
        return flash.flash_attention(q, k, v, True, None, block, block,
                                     False, "pallas")

    def loss(q, k, v):
        return fwd(q, k, v).astype(F32).sum()

    return (jax.grad(loss, argnums=(0, 1, 2)) if train else fwd), shapes


def _banded(T=2048, heads=8, kv_heads=2, d=64, window=512, train=False,
            batch=2, dt=BF16):
    def fwd(q, k, v):
        return banded.banded_attention(q, k, v, window, True, None, 256,
                                       256, False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(F32).sum()

    return (jax.grad(loss, argnums=(0, 1, 2)) if train else fwd), [
        ((batch, T, heads, d), dt), ((batch, T, kv_heads, d), dt),
        ((batch, T, kv_heads, d), dt)]


def _decode(paged, cache_dtype, slots=8, cache=1024, page=128, heads=8,
            kv_heads=2, d=64):
    quant = cache_dtype == I8
    rows = (slots * cache // page, page) if paged else (slots, cache)
    shapes = [((slots, heads, d), BF16),
              (rows + (kv_heads, d), cache_dtype),
              (rows + (kv_heads, d), cache_dtype)]
    shapes += ([((slots, cache // page), I32), ((slots,), I32)] if paged
               else [((slots,), I32), ((slots,), I32)])
    if quant:
        shapes += [(rows + (kv_heads,), F32)] * 2

    def fwd(q, ck, cv, a, b, *scales):
        kw = (dict(scale_k=scales[0], scale_v=scales[1]) if quant else {})
        if paged:
            return banded.paged_decode_attention(q, ck, cv, a, b,
                                                 interpret=False, **kw)
        return banded.banded_decode_attention(q, ck, cv, a, b,
                                              interpret=False, **kw)

    return fwd, shapes


def _sparse(train, T=16384, heads=32, kv_heads=2, d=128):
    # `minicpm_sala`'s selecting layer at the cell's size: selection and
    # the three block-sparse kernels, each at the Q tile it picks for
    # itself from the policy's 256 over K tiles of 512
    sel = sparse.BlockSelection()

    def fwd(q, k, v):
        allow = sparse.select_blocks(q, k, sel)
        return sparse.block_sparse_attention(q, k, v, allow, sel.block_size,
                                             None, 256, 512, False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(F32).sum()

    return (jax.grad(loss, argnums=(0, 1, 2)) if train else fwd), [
        ((1, T, heads, d), BF16), ((1, T, kv_heads, d), BF16),
        ((1, T, kv_heads, d), BF16)]


def _latent(train, T=8192, heads=32, nope=128, rope=64, value=128):
    # `deepseek_v2`'s attention core at the cell's size: 32 heads held, a
    # query of 192 lanes against values of 128, one rope key for all
    # heads; each kernel at the tile it picks for itself from the policy's
    # 512 x 512
    def fwd(q, k_nope, k_rope, v):
        return latent.latent_attention(q, k_nope, k_rope, v, 0.114721, 512,
                                       512, False)

    def loss(*args):
        return fwd(*args).astype(F32).sum()

    return (jax.grad(loss, argnums=(0, 1, 2, 3)) if train else fwd), [
        ((1, T, heads, nope + rope), BF16), ((1, T, heads, nope), BF16),
        ((1, T, rope), BF16), ((1, T, heads, value), BF16)]


def _grouped(m, k, n, groups, train=True):
    # an expert layer's grouped product at a cell's widths, each kernel at
    # the tile and the column blocks it picks for itself
    def fwd(lhs, rhs, sizes):
        return grouped.grouped_dot(lhs, rhs, sizes)

    def loss(lhs, rhs, sizes):
        return fwd(lhs, rhs, sizes).astype(F32).sum()

    return (jax.grad(loss, argnums=(0, 1)) if train else fwd), [
        ((m, k), BF16), ((groups, k, n), BF16), ((groups,), I32)]


def _row_gathers(n, k, c, d, dt=BF16):
    # an expert layer's dispatch and combine at a cell's widths, forward
    # and as each other's transpose: the packing of both sources (the
    # second with two cotangents to add), the row copies, the weights
    def loss(x, weight, index, back, live):
        taken = gather.take_rows(x, index, back, live, 2)
        return gather.sum_rows(taken[0] + taken[1], weight, index, back,
                               live).astype(F32).sum()

    return jax.grad(loss, argnums=(0, 1)), [
        ((n, d), dt), ((c,), dt), ((c,), I32), ((n, k), I32), ((), I32)]


CASES = {
    # the dispatch and combine over the one tier of `granite_4_0_h_small`,
    # `trinity_large` and `deepseek_v2`
    "row_gathers_train_8192_10_73728_4096": lambda: _row_gathers(
        8192, 10, 73728, 4096),
    "row_gathers_train_8192_4_32768_3072": lambda: _row_gathers(
        8192, 4, 32768, 3072),
    "row_gathers_train_8192_6_49152_5120": lambda: _row_gathers(
        8192, 6, 49152, 5120),
    "row_gathers_train_f32": lambda: _row_gathers(1024, 4, 2048, 512, F32),
    # `granite_4_0_h_small`'s one tier, both of an expert's widths;
    # `trinity_large`'s and `deepseek_v2`'s, and a tier of an eighth and a
    # fifth of their rows
    "grouped_dot_train_73728_4096_768": lambda: _grouped(73728, 4096, 768, 9),
    "grouped_dot_train_73728_768_4096": lambda: _grouped(73728, 768, 4096, 9),
    "grouped_dot_train_32768_3072_3072": lambda: _grouped(32768, 3072, 3072,
                                                          8),
    "grouped_dot_train_49152_5120_1536": lambda: _grouped(49152, 5120, 1536,
                                                          8),
    "grouped_dot_train_49152_1536_5120": lambda: _grouped(49152, 1536, 5120,
                                                          8),
    "grouped_dot_train_4096_3072_3072": lambda: _grouped(4096, 3072, 3072, 8),
    "grouped_dot_train_9856_5120_1536": lambda: _grouped(9856, 5120, 1536, 8),
    "grouped_dot_train_9856_1536_5120": lambda: _grouped(9856, 1536, 5120, 8),
    "grouped_dot_fwd_f32": lambda: (
        lambda lhs, rhs, sizes: grouped.grouped_dot(lhs, rhs, sizes),
        [((4096, 1024), F32), ((8, 1024, 512), F32), ((8,), I32)]),
    "latent_fwd_32": lambda: _latent(train=False),
    "latent_train_32": lambda: _latent(train=True),
    "sparse_fwd_32_2": lambda: _sparse(train=False),
    "sparse_train_32_2": lambda: _sparse(train=True),
    "lstm_fwd_f32": lambda: _lstm(F32, train=False),
    "lstm_fwd_bf16": lambda: _lstm(BF16, train=False),
    "lstm_train_f32": lambda: _lstm(F32, train=True),
    "lstm_train_bf16": lambda: _lstm(BF16, train=True),
    "flash_fwd": lambda: _flash(train=False),
    "flash_pallas_bwd": lambda: _flash(train=True),
    # 48 query heads of 128 over 8 KV heads at 8,192 tokens, window 4,096:
    # the shapes a six-wide group folds into the backward's tiles, each
    # backward kernel at the tile it picks for itself
    # (`ops/attention._pick_tile`)
    "flash_pallas_bwd_gqa": lambda: _flash(train=True, heads=48, d=128,
                                           kv_heads=8),
    "banded_fwd_gqa": _banded,
    "banded_train_gqa": lambda: _banded(train=True),
    "banded_train_gqa_48_8": lambda: _banded(
        T=8192, heads=48, kv_heads=8, d=128, window=4096, train=True,
        batch=1),
    # `trinity_large_fit`'s two forward kernels alone, at the tile each
    # picks for itself from the policies' blocks (`ops/attention._pick_tile`)
    "banded_fwd_gqa_48_8": lambda: _banded(
        T=8192, heads=48, kv_heads=8, d=128, window=4096, batch=1),
    "flash_fwd_gqa_48_8": lambda: _flash(train=False, heads=48, d=128,
                                         kv_heads=8),
    # float32 operands at the same tile pass the default scoped VMEM
    "banded_fwd_gqa_48_8_f32": lambda: _banded(
        T=8192, heads=48, kv_heads=8, d=128, window=4096, batch=1, dt=F32),
    "banded_train_gqa_48_8_f32": lambda: _banded(
        T=8192, heads=48, kv_heads=8, d=128, window=4096, train=True,
        batch=1, dt=F32),
    "slot_decode_bf16": lambda: _decode(False, BF16),
    "slot_decode_int8": lambda: _decode(False, I8),
    "paged_decode_bf16": lambda: _decode(True, BF16),
    "paged_decode_int8": lambda: _decode(True, I8),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(chip, name):
    fn, shapes = CASES[name]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# --- the tile a forward kernel picked is published at trace time: the
# gauge says which tile a cell's program really runs (`_pick_tile`; the
# cells' shapes, where the kernels alone were timed over the candidates)
FORWARD_TILES = {
    "banded_fwd_gqa_48_8": ("banded_attention", 6 * 256, 512),
    "flash_fwd_gqa_48_8": ("flash_attention", 1024, 512),
    "sparse_fwd_32_2": ("sparse_attention", 1024, 512),
    "latent_fwd_32": ("latent_attention", 1024, 512),
}


@pytest.mark.parametrize("name", sorted(FORWARD_TILES))
def test_forward_kernel_publishes_its_tile(name):
    from deeplearning4j_tpu.observe import get_registry

    op, rows, keys = FORWARD_TILES[name]
    fn, shapes = CASES[name]()
    gauge = lambda field: get_registry().gauge("attention_fwd_tile", op=op,
                                               field=field)
    gauge("rows").set(0)
    gauge("keys_per_update").set(0)
    with jax.enable_x64(False):
        jax.eval_shape(fn, *[jax.ShapeDtypeStruct(s, d) for s, d in shapes])
    assert (gauge("rows").value, gauge("keys_per_update").value) == (rows,
                                                                     keys)


# --- and so is the tile each backward kernel picked, beside the grid it
# builds (`attention_bwd_tile`, `attention_bwd_steps`): name -> (op, the dQ
# kernel's rows and keys, the dK/dV kernel's)
BACKWARD_TILES = {
    "banded_train_gqa_48_8": ("banded_attention", (6 * 256, 512),
                              (6 * 256, 512)),
    "banded_train_gqa_48_8_f32": ("banded_attention", (6 * 256, 512),
                                  (6 * 256, 512)),
    "flash_pallas_bwd_gqa": ("flash_attention", (1024, 1024), (1024, 1024)),
    "sparse_train_32_2": ("sparse_attention", (1024, 512), (1024, 512)),
    "latent_train_32": ("latent_attention", (1024, 1024), (1024, 1024)),
}


@pytest.mark.parametrize("name", sorted(BACKWARD_TILES))
def test_backward_kernel_publishes_its_tile(name):
    from deeplearning4j_tpu.observe import get_registry

    op, *tiles = BACKWARD_TILES[name]
    fn, shapes = CASES[name]()
    registry = get_registry()
    tile = lambda kernel, field: registry.gauge(
        "attention_bwd_tile", op=op, kernel=kernel, field=field)
    steps = lambda kernel: [registry.gauge(
        "attention_bwd_steps", op=op, kernel=kernel, kind=kind)
        for kind in ("interior", "edge", "dead")]
    for kernel in ("dq", "dkdv"):
        for gauge in [tile(kernel, "rows"), tile(kernel, "keys"),
                      *steps(kernel)]:
            gauge.set(0)
    with jax.enable_x64(False):
        jax.eval_shape(fn, *[jax.ShapeDtypeStruct(s, d) for s, d in shapes])
    for kernel, want in zip(("dq", "dkdv"), tiles):
        assert (tile(kernel, "rows").value,
                tile(kernel, "keys").value) == want, kernel
        interior, edge, dead = (g.value for g in steps(kernel))
        assert edge > 0 and dead > 0, (kernel, interior, edge, dead)
        # a band's and a causal triangle's tiles are mostly interior; a
        # block selection's never
        assert (interior > edge) == (op != "sparse_attention"), kernel


# --- every `pl.pallas_call` site names its kernel: the device trace and
# its reduction find a kernel by that name after a refactor. Lowered for
# the TPU platform (Mosaic runs at lowering), which needs no chip and no
# described topology; nothing is compiled.
conv_fused = importlib.import_module("deeplearning4j_tpu.ops.conv_fused")


def _matmul_stats(m=1024, k=512, n=256):
    return (lambda x, w: conv_fused.matmul_with_channel_stats(x, w),
            [((m, k), BF16), ((k, n), BF16)])


def _conv3_stats(b=8, hw=56, c=64):
    return (lambda x, w: conv_fused.conv3x3_with_channel_stats(x, w),
            [((b, hw, hw, c), BF16), ((3, 3, c, c), BF16)])


KERNEL_NAMES = {
    "lstm_fwd": "lstm_train_f32", "lstm_bwd": "lstm_train_f32",
    "lstm_fwd_inference": "lstm_fwd_f32",
    "flash_attention_fwd": "flash_fwd",
    "flash_attention_bwd_dkdv": "flash_pallas_bwd",
    "flash_attention_bwd_dq": "flash_pallas_bwd",
    "banded_attention_fwd": "banded_fwd_gqa",
    "banded_attention_bwd_dq": "banded_train_gqa",
    "banded_attention_bwd_dkdv": "banded_train_gqa",
    "banded_decode_attention": "slot_decode_bf16",
    "paged_decode_attention": "paged_decode_bf16",
    "sparse_attention_fwd": "sparse_fwd_32_2",
    "sparse_attention_bwd_dq": "sparse_train_32_2",
    "sparse_attention_bwd_dkdv": "sparse_train_32_2",
    "latent_attention_fwd": "latent_fwd_32",
    "latent_attention_bwd_dq": "latent_train_32",
    "latent_attention_bwd_dkdv": "latent_train_32",
    "matmul_channel_stats": _matmul_stats,
    "conv3x3_channel_stats": _conv3_stats,
}


@pytest.mark.parametrize("kernel", sorted(KERNEL_NAMES))
def test_pallas_site_names_its_kernel(kernel):
    case = KERNEL_NAMES[kernel]
    fn, shapes = (CASES[case] if isinstance(case, str) else case)()
    with jax.enable_x64(False):
        args = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
        text = jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
    assert f'kernel_name = "{kernel}"' in text


# --- batch norm's pass count: what the step reads is the compiler's to
# say, and it says so for a described chip. Conv 1x1 -> batch norm -> relu
# -> conv 1x1 at the shapes of stage 2's 64-to-256 convolution and the one
# after it (ResNet-50, batch 256), forward and backward, against the
# two-pass form under plain autodiff: 4.73 against 5.55 GB.
ACTIVATION = (256, 56, 56, 256)


def _conv_bn_conv(apply):
    from deeplearning4j_tpu.nn.layers import BatchNormalization

    layer = BatchNormalization(n_out=ACTIVATION[-1], activation="relu")
    state = {"mean": jnp.zeros(ACTIVATION[-1:], BF16),
             "var": jnp.ones(ACTIVATION[-1:], BF16)}

    def conv(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def loss(x, w1, bn, w2):
        h, new_state = apply(layer, bn, conv(x, w1), state)
        return jnp.sum(jnp.square(conv(h, w2).astype(F32))), new_state

    narrow, wide = 64, ACTIVATION[-1]
    shapes = [(ACTIVATION[:-1] + (narrow,), BF16),
              ((1, 1, narrow, wide), BF16),
              {"gamma": ((wide,), BF16), "beta": ((wide,), BF16)},
              ((1, 1, wide, narrow), BF16)]
    return jax.grad(loss, argnums=(0, 1, 2, 3), has_aux=True), shapes


def _compile(chip, fn, shapes):
    args = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(*s, sharding=chip), shapes,
        is_leaf=lambda s: isinstance(s, tuple))
    return jax.jit(fn).lower(*args).compile()


def _stand_alone_statistics(text, whole=None, forward_only=True):
    """Top-level fusions (of the forward pass, unless told otherwise), not
    convolutions, that read a whole activation (`whole` positions; the
    batch norm case's by default) and write only per-channel vectors."""
    bodies = dict(re.findall(r"^%(fused_computation[\w.]*) .*?\{\n(.*?)^\}",
                             text, re.M | re.S))
    entry = text[text.index("\nENTRY "):]
    shape_of = {name: shapes for name, shapes in re.findall(
        r"^\s+(?:ROOT )?%([\w.\-]+) = (\(.*?\)|\S+) [\w\-]+\(", entry, re.M)}

    def dims(shapes):
        return [[int(d) for d in inner.split(",") if d]
                for inner in re.findall(r"\w+\[([\d,]*)\]", shapes)]

    whole = whole or ACTIVATION[0] * ACTIVATION[1] * ACTIVATION[2]
    found = []
    for name, shapes, operands, calls, meta in re.findall(
            r"^\s+(?:ROOT )?%([\w.\-]+) = (\(.*?\)|\S+) fusion\((.*?)\), "
            r"kind=\w+, calls=%([\w.]+)(.*)$", entry, re.M):
        reads = [d for op in re.findall(r"%([\w.\-]+)", operands)
                 for d in dims(shape_of.get(op, ""))]
        if (all(len(d) == 1 for d in dims(shapes))
                and any(len(d) == 4 and d[0] * d[1] * d[2] == whole
                        for d in reads)
                and " convolution(" not in bodies[calls]
                and not (forward_only and "transpose(" in meta)):
            found.append(name)
    return found


def test_batch_norm_reads_its_activation_twice_not_five_times(chip):
    from batchnorm_reference import two_pass_apply

    new = _compile(chip, *_conv_bn_conv(
        lambda layer, p, x, s: layer.apply(p, x, state=s, train=True)))
    old = _compile(chip, *_conv_bn_conv(two_pass_apply))
    assert _stand_alone_statistics(old.as_text())     # the reader finds them
    assert not _stand_alone_statistics(new.as_text())
    ratio = (new.cost_analysis()["bytes accessed"]
             / old.cost_analysis()["bytes accessed"])
    assert ratio <= 0.87, ratio


# --- a convolution in front of a max-pool: its bias and ReLU run on the
# pool's output (`nn/layers/convolution.defers_to_pool`). VGG16's first
# pair and the convolution after it at the benchmark's batch, forward and
# backward through `MultiLayerNetwork._forward`, against the same net with
# the rule off: 19.73 against 27.54 GB, and the bias gradient is no longer
# a reduction of its own over the full-resolution gradient.
IMAGES = (256, 224, 224, 64)


def _conv_pool_conv():
    from deeplearning4j_tpu import InputType
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import ConvolutionLayer, SubsamplingLayer

    def conv(n_out):
        return ConvolutionLayer(n_out=n_out, kernel=(3, 3), activation="relu",
                                convolution_mode="same")

    net = MultiLayerNetwork(
        NeuralNetConfiguration.builder().dtype("bfloat16")
        .list(conv(64), SubsamplingLayer(pooling="max"), conv(128))
        .set_input_type(InputType.convolutional(*IMAGES[1:])).build())

    def loss(params, x):
        y = net._forward(params, {}, x, train=True, rng=None)[0]
        return jnp.sum(jnp.square(y.astype(F32)))

    first, pool, last = (layer.name for layer in net.layers)
    shapes = [{first: {"W": ((3, 3, 64, 64), BF16), "b": ((64,), BF16)},
               pool: {},
               last: {"W": ((3, 3, 64, 128), BF16), "b": ((128,), BF16)}},
              (IMAGES, BF16)]
    return jax.grad(loss, argnums=(0, 1)), shapes


def test_bias_and_relu_run_after_the_max_pool(chip):
    from deeplearning4j_tpu.models import multilayer

    new = _compile(chip, *_conv_pool_conv())
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(multilayer, "defers_to_pool", lambda conv, pool: False)
        old = _compile(chip, *_conv_pool_conv())
    whole = IMAGES[0] * IMAGES[1] * IMAGES[2]
    assert _stand_alone_statistics(old.as_text(), whole, forward_only=False)
    assert not _stand_alone_statistics(new.as_text(), whole,
                                       forward_only=False)
    ratio = (new.cost_analysis()["bytes accessed"]
             / old.cost_analysis()["bytes accessed"])
    assert ratio <= 0.75, ratio


# --- gradient checkpointing keeps an attention kernel's output and
# log-sum-exp (`ops/attention.RESIDUAL_NAMES`): a window layer and a full
# layer of `trinity_large_fit`'s dense block (8,192 tokens, 48 query heads
# over 8 KV heads of 128, hidden 3072, window 4096), forward and backward
# through `MultiLayerNetwork._forward` under `gradient_checkpointing`,
# against the same net under the bare `jax.checkpoint`: each forward kernel
# is in the program once, not twice, for one hidden-sized tensor a layer.
TOKENS, HIDDEN = 8192, 3072


def _two_attention_blocks(monkeypatch):
    from deeplearning4j_tpu import InputType
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers.attention import (
        SandwichTransformerBlock,
    )

    # the policies ask which backend runs; the described chip is not it
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def block(window):
        return SandwichTransformerBlock(
            num_heads=48, num_kv_heads=8, head_dim=128, qk_norm=True,
            output_gate=True, causal=True, rope=window is not None,
            window=window, max_cache=TOKENS, ffn_width=12288)

    net = MultiLayerNetwork(
        NeuralNetConfiguration.builder().dtype("bfloat16")
        .gradient_checkpointing().list(block(4096), block(None))
        .set_input_type(InputType.recurrent(HIDDEN, TOKENS)).build())

    def loss(params, x):
        y = net._forward(params, {}, x, train=True, rng=None)[0]
        return jnp.sum(jnp.square(y.astype(F32)))

    params = jax.tree_util.tree_map(
        lambda leaf: (leaf.shape, leaf.dtype),
        jax.eval_shape(lambda: net.init().params_tree))
    return jax.grad(loss), [params, ((1, TOKENS, HIDDEN), BF16)]


def _kernel_calls(compiled, kernel):
    return len(re.findall(rf"custom-call\([^\n]*/{kernel}/pallas_call",
                          compiled.as_text()))


def test_checkpointed_attention_runs_its_forward_kernel_once(chip):
    with pytest.MonkeyPatch.context() as mp:
        new = _compile(chip, *_two_attention_blocks(mp))
        mp.setattr(jax.checkpoint_policies, "save_only_these_names",
                   lambda *names: None)
        old = _compile(chip, *_two_attention_blocks(mp))
    for kernel in ("banded_attention", "flash_attention"):
        assert _kernel_calls(old, kernel + "_fwd") == 2     # the reader finds
        assert _kernel_calls(new, kernel + "_fwd") == 1
        for backward in ("_bwd_dq", "_bwd_dkdv"):
            assert _kernel_calls(new, kernel + backward) == 1
    grown = (new.memory_analysis().temp_size_in_bytes
             - old.memory_analysis().temp_size_in_bytes)
    # o [8192, 48 x 128] bf16 and lse [48, 8192] float32 a layer
    assert grown <= 2 * 103e6, grown


# --- the token cells' whole steps
def _cell_step(chip, config):
    """(lowered, compiled, cfg): a token cell's train step at the cell's
    own size, built by the benchmark's own model file from
    `benchmarks/configs/<config>.json` and compiled for the described chip
    with parameters, moments and layer state donated."""
    import json

    from benchmarks import harness

    with pytest.MonkeyPatch.context() as mp:
        # the policies ask which backend runs; the described chip is not it
        mp.setattr(jax, "default_backend", lambda: "tpu")
        with open(os.path.join(harness.BENCH_DIR, "configs",
                               config + ".json"), encoding="utf-8") as fh:
            cfg = json.load(fh)
        net = harness.load_module("models", config + ".py").build(cfg, 0)
        shapes = jax.eval_shape(lambda: (
            net.init().params_tree, net.updater_state, net.state_tree))
        t = cfg["input_shape"][0]
        spec = lambda tree: jax.tree_util.tree_map(
            lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                              sharding=chip), tree)
        ids = jax.ShapeDtypeStruct((1, t), I32, sharding=chip)
        lowered = jax.jit(net.make_step_fn(), donate_argnums=(0, 1, 2)).lower(
            *map(spec, shapes), jax.ShapeDtypeStruct((), I32, sharding=chip),
            ids, ids, None, None,
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip))
        return lowered, lowered.compile(), cfg


# --- the benchmark's `minicpm_sala` step at the cell's own size (one
# sequence of 16,384 ids, bf16, every layer checkpointed), built by the
# benchmark's own model file: it fits the chip with room, each block-sparse
# kernel is in it once (the forward's output and log-sum-exp are kept, not
# remade), and nothing [heads, T, T] exists. The MLP's width is 16,384 too,
# so a [16384, 16384] tensor of rank 2 is the MLP's and says nothing.
def test_minicpm_sala_step_fits_the_chip_with_nothing_t_by_t(chip):
    _, compiled, cfg = _cell_step(chip, "minicpm_sala")
    t = cfg["input_shape"][0]
    memory = compiled.memory_analysis()
    # parameters, moments and layer state are donated: all but the batch
    assert (memory.argument_size_in_bytes - memory.alias_size_in_bytes
            < 2 ** 20)
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 15.75 * 2 ** 30)
    text = compiled.as_text()
    for kernel in ("_fwd", "_bwd_dq", "_bwd_dkdv"):
        assert _kernel_calls(compiled, "sparse_attention" + kernel) == 1
    # no expert layer: the one grouped product is the embedding's gradient
    _embedding_backward_is_grouped(compiled, cfg)
    assert _kernel_calls(compiled, "grouped_dot_drhs") == 1
    square = re.findall(rf"\[(?:\d+,)+{t},{t}\]", text)
    assert not square, sorted(set(square))[:5]


def _grouped_products(compiled):
    """(`ragged-dot` instructions, calls of `ops/grouped_matmul`'s kernels
    by name) of a compiled step."""
    text = compiled.as_text()
    return (len(re.findall(r"^\s*%ragged-dot-none", text, re.M)),
            {kernel: _kernel_calls(compiled, kernel)
             for kernel in ("grouped_dot", "grouped_dot_dlhs",
                            "grouped_dot_drhs")})


def _row_kernels(compiled):
    return {kernel: _kernel_calls(compiled, kernel)
            for kernel in ("take_rows", "sum_rows", "pack_rows")}


def _tier_passes(compiled, rows=(73728, 81920), width=4096):
    """The instructions of a compiled step, kernels apart, that write or
    read an array of a tier's rows by the model's width (in row form too:
    a bf16 row is `[width // 256, 128]` words), by name."""
    text = compiled.as_text()
    shape = re.compile(r"\[(%s),(%d|%d,128)\]" % (
        "|".join(map(str, rows)), width, width // 256))
    made = dict(re.findall(
        r"^\s*(?:ROOT )?%([\w.\-]+) = (\([^=]*?\)|\S+) [\w\-]+\(", text,
        re.M))
    found = []
    for name, out, op, rest in re.findall(
            r"^\s*(?:ROOT )?%([\w.\-]+) = (\([^=]*?\)|\S+) ([\w\-]+)\((.*)$",
            text, re.M):
        if op in ("custom-call", "parameter", "get-tuple-element", "tuple",
                  "bitcast"):
            continue
        operands = re.findall(r"%([\w.\-]+)", rest.split(", metadata=")[0]
                              .split(", calls=")[0])
        if shape.search(out) or any(shape.search(made.get(o, ""))
                                    for o in operands):
            found.append(f"{op} {name}")
    return found


def _embedding_backward_is_grouped(compiled, cfg):
    """The table's gradient is `ops/embedding.py`'s grouped product over
    the ids sorted by vocabulary tile: no `scatter` of XLA's writes an
    array of the table's shape. Its one `grouped_dot_drhs` call site is
    counted with the expert layers' (`_expert_layers_run_the_kernels`) or,
    in a step with none, by the caller."""
    table = "[%d,%d]" % (cfg.get("vocabulary_held", cfg["vocab_size"]),
                         cfg["hidden_size"])
    scatters = re.findall(r"^\s*(?:ROOT )?%[\w.\-]+ = (\S+) scatter\(",
                          compiled.as_text(), re.M)
    assert not [out for out in scatters if table in out], scatters


def _expert_layers_sort_once(compiled, layers, pairs):
    """Of the sorts of a compiled step, those over all `pairs` (token,
    choice) pairs of a layer: one a layer makes the schedule, the packed
    word's, a single operand (`parallel/moe.pair_schedule`; the
    recomputation reads the kept schedule), and two a layer apply it,
    each with a float payload: the weights into row order and their
    cotangent back (`_in_order`, where XLA's gather and scatter-add
    were). No two-integer sort is left (the parent's four `argsort`s a
    layer) and none is a scatter-add's own (the chip's compiler sorts a
    scatter's indices with the updates). Returns every sort's output
    shape."""
    sorts = re.findall(r"^\s*(?:ROOT )?%[\w.\-]+ = (\([^=]*?\)|\S+) sort\(",
                       compiled.as_text(), re.M)
    over_pairs = [out for out in sorts if f"[{pairs}]" in out]
    carried = [out for out in over_pairs if out.startswith("(")]
    assert len(over_pairs) - len(carried) == layers, over_pairs
    assert len(carried) == 2 * layers, carried
    assert not [out for out in carried if out.count("s32[") != 1], carried
    return sorts


def _expert_layers_run_the_kernels(compiled, layers):
    """The `layers` expert layers of a compiled step have one tier each:
    twelve grouped products a layer (three forward, three in the tier's
    own checkpoint, six backward), all `ops/grouped_matmul`'s kernels and
    no `ragged-dot`; three `take_rows` a layer (forward, the tier's own
    recomputation, and `sum_rows`' transpose), two `sum_rows` (forward
    and `take_rows`' transpose; the recomputed one is dead and dropped)
    and a packing of each one's source; no switch over tiers. One more
    `grouped_dot_drhs` than the expert layers account for: the
    embedding's gradient."""
    ragged, kernels = _grouped_products(compiled)
    assert ragged == 0
    assert kernels == {"grouped_dot": 6 * layers,
                       "grouped_dot_dlhs": 3 * layers,
                       "grouped_dot_drhs": 3 * layers + 1}
    assert _row_kernels(compiled) == {"take_rows": 3 * layers,
                                      "sum_rows": 2 * layers,
                                      "pack_rows": 5 * layers}
    assert " conditional(" not in compiled.as_text()


# --- the benchmark's `trinity_large` step at the cell's own size, built
# by the benchmark's own model file: its four expert layers, which have a
# ladder of four tiers where XLA's `ragged_dot` runs, have ONE tier of all
# 32,768 pairs that can fall on the experts held, and what
# `test_granite_step_fits_the_chip_with_one_tied_embedding` says of an
# expert layer holds: the kernels' counts, no `ragged-dot`, no switch. An
# expert's width is the model's here (3,072), so the SwiGLU's elementwise
# work between the products (three fusions a layer: forward, recomputed,
# backward) has the shape of the tier's rows, and nothing else of XLA's
# reads or writes them, in row form either.
ELEMENTWISE = {"fusion", "convert", "broadcast", "negate", "exponential",
               "add", "subtract", "multiply", "divide"}


def test_trinity_large_step_runs_the_kernels_over_one_tier(chip):
    _, compiled, cfg = _cell_step(chip, "trinity_large")
    memory = compiled.memory_analysis()
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 13 * 2 ** 30)
    _expert_layers_run_the_kernels(compiled, 4)
    _expert_layers_sort_once(compiled, 4, 8192 * 4)
    _embedding_backward_is_grouped(compiled, cfg)
    passes = _tier_passes(compiled, rows=(32768,), width=3072)
    assert {p.split()[0] for p in passes} <= ELEMENTWISE, passes
    assert sum(p.startswith("fusion ") for p in passes) == 3 * 4


# --- the benchmark's `deepseek_v2` step at the cell's own size (one
# sequence of 8,192 ids, bf16, every layer checkpointed, 32 of 128 heads
# and 8 of 160 experts held), built by the benchmark's own model file: it
# fits the chip with room, each latent-attention kernel is in it once a
# layer (the forward's output and log-sum-exp are kept, not remade),
# nothing [heads, T, T] exists, and its four expert layers run the kernels
# over ONE tier of 49,152 rows, of which no op of XLA's reads or writes a
# row of 5,120.
def test_deepseek_v2_step_fits_the_chip_with_nothing_t_by_t(chip):
    _, compiled, cfg = _cell_step(chip, "deepseek_v2")
    memory = compiled.memory_analysis()
    assert (memory.argument_size_in_bytes - memory.alias_size_in_bytes
            < 2 ** 20)
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 13 * 2 ** 30)
    layers, t = cfg["num_hidden_layers"], cfg["input_shape"][0]
    for kernel in ("_fwd", "_bwd_dq", "_bwd_dkdv"):
        assert _kernel_calls(compiled, "latent_attention" + kernel) == layers
    _expert_layers_run_the_kernels(compiled, 4)
    _expert_layers_sort_once(compiled, 4, 8192 * 6)
    # no `scatter` whose result is [12800,5120]: 23.3 ms of a 511 ms step
    # on the chip (PR 47), the longest op of the cell
    _embedding_backward_is_grouped(compiled, cfg)
    assert not _tier_passes(compiled, rows=(49152,), width=5120)
    square = re.findall(rf"\[(?:\d+,)+{t},{t}\]", compiled.as_text())
    assert not square, sorted(set(square))[:5]


# --- the benchmark's `granite_4_0_h_small` step at the cell's own size
# (one sequence of 8,192 ids, bf16, every layer checkpointed, 32 of 128
# Mamba-2 heads, 8 of 32 attention heads and 9 of 72 experts held, the
# head tied to the embedding), built by the benchmark's own model file: it
# fits the chip with the scan's [chunks, heads, 256, 256] float32 tensors
# counted, the tied embedding is ONE argument (1.340G parameters, not
# 1.392G), the attention layer's flash kernels are in it once, nothing
# [heads, T, T] exists, the ten expert layers' 120 grouped products are
# `ops/grouped_matmul`'s three kernels and no `ragged-dot`, and their
# gathers are `ops/row_gather`'s: three `take_rows` a layer (forward, the
# tier's own recomputation, and `sum_rows`' transpose), two `sum_rows`
# (forward and `take_rows`' transpose; the recomputed one is dead and
# dropped) and a packing of each one's source, and NO op of XLA's reads or
# writes the tier's 73,728 (or all 81,920) rows of 4,096: no gather, no
# mask, no weighing, no sum of two cotangents. Lowered as a body a shape
# and not a body a call.
def test_granite_step_fits_the_chip_with_one_tied_embedding(chip):
    lowered, compiled, cfg = _cell_step(chip, "granite_4_0_h_small")
    _expert_layers_run_the_kernels(compiled, 10)
    # 8,192 tokens x 10 choices; `top_k` of 10 of 72 is a whole sort of
    # every token's scores on this chip and runs forward alone (the choice
    # is kept), and the embedding's ids are sorted once: 41 sorts, 30 of
    # them over the pairs, where the parent's step held 71 and 50
    sorts = _expert_layers_sort_once(compiled, 10, 81920)
    assert len(sorts) == 41 and sum(
        out.startswith("(f32[8192,72]") for out in sorts) == 10
    _embedding_backward_is_grouped(compiled, cfg)
    assert not _tier_passes(compiled)
    # the grouped products' 3 kernels x 2 shapes and the row gathers' 3
    # (each with its packing), many of them once more where a checkpoint's
    # partial evaluation split a body; the flash kernels' three; and, the
    # 112th, the embedding's gradient: `grouped_dot_drhs` at a shape of its
    # own (a one-hot `[8192, 256]` by the cotangent `[8192, 4096]`)
    assert lowered.as_text().count("tpu_custom_call") <= 112
    memory = compiled.memory_analysis()
    assert (memory.argument_size_in_bytes - memory.alias_size_in_bytes
            < 2 ** 20)
    # bf16 parameters and both moments of 1,340,223,584: the embedding once
    assert memory.argument_size_in_bytes == pytest.approx(
        6 * 1_340_223_584, rel=1e-3)
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 13 * 2 ** 30)
    t = cfg["input_shape"][0]
    for kernel in ("_fwd", "_bwd_dq", "_bwd_dkdv"):
        assert _kernel_calls(compiled, "flash_attention" + kernel) == 1
    square = re.findall(rf"\[(?:\d+,)+{t},{t}\]", compiled.as_text())
    assert not square, sorted(set(square))[:5]


# --- the benchmark's `ouro_2_6b` step at the cell's own size (one sequence
# of 8,192 ids, bf16, 8 blocks run 4 times over shared leaves, each block
# application a checkpoint of its own, four exits of a 49,152-row head),
# built by the benchmark's own model file. The passes are ONE traced body:
# the step holds each block's flash kernels once, 8 call sites a kernel
# for 32 applications, and the kernels' forward is not run a second time
# in the backward. Its memory figures are the ones the configuration's
# file states.
def test_ouro_step_holds_each_block_once_for_its_four_passes(chip):
    _, compiled, cfg = _cell_step(chip, "ouro_2_6b")
    for kernel in ("_fwd", "_bwd_dq", "_bwd_dkdv"):
        assert _kernel_calls(compiled, "flash_attention" + kernel) == 8
    # no expert layer: the one grouped product is the embedding's gradient
    _embedding_backward_is_grouped(compiled, cfg)
    assert _kernel_calls(compiled, "grouped_dot_drhs") == 1
    memory = compiled.memory_analysis()
    # parameters, moments and layer state are donated: all but the batch
    assert (memory.argument_size_in_bytes - memory.alias_size_in_bytes
            < 2 ** 20)
    # bf16 parameters and both moments of 612,438,017: one copy a leaf
    assert memory.argument_size_in_bytes == pytest.approx(
        6 * 612_438_017, rel=1e-3)
    stated = cfg["compiled_for_v5e"]
    assert memory.argument_size_in_bytes / 2 ** 30 == pytest.approx(
        stated["argument_gib"], abs=2e-3)
    assert memory.temp_size_in_bytes / 2 ** 30 == pytest.approx(
        stated["temporary_gib"], abs=0.05)
    t = cfg["input_shape"][0]
    text = compiled.as_text()
    square = re.findall(rf"\[(?:\d+,)+{t},{t}\]", text)
    assert not square, sorted(set(square))[:5]
    # no exit's logits are float32 for more than one exit at a time: none
    # carries the passes' axis
    assert not re.findall(rf"\[4,(?:1,)?{t},{cfg['vocab_size']}\]", text)


# --- the benchmark's `nemotron_3_super` step at the cell's own size (one
# sequence of 8,192 ids, bf16, eleven one-sublayer layers each a checkpoint
# of its own, a two-term head with a prediction module of an attention and
# an expert sublayer), built by the benchmark's own model file. Its six
# LatentMoE layers (five and the module's) have two-matrix experts: eight
# grouped products a layer (two forward, two in the tier's own checkpoint,
# four backward; the layer's recomputation runs none, the routed sum that
# `latent_up`'s gradient reads is named and kept), the row kernels as a
# SwiGLU layer's, no `ragged-dot`, no switch. The embedding's table is
# looked up twice (the ids and, in the module, the labels), so two
# `grouped_dot_drhs` are its gradient's; the flash kernels run once for
# the trunk's attention layer and once for the module's; and no
# `[.., 2, T, vocabulary]` float32 tensor holds both heads' logits at once.
def test_nemotron_step_runs_six_latent_layers_and_two_heads(chip):
    lowered, compiled, cfg = _cell_step(chip, "nemotron_3_super")
    layers = 6
    ragged, kernels = _grouped_products(compiled)
    assert ragged == 0
    assert kernels == {"grouped_dot": 4 * layers,
                       "grouped_dot_dlhs": 2 * layers,
                       "grouped_dot_drhs": 2 * layers + 2}
    assert _row_kernels(compiled) == {"take_rows": 3 * layers,
                                      "sum_rows": 2 * layers,
                                      "pack_rows": 5 * layers}
    text = compiled.as_text()
    assert " conditional(" not in text
    # 8,192 tokens x 22 choices; six `top_k` sorts of 512 scores a token,
    # forward alone, and the two lookups' ids: 26 sorts, 18 of them over
    # the pairs, where the parent's step held 50 and 36
    sorts = _expert_layers_sort_once(compiled, layers, 8192 * 22)
    assert len(sorts) == 26 and sum(
        out.startswith("(f32[8192,512]") for out in sorts) == layers
    _embedding_backward_is_grouped(compiled, cfg)
    for kernel in ("_fwd", "_bwd_dq", "_bwd_dkdv"):
        assert _kernel_calls(compiled, "flash_attention" + kernel) == 2
    memory = compiled.memory_analysis()
    assert (memory.argument_size_in_bytes - memory.alias_size_in_bytes
            < 2 ** 20)
    # bf16 parameters and both moments of 1,102,491,120
    assert memory.argument_size_in_bytes == pytest.approx(
        6 * 1_102_491_120, rel=1e-3)
    stated = cfg["compiled_for_v5e"]
    assert memory.argument_size_in_bytes / 2 ** 30 == pytest.approx(
        stated["argument_gib"], abs=2e-3)
    assert memory.temp_size_in_bytes / 2 ** 30 == pytest.approx(
        stated["temporary_gib"], abs=0.05)
    t, v = cfg["input_shape"][0], cfg["vocabulary_held"]
    square = re.findall(rf"\[(?:\d+,){{2,}}{t},{t}\]", text)
    assert not square, sorted(set(square))[:5]
    assert not re.findall(rf"f32\[(?:\d+,)*2,(?:1,)?{t},{v}\]", text)


def test_selective_scan_makes_c_b_t_once_a_chunk_not_once_a_head(chip):
    """The forward of `ops/selective_scan.py` at the cell's widths (32
    heads of 64, a state of 128, chunks of 256; four chunks here): of the
    matrix products the chip runs, exactly one gives a [256, 256] tile, the
    chunk's `C B^T` over the state's 128 lanes, [chunks, 256, 256] with
    no heads in it; the per-head [chunks, heads, 256, 256] scores are made
    from it elementwise."""
    scan = importlib.import_module("deeplearning4j_tpu.ops.selective_scan")
    t, h, p, n = 1024, 32, 64, 128
    compiled = _compile(
        chip, lambda *a: scan.selective_scan(*a, chunk=256),
        [((1, t, h, p), BF16), ((1, t, h), F32), ((h,), F32),
         ((1, t, 1, n), BF16), ((1, t, 1, n), BF16), ((h,), F32)])
    products = re.findall(r"= (\w+\[[\d,]+\])\S* convolution\(",
                          compiled.as_text())
    tiles = [s for s in products if re.search(r"256,256\]$", s)]
    assert len(tiles) == 1, products
    dims = [int(d) for d in re.findall(r"\d+", tiles[0].split("[")[1])]
    assert math.prod(dims) == (t // 256) * 256 * 256, tiles
