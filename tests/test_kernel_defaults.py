"""The kernel policies (`ops/kernel_defaults.py`): each is an env force
over a predicate on shape and platform. These tests pin the
forces, the memory hazard that moves attention off the dense path, and
(`test_policy_is_the_parents`) the verdict at every shape of a grid.
"""
import pytest

from deeplearning4j_tpu.ops import kernel_defaults as kd


def _tpu(monkeypatch):
    """Put the TPU backend in, so that the real eligibility predicates
    (tiling, head grouping) decide on the CPU suite as on the chip."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_memory_necessity_overrides_speed(monkeypatch):
    """Past DENSE_MAX_T the [T, T] dense path is a memory hazard: flash
    with the O(T) Pallas backward is the path."""
    _tpu(monkeypatch)
    t = kd.dense_max_t()
    pol = kd.attention_policy(t, train=True)
    assert pol.kind == "flash"
    assert pol.backward == "pallas"
    assert "memory necessity" in pol.reason
    # the hazard scales with Tq*Tk, not min: a long-context
    # cross-attention with a short query side must also route to flash
    pol = kd.attention_policy(t // 4, t * 4, train=True)
    assert pol.kind == "flash"
    assert pol.backward == "pallas"
    assert kd.attention_backward(t // 4, t * 4) == "pallas"
    # ...down to the kernel's capability floor (128) on the query side
    pol = kd.attention_policy(256, 2 * t * t // 256, train=True)
    assert pol.kind == "flash", pol
    # but the same short side WITHOUT memory pressure stays dense
    assert kd.attention_policy(256, 256, train=True).kind == "dense"


def test_env_escape_hatches(monkeypatch):
    _tpu(monkeypatch)
    monkeypatch.setenv("DL4J_TPU_ATTN", "dense")
    assert kd.attention_policy(8192, train=True).kind == "dense"
    monkeypatch.setenv("DL4J_TPU_ATTN", "flash")
    pol = kd.attention_policy(1024, train=True)
    assert pol.kind == "flash"
    monkeypatch.setenv("DL4J_TPU_ATTN_BACKWARD", "pallas")
    assert kd.attention_policy(1024, train=True).backward == "pallas"
    monkeypatch.setenv("DL4J_TPU_ATTN_BLOCK", "256x128")
    pol = kd.attention_policy(1024, train=True)
    assert (pol.block_q, pol.block_k) == (256, 128)
    # shape ineligibility still wins over a flash force
    monkeypatch.setenv("DL4J_TPU_ATTN", "flash")
    assert kd.attention_policy(1000, train=True).kind == "dense"
    monkeypatch.setenv("DL4J_TPU_LSTM", "scan")
    assert kd.lstm_policy() == "scan"


def test_dense_max_t_env(monkeypatch):
    _tpu(monkeypatch)
    monkeypatch.setenv("DL4J_TPU_DENSE_MAX_T", "2048")
    assert kd.attention_policy(2048, train=True).kind == "flash"


def test_flash_attention_backward_resolution_matches_policy():
    """flash_attention(backward=None) resolves through the same
    function the policy uses, so layer dispatch and direct op calls
    can't disagree."""
    from deeplearning4j_tpu.ops.attention import _resolve_backward

    for t in (512, 1024, 2048, 8192):
        assert _resolve_backward(None, t, t) == kd.attention_backward(t)
    assert _resolve_backward("pallas", 1024, 1024) == "pallas"


def test_banded_policy_env_hatches(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_ATTN", "dense")
    assert kd.banded_policy(256, 4, 2).kind == "dense"
    # flash cannot band: a flash force on a windowed shape stays dense
    monkeypatch.setenv("DL4J_TPU_ATTN", "flash")
    assert kd.banded_policy(256, 4, 2).kind == "dense"
    monkeypatch.setenv("DL4J_TPU_ATTN", "banded")
    pol = kd.banded_policy(256, 4, 2)
    assert pol.kind == "banded"
    assert (pol.block_q, pol.block_k) == (256, 256)
    # block override flows through the force, like flash's
    monkeypatch.setenv("DL4J_TPU_ATTN_BLOCK", "128x64")
    pol = kd.banded_policy(256, 4, 2)
    assert (pol.block_q, pol.block_k) == (128, 64)
    monkeypatch.delenv("DL4J_TPU_ATTN_BLOCK")
    # ...but tiling ineligibility still wins over the force (backend
    # does NOT: a force runs interpret-mode off-TPU by design)
    assert kd.banded_policy(100, 4, 2).kind == "dense"
    assert kd.banded_policy(256, 4, 3).kind == "dense"   # h % hkv != 0


def test_banded_policy_conservative_without_rows(monkeypatch):
    """An eligible shape below the memory hazard stays dense in both
    modes, and says that the hazard is why."""
    _tpu(monkeypatch)
    for train in (False, True):
        pol = kd.banded_policy(1024, 8, 2, train=train)
        assert pol.kind == "dense", pol
        assert "below the memory hazard" in pol.reason


def test_decode_policy_env_and_default(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_DECODE_ATTN", "dense")
    assert kd.decode_attention_policy(512, 8, 2).kind == "dense"
    monkeypatch.setenv("DL4J_TPU_DECODE_ATTN", "banded")
    pol = kd.decode_attention_policy(512, 8, 2)
    assert pol.kind == "banded" and pol.block_l == 512
    monkeypatch.delenv("DL4J_TPU_DECODE_ATTN")
    # a shape the kernel tiles, on a TPU: dense all the same, unforced
    _tpu(monkeypatch)
    pol = kd.decode_attention_policy(512, 8, 2)
    assert pol.kind == "dense" and pol.block_l == 0
    assert "forced" not in pol.reason


def test_decode_policy_record_flag_gates_counter(monkeypatch):
    """Observers (serving snapshots) ask what WOULD dispatch with
    record=False; kernel_dispatch_total must count only real dispatch
    sites, or snapshot polling would inflate the metric."""
    from deeplearning4j_tpu.observe import get_registry
    monkeypatch.setenv("DL4J_TPU_DECODE_ATTN", "dense")
    c = get_registry().counter("kernel_dispatch_total",
                               op="decode_attention", impl="dense")
    v0 = c.value
    kd.decode_attention_policy(512, 8, 2, record=False)
    assert c.value == v0
    kd.decode_attention_policy(512, 8, 2)
    assert c.value == v0 + 1


def test_current_data_yields_dense_defaults(monkeypatch):
    """Below the memory hazard training and inference attention default
    to XLA dense with the dense backward: no cell has measured today's
    kernels there (ROADMAP D3), and the one that does moves this pin."""
    _tpu(monkeypatch)
    assert kd.attention_policy(2048, train=True).kind == "dense"
    assert kd.attention_policy(2048, train=False).kind == "dense"
    assert kd.attention_backward(2048) == "dense"
    assert "below the memory hazard" in kd.attention_policy(2048).reason


_DENSE, _FLASH = ("dense", 0, 0, "dense"), ("flash", 512, 512, "pallas")
_ATT_VERDICTS = (
    ((128, 128), _DENSE), ((512, 512), _DENSE), ((1024, 1024), _DENSE),
    ((2048, 2048), _DENSE), ((4096, 4096), _DENSE), ((8064, 8064), _DENSE),
    ((8192, 8192), _FLASH), ((16384, 16384), _FLASH),
    ((4096, 16384), _FLASH), ((128, 1048576), _FLASH),
    ((1000, 1000), _DENSE), ((1000, 1048576), _DENSE))   # does not tile

_PARENT_VERDICTS = (
    [("attention_policy", s, {"train": tr}, v)
     for s, v in _ATT_VERDICTS for tr in (False, True)]
    + [("attention_backward", s, {}, v[3]) for s, v in _ATT_VERDICTS[:-1]]
    + [("banded_policy", s, {"train": tr}, v)
       for s, v in (((8192, 48, 8), ("banded", 256, 256)),   # trinity_large
                    ((16384, 32, 2), ("banded", 256, 256)),
                    ((2048, 48, 8), ("dense", 0, 0)),
                    ((4096, 16, 2), ("dense", 0, 0)),
                    ((8192, 48, 7), ("dense", 0, 0)))        # h % hkv != 0
       for tr in (False, True)]
    + [("decode_attention_policy", s, {}, ("dense", 0))
       for s in ((512, 8, 2), (4096, 48, 8), (500, 8, 2))]
    + [("decode_loop_policy", (k,), {"capable": True}, ("fused", b))
       for k, b in ((None, 8), (1, 1), (3, 4), (8, 8), (40, 16))]
    + [("decode_loop_policy", (8,), {"capable": False}, ("stepwise", 1))]
    + [("spec_decode_policy", (k,), {"capable": True}, ("spec", b))
       for k, b in ((None, 8), (1, 1), (3, 4), (8, 8), (40, 16))]
    + [("spec_decode_policy", (8,), {"capable": False}, ("plain", 0))]
    + [("kv_dtype_policy", (k,), {}, (v,))
       for k, v in ((None, "native"), ("auto", "native"),
                    ("native", "native"), ("int8", "int8"), ("fp8", "fp8"))]
    + [("prefix_cache_policy", (p,), {"max_cache": mc, "capable": cap}, v)
       for p, mc, cap, v in ((None, None, True, ("paged", 128)),
                             (None, 4096, True, ("paged", 128)),
                             (100, 4096, True, ("paged", 64)),
                             (128, 192, True, ("paged", 96)),
                             (None, 4096, False, ("off", 0)))]
    + [("lstm_policy", (), {"train": tr}, "fused") for tr in (True, False)]
)


@pytest.mark.parametrize(
    "policy,args,kwargs,want", _PARENT_VERDICTS,
    ids=[f"{p}-{'x'.join(map(str, a))}-"
         f"{'-'.join(f'{k}={v}' for k, v in kw.items())}"
         for p, a, kw, _ in _PARENT_VERDICTS])
def test_policy_is_the_parents(monkeypatch, policy, args, kwargs, want):
    """What each shape dispatches to on a TPU with no variable set, as
    literals taken from the tree that still held July's table (PR 44's):
    an edit that moves a verdict moves it here, in the open."""
    _tpu(monkeypatch)
    got = getattr(kd, policy)(*args, **kwargs)
    if not isinstance(got, str):
        got = tuple(v for f, v in zip(got._fields, got) if f != "reason")
    assert got == want


@pytest.mark.parametrize("width, tile", [
    (5120, 256), (3072, 256), (4096, 256), (2048, 256), (128, 256),
    (96, None), (5000, None)])
def test_embedding_backward_tile_is_one_size_for_rows_of_whole_lanes(
        width, tile):
    """The five token cells' widths take the grouped product with one
    tile; a row off the lanes of 128 keeps XLA's scatter-add. No force."""
    assert kd.embedding_backward_tile(width) == tile


def test_the_one_device_kernels_run_on_the_tpu_backend_alone(monkeypatch):
    assert not kd.kernels_run()     # the suite's backend is the CPU
    _tpu(monkeypatch)
    assert kd.kernels_run()
