"""Rehearsals of `chip_smoke.py` on the CPU, where the chip is never
reached: every phase at a tiny size (the four-device phase on the virtual
CPU mesh), `main()`'s refusal off the chip and its last line on it, the
compile-cache helper, and that the router's imports start no backend.

The steering lives here, not in an option of the script: the test fakes the
platform `main()` reads and routes the LSTM layer to its kernel in interpret
mode; the script only ever asks JAX what it resolved.

The phase rehearsals take tens of seconds each and carry the `rehearsal`
mark, which `conftest.py` collects last.
"""

import json
import os
import subprocess
import sys
import types

import jax
import pytest

import chip_smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------- the phases
@pytest.mark.rehearsal
def test_train_rehearsal():
    facts = chip_smoke.train(image=32, classes=8, batch=8, steps=3,
                             dtype="float32")
    assert facts["steps"] == 3 and facts["step_programs"] == 1
    assert facts["host_syncs"] <= 1 and facts["params_on"] == ["cpu"]
    assert facts["step_flops"] > 0


@pytest.mark.rehearsal
def test_lstm_rehearsal(monkeypatch):
    from deeplearning4j_tpu.nn.layers import recurrent
    from deeplearning4j_tpu.ops.kernel_defaults import lstm_policy

    # on the chip the policy routes a default LSTM to the kernel; here the
    # layer is steered to the same verdict and runs it in interpret mode
    monkeypatch.setattr(
        recurrent.LSTM, "_use_fused",
        lambda self: (lstm_policy() == "fused" if self.fused is None
                      else self.fused))
    out = chip_smoke.lstm(timesteps=8, features=8, hidden=16, classes=4,
                          batch=4, steps=2)
    assert set(out) == {"float32", "bfloat16"}
    for facts in out.values():
        assert facts["fused_dispatches"] > 0
        assert not facts["tpu_custom_call"]      # interpreted, not compiled


@pytest.mark.rehearsal
def test_lstm_phase_fails_when_the_kernel_is_not_dispatched():
    # unsteered on the CPU the policy never reaches the kernel: the phase
    # must say so, not pass on the scan path
    with pytest.raises(chip_smoke.SmokeError, match="did not dispatch"):
        chip_smoke.lstm(timesteps=4, features=4, hidden=8, classes=4,
                        batch=2, steps=2, dtypes=("float32",))


@pytest.mark.rehearsal
def test_serve_rehearsal():
    out = chip_smoke.serve(d_model=32, heads=4, kv_heads=2, blocks=2,
                           vocab=32, cache=128, slots=4, fused_k=4,
                           prefill_chunk=8, requests=4, prompt_min=6,
                           prompt_max=12, new_tokens=8)
    assert set(out) == {leg for leg, _, _ in chip_smoke.SERVE_LEGS}
    assert out["dense"]["decode_attention"] == ["dense"]
    assert out["dense"]["paged"] and not out["banded_unpaged"]["paged"]
    for leg in ("banded", "banded_unpaged"):
        assert out[leg]["banded_dispatches"] > 0
        assert out[leg]["streams_equal_dense"] == 4     # f32 on the CPU
    assert all(f["tokens_generated"] == 32 for f in out.values())


def test_reference_refuses_a_wrong_token():
    import dataclasses

    import numpy as np

    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.utils.textgen import generate
    from deeplearning4j_tpu.zoo.transformer import TextGenerationTransformer

    net = MultiLayerNetwork(dataclasses.replace(TextGenerationTransformer(
        num_classes=32, input_shape=(32, 1), d_model=32, num_heads=4,
        num_kv_heads=2, num_blocks=1, pos_encoding="rope").conf())).init()
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    greedy = generate(net, np.asarray(prompt), 6, greedy=True)[0].tolist()
    ratios = chip_smoke._reference_ranks(net, [prompt], [greedy], 32)[0]
    assert (ratios >= 1.0).all()            # textgen's greedy = first choice
    wrong = list(greedy)
    wrong[2] = (wrong[2] + 1) % 32
    ratios = chip_smoke._reference_ranks(net, [prompt], [wrong], 32)[0]
    assert ratios[2] < 1.0 - chip_smoke.NEAR_TIE["float32"]


@pytest.mark.rehearsal
def test_data_parallel_rehearsal(devices8):
    facts = chip_smoke.data_parallel(chips=4, image=32, classes=8, batch=8,
                                     steps=2, dtype="float32")
    assert facts["param_devices_min"] == 4
    assert len(set(facts["batch_shard_devices"])) == 4
    assert facts["collectives_in_step"]["all-reduce"] > 0
    assert facts["moment_bytes_per_device_share"] == pytest.approx(0.25)


# ------------------------------------------------------------------ main
def test_main_refuses_to_run_off_the_chip():
    # a real process: this is the run the driver makes in the sandbox
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""                       # no result, no phase
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr


@pytest.fixture
def fake_chip(monkeypatch, tmp_path):
    """What `main()` would read on a v5e host, with the phases stubbed:
    a call log instead of work."""
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    calls = []
    monkeypatch.setattr(jax, "devices", lambda *a: [dev] * 4)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    for phase in ("train", "lstm", "serve", "data_parallel"):
        monkeypatch.setattr(
            chip_smoke, phase,
            lambda _phase=phase, **kw: calls.append(_phase))
    return calls


def test_main_last_line_is_the_contract(fake_chip, capsys):
    assert chip_smoke.main([]) == 0
    assert fake_chip == ["train", "lstm", "serve"]
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}}
    setup = json.loads(lines[0])
    assert setup["host_library"] in ("native", "numpy")
    assert setup["compile_cache_from_env"] and setup["peak_flops"] == 197e12


def test_main_four_chips_runs_only_the_data_parallel_phase(fake_chip, capsys):
    assert chip_smoke.main(["--chips", "4"]) == 0
    assert fake_chip == ["data_parallel"]
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last)["device"]["count"] == 4


def test_a_failed_phase_fails_the_run(fake_chip, monkeypatch, capsys):
    def broken(**kw):
        raise chip_smoke.SmokeError("lstm: made to fail")

    monkeypatch.setattr(chip_smoke, "lstm", broken)
    # uncaught, so the interpreter exits 1 and `serve` never starts
    with pytest.raises(chip_smoke.SmokeError):
        chip_smoke.main([])
    assert fake_chip == ["train"]
    assert '"ok"' not in capsys.readouterr().out


def test_main_refuses_an_unknown_device_kind(fake_chip, monkeypatch, capsys):
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])
    with pytest.raises(chip_smoke.SmokeError, match="peak"):
        chip_smoke.main([])
    assert fake_chip == []


# ---------------------------------------------------------- compile cache
def test_compile_cache_left_to_the_environment(monkeypatch, tmp_path):
    from deeplearning4j_tpu.utils import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # set no path


def test_compile_cache_default_is_fixed_inside_the_checkout(monkeypatch):
    from deeplearning4j_tpu.utils import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        where = compile_cache.enable_compile_cache()
        assert where == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == where
        assert compile_cache.enable_compile_cache() == where  # never moves
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# ---------------------------------------------------------------- imports
def test_router_and_model_imports_start_no_backend():
    # the fleet's router process must stay off the chip: a chip belongs to
    # the one process that touches it
    code = (
        "import deeplearning4j_tpu.serving.fleet.router\n"
        "import deeplearning4j_tpu.serving.fleet.launcher\n"
        "import deeplearning4j_tpu.models, deeplearning4j_tpu.zoo\n"
        "import deeplearning4j_tpu.parallel\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, list(xla_bridge._backends)\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
