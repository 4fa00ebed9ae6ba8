"""CPU rehearsals of `run.py`'s path at a tiny size, one per runner path:
one device, `ParallelWrapper` on four virtual devices, and a token model
(integer ids and labels from the `token_stream` generator, the `adam`
rule, float32) held to float32 limits. Each runs in a
process of its own (the device count is fixed when JAX starts). They check
the result line's shape and that nothing measured on a CPU is written
under a device metric's name. Run by hand:

    python -m pytest benchmarks/tests -q
"""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import harness

DEVICE_METRICS = {"device_idle_share.train", "peak_hbm_gib.train",
                  "mfu.train", "conv_time_share.train",
                  "collective_time_share.train", "fill_ms.train",
                  "scoped_op_time_share.train", "updater_time_share.train"}
CELLS = [(1, "fit_stream", "resnet50_tiny"),
         (4, "fit_stream_dp", "resnet50_tiny"),
         (1, "fit_stream", "tokens_tiny"),
         (1, "fit_stream", "tokens_tiny_deep")]     # the key `hidden_layers`


def rehearse(chips: int, traffic: str, trace: int,
             config: str = "resnet50_tiny") -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    done = subprocess.run(
        [sys.executable, "-m", "benchmarks.tests.helpers", str(chips),
         traffic, str(trace), config], cwd=harness.ROOT, env=env,
        capture_output=True, text=True, timeout=1500)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("chips,traffic,config", CELLS)
def test_end_to_end_line(chips, traffic, config):
    result = rehearse(chips, traffic, 0, config)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"train_items_per_s", "setup_s"}
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == chips
    for row in result["metrics"].values():
        assert set(row) == {"value", "unit"} and row["value"] > 0


@pytest.mark.parametrize("chips,traffic,config", CELLS)
def test_traced_line_keeps_cpu_out_of_device_metrics(chips, traffic, config):
    result = rehearse(chips, traffic, 1, config)
    assert result["device"]["platform"] == "cpu"
    assert not DEVICE_METRICS & set(result["metrics"])
    assert "busy_s" not in result["device"]
    assert {"xla_compiles_in_window.train", "etl_wait_ms.train",
            "dispatch_ms.train"} <= set(result["metrics"])
    assert result["metrics"]["xla_compiles_in_window.train"]["value"] == 0


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "resnet50_fit",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert done.returncode == 2
    assert "correct" not in done.stdout
