"""CPU rehearsal of the `minicpm_sala` files at a tiny size
(`configs/minicpm_sala_tiny.json`: a block-selecting softmax layer at
twice its `dense_len` and two decayed linear layers, ids past 256),
through `run_cell` like `trinity_tiny`, in float32 and held to float32
limits; and the same under `test_broken_path.py`'s unchanged-state step,
which has to read not correct. A process each (`python -m benchmarks.tests.
test_minicpm_rehearsal [--broken]`). Run by hand:

    python -m pytest benchmarks/tests/test_minicpm_rehearsal.py -q

and, on the chip, the real cell under the unchanged-state step, held to
its own limits (`correct` has to come out false):

    chiprun -- python3 -m benchmarks.tests.test_minicpm_rehearsal \
        --cell minicpm_sala_fit --broken --seed 2147483777
"""

import json
import os
import subprocess
import sys

from benchmarks import harness

# float32 against float32: what is left is the order of the sums (the
# program's chunked scan against the reference's quadratic form) and a
# block that falls the other side of a tie (none at this size)
LIMITS = {"loss_gap": 1e-4, "grad_norm_gap": 1e-3, "delta_norm_gap": 1e-3,
          "grad_diff_share": 1e-3}
CELL = "minicpm_sala_fit"


def rehearse(*flags) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    done = subprocess.run(
        [sys.executable, "-m", "benchmarks.tests.test_minicpm_rehearsal",
         *flags], cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=1500)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_tiny_cell_is_correct_and_counts_its_blocks():
    result = rehearse("--trace", "1")
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    metrics = result["metrics"]
    # 128 tokens in blocks of 8, 6 kept of up to 16
    assert 0 < metrics["sparse_kept_block_share.train"]["value"] < 100
    # nothing measured on a CPU under a device metric's name
    assert not {"linear_attention_time_share.train",
                "sparse_select_time_share.train",
                "sparse_attention_time_share.train",
                "sparse_attention_roofline.train"} & set(metrics)


def test_unchanged_state_step_is_not_correct():
    assert rehearse("--broken")["correct"] is False


def main(argv=None) -> int:
    import argparse
    import time

    from benchmarks import run as bench_run
    from benchmarks.tests.helpers import tiny_spec

    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--broken", action="store_true")
    ap.add_argument("--cell", help="a cell of BENCHMARK.json, on the chip "
                    "and under its own limits, instead of the tiny one")
    ap.add_argument("--seed", type=int, default=2 ** 31 + 5)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    with open(os.path.join(harness.ROOT, "BENCHMARK.json"),
              encoding="utf-8") as fh:
        real = json.load(fh)
    if args.cell:
        cell = bench_run.load_cell(real, args.cell)
    else:
        spec = tiny_spec(1, "fit_stream", "minicpm_sala_tiny")
        # the metrics that list the real cell read this tiny cell too
        spec["per_layer"] += [
            {k: v for k, v in m.items() if k != "workloads"}
            for m in real["per_layer"] if m.get("workloads") == [CELL]]
        cell = bench_run.load_cell(spec, "tiny_1")
    if args.broken:
        from benchmarks.tests import test_broken_path as broken

        # the model's train step is `MultiLayerNetwork`'s, as `tokens_tiny`'s
        broken.BROKEN.setdefault(cell["config_data"]["model"],
                                 broken.BROKEN["tokens_tiny"])
        broken.break_step(cell["config_data"]["model"])
    result = bench_run.run_cell(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        require_chip=bool(args.cell), t_start=time.perf_counter(),
        limits=None if args.cell else LIMITS)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
