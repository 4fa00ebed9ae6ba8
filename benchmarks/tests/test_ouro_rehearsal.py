"""CPU rehearsal of the `ouro_2_6b` files at a tiny size
(`configs/ouro_2_6b_tiny.json`: 2 sandwich-norm blocks of 4 heads of 16
with rope at 1e6 run 3 times over shared leaves, the norm after every
pass, the exit gate and the expected loss over 3 exits of a 256-row head),
through `run_cell` like `trinity_tiny`, in float32 and held to float32
limits; the same under `test_broken_path.py`'s unchanged-state step; and
under a step that leaves out part of the mathematics (the entropy term, or
every exit but the last): each has to read not correct. A process each
(`python -m benchmarks.tests.test_ouro_rehearsal [--broken | --without
entropy | --without exits]`). Run by hand:

    python -m pytest benchmarks/tests/test_ouro_rehearsal.py -q

and, on the chip, the real cell under the unchanged-state step, held to
its own limits (`correct` has to come out false):

    chiprun -- python3 -m benchmarks.tests.test_ouro_rehearsal \
        --cell ouro_2_6b_fit --broken --seed 2147483777
"""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

from benchmarks import harness

# float32 against float32: what is left is the order of the sums (the
# program's scan over the passes and its exits under `lax.map` against the
# reference's Python loops, its log-space exit distribution against the
# reference's products)
LIMITS = {"loss_gap": 1e-4, "grad_norm_gap": 1e-3, "delta_norm_gap": 1e-3,
          "grad_diff_share": 1e-3}
CELL = "ouro_2_6b_fit"
DEVICE_METRICS = {"looped_attention_roofline.train",
                  "looped_attention_time_share.train",
                  "exit_loss_time_share.train"}
COUNTER_METRICS = {"exit_entropy_share.train"}


def rehearse(*flags) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    done = subprocess.run(
        [sys.executable, "-m", "benchmarks.tests.test_ouro_rehearsal",
         *flags], cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=1500)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_tiny_cell_is_correct_and_every_new_reader_reads():
    result = rehearse("--trace", "1")
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    metrics = result["metrics"]
    # the program's gauge is read on any platform: three exits whose gate
    # starts near (1/2, 1/4, 1/4) hold 1.04 of ln 3 = 1.10
    assert COUNTER_METRICS <= set(metrics)
    assert 85 < metrics["exit_entropy_share.train"]["value"] < 100
    # nothing measured on a CPU under a device metric's name; the readers
    # were all called and gave no value
    assert not DEVICE_METRICS & set(metrics)
    assert result["readers_called"] == sorted(DEVICE_METRICS
                                              | COUNTER_METRICS)


def test_unchanged_state_step_is_not_correct():
    assert rehearse("--broken")["correct"] is False


@pytest.mark.parametrize("part", ["entropy", "exits"])
def test_a_step_that_leaves_out_part_of_the_loss_is_not_correct(part):
    """Without the entropy term the loss is 0.1 x 1.04 of 5.5 off; with
    the last exit alone scored, the gate's leaves get no gradient at all:
    leaving out part of the mathematics fails a limit."""
    assert rehearse("--without", part)["correct"] is False


def leave_out(part: str) -> None:
    """Put a score that lacks `part` under every `ExitGatedOutputLayer`:
    "entropy" (beta 0) or "exits" (the last pass's cross-entropy alone)."""
    from deeplearning4j_tpu.nn.layers.recurrent import ExitGatedOutputLayer

    whole = ExitGatedOutputLayer.score_and_state

    def without_entropy(self, params, x, labels, state, mask=None):
        return whole(dataclasses.replace(self, beta=0.0), params, x, labels,
                     state, mask)

    def last_exit_alone(self, params, x, labels, state, mask=None):
        score, _ = whole(dataclasses.replace(self, passes=1), params,
                         self._states(x)[-1], labels, state, mask)
        return score, state

    ExitGatedOutputLayer.score_and_state = {
        "entropy": without_entropy, "exits": last_exit_alone}[part]


def main(argv=None) -> int:
    import argparse
    import time

    from benchmarks import run as bench_run
    from benchmarks.tests.helpers import tiny_spec

    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--broken", action="store_true")
    ap.add_argument("--without", choices=("entropy", "exits"))
    ap.add_argument("--cell", help="a cell of BENCHMARK.json, on the chip "
                    "and under its own limits, instead of the tiny one")
    ap.add_argument("--seed", type=int, default=2 ** 31 + 5)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    with open(os.path.join(harness.ROOT, "BENCHMARK.json"),
              encoding="utf-8") as fh:
        real = json.load(fh)
    called = []
    if args.cell:
        cell = bench_run.load_cell(real, args.cell)
    else:
        # `tiny_spec` keeps the metrics that list a cell of this mix, the
        # real cell's four among them: they read this tiny cell too
        cell = bench_run.load_cell(
            tiny_spec(1, "fit_stream", "ouro_2_6b_tiny"), "tiny_1")
        assert {m["name"] for m in real["per_layer"]
                if m.get("workloads") == [CELL]} <= {
            m["name"] for m in cell["per_layer"]}
        load = harness.load_module

        def noting(*path):
            if path[0] == "layer_metrics" and path[-1][:-3] in (
                    DEVICE_METRICS | COUNTER_METRICS):
                called.append(path[-1][:-3])
            return load(*path)

        harness.load_module = noting
    if args.broken:
        from benchmarks.tests import test_broken_path as broken

        # the model's train step is `MultiLayerNetwork`'s, as `tokens_tiny`'s
        broken.BROKEN.setdefault(cell["config_data"]["model"],
                                 broken.BROKEN["tokens_tiny"])
        broken.break_step(cell["config_data"]["model"])
    if args.without:
        leave_out(args.without)
    result = bench_run.run_cell(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        require_chip=bool(args.cell), t_start=time.perf_counter(),
        limits=None if args.cell else LIMITS)
    if not args.cell:
        result["readers_called"] = sorted(called)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
