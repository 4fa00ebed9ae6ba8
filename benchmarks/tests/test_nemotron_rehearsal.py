"""CPU rehearsal of the `nemotron_3_super` files at a tiny size
(`configs/nemotron_3_super_tiny.json`: two Mamba-2 layers over one of two
groups of B and C with the gated norm per group at four chunks of 32
tokens, a GQA attention layer over two of four heads, two 3-of-8
sigmoid-routed relu2 expert layers in a latent of 16 with four held
beside a shared relu2 expert, an untied head and a `*E` prediction
module, ids past 256), through `run_cell` like `granite_4_0_h_small_tiny`,
in float32 and held to float32 limits; and the same under
`test_broken_path.py`'s unchanged-state step, which has to read not
correct. A process each (`python -m benchmarks.tests.
test_nemotron_rehearsal [--broken]`). Run by hand:

    python -m pytest benchmarks/tests/test_nemotron_rehearsal.py -q

and, on the chip, the real cell under the unchanged-state step, held to
its own limits (`correct` has to come out false):

    chiprun -- python3 -m benchmarks.tests.test_nemotron_rehearsal \
        --cell nemotron_3_super_fit --broken --seed 2147483777
"""

import json
import os
import subprocess
import sys

from benchmarks import harness

# float32 against float32: what is left is the order of the sums (the
# program's chunked scan against the reference's token-at-a-time
# recurrence, its sorted, grouped expert products against the reference's
# every-expert-on-every-token, both heads' log-softmax) and a pair that
# falls the other side of a tie (none at this size)
LIMITS = {"loss_gap": 1e-4, "grad_norm_gap": 1e-3, "delta_norm_gap": 1e-3,
          "grad_diff_share": 1e-3}
CELL = "nemotron_3_super_fit"
DEVICE_METRICS = {"latent_moe_time_share.train",
                  "latent_moe_routed_time_share.train",
                  "latent_moe_projection_time_share.train",
                  "grouped_ssm_time_share.train",
                  "grouped_ssm_mixer_time_share.train",
                  "mtp_time_share.train", "gqa_attention_roofline.train",
                  "trunk_embedding_time_share.train"}
COUNTER_METRICS = {"latent_moe_held_pair_share.train",
                   "latent_moe_pairs_dropped.train",
                   "latent_moe_rows_visited_share.train",
                   "latent_moe_rows_gathered_share.train",
                   "grouped_ssm_chunk_carry_share.train"}


def rehearse(*flags) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    done = subprocess.run(
        [sys.executable, "-m", "benchmarks.tests.test_nemotron_rehearsal",
         *flags], cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=1500)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_tiny_cell_is_correct_and_every_new_reader_reads():
    result = rehearse("--trace", "1")
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    metrics = result["metrics"]
    # the program's counters are read on any platform: 4 of 8 experts
    # held in three expert layers, the prediction module's among them
    assert COUNTER_METRICS <= set(metrics)
    assert metrics["latent_moe_pairs_dropped.train"]["value"] == 0
    assert 0 < metrics["latent_moe_held_pair_share.train"]["value"] < 100
    assert 0 < metrics["latent_moe_rows_visited_share.train"]["value"] <= 100
    assert 0 < metrics["latent_moe_rows_gathered_share.train"]["value"] <= 100
    assert 0 < metrics["grouped_ssm_chunk_carry_share.train"]["value"] < 100
    # nothing measured on a CPU under a device metric's name; the readers
    # were all called and gave no value
    assert not DEVICE_METRICS & set(metrics)
    # (some more than once: two read through a sibling of this cell's,
    # seven through an accepted reader)
    assert set(result["readers_called"]) == DEVICE_METRICS | COUNTER_METRICS


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
    called = []
    if args.cell:
        cell = bench_run.load_cell(real, args.cell)
    else:
        # `tiny_spec` keeps the metrics that list a cell of this mix, the
        # real cell's thirteen among them: they read this tiny cell too
        cell = bench_run.load_cell(
            tiny_spec(1, "fit_stream", "nemotron_3_super_tiny"), "tiny_1")
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
