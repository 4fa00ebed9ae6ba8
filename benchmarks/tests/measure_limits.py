#!/usr/bin/env python3
"""How the limits in `benchmarks/limits/<cell>.json` were read (by hand,
on the chip; no test calls it):

    chiprun -- python3 benchmarks/tests/measure_limits.py \
        --workload resnet50_fit --seeds 12 --control-seeds 4

(`--test-config tokens_wide` reads a rehearsal preset of
`benchmarks/tests/configs/` on one chip under `fit_stream` instead of a
cell.) In ONE process, at the cell's own size, for each seed: the float32
reference, the control (the reference in float8 put in the program's
place), the reference in bfloat16 for orientation, and the program's own
first steps through the runner's `prepare()`. Prints, for every number
compared, the sound runs' largest and the control's smallest, and writes
every per-leaf reading to `chiprun_out/limits_<cell>.json`.
"""

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import compare, harness, reference_main  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    which = ap.add_mutually_exclusive_group(required=True)
    which.add_argument("--workload")
    which.add_argument("--test-config")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=4)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 11)
    ap.add_argument("--control-modes", default="float8,bfloat16",
                    help="float8 is the control; bfloat16 is orientation")
    args = ap.parse_args(argv)
    import jax

    if args.test_config:
        from benchmarks.tests.helpers import tiny_cell

        cell = tiny_cell(1, "fit_stream", args.test_config)
        args.workload = args.test_config
    else:
        with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as fh:
            cell = bench_run.load_cell(json.load(fh), args.workload)
    config, traffic = cell["config_data"], cell["traffic_data"]
    steps = int(traffic["warmup_steps"])
    harness.enable_compile_cache()
    used = harness.require_chips(cell["chips"])
    runner = harness.load_module("runners", traffic["runner"] + ".py")
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    rows = {}
    for i, seed in enumerate(seeds):
        modes = ["float32"] + (args.control_modes.split(",")
                               if i < args.control_seeds else [])
        rows[seed] = {m: reference_main.reference_numbers(
            config, traffic, chips=cell["chips"], seed=seed, steps=steps,
            mode=m) for m in modes}
        gc.collect()
        print(json.dumps({"seed": seed, "reference_loss":
                          rows[seed]["float32"]["loss"]}), flush=True)
    for seed in seeds:
        ready = runner.prepare(cell, seed, used)
        rows[seed]["program"] = ready["program"]
        # a seed's net off the device before the next one's is built,
        # whoever still holds the object: two do not fit where one fills
        # the chip
        net = ready["net"]
        for leaf in jax.tree_util.tree_leaves(
                (net.params_tree, net.updater_state, net.state_tree)):
            leaf.delete()
        del ready, net
        gc.collect()
    table = {}
    for seed, row in rows.items():
        for who in ("program", "float8", "bfloat16"):
            if who in row:
                numbers = compare.first_steps(row[who], row["float32"])
                row[who]["leaf_diff_shares"] = compare.leaf_diff_shares(
                    row[who]["grad_sample"], row["float32"]["grad_sample"])
                table.setdefault(who, {})[seed] = numbers
                print(json.dumps({"seed": seed, "who": who, **{
                    k: v["value"] for k, v in numbers.items()},
                    "grad_diff_whole":
                        numbers["grad_diff_share"]["whole_gradient"],
                    "leaves": [numbers["grad_norm_gap"]["leaf"],
                               numbers["delta_norm_gap"]["leaf"]]}),
                      flush=True)
    for name in ("loss_gap", "grad_norm_gap", "delta_norm_gap",
                 "grad_diff_share"):
        sound = [t[name]["value"] for t in table["program"].values()]
        line = {"number": name, "program_largest": max(sound),
                "program_smallest": min(sound)}
        for who in ("float8", "bfloat16"):
            if who in table:
                vals = [t[name]["value"] for t in table[who].values()]
                line[who + "_smallest"] = min(vals)
                line[who + "_largest"] = max(vals)
        print(json.dumps(line), flush=True)
    out = os.path.join(harness.ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"limits_{args.workload}.json"), "w") as fh:
        json.dump({str(seed): {who: {k: v for k, v in r.items()
                                     if k != "grad_sample"}
                               for who, r in row.items()}
                   for seed, row in rows.items()}, fh)
    return 0


if __name__ == "__main__":
    sys.exit(main())
