#!/usr/bin/env python3
"""Why a cell's rate differs from seed to seed (by hand, on the chip; no
test calls it):

    chiprun -- python3 benchmarks/tests/probe_step_times.py \
        --workload deepseek_v2_fit --seeds 2590000013,3333333391 --steps 20

The runner's own `prepare()` (weights, pool, the three warm-up steps), then
the window's batches one `fit()` a step, so that every step ends in the
epoch's host sync: prints each step's wall time (the device's step and one
sync) beside the routing gauges `fit()` has just published, by layer. A
step that crosses a tier of `parallel/moe._row_tiers` shows as a jump in
its time where `moe_pairs_held` passes the tier's rows.
(`--test-config deepseek_v2_tiny` rehearses it on the CPU.)
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import harness  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402

GAUGES = ("moe_pairs_held", "moe_tokens_held", "moe_load_max")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    which = ap.add_mutually_exclusive_group(required=True)
    which.add_argument("--workload")
    which.add_argument("--test-config")
    ap.add_argument("--seeds", default="2590000013,3333333391")
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args(argv)
    import jax

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.observe import get_registry

    if args.test_config:
        from benchmarks.tests.helpers import tiny_cell

        cell = tiny_cell(1, "fit_stream", args.test_config)
        used = jax.devices()[:1]
    else:
        with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as fh:
            cell = bench_run.load_cell(json.load(fh), args.workload)
        harness.enable_compile_cache()
        used = harness.require_chips(cell["chips"])
    runner = harness.load_module("runners",
                                 cell["traffic_data"]["runner"] + ".py")
    for seed in map(int, args.seeds.split(",")):
        ready = runner.prepare(cell, seed, used)
        net, pool = ready["net"], ready["pool"]
        net.set_listeners()
        for i in range(args.steps):
            x, y = pool[i % len(pool)]
            t0 = time.perf_counter()
            net.fit(DataSet(x, y))
            row = {"seed": seed, "step": i,
                   "ms": 1000 * (time.perf_counter() - t0)}
            for g in get_registry().series():
                if g.name in GAUGES:
                    row.setdefault(g.name, {})[
                        dict(g.labels).get("layer", "")[:6]] = int(g.value)
            print(json.dumps(row), flush=True)
        # a seed's net off the device before the next one's is built
        for leaf in jax.tree_util.tree_leaves(
                (net.params_tree, net.updater_state, net.state_tree)):
            leaf.delete()
        del ready, net
    return 0


if __name__ == "__main__":
    sys.exit(main())
