"""Shared by the rehearsals: the tiny cell, built the way `run.py` builds
a real one, from files of the tests' own."""

from __future__ import annotations

import json
import os

from benchmarks import harness, run as bench_run

# The rehearsals prove the path, not the arithmetic: at 32x32 and 8 rows a
# chip the first batch norm's shift gradient alone reads 1.2 off in bfloat16.
TINY_LIMITS = {"loss_gap": 1.0, "grad_norm_gap": 10.0, "delta_norm_gap": 1.0,
               "grad_diff_share": 10.0}


def tiny_spec(chips: int, traffic: str) -> dict:
    with open(os.path.join(harness.ROOT, "BENCHMARK.json"),
              encoding="utf-8") as fh:
        spec = json.load(fh)
    spec["configs"] = [{
        "name": "resnet50_tiny",
        "file": "benchmarks/tests/configs/resnet50_tiny.json"}]
    spec["workloads"] = [{"name": f"tiny_{chips}", "config": "resnet50_tiny",
                          "traffic": traffic, "chips": chips}]
    for metric in spec["end_to_end"] + spec["per_layer"]:
        metric.pop("workloads", None)
    return spec


def tiny_cell(chips: int, traffic: str) -> dict:
    return bench_run.load_cell(tiny_spec(chips, traffic), f"tiny_{chips}")


def main(argv=None) -> int:
    """`python -m benchmarks.tests.helpers <chips> <traffic> <trace>`: one
    rehearsal of the tiny cell on whatever platform JAX resolves, without
    the harness's look for a chip. Prints the result as its last line."""
    import sys
    import time

    chips, traffic, trace = (argv or sys.argv[1:])[:3]
    cell = tiny_cell(int(chips), traffic)
    result = bench_run.run_cell(
        cell, seed=2 ** 31 + 5, seconds=3.0, trace=bool(int(trace)),
        require_chip=False, t_start=time.perf_counter(), limits=TINY_LIMITS)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
