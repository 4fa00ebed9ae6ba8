"""Shared by the rehearsals: the tiny cell, built the way `run.py` builds
a real one, from files of the tests' own."""

from __future__ import annotations

import json
import os

from benchmarks import harness, run as bench_run

# `resnet50_tiny` proves the path, not the arithmetic: at 32x32 and 8 rows
# a chip the first batch norm's shift gradient alone reads 1.2 off in
# bfloat16. `tokens_tiny` runs in float32 and is held to float32 tightness.
LIMITS = {"resnet50_tiny": {"loss_gap": 1.0, "grad_norm_gap": 10.0,
                            "delta_norm_gap": 1.0, "grad_diff_share": 10.0},
          "tokens_tiny": {"loss_gap": 1e-4, "grad_norm_gap": 1e-4,
                          "delta_norm_gap": 1e-4, "grad_diff_share": 1e-3}}


def tiny_spec(chips: int, traffic: str, config: str = "resnet50_tiny") -> dict:
    """`BENCHMARK.json` with one tiny cell in the real ones' place. A
    metric that lists its cells stays where one of them has this mix."""
    with open(os.path.join(harness.ROOT, "BENCHMARK.json"),
              encoding="utf-8") as fh:
        spec = json.load(fh)
    mixes = {c["name"]: c["traffic"] for c in spec["workloads"]}
    spec["configs"] = [{
        "name": config, "file": f"benchmarks/tests/configs/{config}.json"}]
    spec["workloads"] = [{"name": f"tiny_{chips}", "config": config,
                          "traffic": traffic, "chips": chips}]
    for kind in ("end_to_end", "per_layer"):
        spec[kind] = [m for m in spec[kind] if "workloads" not in m
                      or traffic in {mixes[w] for w in m.pop("workloads")}]
    return spec


def tiny_cell(chips: int, traffic: str, config: str = "resnet50_tiny") -> dict:
    return bench_run.load_cell(tiny_spec(chips, traffic, config),
                               f"tiny_{chips}")


def main(argv=None) -> int:
    """`python -m benchmarks.tests.helpers <chips> <traffic> <trace>
    [<config>]`: one rehearsal of a tiny cell on whatever platform JAX
    resolves, without the harness's look for a chip. Prints the result as
    its last line."""
    import sys
    import time

    chips, traffic, trace, *rest = (argv or sys.argv[1:])[:4]
    config = rest[0] if rest else "resnet50_tiny"
    cell = tiny_cell(int(chips), traffic, config)
    result = bench_run.run_cell(
        cell, seed=2 ** 31 + 5, seconds=3.0, trace=bool(int(trace)),
        require_chip=False, t_start=time.perf_counter(),
        limits=LIMITS[config])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
