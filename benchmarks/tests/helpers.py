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
LIMITS["tokens_tiny_deep"] = LIMITS["tokens_tiny"]
# `tokens_wide`, by hand on one chip: 3 x the largest of four seeds' sound
# readings (0.00604, 0.00181, 0.00100, 0.00852; my chip runs, PR 28). The
# fp8 control read 0.025 to 0.067, 0.043 to 0.153, 0.0008 to 0.025 and
# 0.261 to 0.270 on three of them: orientation, it decided nothing.
LIMITS["tokens_wide"] = {"loss_gap": 0.018, "grad_norm_gap": 0.0054,
                         "delta_norm_gap": 0.003, "grad_diff_share": 0.026}


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
    its last line. By hand, a rehearsal preset at a real size on the chip
    (`tokens_wide`): `--chip` looks for the chip as `run.py` does,
    `--seed` and `--seconds` are the run's, and `--broken` puts
    `test_broken_path.py`'s unchanged-state step under the net."""
    import argparse
    import time

    ap = argparse.ArgumentParser()
    ap.add_argument("chips", type=int)
    ap.add_argument("traffic")
    ap.add_argument("trace", type=int)
    ap.add_argument("config", nargs="?", default="resnet50_tiny")
    ap.add_argument("--chip", action="store_true")
    ap.add_argument("--seed", type=int, default=2 ** 31 + 5)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--broken", action="store_true")
    args = ap.parse_args(argv)
    cell = tiny_cell(args.chips, args.traffic, args.config)
    if args.broken:
        from benchmarks.tests.test_broken_path import break_step

        break_step(cell["config_data"]["model"])
    result = bench_run.run_cell(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        require_chip=args.chip, t_start=time.perf_counter(),
        limits=LIMITS[args.config])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
