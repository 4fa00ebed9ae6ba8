#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload resnet50_fit --seed 7 \
        --seconds 10 --trace 0

Finds the cell in `BENCHMARK.json`, its configuration in
`benchmarks/configs/`, its traffic mix in `benchmarks/traffic/`, and the
runner the mix names in `benchmarks/runners/`; the runner drives the
program. With `--trace 1` every per-layer metric the cell lists is read by
its own file in `benchmarks/layer_metrics/`. Everything worth keeping is
printed as JSON lines; the LAST line is the result object. Needs the TPU
chips the cell asks for: without them it exits 2 and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import harness  # noqa: E402


def load_cell(spec: dict, workload: str) -> dict:
    """The cell's entry with its configuration, mix and metric lists."""
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in BENCHMARK.json"
                         f" (have {sorted(cells)})")
    cell = dict(cells[workload])
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cell["config_file"] = entry["file"]
    cell["traffic_file"] = os.path.join(
        os.path.relpath(harness.BENCH_DIR, harness.ROOT), "traffic",
        cell["traffic"] + ".json")
    cell["config_data"] = harness.load_json(harness.ROOT, entry["file"])
    cell["traffic_data"] = harness.load_json(harness.ROOT,
                                             cell["traffic_file"])

    def listed(metric):
        return "workloads" not in metric or workload in metric["workloads"]

    cell["end_to_end"] = [m for m in spec["end_to_end"] if listed(m)]
    e2e = {m["name"] for m in cell["end_to_end"]}
    cell["per_layer"] = [m for m in spec["per_layer"]
                         if listed(m) and m["moves"] in e2e]
    return cell


def run_cell(cell: dict, *, seed: int, seconds: float, trace: bool,
             require_chip: bool = True, t_start: float | None = None,
             limits: dict | None = None) -> dict:
    """Drive one run of a cell and return the result object."""
    runner = harness.load_module(
        "runners", cell["traffic_data"]["runner"] + ".py")
    return runner.run(cell, seed=seed, seconds=seconds, trace=trace,
                      require_chip=require_chip,
                      t_start=T_START if t_start is None else t_start,
                      limits=limits)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    with open(os.path.join(harness.ROOT, "BENCHMARK.json"),
              encoding="utf-8") as fh:
        spec = json.load(fh)
    cell = load_cell(spec, args.workload)
    try:
        result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace))
    except harness.NoChip as e:
        print(f"run.py: {e}. Nothing was measured.", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
