#!/usr/bin/env python3
"""By hand, on the chip: one run of a cell through `run.py`'s own path,
then set-up read out of the program's spans.

    python3 benchmarks/tests/record_setup_table.py --workload resnet50_fit \
        --seed 7 --seconds 10 --trace 1 [--out chiprun_out/setup]

Prints, as JSON lines: `setup_table` (for each of the runner's set-up
phases the milliseconds by kind of span, innermost span first, and what no
span covers: `setup_spans.table`; the phases tile `setup_s`, the reference
child taken out as the runner takes it out), `setup_metrics` (this PR's
ten readers, read here so that an untraced run gives them too), and the
result line last, as `run.py` prints it. With `--out` the spans go to
`<out>_<workload>.spans.jsonl`. A rehearsal on the CPU, tiny:
`--workload tiny_1 --test-config tokens_tiny` (no look for a chip)."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import harness, run as bench_run, setup_spans  # noqa: E402

METRICS = ("import_ms.setup", "init_ms.setup", "trace_ms.setup",
           "lower_ms.setup", "compile_or_fetch_ms.setup",
           "xla_compiles.setup", "compile_probe_ms.setup",
           "first_fit_ms.setup", "warmup_sync_ms.setup",
           "xla_compile_spans_in_window.train")


def phases_ns(run: dict, t_start: float) -> list:
    """The runner's set-up phases as (name, start, end) on the spans'
    clock. The reference child ran first and is not set-up: the first
    phase starts where it ended."""
    t = (t_start + run["reference_s"]) * 1e9
    out = []
    for name, seconds in run["setup_phases_s"].items():
        out.append((name, t, t + seconds * 1e9))
        t += seconds * 1e9
    end = (t_start + run["reference_s"] + run["setup_s"]) * 1e9
    out.append(("to_the_window", t, end))   # listeners; traced: the profiler
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out")
    ap.add_argument("--test-config")
    args = ap.parse_args(argv)
    limits = None
    if args.test_config:
        from benchmarks.tests import helpers

        cell = helpers.tiny_cell(1, "fit_stream", args.test_config)
        limits = helpers.LIMITS[args.test_config]
    else:
        with open(os.path.join(harness.ROOT, "BENCHMARK.json"),
                  encoding="utf-8") as fh:
            cell = bench_run.load_cell(json.load(fh), args.workload)
    said, say = {}, harness.say

    def keep(kind, **facts):
        said[kind] = facts
        say(kind, **facts)

    harness.say = keep
    try:
        result = bench_run.run_cell(
            cell, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), t_start=T_START,
            require_chip=not args.test_config, limits=limits)
    except harness.NoChip as e:
        print(f"record_setup_table.py: {e}", file=sys.stderr)
        return 2
    finally:
        harness.say = say
    source = setup_spans.held()
    parts = setup_spans.split(source)
    if parts is not None:
        setup, _ = parts
        rows = setup_spans.table(setup, phases_ns(said["run"], T_START))
        total = {}
        for phase in rows.values():
            for row, ms in phase.items():
                total[row] = total.get(row, 0.0) + ms
        say("setup_table", workload=args.workload, seed=args.seed,
            setup_s=said["run"]["setup_s"], spans=len(setup),
            recorded=source[1], phases=rows, total=total,
            total_ms=sum(total.values()))
    say("setup_metrics", **{m: setup_spans.read(m, source)
                            for m in METRICS})
    if args.out and source is not None:
        path = f"{args.out}_{args.workload}.spans.jsonl"
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w", encoding="utf-8") as fh:
            fh.write(json.dumps({"span_clock": {
                "recorded": source[1], "capacity": source[2],
                "t_start_ns": T_START * 1e9, "run": {
                    k: said["run"][k] for k in (
                        "workload", "seed", "setup_s", "reference_s",
                        "setup_phases_s")}}}) + "\n")
            for s in source[0]:
                fh.write(json.dumps(s) + "\n")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
