#!/usr/bin/env python3
"""A traced window with the program's spans on the device trace's clock,
read by `benchmarks/span_reduce.py`. Run by hand on the chip; no test and
no driver calls it.

    chiprun -- python3 benchmarks/tests/record_scoped_trace.py
        how `tests/data/tiny_tpu_scoped.xplane.pb` and `.spans.jsonl` were
        recorded (PR 25): the tiny test cell (all 50 layers at 32x32,
        batch 8), cut to the device plane's first 4,000 `XLA Ops` events
        with the stats the reductions read (`tf_op` carries the scope),
        its `XLA Modules` events, and the spans that started by then.

    chiprun -- python3 benchmarks/tests/record_scoped_trace.py \
            --workload resnet50_fit --seed 11 --seconds 10 [--traced 30]
        a real cell's window: prints the `clock`, `spans`, `gaps`, `layers`
        and `metrics` lines and keeps nothing but the spans file. With
        `--traced` longer than the window the profiler is stopped when
        `fit()` ends, so the trace holds the whole window, its drain too,
        and no stop stalls the fit loop inside it.

The window is the runner's own (`runners/fit.py`: `prepare`, `_stream`, one
`fit()` of one epoch), without the reference child and its comparison. The
trace is the program's `utils/profiling.DeviceTrace`, opened before `fit()`
as the runner's `TraceWindow` is and stopped `trace_seconds` into the
window from the fit loop's own thread: what an operator gets from
`ProfilerListener`.
"""

import argparse
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import (  # noqa: E402
    harness, run as bench_run, span_reduce, trace_reduce, xplane_schema,
)
from benchmarks.tests import record_trace  # noqa: E402
from benchmarks.tests.helpers import tiny_cell  # noqa: E402

KEEP_OPS = 4000      # some three steps of the tiny cell
KEEP_STATS = ("hlo_category", "flops", "bytes_accessed", "tf_op",
              "memory_access_breakdown")


def cut(space):
    """`record_trace.cut` with this file's `KEEP_OPS` and `KEEP_STATS` (it
    reads both from its module), and of the program runs only those that
    started by the last op kept."""
    record_trace.KEEP, record_trace.KEEP_STATS = KEEP_OPS, KEEP_STATS
    small = record_trace.cut(space)
    for plane in small.planes:
        lines = {line.name: line for line in plane.lines}
        ops = lines[trace_reduce.OPS_LINE]
        last = max(ops.timestamp_ns * 1000 + ev.offset_ps + ev.duration_ps
                   for ev in ops.events)
        runs = lines[trace_reduce.MODULES_LINE]
        kept = [ev for ev in runs.events
                if runs.timestamp_ns * 1000 + ev.offset_ps <= last]
        del runs.events[:]
        runs.events.extend(kept)
    return small


def traced_window(cell, seed: int, seconds: float, traced_s: float,
                  out_dir: str):
    """(xplane path, spans path, devices) of one traced window."""
    from deeplearning4j_tpu.utils.profiling import DeviceTrace

    runner = harness.load_module("runners", "fit.py")
    harness.enable_compile_cache()
    used = harness.require_chips(cell["chips"])
    ready = runner.prepare(cell, seed, used)
    stream = runner._stream(ready["pool"], seconds=seconds)
    shutil.rmtree(out_dir, ignore_errors=True)
    trace = DeviceTrace(out_dir)

    class Stop(harness.Listener):
        def iteration_done(self, model, iteration, epoch, score):
            if trace.active and stream.t_first is not None and \
                    time.perf_counter() - stream.t_first >= traced_s:
                trace.stop()

        def on_fit_end(self, model):
            if trace.active:
                trace.stop()

    ready["net"].set_listeners(Stop())
    trace.start()
    ready["trainer"].fit(stream, epochs=1)
    trace.write_spans()         # again: now with the whole window
    print(json.dumps({"window": {
        "steps": stream.handed, "global_batch": ready["global_batch"],
        "items_per_s_traced": stream.handed * ready["global_batch"]
        / (time.perf_counter() - stream.t_first)}}), flush=True)
    return (trace_reduce.find_xplane(out_dir), trace.spans_path,
            [d.id for d in used], used[0].device_kind)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--traced", type=float, help="seconds of the window "
                    "under the profiler (default: the mix's trace_seconds)")
    args = ap.parse_args(argv)
    out = os.path.join(harness.ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    if args.workload:
        with open(os.path.join(harness.ROOT, "BENCHMARK.json"),
                  encoding="utf-8") as fh:
            cell = bench_run.load_cell(json.load(fh), args.workload)
    else:
        cell = tiny_cell(chips=1, traffic="fit_stream")
        cell["traffic_data"] = dict(cell["traffic_data"], trace_seconds=0.5)
    traced_s = args.traced or min(
        float(cell["traffic_data"]["trace_seconds"]), args.seconds / 2.0)
    pb, spans_path, devices, kind = traced_window(
        cell, args.seed, args.seconds, traced_s,
        os.path.join(harness.SCRATCH, "scoped_trace"))
    space = xplane_schema.read_xspace(pb)
    header, spans = span_reduce.read_span_file(spans_path)
    for name, facts in span_reduce.report(
            space, spans, header, devices=devices,
            peaks=harness.load_json("peaks.json").get(kind)).items():
        print(json.dumps({name: facts}), flush=True)
    if args.workload:
        shutil.copy(spans_path, os.path.join(
            out, f"{args.workload}.spans.jsonl"))
        return 0
    small = cut(space)
    last = max(line.timestamp_ns * 1000 + ev.offset_ps + ev.duration_ps
               for plane in small.planes for line in plane.lines
               if line.name == trace_reduce.OPS_LINE for ev in line.events)
    zero = span_reduce.clock_link(space, header["beacons_ns"])["zero_ns"]
    kept = [s for s in spans if s["start_ns"] <= zero + last / 1000.0]
    with open(os.path.join(out, "tiny_tpu_scoped.xplane.pb"), "wb") as fh:
        fh.write(small.SerializeToString())
    with open(os.path.join(out, "tiny_tpu_scoped.spans.jsonl"), "w",
              encoding="utf-8") as fh:
        fh.write(json.dumps({"span_clock": header}) + "\n")
        fh.writelines(json.dumps(s) + "\n" for s in kept)
    print(space.ByteSize(), "bytes recorded,", small.ByteSize(), "kept;",
          len(spans), "spans,", len(kept), "kept")
    return 0


if __name__ == "__main__":
    sys.exit(main())
