#!/usr/bin/env python3
"""How `benchmarks/tests/data/tiny_tpu.xplane.pb` was recorded (PR 24):

    chiprun -- python3 benchmarks/tests/record_trace.py

runs the tiny test cell (all 50 layers at 32x32, batch 8) through the real
runner on one chip with `--trace 1`, keeps the profiler's file, cuts it to
the device plane's first `KEEP` `XLA Ops` events (with their metadata) and
the `XLA Modules` events, and writes that to `chiprun_out/`.
Run by hand; no test calls it.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import harness, run as bench_run, trace_reduce  # noqa: E402
from benchmarks.tests.helpers import tiny_cell  # noqa: E402


KEEP = 800
KEEP_STATS = ("hlo_category", "flops", "bytes_accessed")


def cut(space):
    """A small XSpace with what the reduction reads, and no more."""
    small = type(space)()
    for plane in space.planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PLANE):
            continue
        out = small.planes.add(id=plane.id, name=plane.name)
        for line in plane.lines:
            if line.name not in (trace_reduce.OPS_LINE,
                                 trace_reduce.MODULES_LINE):
                continue
            events = list(line.events)[:KEEP]
            if not events:
                continue
            kept = out.lines.add(id=line.id, name=line.name,
                                 timestamp_ns=line.timestamp_ns)
            for ev in events:
                kept.events.add().CopyFrom(ev)
                md = plane.event_metadata[ev.metadata_id]
                slim = out.event_metadata[ev.metadata_id]
                if slim.id:
                    continue
                slim.id, slim.name = md.id, md.name[:120]
                slim.display_name = md.display_name
                for st in md.stats:
                    if plane.stat_metadata[st.metadata_id].name in KEEP_STATS:
                        slim.stats.add().CopyFrom(st)
                for st in list(slim.stats) + list(ev.stats):
                    out.stat_metadata[st.metadata_id].CopyFrom(
                        plane.stat_metadata[st.metadata_id])
                    if st.HasField("ref_value"):
                        out.stat_metadata[st.ref_value].CopyFrom(
                            plane.stat_metadata[st.ref_value])
    return small


def main() -> int:
    cell = tiny_cell(chips=1, traffic="fit_stream")
    cell["traffic_data"] = dict(cell["traffic_data"], trace_seconds=0.5,
                                trace_skip_s=0.0)
    seen, reduce_space = [], trace_reduce.reduce_space
    trace_reduce.reduce_space = lambda space, **kw: (
        seen.append(space), reduce_space(space, **kw))[1]
    result = bench_run.run_cell(
        cell, seed=7, seconds=2.0, trace=True, require_chip=True,
        t_start=time.perf_counter(),
        limits={"loss_gap": 10, "grad_norm_gap": 10, "delta_norm_gap": 10,
                "grad_diff_share": 10})
    print(json.dumps(result))
    out = os.path.join(harness.ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    small = cut(seen[0]).SerializeToString()
    with open(os.path.join(out, "tiny_tpu.xplane.pb"), "wb") as fh:
        fh.write(small)
    print(seen[0].ByteSize(), "bytes recorded,", len(small), "kept")
    return 0


if __name__ == "__main__":
    sys.exit(main())
