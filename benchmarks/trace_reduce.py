"""From a profiler trace (`*.xplane.pb`) to busy intervals, per-op time
and idle gaps.

What a TPU v5e trace holds (looked at by hand, PR 24): one plane per
chip, `/device:TPU:<n>`, whose line `XLA Ops` has one event per executed
HLO instruction. An event's metadata carries the instruction's text as
`name`, its short name as `display_name` (`multiply_reduce_fusion.2`), and
the stats `hlo_category` ("convolution fusion", "loop fusion", "copy-done",
"all-reduce", ...), `flops` and `bytes_accessed`; the event itself has
only its offset and duration in picoseconds. `XLA Modules` has one event
per program run (`jit_step_fn(<fingerprint>)`), `Steps` one per step,
`Async XLA Ops` the spans of asynchronous copies. An event starts at its
line's `timestamp_ns` plus its `offset_ps`. Host threads are lines of
`/host:CPU`; the benchmark traces with the host tracer off (see
`runners/fit.py`), so idle gaps are named from the device's own lines.

    python benchmarks/trace_reduce.py <file-or-dir> [--summary]
"""

from __future__ import annotations

import glob
import json
import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmarks import xplane_schema  # noqa: E402

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
BEACON = "jit_dl4j_trace_beacon"    # the clock link's program: no work
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
GAP_FLOOR_PS = 20_000_000      # 20 us: shorter gaps are launch latency
TOP = 10


def find_xplane(directory: str):
    found = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def union(intervals):
    """Merged, sorted [start, end] pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def self_times(events):
    """Each event with `self`: its time less that of the events nested
    inside it, so that a loop's body is not counted twice."""
    out, stack = [], []
    for ev in sorted(events, key=lambda e: (e["start"], -e["dur"])):
        while stack and stack[-1]["end"] <= ev["start"]:
            stack.pop()
        row = dict(ev, self=ev["dur"], end=ev["start"] + ev["dur"])
        if stack:
            stack[-1]["self"] -= ev["dur"]
        stack.append(row)
        out.append(row)
    return out


def op_kind(category: str) -> str:
    """'convolution', 'collective' or 'other', from the trace's own
    `hlo_category`: the compiler's word, no HLO text is parsed."""
    if any(c in category for c in COLLECTIVES):
        return "collective"
    if "convolution" in category:
        return "convolution"
    return "other"


def _stat(stat, stat_names):
    for field in ("str_value", "int64_value", "uint64_value",
                  "double_value"):
        if stat.HasField(field):
            return getattr(stat, field)
    if stat.HasField("ref_value"):
        return stat_names[stat.ref_value].name
    return ""


def device_events(plane):
    """The `XLA Ops` events of one device plane, times in picoseconds."""
    names = plane.stat_metadata
    meta = {}
    for line in plane.lines:
        if line.name != OPS_LINE:
            continue
        base = line.timestamp_ns * 1000
        for ev in line.events:
            if ev.metadata_id not in meta:
                md = plane.event_metadata[ev.metadata_id]
                category = ""
                for st in md.stats:
                    if names[st.metadata_id].name == "hlo_category":
                        category = str(_stat(st, names)).lower()
                short = md.display_name or \
                    md.name.split(" = ")[0].lstrip("%")
                meta[ev.metadata_id] = (short, category)
            short, category = meta[ev.metadata_id]
            yield {"name": short, "category": category,
                   "start": base + ev.offset_ps, "dur": ev.duration_ps}


def module_spans(plane):
    """(start, end, name) of every program run on one device plane, the
    name without its fingerprint: `jit_step_fn`."""
    spans = []
    for line in plane.lines:
        if line.name != MODULES_LINE:
            continue
        base = line.timestamp_ns * 1000
        for ev in line.events:
            name = plane.event_metadata[ev.metadata_id].name.split("(")[0]
            start = base + ev.offset_ps
            spans.append((start, start + ev.duration_ps, name))
    return sorted(spans)


def outside_beacons(plane, events) -> list:
    """`events` of one device plane less those that ran inside a run of the
    clock link's beacon program: a trace with beacons reads as one without."""
    beacons = [(s, e) for s, e, name in module_spans(plane) if name == BEACON]
    return [ev for ev in events
            if not any(s <= ev["start"] < e for s, e in beacons)]


def gap_name(start, end, spans) -> str:
    """What an idle gap lay in, as far as the device's own trace says:
    `inside <program>` if a program run spans it (the chip waited within a
    program), else `before <program>` for the next program to start (the
    chip waited for the host to enqueue it)."""
    for s, e, name in spans:
        if s <= start and end <= e:
            return f"inside {name}"
    for s, _, name in spans:
        if s >= end - 1:
            return f"before {name}"
    return "after the last program"


def line_counts(space) -> dict:
    """Events on every line of every plane: what made a trace large."""
    return {plane.name: {line.name: len(line.events)
                         for line in plane.lines if line.events}
            for plane in space.planes if any(l.events for l in plane.lines)}


def main_module_starts(plane, t0, t1):
    """(name, sorted starts) of the program that took most of [t0, t1) on
    one chip, counting the runs that started inside it: the train step."""
    time_in, starts = {}, {}
    for start, end, name in module_spans(plane):
        if t0 <= start < t1 and name != BEACON:
            time_in[name] = time_in.get(name, 0) + end - start
            starts.setdefault(name, []).append(start)
    if not starts:
        return None, []
    name = max(time_in, key=time_in.get)
    return name, sorted(starts[name])


def runs_per_s(starts):
    """Runs a second of a program from its runs' starts in picoseconds:
    (runs - 1) over (last start - first start), which is exact for a
    program that runs back to back. Counting the runs that START in a
    window over the window's length counts one run too many (4.5% at 22
    runs: chip runs, PR 25). None under two runs."""
    if len(starts) < 2 or starts[-1] <= starts[0]:
        return None
    return (len(starts) - 1) / ((starts[-1] - starts[0]) / 1e12)


def _periods_ms(starts):
    """[shortest, median, longest] start-to-start time of a program's runs
    on the cell's first chip, in ms: one slow step shows here."""
    gaps = sorted((b - a) / 1e9 for a, b in zip(starts, starts[1:]))
    return [gaps[0], gaps[len(gaps) // 2], gaps[-1]] if gaps else None


def reduce_space(space, devices=None, skip_s: float = 0.0):
    """The reduction of one trace; None if no op ran on a device in it.

    The window runs from the first op's start, plus `skip_s` (the
    pipeline's fill at the start of a `fit()`), to the last op's end on ANY
    chip of the cell; ops are clipped to it, and the clock link's beacon
    runs (`BEACON`) are left out, so a trace with them reads as one
    without. `busy_s` is the union of a chip's op intervals, averaged over
    the chips; `idle_share_worst` is that of the idlest chip. Seconds in
    `breakdown` are summed over the chips and divided by their number.
    `main_module_runs_per_s` is `runs_per_s` of the train step's runs that
    start in the window, mean over the chips.
    """
    per_device = {}
    for plane in space.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        ordinal = int(plane.name[len(DEVICE_PLANE):].split()[0])
        if devices is not None and ordinal not in devices:
            continue
        events = outside_beacons(plane, device_events(plane))
        if events:
            per_device[ordinal] = events
    if not per_device:
        return None
    t0 = min(e["start"] for evs in per_device.values() for e in evs) \
        + int(skip_s * 1e12)
    t1 = max(e["start"] + e["dur"] for evs in per_device.values()
             for e in evs)
    for ordinal, events in per_device.items():
        clipped = []
        for e in events:
            start = max(e["start"], t0)
            if e["start"] + e["dur"] > start:
                clipped.append(dict(e, start=start,
                                    dur=e["start"] + e["dur"] - start))
        per_device[ordinal] = clipped
    window = t1 - t0
    if window <= 0 or not all(per_device.values()):
        return None
    main = [main_module_starts(plane, t0, t1) for plane in space.planes
            if plane.name.startswith(DEVICE_PLANE)
            and int(plane.name[len(DEVICE_PLANE):].split()[0])
            in per_device]
    spans = {}
    for plane in space.planes:
        if plane.name.startswith(DEVICE_PLANE):
            spans[int(plane.name[len(DEVICE_PLANE):].split()[0])] = \
                module_spans(plane)
    busy, kinds, ops, cats, gaps = {}, {}, {}, {}, {}
    for ordinal, events in per_device.items():
        merged = union((e["start"], e["start"] + e["dur"]) for e in events)
        busy[ordinal] = sum(e - s for s, e in merged)
        kind = {"convolution": 0, "collective": 0, "other": 0}
        for row in self_times(events):
            kind[op_kind(row["category"])] += row["self"]
            ops[row["name"]] = ops.get(row["name"], 0) + row["self"]
            cats[row["category"]] = cats.get(row["category"], 0) \
                + row["self"]
        kinds[ordinal] = kind
        edges = [[t0, t0]] + merged + [[t1, t1]]
        for (_, e), (s, _) in zip(edges, edges[1:]):
            if s - e >= GAP_FLOOR_PS:
                name = gap_name(e, s, spans.get(ordinal, []))
                gaps[name] = gaps.get(name, 0) + (s - e)
    n = len(per_device)
    rates = [r for r in (runs_per_s(starts) for _, starts in main)
             if r is not None]
    worst = max(per_device, key=lambda d: window - busy[d])

    def top(table):
        rows = sorted(table.items(), key=lambda kv: -kv[1])[:TOP]
        return [[name, ps / n / 1e12] for name, ps in rows]

    return {
        "devices": sorted(per_device),
        "window_s": window / 1e12,
        "main_module": main[0][0],
        "main_module_runs": sum(len(starts) for _, starts in main)
        / len(main),
        "main_module_runs_per_s": sum(rates) / len(rates) if rates else None,
        "main_module_period_ms": _periods_ms(main[0][1]),
        "busy_s": sum(busy.values()) / n / 1e12,
        "idle_share_worst": 1.0 - busy[worst] / window,
        "busy_by_device_s": {str(d): busy[d] / 1e12 for d in busy},
        "kind_s_by_device": {
            str(d): {k: v / 1e12 for k, v in kinds[d].items()}
            for d in kinds},
        "category_s": dict(top(cats)),
        "breakdown": {"device_ops": top(ops), "idle_gaps": top(gaps)},
    }


def reduce_file(path: str, devices=None):
    return reduce_space(xplane_schema.read_xspace(path), devices)


def summarize(path: str, rows: int = 8) -> dict:
    """Planes, lines, event counts, the longest names and the stat keys:
    for looking at one trace by hand."""
    out = {}
    for plane in xplane_schema.read_xspace(path).planes:
        names = plane.stat_metadata
        lines = {}
        for line in plane.lines:
            total, keys = {}, set()
            for ev in line.events:
                md = plane.event_metadata[ev.metadata_id]
                label = md.display_name or md.name[:80]
                total[label] = total.get(label, 0) + ev.duration_ps
            for ev in line.events[:100]:
                md = plane.event_metadata[ev.metadata_id]
                keys.update("event:" + names[s.metadata_id].name
                            for s in ev.stats)
                keys.update("metadata:" + names[s.metadata_id].name
                            for s in md.stats)
            lines[line.name] = {
                "events": len(line.events), "stat_keys": sorted(keys),
                "timestamp_ns": line.timestamp_ns,
                "top_ms": [[k, v / 1e9] for k, v in sorted(
                    total.items(), key=lambda kv: -kv[1])[:rows]]}
        out[plane.name] = lines
    return out


if __name__ == "__main__":
    target = sys.argv[1]
    found = find_xplane(target) if os.path.isdir(target) else target
    if "--summary" in sys.argv:
        print(json.dumps(summarize(found), indent=1))
    else:
        print(json.dumps(reduce_file(found), indent=1))
