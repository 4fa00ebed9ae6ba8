"""From the program's span record and a device trace to: each span's self
time, the window's `fit` subtree, the link between the two clocks, device
idle gaps by the host span they fall in, and device time by layer.

What the two records hold (looked at by hand on a v5e, PR 25):

- Spans (`deeplearning4j_tpu/observe/trace.py`): dicts with `name`,
  `start_ns` / `end_ns` on `time.perf_counter_ns()`, `span_id`,
  `parent_id`, `thread`, `attrs`. The fit loop's names: `fit` > `fit.epoch`
  > `fit.etl` (> `data.put`), `fit.dispatch`, `fit.listeners`,
  `fit.epoch_sync`.
- The trace's `XLA Ops` events carry the `jax.named_scope` of their
  instruction in the metadata stat `tf_op` (the HLO's `op_name`, with a
  colon at its end): `jit(step_fn)/jvp(conv1)/conv_general_dilated:` is
  layer `conv1` forward, `jit(step_fn)/transpose(jvp(conv1))/...:` its
  backward pass, `jit(step_fn)/jvp(fc)/loss/...:` the output layer's score,
  `jit(step_fn)/updater/mul:` the updater. A fusion carries the name of
  one of the ops fused into it. The stat `source` is the Python line, and
  the instruction text in `name` has no `op_name`. `flops` and
  `bytes_accessed` are per run of the op. `bytes_accessed` counts every
  memory: a ResNet-50 step's ops read 3,600 GB/s by it. The stat
  `memory_access_breakdown` (a serialized list of {1: read or write,
  2: memory space, 3: bytes}) splits it: space 1 is HBM (parameters and
  results live there), space 3 the on-chip memory that the compiler's
  `S(1)` layouts name. Only the HBM bytes are held against 819 GB/s.
- Every line of a device plane has `timestamp_ns` 0 and offsets that count
  from the moment the TPU tracer started, 1 to 2 ms into opening the
  profiler session; two runs 4 s apart kept the same offset to the host's
  clock within 0.13 ms. The plane `Task Environment` has the wall time of
  the call (`profile_start_time`), 1.0 to 1.9 ms before that zero. So the
  link is made from beacons: runs of the trivial program
  `jit_dl4j_trace_beacon`, each between two reads of the span clock
  (`utils/profiling.DeviceTrace` makes them and writes them into the
  header of the spans file).

    python benchmarks/span_reduce.py <trace.xplane.pb> <spans.jsonl>
"""

from __future__ import annotations

import json
import os
import re
import statistics
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmarks import trace_reduce  # noqa: E402

BEACON = trace_reduce.BEACON
ASYNC_COPY = ("copy-start", "copy-done")
TOP = 10
_WRAPPED = re.compile(r"^(?:transpose\()?jvp\((.*?)\)+$")


# ------------------------------------------------------------------ spans
def program_spans():
    """The spans the program's store holds now, or None for a program
    that has no such store (the parent of PR 25)."""
    try:
        from deeplearning4j_tpu.observe.trace import get_span_store
    except ImportError:
        return None
    return get_span_store().events()


def read_span_file(path: str):
    """(header, spans) of a file written by `observe/trace.write_spans`."""
    header, spans = {}, []
    with open(path, encoding="utf-8") as fh:
        for line in fh:
            if line.strip():
                row = json.loads(line)
                if "span_clock" in row:
                    header = row["span_clock"]
                else:
                    spans.append(row)
    return header, spans


def window(spans):
    """The subtree of the last root span named `fit`: the window's, when
    read right after the runner's timed `fit()`. [] if there is none."""
    roots = [s for s in spans or [] if s["name"] == "fit"
             and s["parent_id"] is None]
    if not roots:
        return []
    root = max(roots, key=lambda s: s["start_ns"])
    kids = {}
    for s in spans:
        kids.setdefault(s["parent_id"], []).append(s)
    out, todo = [], [root]
    while todo:
        s = todo.pop()
        out.append(s)
        todo.extend(kids.get(s["span_id"], []))
    return sorted(out, key=lambda s: s["start_ns"])


def self_times(spans):
    """Each span with `self_ns`: its duration less its children's."""
    rows = {s["span_id"]: dict(s, self_ns=s["end_ns"] - s["start_ns"])
            for s in spans}
    for s in spans:
        if s["parent_id"] in rows:
            rows[s["parent_id"]]["self_ns"] -= s["end_ns"] - s["start_ns"]
    return list(rows.values())


def durations_ms(spans, name: str):
    return [(s["end_ns"] - s["start_ns"]) / 1e6 for s in spans
            if s["name"] == name and not s["attrs"].get("exhausted")]


def median(values):
    return statistics.median(values) if values else None


# the three `program_span` metrics of PR 25: the span each reads, and how
# the window's spans of that name fold into one number of milliseconds
PROGRAM_SPAN_METRICS = {
    "epoch_sync_ms.train": ("fit.epoch_sync", lambda v: sum(v) or None),
    "put_ms.train": ("data.put", median),
    "listeners_ms.train": ("fit.listeners", median),
}


def program_span_metric(metric: str, spans=None):
    """One of `PROGRAM_SPAN_METRICS` over the window of `spans`, by
    default the program's own store; None where there is nothing to read."""
    name, fold = PROGRAM_SPAN_METRICS[metric]
    return fold(durations_ms(
        window(program_spans() if spans is None else spans), name))


def summary(spans):
    """Of the window: count, seconds and self seconds by span name, and
    `unexplained_share`: the part of the `fit` span that is self time of
    `fit` and `fit.epoch`, the loop's own bookkeeping between the spans of
    its segments."""
    out = {}
    for r in self_times(spans):
        row = out.setdefault(r["name"], {"n": 0, "s": 0.0, "self_s": 0.0})
        row["n"] += 1
        row["s"] += (r["end_ns"] - r["start_ns"]) / 1e9
        row["self_s"] += r["self_ns"] / 1e9
    share = None
    if out.get("fit", {}).get("s"):
        share = (out["fit"]["self_s"] + out.get(
            "fit.epoch", {"self_s": 0.0})["self_s"]) / out["fit"]["s"]
    return {"by_name": out, "unexplained_share": share}


# ------------------------------------------------------------- the clocks
def _device_planes(space, devices=None):
    for plane in space.planes:
        if plane.name.startswith(trace_reduce.DEVICE_PLANE):
            ordinal = int(plane.name[len(trace_reduce.DEVICE_PLANE):]
                          .split()[0])
            if devices is None or ordinal in devices:
                yield ordinal, plane


def clock_link(space, beacons_ns):
    """Where the device trace's zero lies on the span clock.

    The k-th run of the beacon program in the trace lies inside the k-th
    bracket `[t0, t1]`, so the zero lies in `[t0 - start, t1 - end]` for
    every one of them: `zero_ns` is the middle of what all allow, and
    `residual_ns` half its width, the most the link can be off. If no
    zero fits them all (a drifting clock), `consistent` is false and the
    residual is how far apart they are. None without beacons."""
    runs = []
    for _, plane in _device_planes(space):
        runs = [(s / 1000.0, e / 1000.0) for s, e, name in
                trace_reduce.module_spans(plane) if name == BEACON]
        if runs:
            break
    pairs = list(zip(runs, beacons_ns or []))
    if not pairs:
        return None
    lo = max(t0 - start for (start, _), (t0, _) in pairs)
    hi = min(t1 - end for (_, end), (_, t1) in pairs)
    return {"zero_ns": (lo + hi) / 2.0, "residual_ns": abs(hi - lo) / 2.0,
            "consistent": hi >= lo, "beacons": len(pairs)}


# ------------------------------------------------------------- device ops
def _scope_parts(tf_op: str):
    """The parts of a `tf_op` below the jitted function (and a fused
    scan's `while/body`): scopes, then the primitive."""
    parts = [p for p in tf_op.rstrip(":").split("/") if p]
    while parts and (parts[0].startswith(("jit(", "pjit("))
                     or parts[0] in ("while", "body", "cond")):
        parts = parts[1:]
    return parts


def parse_scope(tf_op: str):
    """(scope, direction) of an op's `tf_op`: the first `named_scope`
    under the jitted function, with `loss` kept beside an output layer's
    name, and 'backward' for ops of the transposed pass. (None, None) for
    an op outside every scope."""
    parts = _scope_parts(tf_op)
    if len(parts) < 2:              # only the primitive is left
        return None, None
    first, direction = parts[0], "forward"
    if first.startswith("transpose("):
        direction = "backward"
    wrapped = _WRAPPED.match(first)
    if wrapped:
        first = wrapped.group(1)
    elif "(" in first:              # a transform of no scope: `jvp()`
        return None, None
    if not first:
        return None, None
    if len(parts) > 2 and parts[1] == "loss":
        first += "/loss"
    return first, direction


def inner_scopes(tf_op: str):
    """The `named_scope`s of an op's `tf_op` below its first one, outermost
    first: where a `pl.pallas_call`'s `name=` lands (JAX puts it on the
    name stack), as in `jit(step)/jvp(lstm1)/lstm_cell_fwd/pallas_call:`.
    Transforms are unwrapped as `parse_scope` unwraps them; `loss` stays
    with its output layer's name."""
    out = []
    for part in _scope_parts(tf_op)[1:-1]:
        wrapped = _WRAPPED.match(part)
        part = wrapped.group(1) if wrapped else part
        if part and "(" not in part and part not in (
                "loss", "while", "body", "cond"):
            out.append(part)
    return out


def _varint(raw: bytes, i: int):
    value = shift = 0
    while True:
        value |= (raw[i] & 0x7F) << shift
        shift += 7
        i += 1
        if not raw[i - 1] & 0x80:
            return value, i


def hbm_bytes(raw: bytes) -> float:
    """Bytes read from and written to HBM (memory space 1) in one
    `memory_access_breakdown`: length-delimited entries (field 1) of the
    varint fields 2, memory space, and 3, bytes."""
    total, i = 0, 0
    while i < len(raw):
        tag, i = _varint(raw, i)
        size, i = _varint(raw, i)
        if tag != (1 << 3 | 2):
            return 0.0              # not the layout looked at by hand
        entry, j, end = {}, i, i + size
        while j < end:
            key, j = _varint(raw, j)
            entry[key >> 3], j = _varint(raw, j)
        if entry.get(2) == 1:
            total += entry.get(3, 0)
        i = end
    return float(total)


def scoped_events(plane):
    """The `XLA Ops` events of one device plane with their scope, FLOPs
    and bytes; times in picoseconds from the trace's zero."""
    names, meta = plane.stat_metadata, {}
    for line in plane.lines:
        if line.name != trace_reduce.OPS_LINE:
            continue
        base = line.timestamp_ns * 1000
        for ev in line.events:
            if ev.metadata_id not in meta:
                md = plane.event_metadata[ev.metadata_id]
                stats = {names[st.metadata_id].name:
                         st.bytes_value if st.HasField("bytes_value")
                         else trace_reduce._stat(st, names)
                         for st in md.stats}
                scope, direction = parse_scope(str(stats.get("tf_op", "")))
                meta[ev.metadata_id] = {
                    "name": md.display_name
                    or md.name.split(" = ")[0].lstrip("%"),
                    "category": str(stats.get("hlo_category", "")).lower(),
                    "scope": scope, "direction": direction,
                    "inner": inner_scopes(str(stats.get("tf_op", "")))
                    if scope else [],
                    "flops": float(stats.get("flops") or 0),
                    "bytes": float(stats.get("bytes_accessed") or 0),
                    "hbm_bytes": hbm_bytes(
                        stats.get("memory_access_breakdown") or b"")}
            yield dict(meta[ev.metadata_id], start=base + ev.offset_ps,
                       dur=ev.duration_ps)


def main_module(space, devices=None):
    """(name, [(start_ps, end_ps), ...]) of the program that took most
    device time on the first chip of the cell: the train step."""
    for _, plane in _device_planes(space, devices):
        time_in, runs = {}, {}
        for start, end, name in trace_reduce.module_spans(plane):
            if name == BEACON:
                continue
            time_in[name] = time_in.get(name, 0) + end - start
            runs.setdefault(name, []).append((start, end))
        if runs:
            name = max(time_in, key=time_in.get)
            return name, runs[name]
    return None, []


def step_times(runs):
    """Of the train step's runs on the device: how many, the first, the
    median and the longest in ms, and the seconds they sum to."""
    ms = [(end - start) / 1e9 for start, end in runs]
    if not ms:
        return {"n": 0}
    return {"n": len(ms), "first_ms": ms[0], "median_ms": median(ms),
            "longest_ms": max(ms), "sum_s": sum(ms) / 1e3}


def by_scope(space, devices=None):
    """Device op time by scope, summed over the cell's chips: seconds
    (self time, so a loop's body is not counted twice) forward and
    backward, FLOPs, bytes of every memory and of HBM. The key None holds
    ops outside every scope, with their seconds by op name under `ops`;
    beacon runs are left out. An asynchronous copy (`copy-start`,
    `copy-done`) gives its time and not its bytes: the copy ran beside
    other ops, and the event is the wait for it. A row also counts its
    events (`n`) and keeps, under `inner`, the same sums for every
    `named_scope` below the first (a `pl.pallas_call`'s `name=`)."""
    def empty():
        return {"s": 0.0, "forward_s": 0.0, "backward_s": 0.0, "n": 0,
                "flops": 0.0, "bytes": 0.0, "hbm_bytes": 0.0}

    table = {}
    for _, plane in _device_planes(space, devices):
        events = trace_reduce.outside_beacons(plane, scoped_events(plane))
        for row in trace_reduce.self_times(events):
            cell = table.setdefault(row["scope"], empty())
            cells = [cell] + [cell.setdefault("inner", {}).setdefault(
                name, empty()) for name in row["inner"]]
            for c in cells:
                c["s"] += row["self"] / 1e12
                c["n"] += 1
                if row["direction"]:
                    c[row["direction"] + "_s"] += row["self"] / 1e12
            if not row["direction"]:
                ops = cell.setdefault("ops", {})
                ops[row["name"]] = ops.get(row["name"], 0.0) \
                    + row["self"] / 1e12
            if row["category"] in ASYNC_COPY:
                continue    # its time is the wait alone, its bytes the copy's
            for c in cells:
                for key in ("flops", "bytes", "hbm_bytes"):
                    c[key] += row[key]
    return table


def roofline_share(scopes, name, peaks, flops=None, hbm_bytes=None):
    """A kernel's or a layer's share of its nearer roof, in %: the larger
    of achieved FLOP/s over the bf16 peak and achieved HBM bytes/s over
    the HBM peak, for the ops of `scopes` (a `by_scope` table) whose first
    scope is `name` or that lie under an inner scope `name` (a
    `pl.pallas_call(..., name=name)`). The operations and bytes are the
    trace's own sums unless the caller gives `flops` and `hbm_bytes`, PER
    CALL of the kernel (one device event each; the trace counts nothing
    inside a custom call), from a function of shapes kept with the
    benchmark. None where no such op ran or nothing was counted: never 0.
    A share over 105% is refused: the operations or bytes are then counted
    too high, or the time leaves out part of the work."""
    rows = [scopes[name]] if name in scopes else []
    rows += [row["inner"][name] for row in scopes.values()
             if name in row.get("inner", {})]
    seconds, calls = sum(r["s"] for r in rows), sum(r["n"] for r in rows)
    if not seconds:
        return None
    total_flops = sum(r["flops"] for r in rows) if flops is None \
        else flops * calls
    total_hbm = sum(r["hbm_bytes"] for r in rows) if hbm_bytes is None \
        else hbm_bytes * calls
    share = 100.0 * max(total_flops / seconds / peaks["bf16_flops_per_s"],
                        total_hbm / seconds / peaks["hbm_bytes_per_s"])
    if share > 105.0:
        raise ValueError(
            f"{name}: {share:.1f}% of the roof over {seconds:.6f} s of "
            f"{calls} device events: operations or bytes counted too "
            f"high, or time left out")
    return share or None


def scope_shares(table):
    """(share of op time under any scope, share under `updater`), in %."""
    total = sum(row["s"] for row in table.values())
    if not total:
        return None, None
    scoped = sum(row["s"] for scope, row in table.items() if scope)
    return (100.0 * scoped / total,
            100.0 * table.get("updater", {"s": 0.0})["s"] / total)


def layers(table, steps: int, chips: int, peaks=None):
    """The `TOP` scopes with most device time: ms a step and chip, forward
    and backward, achieved TFLOP/s, GB/s over every memory and over HBM,
    and which roof of `peaks` is nearer (the larger of FLOP/s over the
    bf16 peak and HBM bytes/s over the HBM peak). The ops outside every
    scope come with their five longest op names."""
    rows = []
    for scope, row in sorted(table.items(), key=lambda kv: -kv[1]["s"]):
        if not row["s"]:
            continue
        per = 1e3 / max(steps, 1) / chips
        out = {"scope": scope or "(no scope)", "ms_per_step": row["s"] * per,
               "forward_ms": row["forward_s"] * per,
               "backward_ms": row["backward_s"] * per,
               "tflop_per_s": row["flops"] / row["s"] / 1e12,
               "gb_per_s": row["bytes"] / row["s"] / 1e9,
               "hbm_gb_per_s": row["hbm_bytes"] / row["s"] / 1e9}
        if peaks:
            of_flops = row["flops"] / row["s"] / peaks["bf16_flops_per_s"]
            of_hbm = row["hbm_bytes"] / row["s"] / peaks["hbm_bytes_per_s"]
            out["nearer_roof"] = "flops" if of_flops >= of_hbm else "hbm"
            out["share_of_roof"] = 100.0 * max(of_flops, of_hbm)
        if "ops" in row:
            out["ops_ms_per_step"] = {
                name: s * per for name, s in sorted(
                    row["ops"].items(), key=lambda kv: -kv[1])[:5]}
        rows.append(out)
    return rows[:TOP]


# ------------------------------------------------------------------- gaps
def idle_gaps(space, devices=None):
    """[start_ps, end_ps] of every stretch of `GAP_FLOOR_PS` or more in
    which no op ran on the cell's first chip, between its first op's
    start and its last op's end."""
    for _, plane in _device_planes(space, devices):
        merged = trace_reduce.union(
            (e["start"], e["start"] + e["dur"])
            for e in trace_reduce.device_events(plane))
        return [[e, s] for (_, e), (s, _) in zip(merged, merged[1:])
                if s - e >= trace_reduce.GAP_FLOOR_PS]
    return []


def gaps_by_span(gaps_ps, spans, zero_ns: float):
    """Idle seconds by the innermost host span that covers the whole gap
    (so a gap that straddles two steps goes to their `fit.epoch`), longest
    first; `(no span)` for a gap that no span covers."""
    out = {}
    for start_ps, end_ps in gaps_ps:
        t0, t1 = zero_ns + start_ps / 1000.0, zero_ns + end_ps / 1000.0
        inside = [s for s in spans
                  if s["start_ns"] <= t0 and t1 <= s["end_ns"]]
        name = min(inside, key=lambda s: s["end_ns"] - s["start_ns"])[
            "name"] if inside else "(no span)"
        row = out.setdefault(name, {"s": 0.0, "n": 0, "longest_ms": 0.0})
        row["s"] += (end_ps - start_ps) / 1e12
        row["n"] += 1
        row["longest_ms"] = max(row["longest_ms"], (end_ps - start_ps) / 1e9)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]["s"]))


def fill_ms(spans, step_runs, zero_ns: float):
    """From the start of the window's `fit` span to the first run of the
    train step's program on the device, on the span clock."""
    fit = next((s for s in spans if s["name"] == "fit"), None)
    first = [start for start, _ in step_runs
             if fit and zero_ns + start / 1000.0 >= fit["start_ns"]]
    if not first:
        return None
    return (zero_ns + min(first) / 1000.0 - fit["start_ns"]) / 1e6


# ----------------------------------------------------------------- report
def report(space, spans, header, *, devices=None, peaks=None):
    """Everything above for one traced window, as the JSON lines `clock`,
    `steps`, `spans`, `gaps`, `layers` and `metrics` (the six readings of PR 25's
    per-layer metrics; a key is left out where nothing could be read)."""
    win = window(spans)
    link = clock_link(space, header.get("beacons_ns"))
    name, runs = main_module(space, devices)
    table = by_scope(space, devices)
    chips = len(list(_device_planes(space, devices)))
    scoped, updater = scope_shares(table)
    lines = {"clock": dict(link or {}, main_module=name, steps=len(runs)),
             "steps": step_times(runs),
             "spans": summary(win),
             "layers": layers(table, len(runs), max(chips, 1), peaks)}
    metrics = {m: program_span_metric(m, win) for m in PROGRAM_SPAN_METRICS}
    metrics.update({"scoped_op_time_share.train": scoped,
                    "updater_time_share.train": updater})
    if link is not None:
        lines["gaps"] = gaps_by_span(idle_gaps(space, devices), win,
                                     link["zero_ns"])
        metrics["fill_ms.train"] = fill_ms(win, runs, link["zero_ns"])
    lines["metrics"] = {k: v for k, v in metrics.items() if v is not None}
    return lines


def main(argv=None) -> int:
    from benchmarks import harness, xplane_schema

    args = argv or sys.argv[1:]
    header, spans = read_span_file(args[1])
    peaks = harness.load_json("peaks.json").get(
        args[2] if len(args) > 2 else "TPU v5 lite")
    for kind, facts in report(xplane_schema.read_xspace(args[0]), spans,
                              header, peaks=peaks).items():
        print(json.dumps({kind: facts}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
