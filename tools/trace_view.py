#!/usr/bin/env python
"""Render a reconstructed trace tree (observe/reqtrace.py) as a
waterfall.

    python tools/trace_view.py trace.json        # GET /trace/{id} output
    python tools/trace_view.py flight_*.json     # flight dump: renders
                                                 # its `traces` block
    curl -s :8080/trace/t1a2b-000003 | python tools/trace_view.py -

Each span prints as one indented line: offset from the trace root,
duration, a proportional bar over the trace's wall window, the span
name, and its attributes (queue/dispatch/device segments read straight
off the indentation). Stdlib only — usable wherever the JSON landed.
"""

from __future__ import annotations

import argparse
import json
import sys

BAR_W = 24


def _attrs_brief(attrs: dict, keep: int = 6) -> str:
    parts = []
    for k, v in list(attrs.items())[:keep]:
        if isinstance(v, float):
            v = round(v, 3)
        parts.append(f"{k}={v}")
    if len(attrs) > keep:
        parts.append("…")
    return " ".join(parts)


def _bar(t0: float, span_ts: float, dur_ms: float, total_ms: float) -> str:
    """[  ████    ] — where in the trace window this span burned time."""
    if total_ms <= 0:
        return " " * (BAR_W + 2)
    lo = max(0.0, (span_ts - t0) * 1e3 / total_ms)
    hi = min(1.0, lo + dur_ms / total_ms)
    a, b = int(lo * BAR_W), max(int(lo * BAR_W) + 1, int(hi * BAR_W))
    return "[" + " " * a + "█" * (b - a) + " " * (BAR_W - b) + "]"


def _boundary_rule(attrs: dict, depth: int) -> str:
    """The process-boundary marker a stitched trace prints before each
    grafted subtree: which replica, which pid, and the clock-skew
    correction already applied to its timestamps."""
    pad = "  " * depth
    bits = [f"replica={attrs.get('replica', '?')}"]
    if attrs.get("pid") is not None:
        bits.append(f"pid={attrs['pid']}")
    skew = attrs.get("clock_skew_ms")
    if isinstance(skew, (int, float)) and skew:
        bits.append(f"skew{skew:+.2f}ms corrected")
    if attrs.get("unreachable"):
        bits.append("UNREACHABLE")
    rule = f"  {'':>9}   {'':>9}   {'═' * (BAR_W + 2)} {pad}║ "
    return rule + " ".join(bits)


def _walk(node: dict, depth: int, t0: float, total_ms: float) -> None:
    attrs = node.get("attrs") or {}
    if attrs.get("boundary") == "process":
        print(_boundary_rule(attrs, depth))
    rel_ms = (node.get("ts", t0) - t0) * 1e3
    dur = float(node.get("dur_ms", 0.0))
    pad = "  " * depth
    line = (f"  {rel_ms:+9.2f}ms {dur:9.2f}ms "
            f"{_bar(t0, node.get('ts', t0), dur, total_ms)} "
            f"{pad}{node.get('name', '?')}")
    brief = _attrs_brief(attrs)
    if brief:
        line += f"  {brief}"
    print(line)
    for child in node.get("children") or []:
        _walk(child, depth + 1, t0, total_ms)


def render_tree(doc: dict) -> None:
    """Render one /trace/{id} document: {trace_id, spans, depth, tree}."""
    roots = doc.get("tree") or []
    head = (f"trace {doc.get('trace_id', '?')}  "
            f"({doc.get('spans', '?')} spans, depth "
            f"{doc.get('depth', '?')}")
    if doc.get("stitched"):
        head += (f", stitched across {doc.get('processes', '?')} "
                 f"processes, {doc.get('grafted_spans', 0)} grafted")
    print(head + ")")
    if not roots:
        print("  (no spans)")
        return
    t0 = min(r.get("ts", 0.0) for r in roots)

    def _extent(n):
        end = (n.get("ts", t0) - t0) * 1e3 + float(n.get("dur_ms", 0.0))
        return max([end] + [_extent(c) for c in n.get("children") or []])

    total_ms = max(_extent(r) for r in roots)
    print(f"     offset       dur  {'window':^{BAR_W + 2}}")
    for r in roots:
        _walk(r, 0, t0, total_ms)


def extract_trees(doc) -> list:
    """Accept any of the JSON shapes that carry trace trees."""
    if isinstance(doc, list):                  # incident bundle's
        return [t for t in doc if isinstance(t, dict)]  # stitched_traces
    if "tree" in doc:                          # GET /trace/{id}
        return [doc]
    if isinstance(doc.get("traces"), list):    # flight dump block
        return [t for t in doc["traces"] if isinstance(t, dict)]
    return []


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", help="trace/flight JSON, or - for stdin")
    ap.add_argument("--last", type=int, default=0,
                    help="render only the last N traces (default: all)")
    args = ap.parse_args(argv)

    if args.path == "-":
        doc = json.load(sys.stdin)
    else:
        with open(args.path) as f:
            doc = json.load(f)

    trees = extract_trees(doc)
    if not trees:
        sys.exit("no trace tree found (expected /trace/{id} JSON or a "
                 "flight dump with a `traces` block)")
    if args.last:
        trees = trees[-args.last:]
    for i, t in enumerate(trees):
        if i:
            print()
        render_tree(t)
    return 0


if __name__ == "__main__":
    sys.exit(main())
