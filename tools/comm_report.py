#!/usr/bin/env python3
"""Comm advisor: rank jit owners compute-bound vs comm-bound from the
collective-byte ledger against peak interconnect bandwidth.

Joins two per-owner ledgers the RecompileWatchdog's compile probe
already captures for every compiled program:

  - `costs` — XLA cost analysis (flops) per cache key;
  - `collectives` — the commsmon comm ledger (per-device collective
    wire bytes under the one-pass ring convention) per cache key;

against the device peak specs in `utils/profiling.py`
(PEAK_FLOPS_BY_KIND / PEAK_ICI_BYTES_BY_KIND). For each program:

    t_compute = flops / peak_flops          (perfect-MXU compute time)
    t_comm    = wire_bytes / peak_ici       (perfect-overlap comm time)
    comm_frac = t_comm / (t_comm + t_compute)

An owner whose comm_frac exceeds 0.5 is comm-bound: its collectives
cost more cycles than its math even with perfect overlap, so the fix is
communication-algorithmic — shard the other axis, reduce-scatter into
sharded moments instead of all-reducing into replicated ones
(arXiv:2004.13336), overlap windows, or drop precision on the wire —
not kernel tuning. Owners are ranked by absolute comm time so the
report surfaces where interconnect cycles actually go. Programs with
zero collectives are pure compute rows (comm_frac 0) and rank last.

Input is a watchdog snapshot: `--snapshot FILE` accepts a raw
`RecompileWatchdog.snapshot()` JSON or a flight-recorder dump (the
snapshot under its "watchdog" key); with no file the tool reads the
live process watchdog.
Peaks come from --device-kind or explicit --peak-flops / --peak-ici;
off-TPU there is no default and the tool says so.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def extract_watchdog(blob: dict) -> dict:
    """Accept a raw watchdog snapshot or a flight dump; return the
    watchdog snapshot dict (with `per_owner`)."""
    if "per_owner" in blob:
        return blob
    inner = blob.get("watchdog")
    if isinstance(inner, dict) and "per_owner" in inner:
        return inner
    raise ValueError(
        "no watchdog snapshot found (expected a 'per_owner' mapping, "
        "possibly under a 'watchdog' key)")


def analyze(snapshot: dict, peak_flops: float, peak_ici: float) -> list:
    """Pure join: watchdog snapshot -> ranked per-owner comm rows.

    Returns a list (sorted by absolute comm time, heaviest first) of
    {owner, programs, flops, wire_bytes, collective_ops, by_kind,
    t_compute_s, t_comm_s, comm_frac, bound}. Owners with neither a
    cost nor a collective report are skipped."""
    rows = []
    for tag, owner in snapshot.get("per_owner", {}).items():
        costs = owner.get("costs", {}) or {}
        colls = owner.get("collectives", {}) or {}
        if not costs and not colls:
            continue
        flops = sum(float(c.get("flops") or 0.0) for c in costs.values())
        wire = 0
        ops = 0
        by_kind: dict = {}
        for crow in colls.values():
            wire += int(crow.get("wire_bytes") or 0)
            ops += int(crow.get("ops") or 0)
            for kind, krow in (crow.get("by_kind") or {}).items():
                agg = by_kind.setdefault(kind,
                                         {"ops": 0, "wire_bytes": 0})
                agg["ops"] += krow.get("ops", 0)
                agg["wire_bytes"] += krow.get("wire_bytes", 0)
        if flops <= 0 and wire <= 0:
            continue
        t_compute = flops / peak_flops
        t_comm = wire / peak_ici
        denom = t_compute + t_comm
        comm_frac = t_comm / denom if denom > 0 else 0.0
        rows.append({
            "owner": tag,
            "programs": max(len(costs), len(colls)),
            "flops": flops,
            "wire_bytes": int(wire),
            "collective_ops": ops,
            "by_kind": by_kind,
            "t_compute_s": t_compute,
            "t_comm_s": t_comm,
            "comm_frac": comm_frac,
            "bound": "comm" if comm_frac > 0.5 else "compute",
        })
    rows.sort(key=lambda r: (-r["t_comm_s"], -r["t_compute_s"]))
    return rows


def _fmt_num(x: float) -> str:
    for unit, div in (("T", 1e12), ("G", 1e9), ("M", 1e6), ("K", 1e3)):
        if abs(x) >= div:
            return f"{x / div:.2f}{unit}"
    return f"{x:.1f}"


def render(rows: list, peak_flops: float, peak_ici: float,
           top: int = 10) -> str:
    out = [
        f"comm report: peak {_fmt_num(peak_flops)}FLOP/s compute, "
        f"{_fmt_num(peak_ici)}B/s interconnect "
        f"(one-pass ring wire-byte convention)",
        "",
    ]
    if not rows:
        out.append("no costed or collective-bearing programs in "
                   "snapshot (comm ledger off, or nothing compiled)")
        return "\n".join(out)
    hdr = (f"{'owner':<42} {'bound':<8} {'coll':>5} {'wireB':>8} "
           f"{'comm%':>7} {'t_comm':>9} {'t_comp':>9}")
    out += [hdr, "-" * len(hdr)]
    for r in rows[:top]:
        out.append(
            f"{r['owner'][:42]:<42} {r['bound']:<8} "
            f"{r['collective_ops']:>5} {_fmt_num(r['wire_bytes']):>8} "
            f"{r['comm_frac']:>6.1%} {r['t_comm_s'] * 1e6:>7.2f}us "
            f"{r['t_compute_s'] * 1e6:>7.2f}us")
        for kind, krow in sorted(r["by_kind"].items(),
                                 key=lambda kv: -kv[1]["wire_bytes"]):
            out.append(f"    {kind:<20} {krow['ops']:>3} op(s)  "
                       f"{_fmt_num(krow['wire_bytes'])}B on the wire")
    out += [
        "",
        "comm% = comm time / (comm + compute) at spec peaks with "
        "perfect overlap; a",
        "comm-bound owner needs a different sharding (reduce-scatter "
        "into sharded state,",
        "other-axis placement, wire-dtype cuts) — kernel tuning cannot "
        "buy back the wire.",
    ]
    return "\n".join(out)


def _resolve_peaks(args):
    pf, pi = args.peak_flops, args.peak_ici
    if pf and pi:
        return pf, pi
    from deeplearning4j_tpu.utils.profiling import (
        peak_flops, peak_ici_bytes,
    )
    kind = args.device_kind
    if kind is None:
        import jax
        if jax.default_backend() != "tpu":
            raise SystemExit(
                "not on TPU and no --device-kind / --peak-flops + "
                "--peak-ici given: there is no comm roofline to compare "
                "against (try --device-kind 'TPU v4')")
        kind = jax.devices()[0].device_kind
    pf = pf or peak_flops(kind)
    pi = pi or peak_ici_bytes(kind)
    if not pf or not pi:
        raise SystemExit(
            f"no spec-sheet peaks for device kind {kind!r}; pass "
            f"--peak-flops and --peak-ici explicitly")
    return pf, pi


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--snapshot", help="watchdog snapshot or flight dump "
                    "JSON (default: live process watchdog)")
    ap.add_argument("--device-kind", help="spec-sheet lookup key, e.g. "
                    "'TPU v4' (default: the attached device)")
    ap.add_argument("--peak-flops", type=float,
                    help="override peak FLOP/s")
    ap.add_argument("--peak-ici", type=float,
                    help="override peak interconnect bytes/s")
    ap.add_argument("--top", type=int, default=10,
                    help="owners to show (default 10)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output")
    args = ap.parse_args(argv)

    peak_f, peak_i = _resolve_peaks(args)
    if args.snapshot:
        with open(args.snapshot) as f:
            snap = extract_watchdog(json.load(f))
    else:
        from deeplearning4j_tpu.observe.watchdog import get_watchdog
        snap = get_watchdog().snapshot()

    rows = analyze(snap, peak_f, peak_i)
    if args.json:
        print(json.dumps({"peak_flops": peak_f, "peak_ici": peak_i,
                          "owners": rows}, indent=2))
    else:
        print(render(rows, peak_f, peak_i, top=args.top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
