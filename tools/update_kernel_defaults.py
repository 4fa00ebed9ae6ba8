"""Regenerate the MEASURED table in ops/kernel_defaults.py from
tools/kernel_bench_results.json.

Run after every kernel-bench session on real hardware:

    python tools/kernel_bench.py          # writes kernel_bench_results.json
    python tools/update_kernel_defaults.py

The suite guard (tests/test_kernel_defaults.py) fails if the embedded
table drifts from the results file, so a kernel default can never ship
without a recorded measurement backing it.

Row-name grammar (kernel_bench.py):
    attn_t{T}_{fwd|train}_{flash|dense}[_bq{B}_bk{B}][_bwddense]
    battn_t{T}_w{W}_{fwd|train}_{banded|dense}[_bq{B}_bk{B}]
    dattn_l{L}_{banded|dense}[_bl{B}]
    lstm_{fwd|train}_{fused|scan}
Legacy flash rows without a block suffix or explicit fields were measured
at the then-default 128x128 tiles with the pre-Pallas (dense-recompute)
backward; they are read as such. The banded / decode
sections are emitted only when their rows exist — build_table over a
results file with none of them reproduces the pre-banded table exactly,
which is what keeps the suite guard green until real measurements land.
"""
import json
import os
import pprint
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
RESULTS = os.path.join(HERE, "kernel_bench_results.json")
TARGET = os.path.join(REPO, "deeplearning4j_tpu", "ops",
                      "kernel_defaults.py")
BEGIN = "# --- BEGIN GENERATED (tools/update_kernel_defaults.py) ---"
END = "# --- END GENERATED ---"

_ATTN = re.compile(
    r"^attn_t(?P<t>\d+)_(?P<mode>fwd|train)_(?P<kind>flash|dense)"
    r"(?:_bq(?P<bq>\d+)_bk(?P<bk>\d+))?(?P<bwd>_bwddense)?$")
_BATTN = re.compile(
    r"^battn_t(?P<t>\d+)_w(?P<w>\d+)_(?P<mode>fwd|train)"
    r"_(?P<kind>banded|dense)(?:_bq(?P<bq>\d+)_bk(?P<bk>\d+))?$")
_DATTN = re.compile(
    r"^dattn_l(?P<l>\d+)_(?P<kind>banded|dense)(?:_bl(?P<bl>\d+))?$")
_LSTM = re.compile(r"^lstm_(?P<mode>fwd|train)_(?P<kind>fused|scan)$")


def build_table(rows: dict) -> dict:
    attn = {}   # mode -> T -> {dense_ms, flash candidates}
    banded = {}  # mode -> T -> {dense_ms, banded candidates}
    decode = {}  # L -> {dense_ms, banded candidates}
    lstm = {}   # mode -> {fused_ms, scan_ms}
    devices = set()
    for name, row in rows.items():
        if "error" in row or "per_iter_ms" not in row:
            continue
        devices.add(row.get("device", "?"))
        m = _BATTN.match(name)
        if m:
            t = int(m.group("t"))
            slot = banded.setdefault(m.group("mode"), {}).setdefault(
                t, {"dense_ms": None, "window": int(m.group("w")),
                    "banded": []})
            if m.group("kind") == "dense":
                slot["dense_ms"] = row["per_iter_ms"]
            else:
                slot["banded"].append(
                    {"ms": row["per_iter_ms"],
                     "block_q": row.get("block_q") or (
                         int(m.group("bq")) if m.group("bq") else 256),
                     "block_k": row.get("block_k") or (
                         int(m.group("bk")) if m.group("bk") else 256)})
            continue
        m = _DATTN.match(name)
        if m:
            cl = int(m.group("l"))
            slot = decode.setdefault(cl, {"dense_ms": None, "banded": []})
            if m.group("kind") == "dense":
                slot["dense_ms"] = row["per_iter_ms"]
            else:
                slot["banded"].append(
                    {"ms": row["per_iter_ms"],
                     "block_l": row.get("block_l") or (
                         int(m.group("bl")) if m.group("bl") else 512)})
            continue
        m = _ATTN.match(name)
        if m:
            t = int(m.group("t"))
            slot = attn.setdefault(m.group("mode"), {}).setdefault(
                t, {"dense_ms": None, "flash": []})
            if m.group("kind") == "dense":
                slot["dense_ms"] = row["per_iter_ms"]
            else:
                bq = row.get("block_q") or (
                    int(m.group("bq")) if m.group("bq") else 128)
                bk = row.get("block_k") or (
                    int(m.group("bk")) if m.group("bk") else 128)
                bwd = row.get("backward") or (
                    "dense" if (m.group("bwd")
                                or m.group("mode") == "train") else "n/a")
                slot["flash"].append(
                    {"ms": row["per_iter_ms"], "block_q": bq,
                     "block_k": bk, "backward": bwd})
            continue
        m = _LSTM.match(name)
        if m:
            lstm.setdefault(m.group("mode"), {})[
                m.group("kind") + "_ms"] = row["per_iter_ms"]

    out_attn = {}
    for mode, by_t in attn.items():
        for t, slot in sorted(by_t.items()):
            if slot["dense_ms"] is None or not slot["flash"]:
                continue   # verdict needs both contenders
            best = min(slot["flash"], key=lambda f: f["ms"])
            out_attn.setdefault(mode, {})[t] = {
                "dense_ms": slot["dense_ms"],
                "flash_ms": best["ms"],
                "block_q": best["block_q"],
                "block_k": best["block_k"],
                "backward": best["backward"],
                "winner": ("flash" if best["ms"] < slot["dense_ms"]
                           else "dense"),
            }
    out_lstm = {}
    for mode, d in lstm.items():
        if "fused_ms" in d and "scan_ms" in d:
            out_lstm[mode] = {
                "fused_ms": d["fused_ms"], "scan_ms": d["scan_ms"],
                "winner": ("fused" if d["fused_ms"] < d["scan_ms"]
                           else "scan"),
            }
    table = {"attention": out_attn, "lstm": out_lstm,
             "devices": sorted(devices)}
    # New sections appear only once rows exist: an all-legacy results
    # file must reproduce the pre-banded table byte-for-byte (the suite
    # guard compares the embedded MEASURED against this function).
    out_banded = {}
    for mode, by_t in banded.items():
        for t, slot in sorted(by_t.items()):
            if slot["dense_ms"] is None or not slot["banded"]:
                continue
            best = min(slot["banded"], key=lambda f: f["ms"])
            out_banded.setdefault(mode, {})[t] = {
                "dense_ms": slot["dense_ms"],
                "banded_ms": best["ms"],
                "block_q": best["block_q"],
                "block_k": best["block_k"],
                "window": slot["window"],
                "winner": ("banded" if best["ms"] < slot["dense_ms"]
                           else "dense"),
            }
    if out_banded:
        table["banded"] = out_banded
    out_decode = {}
    for cl, slot in sorted(decode.items()):
        if slot["dense_ms"] is None or not slot["banded"]:
            continue
        best = min(slot["banded"], key=lambda f: f["ms"])
        out_decode[cl] = {
            "dense_ms": slot["dense_ms"],
            "banded_ms": best["ms"],
            "block_l": best["block_l"],
            "winner": ("banded" if best["ms"] < slot["dense_ms"]
                       else "dense"),
        }
    if out_decode:
        table["decode"] = out_decode
    return table


def main():
    with open(RESULTS) as fh:
        rows = json.load(fh)
    table = build_table(rows)
    body = "MEASURED: dict = " + pprint.pformat(table, width=72,
                                                sort_dicts=True)
    with open(TARGET) as fh:
        src = fh.read()
    pre, rest = src.split(BEGIN)
    _, post = rest.split(END)
    new = pre + BEGIN + "\n" + body + "\n" + END + post
    if new != src:
        with open(TARGET, "w") as fh:
            fh.write(new)
        print(f"updated {TARGET}")
    else:
        print("no change")
    print(json.dumps({"attention_modes": {
        m: {t: v["winner"] for t, v in by_t.items()}
        for m, by_t in table["attention"].items()},
        "lstm": {m: v["winner"] for m, v in table["lstm"].items()}}))


if __name__ == "__main__":
    main()
