"""The form of `BENCHMARK.json`, as the driver's check reads it before any
run: a fault here refuses a PR without one measurement (PR 33's first
check: a cell's `why` of 203 characters). No JAX, no import of the
benchmark."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
PATH = re.compile(r"[A-Za-z0-9_.\-/]+")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        return json.load(fh)


def _entries():
    bench = _benchmark()
    return [(section, entry) for section in KEYS for entry in bench[section]]


def _line(text):
    return (isinstance(text, str) and 1 <= len(text) <= 200
            and text.isascii() and text.isprintable())


@pytest.mark.parametrize(
    "section,entry", _entries(),
    ids=[f"{s}:{e.get('name')}" for s, e in _entries()])
def test_entry_has_the_form_the_check_asks(section, entry):
    extra = set(entry) - KEYS[section] - {"workloads"}
    assert not extra and KEYS[section] <= set(entry), (extra, entry)
    assert NAME.fullmatch(entry["name"])
    for key in ("why", "layer", "source"):
        if key in entry:
            assert _line(entry[key]), (key, len(entry[key]))
    if section in ("end_to_end", "per_layer"):
        assert UNIT.fullmatch(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES
    if section == "end_to_end":
        assert entry["source"] in ("host_clock", "device_trace")
    if section == "configs":
        assert len(entry["reduced"]) <= 16
        assert all(NAME.fullmatch(k) for k in entry["reduced"])
        assert PATH.fullmatch(entry["file"])
        assert os.path.isfile(os.path.join(ROOT, entry["file"]))
    if section == "workloads":
        assert entry["chips"] in (1, 4)
        assert NAME.fullmatch(entry["config"])
        assert NAME.fullmatch(entry["traffic"])


def test_the_lists_hold_together():
    bench = _benchmark()
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert all(_line(word) for word in bench["command"])
    configs = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    metrics = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    for names in (configs, cells, metrics):
        assert len(names) == len(set(names))
    assert 1 <= len(configs) <= 24 and 1 <= len(cells) <= 24
    assert 1 <= len(bench["per_layer"]) <= 128
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    assert all(any(f.startswith(p + "/") for p in bench["paths"])
               for f in files)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    for metric in bench["per_layer"]:
        assert metric["moves"] in end_to_end
    for metric in bench["end_to_end"] + bench["per_layer"]:
        assert set(metric.get("workloads", ())) <= set(cells)
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(cells) // 4)
    runs = (2 + 14 * len(cells)) * (bench["run_seconds"] + 60)
    assert runs + 2 * 90 * len(cells) + 1200 <= 43200
