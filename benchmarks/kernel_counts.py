"""Operations and HBM bytes of the attention kernels, from shapes, and the
reduction of a kernel family's device events to a share of its roof.

A Pallas call is a custom call to the trace: it carries no counts. A
`<kernel>_roofline` metric therefore takes a kernel's operations and bytes
PER CALL from here and its seconds and calls from the trace
(`span_reduce.by_scope`'s rows under the call's `name=`).

What is counted is what the mathematics needs, not what the tiles do:
two operations a multiply-add over the (query, key) pairs the causal band
really has (the tiles on the band's edges compute pairs they then mask:
those do not count), and every operand and result moved once (a K/V tile
is fetched again for every Q block that needs it: that does not count
either). Both under-read the work done, so a share can only read low.
"""

from __future__ import annotations

import json
import os

from benchmarks import harness, span_reduce


def causal_pairs(t: int, window=None) -> int:
    """(query, key) pairs of a causal layer over `t` tokens: query i sees
    `min(i + 1, window)` keys."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def attention_calls(prefix: str, *, batch: int, t: int, heads: int,
                    kv_heads: int, head_dim: int, window=None,
                    itemsize: int = 2) -> dict:
    """{call name: (operations, HBM bytes)} per call of the forward kernel
    and the two backward kernels named `<prefix>_fwd`, `<prefix>_bwd_dq`
    and `<prefix>_bwd_dkdv`. Matrix products over the band's pairs: the
    forward has two (scores, values), dQ three (scores, dP, dQ), dK/dV
    four (scores, dP, dV, dK). Bytes: q and o or their cotangents are
    [T, H, Dh], k and v [T, Hkv, Dh]; the row statistics (log-sum-exp and
    delta, float32, 128 lanes wide as the kernels read them) are counted
    where a kernel reads them."""
    product = 2 * batch * heads * head_dim * causal_pairs(t, window)
    wide = batch * t * heads * head_dim * itemsize
    narrow = batch * t * kv_heads * head_dim * itemsize
    stats = batch * t * heads * 128 * 4
    return {
        prefix + "_fwd": (2 * product, 2 * wide + 2 * narrow),
        prefix + "_bwd_dq": (3 * product, 3 * wide + 2 * narrow + 2 * stats),
        prefix + "_bwd_dkdv": (4 * product,
                               2 * wide + 4 * narrow + 2 * stats),
    }


def family_share(scopes, calls: dict, peaks):
    """The share of the nearer roof, in %, of all device events of the
    calls in `calls` ({name: (operations, bytes) per call}) together:
    summed operations over summed seconds against the bf16 peak, or summed
    bytes against the HBM peak, whichever is larger. None where none of
    them ran. Each name goes through `span_reduce.roofline_share` first,
    which refuses a single call over 105% of a roof; the family is held to
    the same."""
    seconds = operations = moved = 0.0
    for name, (ops, nbytes) in calls.items():
        if span_reduce.roofline_share(scopes, name, peaks, flops=ops,
                                      hbm_bytes=nbytes) is None:
            continue
        for row in [r["inner"][name] for r in scopes.values()
                    if name in r.get("inner", {})]:
            seconds += row["s"]
            operations += ops * row["n"]
            moved += nbytes * row["n"]
    if not seconds:
        return None
    share = 100.0 * max(operations / seconds / peaks["bf16_flops_per_s"],
                        moved / seconds / peaks["hbm_bytes_per_s"])
    if share > 105.0:
        raise ValueError(f"{sorted(calls)}: {share:.1f}% of the roof")
    return share


def inner_share(scopes, names) -> float | None:
    """Share, in %, of all device op time that lies under any of the
    inner scopes `names` (below a layer's own scope). None where no op
    carries one of them."""
    total = sum(row["s"] for row in scopes.values())
    under = sum(row["inner"][name]["s"] for row in scopes.values()
                for name in names if name in row.get("inner", {}))
    return 100.0 * under / total if total and under else None


def gauges(name: str) -> list:
    """The values of the program's registry gauges called `name`, one a
    label set; empty where the program has none (an older program)."""
    from deeplearning4j_tpu.observe import get_registry

    return [g.value for g in get_registry().series()
            if g.name == name and getattr(g, "kind", "") == "gauge"]


def cell_config(run: dict):
    """The configuration of the cell a run's facts name (`run["workload"]`),
    through `BENCHMARK.json` as `run.py` finds it; None for a cell that is
    not there (a test's)."""
    with open(os.path.join(harness.ROOT, "BENCHMARK.json"),
              encoding="utf-8") as fh:
        spec = json.load(fh)
    cell = next((c for c in spec["workloads"]
                 if c["name"] == run["workload"]), None)
    if cell is None:
        return None
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    return harness.load_json(harness.ROOT, entry["file"])


def attention_roofline(facts, kind: str, prefix: str):
    """`family_share` of the kernels `<prefix>_*` that serve the layers of
    `layer_types` `kind` in the run's configuration; None where nothing
    was traced, the configuration has no such layer, or none of them ran."""
    run = facts["run"]
    if facts["trace"] is None or not facts["scopes"] or not run["peaks"]:
        return None
    cfg = cell_config(run)
    if cfg is None or kind not in cfg.get("layer_types", ()):
        return None
    calls = attention_calls(
        prefix, batch=run["global_batch"] // run["chips"],
        t=cfg["input_shape"][0], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        window=cfg["sliding_window"] if kind == "sliding_attention"
        else None)
    return family_share(facts["scopes"], calls, run["peaks"])
