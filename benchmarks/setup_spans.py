"""Set-up, from the program's own spans: what the `.setup` readers of
`benchmarks/layer_metrics/` share, and the table of `PERF.md` section 5.

Since PR 38 the program times its set-up itself
(`deeplearning4j_tpu/observe/`): `import.<subpackage>` round each
`__init__.py`'s imports, `net.init` and `wrapper.init`, `step.build`,
JAX's own timed regions as `xla.trace`, `xla.lower` and `xla.compile`
(`fun_name`; `fetched` true where the persistent cache served the
program), the watchdog's probe as `compile.probe` with a child a leg, and
the warm-up `fit()` as the first `fit` root with its `fit.epoch_sync`.

What a reader must know of them:

- An `xla.*` span lies under whatever span was open on its thread when
  JAX's region closed; only a thread's OUTERMOST regions are spans (an
  inner jit's trace is inside its caller's). In time such spans nest with
  spans that are not their relatives: the harness calls `net.init()`
  inside a `jax.jit`, so `net.init` lies inside that program's
  `xla.trace`, and the step's first trace holds the lazy `import.ops`. A
  metric here is the union of the intervals of ONE kind of span; only
  `table` sets the kinds against each other.
- The probe lowers and compiles the step a second time, which fires the
  same regions: every `xla.*` reader leaves out what has a
  `compile.probe` above it.
- In a traced run `TraceWindow.start()` jits its beacon between the
  warm-up and the window: spans whose `fun_name` holds
  `dl4j_trace_beacon` are left out.
- The ring holds 4,096 spans. Once more have been recorded the oldest are
  gone and every reader gives None rather than a part.
- A program without these spans (the parent of PR 38) gives None to every
  reader: the sign is that its store holds no `import.*` span.
"""

from __future__ import annotations

from benchmarks import span_reduce

BEACON = "dl4j_trace_beacon"
PROBE = "compile.probe"
XLA = ("xla.trace", "xla.lower", "xla.compile")


def held():
    """(spans, spans ever recorded, the ring's capacity) of the program's
    span store, or None for a program that has none."""
    try:
        from deeplearning4j_tpu.observe.trace import get_span_store
    except ImportError:
        return None
    store = get_span_store()
    return store.events(), store.count, store.capacity


def split(source=None):
    """(set-up's spans, the window's) out of `source` (what `held` gives):
    set-up's are those that ended before the last `fit` root began, the
    window's that root's subtree. None where there is nothing sound to
    read: no store, a ring that has wrapped, no `fit` root, or a program
    that does not time its set-up."""
    source = held() if source is None else source
    if source is None:
        return None
    spans, count, capacity = source
    if count > capacity:
        return None
    if not any(s["name"].startswith("import.") for s in spans):
        return None
    window = span_reduce.window(spans)
    if not window:
        return None
    start = window[0]["start_ns"]
    return [s for s in spans if s["end_ns"] <= start], window


def _above(spans):
    """{span_id: names of the spans above it}."""
    by_id = {s["span_id"]: s for s in spans}
    out = {}
    for s in spans:
        names, p = [], s["parent_id"]
        while p in by_id:
            names.append(by_id[p]["name"])
            p = by_id[p]["parent_id"]
        out[s["span_id"]] = names
    return out


def program_regions(spans, name: str):
    """The `xla.*` spans named `name` that are the program's own: not the
    probe's and not the traced run's beacon."""
    above = _above(spans)
    return [s for s in spans if s["name"] == name
            and BEACON not in str(s["attrs"].get("fun_name"))
            and not any(a.startswith(PROBE) for a in above[s["span_id"]])]


def union_ms(spans) -> float:
    """Milliseconds covered by at least one of `spans`."""
    total, reach = 0, None
    for s in sorted(spans, key=lambda s: s["start_ns"]):
        lo, hi = s["start_ns"], s["end_ns"]
        if reach is None or lo > reach:
            total += hi - lo
            reach = hi
        elif hi > reach:
            total += hi - reach
            reach = hi
    return total / 1e6


def _first_fit(setup):
    """The subtree of set-up's first `fit` root: the warm-up's."""
    roots = [s for s in setup if s["name"] == "fit"
             and s["parent_id"] is None]
    if not roots:
        return []
    first = min(roots, key=lambda s: s["start_ns"])
    return span_reduce.window(
        [s for s in setup if s["start_ns"] <= first["end_ns"]])


def _kind_ms(setup, names):
    found = [s for s in setup if s["name"].startswith(names)]
    return union_ms(found) if found else None


def _region_ms(setup, name):
    found = program_regions(setup, name)
    return union_ms(found) if found else None


def _compiled(spans):
    """How many of the program's `xla.compile` spans in `spans` were real
    compiles (`fetched` false)."""
    return sum(1 for s in program_regions(spans, "xla.compile")
               if not s["attrs"].get("fetched"))


def read(metric: str, source=None):
    """The value of one of this PR's metrics, or None (see `split`; also
    where set-up holds no span of the metric's kind)."""
    parts = split(source)
    if parts is None:
        return None
    setup, window = parts
    if metric == "import_ms.setup":
        return _kind_ms(setup, "import.")
    if metric == "init_ms.setup":
        return _kind_ms(setup, ("net.init", "wrapper.init"))
    if metric == "trace_ms.setup":
        return _region_ms(setup, "xla.trace")
    if metric == "lower_ms.setup":
        return _region_ms(setup, "xla.lower")
    if metric == "compile_or_fetch_ms.setup":
        return _region_ms(setup, "xla.compile")
    if metric == "compile_probe_ms.setup":
        probes = span_reduce.durations_ms(setup, PROBE)
        return sum(probes) if probes else None
    if metric == "first_fit_ms.setup":
        return sum(span_reduce.durations_ms(_first_fit(setup)[:1], "fit")) \
            or None
    if metric == "warmup_sync_ms.setup":
        syncs = span_reduce.durations_ms(_first_fit(setup), "fit.epoch_sync")
        return sum(syncs) if syncs else None
    # the two counts: 0 is a reading, so None only where the program
    # records no compile at all
    if not program_regions(setup, "xla.compile"):
        return None
    if metric == "xla_compiles.setup":
        return _compiled(setup)
    if metric == "xla_compile_spans_in_window.train":
        return _compiled(window)
    raise KeyError(metric)


# ------------------------------------------------------------- the table
def kind(span, above) -> str:
    """The row of `table` a span's own time goes to."""
    name = span["name"]
    legs = [a for a in above if a.startswith(PROBE + ".")]
    if name in XLA and legs:
        return legs[0]                  # the probe's own regions: its leg
    if name == "xla.compile":
        return "xla.compile (%s)" % (
            "fetched" if span["attrs"].get("fetched") else "compiled")
    if name.startswith("import."):
        return "import"
    if name in ("net.init", "wrapper.init"):
        return "init"
    if name == "fit.epoch":
        return "fit"
    return name


def innermost_ns(spans, lo: int, hi: int) -> dict:
    """{span_id: ns of [lo, hi) in which that span is the innermost one
    open, None: ns no span covers}. `spans` are one thread's, so in time
    they nest or follow each other, whoever their parents are."""
    own, stack, t = {}, [], lo

    def credit(upto):
        nonlocal t
        upto = min(max(upto, lo), hi)
        if upto > t:
            key = stack[-1]["span_id"] if stack else None
            own[key] = own.get(key, 0) + upto - t
            t = upto

    for s in sorted(spans, key=lambda s: (s["start_ns"], -s["end_ns"])):
        while stack and stack[-1]["end_ns"] <= s["start_ns"]:
            credit(stack[-1]["end_ns"])
            stack.pop()
        credit(s["start_ns"])
        stack.append(s)
    while stack:
        credit(stack[-1]["end_ns"])
        stack.pop()
    credit(hi)
    return own


def table(spans, phases) -> dict:
    """{phase: {row: ms}} for `phases`, a list of (name, start_ns, end_ns)
    on the spans' clock that tile set-up: every instant goes to the
    innermost span open on the fit loop's thread (`kind` says under which
    row), or to `(no span)`. The rows of a phase sum to its length."""
    roots = [s for s in spans if s["name"] == "fit"]
    thread = roots[0]["thread"] if roots else None
    mine = [s for s in spans if thread is None or s["thread"] == thread]
    above = _above(mine)
    by_id = {s["span_id"]: s for s in mine}
    out = {}
    for name, lo, hi in phases:
        rows = {}
        for sid, ns in innermost_ns(mine, int(lo), int(hi)).items():
            row = "(no span)" if sid is None else kind(by_id[sid], above[sid])
            rows[row] = rows.get(row, 0.0) + ns / 1e6
        out[name] = rows
    return out
