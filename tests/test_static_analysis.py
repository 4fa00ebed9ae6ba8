"""graft-lint (deeplearning4j_tpu.analysis) — rule fixtures, suppression
and baseline semantics, renderer round-trips, CLI exit codes, and the
meta-test that the shipped tree lints clean under the CI gate.

Every rule in the registry has at least one positive fixture (the rule
fires) and one negative fixture (a near-miss the rule must stay quiet
on) in FIXTURES below — a new rule without fixtures fails
test_every_rule_has_fixtures.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from deeplearning4j_tpu.analysis import (
    DEFAULT_HOT_PREFIXES, RULES, RUNTIME_RULE_HINTS, apply_baseline,
    is_hot, lint_paths, lint_source, load_baseline, runtime_hint,
    write_baseline,
)
from deeplearning4j_tpu.analysis.__main__ import main as lint_main
from deeplearning4j_tpu.analysis.report import (
    render_json, render_sarif, render_text, summarize,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rules_of(src, *, hot=False, path="pkg/mod.py"):
    return [f.rule for f in lint_source(textwrap.dedent(src),
                                        path, hot=hot)]


# --------------------------------------------------------------- fixtures
# rule id -> list of (source, hot, fires?) cases; the first True case is
# the positive fixture, the first False case the negative.

FIXTURES = {
    "GL000": [
        ("def broken(:\n    pass\n", False, True),
        ("x = 1\n", False, False),
    ],
    "GL001": [
        ("""
         import jax
         @jax.jit
         def f(x):
             return float(x)
         """, False, True),
        ("""
         import jax
         @jax.jit
         def f(x):
             return float(x.shape[0])   # static under trace
         """, False, False),
    ],
    "GL002": [
        ("""
         import jax
         @jax.jit
         def f(x):
             return x.item()
         """, False, True),
        ("""
         def host(x):
             return x.item()            # not traced, not hot
         """, False, False),
    ],
    "GL003": [
        ("""
         import jax
         @jax.jit
         def f(x):
             if x > 0:
                 return x
             return -x
         """, False, True),
        ("""
         import jax
         @jax.jit
         def f(x):
             if x is None:              # identity test is host-static
                 return 0
             return x
         """, False, False),
    ],
    "GL004": [
        ("""
         import jax
         @jax.jit
         def f(x):
             assert x > 0
             return x
         """, False, True),
        ("""
         import jax
         @jax.jit
         def f(x):
             assert x.ndim == 2         # shape metadata is static
             return x
         """, False, False),
    ],
    "GL005": [
        ("""
         import jax
         @jax.jit
         def f(x, n):
             acc = x
             for i in range(n):
                 acc = acc + i
             return acc
         """, False, True),
        ("""
         import jax
         @jax.jit
         def f(x):
             acc = x
             for i in range(3):         # static trip count unrolls fine
                 acc = acc + i
             return acc
         """, False, False),
    ],
    "GL101": [
        ("""
         import jax
         from functools import partial
         @partial(jax.jit, static_argnames=("cfg",))
         def f(x, cfg=[]):
             return x
         """, False, True),
        ("""
         import jax
         from functools import partial
         @partial(jax.jit, static_argnames=("cfg",))
         def f(x, cfg=()):
             return x
         """, False, False),
    ],
    "GL102": [
        ("""
         import jax
         def run(x):
             return jax.jit(lambda y: y + 1)(x)
         """, False, True),
        ("""
         import jax
         class Model:
             def run(self, x):
                 if self._jitted is None:
                     self._jitted = jax.jit(self._step)  # cached once
                 return self._jitted(x)
         """, False, False),
    ],
    "GL103": [
        ("""
         import jax
         def train(batches):
             for b in batches:
                 step = jax.jit(lambda y: y * 2)
                 step(b)
         """, False, True),
        ("""
         import jax
         step = jax.jit(lambda y: y * 2)    # module level: compiled once
         """, False, False),
    ],
    "GL201": [
        ("""
         import numpy as np
         import jax.numpy as jnp
         def report(x):
             y = jnp.sum(x)
             return np.asarray(y)
         """, True, True),
        ("""
         import numpy as np
         def report(request_json):
             return np.asarray(request_json["rows"])   # host data
         """, True, False),
    ],
    "GL202": [
        ("""
         import jax.numpy as jnp
         def score(x):
             return float(jnp.sum(x))
         """, True, True),
        ("""
         import os
         def workers():
             return int(os.environ["N_WORKERS"])       # host int
         """, True, False),
    ],
    "GL203": [
        ("""
         def wait(x):
             x.block_until_ready()
         """, True, True),
        ("""
         def wait(x):
             x.block_until_ready()      # cold module: fine
         """, False, False),
    ],
    "GL204": [
        ("""
         import jax.numpy as jnp
         def log_loss(logger, x):
             loss = jnp.mean(x)
             logger.info("loss %s", loss)
         """, True, True),
        ("""
         def log_n(logger, n):
             logger.info("n %d", n)     # host scalar payload
         """, True, False),
    ],
    "GL301": [
        ("""
         import threading
         class Store:
             def __init__(self):
                 self._lock = threading.Lock()
                 self.items = []
             def add(self, x):
                 self.items.append(x)
         """, False, True),
        ("""
         import threading
         class Store:
             def __init__(self):
                 self._lock = threading.Lock()
                 self.items = []
             def add(self, x):
                 with self._lock:
                     self.items.append(x)
         """, False, False),
    ],
    "GL401": [
        ("def f(x, acc=[]):\n    return acc\n", False, True),
        ("def f(x, acc=None):\n    return acc\n", False, False),
    ],
    "GL402": [
        ("""
         def f():
             try:
                 return 1
             except:
                 return 0
         """, False, True),
        ("""
         def f():
             try:
                 return 1
             except Exception:
                 return 0
         """, False, False),
    ],
    "GL403": [
        ("""
         def f():
             try:
                 return 1
             except ValueError:
                 pass
         """, False, True),
        ("""
         import logging
         def f():
             try:
                 return 1
             except ValueError:
                 logging.exception("f failed")
         """, False, False),
    ],
    "GL501": [
        ("""
         import jax
         from jax.sharding import Mesh
         def build():
             return Mesh(jax.devices(), ("data",))
         """, False, True),
        ("""
         from deeplearning4j_tpu.parallel.mesh import make_mesh
         def build():
             return make_mesh()
         """, False, False),
    ],
    "GL601": [
        ("""
         import jax.numpy as jnp
         from deeplearning4j_tpu.observe import span
         def step(x):
             y = jnp.dot(x, x)
             with span("train.step", loss=y):
                 return y
         """, True, True),
        ("""
         import jax.numpy as jnp
         def record(hist, x):
             y = jnp.dot(x, x)
             hist.observe(0.5, exemplar=y)
         """, True, True),
        ("""
         import jax.numpy as jnp
         def step(hist, x, tid):
             y = jnp.dot(x, x)
             hist.observe(y.shape[0], exemplar=tid)
             return y
         """, True, False),
        # stitch seam: grafting a replica subtree under a hop span must
        # stay host-side — a devicey attr on the graft span is a trap
        ("""
         import jax.numpy as jnp
         from deeplearning4j_tpu.observe import reqtrace
         def stitch(tid, hop, x):
             y = jnp.dot(x, x)
             reqtrace.record_span(tid, "decode.hop", tokens=y)
         """, True, True),
        # the real seam passes only host scalars — no finding
        ("""
         from deeplearning4j_tpu.observe import reqtrace
         def stitch(tid, replica, skew_ms):
             reqtrace.record_span(tid, "decode.hop", replica=replica,
                                  clock_skew_ms=skew_ms)
         """, True, False),
    ],
    "GL602": [
        ("""
         from deeplearning4j_tpu.observe.registry import get_registry
         def worker(batches):
             reg = get_registry()
             for b in batches:
                 run(b)
                 doc = reg.snapshot()
         """, True, True),
        ("""
         import jax
         @jax.jit
         def step(metrics, x):
             metrics.to_prometheus()
             return x
         """, False, True),
        ("""
         from deeplearning4j_tpu.observe.registry import get_registry
         def report():
             reg = get_registry()
             return reg.snapshot()
         """, True, False),
        # scrape seam: snapshotting the registry once per replica in
        # the federation loop re-locks every series per iteration
        ("""
         from deeplearning4j_tpu.observe.registry import get_registry
         def scrape(replicas, fed):
             reg = get_registry()
             for name in replicas:
                 fed.ingest(name, reg.snapshot())
         """, True, True),
        # the real scrape tick snapshots once, outside any loop
        ("""
         from deeplearning4j_tpu.observe.registry import get_registry
         def scrape_once(fed):
             reg = get_registry()
             doc = reg.snapshot()
             fed.ingest("self", doc)
             return doc
         """, True, False),
    ],
    # GL7xx — interprocedural lockset pass (callgraph.py + locks.py)
    "GL701": [
        ("""
         import threading
         class Store:
             def __init__(self):
                 self._lock = threading.Lock()
                 self.items = []
             def add(self, x):
                 with self._lock:
                     self.items.append(x)
             def peek(self):
                 return self.items[-1]   # no caller holds _lock
         """, False, True),
        ("""
         import threading
         class Store:
             def __init__(self):
                 self._lock = threading.Lock()
                 self.items = []
             def add(self, x):
                 with self._lock:
                     self._append(x)
             def _append(self, x):
                 self.items.append(x)    # entry-held via add()
         """, False, False),
    ],
    "GL702": [
        ("""
         import threading
         class Pair:
             def __init__(self):
                 self._a_lock = threading.Lock()
                 self._b_lock = threading.Lock()
             def ab(self):
                 with self._a_lock:
                     with self._b_lock:
                         pass
             def ba(self):
                 with self._b_lock:
                     with self._a_lock:
                         pass
         """, False, True),
        ("""
         import threading
         class Pair:
             def __init__(self):
                 self._a_lock = threading.Lock()
                 self._b_lock = threading.Lock()
             def ab(self):
                 with self._a_lock:
                     with self._b_lock:
                         pass
             def ab2(self):              # same order everywhere
                 with self._a_lock:
                     with self._b_lock:
                         pass
         """, False, False),
    ],
    "GL703": [
        ("""
         import threading
         import time
         class Worker:
             def __init__(self):
                 self._lock = threading.Lock()
             def run(self):
                 with self._lock:
                     time.sleep(0.1)     # blocks every other holder
         """, True, True),
        ("""
         import threading
         class Worker:
             def __init__(self):
                 self._cv = threading.Condition()
             def run(self):
                 with self._cv:
                     self._cv.wait(0.1)  # wait() releases its own lock
         """, True, False),
    ],
    "GL704": [
        ("""
         import threading
         class Mgr:
             def __init__(self):
                 self._lock = threading.Lock()
                 self.pending = []
             def submit(self, fut, x):
                 with self._lock:
                     self.pending.append(x)
                     fut.add_done_callback(
                         lambda f: self.pending.append(f))
         """, False, True),
        ("""
         import threading
         class Mgr:
             def __init__(self):
                 self._lock = threading.Lock()
                 self.pending = []
             def submit(self, fut, x):
                 with self._lock:
                     self.pending.append(x)
                     fut.add_done_callback(
                         lambda f: self._consume(f))
             def _consume(self, f):
                 with self._lock:
                     self.pending.append(f)
         """, False, False),
    ],
    "GL801": [
        ("""
         import jax
         def train(state, batch):
             step = jax.jit(lambda s, b: s, donate_argnums=(0,))
             new_state = step(state, batch)
             return state          # read after donation
         """, False, True),
        ("""
         import jax
         def train(state, batch):
             step = jax.jit(lambda s, b: s, donate_argnums=(0,))
             state = step(state, batch)   # same-statement rebind
             return state
         """, False, False),
    ],
    "GL802": [
        ("""
         import jax
         import jax.numpy as jnp
         from jax.sharding import PartitionSpec as P
         @jax.jit
         def f(x, y):
             a = jax.lax.with_sharding_constraint(x, P("data"))
             b = jax.lax.with_sharding_constraint(y, P("model"))
             return jnp.concatenate([a, b])
         """, False, True),
        ("""
         import jax
         import jax.numpy as jnp
         from jax.sharding import PartitionSpec as P
         @jax.jit
         def f(x, y):
             a = jax.lax.with_sharding_constraint(x, P("data"))
             b = jax.lax.with_sharding_constraint(y, P("data"))
             return jnp.concatenate([a, b])   # same spec: no reshard
         """, False, False),
    ],
    "GL803": [
        ("""
         import jax
         step = jax.jit(lambda tree: tree)
         def a(u, v):
             return step({"w": u, "b": v})
         def b(u, v):
             return step({"b": v, "w": u})   # key order flips treedef
         """, False, True),
        ("""
         import jax
         step = jax.jit(lambda tree: tree)
         def a(u, v):
             return step({"w": u, "b": v})
         def b(u, v):
             return step({"w": v, "b": u})   # same treedef
         """, False, False),
    ],
    "GL804": [
        ("""
         import json
         import jax
         def export(params):
             y = jax.jit(lambda a: a)(params)
             return json.dumps({"y": y})
         """, False, True),
        ("""
         import json
         import jax
         import numpy as np
         def export(params):
             y = jax.jit(lambda a: a)(params)
             return json.dumps({"y": np.asarray(y).tolist()})
         """, False, False),
    ],
    "GL805": [
        ("""
         import jax
         @jax.jit
         def f(x):
             return jax.lax.psum(x, "data")
         """, False, True),
        ("""
         import jax
         @jax.jit
         def f(x, axis):
             return jax.lax.psum(x, axis)   # spine-provided axis name
         """, False, False),
    ],
}


def test_every_rule_has_fixtures():
    assert len(RULES) >= 12
    missing = set(RULES) - set(FIXTURES)
    assert not missing, f"rules without fixtures: {sorted(missing)}"
    for rid, cases in FIXTURES.items():
        outcomes = {fires for _, _, fires in cases}
        assert outcomes == {True, False}, \
            f"{rid} needs both a positive and a negative fixture"


@pytest.mark.parametrize(
    "rid,src,hot,fires",
    [(rid, src, hot, fires)
     for rid, cases in sorted(FIXTURES.items())
     for src, hot, fires in cases],
    ids=lambda v: v if isinstance(v, str) and v.startswith("GL") else None)
def test_rule_fixture(rid, src, hot, fires):
    got = rules_of(src, hot=hot)
    if fires:
        assert rid in got, f"{rid} should fire; got {got}"
    else:
        assert rid not in got, f"{rid} must stay quiet; got {got}"


# ----------------------------------------------------- traced-context IQ

def test_wrapper_call_slots_mark_traced():
    # function passed to lax.while_loop is traced even without @jit
    src = """
    import jax
    from jax import lax
    def cond(state):
        if state[0] > 0:            # tracer branch inside traced body
            return True
        return False
    def run(x):
        return lax.while_loop(cond, lambda s: s, (x,))
    """
    assert "GL003" in rules_of(src)


def test_host_result_jax_calls_are_not_devicey():
    src = """
    import jax
    def split(x, sharding):
        if jax.process_count() == 1:    # host int — not a sync
            return jax.device_put(x, sharding)
        return x
    """
    assert "GL202" not in rules_of(src, hot=True)


def test_tree_map_is_transparent_to_devicey_taint():
    src = """
    import jax
    import numpy as np
    def mean_of_host(gathered):
        m = jax.tree_util.tree_map(lambda g: g.mean(axis=0), gathered)
        return float(m["s"])            # host numpy stays host
    """
    assert "GL202" not in rules_of(src, hot=True)


# ------------------------------------------------------------ suppression

HOT_SYNC_SRC = """
import jax.numpy as jnp
def score(x):
    y = jnp.sum(x)
    return float(y){comment}
"""


def test_allow_sync_with_reason_suppresses():
    src = HOT_SYNC_SRC.format(
        comment="  # graft: allow-sync(once per epoch)")
    assert rules_of(src, hot=True) == []


def test_allow_sync_without_reason_does_not_suppress():
    src = HOT_SYNC_SRC.format(comment="  # graft: allow-sync()")
    assert "GL202" in rules_of(src, hot=True)


def test_allow_sync_comment_line_above():
    src = """
    import jax.numpy as jnp
    def score(x):
        y = jnp.sum(x)
        # graft: allow-sync(final readback)
        return float(y)
    """
    assert rules_of(src, hot=True) == []


def test_allow_sync_does_not_cover_tracer_rules():
    src = """
    import jax
    @jax.jit
    def f(x):
        # graft: allow-sync(not a sync rule)
        if x > 0:
            return x
        return -x
    """
    assert "GL003" in rules_of(src)


def test_allow_rule_same_line():
    src = """
    def f():
        try:
            return 1
        except ValueError:  # graft: allow(GL403): drain-until-empty
            pass
    """
    assert rules_of(src) == []


def test_allow_rule_comment_block_above():
    # the directive may sit anywhere in the contiguous comment block
    # directly above the flagged line (multi-line reasons)
    src = """
    import jax
    def train(batches):
        for b in batches:
            @jax.jit
            # graft: allow(GL103): one program per layer by
            # design -- layerwise pretraining compiles each once
            def step(y):
                return y * 2
            step(b)
    """
    assert "GL103" not in rules_of(src)


class TestMeshOutsideSpine:
    """GL501 — placement construction must flow through parallel/mesh.py."""

    def test_jax_attribute_forms_fire(self):
        src = """
        import jax
        import jax.sharding as jsh
        def build():
            m = jax.sharding.Mesh(jax.devices(), ("data",))
            n = jsh.Mesh(jax.local_devices(), ("data",))
            return m, n
        """
        assert rules_of(src).count("GL501") == 4

    def test_spine_module_itself_is_exempt(self):
        src = """
        import jax
        from jax.sharding import Mesh
        def make_mesh():
            return Mesh(jax.devices(), ("data",))
        """
        for path in ("deeplearning4j_tpu/parallel/mesh.py",
                     "parallel/mesh.py"):
            assert rules_of(src, path=path) == []

    def test_non_jax_mesh_or_devices_stay_quiet(self):
        src = """
        from mylib import Mesh
        class Topo:
            pass
        def build(t: Topo):
            return Mesh(t.devices(), ("data",))
        """
        assert "GL501" not in rules_of(src)

    def test_allow_with_reason_suppresses(self):
        src = """
        import jax
        def kinds():
            return jax.devices()[0].device_kind  # graft: allow(GL501): display only
        """
        assert rules_of(src) == []


def test_allow_wrong_rule_id_does_not_suppress():
    src = """
    def f():
        try:
            return 1
        except ValueError:  # graft: allow(GL402): wrong id
            pass
    """
    assert "GL403" in rules_of(src)


# --------------------------------------------------------------- baseline

def _two_findings_src(pad=0):
    return ("\n" * pad) + textwrap.dedent("""
    def f():
        try:
            return 1
        except ValueError:
            pass

    def g():
        try:
            return 2
        except KeyError:
            pass
    """)


def test_baseline_roundtrip_and_budget(tmp_path):
    findings = lint_source(_two_findings_src(), "a.py")
    assert len(findings) == 2
    bl_path = str(tmp_path / "bl.json")
    doc = write_baseline(findings, bl_path)
    assert doc["version"] == 1
    loaded = load_baseline(bl_path)
    new, used = apply_baseline(findings, loaded)
    assert new == [] and used == 2
    # a third identical finding exceeds the per-key budget
    tripled = findings + [findings[0]]
    new, used = apply_baseline(tripled, loaded)
    assert used == 2 and len(new) == 1


def test_baseline_is_line_number_insensitive(tmp_path):
    bl_path = str(tmp_path / "bl.json")
    write_baseline(lint_source(_two_findings_src(), "a.py"), bl_path)
    shifted = lint_source(_two_findings_src(pad=7), "a.py")
    new, used = apply_baseline(shifted, load_baseline(bl_path))
    assert new == [] and used == 2


def test_baseline_version_check(tmp_path):
    p = tmp_path / "bl.json"
    p.write_text(json.dumps({"version": 99, "findings": []}))
    with pytest.raises(ValueError):
        load_baseline(str(p))


# -------------------------------------------------------------- renderers

def _sample_findings():
    return lint_source(_two_findings_src(), "pkg/sample.py")


def test_json_roundtrip():
    findings = _sample_findings()
    doc = json.loads(render_json(findings, files=1, baselined=3))
    assert doc["tool"] == "graft-lint"
    s = doc["summary"]
    assert s["findings"] == len(findings) == 2
    assert s["files"] == 1 and s["baselined"] == 3
    assert s["by_rule"] == {"GL403": 2}
    for f, d in zip(findings, doc["findings"]):
        assert d["rule"] == f.rule and d["line"] == f.line
        assert d["path"] == "pkg/sample.py"


def test_sarif_shape():
    findings = _sample_findings()
    doc = json.loads(render_sarif(findings, files=1))
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "graft-lint"
    assert len(run["results"]) == len(findings)
    declared = {r["id"] for r in run["tool"]["driver"]["rules"]}
    for res in run["results"]:
        assert res["ruleId"] in declared
        loc = res["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"] == "pkg/sample.py"
        assert loc["region"]["startLine"] >= 1


def test_text_render_mentions_location_and_summary():
    out = render_text(_sample_findings(), files=1)
    assert "pkg/sample.py:" in out and "GL403" in out
    assert "2 finding(s)" in out


def test_summarize_counts_severities():
    s = summarize(_sample_findings())
    assert s["errors"] == 0 and s["warnings"] == 2


# -------------------------------------------------------------------- CLI

def _write(tmp_path, name, src):
    p = tmp_path / name
    p.write_text(textwrap.dedent(src))
    return str(p)


def test_cli_exit_codes(tmp_path, capsys):
    clean = _write(tmp_path, "clean.py", "x = 1\n")
    err = _write(tmp_path, "err.py", """
        import jax
        @jax.jit
        def f(x):
            return float(x)
        """)
    warn = _write(tmp_path, "warn.py", """
        def f(x, acc=[]):
            return acc
        """)
    assert lint_main([clean]) == 0
    assert lint_main([err]) == 1
    assert lint_main([warn]) == 0          # warnings pass by default
    assert lint_main([warn, "--strict"]) == 1
    assert lint_main([clean, "--baseline", str(tmp_path / "nope.json")]) == 2
    capsys.readouterr()


def test_cli_baseline_gate(tmp_path, capsys):
    err = _write(tmp_path, "err.py", """
        import jax
        @jax.jit
        def f(x):
            return float(x)
        """)
    bl = str(tmp_path / "bl.json")
    assert lint_main([err, "--write-baseline", bl]) == 0
    assert lint_main([err, "--strict", "--baseline", bl]) == 0
    out = capsys.readouterr().out
    assert "1 baselined" in out


def test_cli_select_ignore_and_formats(tmp_path, capsys):
    mixed = _write(tmp_path, "mixed.py", """
        import jax
        @jax.jit
        def f(x, acc=[]):
            return float(x)
        """)
    assert lint_main([mixed, "--select", "GL4", "--strict"]) == 1
    capsys.readouterr()
    assert lint_main([mixed, "--ignore", "GL0,GL4"]) == 0
    capsys.readouterr()
    assert lint_main([mixed, "--format", "json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert {f["rule"] for f in doc["findings"]} == {"GL001", "GL401"}
    assert lint_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in RULES:
        assert rid in out


def test_hot_prefix_override(tmp_path, capsys):
    hot_src = """
        import jax.numpy as jnp
        def score(x):
            return float(jnp.sum(x))
        """
    cold = _write(tmp_path, "cold.py", hot_src)
    assert lint_main([cold]) == 0
    assert lint_main([cold, "--hot-prefix", str(tmp_path)]) == 1
    capsys.readouterr()


def test_is_hot_prefixes():
    assert is_hot("deeplearning4j_tpu/optim/solvers.py",
                  DEFAULT_HOT_PREFIXES)
    assert not is_hot("deeplearning4j_tpu/nlp/glove.py",
                      DEFAULT_HOT_PREFIXES)


# ------------------------------------------------- runtime cross-check

def test_runtime_hint_strings():
    assert runtime_hint("recompile") == "GL101/GL102/GL103"
    assert runtime_hint("host_sync") == "GL001/GL002/GL201/GL202/GL203"
    assert runtime_hint("unknown") == ""
    for kind, rids in RUNTIME_RULE_HINTS.items():
        for rid in rids:
            assert rid in RULES, (kind, rid)


def test_watchdog_snapshot_carries_static_rules():
    from deeplearning4j_tpu.observe.watchdog import RecompileWatchdog
    wd = RecompileWatchdog(threshold=2)
    wd.record_compile("tag", "Cls", (1, 2))
    assert wd.snapshot()["static_rules"] == runtime_hint("recompile")


def test_syncmon_snapshot_carries_static_rules():
    from deeplearning4j_tpu.observe.syncmon import HostSyncMonitor
    snap = HostSyncMonitor().snapshot()
    assert snap["static_rules"] == runtime_hint("host_sync")
    assert snap["total"] == 0


def test_watchdog_warning_names_lint_rules(caplog):
    import logging
    from deeplearning4j_tpu.observe.watchdog import RecompileWatchdog
    wd = RecompileWatchdog(threshold=2)
    with caplog.at_level(logging.WARNING, logger="deeplearning4j_tpu"):
        wd.record_compile("tag", "Cls", (1,))
        wd.record_compile("tag", "Cls", (2,))
    assert any("GL101/GL102/GL103" in r.getMessage()
               for r in caplog.records)


# ------------------------------------------------- call graph (GL7xx)

def _program(src, path="pkg/mod.py"):
    from deeplearning4j_tpu.analysis.callgraph import CallGraph, Program
    prog = Program.from_sources([(path, textwrap.dedent(src))])
    return prog, CallGraph(prog)


def test_callgraph_resolves_self_dispatch():
    import ast
    prog, graph = _program("""
        class A:
            def f(self):
                self.g()
            def g(self):
                pass
        """)
    mod = prog.modules["pkg.mod"]
    f = mod.classes["A"].methods["f"]
    call = next(n for n in ast.walk(f.node) if isinstance(n, ast.Call))
    targets = graph.resolve(f, call)
    assert [t.qualname for t in targets] == ["pkg.mod.A.g"]


def test_callgraph_resolves_module_functions():
    import ast
    prog, graph = _program("""
        def helper():
            pass
        def entry():
            helper()
        """)
    mod = prog.modules["pkg.mod"]
    entry = mod.functions["entry"]
    call = next(n for n in ast.walk(entry.node)
                if isinstance(n, ast.Call))
    targets = graph.resolve(entry, call)
    assert [t.qualname for t in targets] == ["pkg.mod.helper"]


def test_callgraph_inherited_method_lookup():
    import ast
    prog, graph = _program("""
        class Base:
            def g(self):
                pass
        class A(Base):
            def f(self):
                self.g()
        """)
    mod = prog.modules["pkg.mod"]
    f = mod.classes["A"].methods["f"]
    call = next(n for n in ast.walk(f.node) if isinstance(n, ast.Call))
    targets = graph.resolve(f, call)
    assert [t.qualname for t in targets] == ["pkg.mod.Base.g"]


def test_lockset_recursion_terminates():
    # mutually recursive lock-holding methods must not loop the
    # entry-held fixpoint; bounded propagation makes this terminate
    # and the guarded access under recursion stays quiet.
    src = """
        import threading
        class R:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0
            def a(self, k):
                with self._lock:
                    self.n += 1
                    self.b(k)
            def b(self, k):
                if k:
                    self.a(k - 1)
                self.n += 1
        """
    got = rules_of(src)
    assert "GL701" not in got


# -------------------------------------- SARIF relatedLocations (GL7xx)

def _gl701_findings():
    src = FIXTURES["GL701"][0][0]
    return [f for f in lint_source(textwrap.dedent(src), "pkg/mod.py")
            if f.rule == "GL701"]


def test_gl701_finding_carries_related_guard_site():
    findings = _gl701_findings()
    assert findings, "positive GL701 fixture must fire"
    f = findings[0]
    assert f.related, "GL701 must point back at the guard site"
    rp, rl, rm = f.related[0]
    assert rp == "pkg/mod.py" and rl >= 1 and "Store._lock" in rm
    # to_dict round-trips the related sites for the JSON renderer
    d = f.to_dict()
    assert d["related"][0]["path"] == rp
    assert d["related"][0]["line"] == rl


def test_sarif_related_locations_roundtrip():
    findings = _gl701_findings()
    doc = json.loads(render_sarif(findings, files=1))
    res = doc["runs"][0]["results"][0]
    assert res["ruleId"] == "GL701"
    rel = res["relatedLocations"]
    assert rel, "GL7xx SARIF results must carry relatedLocations"
    phys = rel[0]["physicalLocation"]
    assert phys["artifactLocation"]["uri"] == "pkg/mod.py"
    assert phys["region"]["startLine"] == findings[0].related[0][1]
    assert rel[0]["message"]["text"] == findings[0].related[0][2]


def test_gl702_relates_both_acquisition_orders():
    src = FIXTURES["GL702"][0][0]
    findings = [f for f in lint_source(textwrap.dedent(src),
                                       "pkg/mod.py")
                if f.rule == "GL702"]
    assert len(findings) == 1
    assert "Pair._a_lock" in findings[0].message
    assert "Pair._b_lock" in findings[0].message
    # the finding anchors on one acquisition order; related points at
    # the opposing one
    assert findings[0].related
    assert "acquired here while" in findings[0].related[0][2]


# ----------------------------------------------------- --changed mode

def test_cli_changed_mode(tmp_path, capsys):
    import subprocess as sp
    repo = tmp_path / "r"
    repo.mkdir()
    env = {**os.environ, "GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
           "GIT_COMMITTER_NAME": "t", "GIT_COMMITTER_EMAIL": "t@t"}

    def git(*args):
        sp.run(["git", *args], cwd=repo, check=True, env=env,
               capture_output=True)

    git("init", "-q")
    (repo / "clean.py").write_text("x = 1\n")
    git("add", "."); git("commit", "-qm", "seed")
    cwd = os.getcwd()
    os.chdir(repo)
    try:
        # nothing changed vs HEAD -> no files -> exit 0
        assert lint_main(["--changed", "--strict"]) == 0
        capsys.readouterr()
        # an untracked file with an error IS picked up
        (repo / "err.py").write_text(textwrap.dedent("""
            import jax
            @jax.jit
            def f(x):
                return float(x)
            """))
        assert lint_main(["--changed"]) == 1
        out = capsys.readouterr().out
        assert "err.py" in out and "clean.py" not in out
        # positional paths filter the changed set
        assert lint_main(["clean.py", "--changed", "--strict"]) == 0
        capsys.readouterr()
        # committed -> clean again vs HEAD
        git("add", "."); git("commit", "-qm", "more")
        assert lint_main(["--changed", "--strict"]) == 0
        capsys.readouterr()
    finally:
        os.chdir(cwd)


# --------------------------------------- lockmon (runtime cross-check)

def test_lockmon_disabled_by_default(monkeypatch):
    from deeplearning4j_tpu.observe import lockmon
    monkeypatch.delenv("DL4J_TPU_LOCKMON", raising=False)
    lockmon.reset_witness()
    assert lockmon.get_witness() is None
    # MonitoredLock degrades to a plain lock with no witness
    lk = lockmon.MonitoredLock("X._lock")
    with lk:
        assert lk.locked()
    assert not lk.locked()


def test_lockmon_env_flag_enables(monkeypatch):
    from deeplearning4j_tpu.observe import lockmon
    monkeypatch.setenv("DL4J_TPU_LOCKMON", "1")
    lockmon.reset_witness()
    try:
        w = lockmon.get_witness()
        assert w is not None and lockmon.get_witness() is w
    finally:
        lockmon.reset_witness()


def test_lockmon_witness_field_unguarded():
    from deeplearning4j_tpu.observe.lockmon import (
        LockWitness, MonitoredLock,
    )
    w = LockWitness()
    lk = MonitoredLock("Store._lock", witness=w)
    with lk:
        w.witness_field("Store", "items", "Store._lock", write=True)
    w.witness_field("Store", "items", "Store._lock")   # guard not held
    rep = w.report()
    assert len(rep["unguarded"]) == 1
    ev = rep["unguarded"][0]
    assert ev["rule"] == "GL701"
    assert ev["field"] == "Store.items"
    assert rep["static_rules"]["guarded_field"] == runtime_hint(
        "guarded_field")


def test_lockmon_hammer_matches_static_gl702():
    """Thread-hammer the seeded ABBA pair: the runtime witness must
    name the same lock pair and rule id the static pass reports."""
    import threading
    from deeplearning4j_tpu.observe.lockmon import (
        LockWitness, MonitoredLock,
    )
    src = FIXTURES["GL702"][0][0]
    static = [f for f in lint_source(textwrap.dedent(src), "pkg/mod.py")
              if f.rule == "GL702"]
    assert len(static) == 1

    w = LockWitness()
    a = MonitoredLock("Pair._a_lock", witness=w)
    b = MonitoredLock("Pair._b_lock", witness=w)
    gate = threading.Event()

    def ab():
        with a:
            with b:
                pass
        gate.set()

    def ba():
        gate.wait(5.0)          # phase the orders: never deadlocks
        with b:
            with a:
                pass

    ts = [threading.Thread(target=ab), threading.Thread(target=ba)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=10.0)
        assert not t.is_alive()

    rep = w.report()
    assert len(rep["inversions"]) == 1
    inv = rep["inversions"][0]
    assert inv["rule"] == "GL702"
    assert inv["locks"] == ["Pair._a_lock", "Pair._b_lock"]
    # the cross-check: every runtime lock name appears verbatim in the
    # static finding's message, and the rule ids agree
    assert static[0].rule == inv["rule"]
    for name in inv["locks"]:
        assert name in static[0].message
    assert rep["static_rules"]["lock_order"] == runtime_hint("lock_order")


# ------------------------------------------------------------- meta-test

def test_repo_lints_clean_under_ci_gate():
    """The shipped tree passes the exact gate tools/ci_check.sh runs."""
    proc = subprocess.run(
        [sys.executable, "-m", "deeplearning4j_tpu.analysis",
         "deeplearning4j_tpu", "tests", "--strict",
         "--baseline", ".graftlint-baseline.json"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, \
        f"graft-lint gate failed:\n{proc.stdout}\n{proc.stderr}"


def test_lint_paths_filters_and_sorts(tmp_path):
    _write(tmp_path, "b.py", "def f(x, acc=[]):\n    return acc\n")
    _write(tmp_path, "a.py", "def g(x, acc={}):\n    return acc\n")
    found = lint_paths([str(tmp_path)])
    assert [f.rule for f in found] == ["GL401", "GL401"]
    assert found[0].path <= found[1].path
    assert lint_paths([str(tmp_path)], ignore=["GL4"]) == []
    assert len(lint_paths([str(tmp_path)], select=["GL401"])) == 2


# ---------------------------------- GL8xx shardflow (sharding/donation)

_HELPER_UAD_SRC = """
import jax
import jax.numpy as jnp


def make_step():
    def step(state, batch):
        return jax.tree_util.tree_map(lambda a: a + batch, state)

    return jax.jit(step, donate_argnums=(0,))


def train(state, batches):
    step = make_step()
    for batch in batches:
        new_state = step(state, batch)
        norm = jnp.sqrt(sum(jnp.sum(a * a) for a in state.values()))
        state = new_state
    return state
"""


def test_gl801_through_helper():
    """Donation facts cross a resolved helper: `make_step()` returns a
    donating callable, so the bound `step`'s first arg is donated."""
    findings = [f for f in lint_source(_HELPER_UAD_SRC, "pkg/train.py")
                if f.rule == "GL801"]
    assert len(findings) == 1
    f = findings[0]
    assert "`state`" in f.message
    assert f.related, "GL801 must point back at the donating call site"
    assert "donated here" in f.related[0][2]
    # the related donation site is the step(state, batch) call line
    assert f.related[0][1] < f.line or f.related[0][1] > 0


def test_gl801_self_attr_lazy_step():
    """The repo's lazily-built donated step idiom: `self._step =
    self._build_step()` types the attribute, and a stale read of the
    donated `self.params` after the call fires."""
    src = """
import jax


class Net:
    def _build_step(self):
        def step(params, opt, x):
            return params, opt

        return jax.jit(step, donate_argnums=(0, 1))

    def fit(self, x):
        if self._step is None:
            self._step = self._build_step()
        new_p, new_o = self._step(self.params, self.opt, x)
        norm = self.params          # stale: donated at position 0
        self.params, self.opt = new_p, new_o
        return norm
"""
    findings = [f for f in lint_source(src, "pkg/net.py")
                if f.rule == "GL801"]
    assert len(findings) == 1
    assert "`self.params`" in findings[0].message


def test_gl801_through_jit_step():
    """The trainers' idiom since `optim/step.py`: the getter returns
    `jit_step(...)`, which donates (params, opt_state, states), so a stale
    read of one of them between the call and the rebind fires."""
    src = """
from deeplearning4j_tpu.optim.step import jit_step


class Net:
    def _get_train_step(self, key):
        if key in self._jit_cache:
            return self._jit_cache[key]
        return jit_step(self.make_step_fn(), cache=self._jit_cache,
                        key=key, name="Net._step")

    def _fit_batch(self, x):
        fn = self._get_train_step(0)
        new_p, new_o, new_s, loss = fn(self.params, self.opt, self.states, x)
        norm = self.opt             # stale: donated at position 1
        self.params, self.opt, self.states = new_p, new_o, new_s
        return norm, loss
"""
    findings = [f for f in lint_source(src, "pkg/net.py")
                if f.rule == "GL801"]
    assert len(findings) == 1
    assert "`self.opt`" in findings[0].message


def test_gl801_real_pipeline_clean_and_mutant_fires():
    """Regression pin for the audited tree: the shipped
    parallel/pipeline.py same-statement-rebind idiom is GL801-clean,
    and re-introducing a stale read between the donating call and the
    rebind fires at exactly that read."""
    path = os.path.join(REPO_ROOT, "deeplearning4j_tpu", "parallel",
                        "pipeline.py")
    with open(path, encoding="utf-8") as fh:
        src = fh.read()
    rel = "deeplearning4j_tpu/parallel/pipeline.py"
    clean = [f for f in lint_source(src, rel) if f.rule == "GL801"]
    assert clean == [], [f.message for f in clean]

    target = """        self.params, self.opt_state, loss = self._step(
            self.params, self.opt_state, jnp.asarray(it, jnp.int32),
            x_mb, y_mb)
        return float(loss)"""
    mutant = """        new_params, new_opt, loss = self._step(
            self.params, self.opt_state, jnp.asarray(it, jnp.int32),
            x_mb, y_mb)
        norm = _tmap(lambda a: a * a, self.params)
        self.params, self.opt_state = new_params, new_opt
        return float(loss)"""
    assert target in src, "pipeline fit_batch idiom moved; update test"
    broken = src.replace(target, mutant, 1)
    fired = [f for f in lint_source(broken, rel) if f.rule == "GL801"]
    assert fired, "stale read of donated self.params must fire GL801"
    assert "`self.params`" in fired[0].message
    assert fired[0].related and "donated here" in fired[0].related[0][2]


def test_gl802_relates_both_placement_sites():
    src = FIXTURES["GL802"][0][0]
    findings = [f for f in lint_source(textwrap.dedent(src), "pkg/mod.py")
                if f.rule == "GL802"]
    assert len(findings) == 1
    f = findings[0]
    assert f.related and len(f.related) >= 2, \
        "GL802 must relate the two placement sites"


def test_gl803_two_call_sites_carry_related():
    src = FIXTURES["GL803"][0][0]
    findings = [f for f in lint_source(textwrap.dedent(src), "pkg/mod.py")
                if f.rule == "GL803"]
    assert len(findings) == 1
    f = findings[0]
    assert f.related, "GL803 must point at the other call site"
    assert f.related[0][1] != f.line


def test_gl804_device_get_launders():
    src = """
import json
import jax


def export(params):
    y = jax.jit(lambda a: a)(params)
    return json.dumps({"y": jax.device_get(y)})
"""
    assert [f.rule for f in lint_source(src, "pkg/mod.py")
            if f.rule == "GL804"] == []


def test_gl805_mesh_module_is_exempt():
    src = textwrap.dedent(FIXTURES["GL805"][0][0])
    in_mesh = [f.rule for f in lint_source(
        src, "deeplearning4j_tpu/parallel/mesh.py")]
    assert "GL805" not in in_mesh


def test_gl8_allow_suppression_covers():
    src = """
import jax


def train(state, batch):
    step = jax.jit(lambda s, b: s, donate_argnums=(0,))
    new_state = step(state, batch)
    return state   # graft: allow(GL801): checkpoint reads pre-donation copy
"""
    assert "GL801" not in [f.rule for f in lint_source(src, "pkg/mod.py")]


def test_gl8_sarif_related_locations_roundtrip():
    findings = [f for f in lint_source(_HELPER_UAD_SRC, "pkg/train.py")
                if f.rule == "GL801"]
    doc = json.loads(render_sarif(findings, files=1))
    res = doc["runs"][0]["results"][0]
    assert res["ruleId"] == "GL801"
    rel = res["relatedLocations"]
    assert rel, "GL8xx SARIF results must carry relatedLocations"
    phys = rel[0]["physicalLocation"]
    assert phys["artifactLocation"]["uri"] == "pkg/train.py"
    assert phys["region"]["startLine"] == findings[0].related[0][1]
    assert rel[0]["message"]["text"] == findings[0].related[0][2]


def test_repo_gl8_audit_clean():
    """Acceptance gate: the strict GL8xx pass exits 0 over the package
    with no baseline."""
    proc = subprocess.run(
        [sys.executable, "-m", "deeplearning4j_tpu.analysis",
         "deeplearning4j_tpu", "--strict", "--select", "GL8",
         "--no-cache"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, \
        f"GL8xx audit failed:\n{proc.stdout}\n{proc.stderr}"


# ------------------------------- result cache (.graftlint-cache.json)

def _seed_tree(tmp_path, n=40):
    """A small synthetic package: every file parses, a couple carry
    findings, and the volume makes the cold interprocedural pass cost
    measurable."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    for i in range(n):
        body = "\n".join(
            f"def f{i}_{j}(x):\n"
            f"    y = x + {j}\n"
            f"    return y\n" for j in range(12))
        (pkg / f"m{i}.py").write_text(
            "import threading\n\n" + body, encoding="utf-8")
    (pkg / "bad.py").write_text(
        "def f(x, acc=[]):\n    return acc\n", encoding="utf-8")
    return pkg


def test_cache_warm_parity_and_speedup(tmp_path):
    import time
    pkg = _seed_tree(tmp_path)
    cache = str(tmp_path / "cache.json")
    t0 = time.perf_counter()
    cold = lint_paths([str(pkg)], cache_path=cache)
    t1 = time.perf_counter()
    warm = lint_paths([str(pkg)], cache_path=cache)
    t2 = time.perf_counter()
    assert [f.to_dict() for f in warm] == [f.to_dict() for f in cold]
    assert any(f.rule == "GL401" for f in warm)
    cold_s, warm_s = t1 - t0, t2 - t1
    assert warm_s * 5 <= cold_s, \
        f"warm re-lint must be >=5x faster (cold {cold_s:.3f}s, " \
        f"warm {warm_s:.3f}s)"


def test_cache_invalidated_on_edit(tmp_path):
    pkg = _seed_tree(tmp_path, n=3)
    cache = str(tmp_path / "cache.json")
    before = lint_paths([str(pkg)], cache_path=cache)
    assert sum(f.rule == "GL401" for f in before) == 1
    # introduce a new finding in a previously-clean file; bump mtime
    target = pkg / "m0.py"
    target.write_text("def g(x, acc={}):\n    return acc\n",
                      encoding="utf-8")
    os.utime(target, (0, 0))    # force a stat-signature change
    after = lint_paths([str(pkg)], cache_path=cache)
    assert sum(f.rule == "GL401" for f in after) == 2


def test_cache_invalidated_on_rules_version(tmp_path):
    from deeplearning4j_tpu.analysis import cache as cache_mod
    pkg = _seed_tree(tmp_path, n=2)
    cache = str(tmp_path / "cache.json")
    cold = lint_paths([str(pkg)], cache_path=cache)
    with open(cache, encoding="utf-8") as fh:
        doc = json.load(fh)
    assert doc["rules_version"] == cache_mod.RULES_VERSION
    # a rules-version bump discards the doc wholesale
    doc["rules_version"] = cache_mod.RULES_VERSION - 1
    with open(cache, "w", encoding="utf-8") as fh:
        json.dump(doc, fh)
    fresh = cache_mod.load_cache(cache, doc["config"])
    assert fresh["files"] == {}
    # and a relint recomputes with identical results
    warm = lint_paths([str(pkg)], cache_path=cache)
    assert [f.to_dict() for f in warm] == [f.to_dict() for f in cold]


def test_cache_partial_run_keeps_other_entries(tmp_path):
    """A subset (--changed-style) run must not evict full-run entries."""
    pkg = _seed_tree(tmp_path, n=3)
    cache = str(tmp_path / "cache.json")
    lint_paths([str(pkg)], cache_path=cache)
    with open(cache, encoding="utf-8") as fh:
        n_full = len(json.load(fh)["files"])
    lint_paths([str(pkg / "bad.py")], cache_path=cache)
    with open(cache, encoding="utf-8") as fh:
        assert len(json.load(fh)["files"]) == n_full


def test_cli_no_cache_flag(tmp_path, capsys):
    _write(tmp_path, "ok.py", "x = 1\n")
    cwd = os.getcwd()
    os.chdir(tmp_path)
    try:
        assert lint_main(["ok.py", "--strict"]) == 0
        assert os.path.exists(".graftlint-cache.json")
        os.remove(".graftlint-cache.json")
        assert lint_main(["ok.py", "--strict", "--no-cache"]) == 0
        assert not os.path.exists(".graftlint-cache.json")
    finally:
        os.chdir(cwd)
        capsys.readouterr()


# ------------------------------------------------------ prune-baseline

def test_prune_baseline_cli(tmp_path, capsys):
    _write(tmp_path, "mod.py",
           "def f(x, acc=[]):\n    return acc\n")
    cwd = os.getcwd()
    os.chdir(tmp_path)
    try:
        assert lint_main(["mod.py", "--write-baseline", "bl.json"]) == 0
        with open("bl.json", encoding="utf-8") as fh:
            doc = json.load(fh)
        doc["findings"].append({"rule": "GL402", "path": "gone.py",
                                "snippet": "except:", "count": 2})
        with open("bl.json", "w", encoding="utf-8") as fh:
            json.dump(doc, fh)
        capsys.readouterr()
        assert lint_main(["mod.py", "--baseline", "bl.json",
                          "--prune-baseline"]) == 0
        out = capsys.readouterr().out
        assert "pruned GL402 gone.py" in out
        assert "1 remain" in out
        kept = load_baseline("bl.json")
        assert list(kept) == [("GL401", "mod.py", "def f(x, acc=[]):")]
        # idempotent: nothing left to prune
        assert lint_main(["mod.py", "--baseline", "bl.json",
                          "--prune-baseline"]) == 0
        assert "pruned 0 stale" in capsys.readouterr().out
    finally:
        os.chdir(cwd)


# -------------------------------------- donatemon (runtime cross-check)

def test_donatemon_disabled_is_identity(monkeypatch):
    from deeplearning4j_tpu.observe import donatemon
    monkeypatch.delenv("DL4J_TPU_DONATEMON", raising=False)
    donatemon.reset_donation_witness()
    assert donatemon.get_donation_witness() is None

    def step(s, b):
        return s
    # zero-overhead contract: the function object comes back unchanged
    assert donatemon.instrument(step, (0,)) is step


def test_donatemon_env_flag_enables(monkeypatch):
    from deeplearning4j_tpu.observe import donatemon
    monkeypatch.setenv("DL4J_TPU_DONATEMON", "1")
    donatemon.reset_donation_witness()
    try:
        w = donatemon.get_donation_witness()
        assert w is not None and donatemon.get_donation_witness() is w

        def step(s, b):
            return s
        wrapped = donatemon.instrument(step, (0,))
        assert wrapped is not step
        assert wrapped.__wrapped__ is step
    finally:
        donatemon.reset_donation_witness()


def test_donatemon_witness_marks_and_touches():
    import numpy as np
    from deeplearning4j_tpu.observe.donatemon import DonationWitness
    w = DonationWitness()
    state = {"w": np.zeros((2, 2), np.float32),
             "b": np.zeros((2,), np.float32)}
    assert w.mark_donated(state, "state", "train_step") == 2
    # scalar leaves are not buffers
    assert w.mark_donated({"k": 3}, "k", "train_step") == 0
    events = w.touch(state, "state")
    assert len(events) == 2
    assert all(ev["rule"] == "GL801" for ev in events)
    assert events[0]["buffer"] == "state"
    # dedup: touching again reports nothing new
    assert w.touch(state, "state") == []
    rep = w.report()
    assert rep["donations"] == 2 and len(rep["events"]) == 2
    assert rep["static_rules"]["use_after_donate"] == runtime_hint(
        "use_after_donate")


def test_donatemon_fresh_buffers_stay_quiet():
    import numpy as np
    from deeplearning4j_tpu.observe.donatemon import (
        DonationWitness, instrument,
    )
    w = DonationWitness()

    def step(state, batch):
        return {k: v + batch for k, v in state.items()}

    inst = instrument(step, (0,), arg_names=("state", "batch"), witness=w)
    state = {"w": np.zeros((2,), np.float32)}
    for _ in range(5):
        state = inst(state, np.float32(1.0))   # rebind: always fresh
    assert w.report()["events"] == []


def test_donatemon_raise_mode():
    import numpy as np
    from deeplearning4j_tpu.observe.donatemon import (
        DonationWitness, UseAfterDonateError, instrument,
    )
    w = DonationWitness(raise_on_use=True)

    def step(state, batch):
        return dict(state)

    inst = instrument(step, (0,), arg_names=("state", "batch"), witness=w)
    state = {"w": np.zeros((2,), np.float32)}
    inst(state, None)
    with pytest.raises(UseAfterDonateError) as ei:
        inst(state, None)
    assert ei.value.event["rule"] == "GL801"
    assert ei.value.event["buffer"] == "state"


def test_donatemon_matches_static_gl801():
    """The cross-check the smoke tool automates: same rule id, same
    buffer identity, statically and at runtime."""
    import numpy as np
    from deeplearning4j_tpu.observe.donatemon import (
        DonationWitness, instrument,
    )
    static = [f for f in lint_source(_HELPER_UAD_SRC, "pkg/train.py")
              if f.rule == "GL801"]
    assert len(static) == 1
    assert "`state`" in static[0].message

    w = DonationWitness()

    def step(state, batch):
        return {k: v + batch for k, v in state.items()}

    inst = instrument(step, (0,), name="make_step.step",
                      arg_names=("state", "batch"), witness=w)
    state = {"w": np.zeros((3,), np.float32)}
    inst(state, np.float32(1.0))
    inst(state, np.float32(1.0))     # the seeded stale reuse
    events = w.report()["events"]
    assert events and events[0]["rule"] == static[0].rule == "GL801"
    assert events[0]["buffer"] == "state"


def test_donatemon_smoke_script():
    proc = subprocess.run(
        [sys.executable, os.path.join("tools", "donatemon_smoke.py")],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, \
        f"donatemon_smoke failed:\n{proc.stdout}\n{proc.stderr}"
    assert "OK" in proc.stdout
