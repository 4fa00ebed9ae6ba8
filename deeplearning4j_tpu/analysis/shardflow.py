"""Interprocedural sharding & donation dataflow — the GL8xx family.

The lockset pass (analysis/locks.py) made concurrency bugs
machine-checkable; this pass does the same for the three dataflow
properties that kill sharded jax programs, over the same whole-program
call graph (analysis/callgraph.py):

  donated   — a value passed at a `donate_argnums` position of a jitted
              call is DEAD afterwards: XLA may alias its buffer into
              the output. Donating callables are discovered from
              `@partial(jax.jit, donate_argnums=...)` decorators,
              `name = jax.jit(f, donate_argnums=...)` bindings (local,
              module-global, and `self.attr = ...` class attributes),
              immediately-invoked jit calls, and functions that RETURN
              a donating callable (`self._step = self._build_step()`),
              and donation flows through resolved helper calls: a
              helper that forwards its parameter into a donated slot
              kills its caller's argument too.
  placement — which `with_sharding_constraint`/`device_put` site a
              value's spec came from. Two values with *textually
              different* specs combined in one binop/concat/stack mean
              GSPMD inserts an implicit resharding collective at the
              combine point.
  device    — the engine's host-side device taint (`_devicey`),
              followed to serialization sinks. `np.asarray()` /
              `jax.device_get()` launder the taint, exactly as the
              sync rules model it; the taint also flows through
              resolved helpers whose parameter reaches a sink.

Rules (CAT_SHARDING):

  GL801 use-after-donate [error]        — read/pass of a donated value
        after the donating call, incl. through resolved helpers.
        Related location: the donating call site.
  GL802 cross-spec-combine [warn]       — operands with differing
        placement provenance combined. Related: both placement sites.
  GL803 jit-pytree-churn [warn]         — one jitted callee invoked
        with differing literal pytree structure across call sites
        (same dict keys in a different order, or list-vs-tuple of the
        same length — same leaves, different treedef, silent
        recompile). Related: the other call site.
  GL804 device-value-serialized [error] — device taint reaching
        json.dumps/pickle/struct.pack/b64encode/.tobytes() without
        laundering. Related (helper case): the sink inside the helper.
  GL805 collective-axis-literal [warn]  — psum/all_gather/ppermute/...
        axis given as a string literal outside parallel/mesh.py.

Soundness posture mirrors locks.py: facts only come from code the call
graph actually resolves, so an unresolved dynamic call never invents a
donation — GL801/GL804 fire only on provable flows. Loop bodies are
walked twice so a loop-carried use-after-donate (`for b: loss =
step(params, b)` with donated `params`) is caught; `if`/`else` arms
fork the dead-set and merge may-dead, so mutually-exclusive branches
don't poison each other. The same-statement reassignment idiom
(`self.params, self.opt_state, loss = self._step(self.params, ...)`)
is clean by construction: the call's arguments are read (and the
donation recorded) before the assignment targets re-bind the names.

Suppression uses the engine grammar (`# graft: allow(GL80x): reason`);
runtime cross-check is observe/donatemon.py (`DL4J_TPU_DONATEMON=1`),
whose events carry the same GL801 rule id and buffer names, so static
and runtime findings are string-comparable (tools/donatemon_smoke.py
asserts it).
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

from deeplearning4j_tpu.analysis.callgraph import (
    MAX_PROPAGATION_ROUNDS, CallGraph, FunctionInfo, ModuleInfo, Program,
)
from deeplearning4j_tpu.analysis.engine import (
    DEFAULT_HOT_PREFIXES, Finding, _collect_suppressions, _Ctx,
    _FileLinter, _Imports, _terminal, suppression_covers,
)

#: Terminals that retag placement: x = with_sharding_constraint(v, SPEC)
_PLACEMENT_FUNCS = frozenset({"with_sharding_constraint", "device_put"})

#: Combining callables (beyond BinOp) that materialize both operands
#: under ONE spec — a cross-spec call forces a reshard of the odd one.
_COMBINE_FUNCS = frozenset({
    "concatenate", "stack", "hstack", "vstack", "einsum", "matmul",
    "dot", "tensordot", "where", "add", "multiply",
})

#: Collectives whose axis argument is a mesh-axis name (GL805), mapped
#: to the positional index the axis occupies.
_COLLECTIVE_AXIS_POS: Dict[str, int] = {
    "psum": 1, "pmean": 1, "pmax": 1, "pmin": 1, "all_gather": 1,
    "ppermute": 1, "all_to_all": 1, "psum_scatter": 1, "pshuffle": 1,
    "pswapaxes": 1, "axis_index": 0,
}

#: Serialization sinks: module-rooted call terminals, by root name.
_SINK_FUNCS: Dict[str, Tuple[str, ...]] = {
    "json": ("dumps", "dump"),
    "pickle": ("dumps", "dump"),
    "struct": ("pack", "pack_into"),
    "base64": ("b64encode", "b85encode", "standard_b64encode",
               "urlsafe_b64encode"),
}
_SINK_BARE = frozenset({"b64encode", "b85encode"})

#: `donatemon.instrument(jit(...), ...)` wraps a donating callable
#: without changing its donation contract — treat it as transparent.
_TRANSPARENT_WRAPPERS = frozenset({"instrument"})

#: `optim/step.jit_step(fn, ...)` is the trainers' one donating jit:
#: (params, opt_state, states) of what it returns are donated;
#: `build_step(...)` is `jit_step` behind the step's construction.
_DONATING_JITS: Dict[str, Tuple[int, ...]] = {"jit_step": (0, 1, 2),
                                              "build_step": (0, 1, 2)}


def _donated_positions(call: ast.Call) -> Tuple[int, ...]:
    """donate_argnums=(0, 1) positions of a jit(...) call node."""
    for kw in call.keywords:
        if kw.arg != "donate_argnums":
            continue
        v = kw.value
        nodes = v.elts if isinstance(v, (ast.Tuple, ast.List)) else [v]
        out = [n.value for n in nodes
               if isinstance(n, ast.Constant) and isinstance(n.value, int)]
        return tuple(sorted(set(out)))
    return ()


def _pytree_sig(node: ast.AST):
    """Literal container structure of a call argument, or None when the
    treedef is not statically visible. ('dict', keys-in-order) keeps the
    ORDER — jax treedefs are insertion-order-sensitive for dicts only up
    to sorting, but a reordered literal is the reviewable smell."""
    if isinstance(node, ast.Dict):
        keys = [k.value for k in node.keys
                if isinstance(k, ast.Constant) and isinstance(k.value, str)]
        if len(keys) == len(node.keys) and keys:
            return ("dict", tuple(keys))
        return None
    if isinstance(node, ast.List):
        return ("list", len(node.elts))
    if isinstance(node, ast.Tuple):
        return ("tuple", len(node.elts))
    return None


def _sigs_conflict(a, b) -> Optional[str]:
    """The churn description when two literal sigs imply the same
    leaves under different treedefs, else None."""
    if a == b or a is None or b is None:
        return None
    if a[0] == "dict" and b[0] == "dict" and set(a[1]) == set(b[1]):
        return ("same dict keys in a different order "
                f"({', '.join(a[1])} vs {', '.join(b[1])})")
    if {a[0], b[0]} == {"list", "tuple"} and a[1] == b[1]:
        return f"list-vs-tuple of the same length ({a[1]})"
    return None


@dataclass
class _Donation:
    """Why an identity is dead: the donating call."""
    site: Tuple[str, int]          # (path, line) of the donating call
    callee: str                    # rendered callee, e.g. "self._step"
    pos: int                       # donated argument position


@dataclass
class _Placement:
    spec: str                      # normalized spec text
    site: Tuple[str, int]          # (path, line)
    via: str                       # "with_sharding_constraint"/"device_put"


@dataclass
class _ModCtx:
    """Per-module helpers shared by both walker passes."""
    mod: ModuleInfo
    fl: _FileLinter                # engine adapter: imports + _devicey
    traced_names: Set[str] = field(default_factory=set)


@dataclass
class _CallSig:
    """A GL803 observation: one call site's literal arg structures."""
    key: str                       # callee identity
    sigs: Tuple                    # per-arg _pytree_sig results
    mod: ModuleInfo
    node: ast.Call


class _ShardAnalysis:
    def __init__(self, prog: Program, *,
                 hot_prefixes: Sequence[str] = DEFAULT_HOT_PREFIXES):
        self.prog = prog
        self.graph = CallGraph(prog)
        self.hot_prefixes = hot_prefixes
        self.findings: List[Finding] = []
        self._allow: Dict[str, Dict[int, Set[str]]] = {}
        self._emitted: Set[Tuple] = set()
        # donation facts --------------------------------------------------
        #: callee key -> {donated position: (path, line)}. Keys are
        #: function qualnames, "Cls.qualname.attr" for self-attr
        #: bindings, and "mod.name.var" for module-global bindings.
        self.donates: Dict[str, Dict[int, Tuple[str, int]]] = {}
        #: qualname -> donated positions of the callable it RETURNS
        self.returns_donating: Dict[str, Tuple[int, ...]] = {}
        #: jitted callee keys (donating or not) for GL803
        self.jitted: Set[str] = set()
        #: qualname -> {param index: (sink description, (path, line))}
        self.ser_flow: Dict[str, Dict[int, Tuple[str, Tuple[str, int]]]] = {}
        # pre-scan products ----------------------------------------------
        self._mods: Dict[str, _ModCtx] = {}
        self._sigs: List[_CallSig] = []

    # ------------------------------------------------------------ entry
    def run(self) -> List[Finding]:
        for mod in self.prog.modules.values():
            self._mods[mod.name] = self._mod_ctx(mod)
        self._collect_direct_facts()
        self._fixpoint_summaries()
        for fn in self.prog.functions.values():
            _FnFlow(self, fn).run()
        self._gl803()
        self.findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
        return self.findings

    def _mod_ctx(self, mod: ModuleInfo) -> _ModCtx:
        fl = _FileLinter(mod.path, mod.source, hot=True)
        fl.imports = _Imports(mod.tree)
        fl.module_defs = {}
        mc = _ModCtx(mod, fl)
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call):
                slots = fl.imports.wrapper_slots(node.func)
                if slots is None:
                    continue
                for i in slots:
                    if i < len(node.args) and isinstance(node.args[i],
                                                         ast.Name):
                        mc.traced_names.add(node.args[i].id)
        return mc

    # ------------------------------------------------------------- emit
    def _emit(self, rule: str, mod: ModuleInfo, node: ast.AST,
              message: str,
              related: Sequence[Tuple[str, int, str]] = (),
              dedup: Optional[Tuple] = None) -> None:
        line = getattr(node, "lineno", 1)
        if dedup is None:
            dedup = (rule, mod.path, line, message)
        if dedup in self._emitted:
            return
        self._emitted.add(dedup)
        end = getattr(node, "end_lineno", line) or line
        allow = self._allow.setdefault(
            mod.path, _collect_suppressions(mod.lines))
        if suppression_covers(mod.lines, allow, rule, line, end):
            return
        snippet = (mod.lines[line - 1].strip()
                   if 0 < line <= len(mod.lines) else "")
        self.findings.append(Finding(
            rule, mod.path, line, getattr(node, "col_offset", 0),
            message, snippet, related=tuple(related)))

    # ------------------------------------------------ direct fact scan
    def _collect_direct_facts(self) -> None:
        """Decorator donations, jit bindings (module/attr), and jitted
        callee keys — everything visible without a fixpoint."""
        for fn in self.prog.functions.values():
            mc = self._mods[fn.module.name]
            dec_call = self._jit_decorator_call(fn, mc)
            if dec_call is not None:
                self.jitted.add(fn.qualname)
                pos = _donated_positions(dec_call) \
                    if isinstance(dec_call, ast.Call) else ()
                if pos:
                    self.donates[fn.qualname] = {
                        p: (fn.module.path, fn.node.lineno) for p in pos}
            for node in ast.walk(fn.node):
                if isinstance(node, ast.Assign):
                    self._scan_binding_assign(fn, mc, node)
        for mod in self.prog.modules.values():
            mc = self._mods[mod.name]
            for stmt in mod.tree.body:
                if isinstance(stmt, ast.Assign):
                    self._scan_module_binding(mod, mc, stmt)

    def _jit_decorator_call(self, fn: FunctionInfo,
                            mc: _ModCtx) -> Optional[ast.AST]:
        """The jit-family decorator node of `fn`, preferring the call
        form (which carries donate_argnums), else None."""
        fl = mc.fl
        for dec in fn.node.decorator_list:
            jf = fl._jitish_decorator(dec)
            if jf is None or _terminal(jf) not in ("jit", "pjit", "pmap"):
                continue
            if isinstance(dec, ast.Call):
                return dec            # @partial(jax.jit, ...) / @jit(...)
            return jf
        return None

    def _donating_value(self, mc: _ModCtx,
                        value: ast.AST) -> Optional[Tuple[Tuple[int, ...],
                                                          bool]]:
        """(donated positions, is_jitted) when `value` is a jit-family
        call (possibly wrapped in donatemon.instrument), else None."""
        if (isinstance(value, ast.Call)
                and _terminal(value.func) in _TRANSPARENT_WRAPPERS
                and value.args):
            return self._donating_value(mc, value.args[0])
        if isinstance(value, ast.Call) \
                and mc.fl.imports.is_jit_family(value.func):
            return _donated_positions(value), True
        if isinstance(value, ast.Call) \
                and _terminal(value.func) in _DONATING_JITS:
            return _DONATING_JITS[_terminal(value.func)], True
        return None

    def _scan_binding_assign(self, fn: FunctionInfo, mc: _ModCtx,
                             node: ast.Assign) -> None:
        """`self.attr = jax.jit(f, donate_argnums=...)` anywhere in a
        method body types the class attribute as a donating callable
        (the lazily-built-step idiom); the indirect form
        `self.attr = self._build_step()` is resolved by the fixpoint."""
        got = self._donating_value(mc, node.value)
        if got is None:
            return
        pos, _ = got
        site = (fn.module.path, node.lineno)
        for t in node.targets:
            if (fn.cls is not None and isinstance(t, ast.Attribute)
                    and isinstance(t.value, ast.Name)
                    and t.value.id == fn.self_name):
                key = f"{fn.cls.qualname}.{t.attr}"
                self.jitted.add(key)
                if pos:
                    self.donates[key] = {p: site for p in pos}

    def _scan_module_binding(self, mod: ModuleInfo, mc: _ModCtx,
                             stmt: ast.Assign) -> None:
        got = self._donating_value(mc, stmt.value)
        if got is None:
            return
        pos, _ = got
        for t in stmt.targets:
            if isinstance(t, ast.Name):
                key = f"{mod.name}.{t.id}"
                self.jitted.add(key)
                if pos:
                    self.donates[key] = {
                        p: (mod.path, stmt.lineno) for p in pos}

    # ------------------------------------------------------- fixpoints
    def _fixpoint_summaries(self) -> None:
        """Three bounded fixpoints over the call graph:
        1. returns_donating — `return jax.jit(f, donate_argnums=...)`
           (or a local bound to one, or a call to a fn that returns
           one) makes the *caller's binding* a donating callable;
        2. donates — a fn that forwards param i into a donated slot of
           a resolved donating call donates position i itself;
        3. ser_flow — a fn whose param i reaches a serialization sink
           unlaundered taints its callers' argument i."""
        summaries = {fn.qualname: _FnSummary(self, fn).collect()
                     for fn in self.prog.functions.values()}
        for _ in range(MAX_PROPAGATION_ROUNDS):
            changed = False
            for q, s in summaries.items():
                changed |= self._apply_summary(q, s)
            if not changed:
                break

    def _apply_summary(self, q: str, s: "_FnSummaryData") -> bool:
        changed = False
        # 1. returns_donating / attr-from-returner bindings. Only a
        # *returner* chain propagates (`return self._build_step()`) —
        # calling a donating callable returns arrays, not a callable.
        for ret_keys in s.return_calls:
            for key, _offset in ret_keys:
                pos = self.returns_donating.get(key)
                if pos and self.returns_donating.get(q) != pos:
                    self.returns_donating[q] = pos
                    changed = True
        for (bind_key, callee_keys, site) in s.bindings_from_calls:
            for key, offset in callee_keys:
                pos = self.returns_donating.get(key)
                if pos:
                    cur = self.donates.setdefault(bind_key, {})
                    self.jitted.add(bind_key)
                    for p in pos:
                        if p not in cur:
                            cur[p] = site
                            changed = True
        # 2. donation through helpers; 3. serialization through helpers
        for (callee_keys, arg_params, node_site) in s.calls:
            for key, offset in callee_keys:
                dpos = self.donates.get(key, {})
                for p, dsite in dpos.items():
                    ai = p - offset
                    param = arg_params.get(ai)
                    if param is None:
                        continue
                    cur = self.donates.setdefault(q, {})
                    if param not in cur:
                        cur[param] = node_site
                        changed = True
                spos = self.ser_flow.get(key, {})
                for p, (what, ssite) in spos.items():
                    ai = p - offset
                    param = arg_params.get(ai)
                    if param is None:
                        continue
                    cur2 = self.ser_flow.setdefault(q, {})
                    if param not in cur2:
                        cur2[param] = (what, ssite)
                        changed = True
        for (pidx, what, site) in s.direct_sinks:
            cur2 = self.ser_flow.setdefault(q, {})
            if pidx not in cur2:
                cur2[pidx] = (what, site)
                changed = True
        return changed

    # ------------------------------------------------- call resolution
    def callee_keys(self, fn: FunctionInfo,
                    call: ast.Call) -> List[Tuple[str, int]]:
        """(key, arg-offset) pairs a call site may dispatch to. Offset
        is 1 for bound-method calls resolved to a def whose first param
        is self (donate_argnums counts params, calls pass args)."""
        out: List[Tuple[str, int]] = []
        func = call.func
        # self.attr(...) — a jit-binding class attribute
        if (fn.cls is not None and isinstance(func, ast.Attribute)
                and isinstance(func.value, ast.Name)
                and func.value.id == fn.self_name):
            out.append((f"{fn.cls.qualname}.{func.attr}", 0))
        # module-global binding / local binding keys are added by the
        # walker (it owns the local scope); resolved defs:
        for cand in self.graph.resolve(fn, call):
            offset = 0
            if cand.cls is not None and isinstance(func, ast.Attribute):
                offset = 1        # self.m(a): a is param 1
            out.append((cand.qualname, offset))
        if isinstance(func, ast.Name):
            out.append((f"{fn.module.name}.{func.id}", 0))
        return out

    # ------------------------------------------------------------ GL803
    def note_call_sig(self, key: str, mod: ModuleInfo,
                      node: ast.Call) -> None:
        sigs = tuple(_pytree_sig(a) for a in node.args)
        if any(s is not None for s in sigs):
            self._sigs.append(_CallSig(key, sigs, mod, node))

    def _gl803(self) -> None:
        by_key: Dict[str, List[_CallSig]] = {}
        for cs in self._sigs:
            if cs.key in self.jitted:
                by_key.setdefault(cs.key, []).append(cs)
        for key, sites in by_key.items():
            sites.sort(key=lambda c: (c.mod.path, c.node.lineno))
            for i, a in enumerate(sites):
                for b in sites[i + 1:]:
                    n = min(len(a.sigs), len(b.sigs))
                    for ai in range(n):
                        why = _sigs_conflict(a.sigs[ai], b.sigs[ai])
                        if why is None:
                            continue
                        short = key.split(".")[-1]
                        self._emit(
                            "GL803", b.mod, b.node,
                            f"jitted callee `{short}` is called with a "
                            f"different pytree structure for argument "
                            f"{ai} than at {a.mod.path}:"
                            f"{a.node.lineno} — {why}; same leaves, "
                            f"different treedef, so the jit cache "
                            f"recompiles silently",
                            related=[(a.mod.path, a.node.lineno,
                                      "first structure used here")],
                            dedup=("GL803", key, ai))
                        break


@dataclass
class _FnSummaryData:
    #: resolved (key, offset) lists of calls in `return <call>` position
    return_calls: List[List[Tuple[str, int]]] = field(default_factory=list)
    #: (binding key, callee keys, site) for `self.attr = self._build()`
    bindings_from_calls: List[Tuple[str, List[Tuple[str, int]],
                                    Tuple[str, int]]] = \
        field(default_factory=list)
    #: (callee keys, {arg idx: caller param idx}, (path, line))
    calls: List[Tuple[List[Tuple[str, int]], Dict[int, int],
                      Tuple[str, int]]] = field(default_factory=list)
    #: (param idx, sink description, (path, line)) — direct sinks
    direct_sinks: List[Tuple[int, str, Tuple[str, int]]] = \
        field(default_factory=list)


class _FnSummary:
    """Unordered single sweep over one function body collecting the
    facts the fixpoint needs (no emission, no dead-tracking)."""

    def __init__(self, an: _ShardAnalysis, fn: FunctionInfo):
        self.an = an
        self.fn = fn
        self.mc = an._mods[fn.module.name]
        params = [a.arg for a in
                  getattr(fn.node.args, "posonlyargs", [])
                  + fn.node.args.args]
        self.param_idx = {p: i for i, p in enumerate(params)}
        self.data = _FnSummaryData()

    def collect(self) -> _FnSummaryData:
        fn, d = self.fn, self.data
        path = fn.module.path
        # pass 1: local names bound to donating callables (needed so a
        # bare `return fn` after `fn = jax.jit(...)` summarizes)
        local_don: Dict[str, Tuple[int, ...]] = {}
        for node in ast.walk(fn.node):
            if isinstance(node, ast.Assign):
                got = self.an._donating_value(self.mc, node.value)
                if got is not None and got[0]:
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            local_don[t.id] = got[0]
        for node in ast.walk(fn.node):
            if isinstance(node, ast.Return) and node.value is not None:
                if isinstance(node.value, ast.Call):
                    got = self.an._donating_value(self.mc, node.value)
                    if got is not None and got[0]:
                        self.an.returns_donating.setdefault(
                            fn.qualname, got[0])
                    else:
                        d.return_calls.append(
                            self.an.callee_keys(fn, node.value))
                elif isinstance(node.value, ast.Name) \
                        and node.value.id in local_don:
                    self.an.returns_donating.setdefault(
                        fn.qualname, local_don[node.value.id])
            elif isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Call):
                keys = self.an.callee_keys(fn, node.value)
                site = (path, node.lineno)
                for t in node.targets:
                    if (fn.cls is not None
                            and isinstance(t, ast.Attribute)
                            and isinstance(t.value, ast.Name)
                            and t.value.id == fn.self_name):
                        d.bindings_from_calls.append(
                            (f"{fn.cls.qualname}.{t.attr}", keys, site))
            if isinstance(node, ast.Call):
                self._scan_call(node)
        return d

    def _scan_call(self, node: ast.Call) -> None:
        fn, d = self.fn, self.data
        path = fn.module.path
        keys = self.an.callee_keys(fn, node)
        if keys:
            arg_params = {
                i: self.param_idx[a.id]
                for i, a in enumerate(node.args)
                if isinstance(a, ast.Name) and a.id in self.param_idx}
            # self.attr params: `self.params` forwarded — identity is
            # not a param index, so only bare names summarize (sound:
            # missing a flow only loses a finding, never invents one)
            if arg_params:
                d.calls.append((keys, arg_params, (path, node.lineno)))
        sink = _sink_of(node)
        if sink is None:
            return
        what, payload = sink
        for a in payload:
            if isinstance(a, ast.Name) and a.id in self.param_idx:
                d.direct_sinks.append(
                    (self.param_idx[a.id], what, (path, node.lineno)))
            elif (isinstance(a, ast.Attribute)
                  and isinstance(a.value, ast.Name)
                  and a.value.id in self.param_idx
                  and a.attr not in ("shape", "ndim", "dtype", "size")):
                d.direct_sinks.append(
                    (self.param_idx[a.value.id], what,
                     (path, node.lineno)))


def _sink_of(node: ast.Call) -> Optional[Tuple[str, List[ast.AST]]]:
    """(sink description, payload expressions) for serialization sinks,
    else None. `.tobytes()` reports its receiver as the payload."""
    func = node.func
    term = _terminal(func)
    if term == "tobytes" and isinstance(func, ast.Attribute) \
            and not node.args:
        return (".tobytes()", [func.value])
    if isinstance(func, ast.Attribute) and isinstance(func.value, ast.Name):
        root = func.value.id
        if term in _SINK_FUNCS.get(root, ()):
            return (f"{root}.{term}()", list(node.args))
    if isinstance(func, ast.Name) and term in _SINK_BARE:
        return (f"{term}()", list(node.args))
    return None


class _FnFlow:
    """Ordered statement walk of one function body: tracks dead
    (donated) identities, placement tags, and device taint; emits
    GL801/GL802/GL804/GL805 and records GL803 call signatures.

    Identities are bare names ("x") and one-level self attributes
    ("self.params"). Branch arms fork the dead-set and merge may-dead;
    loop bodies run twice to expose loop-carried donation."""

    def __init__(self, an: _ShardAnalysis, fn: FunctionInfo):
        self.an = an
        self.fn = fn
        self.mc = an._mods[fn.module.name]
        self.fl = self.mc.fl
        self.dead: Dict[str, _Donation] = {}
        self.placed: Dict[str, _Placement] = {}
        #: local names bound to donating/jitted callables
        self.local_don: Dict[str, Dict[int, Tuple[str, int]]] = {}
        self.local_jit: Set[str] = set()
        self.ctx = _Ctx()          # .dev drives the engine's _devicey
        self.traced = self._is_traced()

    def _is_traced(self) -> bool:
        if self.fn.name in self.mc.traced_names:
            return True
        return self.an._jit_decorator_call(self.fn, self.mc) is not None

    # ---------------------------------------------------------- helpers
    def _ident(self, node: ast.AST) -> Optional[str]:
        if isinstance(node, ast.Name):
            return node.id
        if (isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id == self.fn.self_name):
            return f"{node.value.id}.{node.attr}"
        return None

    def _devicey(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Dict):    # engine stops at dict literals
            return any(self._devicey(v) for v in node.values
                       if v is not None) \
                or any(self._devicey(k) for k in node.keys
                       if k is not None)
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return any(self._devicey(e) for e in node.elts)
        return self.fl._devicey(node, self.ctx)

    def run(self) -> None:
        for stmt in self.fn.node.body:
            self._stmt(stmt)

    # ------------------------------------------------------- statements
    def _stmt(self, node: ast.AST) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return      # nested defs run later; fresh scope, no flow
        if isinstance(node, ast.Assign):
            self._assign(node.targets, node.value, node)
        elif isinstance(node, ast.AugAssign):
            self._expr(node.value)
            self._expr(node.target)
            ident = self._ident(node.target)
            if ident is not None:
                self.dead.pop(ident, None)
        elif isinstance(node, ast.AnnAssign):
            if node.value is not None:
                self._assign([node.target], node.value, node)
        elif isinstance(node, ast.If):
            self._expr(node.test)
            before = dict(self.dead)
            self._body(node.body)
            after_body = self.dead
            self.dead = dict(before)
            self._body(node.orelse)
            self.dead.update(after_body)       # may-dead merge
        elif isinstance(node, (ast.For, ast.AsyncFor, ast.While)):
            if isinstance(node, ast.While):
                self._expr(node.test)
            else:
                self._expr(node.iter)
                t_ident = self._ident(node.target)
                if t_ident is not None:
                    self.dead.pop(t_ident, None)
            for _round in (0, 1):              # expose loop-carried UAD
                self._body(node.body)
            self._body(node.orelse)
        elif isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                self._expr(item.context_expr)
            self._body(node.body)
        elif isinstance(node, ast.Try):
            self._body(node.body)
            for h in node.handlers:
                if h.type is not None:
                    self._expr(h.type)
                self._body(h.body)
            self._body(node.orelse)
            self._body(node.finalbody)
        elif isinstance(node, (ast.Return, ast.Expr)):
            if node.value is not None:
                self._expr(node.value)
        elif isinstance(node, ast.Raise):
            if node.exc is not None:
                self._expr(node.exc)
        elif isinstance(node, ast.Delete):
            for t in node.targets:
                ident = self._ident(t)
                if ident is not None:
                    self.dead.pop(ident, None)
        else:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.stmt):
                    self._stmt(child)
                elif isinstance(child, ast.expr):
                    self._expr(child)

    def _body(self, stmts: List[ast.stmt]) -> None:
        for s in stmts:
            self._stmt(s)

    def _assign(self, targets: List[ast.AST], value: ast.AST,
                stmt: ast.AST) -> None:
        self._expr(value)                     # reads + donation marking
        # local jit/donating binding?
        got = self.an._donating_value(self.mc, value)
        bound_don: Optional[Dict[int, Tuple[str, int]]] = None
        bound_jit = got is not None
        if got is not None and got[0]:
            bound_don = {p: (self.fn.module.path, stmt.lineno)
                         for p in got[0]}
        if bound_don is None and isinstance(value, ast.Call):
            # `fn = self._build_step()` — returner fixpoint result
            for key, _off in self.an.callee_keys(self.fn, value):
                pos = self.an.returns_donating.get(key)
                if pos:
                    bound_don = {p: (self.fn.module.path, stmt.lineno)
                                 for p in pos}
                    bound_jit = True
                    break
        placement = self._placement_of(value)
        devicey = not self.traced and self._devicey(value)
        if not devicey and not self.traced and isinstance(value, ast.Call):
            # the engine's name-based taint misses jit results bound
            # under neutral names — but THIS pass knows which callees
            # are jitted, so `y = fwd(x)` taints when fwd is jit-bound
            vf = value.func
            if isinstance(vf, ast.Name) and vf.id in self.local_jit:
                devicey = True
            elif self.an._donating_value(self.mc, vf) is not None:
                devicey = True        # jax.jit(...)(...) called inline
            elif any(key in self.an.jitted
                     for key, _ in self.an.callee_keys(self.fn, value)):
                devicey = True
        stack = list(targets)
        while stack:
            t = stack.pop()
            if isinstance(t, (ast.Tuple, ast.List)):
                stack.extend(t.elts)
                continue
            if isinstance(t, ast.Starred):
                stack.append(t.value)
                continue
            ident = self._ident(t)
            if ident is None:
                continue
            self.dead.pop(ident, None)        # reassignment revives
            if isinstance(t, ast.Name):
                if bound_don is not None:
                    self.local_don[t.id] = bound_don
                if bound_jit:
                    self.local_jit.add(t.id)
                    self.an.jitted.add(
                        f"{self.fn.qualname}.{t.id}")
                (self.ctx.dev.add if devicey
                 else self.ctx.dev.discard)(t.id)
            if placement is not None:
                self.placed[ident] = placement
            elif self._ident(value) in self.placed:
                self.placed[ident] = self.placed[self._ident(value)]
            else:
                self.placed.pop(ident, None)

    # ------------------------------------------------------ expressions
    def _expr(self, node: ast.AST) -> None:
        if isinstance(node, ast.Call):
            self._call(node)
            return
        if isinstance(node, (ast.Lambda, ast.FunctionDef)):
            return
        ident = self._ident(node)
        if ident is not None:
            self._check_dead(node, ident)
            if isinstance(node, ast.Attribute):
                return                         # don't re-check the base
        if isinstance(node, ast.BinOp):
            self._check_combine(node, [node.left, node.right], "binop")
        if isinstance(node, (ast.GeneratorExp, ast.ListComp, ast.SetComp,
                             ast.DictComp)):
            # comprehension generators are ast.comprehension, not
            # ast.expr — walk their iter/ifs explicitly or reads like
            # `for a in state.values()` are invisible to the dead check
            for comp in node.generators:
                self._expr(comp.iter)
                for cond in comp.ifs:
                    self._expr(cond)
            if isinstance(node, ast.DictComp):
                self._expr(node.key)
                self._expr(node.value)
            else:
                self._expr(node.elt)
            return
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.expr):
                self._expr(child)

    def _check_dead(self, node: ast.AST, ident: str) -> None:
        don = self.dead.get(ident)
        if don is None:
            return
        self.an._emit(
            "GL801", self.fn.module, node,
            f"`{ident}` is read after being donated to "
            f"`{don.callee}` (donate_argnums position {don.pos}) — the "
            f"buffer is dead by contract; rebind the result in the "
            f"same statement (`x, ... = {don.callee}(x, ...)`) or drop "
            f"the donation",
            related=[(don.site[0], don.site[1],
                      f"donated here, argument {don.pos} of "
                      f"`{don.callee}`")],
            dedup=("GL801", self.fn.qualname, id(node), ident))
        # one report per (site, identity); keep walking without cascades
        self.dead.pop(ident, None)

    def _placement_of(self, node: ast.AST) -> Optional[_Placement]:
        """Tag for `with_sharding_constraint(x, SPEC)`/`device_put(x,
        SPEC)` values; propagates through a directly-placed name."""
        if isinstance(node, ast.Call):
            term = _terminal(node.func)
            if term in _PLACEMENT_FUNCS and len(node.args) >= 2:
                try:
                    spec = ast.unparse(node.args[1])
                except Exception:       # pragma: no cover - unparse total
                    spec = "<spec>"
                spec = "".join(spec.split())
                return _Placement(spec,
                                  (self.fn.module.path, node.lineno),
                                  term or "")
            return None
        ident = self._ident(node)
        if ident is not None:
            return self.placed.get(ident)
        return None

    def _check_combine(self, node: ast.AST, operands: List[ast.AST],
                       how: str) -> None:
        tags: List[Tuple[ast.AST, _Placement]] = []
        for op in operands:
            p = self._placement_of(op)
            if p is not None:
                tags.append((op, p))
        for i in range(len(tags)):
            for j in range(i + 1, len(tags)):
                a, b = tags[i][1], tags[j][1]
                if a.spec != b.spec:
                    self.an._emit(
                        "GL802", self.fn.module, node,
                        f"{how} combines values under different "
                        f"placement specs ({a.spec} via {a.via} vs "
                        f"{b.spec} via {b.via}) — GSPMD inserts an "
                        f"implicit resharding collective here; "
                        f"constrain both operands to one spec first",
                        related=[(a.site[0], a.site[1],
                                  f"placed as {a.spec} here"),
                                 (b.site[0], b.site[1],
                                  f"placed as {b.spec} here")],
                        dedup=("GL802", self.fn.qualname, id(node)))
                    return

    # ------------------------------------------------------------ calls
    def _call(self, node: ast.Call) -> None:
        func = node.func
        term = _terminal(func)

        # visit callee receiver + args FIRST: the call reads its
        # arguments while they are still alive; donation kills after.
        if isinstance(func, ast.Attribute):
            self._expr(func.value)
        elif isinstance(func, (ast.Call, ast.Lambda)):
            self._expr(func)
        for a in node.args:
            self._expr(a)
        for k in node.keywords:
            self._expr(k.value)

        # GL805 — collective with a literal axis name
        self._check_collective(node, term)

        # GL802 — combining callables (concatenate/stack/...)
        if term in _COMBINE_FUNCS:
            ops: List[ast.AST] = []
            for a in node.args:
                if isinstance(a, (ast.Tuple, ast.List)):
                    ops.extend(a.elts)
                else:
                    ops.append(a)
            self._check_combine(node, ops, f"{term}()")

        # GL804 — direct serialization sink
        sink = _sink_of(node)
        if sink is not None:
            what, payload = sink
            for a in payload:
                if self._devicey(a):
                    self.an._emit(
                        "GL804", self.fn.module, node,
                        f"device-tainted value reaches {what} without "
                        f"an np.asarray()/jax.device_get() laundering "
                        f"point — the wire format captures a live "
                        f"device buffer; copy to host first",
                        dedup=("GL804", self.fn.qualname, id(node)))
                    break

        # donation + helper-mediated serialization at resolved calls
        keys = self.an.callee_keys(self.fn, node)
        if isinstance(func, ast.Name) and func.id in self.local_don:
            self._donate_args(node, self.local_don[func.id], 0,
                              func.id)
        if isinstance(func, ast.Name) and func.id in self.local_jit:
            self.an.note_call_sig(
                f"{self.fn.qualname}.{func.id}", self.fn.module, node)
        # immediately-invoked donating jit: jax.jit(f, donate...)(x)
        if isinstance(func, ast.Call):
            inner = self.an._donating_value(self.mc, func)
            if inner is not None and inner[0]:
                site = (self.fn.module.path, node.lineno)
                self._donate_args(
                    node, {p: site for p in inner[0]}, 0,
                    _terminal(func.args[0].func
                              if isinstance(func.args[0], ast.Call)
                              else func.args[0])
                    if func.args else "jit(...)")
        for key, offset in keys:
            dpos = self.an.donates.get(key)
            if dpos:
                callee_desc = self._render_callee(func, key)
                self._donate_args(node, dpos, offset, callee_desc)
            if key in self.an.jitted:
                self.an.note_call_sig(key, self.fn.module, node)
            spos = self.an.ser_flow.get(key)
            if spos:
                for p, (what, ssite) in spos.items():
                    ai = p - offset
                    if 0 <= ai < len(node.args) \
                            and self._devicey(node.args[ai]):
                        self.an._emit(
                            "GL804", self.fn.module, node,
                            f"device-tainted argument {ai} flows "
                            f"through `{self._render_callee(func, key)}"
                            f"` into {what} with no laundering point "
                            f"on the way — copy to host "
                            f"(np.asarray/jax.device_get) before the "
                            f"call",
                            related=[(ssite[0], ssite[1],
                                      f"serialized here via {what}")],
                            dedup=("GL804", self.fn.qualname, id(node),
                                   ai))

    def _render_callee(self, func: ast.AST, key: str) -> str:
        if isinstance(func, ast.Attribute) \
                and isinstance(func.value, ast.Name):
            return f"{func.value.id}.{func.attr}"
        if isinstance(func, ast.Name):
            return func.id
        return key.split(".")[-1]

    def _donate_args(self, node: ast.Call,
                     dpos: Dict[int, Tuple[str, int]], offset: int,
                     callee_desc: str) -> None:
        site = (self.fn.module.path, node.lineno)
        for p in dpos:
            ai = p - offset
            if not (0 <= ai < len(node.args)):
                continue
            ident = self._ident(node.args[ai])
            if ident is None:
                continue
            self.dead[ident] = _Donation(site, callee_desc, p)

    def _check_collective(self, node: ast.Call,
                          term: Optional[str]) -> None:
        if term not in _COLLECTIVE_AXIS_POS:
            return
        imports = self.fl.imports
        func = node.func
        rooted = imports.is_jax_call_root(func) or (
            isinstance(func, ast.Name) and func.id in imports.from_jax)
        if not rooted:
            return
        norm = self.fn.module.path.replace(os.sep, "/")
        if norm.endswith("parallel/mesh.py"):
            return
        cands: List[ast.AST] = []
        pos = _COLLECTIVE_AXIS_POS[term]
        if pos < len(node.args):
            cands.append(node.args[pos])
        for kw in node.keywords:
            if kw.arg in ("axis_name", "axis"):
                cands.append(kw.value)
        for c in cands:
            if isinstance(c, ast.Constant) and isinstance(c.value, str):
                self.an._emit(
                    "GL805", self.fn.module, node,
                    f"{term}() axis name {c.value!r} is a string "
                    f"literal outside parallel/mesh.py — read mesh "
                    f"axis names from the active MeshContext / "
                    f"parallel.mesh constants so a mesh reshape "
                    f"cannot silently detach this collective",
                    dedup=("GL805", self.fn.qualname, id(node)))
                return


# ------------------------------------------------------------ public API

def analyze_shardflow_program(
        prog: Program, *,
        hot_prefixes: Sequence[str] = DEFAULT_HOT_PREFIXES,
) -> List[Finding]:
    """Run the GL8xx sharding/donation pass over a prebuilt Program —
    the shared-callgraph entry point lint_paths uses so the lockset and
    shardflow passes parse the repo once."""
    return _ShardAnalysis(prog, hot_prefixes=hot_prefixes).run()


def analyze_shardflow_sources(
        sources: Sequence[Tuple[str, str]], *,
        hot_prefixes: Sequence[str] = DEFAULT_HOT_PREFIXES,
) -> List[Finding]:
    return analyze_shardflow_program(Program.from_sources(sources),
                                     hot_prefixes=hot_prefixes)


def analyze_shardflow_paths(
        files: Sequence[str], *,
        hot_prefixes: Sequence[str] = DEFAULT_HOT_PREFIXES,
) -> List[Finding]:
    return analyze_shardflow_program(Program.from_paths(files),
                                     hot_prefixes=hot_prefixes)
