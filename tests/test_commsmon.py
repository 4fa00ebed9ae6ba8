"""Collective-traffic observability (commsmon): the compiled-HLO comm
ledger and the runtime reshard witness.

Contract under test, on the 8-device virtual CPU mesh:

- the HLO parser classifies all five collective kinds, reads explicit
  and iota replica groups, counts async `-start` forms once, tolerates
  unknown ops, and prices wire bytes under the documented one-pass ring
  convention (`payload * (g-1)/g`; full payload for collective-permute;
  degenerate single-participant groups never count toward totals);
- `instrument()` with commsmon off returns the function UNCHANGED (the
  donatemon identity contract — zero wrapper on any hot path), and a
  forced witness records GL802-tagged events only for committed leaves
  whose spec actually diverges from the spine's declaration;
- a fused decode window on a single-replica model contains ZERO
  collectives — ROADMAP item 1's "no per-token collectives beyond what
  GSPMD inserts" line, now measurable;
- the pure-DP training step's gradient all-reduce reconciles with the
  textbook `4 * param_count * (n-1)/n` per-device ring bytes.
"""

import types

import numpy as np
import pytest
import jax
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.observe.commsmon import (
    ReshardWitness, canonical_spec, check_dispatch_args,
    commsmon_enabled, get_reshard_witness, instrument,
    parse_hlo_collectives, reset_reshard_witness, summarize_collectives,
    wire_bytes,
)
from deeplearning4j_tpu.observe.watchdog import (
    RecompileWatchdog, get_watchdog, set_watchdog,
)


# ------------------------------------------------- wire-byte convention
class TestWireBytesConvention:
    def test_ring_fraction(self):
        # 1024B payload over an 8-way ring: 7/8 of it crosses the wire
        assert wire_bytes("all-reduce", 1024, 8) == 896
        assert wire_bytes("all-gather", 1024, 4) == 768
        assert wire_bytes("reduce-scatter", 1024, 2) == 512

    def test_permute_is_full_payload(self):
        assert wire_bytes("collective-permute", 1024, 8) == 1024

    def test_degenerate_group_is_free(self):
        assert wire_bytes("all-reduce", 1024, 1) == 0

    def test_unknown_group_counts_full_payload(self):
        # conservative: no group info -> assume the bytes move
        assert wire_bytes("all-reduce", 1024, 0) == 1024


# ------------------------------------------------------------ HLO parser
_FIVE_KINDS = """\
HloModule five
ENTRY main {
  %p0 = f32[256]{0} parameter(0)
  %ar = f32[256]{0} all-reduce(f32[256]{0} %p0), \
replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add
  %ag = f32[1024]{0} all-gather(f32[256]{0} %p0), \
replica_groups={{0,1,2,3}}, dimensions={0}
  %rs = f32[64]{0} reduce-scatter(f32[256]{0} %p0), \
replica_groups={{0,1,2,3}}, dimensions={0}, to_apply=%add
  %cp = f32[256]{0} collective-permute(f32[256]{0} %p0), \
source_target_pairs={{0,1},{1,0}}
  ROOT %aa = f32[256]{0} all-to-all(f32[256]{0} %p0), \
replica_groups={{0,1}}, dimensions={0}
}
"""


class TestHloParser:
    def test_all_five_kinds(self):
        ops = parse_hlo_collectives(_FIVE_KINDS)
        kinds = sorted(o["kind"] for o in ops)
        assert kinds == sorted(["all-reduce", "all-gather",
                                "reduce-scatter", "collective-permute",
                                "all-to-all"])

    def test_bytes_math_per_kind(self):
        by = {o["kind"]: o for o in parse_hlo_collectives(_FIVE_KINDS)}
        # all-reduce: 256 f32 payload, 8-way ring
        assert by["all-reduce"]["payload_bytes"] == 1024
        assert by["all-reduce"]["wire_bytes"] == 896
        # all-gather: result is the gathered 1024-elem tensor
        assert by["all-gather"]["payload_bytes"] == 4096
        assert by["all-gather"]["wire_bytes"] == 3072
        # reduce-scatter: payload is the PRE-scatter input, result x g
        assert by["reduce-scatter"]["payload_bytes"] == 64 * 4 * 4
        assert by["reduce-scatter"]["wire_bytes"] == 768
        # permute ships the whole buffer point-to-point
        assert by["collective-permute"]["payload_bytes"] == 1024
        assert by["collective-permute"]["wire_bytes"] == 1024

    def test_replica_group_attribution(self):
        by = {o["kind"]: o for o in parse_hlo_collectives(_FIVE_KINDS)}
        assert by["all-reduce"]["group_count"] == 1
        assert by["all-reduce"]["group_size"] == 8
        assert by["all-gather"]["group_count"] == 1
        assert by["all-gather"]["group_size"] == 4
        assert by["all-to-all"]["group_size"] == 2

    def test_iota_replica_groups(self):
        text = ("%ar = f32[64]{0} all-reduce(f32[64]{0} %x), "
                "replica_groups=[2,4]<=[8], to_apply=%add\n")
        (op,) = parse_hlo_collectives(text)
        assert (op["group_count"], op["group_size"]) == (2, 4)
        assert op["wire_bytes"] == int(256 * 3 / 4)

    def test_async_start_counted_once(self):
        text = (
            "%ars = (f32[128]{0}, f32[128]{0}) "
            "all-reduce-start(f32[128]{0} %x), "
            "replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add\n"
            "%ard = f32[128]{0} all-reduce-done("
            "(f32[128]{0}, f32[128]{0}) %ars)\n")
        ops = parse_hlo_collectives(text)
        assert len(ops) == 1
        assert ops[0]["kind"] == "all-reduce"
        # tuple shape: payload is the largest component, not the sum
        assert ops[0]["payload_bytes"] == 512

    def test_unknown_ops_and_junk_tolerated(self):
        text = ("HloModule junk\n"
                "%a = f32[8]{0} frobnicate(f32[8]{0} %x)\n"
                "not an instruction at all\n"
                "%b = f32[8]{0} add(f32[8]{0} %a, f32[8]{0} %a)\n")
        assert parse_hlo_collectives(text) == []
        assert summarize_collectives([])["ops"] == 0

    def test_degenerate_listed_but_excluded(self):
        text = ("%ar = f32[64]{0} all-reduce(f32[64]{0} %x), "
                "replica_groups={{0}}, to_apply=%add\n")
        (op,) = parse_hlo_collectives(text)
        assert op["degenerate"] and op["wire_bytes"] == 0
        s = summarize_collectives([op])
        assert s["ops"] == 0 and s["wire_bytes"] == 0
        assert s["degenerate_ops"] == 1

    def test_summary_by_kind_rollup(self):
        s = summarize_collectives(parse_hlo_collectives(_FIVE_KINDS))
        assert s["ops"] == 5
        assert s["by_kind"]["all-reduce"]["max_group_size"] == 8
        assert s["wire_bytes"] == sum(
            k["wire_bytes"] for k in s["by_kind"].values())


# -------------------------------------------------------- reshard witness
def _leaf(spec, shape=(8, 4)):
    """Metadata stub for a committed jax.Array — the witness only reads
    .shape/.dtype/.sharding.spec."""
    return types.SimpleNamespace(
        shape=shape, dtype="float32",
        sharding=types.SimpleNamespace(spec=spec))


class TestReshardWitness:
    def test_disabled_instrument_is_identity(self, monkeypatch):
        monkeypatch.delenv("DL4J_TPU_COMMSMON", raising=False)
        reset_reshard_witness()
        assert not commsmon_enabled()
        assert get_reshard_witness() is None

        def fn(x):
            return x

        assert instrument(fn, arg_specs=(P("data", None),)) is fn
        # the in-place seam is likewise a no-op
        check_dispatch_args("X", {"x": (_leaf(("x",)), ())})

    def test_env_flag_enables(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_COMMSMON", "1")
        reset_reshard_witness()
        try:
            assert commsmon_enabled()
            w = get_reshard_witness()
            assert isinstance(w, ReshardWitness)
            assert get_reshard_witness() is w      # process-global
        finally:
            reset_reshard_witness()

    def test_divergence_event_is_gl802(self):
        w = ReshardWitness()
        events = w.check(_leaf((None, "model")), "x", ("data", None),
                         owner="Net")
        assert len(events) == 1
        ev = events[0]
        assert ev["rule"] == "GL802"
        assert ev["expected"] == "('data',None)"
        assert ev["actual"] == "(None,'model')"
        assert ev["owner"] == "Net" and ev["arg"] == "x"
        rep = w.report()
        assert rep["static_rules"].get("reshard") == "GL802"

    def test_matching_and_uncommitted_leaves_pass(self):
        w = ReshardWitness()
        assert w.check(_leaf(("data", None)), "x", ("data", None),
                       owner="Net") == []
        # a host array has no NamedSharding: nothing to reshard
        assert w.check(np.zeros((4, 4), np.float32), "x", ("data", None),
                       owner="Net") == []
        assert w.report()["events"] == []
        assert w.checks == 2

    def test_one_event_per_owner_leaf(self):
        w = ReshardWitness()
        bad = {"grads": [_leaf((None,), shape=(8,))]}
        assert len(w.check(bad, "state", ("data",), owner="Net")) == 1
        # the same divergence on the next step is not re-reported
        assert w.check(bad, "state", ("data",), owner="Net") == []
        assert len(w.report()["events"]) == 1

    def test_callable_spec_and_wrapper_naming(self):
        w = ReshardWitness()

        def fn(x):
            return "ran"

        inst = instrument(fn, name="step", witness=w,
                          arg_specs=(lambda leaf: ("data",)
                                     + (None,) * (len(leaf.shape) - 1),),
                          arg_names=("batch",))
        assert inst is not fn and inst.__name__ == "commsmon[step]"
        assert inst(_leaf((None, None))) == "ran"    # still calls through
        (ev,) = w.report()["events"]
        assert ev["expected"] == "('data',None)" and ev["arg"] == "batch"

    def test_reshard_counter_published(self):
        from deeplearning4j_tpu.observe.registry import get_registry
        w = ReshardWitness()
        w.check(_leaf(("model",), shape=(8,)), "x", ("data",),
                owner="CounterNet")
        prom = get_registry().to_prometheus()
        assert any("reshard_events_total" in line and "CounterNet" in line
                   for line in prom.splitlines())


# --------------------------------------------- end-to-end ledger (8 dev)
class TestCommLedgerEndToEnd:
    def _fresh_watchdog(self):
        prev = get_watchdog()
        wd = RecompileWatchdog()
        set_watchdog(wd)
        return prev, wd

    def test_sharded_jit_lands_in_snapshot(self, devices8):
        from jax.sharding import NamedSharding
        from deeplearning4j_tpu.observe.watchdog import WatchedJitCache
        from deeplearning4j_tpu.parallel import make_mesh

        prev, wd = self._fresh_watchdog()
        try:
            owner = types.SimpleNamespace()
            cache = WatchedJitCache(owner, owner_class="LedgerOwner")
            mesh = make_mesh({"data": 8})
            x = jax.device_put(
                np.ones((16, 64), np.float32),
                NamedSharding(mesh, P("data", None)))
            w = jax.device_put(np.ones((64, 32), np.float32),
                               NamedSharding(mesh, P()))
            fn = cache.setdefault("step", jax.jit(
                lambda a, b: (a @ b).sum()))
            with mesh:
                fn(x, w).block_until_ready()
            tot = wd.owner_comm_totals(cache.owner_tag)
            assert tot is not None and tot["ops"] >= 1
            snap = wd.snapshot()["per_owner"][cache.owner_tag]
            kinds = set()
            for row in snap["collectives"].values():
                kinds |= set(row["by_kind"])
            # the sum over the data axis is exactly one all-reduce
            assert "all-reduce" in kinds
        finally:
            set_watchdog(prev)

    def _probed(self, wd, fn, *args):
        """(the probe's legs, the owner's snapshot) of one first call of
        `fn` through a watched cache."""
        from deeplearning4j_tpu.observe import get_span_store
        from deeplearning4j_tpu.observe.watchdog import WatchedJitCache

        cache = WatchedJitCache(types.SimpleNamespace(),
                                owner_class="ProbeOwner")
        step = cache.setdefault("step", fn)
        store = get_span_store()
        n0 = store.count
        jax.block_until_ready(step(*args))
        events = store.events(n0)
        probe = next(e for e in events if e["name"] == "compile.probe")
        legs = [e["name"] for e in events
                if e["parent_id"] == probe["span_id"]]
        return legs, wd.snapshot()["per_owner"][cache.owner_tag]

    def test_one_device_program_records_its_empty_inventory_unread(self):
        """The text of a module compiled for one device holds no
        collective: the probe records what the parse would have returned
        and leaves the text leg out; the cost is still priced."""
        prev, wd = self._fresh_watchdog()
        try:
            legs, snap = self._probed(
                wd, jax.jit(lambda a, b: (a @ b).sum()),
                np.ones((16, 64), np.float32), np.ones((64, 32), np.float32))
            assert legs == ["compile.probe.lower", "compile.probe.compile",
                            "compile.probe.cost"]
            (row,) = snap["collectives"].values()
            assert row == {"ops": 0, "payload_bytes": 0, "wire_bytes": 0,
                           "degenerate_ops": 0, "by_kind": {}}
            (cost,) = snap["costs"].values()
            assert cost["flops"] >= 2 * 16 * 64 * 32
        finally:
            set_watchdog(prev)

    def test_two_device_psum_keeps_the_text_leg_and_its_inventory(
            self, devices8):
        from jax.sharding import NamedSharding
        from deeplearning4j_tpu.parallel import make_mesh

        prev, wd = self._fresh_watchdog()
        try:
            mesh = make_mesh({"data": 2}, devices=devices8[:2])
            summed = jax.jit(jax.shard_map(
                lambda a: jax.lax.psum(a.sum(0), "data"), mesh=mesh,
                in_specs=P("data", None), out_specs=P()))
            x = jax.device_put(np.ones((4, 64), np.float32),
                               NamedSharding(mesh, P("data", None)))
            legs, snap = self._probed(wd, summed, x)
            assert legs == ["compile.probe.lower", "compile.probe.compile",
                            "compile.probe.cost", "compile.probe.text"]
            (row,) = snap["collectives"].values()
            assert row["ops"] == 1 and set(row["by_kind"]) == {"all-reduce"}
            assert row["by_kind"]["all-reduce"]["max_group_size"] == 2
            assert row["wire_bytes"] > 0
        finally:
            set_watchdog(prev)

    def test_decode_window_has_zero_collectives(self, devices8):
        """ROADMAP item 1's acceptance line, measured: a fused decode
        window on a single-replica model compiles to ZERO collectives
        (degenerate single-participant ops excluded by contract)."""
        from test_decode_sessions import _make_net

        prev, wd = self._fresh_watchdog()
        try:
            from test_fused_decode import _plane
            net = _make_net()
            registry, sched, mgr = _plane(net, fused_k=4)
            try:
                sess = mgr.open_session([1, 2, 3], max_tokens=8,
                                        greedy=True)
                assert sess.result(timeout=60)
            finally:
                sched.shutdown()
                registry.close()
            totals = wd.comm_totals()
            assert totals, "comm ledger recorded no programs at all"
            for tag, tot in totals.items():
                assert tot["ops"] == 0 and tot["wire_bytes"] == 0, \
                    f"{tag} emitted collectives on 1 replica: {tot}"
        finally:
            set_watchdog(prev)

    def test_dp_all_reduce_reconciles(self, devices8):
        """The replicated-leg gradient all-reduce prices at the textbook
        4 * param_count * (n-1)/n ring bytes (+ the scalar-loss
        all-reduce's ~4B of slack)."""
        from deeplearning4j_tpu.parallel import ParallelWrapper, make_mesh
        from test_sharding_spine import _net, _toy

        prev, wd = self._fresh_watchdog()
        try:
            x, y = _toy(n=64)
            net = _net()
            pw = ParallelWrapper(net, mesh=make_mesh({"data": 8}),
                                 prefetch_buffer=0,
                                 shard_opt_state=False)
            pw.fit(x, y, epochs=1, batch_size=64)
            param_count = sum(
                int(leaf.size) for leaf in
                jax.tree_util.tree_leaves(net.params_tree))
            expected = 4.0 * param_count * 7 / 8
            snap = wd.snapshot()["per_owner"]
            measured = 0
            for tag, owner in snap.items():
                if not tag.startswith("ParallelWrapper@"):
                    continue
                for row in (owner.get("collectives") or {}).values():
                    ar = (row.get("by_kind") or {}).get("all-reduce")
                    if ar:
                        measured = max(measured, ar["wire_bytes"])
            assert measured, "no all-reduce recorded for the train step"
            # slack: the scalar loss all-reduce rides the same program
            assert expected <= measured <= expected + 64, \
                (measured, expected, param_count)
        finally:
            set_watchdog(prev)
