"""Serving fleet: router tier over N replicas (PR 17).

What these pin:
  * the handoff wire format (kv-handoff-v1): fp32/int8/fp8 pages and
    their in-page scale rows serialize → deserialize bit-exactly —
    quantized bytes ship AS bytes, a handoff never dequantizes
  * KV page round-trips between real paged pools: export a warm stem
    (full pages, a partially-filled tail page, a CoW-forked page) from
    a donor plane, install into a recipient, and the recipient's greedy
    stream is bit-exact against the donor's; a duplicate install leaks
    zero pages; a dtype-mismatched install is refused
  * prefill-only sessions (the fleet prefill role's admission path)
  * the router end-to-end over in-process HTTP replicas: disaggregated
    prefill→handoff→decode parity against a single-plane reference,
    one causal trace tree spanning router→prefill→decode, sticky
    sessions, drain = migration (never a drop), SLO burn-rate firing →
    automatic drain + reroute with zero failed in-flight, and
    fleet-coordinated hot-swap with rollback everywhere when one
    replica's deploy fails
  * chaos (slow): a SIGKILLed replica PROCESS mid-stream — the stream
    resumes on another replica and the client's token sequence is
    byte-equal to an uninterrupted run
"""

import json
import time

import numpy as np
import pytest

from deeplearning4j_tpu.observe import reqtrace
from deeplearning4j_tpu.serving.fleet import client, handoff
from deeplearning4j_tpu.serving.fleet.handoff import (
    HandoffError, export_prefix, install_prefix, payload_bytes,
)
from deeplearning4j_tpu.serving.fleet.replica_main import (
    build_bench_lm, make_server,
)
from deeplearning4j_tpu.serving.fleet.router import (
    FleetRouter, ReplicaHandle,
)

V, T = 13, 6
LP = 4              # page length for every paged plane in this file


def _make_net(seed=0, emb=12, max_len=64, window=8, max_cache=16):
    """Non-rolling decode stack (rolling rings cannot page)."""
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers.attention import (
        PositionEmbeddingLayer, TransformerEncoderBlock,
    )
    from deeplearning4j_tpu.nn.layers.feedforward import (
        EmbeddingSequenceLayer,
    )
    from deeplearning4j_tpu.nn.layers.recurrent import RnnOutputLayer
    from deeplearning4j_tpu.optim.updaters import Adam

    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-3))
            .activation("identity")
            .list(EmbeddingSequenceLayer(n_in=V, n_out=emb),
                  PositionEmbeddingLayer(max_length=max_len),
                  TransformerEncoderBlock(num_heads=2, causal=True,
                                          window=window,
                                          rolling_cache=False,
                                          max_cache=max_cache),
                  RnnOutputLayer(n_out=V, activation="softmax"))
            .set_input_type(InputType.recurrent(1, T)).build())
    return MultiLayerNetwork(conf).init()


@pytest.fixture(scope="module")
def net():
    return _make_net()


def _plane(net, *, slots=2, chunk=4, page_len=LP, kv_dtype=None):
    from deeplearning4j_tpu.serving import (
        ContinuousBatchingScheduler, ModelRegistry, ServingStats,
    )
    from deeplearning4j_tpu.serving.sessions import DecodeSessionManager

    registry = ModelRegistry()
    registry.deploy("default", 1, net, warm=False)
    stats = ServingStats()
    sched = ContinuousBatchingScheduler(registry, stats, max_batch_size=8)
    mgr = DecodeSessionManager(registry, sched, "default", slots=slots,
                               prefill_chunk=chunk, page_len=page_len,
                               kv_dtype=kv_dtype, metrics=stats.registry)
    return registry, sched, mgr


def _run(mgr, prompt, max_tokens=4, **kw):
    sess = mgr.open_session(prompt, max_tokens=max_tokens, greedy=True,
                            **kw)
    return sess.result(timeout=60)


def _page_bytes(payload):
    """The raw per-page wire bytes, for bit-exactness comparisons."""
    return [{k: spec["data"] for k, spec in page.items()}
            for page in payload["pages"]]


# ------------------------------------------------------- wire format
class TestWireFormat:
    """kv-handoff-v1 leaf serialization, no pools involved. fp8 is
    covered HERE because the pool degrades fp8→int8 on CPU backends —
    the wire format itself must round-trip fp8 bytes for TPU fleets."""

    def _roundtrip(self, leaves):
        wire = handoff._leaves_to_wire(leaves)
        # through real JSON: the payload crosses an HTTP hop in prod
        back = handoff._wire_to_leaves(json.loads(json.dumps(wire)))
        assert set(back) == set(leaves)
        for key, arr in leaves.items():
            got = back[key]
            assert got.dtype == np.asarray(arr).dtype
            assert got.shape == np.asarray(arr).shape
            assert got.tobytes() == np.ascontiguousarray(arr).tobytes()
        return wire

    def test_fp32_roundtrip(self):
        rng = np.random.default_rng(0)
        self._roundtrip({
            "blk/cache_k": rng.standard_normal((LP, 2, 8), dtype=np.float32),
            "blk/cache_v": rng.standard_normal((LP, 2, 8), dtype=np.float32),
        })

    def test_int8_with_scale_rows_roundtrip(self):
        rng = np.random.default_rng(1)
        self._roundtrip({
            "blk/cache_k": rng.integers(-128, 128, (LP, 2, 8),
                                        dtype=np.int8),
            "blk/scale_k": rng.standard_normal((LP, 2)).astype(np.float32),
            "blk/cache_v": rng.integers(-128, 128, (LP, 2, 8),
                                        dtype=np.int8),
            "blk/scale_v": rng.standard_normal((LP, 2)).astype(np.float32),
        })

    def test_fp8_roundtrip(self):
        ml_dtypes = pytest.importorskip("ml_dtypes")
        rng = np.random.default_rng(2)
        vals = rng.standard_normal((LP, 2, 8)).astype(np.float32)
        fp8 = vals.astype(ml_dtypes.float8_e4m3fn)
        wire = self._roundtrip({"blk/cache_k": fp8,
                                "blk/scale_k": np.ones((LP, 2),
                                                       np.float32)})
        assert wire["blk/cache_k"]["dtype"] == "float8_e4m3fn"

    def test_unknown_dtype_refused(self):
        with pytest.raises(HandoffError, match="unknown dtype"):
            handoff._wire_to_leaves(
                {"blk/cache_k": {"shape": [1], "dtype": "not_a_dtype",
                                 "data": "AA=="}})

    def test_payload_bytes_counts_decoded_bytes(self):
        arr = np.arange(24, dtype=np.float32).reshape(2, 12)
        payload = {"pages": [handoff._leaves_to_wire({"k": arr})]}
        assert payload_bytes(payload) == arr.nbytes


# --------------------------------------------- pool page round-trips
class TestKVPageRoundTrip:
    """export_prefix → install_prefix between two REAL paged pools."""

    PROMPT = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5]   # stem 10 = 2 full + 2

    @pytest.fixture(params=[None, "int8"], ids=["native", "int8"])
    def kv_dtype(self, request):
        # fp8 degrades to int8 on CPU (policy: _fp8_capable needs TPU);
        # its wire format is pinned in TestWireFormat instead
        return request.param

    @pytest.fixture()
    def planes(self, net, kv_dtype):
        donor = _plane(net, kv_dtype=kv_dtype)
        recip = _plane(net, kv_dtype=kv_dtype)
        yield donor, recip
        for registry, sched, _ in (donor, recip):
            sched.shutdown()
            registry.close()

    def test_roundtrip_bit_exact_and_warm_parity(self, planes):
        (_, _, d_mgr), (_, _, r_mgr) = planes
        prompt = np.asarray(self.PROMPT)
        donor_out = _run(d_mgr, prompt, max_tokens=4)
        stem = self.PROMPT[:-1]
        payload = export_prefix(d_mgr.pool, d_mgr.prefix_cache, stem)
        assert payload is not None
        assert payload["format"] == "kv-handoff-v1"
        assert payload["cached_len"] == len(stem)
        # stem 10 over page_len 4: two immutable full pages + a
        # mid-chain page matched 2 tokens deep
        assert payload["full_pages"] == 2
        assert payload["partial_tokens"] == 2
        assert payload["kv_dtype"] == d_mgr.pool.kv_dtype
        if d_mgr.pool.kv_dtype == "int8":
            specs = payload["pages"][0]
            assert any(k.endswith("scale_k") for k in specs)
            assert any(s["dtype"] == "int8" for s in specs.values())

        installed = install_prefix(r_mgr.pool, r_mgr.prefix_cache,
                                   json.loads(json.dumps(payload)))
        assert installed == len(stem)
        # re-export from the recipient: byte-for-byte the same pages
        back = export_prefix(r_mgr.pool, r_mgr.prefix_cache, stem)
        assert back is not None
        assert back["tokens"] == payload["tokens"]
        assert _page_bytes(back) == _page_bytes(payload)

        # warm greedy stream on the recipient is bit-exact vs donor
        warm = _run(r_mgr, prompt, max_tokens=4)
        assert list(warm) == list(donor_out)
        stats = r_mgr.prefix_cache.stats()
        assert stats["hits"] >= 1
        assert stats["hit_tokens"] >= len(stem) - LP + 1

    def test_cow_forked_page_exports(self, planes):
        (_, _, d_mgr), (_, _, r_mgr) = planes
        base = [1, 2, 3, 4, 5, 6, 7, 8]
        fork = base[:6] + [9, 10, 11]       # diverges mid-page 2
        _run(d_mgr, np.asarray(base), max_tokens=2)
        donor_out = _run(d_mgr, np.asarray(fork), max_tokens=4)
        assert d_mgr.prefix_cache.stats()["cow_forks"] >= 1
        payload = export_prefix(d_mgr.pool, d_mgr.prefix_cache,
                                fork[:-1])
        assert payload is not None
        assert payload["cached_len"] == len(fork) - 1
        install_prefix(r_mgr.pool, r_mgr.prefix_cache, payload)
        warm = _run(r_mgr, np.asarray(fork), max_tokens=4)
        assert list(warm) == list(donor_out)

    def test_duplicate_install_leaks_nothing(self, planes):
        (_, _, d_mgr), (_, _, r_mgr) = planes
        _run(d_mgr, np.asarray(self.PROMPT), max_tokens=4)
        payload = export_prefix(d_mgr.pool, d_mgr.prefix_cache,
                                self.PROMPT[:-1])
        install_prefix(r_mgr.pool, r_mgr.prefix_cache, payload)
        with r_mgr.pool.lock():
            free_before = r_mgr.pool.pages_free_locked()
        cached_before = r_mgr.prefix_cache.stats()["cached_pages"]
        # second install: the radix declines every chunk (already
        # cached) and each fresh page must return to the free list
        install_prefix(r_mgr.pool, r_mgr.prefix_cache, payload)
        with r_mgr.pool.lock():
            assert r_mgr.pool.pages_free_locked() == free_before
        assert (r_mgr.prefix_cache.stats()["cached_pages"]
                == cached_before)

    def test_dtype_mismatch_refused(self, net):
        donor = _plane(net, kv_dtype="int8")
        recip = _plane(net, kv_dtype=None)
        try:
            d_mgr, r_mgr = donor[2], recip[2]
            _run(d_mgr, np.asarray(self.PROMPT), max_tokens=2)
            payload = export_prefix(d_mgr.pool, d_mgr.prefix_cache,
                                    self.PROMPT[:-1])
            with pytest.raises(HandoffError, match="kv_dtype mismatch"):
                install_prefix(r_mgr.pool, r_mgr.prefix_cache, payload)
        finally:
            for registry, sched, _ in (donor, recip):
                sched.shutdown()
                registry.close()

    def test_bad_payloads_refused(self, net):
        registry, sched, mgr = _plane(net)
        try:
            with pytest.raises(HandoffError, match="unknown handoff"):
                install_prefix(mgr.pool, mgr.prefix_cache,
                               {"format": "kv-handoff-v0"})
            with pytest.raises(HandoffError, match="page_len mismatch"):
                install_prefix(
                    mgr.pool, mgr.prefix_cache,
                    {"format": "kv-handoff-v1", "page_len": LP + 1,
                     "kv_dtype": mgr.pool.kv_dtype, "cached_len": 0,
                     "tokens": [], "full_pages": 0,
                     "partial_tokens": 0, "pages": []})
        finally:
            sched.shutdown()
            registry.close()


# -------------------------------------------- prefill-only admission
class TestPrefillOnly:
    PROMPT = [2, 7, 1, 8, 2, 8, 1, 8, 2, 8]

    def test_prefill_only_indexes_stem(self, net):
        registry, sched, mgr = _plane(net)
        try:
            sess = mgr.open_prefill(np.asarray(self.PROMPT))
            out = sess.result(timeout=60)
            assert list(out) == []          # zero generated tokens
            payload = export_prefix(mgr.pool, mgr.prefix_cache,
                                    self.PROMPT[:-1])
            assert payload is not None
            assert payload["cached_len"] == len(self.PROMPT) - 1
        finally:
            sched.shutdown()
            registry.close()

    def test_prefill_only_requires_paged_pool(self, net, monkeypatch):
        # the policy would otherwise auto-enable paging for this net
        monkeypatch.setenv("DL4J_TPU_PREFIX_CACHE", "off")
        registry, sched, mgr = _plane(net, page_len=None)
        try:
            with pytest.raises(ValueError, match="prefill-only"):
                mgr.open_prefill(np.asarray(self.PROMPT))
        finally:
            sched.shutdown()
            registry.close()


# --------------------------------------------------- router end-to-end
SPEC = {"kind": "bench_lm", "seed": 0, "vocab": 17, "chunk": 4,
        "max_cache": 32, "blocks": 1}
PROMPT = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8]


def _replica_cfg(name, role, **kw):
    cfg = {"name": name, "role": role, "model": dict(SPEC),
           "decode_slots": 3, "prefill_chunk": 4, "page_len": LP}
    cfg.update(kw)
    return cfg


def _start_fleet(cfgs, **router_kw):
    """In-process replicas + a router, over real localhost HTTP.
    Returns {"servers", "router", "url", "urls"}."""
    servers = [make_server(c) for c in cfgs]
    handles = []
    for srv in servers:
        port = srv.start()
        handles.append((srv.replica_name,
                        f"http://127.0.0.1:{port}", srv.role))
    router_kw.setdefault("poll_interval", None)   # tests drive poll_once
    router = FleetRouter(handles, **router_kw)
    rport = router.start()
    return {"servers": {s.replica_name: s for s in servers},
            "router": router,
            "url": f"http://127.0.0.1:{rport}",
            "urls": {name: url for name, url, _ in handles}}


def _stop_fleet(fleet):
    fleet["router"].stop()
    for srv in fleet["servers"].values():
        srv.stop()


def _ref_tokens(spec, prompt, max_tokens):
    """Greedy reference from a fresh single plane of the same spec."""
    registry, sched, mgr = _plane(build_bench_lm(spec), slots=3, chunk=4)
    try:
        return [int(t) for t in
                _run(mgr, np.asarray(prompt), max_tokens=max_tokens)]
    finally:
        sched.shutdown()
        registry.close()


def _stream(url, body):
    """Consume one router SSE stream: (first_frame, tokens, terminal)."""
    first, tokens, terminal = None, [], None
    for ev in client.sse_events(url, "/generate", dict(body),
                                timeout=120.0):
        if first is None and "replica" in ev and "token" not in ev:
            first = ev
        if "token" in ev:
            tokens.append(int(ev["token"]))
        if "done" in ev or "error" in ev:
            terminal = ev
    return first, tokens, terminal


@pytest.mark.slow   # ~12s of in-proc servers; ci_check --fleet
class TestFleetRouter:  # smokes the same seams against real processes
    """One prefill + two decode replicas behind the router."""

    @pytest.fixture(scope="class")
    def fleet(self):
        fl = _start_fleet([_replica_cfg("pf0", "prefill"),
                           _replica_cfg("dc0", "decode"),
                           _replica_cfg("dc1", "decode")])
        yield fl
        _stop_fleet(fl)

    @pytest.fixture(scope="class")
    def ref16(self):
        return _ref_tokens(SPEC, PROMPT, 16)

    def test_disaggregated_parity_and_metrics(self, fleet, ref16):
        router = fleet["router"]
        out = client.post_json(
            fleet["url"], "/generate",
            {"prompt_ids": PROMPT, "max_tokens": 8, "greedy": True,
             "stream": False})
        assert out["outcome"] == "completed"
        assert out["tokens"] == ref16[:8]
        assert router._c_requests.value >= 1
        assert router._c_handoffs.value == 1
        assert router._c_handoff_bytes.value > 0
        assert router._c_failed.value == 0
        # the decode home's radix matched the handed-off stem: its
        # admission never re-prefilled the warm pages
        info = client.get_json(fleet["url"], "/fleet?refresh=1")
        hits = sum(
            i["decode"]["default"]["prefix"]["hits"]
            for name, i in info["info"].items()
            if name.startswith("dc"))
        assert hits >= 1
        assert info["info"]["pf0"]["role"] == "prefill"

    def test_trace_spans_one_causal_tree(self, fleet, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_TRACE_SAMPLE", "1")
        store = reqtrace.TraceStore()
        prev = reqtrace.set_trace_store(store)
        try:
            prompt = [2, 4, 6, 8, 10, 12, 1, 3, 5, 7, 9, 11]
            out = client.post_json(
                fleet["url"], "/generate",
                {"prompt_ids": prompt, "max_tokens": 4, "greedy": True,
                 "stream": False})
            spans = store.spans(out["trace_id"])
        finally:
            reqtrace.set_trace_store(prev)
        names = {s["name"] for s in spans}
        assert {"fleet.generate", "route", "prefill.hop", "handoff",
                "decode.hop"} <= names
        roots = [s for s in spans if s["name"] == "fleet.generate"]
        assert len(roots) == 1 and roots[0]["parent_id"] is None
        root_id = roots[0]["span_id"]
        for s in spans:
            if s is not roots[0]:
                assert s["parent_id"] == root_id
        # cross-process correlation: the hop spans carry the replicas'
        # names and own trace ids
        hop = next(s for s in spans if s["name"] == "decode.hop")
        assert hop["attrs"].get("replica", "").startswith("dc")
        pre = next(s for s in spans if s["name"] == "prefill.hop")
        assert pre["attrs"]["replica"] == "pf0"

    def test_sticky_session_repeats_home(self, fleet):
        body = {"prompt_ids": [5, 5, 7, 7, 5, 5, 7, 7, 2],
                "max_tokens": 3, "greedy": True,
                "fleet_session": "sticky-1"}
        first_a, _, _ = _stream(fleet["url"], body)
        first_b, _, _ = _stream(fleet["url"], body)
        assert first_a["replica"] == first_b["replica"]
        assert first_a["fleet_session"] == "sticky-1"

    def test_drain_migrates_and_draining_refuses(self, fleet, ref16):
        router = fleet["router"]
        body = {"prompt_ids": PROMPT, "max_tokens": 8, "greedy": True,
                "fleet_session": "mig-1"}
        first, tokens, _ = _stream(fleet["url"], body)
        assert tokens == ref16[:8]
        home = first["replica"]
        other = {"dc0": "dc1", "dc1": "dc0"}[home]

        res = client.post_json(fleet["url"], "/fleet/drain",
                               {"replica": home})
        assert res["draining"] is True
        assert res["migrated"] >= 1
        assert router._c_migrations.value >= 1
        with router._lock:
            assert router._sessions["mig-1"] == other
        info = client.get_json(fleet["url"], "/fleet")
        by = {r["name"]: r for r in info["replicas"]}
        assert by[home]["draining"] is True

        # the drained replica refuses NEW admissions itself (503) but
        # the router's migration resumes bypass the refusal
        with pytest.raises(client.ReplicaHTTPError) as ei:
            client.post_json(fleet["urls"][home], "/generate",
                             {"prompt_ids": PROMPT, "max_tokens": 1})
        assert ei.value.status == 503

        # the sticky follow-up continues the SAME greedy sequence on
        # the new home: migrated KV + prompt-extension resume
        follow = {"prompt_ids": PROMPT + ref16[:8], "max_tokens": 8,
                  "greedy": True, "fleet_session": "mig-1"}
        first2, tokens2, _ = _stream(fleet["url"], follow)
        assert first2["replica"] == other
        assert tokens2 == ref16[8:]
        assert router._c_failed.value == 0

        res = client.post_json(fleet["url"], "/fleet/drain",
                               {"replica": home, "draining": False})
        assert res["draining"] is False

    def test_router_healthz(self, fleet):
        hz = client.get_json(fleet["url"], "/healthz")
        assert hz["status"] == "ok"
        assert hz["tier"] == "router"
        assert hz["routable"] >= 2


class TestRouterEdge:
    def test_empty_fleet_is_503(self):
        router = FleetRouter([], poll_interval=None)
        port = router.start()
        try:
            with pytest.raises(client.ReplicaHTTPError) as ei:
                client.post_json(f"http://127.0.0.1:{port}", "/generate",
                                 {"prompt_ids": [1, 2, 3],
                                  "stream": False})
            assert ei.value.status == 503
            hz = client.get_json(f"http://127.0.0.1:{port}", "/healthz")
            assert hz["status"] == "degraded"
            assert "no healthy replica" in hz["reasons"]
        finally:
            router.stop()


@pytest.mark.slow   # boots two servers + an SLO sampler
class TestSLODrain:
    """A replica whose burn-rate SLO fires gets drained by the control
    loop; traffic reroutes with zero failed in-flight requests."""

    @pytest.fixture(scope="class")
    def fleet(self):
        # an SLO that always fires once any request lands: ttft p99 > 0
        slo_cfg = {"interval": 0.1, "objectives": [
            {"name": "always-breached", "series": "serving_ttft_ms:p99",
             "threshold": 0.0, "budget": 1.0, "fast_s": 30.0,
             "slow_s": 60.0, "burn_threshold": 0.5}]}
        fl = _start_fleet([_replica_cfg("slo0", "mixed", slo=slo_cfg),
                           _replica_cfg("ok0", "mixed")],
                          auto_drain_on_slo=True)
        yield fl
        _stop_fleet(fl)

    def test_slo_breach_drains_and_reroutes(self, fleet):
        router = fleet["router"]
        # land one request on slo0 so its ttft series has points
        client.post_json(fleet["urls"]["slo0"], "/generate",
                         {"prompt_ids": PROMPT, "max_tokens": 2,
                          "greedy": True, "stream": False})
        deadline = time.monotonic() + 30.0
        firing = []
        while time.monotonic() < deadline:
            hz = client.get_json(fleet["urls"]["slo0"], "/healthz")
            firing = [r for r in hz.get("reasons", ())
                      if r.startswith("slo firing")]
            if firing:
                break
            time.sleep(0.1)
        assert firing, "SLO never fired on the breached replica"

        verdicts = router.poll_once()
        assert "slo firing" in verdicts["slo0"]
        with router._lock:
            r = router._replicas["slo0"]
            assert r.draining and r.slo_drained
        assert router._c_slo_drains.value == 1

        # traffic reroutes; nothing in flight fails
        out = client.post_json(
            fleet["url"], "/generate",
            {"prompt_ids": PROMPT, "max_tokens": 4, "greedy": True,
             "stream": False})
        assert out["outcome"] == "completed"
        first, _, _ = _stream(fleet["url"],
                              {"prompt_ids": PROMPT, "max_tokens": 2,
                               "greedy": True})
        assert first["replica"] == "ok0"
        assert router._c_failed.value == 0


@pytest.mark.slow   # two servers + three fleet-wide deploys
class TestFleetDeploy:
    """Coordinated hot-swap: every replica flips or every flipped
    replica rolls back."""

    @pytest.fixture(scope="class")
    def fleet(self):
        fl = _start_fleet([_replica_cfg("da", "mixed"),
                           _replica_cfg("db", "mixed")])
        yield fl
        _stop_fleet(fl)

    def test_deploy_flips_fleet_then_rolls_back_on_failure(self, fleet):
        router = fleet["router"]
        v2_spec = dict(SPEC, seed=1)
        res = client.post_json(
            fleet["url"], "/fleet/deploy",
            {"name": "default", "version": 2, "spec": v2_spec},
            timeout=120.0)
        assert res["ok"] is True
        assert sorted(res["replicas"]) == ["da", "db"]
        ref_v2 = _ref_tokens(v2_spec, PROMPT, 6)
        out = client.post_json(
            fleet["url"], "/generate",
            {"prompt_ids": PROMPT, "max_tokens": 6, "greedy": True,
             "stream": False})
        assert out["tokens"] == ref_v2

        # a replica that can't take the deploy (unreachable here) must
        # roll every already-flipped replica back to the v2 fleet spec
        router.add_replica(ReplicaHandle("ghost", "http://127.0.0.1:9",
                                         "mixed"))
        res = client.post_json(
            fleet["url"], "/fleet/deploy",
            {"name": "default", "version": 3,
             "spec": dict(SPEC, seed=2)}, timeout=120.0)
        assert res["ok"] is False
        assert res["failure"]["replica"] == "ghost"
        rolled = {r["replica"] for r in res["rolled_back"]}
        assert rolled == {"da", "db"}
        assert router._c_rollbacks.value == 1
        with router._lock:
            assert router._specs["default"]["version"] == 2
        # the fleet still serves the v2 weights everywhere
        out = client.post_json(
            fleet["url"], "/generate",
            {"prompt_ids": PROMPT, "max_tokens": 6, "greedy": True,
             "stream": False})
        assert out["tokens"] == ref_v2

    def test_bad_spec_fails_without_flipping(self, fleet):
        router = fleet["router"]
        res = client.post_json(
            fleet["url"], "/fleet/deploy",
            {"name": "default", "version": 9,
             "spec": {"kind": "no_such_builder"}}, timeout=120.0)
        assert res["ok"] is False
        assert "bad model spec" in res["failure"]["error"]
        assert res["rolled_back"] == []     # nothing flipped first
        with router._lock:
            assert router._specs["default"]["version"] == 2


# ------------------------------------------------------------- chaos
@pytest.mark.chaos
@pytest.mark.slow
class TestReplicaKillChaos:
    """SIGKILL one replica PROCESS mid-stream: the router fails the
    stream over and the client's token sequence is byte-equal to an
    uninterrupted run (greedy resume from prompt + emitted)."""

    def test_replica_kill_midstream_stream_continues(self, tmp_path):
        from deeplearning4j_tpu.parallel.chaos import ReplicaKill
        from deeplearning4j_tpu.serving.fleet.launcher import (
            launch_replica,
        )

        cpu = {"JAX_PLATFORMS": "cpu"}
        procs = [launch_replica(_replica_cfg("ka", "mixed"), env=cpu,
                                log_dir=str(tmp_path)),
                 launch_replica(_replica_cfg("kb", "mixed"), env=cpu,
                                log_dir=str(tmp_path))]
        router = FleetRouter([(p.name, p.url, p.role) for p in procs],
                             poll_interval=None)
        rport = router.start()
        url = f"http://127.0.0.1:{rport}"
        try:
            ref = _ref_tokens(SPEC, PROMPT, 12)
            # warm both replicas' compiled windows with a throwaway
            # stream so the kill run streams at steady state
            _, tokens, _ = _stream(url, {"prompt_ids": PROMPT,
                                         "max_tokens": 12,
                                         "greedy": True})
            assert tokens == ref

            by_name = {p.name: p for p in procs}
            kill = None
            tokens = []
            for ev in client.sse_events(
                    url, "/generate",
                    {"prompt_ids": PROMPT, "max_tokens": 12,
                     "greedy": True}, timeout=120.0):
                if kill is None and "replica" in ev and \
                        "token" not in ev:
                    # kill the serving replica at the FIRST token so
                    # the stream must fail over to the survivor
                    kill = ReplicaKill(by_name[ev["replica"]],
                                       after_tokens=1)
                if "token" in ev:
                    tokens.append(int(ev["token"]))
                    kill.maybe_fire(len(tokens))
                if "error" in ev:
                    pytest.fail(f"stream errored: {ev}")
            assert kill is not None and kill.fired
            assert tokens == ref
            assert router._c_reroutes.value >= 1
            assert router._c_failed.value == 0
        finally:
            router.stop()
            for p in procs:
                p.terminate()
