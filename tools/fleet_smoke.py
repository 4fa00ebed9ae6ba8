#!/usr/bin/env python
"""CI smoke for the serving fleet tier (`tools/ci_check.sh --fleet`).

Boots 1 router + 2 replica PROCESSES on localhost (pf0 prefill, dc0
decode — each its own interpreter and JAX runtime) and walks the three
seams the fleet contract hangs on:

  1. disaggregated request: the stem prefills on pf0, the warm pages
     ship over the dtype-aware handoff into dc0, dc0 streams — and a
     second, hint-warm request for the same prompt must produce the
     IDENTICAL greedy tokens without a second handoff;
  2. drain-migration: a finished session's home (dc0) is drained; its
     warm stem migrates out (export → install) and the sticky
     follow-up resumes on the survivor, continuing the exact greedy
     sequence an uninterrupted run would have produced;
  3. /metrics reconcile across tiers: the router's counters, both
     replicas' decode metrics, and the client-observed token count
     must agree EXACTLY (every generated token is accounted once).

Exits nonzero with the offending JSON on any miss, so the gate catches
a broken seam, not just a broken import.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

SPEC = {"kind": "bench_lm", "seed": 0, "vocab": 32, "chunk": 8,
        "max_cache": 64, "blocks": 1}
PROMPT = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8]
PROMPT2 = [2, 7, 1, 8, 2, 8, 1, 8, 2, 8, 4, 5]


def _cfg(name: str, role: str) -> dict:
    return {"name": name, "role": role, "port": 0, "model": SPEC,
            "decode_slots": 3, "prefill_chunk": 8, "page_len": 16}


def _fail(msg: str, doc=None) -> None:
    if doc is not None:
        print(json.dumps(doc, indent=1, default=str)[:4000])
    sys.exit(f"FAIL: {msg}")


def _stream(client, url: str, body: dict):
    """One /generate stream → (first_frame, tokens, terminal)."""
    first, tokens, terminal = None, [], None
    for ev in client.sse_events(url, "/generate", body, timeout=120.0):
        if first is None and "token" not in ev and "done" not in ev \
                and "error" not in ev:
            first = ev
        elif "token" in ev:
            tokens.append(int(ev["token"]))
        elif "done" in ev or "error" in ev:
            terminal = ev
            break
    return first or {}, tokens, terminal or {}


def _counter(snap: dict, name: str) -> float:
    for entry in (snap.get("series") or {}).get(name, ()):
        if "value" in entry:
            return float(entry["value"])
    return 0.0


def _walk_spans(node, depth=1):
    """Yield (node, depth) over one tree."""
    yield node, depth
    for c in node.get("children") or ():
        yield from _walk_spans(c, depth + 1)


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    log_dir = tempfile.mkdtemp(prefix="fleet_smoke_")
    # observability plane under test: sample every request, give the
    # router its own flight/incident dirs, no incident rate-limiting
    incident_dir = tempfile.mkdtemp(prefix="fleet_incidents_")
    os.environ["DL4J_TPU_TRACE_SAMPLE"] = "1"
    os.environ["DL4J_TPU_FLIGHT_DIR"] = tempfile.mkdtemp(
        prefix="fleet_router_flight_")
    os.environ["DL4J_TPU_INCIDENT_DIR"] = incident_dir
    os.environ["DL4J_TPU_INCIDENT_MIN_S"] = "0"
    # first-request compile makes CPU TTFT huge; this smoke tests the
    # federation/stitching plumbing, not the fleet SLO thresholds
    os.environ["DL4J_TPU_FLEET_SLO_TTFT_MS"] = "1e9"

    from deeplearning4j_tpu.serving.fleet import client
    from deeplearning4j_tpu.serving.fleet.launcher import launch_replica
    from deeplearning4j_tpu.serving.fleet.router import (
        FleetRouter, ReplicaHandle,
    )

    procs = []
    router = None
    try:
        for name, role in (("pf0", "prefill"), ("dc0", "decode")):
            procs.append(launch_replica(
                _cfg(name, role), log_dir=log_dir,
                env={"JAX_PLATFORMS": "cpu",
                     "DL4J_TPU_FLIGHT_DIR": tempfile.mkdtemp(
                         prefix=f"fleet_{name}_flight_")}))
        pf0, dc0 = procs
        router = FleetRouter([p.handle() for p in procs],
                             poll_interval=None)
        url = f"http://127.0.0.1:{router.start()}"

        # -- 1. disaggregated prefill→handoff→decode ------------------
        body = {"prompt_ids": PROMPT, "max_tokens": 8, "greedy": True}
        first, t1, term = _stream(client, url, body)
        if term.get("outcome") != "completed" or len(t1) != 8:
            _fail("disaggregated stream did not complete 8 tokens",
                  {"first": first, "terminal": term, "tokens": t1})
        if first.get("replica") != "dc0":
            _fail(f"decode landed on {first.get('replica')!r}, "
                  f"expected the decode-role replica", first)
        snap = client.get_json(url, "/metrics")
        if _counter(snap, "fleet_handoffs_total") != 1 or \
                _counter(snap, "fleet_handoff_failures_total"):
            _fail("expected exactly one successful KV handoff",
                  snap.get("series"))
        if _counter(snap, "fleet_handoff_bytes_total") <= 0:
            _fail("handoff shipped zero KV bytes")
        info = client.get_json(dc0.url, "/fleet/info")
        hits = ((info.get("decode") or {}).get("default", {})
                .get("prefix") or {}).get("hits", 0)
        if hits < 1:
            _fail("decode replica's radix saw no hit — the handed-off "
                  "pages were not matched at admission", info)
        # hint-warm repeat: same prompt, no second handoff, same tokens
        _, t2, _ = _stream(client, url, body)
        if t2 != t1:
            _fail(f"warm repeat diverged: {t2} vs {t1}")
        snap = client.get_json(url, "/metrics")
        if _counter(snap, "fleet_handoffs_total") != 1:
            _fail("hint-warm repeat triggered a redundant handoff")
        print(f"fleet smoke: handoff OK (pf0→dc0, tokens={t1})")

        # -- 1b. cross-process trace stitching ------------------------
        tid = first.get("trace_id")
        if not tid:
            _fail("sampled request carried no trace_id", first)
        tree = client.get_json(url, f"/trace/{tid}")
        if not tree.get("stitched") or tree.get("processes", 0) < 2:
            _fail("trace did not stitch across >=2 processes", tree)
        if tree.get("depth", 0) < 5:
            _fail(f"stitched depth {tree.get('depth')} < 5", tree)
        names, hops, grafted_session = set(), set(), False
        for root in tree.get("tree") or ():
            for node, _ in _walk_spans(root):
                names.add(node.get("name"))
                if node.get("name") in ("prefill.hop", "decode.hop"):
                    hops.add(node["name"])
                    for sub, _ in _walk_spans(node):
                        if str(sub.get("name", "")).startswith(
                                "session."):
                            grafted_session = True
        if hops != {"prefill.hop", "decode.hop"}:
            _fail(f"expected both hop spans, saw {sorted(hops)}",
                  {"names": sorted(names)})
        if not grafted_session:
            _fail("no replica session.* span grafted under a hop",
                  {"names": sorted(names)})
        print(f"fleet smoke: stitched trace OK (depth={tree['depth']}, "
              f"processes={tree['processes']}, "
              f"grafted={tree.get('grafted_spans')})")

        # -- 2. drain-migration ---------------------------------------
        sid = "smoke-mig"
        body2 = {"prompt_ids": PROMPT2, "max_tokens": 8, "greedy": True,
                 "fleet_session": sid}
        first, mig1, term = _stream(client, url, body2)
        home = first.get("replica")
        if term.get("outcome") != "completed" or home != "dc0":
            _fail("migration session did not complete on dc0",
                  {"first": first, "terminal": term})
        # pf0 becomes a decode-capable target, then the home drains
        router.add_replica(ReplicaHandle("pf0", pf0.url, "mixed"))
        drained = client.post_json(url, "/fleet/drain",
                                   {"replica": "dc0"})
        if drained.get("migrated", 0) < 1 or drained.get("failed"):
            _fail("drain migrated no sessions", drained)
        first, mig2, term = _stream(client, url, {
            **body2, "prompt_ids": PROMPT2 + mig1})
        if first.get("replica") != "pf0" or \
                term.get("outcome") != "completed":
            _fail("sticky follow-up did not resume on the survivor",
                  {"first": first, "terminal": term})
        # the migrated continuation must equal one uninterrupted run
        _, ref16, _ = _stream(client, url, {
            "prompt_ids": PROMPT2, "max_tokens": 16, "greedy": True})
        if mig1 + mig2 != ref16:
            _fail(f"migrated stream diverged: {mig1 + mig2} vs {ref16}")
        client.post_json(url, "/fleet/drain",
                         {"replica": "dc0", "draining": False})
        print(f"fleet smoke: drain-migration OK "
              f"(dc0→pf0, migrated={drained['migrated']})")

        # -- 3. /metrics reconcile across tiers -----------------------
        client_tokens = len(t1 + t2 + mig1 + mig2 + ref16)
        snap = client.get_json(url, "/metrics")
        router_tokens = _counter(snap, "fleet_tokens_streamed_total")
        router_reqs = _counter(snap, "fleet_requests_total")
        failed = _counter(snap, "fleet_failed_requests_total")
        rep_tokens = 0
        for p in procs:
            rep = client.get_json(p.url, "/metrics")
            for d in (rep.get("decode") or {}).values():
                rep_tokens += int(d.get("tokens_streamed") or 0)
        if failed:
            _fail(f"router counted {failed} failed requests")
        if not (router_tokens == rep_tokens == client_tokens):
            _fail(f"token ledgers disagree: router={router_tokens} "
                  f"replicas={rep_tokens} client={client_tokens}")
        if router_reqs != 5:
            _fail(f"router counted {router_reqs} requests, made 5")
        print(f"fleet smoke: {int(router_tokens)} tokens reconciled "
              f"across router, {len(procs)} replicas, and the client "
              f"({int(router_reqs)} requests, 0 failed)")

        # -- 4. federated /fleet/metrics reconcile --------------------
        fed = client.get_json(url, "/fleet/metrics?refresh=1")
        fed_tokens = 0.0
        for entry in (fed.get("series") or {}).get(
                "serving_decode_tokens_total", ()):
            if "replica" not in (entry.get("labels") or {}):
                fed_tokens += float(entry.get("value") or 0.0)
        if fed_tokens != rep_tokens:
            _fail(f"federated token counter {fed_tokens} != "
                  f"per-replica sum {rep_tokens}", fed.get("replicas"))
        stale = [r for r, row in (fed.get("replicas") or {}).items()
                 if row.get("stale")]
        if stale:
            _fail(f"live replicas marked stale: {stale}",
                  fed.get("replicas"))
        print(f"fleet smoke: federation OK ({int(fed_tokens)} tokens "
              f"reconciled via /fleet/metrics, 0 stale)")

        # -- 5. ReplicaKill → failover → incident bundle --------------
        from deeplearning4j_tpu.parallel.chaos import ReplicaKill
        by_name = {"pf0": pf0, "dc0": dc0}
        kill, tokens5, term5, first5 = None, [], {}, {}
        body5 = {"prompt_ids": PROMPT, "max_tokens": 8, "greedy": True,
                 "fleet_session": "smoke-kill"}
        for ev in client.sse_events(url, "/generate", body5,
                                    timeout=120.0):
            if "replica" in ev and "token" not in ev and kill is None:
                first5 = ev
                kill = ReplicaKill(by_name[ev["replica"]],
                                   after_tokens=3)
            elif "token" in ev:
                tokens5.append(int(ev["token"]))
                if kill is not None:
                    kill.maybe_fire(len(tokens5))
            elif "done" in ev or "error" in ev:
                term5 = ev
                break
        dead = first5.get("replica")
        if term5.get("outcome") != "completed" or len(tokens5) != 8:
            _fail("stream did not survive the replica kill",
                  {"first": first5, "terminal": term5,
                   "tokens": tokens5})
        # The smoke router has no background poll thread
        # (poll_interval=None), and killing the prefill replica does
        # not interrupt the decode stream — drive crash detection
        # explicitly until the incident lands.
        bundles, deadline = [], time.time() + 60.0
        while time.time() < deadline:
            router.poll_once()
            if not router.obsplane.wait_idle(timeout=60.0):
                _fail("incident collector did not finish")
            bundles = sorted(
                d for d in os.listdir(incident_dir)
                if d.startswith("incident-") and os.path.isfile(
                    os.path.join(incident_dir, d, "manifest.json")))
            if bundles:
                break
            time.sleep(0.5)
        if not bundles:
            _fail(f"no incident bundle under {incident_dir}")
        with open(os.path.join(incident_dir, bundles[-1],
                               "manifest.json")) as f:
            man = json.load(f)
        if not man.get("router_flight"):
            _fail("incident manifest missing the router flight dump",
                  man)
        rows = {r["name"]: r for r in man.get("replicas") or ()}
        if dead not in rows or not rows[dead].get("unreachable"):
            _fail(f"dead replica {dead!r} not marked unreachable", man)
        survivors = [r for r in rows.values()
                     if not r.get("unreachable") and r.get("flight")]
        if not survivors:
            _fail("no surviving replica's flight dump in the bundle",
                  man)
        print(f"fleet smoke OK: kill of {dead} -> failover resumed "
              f"(8 tokens), incident bundle "
              f"{bundles[-1]} (survivor dumps: "
              f"{[r['name'] for r in survivors]})")
        return 0
    finally:
        if router is not None:
            router.stop()
        for p in procs:
            p.terminate()


if __name__ == "__main__":
    sys.exit(main())
