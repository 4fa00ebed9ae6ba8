"""Stateful decode serving: per-request sessions over a shared KV slot
pool, stepped through the continuous-batching scheduler.

The old decode path (`utils/textgen.generate`) drives `rnn_time_step`,
which mutates MODEL-GLOBAL carries — one autoregressive stream per net,
and a server would have to dedicate a model replica per conversation.
This module turns decode into data: each session owns a SLOT in a
`KVSlotPool` (one batch row of a [slots, ...] carry tree), and every
step — prefill chunk or fused decode window — is submitted to the
`ContinuousBatchingScheduler` as an ordinary one-row request against a
dedicated `<model>@decode` endpoint. The scheduler coalesces whatever
rows are queued (sessions at different phases — one mid-prefill,
another deep into decode — share the same dispatch), and the
endpoint's `run_batch` runs at most two jitted programs: one
`session_step` over the co-batched prefill chunks (its logits are
never read back), then one `session_decode_window` that advances every
decoding lane K TOKENS — sampling on-device (greedy/temperature/
top-k/top-p as lax ops), feeding each sample back through the model
inside a `lax.scan`, early-exiting lanes on EOS/budget via the active
mask. The callback chain consumes K sampled tokens per round-trip
instead of one: host round-trips, the dominant decode cost, are
amortized K-fold (`decode_loop_policy` picks K; DL4J_TPU_DECODE_LOOP /
DL4J_TPU_DECODE_K force it). Greedy fused output is bit-exact against
step-by-step decode by contract (tests/test_fused_decode.py).

Shapes are the contract: every dispatch runs at a prefill bucket (1 or
`prefill_chunk`) and/or the one window length K — all warmed at
construction, so session churn causes ZERO recompiles — the watchdog
stays quiet (see PERF_NOTES). TTFT/ITL histograms, token counters and
shared-dispatch counters ride the server's metrics registry so the
closed-loop bench can reconcile its client-side numbers; ITL inside a
window is amortized (window gap / tokens) since tokens arrive in
bursts of K.

Speculative decoding rides the same machinery: wire a DRAFT net in and
`spec_decode_policy` flips each window to draft-propose + target-verify
— the draft proposes spec_k tokens through its own fused window (its
slots live in a lockstep KVSlotPool, registered as `<model>@draft`),
the target scores all of them in ONE chunked forward, and accept/
reject (utils/sampling.spec_accept_lanes: greedy longest-prefix fast
path, standard rejection rule otherwise) stays on device. Rejected
proposals are un-written by rewinding per-slot positions, so both nets
must be rewind-capable (no recurrent carries, no rolling rings). The
host still pays exactly one sync per window — the verify's packed
result rows. `kv_dtype_policy` independently picks the pools' cache
storage (int8/fp8 with per-(token, kv-head) scales), multiplying
slots-per-chip at fixed memory.

Prefix cache: when `prefix_cache_policy` verdicts "paged", the pool
stores KV in fixed-size pages behind per-slot page tables and a radix
index (`prefix_cache.py`) maps prompt prefixes to refcounted shared
page chains. Admission matches the prompt stem, adopts the matched
pages, forks at most one partially-matched page (copy-on-write) and
installs the slot's table — all under the pool lock, all traced-scalar
programs — then prefill RESUMES after the cached prefix: a warm prefix
never re-prefills, so its TTFT approaches one decode window. Completed
prefills are offered back to the index at the phase-0→1 transition.
Eviction is leaf-first LRU over refcount-1 (cache-only) pages; a live
session's pages can never be reclaimed. Mutually exclusive with the
draft model (a draft's lockstep pool must prefill every token).

Hot-swap: the manager subscribes to registry deploy hooks for its base
model. In the "warm" phase it verifies the candidate can host the live
carry tree and pre-compiles its session-step buckets (raising rides
the normal rollback — sessions keep serving the old version); in the
"flipped" phase it rebinds the pool, migrating every live session onto
the new weights mid-stream instead of dropping them.
"""

from __future__ import annotations

import itertools
import logging
import queue
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from deeplearning4j_tpu.observe import reqtrace
from deeplearning4j_tpu.ops.kernel_defaults import (
    decode_loop_policy, kv_dtype_policy, prefix_cache_policy,
    spec_decode_policy,
)
from deeplearning4j_tpu.serving.kv_pool import (
    IncompatibleSessionSwapError, KVSlotPool, SlotPoolExhaustedError,
)
from deeplearning4j_tpu.serving.prefix_cache import PrefixCache
from deeplearning4j_tpu.serving.registry import ModelEntry
from deeplearning4j_tpu.serving.scheduler import (
    DeadlineExceededError, RequestShedError, SchedulerClosedError,
)
from deeplearning4j_tpu.utils.sampling import (
    SamplingParams, lane_param_arrays,
)
from deeplearning4j_tpu.utils.textgen import (
    _encode, _input_encoding, _resolve_net,
)

logger = logging.getLogger("deeplearning4j_tpu")

_OUTCOMES = ("completed", "cancelled", "expired", "failed")


class DecodeSession:
    """One streaming generation: a slot, a cursor into the prompt, the
    sampling state, and a queue of token events the client drains."""

    def __init__(self, sid: str, slot: int, prompt: np.ndarray, *,
                 max_tokens: int, params: SamplingParams,
                 seed: Optional[int], deadline_ms: Optional[float],
                 eos_id: Optional[int], trace=None):
        self.id = sid
        self.slot = slot
        self.prompt = prompt
        # sampled requests carry their TraceContext through every
        # resubmitted step; None on the sampled-off fast path
        self.trace = trace
        self.max_tokens = int(max_tokens)
        self.params = params
        # sampling runs ON-DEVICE inside the fused window: the session
        # carries a threefry base key, and token i always draws with
        # fold_in(base_key, i) — the stream is deterministic in the seed
        # and invariant to K and to dispatch co-batching
        seed = 0 if seed is None else int(seed)
        self.base_key = np.array(
            [(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)
        self.eos_id = eos_id
        self.opened_at = time.monotonic()
        self.deadline = (None if deadline_ms is None
                         else self.opened_at + deadline_ms / 1000.0)
        self.generated: List[int] = []
        self.outcome: Optional[str] = None
        self.error: Optional[BaseException] = None
        self.ttft_ms: Optional[float] = None
        self.done = threading.Event()
        self.cancelled = False
        self._events: "queue.Queue[dict]" = queue.Queue()
        self._off = 0              # prompt tokens already submitted
        self._last_tok_at: Optional[float] = None
        self._finished = False     # guarded by the manager lock
        # speculative-decode bookkeeping (manager-owned; safe to read and
        # write in run_batch because each session has exactly one row in
        # flight): how far the draft's positions must rewind on window
        # entry, and the catch-up token (d_k) the draft never cached
        # when the previous window fully accepted
        self._spec_rewind = 0
        self._spec_pre_tok = 0
        self._spec_pre_valid = False
        # paged prefix-cache bookkeeping (manager-owned): the session's
        # physical page chain, how many prompt tokens admission found
        # already cached (prefill skips them), and whether the finished
        # prefill was offered to the radix index yet
        self._pages: List[int] = []
        self._cached_len = 0
        self._prefix_inserted = False
        # the manager's radix deploy generation at admission: when a
        # hot-swap flips mid-stream, this session's KV belongs to the
        # old weights and must not be offered back to the radix index
        self._gen = 0
        # fleet disaggregation: a prefill-only session runs the prompt
        # stem through prefill, offers the pages to the radix index, and
        # finishes WITHOUT sampling — the decode role lives on another
        # replica, which imports the pages and decodes from the warm stem
        self._prefill_only = False

    # -------------------------------------------------------- client API
    def stream(self, timeout: Optional[float] = None):
        """Yield token events as they arrive: `{"token", "index"}` per
        token, then exactly one terminal event (`{"done": ...}` or
        `{"error": ...}`). Raises queue.Empty if `timeout` seconds pass
        without an event (a stalled-stream guard for clients)."""
        while True:
            ev = self._events.get(timeout=timeout)
            yield ev
            if "done" in ev or "error" in ev:
                return

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Block until the session finishes; returns the generated token
        ids, or raises the session's error (deadline, shed, crash)."""
        if not self.done.wait(timeout):
            raise TimeoutError(f"session {self.id} still running")
        if self.error is not None:
            raise self.error
        return list(self.generated)

    def cancel(self) -> None:
        """Request cancellation; honored at the next window boundary
        (there is always at most one row in flight per session, and a
        window is at most `fused_k` tokens). Tokens already streamed
        stay streamed."""
        self.cancelled = True

    def remaining_ms(self) -> Optional[float]:
        if self.deadline is None:
            return None
        return (self.deadline - time.monotonic()) * 1000.0

    def describe(self) -> dict:
        return {"id": self.id, "slot": self.slot,
                "prompt_len": int(self.prompt.size),
                "generated": len(self.generated),
                "max_tokens": self.max_tokens,
                "ttft_ms": self.ttft_ms,
                "outcome": self.outcome,
                "trace_id": (self.trace.trace_id
                             if self.trace is not None else None)}


class DecodeSessionManager:
    """Owns the slot pool, the `<model>@decode` endpoint, and the
    callback chain that steps every live session."""

    def __init__(self, registry, scheduler, model: str = "default", *,
                 slots: int = 4, prefill_chunk: int = 8,
                 fused_k: Optional[int] = None,
                 draft_net=None, spec_k: Optional[int] = None,
                 kv_dtype: Optional[str] = None,
                 page_len: Optional[int] = None,
                 metrics=None, warm: bool = True):
        if prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        base = registry.get(model)      # KeyError if not deployed
        if not hasattr(base.net, "session_carries"):
            raise TypeError(
                f"decode sessions need a net with session_carries() "
                f"(MultiLayerNetwork); got {type(base.net).__name__}")
        self.registry = registry
        self.scheduler = scheduler
        self.model = model
        self.decode_name = f"{model}@decode"
        self.prefill_chunk = int(prefill_chunk)
        self.buckets = sorted({1, self.prefill_chunk})
        # decode-loop verdict: how many tokens one dispatch advances.
        # K is part of the compile key, so it is fixed per manager (and
        # bucketed inside the policy) — request churn never mints a new
        # program. "stepwise" is simply K=1 through the same window
        # program: one code path, on-device sampling everywhere.
        loop = decode_loop_policy(
            k=fused_k,
            capable=hasattr(base.net, "session_decode_window"))
        if loop.kind == "stepwise" and \
                not hasattr(base.net, "session_decode_window"):
            raise TypeError(
                f"decode sessions need session_decode_window "
                f"(MultiLayerNetwork); got {type(base.net).__name__}")
        self.loop_kind = loop.kind
        self.fused_k = int(loop.k)
        self._loop_reason = loop.reason
        self._lock = threading.Lock()
        self._net = base.net
        self._sessions: Dict[str, DecodeSession] = {}
        self._sid = itertools.count(1)
        self._seed_rng = np.random.default_rng()
        self._closed = False

        first, vocab = _resolve_net(base.net)
        self.vocab = int(vocab)
        self._encoding = _input_encoding(first)
        self._limit = base.net.decode_limit()

        # kv-dtype verdict: storage dtype for every pool this manager
        # owns — target and draft slots quantize together, mixed-dtype
        # pools would double the compiled-program set for no benefit
        kvp = kv_dtype_policy(kv_dtype)
        self.kv_dtype = kvp.kind
        self._kv_reason = kvp.reason

        # speculative-decode verdict: needs a draft that exists, shares
        # the target's vocabulary (acceptance compares the two nets'
        # distributions token for token) and can REWIND — as must the
        # target, since rejected proposals are un-written by snapping
        # per-slot positions back (recurrent carries and rolling rings
        # hold state that cannot be un-written, so either disqualifies)
        self.draft_net = draft_net
        spec_capable = False
        if draft_net is not None and \
                hasattr(draft_net, "session_propose_window"):
            _, dv = _resolve_net(draft_net)
            spec_capable = (
                int(dv) == self.vocab
                and getattr(base.net, "spec_decode_capable",
                            lambda: False)()
                and draft_net.spec_decode_capable())
        spec = spec_decode_policy(spec_k, capable=spec_capable)
        self.spec_enabled = spec.kind == "spec"
        self.spec_k = int(spec.k)
        self._spec_reason = spec.reason
        self.draft_name = f"{model}@draft" if self.spec_enabled else None

        # prefix-cache verdict: paged KV + radix prefix reuse. Needs a
        # net whose attention caches can be paged (non-rolling, uniform
        # max_cache — prefix_cache_capable) and NO active draft: the
        # draft's lockstep pool prefills every prompt token into its own
        # cache, so skipping the target's prefill would desync the pair
        mc = None
        for layer in getattr(base.net, "layers", ()):
            if hasattr(layer, "decode_carry") and \
                    hasattr(layer, "max_cache"):
                mc = int(layer.max_cache)
                break
        pcap = (mc is not None
                and getattr(base.net, "prefix_cache_capable",
                            lambda: False)()
                and not self.spec_enabled)
        ppol = prefix_cache_policy(page_len, max_cache=mc, capable=pcap)
        self.prefix_enabled = ppol.kind == "paged"
        self.page_len = int(ppol.page_len)
        self._prefix_reason = ppol.reason

        from deeplearning4j_tpu.observe import get_registry
        if metrics is None:
            metrics = get_registry()
        self.metrics = metrics
        # the policy consults above counted on the process-global
        # registry (record_dispatch); mirror onto the server's registry
        # when it is a private one so /metrics surfaces the decode_loop,
        # spec_decode and kv_dtype verdicts too
        if metrics is not get_registry():
            metrics.counter("kernel_dispatch_total", op="decode_loop",
                            impl=self.loop_kind).inc()
            metrics.counter("kernel_dispatch_total", op="spec_decode",
                            impl="spec" if self.spec_enabled
                            else "plain").inc()
            metrics.counter("kernel_dispatch_total", op="kv_dtype",
                            impl=self.kv_dtype).inc()
            metrics.counter("kernel_dispatch_total", op="prefix_cache",
                            impl="paged" if self.prefix_enabled
                            else "off").inc()
        self.pool = KVSlotPool(
            base.net, slots, model=model, metrics=metrics,
            kv_dtype=self.kv_dtype,
            page_len=self.page_len if self.prefix_enabled else None)
        self.prefix_cache = (PrefixCache(self.pool, metrics=metrics)
                             if self.prefix_enabled else None)
        # radix deploy generation (guarded by the pool lock): bumped at
        # every hot-swap flip alongside flush(). A session stamped with
        # an older generation prefilled under the OLD weights — its KV
        # must never be re-indexed after the flip, or new sessions would
        # match stale-weight pages and decode wrong logits silently.
        self._prefix_gen = 0
        # the draft rides a lockstep slot pool: slot i of the draft pool
        # always belongs to the session holding slot i of the target
        # pool, so no independent alloc/free bookkeeping — _finish just
        # zeroes the row for the next tenant
        self.draft_pool = None
        if self.spec_enabled:
            self.draft_pool = KVSlotPool(
                draft_net, slots, model=self.draft_name,
                metrics=metrics, kv_dtype=self.kv_dtype)
        self._g_active = metrics.gauge("serving_sessions_active",
                                       model=model)
        self._c_opened = metrics.counter("serving_sessions_total",
                                         model=model, outcome="opened")
        self._c_out = {o: metrics.counter("serving_sessions_total",
                                          model=model, outcome=o)
                       for o in _OUTCOMES}
        self._c_tokens = metrics.counter("serving_decode_tokens_total",
                                         model=model)
        self._h_ttft = metrics.histogram("serving_ttft_ms", model=model)
        self._h_itl = metrics.histogram("serving_itl_ms", model=model)
        self._c_disp = metrics.counter("serving_decode_dispatches_total",
                                       model=model)
        self._c_rows = metrics.counter(
            "serving_decode_dispatch_rows_total", model=model)
        self._c_shared = metrics.counter(
            "serving_decode_shared_dispatches_total", model=model)
        # fused-window accounting: windows run and tokens they emitted —
        # dispatches/tokens is the round-trips-per-token the bench trends
        self._c_windows = metrics.counter(
            "serving_decode_windows_total", model=model)
        self._c_window_tokens = metrics.counter(
            "serving_decode_window_tokens_total", model=model)
        # spec accounting: the counter PAIR makes the acceptance rate
        # derivable from /metrics alone (accepted / draft), and the
        # per-lane-window histogram gives its distribution
        self._c_draft_toks = metrics.counter("draft_tokens_total",
                                             model=model)
        self._c_accepted = metrics.counter("accepted_tokens_total",
                                           model=model)
        self._h_accept = metrics.histogram(
            "serving_spec_acceptance_rate", model=model)
        # commsmon reshard witness — None when DL4J_TPU_COMMSMON is off,
        # so the disabled dispatch path pays one attribute read
        from deeplearning4j_tpu.observe.commsmon import get_reshard_witness
        self._reshard = get_reshard_witness()

        # the decode endpoint: an ordinary registry entry whose "runner"
        # is this manager — scheduler dispatch, drain-on-retire and
        # registry.close() all work unchanged
        self.entry = registry.register_entry(
            self.decode_name,
            ModelEntry(self.decode_name, getattr(base, "version", None),
                       base.net, runner=self))
        # the draft is a first-class registry citizen (PR 7 seam): it
        # shows up in describe(), and registry.close() reaches this
        # manager through its runner (shutdown is idempotent)
        if self.spec_enabled:
            registry.register_entry(
                self.draft_name,
                ModelEntry(self.draft_name, getattr(base, "version", None),
                           draft_net, runner=self))
        registry.add_deploy_hook(model, self._deploy_hook)
        # kernel-policy verdict cached once (and refreshed on hot-swap):
        # session-step spans stamp it per ITL step, and re-deriving it
        # per dispatch would price policy evaluation into the hot path
        self._policy_kind = self._policy_brief()
        if warm:
            self.warmup()

    # ------------------------------------------------------------ warmup
    def _feat_dim(self) -> int:
        return 1 if self._encoding == "ids" else self.vocab

    def _session_carries(self, net):
        """Build a carry tree shaped exactly like the pool's (paged
        geometry included) — warmup and swap-compat checks must compile
        and compare the same programs the live tree will run."""
        if self.prefix_enabled:
            return net.session_carries(self.pool.slots,
                                       kv_dtype=self.kv_dtype,
                                       page_len=self.pool.page_len,
                                       pages=self.pool.pages)
        return net.session_carries(self.pool.slots,
                                   kv_dtype=self.kv_dtype)

    def _compile_buckets(self, net) -> None:
        """Run one all-lanes-inactive step per prefill bucket plus one
        all-lanes-inactive window program (plain fused window, or the
        propose+verify pair when speculating) so every dispatch shape
        this manager will ever use is compiled before traffic (the
        zero-recompiles-after-warmup contract the bench asserts). On a
        hot-swap warm phase `net` is the TARGET candidate; the draft is
        not part of the deploy, so its already-compiled programs feed
        the candidate's verify warmup."""
        carries = self._session_carries(net)
        S, F = self.pool.slots, self._feat_dim()
        act = np.zeros((S,), bool)
        knobs = dict(temperature=np.ones((S,), np.float32),
                     top_k=np.full((S,), self.vocab, np.int32),
                     top_p=np.ones((S,), np.float32),
                     greedy=np.ones((S,), bool),
                     keys=np.zeros((S, 2), np.uint32),
                     offsets=np.zeros((S,), np.int32))
        for b in self.buckets:
            x = np.zeros((S, b, F), np.float32)
            val = np.zeros((S, b), np.float32)
            out, _ = net.session_step(x, carries, active=act, valid=val)
            # materialize: compile time must land in warmup, not on the
            # first live dispatch
            # graft: allow-sync(warmup barrier — pre-traffic by design)
            np.asarray(out)
        if self.spec_enabled:
            # graft: allow(GL701): warmup runs at construction/deploy
            # time, before the draft pool is shared with request
            # threads; steady-state readers take the pool lock
            draft = self.draft_pool.net
            dcar = draft.session_carries(S, kv_dtype=self.kv_dtype)
            for b in self.buckets:
                x = np.zeros((S, b, F), np.float32)
                val = np.zeros((S, b), np.float32)
                out, _ = draft.session_step(x, dcar, active=act,
                                            valid=val)
                # graft: allow-sync(warmup barrier — pre-traffic)
                np.asarray(out)
            d_toks, d_probs, _ = draft.session_propose_window(
                np.zeros((S,), np.int64), dcar, active=act,
                k=self.spec_k, rewind=np.zeros((S,), np.int32),
                pre_tokens=np.zeros((S,), np.int32),
                pre_valid=np.zeros((S,), bool), **knobs)
            packed, _ = net.session_verify_window(
                np.zeros((S,), np.int64), carries, active=act,
                k=self.spec_k, draft_tokens=d_toks, draft_probs=d_probs,
                budgets=np.zeros((S,), np.int32),
                eos_ids=np.full((S,), -1, np.int32), **knobs)
            # graft: allow-sync(warmup barrier — pre-traffic by design)
            np.asarray(packed)
        else:
            toks, _, _ = net.session_decode_window(
                np.zeros((S,), np.int64), carries, active=act,
                k=self.fused_k, budgets=np.zeros((S,), np.int32),
                eos_ids=np.full((S,), -1, np.int32), **knobs)
            # graft: allow-sync(warmup barrier — pre-traffic by design)
            np.asarray(toks)

    def warmup(self) -> None:
        # graft: allow(GL701): warmup runs at construction/deploy time,
        # before the pool is shared with request threads; steady-state
        # readers take the pool lock in run_batch
        self._compile_buckets(self.pool.net)

    # ---------------------------------------------------------- sessions
    def open_session(self, prompt_ids, *, max_tokens: int = 16,
                     temperature: float = 1.0,
                     top_k: Optional[int] = None,
                     top_p: Optional[float] = None,
                     greedy: bool = False, seed: Optional[int] = None,
                     deadline_ms: Optional[float] = None,
                     eos_id: Optional[int] = None,
                     alloc_timeout_s: float = 0.0,
                     trace=None,
                     prefill_only: bool = False) -> DecodeSession:
        """Admit one generation: claim a slot (SlotPoolExhaustedError →
        503 upstream), validate the token budget against the net's
        decode limit, and kick off the prefill→decode callback chain.
        Returns immediately; consume via `stream()`/`result()`."""
        prompt = np.asarray(prompt_ids, dtype=np.int64).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt_ids must contain at least one token")
        if prompt.min() < 0 or prompt.max() >= self.vocab:
            raise ValueError(
                f"prompt token ids must be in [0, {self.vocab})")
        if max_tokens < 1:
            raise ValueError("max_tokens must be >= 1")
        params = SamplingParams(temperature=temperature, top_k=top_k,
                                top_p=top_p, greedy=greedy)
        # a speculative verify transiently writes spec_k + 1 entries
        # past the confirmed position before the cut snaps it back; the
        # cache must leave that headroom or the last window's scatter
        # would silently drop rows
        head = (self.spec_k + 1) if self.spec_enabled else 0
        if self._limit is not None and \
                int(prompt.size) + int(max_tokens) + head > self._limit:
            raise ValueError(
                f"prompt ({prompt.size}) + max_tokens ({max_tokens})"
                f"{f' + spec headroom ({head})' if head else ''} "
                f"exceeds the decode budget of {self._limit} for this "
                f"net (non-rolling cache)")
        with self._lock:
            if self._closed:
                raise SchedulerClosedError("session manager is shut down")
            if seed is None:
                # unseeded requests still get independent device streams
                seed = int(self._seed_rng.integers(0, 2 ** 63))
        slot = self.pool.alloc(alloc_timeout_s)
        cached_len, pages, gen = 0, [], 0
        if self.prefix_enabled:
            try:
                with self.pool.lock():
                    # graft: allow(GL301): guarded by the pool lock just
                    # above — _prefix_gen shares the pool's Condition
                    gen = self._prefix_gen
                    cached_len, pages = self._admit_pages(
                        slot, prompt, int(max_tokens), head)
            except BaseException:
                self.pool.free(slot)
                raise
        sess = DecodeSession(
            f"s{next(self._sid):06d}", slot, prompt,
            max_tokens=max_tokens, params=params, seed=seed,
            deadline_ms=deadline_ms, eos_id=eos_id, trace=trace)
        sess._pages = pages
        sess._cached_len = cached_len
        sess._gen = gen
        sess._prefill_only = bool(prefill_only)
        # prefill resumes AFTER the cached prefix: a fully warm stem
        # goes straight to the decode window (TTFT ~ one window)
        sess._off = cached_len
        with self._lock:
            self._sessions[sess.id] = sess
            n_active = len(self._sessions)
        self._c_opened.inc()
        self._g_active.set(n_active)
        try:
            from deeplearning4j_tpu.observe import get_flight
            get_flight().record("session_open", model=self.model,
                                session=sess.id, slot=slot,
                                prompt_len=int(prompt.size),
                                max_tokens=int(max_tokens))
        # graft: allow(GL403): breadcrumbs are best-effort
        except Exception:
            pass
        self._submit_next(sess)
        return sess

    def open_prefill(self, prompt_ids, *,
                     deadline_ms: Optional[float] = None,
                     alloc_timeout_s: float = 0.0,
                     trace=None) -> DecodeSession:
        """Admit a prefill-ONLY session (fleet prefill role): run the
        prompt stem through chunked prefill, offer the resulting pages
        to the radix index, and finish with zero generated tokens. The
        warm stem is then exportable via the fleet handoff path. Needs
        the prefix cache — without an index the prefilled pages would
        be unreachable the moment the slot frees."""
        if not self.prefix_enabled:
            raise ValueError(
                "prefill-only sessions require a paged pool with the "
                "prefix cache enabled (page_len=...)")
        return self.open_session(
            prompt_ids, max_tokens=1, greedy=True,
            deadline_ms=deadline_ms, alloc_timeout_s=alloc_timeout_s,
            trace=trace, prefill_only=True)

    def get_session(self, sid: str) -> Optional[DecodeSession]:
        with self._lock:
            return self._sessions.get(sid)

    def cancel(self, sid: str) -> bool:
        sess = self.get_session(sid)
        if sess is None:
            return False
        sess.cancel()
        return True

    # ----------------------------------------------- paged admission
    def _admit_pages(self, slot: int, prompt: np.ndarray,
                     max_tokens: int, head: int):
        """All page bookkeeping for one session happens HERE, under the
        pool lock, at admission: match the prompt stem against the radix
        index, adopt the shared full pages by reference, fork (copy) at
        most ONE partially-matched page, allocate fresh pages for the
        rest of the token budget, and install the slot's page table +
        position in one jitted program. Steady-state windows then never
        touch host page state — page indices are traced scalars inside
        the compiled step, zero extra syncs and zero recompiles. Returns
        `(cached_len, page_chain)`. Caller holds the pool lock."""
        Lp = self.pool.page_len
        stem = int(prompt.size) - 1
        cl, shared, partial = self.prefix_cache.match(prompt[:stem])
        # pin every matched page BEFORE the eviction pass below can run:
        # match() leaves a cache-only chain at refcount 1, which the LRU
        # sweep would be free to reclaim out from under this very
        # admission. Refcount 2 (cache + us) makes the matched pages
        # unevictable by construction. The shared-page pins double as
        # the session's own references; the partial source's pin is
        # transient — it only has to survive until the CoW copy.
        pinned = list(shared)
        if partial is not None:
            pinned.append(partial[0])
        for p in pinned:
            self.pool.page_ref_locked(p)
        fresh = []
        try:
            total = int(prompt.size) + max_tokens + head
            need = -(-total // Lp)      # ceil: whole session footprint
            n_fresh = need - len(shared)
            short = n_fresh - self.pool.pages_free_locked()
            if short > 0:
                # LRU-evict cold cache-only chains; live (and pinned)
                # pages untouchable
                self.prefix_cache.evict(short)
            if self.pool.pages_free_locked() < n_fresh:
                raise SlotPoolExhaustedError(
                    f"need {n_fresh} KV pages, "
                    f"{self.pool.pages_free_locked()} free after "
                    f"eviction")
            fresh = self.pool.page_alloc_locked(n_fresh)
            chain = list(shared) + fresh
            if partial is not None:
                # the one copy-on-write fork of an admission: the match
                # ends mid-page, so the follower takes a private copy
                # and prefill resumes inside it at the divergence offset
                src, _ = partial
                self.pool.copy_page_locked(src, chain[len(shared)])
                self.prefix_cache.note_cow_fork()
            self.pool.install_pages_locked(slot, chain, cl)
        except BaseException:
            # no page escapes a failed admission: drop the fresh pages
            # and every pin taken above
            for p in fresh:
                self.pool.page_unref_locked(p)
            for p in pinned:
                self.pool.page_unref_locked(p)
            raise
        if partial is not None:
            # copy done — the partial source goes back to cache-only
            # (the session keeps the private copy, not the source)
            self.pool.page_unref_locked(partial[0])
        return cl, chain

    def _insert_prefix(self, sess: DecodeSession) -> None:
        """Offer a freshly completed prefill to the radix index (called
        once, at the session's phase-0 -> phase-1 transition, when every
        prefill future has resolved). Best-effort: indexing is a perf
        optimization and must never take down the session chain."""
        stem = sess.prompt.size - 1
        if stem <= 0 or not sess._pages:
            return
        try:
            with self.pool.lock():
                if sess._gen != self._prefix_gen:
                    # a hot-swap flipped between this session's
                    # admission and its first decode row: its pages
                    # hold OLD-weight KV. flush() already dropped that
                    # generation's chains — re-indexing them here would
                    # hand stale KV to new-weight matches.
                    return
                # graft: allow(GL301): guarded by the pool lock just
                # above — the radix index shares the pool's Condition
                self.prefix_cache.insert(sess.prompt[:stem], sess._pages)
        # graft: allow(GL403): cache indexing is best-effort
        except Exception:
            logger.exception("prefix-cache insert failed (session %s)",
                             sess.id)

    # --------------------------------------------------- stepping chain
    def _next_row(self, sess: DecodeSession) -> np.ndarray:
        """The session's next request row, fixed width [1, 3 + chunk]:
        [slot, phase, n_valid, tok_0..]. Phase 0 rows carry up to
        `chunk` prompt-STEM tokens (`prompt[:-1]` — their logits are
        never read back); the phase 1 row carries the window's first
        input token: the last prompt token before anything is sampled,
        the previous window's last sample afterwards. The fused window
        derives everything else (sampling knobs, rng key, budget, EOS)
        from the session table at dispatch time."""
        row = np.zeros((1, 3 + self.prefill_chunk), np.float32)
        row[0, 0] = sess.slot
        stem = sess.prompt.size - 1
        if sess._off < stem:
            toks = sess.prompt[sess._off:min(stem, sess._off +
                                             self.prefill_chunk)]
            sess._off += toks.size
        else:
            if self.prefix_enabled and not sess._prefix_inserted:
                # first decode row => the last prefill future resolved:
                # the stem's pages hold final KV, index them now
                sess._prefix_inserted = True
                self._insert_prefix(sess)
            row[0, 1] = 1.0
            toks = np.asarray([sess.generated[-1] if sess.generated
                               else sess.prompt[-1]], np.int64)
        row[0, 2] = toks.size
        row[0, 3:3 + toks.size] = toks
        return row

    def _submit_next(self, sess: DecodeSession) -> None:
        with self._lock:
            if sess._finished:
                return      # aborted (shutdown/cancel) — stop the chain
        rem = sess.remaining_ms()
        if rem is not None and rem <= 0:
            self._finish(sess, error=DeadlineExceededError(
                f"session {sess.id} deadline passed"))
            return
        if sess._prefill_only and sess._off >= sess.prompt.size - 1:
            # disaggregated prefill role: the stem is fully prefilled —
            # index the pages (a fleet handoff exports them from the
            # radix) and finish without ever entering a decode window
            if self.prefix_enabled and not sess._prefix_inserted:
                sess._prefix_inserted = True
                self._insert_prefix(sess)
            self._finish(sess, outcome="completed")
            return
        row = self._next_row(sess)
        try:
            # explicit trace: resubmits run on scheduler worker threads,
            # where the edge's contextvar carrier is not in scope
            fut = self.scheduler.submit(self.decode_name, row,
                                        deadline_ms=rem,
                                        trace=sess.trace)
        except BaseException as e:
            self._finish(sess, error=e)
            return
        fut.add_done_callback(lambda f: self._on_step(sess, f))

    def _on_step(self, sess: DecodeSession, fut) -> None:
        """Future callback (runs on the scheduler worker): consume this
        round-trip's result, maybe finish, else chain the next row.
        Prefill legs return a zero count (their logits never left the
        device); window legs return the device-sampled tokens, so this
        callback only does bookkeeping — no host sampling. Every path
        must end in _finish or _submit_next — an escaped exception here
        would orphan the session's slot."""
        with self._lock:
            if sess._finished:
                return      # session was aborted while this step flew
        try:
            y = fut.result()
        except BaseException as e:
            self._finish(sess, error=e)
            return
        try:
            if sess.cancelled:
                self._finish(sess, outcome="cancelled")
                return
            n = int(np.asarray(y)[0, 0])
            if n <= 0:
                # mid-prefill (or a window whose lane was dropped):
                # nothing was sampled; keep the chain moving
                self._submit_next(sess)
                return
            toks = np.asarray(y)[0, 1:1 + n].astype(np.int64)
            now = time.monotonic()
            tid = sess.trace.trace_id if sess.trace is not None else None
            if sess.ttft_ms is None:
                sess.ttft_ms = (now - sess.opened_at) * 1000.0
                self._h_ttft.observe(sess.ttft_ms, exemplar=tid)
            else:
                # tokens arrive in a burst of n: the honest per-token
                # latency is the window gap amortized over the window
                gap_ms = (now - sess._last_tok_at) * 1000.0
                for _ in range(n):
                    self._h_itl.observe(gap_ms / n, exemplar=tid)
            sess._last_tok_at = now
            hit_eos, appended = False, 0
            for t in toks:
                tok = int(t)
                sess.generated.append(tok)
                appended += 1
                sess._events.put({"token": tok,
                                  "index": len(sess.generated) - 1})
                if sess.eos_id is not None and tok == sess.eos_id:
                    hit_eos = True
                    break   # the device stopped emitting after EOS too
            self._c_tokens.inc(appended)
            if hit_eos or len(sess.generated) >= sess.max_tokens:
                self._finish(sess, outcome="completed")
            else:
                self._submit_next(sess)
        except BaseException as e:
            self._finish(sess, error=e)

    def _finish(self, sess: DecodeSession, *, outcome: Optional[str] = None,
                error: Optional[BaseException] = None) -> None:
        with self._lock:
            if sess._finished:
                return
            sess._finished = True
            self._sessions.pop(sess.id, None)
            n_active = len(self._sessions)
        if error is not None:
            outcome = ("expired" if isinstance(error, DeadlineExceededError)
                       else "failed")
        sess.outcome = outcome
        sess.error = error
        if sess.trace is not None:
            reqtrace.record_span(
                sess.trace.trace_id, "session.close",
                parent_id=sess.trace.span_id, session=sess.id,
                slot=sess.slot, outcome=outcome,
                tokens=len(sess.generated),
                error=None if error is None else type(error).__name__)
        self.pool.free(sess.slot)
        if self.prefix_enabled and sess._pages:
            # release the session's page references — free() only wiped
            # the slot's table/pos rows. Pages the radix index adopted
            # survive (its own refcount keeps them); purely private
            # pages drop to zero and return to the free list.
            with self.pool.lock():
                for p in sess._pages:
                    self.pool.page_unref_locked(p)
            sess._pages = []
        if self.draft_pool is not None:
            # lockstep draft slot: zero the mirror row for the next
            # tenant (reset, not free — the draft pool's free list is
            # deliberately unused)
            self.draft_pool.reset(sess.slot)
        self._c_out[outcome].inc()
        self._g_active.set(n_active)
        try:
            from deeplearning4j_tpu.observe import get_flight
            get_flight().record(
                "session_close", model=self.model, session=sess.id,
                outcome=outcome, tokens=len(sess.generated),
                error=None if error is None else type(error).__name__)
        # graft: allow(GL403): breadcrumbs are best-effort
        except Exception:
            pass
        if error is not None:
            sess._events.put({"error": str(error), "outcome": outcome})
        else:
            sess._events.put({"done": True, "outcome": outcome,
                              "tokens": len(sess.generated)})
        sess.done.set()

    # ------------------------------------------------- scheduler runner
    def run_batch(self, xs) -> np.ndarray:
        """The decode endpoint's data plane. `xs` is a stack of session
        rows ([k, 3+chunk], possibly from k different sessions — this
        coalescing IS continuous batching, and prefill rows co-batch
        with decode windows). At most two jitted programs run under the
        pool lock: one `session_step` over the prefill lanes (logits
        stay on device — prefill pays NO host sync), then one
        `session_decode_window` advancing every decoding lane K tokens
        with on-device sampling. Returns one result row per request
        row: `[count, tok_0..tok_{K-1}]` — count 0 for prefill legs.

        Speculating, the window half becomes draft-propose + target-
        verify (plus a mirrored draft prefill), accept/reject stays on
        device, and the ONE host sync per window reads back the verify's
        packed [S, spec_k+4] rows — emit/accept counts, catch-up token
        and emitted tokens together, so speculation never adds a sync."""
        xs = np.asarray(xs)
        if xs.ndim != 2 or xs.shape[1] != 3 + self.prefill_chunk:
            raise ValueError(
                f"decode rows must be [k, {3 + self.prefill_chunk}], "
                f"got {xs.shape}")
        k = xs.shape[0]
        # fan-in handoff: the scheduler worker opened a dispatch window
        # iff at least one co-batched row belongs to a sampled trace —
        # None here keeps the sampled-off path allocation-free
        dtrace = reqtrace.active_dispatch()
        t0 = time.perf_counter() if dtrace is not None else 0.0
        slots_idx = xs[:, 0].astype(np.int64)
        phase = xs[:, 1].astype(np.int64)
        nvalid = xs[:, 2].astype(np.int64)
        pre = np.nonzero(phase == 0)[0]
        dec = np.nonzero(phase == 1)[0]
        S, K = self.pool.slots, self.fused_k
        # a spec window can emit up to spec_k accepted drafts plus the
        # correction/bonus token; plain windows top out at K
        W = (self.spec_k + 1) if self.spec_enabled else K
        ys = np.zeros((k, 1 + W), np.float32)

        # prefill scatter: [S, bucket] chunk step, inactive lanes masked
        bucket = 0
        if pre.size:
            need = int(nvalid[pre].max())
            bucket = min(b for b in self.buckets if b >= need)
            tok = np.zeros((S, bucket), np.int64)
            val = np.zeros((S, bucket), np.float32)
        act_p = np.zeros((S,), bool)
        for i in pre:
            s, n = int(slots_idx[i]), int(nvalid[i])
            tok[s, :n] = xs[i, 3:3 + n].astype(np.int64)
            val[s, :n] = 1.0
            act_p[s] = True

        # window lanes: per-lane sampling knobs / keys / budgets from
        # the session table. Reading session fields here is safe — each
        # session has exactly one row in flight (this one), so nothing
        # mutates them concurrently.
        act_d = np.zeros((S,), bool)
        by_slot: Dict[int, DecodeSession] = {}
        if dec.size:
            with self._lock:
                by_slot = {s.slot: s for s in self._sessions.values()}
            tok0 = np.zeros((S,), np.int64)
            lane_params: List[Optional[SamplingParams]] = [None] * S
            keys = np.zeros((S, 2), np.uint32)
            offs = np.zeros((S,), np.int32)
            buds = np.zeros((S,), np.int32)
            eos = np.full((S,), -1, np.int32)
            rew = np.zeros((S,), np.int32)
            ptk = np.zeros((S,), np.int32)
            pvl = np.zeros((S,), bool)
            for i in dec:
                s = int(slots_idx[i])
                sess = by_slot.get(s)
                if sess is None:
                    continue    # finished while the row was queued
                act_d[s] = True
                tok0[s] = int(xs[i, 3])
                lane_params[s] = sess.params
                keys[s] = sess.base_key
                offs[s] = len(sess.generated)
                buds[s] = sess.max_tokens - len(sess.generated)
                if sess.eos_id is not None:
                    eos[s] = sess.eos_id
                if self.spec_enabled:
                    rew[s] = sess._spec_rewind
                    ptk[s] = sess._spec_pre_tok
                    pvl[s] = sess._spec_pre_valid
            temps, tks, tps, grd = lane_param_arrays(lane_params,
                                                     self.vocab)

        toks_d = None
        packed_d = None
        with self.pool.lock():
            # drop rows whose slot was freed while the row was queued
            # (session aborted mid-flight): stepping a freed slot would
            # dirty carries the pool just reset for the next tenant.
            # Reading _active is safe here — we hold the pool lock.
            for i in range(k):
                s = int(slots_idx[i])
                if not self.pool._active[s]:
                    act_p[s] = False
                    act_d[s] = False
            net = self.pool.net
            carries = self.pool.carries
            if self._reshard is not None:
                self._witness_carries(net, carries)
            if pre.size and act_p.any():
                x = _encode(tok, self._encoding, self.vocab)
                _, carries = net.session_step(
                    x, carries, active=act_p, valid=val)
            if self.spec_enabled:
                # fixed lock order, target pool THEN draft pool — every
                # acquirer nests the draft inside the target, so the
                # pair can never deadlock (graft-lint lock-order pass)
                with self.draft_pool.lock():
                    dnet = self.draft_pool.net
                    dcarries = self.draft_pool.carries
                    if pre.size and act_p.any():
                        # mirrored prefill: the draft consumes the same
                        # prompt stem (logits stay on device here too)
                        _, dcarries = dnet.session_step(
                            x, dcarries, active=act_p, valid=val)
                    if dec.size and act_d.any():
                        d_toks, d_probs, dcarries = \
                            dnet.session_propose_window(
                                tok0, dcarries, active=act_d,
                                k=self.spec_k, temperature=temps,
                                top_k=tks, top_p=tps, greedy=grd,
                                keys=keys, offsets=offs, rewind=rew,
                                pre_tokens=ptk, pre_valid=pvl)
                        packed_d, carries = net.session_verify_window(
                            tok0, carries, active=act_d, k=self.spec_k,
                            draft_tokens=d_toks, draft_probs=d_probs,
                            temperature=temps, top_k=tks, top_p=tps,
                            greedy=grd, keys=keys, offsets=offs,
                            budgets=buds, eos_ids=eos)
                    self.draft_pool.swap_carries(dcarries)
            elif dec.size and act_d.any():
                toks_d, emits_d, carries = net.session_decode_window(
                    tok0, carries, active=act_d, k=K,
                    temperature=temps, top_k=tks, top_p=tps, greedy=grd,
                    keys=keys, offsets=offs, budgets=buds, eos_ids=eos)
            self.pool.swap_carries(carries)
        emit_n = {}
        acc_n = {}
        if packed_d is not None:
            # ONE host sync per speculative window, after both locks are
            # released: counts, the catch-up token and all emissions
            # ride the verify's packed rows — the draft adds NO sync.
            # graft: allow-sync(decode endpoint window readback — the
            # one intended host sync per K-token window)
            ph = np.asarray(packed_d)
            wtoks = wdraft = wacc = 0
            for i in dec:
                s = int(slots_idx[i])
                if not act_d[s]:
                    continue
                n = int(ph[s, 0])
                emit_n[s] = n
                # accepted drafts actually EMITTED this window: the
                # verify's acceptance count, clipped to the emit count —
                # a token-budget cut mid-window truncates an accepted
                # run, and acceptance accounting must follow the tokens
                # that left the device or /metrics' rate drifts
                acc = min(int(ph[s, 1]), n)
                acc_n[s] = acc
                ys[i, 0] = n
                ys[i, 1:1 + n] = ph[s, 3:3 + n]
                sess = by_slot.get(s)
                if sess is not None:
                    # next window's draft entry bookkeeping (safe: this
                    # was the session's one in-flight row)
                    sess._spec_rewind = max(self.spec_k - n, 0)
                    sess._spec_pre_valid = bool(n == self.spec_k + 1)
                    sess._spec_pre_tok = int(ph[s, 2])
                wtoks += n
                wdraft += self.spec_k
                wacc += acc
                self._h_accept.observe(acc / self.spec_k)
            self._c_windows.inc()
            self._c_window_tokens.inc(wtoks)
            self._c_draft_toks.inc(wdraft)
            self._c_accepted.inc(wacc)
        if toks_d is not None:
            # device->host sync AFTER releasing the pool lock: the next
            # dispatch can enqueue its programs while we read this one
            # back. Prefill legs never reach this — the fused window's
            # sampled tokens are the ONE intended host sync, and it
            # covers K tokens per lane.
            # graft: allow-sync(decode endpoint window readback — the
            # one intended host sync per K-token window)
            toks_h = np.asarray(toks_d)
            emits_h = np.asarray(emits_d)
            wtoks = 0
            for i in dec:
                s = int(slots_idx[i])
                if not act_d[s]:
                    continue
                n = int(emits_h[s].sum())
                emit_n[s] = n
                ys[i, 0] = n
                ys[i, 1:1 + K] = toks_h[s]
                wtoks += n
            self._c_windows.inc()
            self._c_window_tokens.inc(wtoks)
        self._c_disp.inc()
        self._c_rows.inc(k)
        if k >= 2:
            self._c_shared.inc()
        if dtrace is not None:
            self._trace_windows(dtrace, slots_idx, phase, nvalid, emit_n,
                                acc_n, bucket, k,
                                (time.perf_counter() - t0) * 1e3)
        return ys

    def _witness_carries(self, net, carries) -> None:
        """Reshard-witness seam (commsmon, GL802) for the decode
        dispatch: until the model axis ships (ROADMAP item 1), session
        carries are REPLICATED by contract — a committed non-replicated
        sharding on any carry leaf is exactly where GSPMD would insert a
        per-window reshard collective. No active mesh context means
        single-device semantics: nothing to check, zero cost."""
        from deeplearning4j_tpu.observe.commsmon import check_dispatch_args
        from deeplearning4j_tpu.parallel.mesh import current_mesh_context
        if current_mesh_context() is None:
            return
        check_dispatch_args(f"{type(net).__name__}.decode",
                            {"carries": (carries, ())},
                            witness=self._reshard)

    def _comm_totals(self) -> Optional[dict]:
        """Owner-level compiled-collective totals for the serving net's
        active jit cache (None when the ledger has priced nothing)."""
        try:
            from deeplearning4j_tpu.observe.watchdog import get_watchdog
            with self.pool.lock():
                net = self.pool.net
            tag = getattr(net._jit_cache, "owner_tag", None)
            if tag is None:
                return None
            return get_watchdog().owner_comm_totals(tag)
        # graft: allow(GL403): span decoration is best-effort by design
        except Exception:
            return None

    def _trace_windows(self, dtrace, slots_idx, phase, nvalid,
                       emit_n: dict, acc_n: dict, bucket: int, k: int,
                       dur_ms: float) -> None:
        """One `session.window` span per sampled row of this dispatch —
        the per-window leaf of the fan-in tree, parented on that trace's
        dispatch span. Decode spans carry per-token attrs (`tokens`
        emitted this window, the window length `win`, and the per-token
        `itl` exemplars land on the histogram from the callback);
        prefill spans carry the chunk size. Host scalars only (the span
        contract)."""
        with self._lock:
            by_slot = {s.slot: s for s in self._sessions.values()
                       if s.trace is not None}
        # comm ledger totals for the serving net, once per dispatch:
        # every window span of this dispatch carries the same owner-level
        # collective figures (host metadata; {} keeps attrs uniform)
        comm = self._comm_totals() or {}
        for i in range(slots_idx.shape[0]):
            s = int(slots_idx[i])
            sess = by_slot.get(s)
            if sess is None:
                continue
            sid = dtrace.span_ids.get(sess.trace.trace_id)
            if sid is None:
                continue        # co-batched with a different endpoint
            decode = int(phase[i]) == 1
            # one row is in flight per session, so `generated` still
            # reflects the state the row was built from: prefill chunks
            # all precede the first sampled token
            reqtrace.record_span(
                sess.trace.trace_id, "session.window", parent_id=sid,
                dur_ms=dur_ms, session=sess.id, slot=sess.slot,
                phase="decode" if decode else "prefill",
                step=len(sess.generated),
                win=int((self.spec_k if self.spec_enabled
                         else self.fused_k) if decode else nvalid[i]),
                tokens=int(emit_n.get(s, 0)), bucket=bucket, rows=k,
                spec=bool(self.spec_enabled and decode),
                accepted=int(acc_n.get(s, 0)),
                prefix_cache=int(sess._cached_len),
                comm_ops=int(comm.get("ops", 0)),
                comm_bytes=int(comm.get("wire_bytes", 0)),
                # graft: allow(GL701): span attribute reads one atomic
                # str reference; a concurrent hot-swap may label one
                # window with the outgoing kernel kind — harmless
                kernel=self._policy_kind, loop=self.loop_kind)

    # --------------------------------------------------------- hot-swap
    def _deploy_hook(self, phase: str, name: str, version, net) -> None:
        if phase == "warm":
            # canary: live sessions must be hostable on the candidate
            # (raises IncompatibleSessionSwapError → deploy rolls back,
            # sessions keep serving the current version), and its step
            # buckets compile NOW so the flip costs zero recompiles
            want = self._check_swap_compat(net)
            del want
            self._compile_buckets(net)
            return
        if phase == "flipped":
            self.pool.rebind(net)
            if self.prefix_enabled:
                # old-weight KV is meaningless to NEW sessions under the
                # new weights: flush every cached chain. Live sessions
                # keep their own page references and finish coherently
                # on the pages they hold (the migration contract).
                with self.pool.lock():
                    # graft: allow(GL301): guarded by the pool lock
                    # just above — _prefix_gen shares the Condition.
                    # Bump first so in-flight old-generation sessions
                    # can never re-index the chains flush() drops.
                    self._prefix_gen += 1
                    self.prefix_cache.flush()
            with self._lock:
                self._net = net
                n = len(self._sessions)
            self.entry.net = net
            self.entry.version = version
            kind = self._policy_brief()     # takes _lock; compute first
            with self._lock:
                self._policy_kind = kind
            try:
                from deeplearning4j_tpu.observe import get_flight
                get_flight().record("decode_sessions_migrated",
                                    model=name, version=version,
                                    live_sessions=n)
            # graft: allow(GL403): breadcrumbs are best-effort
            except Exception:
                pass
            logger.info("decode sessions migrated to %s@%r (%d live)",
                        name, version, n)

    def _check_swap_compat(self, net):
        import jax
        if self.spec_enabled and not (
                hasattr(net, "spec_decode_capable")
                and net.spec_decode_capable()):
            raise IncompatibleSessionSwapError(
                f"deploy candidate for {self.model!r} cannot rewind its "
                f"decode caches (recurrent carries or rolling rings) — "
                f"this manager speculates; rolling back")
        if self.prefix_enabled and not (
                hasattr(net, "prefix_cache_capable")
                and net.prefix_cache_capable()):
            raise IncompatibleSessionSwapError(
                f"deploy candidate for {self.model!r} cannot page its "
                f"KV caches — this manager runs the prefix cache; "
                f"rolling back")
        want = jax.eval_shape(lambda: self._session_carries(net))
        have = jax.eval_shape(lambda: self.pool.carries)
        if jax.tree_util.tree_structure(want) != \
                jax.tree_util.tree_structure(have) or \
                [(l.shape, str(l.dtype))
                 for l in jax.tree_util.tree_leaves(want)] != \
                [(l.shape, str(l.dtype))
                 for l in jax.tree_util.tree_leaves(have)]:
            raise IncompatibleSessionSwapError(
                f"deploy candidate for {self.model!r} cannot host the "
                f"live session carries; rolling back")
        return want

    # -------------------------------------------------------- lifecycle
    def snapshot(self) -> dict:
        with self._lock:
            active = len(self._sessions)
        disp = int(self._c_disp.value)
        return {
            "model": self.model,
            "endpoint": self.decode_name,
            "sessions": {
                "active": active,
                "opened": int(self._c_opened.value),
                **{o: int(self._c_out[o].value) for o in _OUTCOMES},
            },
            "slots": self.pool.describe(),
            "tokens_streamed": int(self._c_tokens.value),
            "ttft_ms": self._h_ttft.percentiles(),
            "itl_ms": self._h_itl.percentiles(),
            "dispatches": {"total": disp,
                           "rows": int(self._c_rows.value),
                           "shared": int(self._c_shared.value),
                           "windows": int(self._c_windows.value),
                           "window_tokens":
                               int(self._c_window_tokens.value)},
            "buckets": list(self.buckets),
            "kernel_policy": self._kernel_policy(),
            "decode_loop": {"kind": self.loop_kind, "k": self.fused_k,
                            "reason": self._loop_reason},
            "spec_decode": {
                "enabled": self.spec_enabled, "k": self.spec_k,
                "reason": self._spec_reason, "draft": self.draft_name,
                "draft_tokens": int(self._c_draft_toks.value),
                "accepted_tokens": int(self._c_accepted.value),
                "acceptance_rate": (
                    round(int(self._c_accepted.value)
                          / int(self._c_draft_toks.value), 4)
                    if int(self._c_draft_toks.value) else None),
            },
            "kv_dtype": {"kind": self.kv_dtype,
                         "reason": self._kv_reason},
            "prefix_cache": self._prefix_snapshot(),
        }

    def _prefix_snapshot(self) -> dict:
        out = {"enabled": self.prefix_enabled,
               "page_len": self.page_len if self.prefix_enabled else 0,
               "reason": self._prefix_reason}
        if self.prefix_cache is not None:
            with self.pool.lock():
                out.update(self.prefix_cache.stats())
                out["pages"] = self.pool.pages
                out["pages_free"] = self.pool.pages_free_locked()
        return out

    def _policy_brief(self) -> str:
        """Compact kernel-policy verdict for span attributes: the sorted
        set of dispatch kinds across cached-attention layers."""
        kinds = sorted({p.get("kind") for p in self._kernel_policy()
                        if p.get("kind")})
        return ",".join(kinds) if kinds else "n/a"

    def _kernel_policy(self) -> list:
        """Which decode-attention kernel each cached-attention layer
        shape would dispatch to (kernel_defaults.decode_attention_policy
        — same call the layer makes per step), so snapshots show WHERE
        single-token steps run without reverse-engineering env
        hatches. Best-effort: policy evaluation must never take down
        /metrics."""
        try:
            from deeplearning4j_tpu.ops.kernel_defaults import (
                decode_attention_policy,
            )

            with self._lock:
                net = self._net
            seen, out = set(), []
            for layer in getattr(net, "layers", ()):
                heads = getattr(layer, "num_heads", None)
                if heads is None or not hasattr(layer, "decode_carry"):
                    continue
                # TransformerEncoderBlock carries num_kv_heads directly;
                # MultiHeadAttention resolves it via the _kv_heads prop
                hkv = getattr(layer, "_kv_heads", None) or getattr(
                    layer, "num_kv_heads", None) or heads
                key = (layer.max_cache, heads, hkv)
                if key in seen:
                    continue
                seen.add(key)
                pol = decode_attention_policy(*key, record=False)
                out.append({"layer": layer.name, "cache_len": key[0],
                            "heads": key[1], "kv_heads": key[2],
                            "kind": pol.kind, "reason": pol.reason})
            return out
        # graft: allow(GL403): snapshot decoration is best-effort
        except Exception:
            return []

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Wait for every live session to finish (no new admissions are
        blocked — callers close admission first if they need that)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            with self._lock:
                live = list(self._sessions.values())
            if not live:
                return True
            if deadline is not None and time.monotonic() >= deadline:
                return False
            live[0].done.wait(0.05)

    def shutdown(self) -> None:
        """Abort every live session (clients get a terminal error event)
        and detach from the registry. Called by registry.close() through
        the entry's runner seam, or directly."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            live = list(self._sessions.values())
        for sess in live:
            self._finish(sess, error=SchedulerClosedError(
                "decode session manager shut down"))
        try:
            self.registry.remove_deploy_hook(self.model, self._deploy_hook)
        # graft: allow(GL403): registry may already be closing
        except Exception:
            pass
