"""Measured-data-driven kernel dispatch policy.

The framework hand-writes TPU kernels in two places (flash attention,
fused LSTM). Whether the hand-written kernel — and which tile
configuration of it — actually beats the XLA baseline is an empirical
question answered by `tools/kernel_bench.py` on real hardware, and the
answer has flipped more than once during development. This module makes
the dispatch *derive from the recorded measurements* instead of from
prose: `tools/update_kernel_defaults.py` regenerates the MEASURED table
below from `tools/kernel_bench_results.json`, and a suite guard
(`tests/test_kernel_defaults.py`) fails if a shipped default contradicts
the best recorded row — a default can never again ship on prose.

This is the same "earn your dispatch with measurements" discipline the
reference applied to its vendor kernels (cuDNN helpers are picked over
built-ins only where they win — `deeplearning4j-cuda/.../
CudnnConvolutionHelper.java:54`), applied to Pallas-vs-XLA.

Policy, in order:
  1. Env escape hatches always win (ops run in production; a lowering
     bug or perf regression must be routable around without a release):
       DL4J_TPU_ATTN           = auto|flash|banded|dense
       DL4J_TPU_ATTN_BACKWARD  = auto|pallas|dense
       DL4J_TPU_ATTN_BLOCK     = "512" or "512x256"   (block_q x block_k)
       DL4J_TPU_DENSE_MAX_T    = int (memory-necessity threshold)
       DL4J_TPU_DECODE_ATTN    = auto|banded|dense   (serving decode step)
       DL4J_TPU_DECODE_LOOP    = auto|fused|stepwise (serving decode loop)
       DL4J_TPU_DECODE_K       = int (fused decode window length; bucketed)
       DL4J_TPU_SPEC_DECODE    = auto|on|off  (draft-model speculative decode)
       DL4J_TPU_DRAFT_K        = int (draft proposal window; bucketed)
       DL4J_TPU_KV_DTYPE       = auto|native|int8|fp8 (KV-cache storage)
       DL4J_TPU_PREFIX_CACHE   = auto|on|off  (paged KV prefix reuse)
       DL4J_TPU_KV_PAGE        = int (KV page length; snapped to divisors)
  2. Shape eligibility: flash needs the TPU backend and 128-lane-tileable
     sequence lengths; otherwise dense.
  3. Memory necessity: when Tq*Tk >= DENSE_MAX_T^2 (default 8192^2) the
     dense [Tq, Tk] score matrix is prohibitive regardless of speed (32
     heads of 8192^2 f32 scores = 8 GiB on a 16 GiB chip — and a
     Tq=4096 x Tk=16384 cross-attention is the same 8 GiB), so flash +
     the Pallas O(T) backward is mandatory.
  4. Otherwise the MEASURED verdict at the nearest benchmarked T decides,
     including the winning block sizes and backward implementation. With
     no winning measured row, the conservative default is the XLA dense
     path (it is the measured winner everywhere rows exist today).
"""

from __future__ import annotations

import os
from typing import NamedTuple, Optional

# --- BEGIN GENERATED (tools/update_kernel_defaults.py) ---
MEASURED: dict = {'attention': {'fwd': {1024: {'backward': 'n/a',
                              'block_k': 128,
                              'block_q': 128,
                              'dense_ms': 0.119,
                              'flash_ms': 0.629,
                              'winner': 'dense'},
                       2048: {'backward': 'n/a',
                              'block_k': 128,
                              'block_q': 128,
                              'dense_ms': 1.148,
                              'flash_ms': 2.302,
                              'winner': 'dense'},
                       4096: {'backward': 'n/a',
                              'block_k': 128,
                              'block_q': 128,
                              'dense_ms': 4.419,
                              'flash_ms': 11.742,
                              'winner': 'dense'}},
               'train': {1024: {'backward': 'dense',
                                'block_k': 128,
                                'block_q': 128,
                                'dense_ms': 0.475,
                                'flash_ms': 1.097,
                                'winner': 'dense'},
                         2048: {'backward': 'dense',
                                'block_k': 128,
                                'block_q': 128,
                                'dense_ms': 3.993,
                                'flash_ms': 5.953,
                                'winner': 'dense'},
                         4096: {'backward': 'dense',
                                'block_k': 128,
                                'block_q': 128,
                                'dense_ms': 14.989,
                                'flash_ms': 23.392,
                                'winner': 'dense'}}},
 'devices': ['TPU v5 lite0'],
 'lstm': {'train': {'fused_ms': 1.697,
                    'scan_ms': 3.991,
                    'winner': 'fused'}}}
# --- END GENERATED ---


class AttentionPolicy(NamedTuple):
    kind: str            # "flash" | "dense"
    block_q: int
    block_k: int
    backward: str        # "pallas" | "dense"
    reason: str          # why this choice (for logs/tests)


class BandedPolicy(NamedTuple):
    kind: str            # "banded" | "dense"
    block_q: int
    block_k: int
    reason: str


def record_dispatch(op: str, impl: str) -> None:
    """Count a dispatch-policy verdict on the shared metrics spine:
    `kernel_dispatch_total{op, impl}`. Policies are consulted at TRACE
    time, so each increment means "one compiled program serves `op` via
    `impl`" — a decode stack showing anything but one banded row per
    bucket, or a counter that keeps growing across steps (shape churn
    re-tracing), is diagnostic, not cosmetic. Visible in `/metrics` and
    in bench snapshots."""
    from deeplearning4j_tpu.observe import get_registry

    get_registry().counter("kernel_dispatch_total", op=op, impl=impl).inc()


def _env(name: str, default: str = "auto") -> str:
    v = os.environ.get(name, default).strip().lower()
    return v or default


def dense_max_t() -> int:
    """Sequence length at which the dense [T, T] path becomes a memory
    hazard and flash is used regardless of measured speed."""
    return int(os.environ.get("DL4J_TPU_DENSE_MAX_T", "8192"))


def _mem_hazard(tq: int, tk: int) -> bool:
    """The dense path materializes [Tq, Tk] scores per head, so the
    hazard scales with the PRODUCT: cross-attention over a long context
    (Tq=4096, Tk=16384) is exactly as dangerous as self-attention at
    sqrt(Tq*Tk). Threshold: product >= DENSE_MAX_T^2."""
    return tq * tk >= dense_max_t() ** 2


def _t_eff(tq: int, tk: int) -> int:
    """Effective length for measured-row lookup: the geometric mean, so
    a [Tq, Tk] problem maps to the self-attention T with the same score
    -matrix area (the measured rows are all self-attention)."""
    import math

    return max(128, int(round(math.sqrt(tq * tk))))


def _nearest_measured(table: dict, t: int) -> Optional[int]:
    """Benchmarked T closest to t in log-space (perf scales ~T^2, so the
    nearest decade is the right generalization)."""
    if not table:
        return None
    import math

    return min(table, key=lambda mt: abs(math.log(mt) - math.log(max(t, 1))))


def _blocks_from_env() -> Optional[tuple]:
    spec = os.environ.get("DL4J_TPU_ATTN_BLOCK", "").strip()
    if not spec:
        return None
    parts = spec.lower().replace("x", ",").split(",")
    bq = int(parts[0])
    bk = int(parts[1]) if len(parts) > 1 else bq
    return bq, bk


def _shape_eligible(tq: int, tk: int, *, min_t: int = 512) -> bool:
    # one canonical predicate for "can flash run here" — ops.attention.
    # min_t=128 is raw kernel capability (memory-necessity path); the
    # default 512 is the perf floor for measured-verdict consults.
    from deeplearning4j_tpu.ops.attention import flash_eligible

    return flash_eligible(tq, tk, min_t=min_t)


def attention_backward(tq: int, tk: Optional[int] = None) -> str:
    """Backward implementation for an already-chosen flash path: "dense"
    (whole-[Tq, Tk] XLA recompute — numerically the oracle, and the
    measured train winner wherever rows exist; ADVICE r4 medium) unless
    a winning measured pallas row or memory necessity says otherwise."""
    tk = tq if tk is None else tk
    forced = _env("DL4J_TPU_ATTN_BACKWARD")
    if forced in ("pallas", "dense"):
        return forced
    if _mem_hazard(tq, tk):
        return "pallas"       # the O(T)-memory backward is the point
    table = MEASURED.get("attention", {}).get("train", {})
    mt = _nearest_measured(table, _t_eff(tq, tk))
    if mt is not None:
        row = table[mt]
        if row["winner"] == "flash" and row.get("backward") == "pallas":
            return "pallas"
    return "dense"


def attention_policy(tq: int, tk: Optional[int] = None,
                     train: bool = False) -> AttentionPolicy:
    """Decide flash-vs-dense (and tile config) for one attention call.

    tq/tk are the query/key sequence lengths; `train` selects which
    measured mode (fwd-only vs fwd+bwd) the verdict comes from.
    """
    tk = tq if tk is None else tk
    t = _t_eff(tq, tk)
    forced = _env("DL4J_TPU_ATTN")
    can_flash = _shape_eligible(tq, tk, min_t=128)   # kernel capability
    blocks = _blocks_from_env()

    def flash(bq, bk, reason):
        if blocks is not None:
            bq, bk = blocks
        record_dispatch("attention", "flash")
        return AttentionPolicy("flash", bq, bk,
                               attention_backward(tq, tk), reason)

    def dense(reason):
        record_dispatch("attention", "dense")
        return AttentionPolicy("dense", 0, 0, "dense", reason)

    if forced == "dense":
        return dense("forced by DL4J_TPU_ATTN=dense")
    if forced == "flash":
        if not can_flash:
            return dense("DL4J_TPU_ATTN=flash but shape ineligible "
                         f"(backend/tiling, tq={tq} tk={tk})")
        return flash(512, 512, "forced by DL4J_TPU_ATTN=flash")
    if not can_flash:
        return dense(f"shape ineligible (tq={tq}, tk={tk})")
    if _mem_hazard(tq, tk):
        # capability floor (128), not the perf floor: a short-query
        # cross-attention over a huge context must still avoid the
        # [Tq, Tk] dense materialization
        row = _best_measured_flash("train" if train else "fwd", t)
        bq, bk = (row["block_q"], row["block_k"]) if row else (512, 512)
        return flash(bq, bk,
                     f"memory necessity: Tq*Tk >= {dense_max_t()}^2")
    if not _shape_eligible(tq, tk):     # perf floor for measured consults
        return dense(f"below flash perf floor (tq={tq}, tk={tk})")
    mode = "train" if train else "fwd"
    table = MEASURED.get("attention", {}).get(mode, {})
    mt = _nearest_measured(table, t)
    if mt is not None and table[mt]["winner"] == "flash":
        row = table[mt]
        return flash(row["block_q"], row["block_k"],
                     f"measured win at T={mt} "
                     f"({row['flash_ms']} vs {row['dense_ms']} ms)")
    if mt is not None:
        row = table[mt]
        return dense(f"measured loss at T={mt} "
                     f"({row.get('flash_ms')} vs {row['dense_ms']} ms)")
    return dense("no measured rows; conservative default")


def _best_measured_flash(mode: str, t: int) -> Optional[dict]:
    """Tile config worth adopting: only a WINNING flash row — a losing
    row's blocks are the measured-worst configuration (128^2 runs 2-5x
    behind dense), exactly what the memory-necessity path must not
    inherit. No winning row -> caller falls back to the 512^2 default."""
    table = MEASURED.get("attention", {}).get(mode, {})
    mt = _nearest_measured(table, t)
    if mt is None:
        return None
    row = table[mt]
    return row if (row.get("block_q") and row["winner"] == "flash") else None


def _best_measured_banded(mode: str, t: int) -> Optional[dict]:
    """Winning banded row's tile config (same rule as
    `_best_measured_flash`: a losing row's blocks are the measured-worst
    configuration and must not be inherited)."""
    table = MEASURED.get("banded", {}).get(mode, {})
    mt = _nearest_measured(table, t)
    if mt is None:
        return None
    row = table[mt]
    return row if (row.get("block_q") and row["winner"] == "banded") \
        else None


def banded_policy(t: int, h: int, hkv: int,
                  train: bool = False) -> BandedPolicy:
    """Banded-vs-dense for one windowed/GQA attention call (the shapes
    `attention_policy` never serves: its flash kernel is full-context).

    Same lattice as `attention_policy`: env force, then shape capability,
    then memory necessity, then the measured verdict, with dense the
    no-data default. Memory necessity holds for training shapes as for
    forward-only ones: the banded backward is blockwise over the band's
    tiles (`ops/banded_attention._run_banded_bwd`), so where the dense
    scores cannot exist (T 8,192 with 48 heads: 6 GiB a copy in bf16, and
    the backward holds three) banded is the path in both directions.

    What the chip showed for training at 8,192 (`trinity_large_fit`: 48
    query heads over 8 KV heads of 128, window 4,096, bf16; PERF.md
    sections 5 and 6): these blocks of 256 are where each of the three
    kernels starts, and each picks its own tile from them
    (`ops/attention._pick_tile`): the group's 256 tokens (1,536 rows) by
    512 keys in all three. The forward takes 5.3 ms a call since PR 36,
    where it took 14.7; dQ 7.7 and dK/dV 8.9 since PR 39, where a Q block
    of 128 took 9.9 and 16.5 (host clock). There is no MEASURED row for
    it and there cannot be one: a row is a head-to-head, and the dense
    contender does not fit the chip at this shape (it asked the compiler
    for 18.7 GiB)."""
    forced = _env("DL4J_TPU_ATTN")
    blocks = _blocks_from_env()
    from deeplearning4j_tpu.ops.banded_attention import banded_eligible

    can = banded_eligible(t, h, hkv, min_t=128)

    def banded(bq, bk, reason):
        if blocks is not None:
            bq, bk = blocks
        record_dispatch("banded_attention", "banded")
        return BandedPolicy("banded", bq, bk, reason)

    def dense(reason):
        record_dispatch("banded_attention", "dense")
        return BandedPolicy("dense", 0, 0, reason)

    if forced == "dense":
        return dense("forced by DL4J_TPU_ATTN=dense")
    if forced == "flash":
        return dense("DL4J_TPU_ATTN=flash: the full-context flash kernel "
                     "cannot band; windowed shapes stay dense")
    if forced == "banded":
        # Backend is waived: the force must hold off-TPU too (the layer
        # runs the kernel in interpret mode there), or a CPU smoke of a
        # production config would silently exercise a different path.
        if not banded_eligible(t, h, hkv, min_t=128, any_backend=True):
            return dense("DL4J_TPU_ATTN=banded but shape ineligible "
                         f"(tiling, t={t} h={h} hkv={hkv})")
        return banded(256, 256, "forced by DL4J_TPU_ATTN=banded")
    if not can:
        return dense(f"shape ineligible (t={t}, h={h}, hkv={hkv})")
    if _mem_hazard(t, t):
        row = _best_measured_banded("train" if train else "fwd", t)
        bq, bk = (row["block_q"], row["block_k"]) if row else (256, 256)
        return banded(bq, bk,
                      f"memory necessity: T^2 >= {dense_max_t()}^2")
    mode = "train" if train else "fwd"
    table = MEASURED.get("banded", {}).get(mode, {})
    mt = _nearest_measured(table, t)
    if mt is not None and table[mt]["winner"] == "banded":
        row = table[mt]
        return banded(row["block_q"], row["block_k"],
                      f"measured win at T={mt} "
                      f"({row['banded_ms']} vs {row['dense_ms']} ms)")
    if mt is not None:
        row = table[mt]
        return dense(f"measured loss at T={mt} "
                     f"({row.get('banded_ms')} vs {row['dense_ms']} ms)")
    return dense("no measured rows; conservative default")


class SparsePolicy(NamedTuple):
    kind: str            # "kernel" | "masked"
    block_q: int
    block_k: int
    reason: str


def sparse_policy(t: int, block_size: int) -> SparsePolicy:
    """Kernels-vs-masked for attention over selected key blocks
    (`ops/sparse_attention.py`). A layer comes here only past its
    `dense_len`, where the masked path's [T, T] scores are a memory
    hazard by construction, so there is nothing to measure against: the
    kernels serve every shape they tile on a TPU, the dense masked
    softmax the rest (small shapes off the chip, the tests').
    `DL4J_TPU_ATTN=dense` forces the masked path as it forces the others.

    Tiles: K 512 (8 blocks of 64 keys) is the walk's unit and every
    kernel's K tile; Q 256 is where each kernel's own Q tile starts
    (`ops/attention._pick_tile`: 1,024 tokens in all three at
    `minicpm_sala_fit`'s 32 query heads over 2 KV heads of 128 and 16,384
    tokens, bf16, where a forward call takes 26.7 ms since PR 36 and took
    50.0, dQ and dK/dV 31.1 and 35.5 since PR 39 and took 41.4 and 55.1;
    PERF.md sections 5 and 6)."""
    import jax

    from deeplearning4j_tpu.ops.sparse_attention import sparse_eligible

    bq, bk = min(256, t), min(512, t)

    def verdict(kind, reason):
        record_dispatch("sparse_attention", kind)
        return SparsePolicy(kind, bq, bk, reason)

    if _env("DL4J_TPU_ATTN") == "dense":
        return verdict("masked", "forced by DL4J_TPU_ATTN=dense")
    if jax.default_backend() != "tpu":
        return verdict("masked", "no TPU: the dense masked softmax")
    if not sparse_eligible(t, block_size, bq, bk):
        return verdict("masked", f"shape ineligible (t={t}, blocks of "
                                 f"{block_size})")
    return verdict("kernel", "past dense_len the masked path's scores "
                             "cannot exist")


class LatentPolicy(NamedTuple):
    kind: str            # "kernel" | "dense"
    block_q: int
    block_k: int
    reason: str


def latent_policy(t: int) -> LatentPolicy:
    """Kernels-vs-dense for latent attention's core
    (`ops/latent_attention.py`). No other kernel computes it (a query 192
    wide against values of 128, one rope key for all heads), and its
    dense form makes [heads, T, T] scores, so there is one verdict: the
    kernels serve every sequence they tile on a TPU, the dense form the
    rest (off the chip, the tests' odd lengths). `DL4J_TPU_ATTN=dense`
    forces the dense form as it forces the others.

    Tiles: 512 x 512 is where each kernel's own tile starts
    (`ops/attention._pick_tile`: 1,024 x 512 forward and 1,024 x 1,024 in
    both backward kernels at `deepseek_v2_fit`'s 32 heads and 8,192
    tokens, as the flash kernels take at the same length)."""
    import jax

    from deeplearning4j_tpu.ops.latent_attention import latent_eligible

    def verdict(kind, reason):
        record_dispatch("latent_attention", kind)
        return LatentPolicy(kind, min(512, t), min(512, t), reason)

    if _env("DL4J_TPU_ATTN") == "dense":
        return verdict("dense", "forced by DL4J_TPU_ATTN=dense")
    if jax.default_backend() != "tpu":
        return verdict("dense", "no TPU: the dense form")
    if not latent_eligible(t):
        return verdict("dense", f"shape ineligible (t={t})")
    return verdict("kernel", "no other kernel computes it")


class DecodePolicy(NamedTuple):
    kind: str            # "banded" | "dense"
    block_l: int
    reason: str


def decode_attention_policy(cache_len: int, h: int, hkv: int,
                            record: bool = True) -> DecodePolicy:
    """Single-query decode-step attention: the Pallas kernel that reads
    the KVSlotPool layout directly vs the layer's dense einsum. Env hatch
    DL4J_TPU_DECODE_ATTN=auto|banded|dense; measured rows live under
    MEASURED["decode"] keyed by cache length. `record=False` is for
    observers (serving snapshots) that ask what WOULD dispatch —
    kernel_dispatch_total must count only real dispatch sites."""
    forced = _env("DL4J_TPU_DECODE_ATTN")
    from deeplearning4j_tpu.ops.banded_attention import decode_eligible

    can = decode_eligible(cache_len, h, hkv)

    def banded(bl, reason):
        if record:
            record_dispatch("decode_attention", "banded")
        return DecodePolicy("banded", bl, reason)

    def dense(reason):
        if record:
            record_dispatch("decode_attention", "dense")
        return DecodePolicy("dense", 0, reason)

    if forced == "dense":
        return dense("forced by DL4J_TPU_DECODE_ATTN=dense")
    if forced == "banded":
        # An explicit force runs even off-TPU (interpret mode): that is
        # the CPU parity/integration seam, and production force-routing
        # must not silently un-force itself.
        return banded(512, "forced by DL4J_TPU_DECODE_ATTN=banded")
    if not can:
        return dense(f"shape ineligible (L={cache_len}, h={h}, "
                     f"hkv={hkv})")
    table = MEASURED.get("decode", {})
    mt = _nearest_measured(table, cache_len)
    if mt is not None and table[mt]["winner"] == "banded":
        row = table[mt]
        return banded(row.get("block_l", 512),
                      f"measured win at L={mt} "
                      f"({row['banded_ms']} vs {row['dense_ms']} ms)")
    if mt is not None:
        row = table[mt]
        return dense(f"measured loss at L={mt} "
                     f"({row.get('banded_ms')} vs {row['dense_ms']} ms)")
    return dense("no measured rows; conservative default")


class DecodeLoopPolicy(NamedTuple):
    kind: str            # "fused" | "stepwise"
    k: int               # window length (1 when stepwise)
    reason: str


# Fused decode windows compile one program per K, so K is snapped to a
# small bucket set exactly like the seq-ctx buckets: session churn and
# per-request budgets never mint new programs (the zero-recompile
# contract the watchdog polices).
DECODE_K_BUCKETS = (1, 2, 4, 8, 16)


def _bucket_k(k: int) -> int:
    for b in DECODE_K_BUCKETS:
        if b >= k:
            return b
    return DECODE_K_BUCKETS[-1]


def decode_loop_policy(k: Optional[int] = None, *, capable: bool = True,
                       record: bool = True) -> DecodeLoopPolicy:
    """Fused-K decode loop (one `lax.scan` dispatch advances every active
    session K tokens, sampling on-device) vs the stepwise one-token-per-
    dispatch loop. Same lattice as the other policies — env force, then
    capability, then the measured verdict — but the no-data default is
    FUSED, not conservative: both sides lower through the identical
    per-step XLA program (no hand-written kernel to mistrust), and the
    K-fold host round-trip amortization is structural, exactly like
    `lstm_policy`'s fused default. `k` is the caller's requested window
    (None = the default bucket); it is snapped to DECODE_K_BUCKETS so
    request churn costs zero compiles. `capable=False` (the model has no
    `session_decode_window`, e.g. a ComputationGraph endpoint) degrades
    to stepwise. `record=False` is for observers (serving snapshots)
    asking what WOULD dispatch."""
    forced = _env("DL4J_TPU_DECODE_LOOP")
    env_k = os.environ.get("DL4J_TPU_DECODE_K", "").strip()
    if env_k:
        k = int(env_k)
    want_k = _bucket_k(8 if k is None else max(1, int(k)))

    def fused(kk, reason):
        if record:
            record_dispatch("decode_loop", "fused")
        return DecodeLoopPolicy("fused", kk, reason)

    def stepwise(reason):
        if record:
            record_dispatch("decode_loop", "stepwise")
        return DecodeLoopPolicy("stepwise", 1, reason)

    if forced == "stepwise":
        return stepwise("forced by DL4J_TPU_DECODE_LOOP=stepwise")
    if forced == "fused":
        if not capable:
            return stepwise("DL4J_TPU_DECODE_LOOP=fused but the model "
                            "has no session_decode_window")
        return fused(want_k, "forced by DL4J_TPU_DECODE_LOOP=fused")
    if not capable:
        return stepwise("model has no session_decode_window")
    row = MEASURED.get("decode_loop")
    if row is not None:
        mt = _nearest_measured(row, want_k)
        if mt is not None and row[mt]["winner"] == "stepwise":
            return stepwise(f"measured loss at K={mt} "
                            f"({row[mt]['fused_ms']} vs "
                            f"{row[mt]['stepwise_ms']} ms)")
        if mt is not None:
            return fused(want_k, f"measured win at K={mt} "
                         f"({row[mt]['fused_ms']} vs "
                         f"{row[mt]['stepwise_ms']} ms)")
    return fused(want_k, "structural default: identical per-step XLA "
                 "program, K-fold fewer host round-trips")


class SpecDecodePolicy(NamedTuple):
    kind: str            # "spec" | "plain"
    k: int               # draft window length (0 when plain)
    reason: str


def spec_decode_policy(k: Optional[int] = None, *, capable: bool = True,
                       record: bool = True) -> SpecDecodePolicy:
    """Draft-model speculative decoding (draft proposes D tokens per
    lane, the target verifies all D in ONE chunk dispatch, accept/reject
    on device) vs the plain fused window. Same lattice as
    `decode_loop_policy` — env force, then capability, then the measured
    verdict. The no-data default is SPEC when a draft is wired up:
    verification lowers through the same chunked forward the prefill
    path already runs, and replacing D sequential target steps with one
    chunk is structural. `capable=False` means no draft model is
    registered, or either net cannot rewind its caches (recurrent
    carries / rolling rings hold state that cannot be un-written after
    a rejection) — degrades to plain. `k` is the requested draft window
    (None = default bucket), snapped to DECODE_K_BUCKETS so draft-length
    churn costs zero compiles."""
    forced = _env("DL4J_TPU_SPEC_DECODE")
    env_k = os.environ.get("DL4J_TPU_DRAFT_K", "").strip()
    if env_k:
        k = int(env_k)
    want_k = _bucket_k(8 if k is None else max(1, int(k)))

    def spec(kk, reason):
        if record:
            record_dispatch("spec_decode", "spec")
        return SpecDecodePolicy("spec", kk, reason)

    def plain(reason):
        if record:
            record_dispatch("spec_decode", "plain")
        return SpecDecodePolicy("plain", 0, reason)

    if forced == "off":
        return plain("forced by DL4J_TPU_SPEC_DECODE=off")
    if forced == "on":
        if not capable:
            return plain("DL4J_TPU_SPEC_DECODE=on but no rewindable "
                         "draft/target pair (draft missing, recurrent "
                         "carries, or rolling KV rings)")
        return spec(want_k, "forced by DL4J_TPU_SPEC_DECODE=on")
    if not capable:
        return plain("no rewindable draft/target pair (draft missing, "
                     "recurrent carries, or rolling KV rings)")
    row = MEASURED.get("spec_decode")
    if row is not None:
        mt = _nearest_measured(row, want_k)
        if mt is not None and row[mt]["winner"] == "plain":
            return plain(f"measured loss at D={mt} "
                         f"({row[mt]['spec_ms']} vs "
                         f"{row[mt]['plain_ms']} ms)")
        if mt is not None:
            return spec(want_k, f"measured win at D={mt} "
                        f"({row[mt]['spec_ms']} vs "
                        f"{row[mt]['plain_ms']} ms)")
    return spec(want_k, "structural default: one chunk verify replaces "
                "D sequential target dispatches")


class KVDtypePolicy(NamedTuple):
    kind: str            # "native" | "int8" | "fp8"
    reason: str


def _fp8_capable() -> bool:
    """fp8 KV storage needs a backend whose e4m3 cast lowering is
    trusted; off-TPU the int8 path is the portable one."""
    import jax

    return jax.default_backend() == "tpu"


def kv_dtype_policy(kind: Optional[str] = None, *,
                    record: bool = True) -> KVDtypePolicy:
    """Storage dtype for the KVSlotPool's attention caches: "native"
    (the model dtype), "int8" (per-(token, kv-head) scale rows,
    quantize-on-write / dequantize-on-read fused into the banded decode
    kernel's block loads and the dense fallback), or "fp8" (e4m3, same
    scale rows, capable backends only). Env hatch DL4J_TPU_KV_DTYPE
    always wins; `kind` is the caller's request (server knob); the
    no-data default is NATIVE — quantization trades ulps for slots, and
    that trade is opted into per deployment, not defaulted. A MEASURED
    ["kv_dtype"] verdict (from the autotune sweep) can flip the auto
    default once rows exist."""
    forced = _env("DL4J_TPU_KV_DTYPE")
    want = forced if forced != "auto" else (kind or "").strip().lower()
    if want not in ("", "auto", "native", "int8", "fp8"):
        # an explicit-but-unknown request must fail the deploy, not
        # silently serve unquantized
        raise ValueError(f"unknown kv_dtype {want!r} "
                         "(expected native|int8|fp8)")

    def verdict(kd, reason):
        if record:
            record_dispatch("kv_dtype", kd)
        return KVDtypePolicy(kd, reason)

    if want in ("native", "int8"):
        src = "DL4J_TPU_KV_DTYPE" if forced != "auto" else "caller"
        return verdict(want, f"forced by {src}={want}")
    if want == "fp8":
        if not _fp8_capable():
            src = "DL4J_TPU_KV_DTYPE" if forced != "auto" else "caller"
            return verdict("int8", f"{src}=fp8 but backend lacks e4m3 "
                           "support; int8 carries the same scale rows")
        src = "DL4J_TPU_KV_DTYPE" if forced != "auto" else "caller"
        return verdict("fp8", f"forced by {src}=fp8")
    row = MEASURED.get("kv_dtype")
    if row is not None and row.get("winner") in ("int8", "fp8"):
        kd = row["winner"]
        if kd == "fp8" and not _fp8_capable():
            kd = "int8"
        return verdict(kd, f"measured win ({row})")
    return verdict("native", "no measured rows; quantization is "
                   "opt-in per deployment")


class PrefixCachePolicy(NamedTuple):
    kind: str            # "paged" | "off"
    page_len: int        # KV page length in tokens (0 when off)
    reason: str


def prefix_cache_policy(page_len: Optional[int] = None, *,
                        max_cache: Optional[int] = None,
                        capable: bool = True,
                        record: bool = True) -> PrefixCachePolicy:
    """Paged KV storage + radix prefix cache vs monolithic per-slot
    caches. Same lattice as the other policies — env force, then
    capability — but like `decode_loop_policy` the no-data default is
    ON when the model is capable: a warm prefix replaces its whole
    prefill with admission-time page-table writes, and that bookkeeping
    costs the steady-state window nothing (page indices are traced
    scalars, one compiled program either way), so there is no measured
    trade to wait on. `capable=False` (recurrent carries, rolling KV
    rings, non-uniform max_cache, or an active draft model whose own
    cache cannot skip the prefill) degrades to off. The page length
    (DL4J_TPU_KV_PAGE, or `page_len`, default 128 — the TPU lane tile,
    so the banded paged kernel stays eligible) is snapped down to the
    largest divisor of `max_cache` so a slot's table tiles exactly."""
    forced = _env("DL4J_TPU_PREFIX_CACHE")
    env_p = os.environ.get("DL4J_TPU_KV_PAGE", "").strip()
    if env_p:
        page_len = int(env_p)
    want = max(1, int(page_len)) if page_len else 128
    if max_cache:
        mc = int(max_cache)
        want = min(want, mc)
        while mc % want:
            want -= 1

    def paged(reason):
        if record:
            record_dispatch("prefix_cache", "paged")
        return PrefixCachePolicy("paged", want, reason)

    def off(reason):
        if record:
            record_dispatch("prefix_cache", "off")
        return PrefixCachePolicy("off", 0, reason)

    if forced == "off":
        return off("forced by DL4J_TPU_PREFIX_CACHE=off")
    if forced == "on":
        if not capable:
            return off("DL4J_TPU_PREFIX_CACHE=on but the model cannot "
                       "page its KV (recurrent carries, rolling rings, "
                       "non-uniform max_cache, or active draft model)")
        return paged("forced by DL4J_TPU_PREFIX_CACHE=on")
    if not capable:
        return off("model cannot page its KV (recurrent carries, "
                   "rolling rings, non-uniform max_cache, or active "
                   "draft model)")
    return paged("structural default: a warm prefix replaces its whole "
                 "prefill; admission-time bookkeeping costs the "
                 "steady-state window nothing")


def lstm_policy(train: bool = True) -> str:
    """"fused" (Pallas) or "scan" (lax.scan baseline) for the LSTM core.

    The fused kernel exists precisely because the recurrence carry is a
    fusion XLA cannot do across scan steps; the measured train win is
    2.35x (tools/kernel_bench_results.json: lstm_train_fused). An
    unmeasured mode falls back to the other mode's verdict (documented:
    both run the identical kernel; only the cotangent pass differs).
    """
    forced = _env("DL4J_TPU_LSTM")
    if forced in ("fused", "scan"):
        record_dispatch("lstm", forced)
        return forced
    table = MEASURED.get("lstm", {})
    mode = "train" if train else "fwd"
    row = table.get(mode) or table.get("fwd" if train else "train")
    verdict = "fused"   # no data at all: structural argument above
    if row is not None:
        verdict = "fused" if row["winner"] == "fused" else "scan"
    record_dispatch("lstm", verdict)
    return verdict
