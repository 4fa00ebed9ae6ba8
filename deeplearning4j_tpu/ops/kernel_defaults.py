"""Which kernel serves a call: ten policies, one kind of rule.

`attention_policy` (with `attention_backward`), `banded_policy`,
`sparse_policy`, `latent_policy`, `decode_attention_policy`,
`decode_loop_policy`, `spec_decode_policy`, `kv_dtype_policy`,
`prefix_cache_policy` and `lstm_policy` each answer at TRACE time, and
each is, in order:

  1. An env force, which always wins (ops run in production; a
     lowering bug or perf regression must be routable around without a
     release):
       DL4J_TPU_ATTN           = auto|flash|banded|dense
       DL4J_TPU_ATTN_BACKWARD  = auto|pallas|dense
       DL4J_TPU_ATTN_BLOCK     = "512" or "512x256"   (block_q x block_k)
       DL4J_TPU_DENSE_MAX_T    = int (the hazard threshold below)
       DL4J_TPU_DECODE_ATTN    = auto|banded|dense   (serving decode step)
       DL4J_TPU_DECODE_LOOP    = auto|fused|stepwise (serving decode loop)
       DL4J_TPU_DECODE_K       = int (fused decode window length; bucketed)
       DL4J_TPU_SPEC_DECODE    = auto|on|off  (draft-model speculative decode)
       DL4J_TPU_DRAFT_K        = int (draft proposal window; bucketed)
       DL4J_TPU_KV_DTYPE       = auto|native|int8|fp8 (KV-cache storage)
       DL4J_TPU_PREFIX_CACHE   = auto|on|off  (paged KV prefix reuse)
       DL4J_TPU_KV_PAGE        = int (KV page length; snapped to divisors)
       DL4J_TPU_LSTM           = auto|fused|scan
  2. Eligibility: the TPU backend and a shape the kernel tiles (its own
     `*_eligible` predicate), or what the caller says the model can do
     (`capable`); otherwise the XLA path.
  3. One predicate on the shape. For flash and banded attention it is
     the memory hazard: when Tq*Tk >= DENSE_MAX_T^2 (default 8192^2) the
     dense [Tq, Tk] score matrix cannot be afforded (32 heads of 8192^2
     f32 scores = 8 GiB on a 16 GiB chip, and a Tq=4096 x Tk=16384
     cross-attention is the same 8 GiB), so the kernel and its O(T)
     Pallas backward are the path. Below the hazard dense is the default
     because no cell has measured today's kernels there (ROADMAP D3).
     The other policies have no shape to weigh: the decode step stays
     dense, the fused window, speculative decoding and the prefix cache
     are on where the model is capable, KV storage is native unless
     asked, the LSTM core is the fused kernel.

Two more answers at trace time have steps 2 and 3 alone, no force:
`kernels_run` (may the kernels written for one device's arrays run) and
`embedding_backward_tile` (the embedding's gradient as a grouped product).
"""

from __future__ import annotations

import os
from typing import NamedTuple, Optional


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
    in the serving snapshot."""
    from deeplearning4j_tpu.observe import get_registry

    get_registry().counter("kernel_dispatch_total", op=op, impl=impl).inc()


def kernels_run() -> bool:
    """Whether a layer traced now may run the kernels written for ONE
    device's arrays (`ops/grouped_matmul`, `ops/row_gather`, and through
    them an expert layer's tier and the embedding's gradient): on the TPU
    backend with no mesh context, under which their operands may be
    sharded. No force: the backend and the mesh are all there is to ask."""
    import jax

    from deeplearning4j_tpu.parallel.mesh import current_mesh_context

    return (jax.default_backend() == "tpu"
            and current_mesh_context() is None)


def embedding_backward_tile(width: int) -> Optional[int]:
    """The rows of a vocabulary tile for the embedding's gradient as a
    grouped product (`ops/embedding.py`), or None where XLA's scatter-add
    stays: a row that is not whole lanes of 128, which the kernel's blocks
    are cut in. One size, no rule on the table's height or the tokens: at
    the five token cells' shapes (12,544 to 49,152 rows of 2,048 to 5,120,
    8,192 or 16,384 ids) the whole gradient read 0.57 to 2.63 ms with
    tiles of 256 rows, within 0.08 ms of 128 at every shape, and 512 lost
    0.02 to 0.27, where the scatter read 1.61 to 23.32: `ouro_2_6b`'s 192
    tiles and `deepseek_v2`'s 50 want no rule between them (chip runs,
    PR 48)."""
    return None if width % 128 else 256


def _env(name: str, default: str = "auto") -> str:
    v = os.environ.get(name, default).strip().lower()
    return v or default


def dense_max_t() -> int:
    """Sequence length at which the dense [T, T] path becomes a memory
    hazard and the kernels take over."""
    return int(os.environ.get("DL4J_TPU_DENSE_MAX_T", "8192"))


def _mem_hazard(tq: int, tk: int) -> bool:
    """The dense path materializes [Tq, Tk] scores per head, so the
    hazard scales with the PRODUCT: cross-attention over a long context
    (Tq=4096, Tk=16384) is exactly as dangerous as self-attention at
    sqrt(Tq*Tk). Threshold: product >= DENSE_MAX_T^2."""
    return tq * tk >= dense_max_t() ** 2


def _blocks_from_env() -> Optional[tuple]:
    spec = os.environ.get("DL4J_TPU_ATTN_BLOCK", "").strip()
    if not spec:
        return None
    parts = spec.lower().replace("x", ",").split(",")
    bq = int(parts[0])
    bk = int(parts[1]) if len(parts) > 1 else bq
    return bq, bk


def attention_backward(tq: int, tk: Optional[int] = None) -> str:
    """Backward implementation for an already-chosen flash path: "pallas"
    (two blockwise kernels, O(T) memory) at the memory hazard, where it
    is the point of taking flash; "dense" (whole-[Tq, Tk] XLA recompute,
    numerically the oracle) below it."""
    tk = tq if tk is None else tk
    forced = _env("DL4J_TPU_ATTN_BACKWARD")
    if forced in ("pallas", "dense"):
        return forced
    return "pallas" if _mem_hazard(tq, tk) else "dense"


def attention_policy(tq: int, tk: Optional[int] = None,
                     train: bool = False) -> AttentionPolicy:
    """Decide flash-vs-dense (and where the tiles start) for one
    attention call. tq/tk are the query/key sequence lengths; `train`
    does not move the verdict (the hazard holds in both directions)."""
    tk = tq if tk is None else tk
    forced = _env("DL4J_TPU_ATTN")
    blocks = _blocks_from_env()
    from deeplearning4j_tpu.ops.attention import flash_eligible

    can_flash = flash_eligible(tq, tk, min_t=128)   # kernel capability

    def flash(reason):
        record_dispatch("attention", "flash")
        bq, bk = blocks or (512, 512)
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
        return flash("forced by DL4J_TPU_ATTN=flash")
    if not can_flash:
        return dense(f"shape ineligible (tq={tq}, tk={tk})")
    if _mem_hazard(tq, tk):
        # the floor is capability (128): a short-query cross-attention
        # over a huge context must still avoid the [Tq, Tk] scores
        return flash(f"memory necessity: Tq*Tk >= {dense_max_t()}^2")
    return dense(f"below the memory hazard (Tq*Tk < {dense_max_t()}^2)")


def banded_policy(t: int, h: int, hkv: int,
                  train: bool = False) -> BandedPolicy:
    """Banded-vs-dense for one windowed/GQA attention call (the shapes
    `attention_policy` never serves: its flash kernel is full-context).

    Same order as `attention_policy`: env force, then shape capability,
    then the memory hazard, with dense below it. The hazard holds for
    training shapes as for forward-only ones: the banded backward is
    blockwise over the band's tiles
    (`ops/banded_attention._run_banded_bwd`), so where the dense scores
    cannot exist (T 8,192 with 48 heads: 6 GiB a copy in bf16, and the
    backward holds three; the dense form asked the compiler for 18.7 GiB)
    banded is the path in both directions.

    What the chip showed for training at 8,192 (`trinity_large_fit`: 48
    query heads over 8 KV heads of 128, window 4,096, bf16; PERF.md
    sections 5 and 6): these blocks of 256 are where each of the three
    kernels starts, and each picks its own tile from them
    (`ops/attention._pick_tile`): the group's 256 tokens (1,536 rows) by
    512 keys in all three. The forward takes 5.3 ms a call since PR 36,
    where it took 14.7; dQ 7.7 and dK/dV 8.9 since PR 39, where a Q block
    of 128 took 9.9 and 16.5 (host clock)."""
    forced = _env("DL4J_TPU_ATTN")
    blocks = _blocks_from_env()
    from deeplearning4j_tpu.ops.banded_attention import banded_eligible

    def banded(reason):
        record_dispatch("banded_attention", "banded")
        bq, bk = blocks or (256, 256)
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
        return banded("forced by DL4J_TPU_ATTN=banded")
    if not banded_eligible(t, h, hkv, min_t=128):
        return dense(f"shape ineligible (t={t}, h={h}, hkv={hkv})")
    if _mem_hazard(t, t):
        return banded(f"memory necessity: T^2 >= {dense_max_t()}^2")
    return dense(f"below the memory hazard (T^2 < {dense_max_t()}^2)")


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
    the KVSlotPool layout directly vs the layer's dense einsum. Dense
    unless DL4J_TPU_DECODE_ATTN=banded forces the kernel: no serving
    cell has measured it. `record=False` is for observers (serving
    snapshots) that ask what WOULD dispatch — kernel_dispatch_total
    must count only real dispatch sites."""
    forced = _env("DL4J_TPU_DECODE_ATTN")

    def verdict(kind, block_l, reason):
        if record:
            record_dispatch("decode_attention", kind)
        return DecodePolicy(kind, block_l, reason)

    if forced == "dense":
        return verdict("dense", 0, "forced by DL4J_TPU_DECODE_ATTN=dense")
    if forced == "banded":
        # An explicit force runs even off-TPU (interpret mode): that is
        # the CPU parity/integration seam, and production force-routing
        # must not silently un-force itself.
        return verdict("banded", 512,
                       "forced by DL4J_TPU_DECODE_ATTN=banded")
    return verdict("dense", 0,
                   "no serving cell has measured the decode kernel")


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
    dispatch loop. Env force, then capability, and the default is FUSED:
    both sides lower through the identical per-step XLA program (no
    hand-written kernel to mistrust), and the K-fold host round-trip
    amortization is structural. `k` is the caller's requested window
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
    on device) vs the plain fused window. As `decode_loop_policy`: env
    force, then capability. The default is SPEC when a draft is wired up:
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
    default is NATIVE — quantization trades ulps for slots, and that
    trade is opted into per deployment, not defaulted."""
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
    return verdict("native", "quantization is opt-in per deployment")


class PrefixCachePolicy(NamedTuple):
    kind: str            # "paged" | "off"
    page_len: int        # KV page length in tokens (0 when off)
    reason: str


def prefix_cache_policy(page_len: Optional[int] = None, *,
                        max_cache: Optional[int] = None,
                        capable: bool = True,
                        record: bool = True) -> PrefixCachePolicy:
    """Paged KV storage + radix prefix cache vs monolithic per-slot
    caches. Env force, then capability, and like `decode_loop_policy`
    the default is ON when the model is capable: a warm prefix replaces
    its whole prefill with admission-time page-table writes, and that
    bookkeeping costs the steady-state window nothing (page indices are
    traced scalars, one compiled program either way). `capable=False` (recurrent carries, rolling KV
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
    """"fused" (Pallas) or "scan" (lax.scan baseline) for the LSTM core:
    fused unless DL4J_TPU_LSTM forces otherwise, in both modes (they run
    the identical kernel; only the cotangent pass differs). The fused
    kernel exists because the recurrence carry is a fusion XLA cannot do
    across scan steps. Whether a shape can take the kernel is the call
    site's check (`nn/layers/recurrent.py`)."""
    forced = _env("DL4J_TPU_LSTM")
    verdict = forced if forced in ("fused", "scan") else "fused"
    record_dispatch("lstm", verdict)
    return verdict
