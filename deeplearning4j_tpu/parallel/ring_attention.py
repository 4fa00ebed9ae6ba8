"""Ring attention — sequence/context parallelism over a mesh axis.

No reference counterpart (SURVEY §5: 'No ring attention / context parallel…
RNN era'); this is the green-field long-context mechanism the charter
requires. Design: the sequence axis is sharded over the `seq` mesh axis;
each device holds a local block of Q/K/V. K/V blocks rotate around the ring
via `lax.ppermute` while each device accumulates its queries' attention with
the numerically-stable online-softmax (flash-attention style) running
(max, sum, out) triple — so peak memory is O(T_local²) instead of O(T²) and
the K/V transfer rides ICI neighbor links (the ring pattern maps exactly
onto the TPU torus).

Blockwise comm/compute overlap: each ppermute is issued before the block
accumulation it hides behind (XLA schedules the collective-permute
asynchronously).
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import AXIS_SEQ


# ---------------------------------------------------- layer integration
@dataclasses.dataclass(frozen=True)
class _SeqParallelCtx:
    mesh: Mesh
    axis: str


_SEQ_CTX: contextvars.ContextVar[Optional[_SeqParallelCtx]] = \
    contextvars.ContextVar("sequence_parallel_ctx", default=None)


@contextlib.contextmanager
def sequence_parallel(mesh: Mesh, axis: str = AXIS_SEQ):
    """Route every MultiHeadAttention (and thus TransformerEncoderBlock)
    applied inside this context through ring attention over `axis` —
    sequence parallelism at the model level, no layer changes:

        with sequence_parallel(make_mesh({"seq": 8})):
            net.fit(x, y, ...)

    The swap happens at TRACE time: wrap the calls that trace/compile
    (fit/output); a step compiled inside the context stays
    sequence-parallel when reused."""
    token = _SEQ_CTX.set(_SeqParallelCtx(mesh, axis))
    try:
        yield
    finally:
        _SEQ_CTX.reset(token)


def current_sequence_mesh() -> Optional[_SeqParallelCtx]:
    return _SEQ_CTX.get()


class SeqCtxJitCache:
    """Mixin: a `_jit_cache` dict partitioned by the active
    sequence-parallel context. Any object caching compiled traces of a
    forward that consults `current_sequence_mesh()` at trace time must
    never reuse a trace across context boundaries — a ring trace outside
    the context (or a dense trace inside it) is silently wrong."""

    @property
    def _jit_cache(self):
        caches = self.__dict__.setdefault("_jit_caches", {})
        cache = caches.get(current_sequence_mesh())
        if cache is None:
            # every compiled-program cache in the framework flows through
            # this property, so a counting dict here gives the
            # RecompileWatchdog full coverage of (re)compiles
            from deeplearning4j_tpu.observe.watchdog import WatchedJitCache
            cache = caches[current_sequence_mesh()] = \
                WatchedJitCache(owner=self)
        return cache


class SeqCtxSolverCache:
    """Mixin: the full-batch `_solver` cache, partitioned like
    SeqCtxJitCache (the solver holds its own compiled forward traces)."""

    @property
    def _solver(self):
        return self.__dict__.setdefault("_solvers", {}).get(
            current_sequence_mesh())

    @_solver.setter
    def _solver(self, value):
        self.__dict__.setdefault("_solvers", {})[
            current_sequence_mesh()] = value


def _block_accumulate(q, k, v, m, l, o, *, scale, q_off, k_off, causal):
    """Online-softmax accumulation of one K/V block into (m, l, o).

    q: [B,Tq,H,D]  k,v: [B,Tk,H,D]  m,l: [B,H,Tq]  o: [B,Tq,H,D]
    q_off/k_off: global offsets of the blocks (for causal masking).
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale  # [B,H,Tq,Tk]
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        qi = q_off + jnp.arange(tq)[:, None]
        ki = k_off + jnp.arange(tk)[None, :]
        s = jnp.where(ki > qi, -jnp.inf, s)
    m_blk = jnp.max(s, axis=-1)                       # [B,H,Tq]
    m_new = jnp.maximum(m, m_blk)
    # guard fully-masked rows (all -inf) against NaN
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    corr = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
    corr = jnp.where(jnp.isfinite(corr), corr, 0.0)
    l_new = l * corr + jnp.sum(p, axis=-1)
    o_new = (o * corr.transpose(0, 2, 1)[..., None]
             + jnp.einsum("bhqk,bkhd->bqhd", p, v))
    return m_new, l_new, o_new


def attention(q, k, v, *, causal: bool = False, scale: Optional[float] = None):
    """Single-device reference attention (used when no seq axis / tests)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(q.dtype)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        mask = jnp.arange(tk)[None, :] > jnp.arange(tq)[:, None]
        s = jnp.where(mask, -jnp.inf, s)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _flash_shard(q, k, v, causal: bool, scale: float, interpret: bool):
    """One K/V shard through the Pallas kernel; [B,T,H,D] in/out with
    per-row lse [B,H,Tq] for cross-shard merging."""
    from deeplearning4j_tpu.ops.attention import (_fold3, _unfold3,
                                                  flash_attention_with_lse)

    B, T, H, _ = q.shape
    q3, shape = _fold3(q)
    k3, _ = _fold3(k)
    v3, _ = _fold3(v)
    o, lse = flash_attention_with_lse(q3, k3, v3, causal, scale, 512, 512,
                                      interpret)
    return _unfold3(o, shape), lse.reshape(B, H, T)


def _ring_attention_local(q, k, v, *, axis_name: str, causal: bool,
                          scale: Optional[float], use_flash: bool = False,
                          interpret: bool = False):
    """Per-shard body (runs under shard_map). q/k/v: local blocks
    [B, T_local, H, D].

    Two per-shard compute paths: the XLA online-softmax accumulation
    (any backend/shape), or the Pallas flash kernel (`use_flash`) where
    each held shard is one of exactly three causal cases — fully visible
    (src < my: plain kernel), diagonal (src == my: the kernel's aligned
    causal mask), or fully masked (src > my: skipped, zero FLOPs) — and
    partial outputs merge via logaddexp of the emitted lse."""
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, Tq, H, D = q.shape
    scale_ = scale if scale is not None else 1.0 / (D ** 0.5)
    perm = [(j, (j + 1) % n) for j in range(n)]

    if use_flash:
        o0 = jnp.zeros((B, Tq, H, D), jnp.float32)
        lse0 = jnp.full((B, H, Tq), -jnp.inf, jnp.float32)

        def body(i, carry):
            k_blk, v_blk, o, lse = carry
            src = (my - i) % n
            k_nxt = lax.ppermute(k_blk, axis_name, perm)
            v_nxt = lax.ppermute(v_blk, axis_name, perm)
            if causal:
                def diag(args):
                    return _flash_shard(*args, True, scale_, interpret)

                def full(args):
                    return _flash_shard(*args, False, scale_, interpret)

                def dead(args):
                    return (jnp.zeros((B, Tq, H, D), q.dtype),
                            jnp.full((B, H, Tq), -jnp.inf, jnp.float32))

                o_i, lse_i = lax.cond(
                    src == my, diag,
                    lambda args: lax.cond(src < my, full, dead, args),
                    (q, k_blk, v_blk))
            else:
                o_i, lse_i = _flash_shard(q, k_blk, v_blk, False, scale_,
                                          interpret)
            lse_new = jnp.logaddexp(lse, lse_i)
            # exp(-inf - -inf) guard: a row with no visible keys yet
            w_old = jnp.where(jnp.isneginf(lse_new), 0.0,
                              jnp.exp(lse - lse_new))
            w_new = jnp.where(jnp.isneginf(lse_new), 0.0,
                              jnp.exp(lse_i - lse_new))
            o = (o * w_old.transpose(0, 2, 1)[..., None]
                 + o_i.astype(jnp.float32)
                 * w_new.transpose(0, 2, 1)[..., None])
            return (k_nxt, v_nxt, o, lse_new)

        _, _, o, _ = lax.fori_loop(0, n, body, (k, v, o0, lse0))
        return o.astype(q.dtype)

    m0 = jnp.full((B, H, Tq), -jnp.inf, q.dtype)
    l0 = jnp.zeros((B, H, Tq), q.dtype)
    o0 = jnp.zeros_like(q)

    def body(i, carry):
        k_blk, v_blk, m, l, o = carry
        # Block currently held arrived from device (my - i) mod n.
        src = (my - i) % n
        # Rotate early so the permute overlaps the block math below.
        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        m, l, o = _block_accumulate(
            q, k_blk, v_blk, m, l, o,
            scale=scale_, q_off=my * Tq, k_off=src * Tq, causal=causal)
        return (k_nxt, v_nxt, m, l, o)

    _, _, m, l, o = lax.fori_loop(0, n, body, (k, v, m0, l0, o0))
    l_safe = jnp.maximum(l, 1e-20)
    return o / l_safe.transpose(0, 2, 1)[..., None]


def ring_self_attention(q, k, v, mesh: Mesh, *, axis: str = AXIS_SEQ,
                        causal: bool = False,
                        scale: Optional[float] = None,
                        use_flash: Optional[bool] = None,
                        interpret: bool = False):
    """Sequence-parallel attention: q/k/v [B, T, H, D] with T sharded over
    `axis`. Returns output with the same sharding.

    use_flash: route each shard's block math through the Pallas flash
    kernel (ops/attention.py) instead of the XLA online-softmax sweep.
    Default (None) = auto: on when running on TPU and the local sequence
    block is 128-lane tileable. `interpret=True` runs the kernel in
    interpret mode so the flash path is testable on a CPU mesh."""
    from deeplearning4j_tpu.parallel.mesh import shard_map_compat

    if use_flash is None:
        from deeplearning4j_tpu.ops.attention import flash_eligible

        t_local = q.shape[1] // mesh.shape[axis]
        use_flash = flash_eligible(t_local) and k.shape[1] == q.shape[1]

    spec = P(None, axis, None, None)
    fn = shard_map_compat(
        functools.partial(_ring_attention_local, axis_name=axis,
                          causal=causal, scale=scale, use_flash=use_flash,
                          interpret=interpret),
        mesh, (spec, spec, spec), spec)
    return fn(q, k, v)
