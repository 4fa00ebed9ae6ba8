"""Attention layers — modern extension (the RNN-era reference has none;
required so the framework serves transformer-class models at TPU scale,
per the project charter's long-context mandate).

MultiHeadAttention follows this framework's Layer contract so it composes
with MultiLayerNetwork/ComputationGraph like any reference layer. When a
mesh+seq axis is configured (see `parallel.ring_attention`), the same layer
runs sequence-parallel without code changes — the attention core is swapped.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer
from deeplearning4j_tpu.parallel.ring_attention import attention


def rope_rotate(x, positions, base: float = 10000.0, inv_freq=None):
    """Rotary position embedding (RoPE): rotate [B, T, H, Dh] per-head
    pairs by position-dependent angles. Attention scores between rotated
    q/k depend only on RELATIVE distance, so there is no learned
    position table and no absolute-length cap (modern extension; the
    RNN-era reference has no positional encodings at all).

    `positions` is [T] (one stream, or all rows at the same offset) or
    [B, T] (per-row offsets — the slot-indexed decode path, where each
    session in the batch sits at its own absolute position). `inv_freq`,
    `Dh // 2` frequencies, takes the place of `base ** (-i / half)`
    (a scaled rope: `yarn_inv_freq`)."""
    dh = x.shape[-1]
    if dh % 2:
        raise ValueError(f"RoPE needs an even head dim, got {dh}")
    half = dh // 2
    if inv_freq is None:
        freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    else:
        freqs = jnp.asarray(inv_freq, jnp.float32)
        if freqs.shape != (half,):
            raise ValueError(f"{freqs.shape[0]} frequencies for {half} "
                             f"pairs")
    ang = positions.astype(jnp.float32)[..., None] * freqs
    if ang.ndim == 2:                  # [T, half] -> [1, T, half]
        ang = ang[None]
    c = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    s = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def rms_norm(x, gain, eps: float = 1e-5, axis_name=None):
    """x / sqrt(mean(x^2) + eps) * gain over the last axis; no centring,
    no bias. The mean is taken in float32 where x is narrower. With
    `axis_name` the last axis is one device's share of the lanes and the
    mean runs over all of them: the sum of squares and the lane count are
    summed over that mesh axis."""
    wide = x.astype(jnp.promote_types(x.dtype, jnp.float32))
    if axis_name is None:
        ms = jnp.mean(jnp.square(wide), axis=-1, keepdims=True)
    else:
        ms = (jax.lax.psum(jnp.sum(jnp.square(wide), axis=-1, keepdims=True),
                           axis_name)
              / jax.lax.psum(x.shape[-1], axis_name))
    return x * jax.lax.rsqrt(ms + eps).astype(x.dtype) * gain


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, base: float, scaling: dict) -> tuple:
    """The `dim // 2` rotary frequencies of a YaRN-scaled rope
    (`rope_scaling` with `type` "yarn": `factor`, `beta_fast`,
    `beta_slow`, `original_max_position_embeddings`): pair `i` keeps
    `base^(-2i/dim)` below the pair that turns `beta_fast` times over the
    original length, is divided by `factor` above the one that turns
    `beta_slow` times, and goes linearly from one to the other between."""
    half, span = dim // 2, scaling["original_max_position_embeddings"]

    def pair_of(turns):
        return (dim * math.log(span / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(pair_of(scaling["beta_fast"])), 0)
    high = min(math.ceil(pair_of(scaling["beta_slow"])), dim - 1)
    out = []
    for i in range(half):
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        f = base ** (-2.0 * i / dim)
        out.append(f * (1.0 - ramp) + f / scaling["factor"] * ramp)
    return tuple(out)


def yarn_factors(scaling: dict) -> tuple:
    """(what multiplies cos and sin, what multiplies the softmax scale) of
    a YaRN-scaled rope: `mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)` and `mscale(factor, mscale_all_dim) ** 2`."""
    every = _yarn_mscale(scaling["factor"], scaling.get("mscale_all_dim", 0))
    one = _yarn_mscale(scaling["factor"], scaling.get("mscale", 1))
    return one / every, every * every


# a step's selection counters, in a `sparse=` layer's state: the block
# visits (token x KV group x block of keys) the selection kept, and those a
# dense causal layer would make; `fit()` publishes them as gauges
# `<name>{layer=}` where an epoch synchronises
SPARSE_COUNTERS = ("sparse_blocks_kept", "sparse_blocks_causal")


@register_layer
@dataclasses.dataclass(frozen=True)
class MultiHeadAttention(Layer):
    """Self-attention over [batch, time, features].

    `num_kv_heads < num_heads` enables grouped-query attention (GQA):
    K/V project to fewer heads and each group of `num_heads //
    num_kv_heads` query heads shares one KV head. The KV cache (and its
    per-token decode HBM traffic — the binding resource of
    autoregressive decoding on TPU) shrinks by the group factor;
    num_kv_heads=1 is multi-query attention. Modern extension (the
    RNN-era reference has no attention); default (None) is standard MHA.
    """

    n_in: Optional[int] = None
    n_out: Optional[int] = None       # model dim (defaults to n_in)
    num_heads: int = 4
    num_kv_heads: Optional[int] = None  # None -> num_heads (standard MHA)
    causal: bool = False
    attn_dropout: float = 0.0
    max_cache: int = 1024             # KV-cache length for decode stepping
    rope: bool = False                # rotary position embedding on q/k
    rope_base: float = 10000.0        # the rotary base (`rope_theta`)
    window: Optional[int] = None      # sliding-window (local) attention:
    # each position sees at most `window` keys back (causal) or within
    # |i-j| < window (bidirectional) — Mistral-style locality; O(T*w)
    # useful score mass. Windowed layers route through the banded Pallas
    # kernel when `kernel_defaults.banded_policy` approves (O(T*w) by
    # grid construction), else the dense band-masked path; the flash
    # kernel and the ring remain full-context codepaths.
    rolling_cache: bool = False       # causal+window decode streams in a
    # FIXED max_cache-slot ring buffer (Mistral's rolling KV cache):
    # slot = position % max_cache, so generation length is unbounded in
    # O(window) memory. Each step needs max_cache >= T + window - 1.
    head_dim: Optional[int] = None    # None -> n_out // num_heads; set, the
    # heads are that wide whatever n_out is (48 heads of 128 over 3072)
    qk_norm: bool = False             # RMS norm of q and k over the head,
    # one gain vector each for all heads, before the positions go on
    output_gate: bool = False         # o * sigmoid(x Wg) before Wo
    bias: bool = True                 # Wo's bias
    norm_eps: float = 1e-5            # of the q and k norms
    softmax_scale: Optional[float] = None   # None -> head_dim ** -0.5; a
    # given one (`attention_multiplier`) through the flash kernel, the ring
    # and the dense core; windows, masks, dropout and selections refuse it
    sparse: Optional[Any] = None      # `ops.sparse_attention.BlockSelection`
    # (or its values): past `dense_len` tokens every query and KV group
    # reads `topk` blocks of keys chosen from scores over mean-pooled keys
    # (InfLLM-V2), by the block-sparse kernels; at or under it the layer is
    # plain causal attention. Training and scoring only: no decode.

    def infer_n_in(self, input_type: InputType):
        upd = {}
        if self.n_in is None:
            upd["n_in"] = input_type.size
        if self.n_out is None:
            upd["n_out"] = upd.get("n_in", self.n_in)
        return dataclasses.replace(self, **upd) if upd else self

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    @property
    def _kv_heads(self) -> int:
        return (self.num_kv_heads if self.num_kv_heads is not None
                else self.num_heads)

    @property
    def _head_dim(self) -> int:
        return self.head_dim or self.n_out // self.num_heads

    def _check_heads(self):
        H, Hkv = self.num_heads, self._kv_heads
        if self.head_dim is None and self.n_out % H:
            raise ValueError(
                f"n_out {self.n_out} not divisible by num_heads {H}")
        if not 1 <= Hkv <= H or H % Hkv:
            raise ValueError(
                f"num_kv_heads {Hkv} must divide num_heads {H}")

    def init_params(self, key, input_type, dtype=jnp.float32):
        d = self.n_out
        self._check_heads()
        if self.window is not None and self.window < 1:
            raise ValueError(f"window must be >= 1, got {self.window}")
        if self.softmax_scale is not None and (self.window is not None
                                               or self.sparse is not None):
            raise ValueError("a given softmax_scale goes with neither a "
                             "window nor a block selection: their kernels "
                             "derive the scale from the head size")
        if self.rolling_cache:
            if self.window is None or not self.causal:
                raise ValueError(
                    "rolling_cache needs causal=True and a window (the "
                    "ring buffer only ever holds the last `window` keys)")
            if self.max_cache < self.window:
                raise ValueError(
                    f"rolling_cache: max_cache {self.max_cache} < window "
                    f"{self.window}; the buffer cannot hold the band")
        dh = self._head_dim
        dq, dkv = self.num_heads * dh, self._kv_heads * dh
        ks = jax.random.split(key, 5)
        winit = self._winit()
        params = {
            "Wq": winit(ks[0], (self.n_in, dq), dtype),
            "Wk": winit(ks[1], (self.n_in, dkv), dtype),
            "Wv": winit(ks[2], (self.n_in, dkv), dtype),
            "Wo": winit(ks[3], (dq, d), dtype),
        }
        if self.bias:
            params["b"] = jnp.zeros((d,), dtype)
        if self.output_gate:
            params["Wg"] = winit(ks[4], (self.n_in, dq), dtype)
        if self.qk_norm:
            params["q_norm"] = jnp.ones((dh,), dtype)
            params["k_norm"] = jnp.ones((dh,), dtype)
        if self._selection is None:
            return params, {}
        if not self.causal or self.window is not None or self.rope:
            raise ValueError("block selection needs causal=True and goes "
                             "with no window and no positions")
        return params, {k: jnp.zeros((), jnp.int32)
                        for k in SPARSE_COUNTERS}

    @property
    def _selection(self):
        from deeplearning4j_tpu.ops.sparse_attention import BlockSelection

        sel = self.sparse
        if sel is None or isinstance(sel, BlockSelection):
            return sel
        return (BlockSelection(**sel) if isinstance(sel, dict)
                else BlockSelection(*sel))

    def _qkv(self, params, x):
        """q [B, T, H, Dh], k and v [B, T, Hkv, Dh] (normed where
        `qk_norm`, positions not yet on) and the output gate [B, T, H*Dh]
        or None."""
        B, T, _ = x.shape
        dh = self._head_dim

        def split(w, heads):
            return (x @ w).reshape(B, T, heads, dh)

        q = split(params["Wq"], self.num_heads)
        k = split(params["Wk"], self._kv_heads)
        v = split(params["Wv"], self._kv_heads)
        if self.qk_norm:
            q = rms_norm(q, params["q_norm"], self.norm_eps)
            k = rms_norm(k, params["k_norm"], self.norm_eps)
        gate = (jax.nn.sigmoid(x @ params["Wg"]) if self.output_gate
                else None)
        return q, k, v, gate

    def _positioned(self, q, k):
        """q and k with the rotary positions 0..T-1 on, where `rope`."""
        if not self.rope:
            return q, k
        positions = jnp.arange(q.shape[1])
        return (rope_rotate(q, positions, self.rope_base),
                rope_rotate(k, positions, self.rope_base))

    def _project_out(self, params, o, gate):
        """[B, T, H, Dh] heads -> the layer's output, gated where the
        layer has a gate."""
        o = o.reshape(o.shape[0], o.shape[1], -1)
        if gate is not None:
            o = o * gate
        y = o @ params["Wo"]
        if self.bias:
            y = y + params["b"]
        return self._act(y)

    def decode_carry(self, batch: int, dtype=jnp.float32, *,
                     per_slot: bool = False, kv_dtype: str = None,
                     page_len: int = None, pages: int = None):
        """Preallocated KV cache for incremental decoding (the transformer
        analogue of the reference's rnnTimeStep statefulness,
        `MultiLayerNetwork.java:rnnTimeStep`): fixed [B, max_cache, Hkv,
        Dh] buffers + a write position, so every step reuses one compiled
        program instead of growing shapes. Under GQA the cache holds only
        the Hkv KV heads — the group factor comes straight off decode's
        per-token HBM traffic.

        `per_slot=True` makes the write position a [batch] vector — each
        batch row is an independent decode SLOT at its own position
        (serving sessions: rows advance at different rates, inactive
        lanes stand still). Requires causal attention.

        `kv_dtype` in ("int8", "fp8") stores K/V quantized with one f32
        scale per (token, kv-head) — `scale_k`/`scale_v` rows of
        [B, L, Hkv] ride the carry next to the caches. Quantize-on-write
        and dequantize-on-read live in `_decode`; the scale rows cost
        1/Dh of a native cache, so slots-per-chip multiplies by
        ~4·Dh/(Dh+4) at int8.

        `page_len` switches the storage to PAGED layout: a pool of
        `pages` fixed-size KV blocks `[P, page_len, Hkv, Dh]` plus a
        per-slot `page_table` [B, max_cache/page_len] int32 mapping each
        logical page to a physical block. Positions stay LOGICAL —
        `_decode` translates position -> (page_table[pos // page_len],
        pos % page_len) at the scatter/gather, so visibility arithmetic
        and RoPE are unchanged and page indices ride the trace like slot
        ids (zero recompiles under page churn). This is the KVSlotPool's
        prefix-cache layout: sessions sharing a prompt prefix point their
        tables at the same refcounted physical blocks. Requires per_slot
        and a non-rolling cache (the ring's held-index arithmetic
        addresses the monolithic slot layout). `pages` defaults to
        `batch * max_cache / page_len` — the same memory as the
        monolithic layout."""
        if self.sparse is not None:
            raise NotImplementedError(
                f"MultiHeadAttention {self.name!r} selects its key blocks "
                f"(sparse=): decoding it needs the selection inside paged "
                f"attention, which serving does not have yet")
        Dh = self._head_dim
        L = self.max_cache
        Hkv = self._kv_heads
        if per_slot and not self.causal:
            raise ValueError(
                "per-slot decode carries need causal=True (each lane's "
                "visible prefix is its own position)")
        cdt = dtype
        if kv_dtype in ("int8", "fp8"):
            if not per_slot:
                raise ValueError(
                    "quantized KV carries are a session-pool feature "
                    "(per_slot=True); the lockstep rnn_time_step path "
                    "stays native")
            cdt = jnp.int8 if kv_dtype == "int8" else jnp.float8_e4m3fn
        elif kv_dtype not in (None, "native"):
            raise ValueError(f"unknown kv_dtype {kv_dtype!r}")
        if page_len is not None:
            if not per_slot:
                raise ValueError(
                    "paged KV carries are a session-pool feature "
                    "(per_slot=True)")
            if self.rolling_cache:
                raise ValueError(
                    "paged KV carries cannot ride a rolling ring: the "
                    "ring's held-index arithmetic addresses the "
                    "monolithic slot layout")
            if page_len < 1 or L % page_len:
                raise ValueError(
                    f"max_cache {L} not divisible by page_len {page_len}")
            npg = L // page_len
            P = int(pages) if pages is not None else batch * npg
            if P < npg:
                raise ValueError(
                    f"page pool of {P} blocks cannot hold even one "
                    f"slot's {npg} logical pages")
            carry = {
                "cache_k": jnp.zeros((P, page_len, Hkv, Dh), cdt),
                "cache_v": jnp.zeros((P, page_len, Hkv, Dh), cdt),
                "page_table": jnp.zeros((batch, npg), jnp.int32),
                "pos": jnp.zeros((batch,), jnp.int32),
            }
            if kv_dtype in ("int8", "fp8"):
                carry["scale_k"] = jnp.zeros((P, page_len, Hkv),
                                             jnp.float32)
                carry["scale_v"] = jnp.zeros((P, page_len, Hkv),
                                             jnp.float32)
            return carry
        carry = {
            "cache_k": jnp.zeros((batch, L, Hkv, Dh), cdt),
            "cache_v": jnp.zeros((batch, L, Hkv, Dh), cdt),
            "pos": jnp.zeros((batch,) if per_slot else (), jnp.int32),
        }
        if kv_dtype in ("int8", "fp8"):
            carry["scale_k"] = jnp.zeros((batch, L, Hkv), jnp.float32)
            carry["scale_v"] = jnp.zeros((batch, L, Hkv), jnp.float32)
        return carry

    def _decode(self, params, x, state, mask=None):
        """One decode step: append this block's K/V at `pos`, attend the
        incoming queries over the visible cache prefix.

        Two position layouts share this method (and one compiled program
        each): a SCALAR `pos` carry steps every batch row in lockstep
        (the classic `rnn_time_step` path — `mask` is ignored, as
        before), while a VECTOR `pos` carry ([B]) steps slot-indexed
        session lanes independently. In vector mode `mask` is a [B, T]
        prefix-validity mask: padded tokens are dropped from the cache
        write (scatter index pushed out of range, `mode="drop"`) and do
        not advance the row's position, so a prefill chunk and a
        single-token step can share one padded bucket shape.

        A `page_table` in the carry switches both the scatter and the
        reads to PAGED addressing (see `decode_carry`): logical position
        j lives at physical row `page_table[j // Lp]`, offset `j % Lp`.
        Everything position-flavored — visibility, RoPE, overflow
        poison — keeps operating on logical positions, so the paged and
        monolithic layouts are bit-identical by construction."""
        B, T, _ = x.shape
        H = self.num_heads
        Hkv = self._kv_heads
        Dh = self._head_dim
        paged = "page_table" in state
        if paged:
            if self.rolling_cache:
                raise ValueError(
                    "paged KV caches cannot ride a rolling ring")
            pt = state["page_table"]                   # [B, NP] int32
            npg = pt.shape[1]
            Lp = state["cache_k"].shape[1]
            L = npg * Lp
        else:
            L = state["cache_k"].shape[1]
        if self.rolling_cache:
            # per-step feasibility is static: the T new keys plus the
            # window tail of the oldest query must coexist in the ring
            if T + self.window - 1 > L:
                raise ValueError(
                    f"rolling decode step of {T} tokens needs max_cache "
                    f">= {T + self.window - 1} (window {self.window}), "
                    f"have {L}")
        elif T > L:
            raise ValueError(f"decode step of {T} tokens > max_cache {L}")
        pos = state["pos"]
        per_slot = getattr(pos, "ndim", 0) == 1
        if per_slot and not self.causal:
            raise ValueError("per-slot decode needs causal=True")
        if paged and not per_slot:
            raise ValueError("paged KV caches require per-slot mode")
        quant = "scale_k" in state
        if quant and not per_slot:
            raise ValueError("quantized KV carries require per-slot mode")
        if (not self.rolling_cache and not per_slot
                and not isinstance(pos, jax.core.Tracer)
                and int(pos) + T > L):
            raise ValueError(
                f"KV cache overflow: pos {int(pos)} + step {T} > "
                f"max_cache {L}; raise max_cache or clear state")

        q, k, v, gate = self._qkv(params, x)
        if self.softmax_scale is not None:
            # the decode cores divide by sqrt(Dh): the given scale rides q
            q = q * jnp.asarray(self.softmax_scale * math.sqrt(Dh), q.dtype)
        if per_slot:
            valid = None if mask is None else (mask > 0)       # [B, T]
            n_new = (jnp.full(pos.shape, T, pos.dtype) if valid is None
                     else valid.sum(axis=1).astype(pos.dtype))  # [B]
            q_ids = pos[:, None] + jnp.arange(T)               # [B, T]
            if self.rope:
                q = rope_rotate(q, q_ids, self.rope_base)
                k = rope_rotate(k, q_ids, self.rope_base)
            rows = jnp.arange(B)[:, None]
            tgt = q_ids % L if self.rolling_cache else q_ids
            if valid is not None:
                # padded tokens scatter out of range -> dropped, so a
                # short chunk in a wide bucket never dirties the cache
                tgt = jnp.where(valid, tgt, L)
            if paged:
                # logical target -> (physical page, in-page offset);
                # padded/overflowing rows land at offset Lp, out of the
                # page dim's bounds, so mode="drop" keeps them out
                # exactly like the monolithic layout's tgt >= L. The
                # page indices are traced gathers from the carry —
                # page churn never mints a new program.
                i0 = pt[rows, jnp.clip(tgt // Lp, 0, npg - 1)]  # [B, T]
                i1 = jnp.where(tgt < L, tgt % Lp, Lp)
            else:
                i0, i1 = rows, tgt
            cdt = state["cache_k"].dtype
            if quant:
                # quantize-on-write: one f32 scale per (token, kv-head),
                # amax-scaled to the storage format's dynamic range.
                # Zero-amax rows keep scale 1 so dequant stays finite.
                qmax = 127.0 if cdt == jnp.int8 else 448.0

                def _q(val):
                    amax = jnp.max(jnp.abs(val), axis=-1)      # [B, T, Hkv]
                    sc = jnp.where(amax > 0.0, amax / qmax, 1.0)
                    scaled = val.astype(jnp.float32) / sc[..., None]
                    if cdt == jnp.int8:
                        qv = jnp.clip(jnp.round(scaled), -127.0,
                                      127.0).astype(jnp.int8)
                    else:
                        qv = scaled.astype(cdt)
                    return qv, sc.astype(jnp.float32)

                kq, sk = _q(k)
                vq, sv = _q(v)
                ck = state["cache_k"].at[i0, i1].set(kq, mode="drop")
                cv = state["cache_v"].at[i0, i1].set(vq, mode="drop")
                csk = state["scale_k"].at[i0, i1].set(sk, mode="drop")
                csv = state["scale_v"].at[i0, i1].set(sv, mode="drop")
            else:
                ck = state["cache_k"].at[i0, i1].set(
                    k.astype(cdt), mode="drop")
                cv = state["cache_v"].at[i0, i1].set(
                    v.astype(cdt), mode="drop")
            if self.rolling_cache:
                # per-row held-position arithmetic (see scalar branch)
                end = pos + n_new - 1                          # [B]
                j = jnp.arange(L)[None, :]
                held = end[:, None] - ((end[:, None] - j) % L)  # [B, L]
                held = held[:, None, :]                     # [B, 1, L]
                qe = q_ids[:, :, None]                      # [B, T, 1]
                vis = ((held >= 0) & (held <= qe)
                       & (held > qe - self.window))         # [B, T, L]
            else:
                # per-row overflow poison (tracer-safe, like scalar)
                q = jnp.where((pos + n_new <= L)[:, None, None, None],
                              q, jnp.nan)
                k_ids = jnp.arange(L)[None, None, :]
                qe = q_ids[:, :, None]
                vis = k_ids <= qe
                if self.window is not None:
                    vis = vis & (k_ids > qe - self.window)
            pos_new = pos + n_new
        elif self.rolling_cache:
            # Mistral-style ring buffer: slot = global position mod L.
            # The write is a scatter (it may wrap the boundary); each
            # slot's CURRENT occupant is recovered arithmetically from
            # the newest written global position, so visibility needs no
            # stored metadata.
            if self.rope:
                positions = pos + jnp.arange(T)
                q = rope_rotate(q, positions, self.rope_base)
                k = rope_rotate(k, positions, self.rope_base)
            slots = (pos + jnp.arange(T)) % L
            ck = state["cache_k"].at[:, slots].set(
                k.astype(state["cache_k"].dtype))
            cv = state["cache_v"].at[:, slots].set(
                v.astype(state["cache_v"].dtype))
            end = pos + T - 1               # newest written global pos
            j = jnp.arange(L)
            held = end - ((end - j) % L)    # global pos held in slot j
            q_ids = pos + jnp.arange(T)[:, None]
            vis = ((held[None, :] >= 0)     # slot ever written
                   & (held[None, :] <= q_ids)          # causal
                   & (held[None, :] > q_ids - self.window))
            pos_new = pos + T
        else:
            # Tracer-safe overflow poison: under jit the eager check
            # above cannot fire, and dynamic_update_slice would silently
            # clamp the write into the last rows — poison the output
            # with NaN instead so overflow is loud, not wrong.
            if self.rope:
                # rotate with ABSOLUTE positions continuing from the
                # carry; the cache stores rotated keys (standard RoPE)
                positions = pos + jnp.arange(T)
                q = rope_rotate(q, positions, self.rope_base)
                k = rope_rotate(k, positions, self.rope_base)
            q = jnp.where(pos + T <= L, q, jnp.nan)
            z = jnp.zeros((), pos.dtype)   # index dtypes must match `pos`
            ck = jax.lax.dynamic_update_slice(
                state["cache_k"], k.astype(state["cache_k"].dtype),
                (z, pos, z, z))
            cv = jax.lax.dynamic_update_slice(
                state["cache_v"], v.astype(state["cache_v"].dtype),
                (z, pos, z, z))
            k_ids = jnp.arange(L)[None, :]
            q_ids = pos + jnp.arange(T)[:, None]
            # causal: each new query sees cache + itself; non-causal:
            # the whole written prefix (never the unwritten tail)
            vis = k_ids <= q_ids if self.causal else k_ids < pos + T
            if self.window is not None:
                # sliding window: `window` keys back; bidirectional also
                # bounds the forward side (|i-j| < window, matching the
                # dense band — still never past the written prefix)
                vis = vis & (k_ids > q_ids - self.window)
                if not self.causal:
                    vis = vis & (k_ids < q_ids + self.window)
            pos_new = pos + T
        # [T, L] (lockstep) or [B, T, L] (per-slot) -> broadcastable
        vb = vis if vis.ndim == 3 else vis[None]
        if paged:
            # logical [B, L, Hkv, Dh] view for the dense paths: gather
            # each slot's page chain back into position order. The
            # banded kernel below never materializes this — its
            # BlockSpec index_map reads the page table directly.
            ck_r = jnp.take(ck, pt, axis=0).reshape(B, L, Hkv, Dh)
            cv_r = jnp.take(cv, pt, axis=0).reshape(B, L, Hkv, Dh)
            csk_r = (jnp.take(csk, pt, axis=0).reshape(B, L, Hkv)
                     if quant else None)
            csv_r = (jnp.take(csv, pt, axis=0).reshape(B, L, Hkv)
                     if quant else None)
        else:
            ck_r, cv_r = ck, cv
            csk_r, csv_r = (csk, csv) if quant else (None, None)
        if quant:
            # dequantize-on-read for the dense fallback: the banded
            # kernel path below instead fuses this product into its
            # block loads and never materializes the f32 cache
            ck_a = ck_r.astype(q.dtype) * csk_r.astype(q.dtype)[..., None]
            cv_a = cv_r.astype(q.dtype) * csv_r.astype(q.dtype)[..., None]
        else:
            ck_a, cv_a = ck_r, cv_r
        dpol = None
        if T == 1:
            from deeplearning4j_tpu.ops.kernel_defaults import (
                decode_attention_policy,
            )

            dpol = decode_attention_policy(L, H, Hkv)
        use_banded = dpol is not None and dpol.kind == "banded"
        if use_banded and paged and jax.default_backend() == "tpu" \
                and Lp % 128:
            # the paged kernel's cache block IS one page; a page that
            # Mosaic cannot tile falls back to the dense gather
            use_banded = False
        if use_banded:
            # Single-token step: the banded decode kernel reads the cache
            # in its stored [*, L, Hkv, Dh] layout (same arithmetic as
            # `vis` above, held-index ring included) without broadcasting
            # KV to H heads or materializing [B, H, 1, L] scores in HBM.
            # Paged carries route to the paged variant: the page table
            # rides the scalar-prefetch lane and the kernel's index_map
            # resolves logical block -> physical page, so shared-prefix
            # sessions read the same HBM blocks with no gather.
            if per_slot:
                dec_pos = pos
                dec_end = (pos + n_new - 1 if self.rolling_cache
                           else pos)
            else:
                dec_pos = jnp.broadcast_to(pos, (B,))
                dec_end = dec_pos
            if paged:
                from deeplearning4j_tpu.ops.banded_attention import (
                    paged_decode_attention,
                )

                o = paged_decode_attention(
                    q[:, 0], ck, cv, pt, dec_pos.astype(jnp.int32),
                    window=self.window,
                    interpret=jax.default_backend() != "tpu",
                    scale_k=csk if quant else None,
                    scale_v=csv if quant else None)
            else:
                from deeplearning4j_tpu.ops.banded_attention import (
                    banded_decode_attention,
                )

                o = banded_decode_attention(
                    q[:, 0], ck, cv, dec_pos.astype(jnp.int32),
                    dec_end.astype(jnp.int32), window=self.window,
                    rolling=self.rolling_cache, block_l=dpol.block_l,
                    interpret=jax.default_backend() != "tpu",
                    scale_k=csk if quant else None,
                    scale_v=csv if quant else None)
            o = o[:, None]
        elif Hkv != H:
            # GQA: group the query heads against the Hkv-wide cache in
            # the einsum itself — the cache is never broadcast to H
            # heads, so the per-token HBM sweep (decode's binding
            # resource) really is Hkv/H of full MHA
            G = H // Hkv
            qg = q.reshape(B, T, Hkv, G, Dh)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, ck_a) / jnp.sqrt(Dh)
            s = jnp.where(vb[:, None, None], s, -1e30)
            o = jnp.einsum("bhgqk,bkhd->bqhgd",
                           jax.nn.softmax(s, axis=-1), cv_a)
            o = o.reshape(B, T, H, Dh)
        else:
            s = jnp.einsum("bqhd,bkhd->bhqk", q, ck_a) / jnp.sqrt(Dh)
            s = jnp.where(vb[:, None], s, -1e30)
            o = jnp.einsum("bhqk,bkhd->bqhd",
                           jax.nn.softmax(s, axis=-1), cv_a)
        y = self._project_out(params, o, gate)
        new_state = {"cache_k": ck, "cache_v": cv, "pos": pos_new}
        if paged:
            new_state["page_table"] = pt
        if quant:
            new_state["scale_k"] = csk
            new_state["scale_v"] = csv
        return y, new_state

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        if state is not None and "cache_k" in state:
            return self._decode(params, x, state, mask=mask)
        q, k, v, gate = self._qkv(params, x)
        q, k = self._positioned(q, k)
        sel = self._selection
        if sel is not None:
            o, counters = self._selected_core(q, k, v, sel, train=train,
                                              rng=rng, mask=mask)
            return (self._project_out(params, o, gate),
                    {**(state or {}), **counters})
        with jax.named_scope("attention_core"):
            o = self._core(q, k, v, train=train, rng=rng, mask=mask)
        return self._project_out(params, o, gate), state

    def _selected_core(self, q, k, v, sel, *, train, rng, mask):
        """A layer with `sparse`: past `dense_len` tokens the selection
        (scope `sparse_select`) and attention over the blocks it kept
        (`sparse_attention_core`), else the dense causal core. Returns the
        heads and the step's `SPARSE_COUNTERS`."""
        from deeplearning4j_tpu.ops import sparse_attention as sp

        B, T = q.shape[0], q.shape[1]
        if T <= sel.dense_len:
            sp.note_dense_run()
            with jax.named_scope("attention_core"):
                o = self._core(q, k, v, train=train, rng=rng, mask=mask)
            causal = sp.causal_visits(B, self._kv_heads, T, sel.block_size)
            return o, dict.fromkeys(SPARSE_COUNTERS,
                                    jnp.asarray(causal, jnp.int32))
        if mask is not None or (train and self.attn_dropout):
            raise ValueError("block selection takes no padding mask and "
                             "no attention dropout")
        from deeplearning4j_tpu.ops.kernel_defaults import sparse_policy

        from deeplearning4j_tpu.ops.attention import name_block_residual

        with jax.named_scope("sparse_select"):
            # named: a checkpointed layer keeps the choice (bools) and its
            # recomputed forward does not choose again
            allow = name_block_residual(sp.select_blocks(q, k, sel),
                                        "block_selection")
            kept, causal = sp.selection_counts(allow, sel.block_size)
        pol = sparse_policy(T, sel.block_size)
        with jax.named_scope("sparse_attention_core"):
            if pol.kind == "kernel":
                o = sp.block_sparse_attention(
                    q, k, v, allow, sel.block_size, None, pol.block_q,
                    pol.block_k, False)
            else:
                o = sp.masked_attention(q, k, v, allow, sel.block_size)
        return o, dict(zip(SPARSE_COUNTERS, (kept, causal)))

    def _core(self, q, k, v, *, train, rng, mask):
        """softmax(q k^T / sqrt(Dh)) v (or times `softmax_scale`, where
        that is given) over [B, T, H, Dh] queries and
        [B, T, Hkv, Dh] keys and values, by the path the policies pick."""
        T, H, Hkv = q.shape[1], self.num_heads, self._kv_heads

        def broadcast_kv(k, v):
            # GQA fallback for the H-wide attention cores (ring, dense):
            # broadcast KV heads up to the query heads. The banded and
            # flash kernels read the native Hkv layout by `h // G`.
            if Hkv != H:
                k = jnp.repeat(k, H // Hkv, axis=2)
                v = jnp.repeat(v, H // Hkv, axis=2)
            return k, v

        from deeplearning4j_tpu.parallel.ring_attention import (
            current_sequence_mesh,
        )

        seq_ctx = current_sequence_mesh()
        drop = (self.attn_dropout
                if train and self.attn_dropout and rng is not None else 0.0)
        if seq_ctx is not None and (drop or mask is not None
                                    or self.window is not None):
            # The user asked for sequence parallelism (usually because T
            # is too long for dense attention) but attention-dropout, a
            # padding mask, or a sliding window forces the dense path —
            # degrade loudly.
            import warnings

            why = ("attn_dropout" if drop
                   else "a sliding window" if self.window is not None
                   else "a padding mask")
            warnings.warn(
                f"sequence_parallel is active but {why} forces the dense "
                f"[T, T] attention path; the ring is bypassed for this "
                f"layer", stacklevel=2)
            seq_ctx = None
        if seq_ctx is not None:
            # sequence_parallel(mesh) context: T is sharded over the seq
            # axis; K/V ride the ring (parallel.ring_attention) so no
            # device holds the [T, T] scores. Padding masks and
            # attention-dropout keep the dense path.
            from deeplearning4j_tpu.parallel.ring_attention import (
                ring_self_attention,
            )

            k, v = broadcast_kv(k, v)
            return ring_self_attention(q, k, v, seq_ctx.mesh,
                                       axis=seq_ctx.axis, causal=self.causal,
                                       scale=self.softmax_scale)
        if self.window is not None and mask is None and not drop:
            # Sliding window (no mask/dropout): the banded kernel serves
            # this O(T·w) by grid construction, forward and backward,
            # GQA-native. Banded-vs-dense is the policy's call
            # (kernel_defaults.banded_policy; env hatch
            # DL4J_TPU_ATTN=banded|dense).
            from deeplearning4j_tpu.ops.kernel_defaults import (
                banded_policy,
            )

            pol = banded_policy(T, H, Hkv, train=train)
            if pol.kind == "banded":
                from deeplearning4j_tpu.ops.banded_attention import (
                    banded_attention,
                )

                return banded_attention(
                    q, k, v, self.window, self.causal, None, pol.block_q,
                    pol.block_k, jax.default_backend() != "tpu")
            k, v = broadcast_kv(k, v)
            return self._masked_attention(q, k, v, None, self.causal,
                                          window=self.window)
        if mask is not None or drop:
            # Padding mask and attention-weight dropout need the dense
            # path (dropout perturbs the post-softmax weights, which
            # never materialize inside the fused kernels).
            k, v = broadcast_kv(k, v)
            return self._masked_attention(q, k, v, mask, self.causal,
                                          dropout=drop, rng=rng,
                                          window=self.window,
                                          scale=self.softmax_scale)
        # Flash-vs-dense, tile config, and backward selection all come
        # from ops/kernel_defaults.attention_policy: flash where dense
        # memory pressure makes the O(T) path mandatory, dense below.
        # Env hatches: DL4J_TPU_ATTN*.
        from deeplearning4j_tpu.ops.kernel_defaults import attention_policy

        pol = attention_policy(T, train=train)
        if pol.kind == "flash":
            from deeplearning4j_tpu.ops.attention import flash_attention

            return flash_attention(q, k, v, self.causal, self.softmax_scale,
                                   pol.block_q, pol.block_k, False,
                                   pol.backward)
        k, v = broadcast_kv(k, v)
        return attention(q, k, v, causal=self.causal,
                         scale=self.softmax_scale)

    @staticmethod
    def _masked_attention(q, k, v, mask, causal=False, dropout=0.0,
                          rng=None, window=None, scale=None):
        d = q.shape[-1]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k)
        s = s / jnp.sqrt(d) if scale is None else s * scale
        bias = jnp.zeros((), s.dtype)
        if mask is not None:
            bias = jnp.where(mask[:, None, None, :] > 0, 0.0, -1e30)
        if causal:
            t = s.shape[-1]
            band = jnp.tril(jnp.ones((t, t), jnp.bool_))
            bias = bias + jnp.where(band[None, None], 0.0, -1e30)
        if window is not None:
            # sliding window: `window` keys back (causal combines with
            # the tril above); bidirectional keeps |i-j| < window
            tq, tk = s.shape[-2], s.shape[-1]
            qi = jnp.arange(tq)[:, None]
            ki = jnp.arange(tk)[None, :]
            local = (ki > qi - window) if causal else (
                jnp.abs(qi - ki) < window)
            bias = bias + jnp.where(local[None, None], 0.0, -1e30)
        p = jax.nn.softmax(s + bias, axis=-1)
        if dropout:
            # Inverted dropout on the attention weights (the standard
            # attention-dropout placement, post-softmax pre-V).
            keep = 1.0 - dropout
            keep_mask = jax.random.bernoulli(rng, keep, p.shape)
            p = jnp.where(keep_mask, p / keep, 0.0)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@register_layer
@dataclasses.dataclass(frozen=True)
class PositionEmbeddingLayer(Layer):
    """Learned absolute position embedding added to [B, T, d] activations
    (extension: pairs with EmbeddingSequenceLayer for transformer inputs)."""

    CONSUMES = "rnn"   # [B, T, d] — shape-preserving sequence layer

    max_length: int = 512
    n_out: Optional[int] = None

    def infer_n_in(self, input_type: InputType):
        if self.n_out is None:
            return dataclasses.replace(self, n_out=input_type.size)
        return self

    def init_params(self, key, input_type, dtype=jnp.float32):
        d = self.n_out or input_type.size
        return {"P": 0.02 * jax.random.normal(
            key, (self.max_length, d), dtype)}, {}

    def decode_carry(self, batch: int, dtype=jnp.float32, *,
                     per_slot: bool = False, kv_dtype: str = None,
                     page_len: int = None, pages: int = None):
        # no KV here — kv_dtype/page geometry are accepted (and ignored)
        # so the session-carry builder can pass one policy to every
        # decode layer
        return {"pos": jnp.zeros((batch,) if per_slot else (), jnp.int32)}

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None):
        t = x.shape[1]
        if t > self.max_length:
            raise ValueError(f"sequence length {t} > max_length "
                             f"{self.max_length}")
        if state is not None and "pos" in state:
            # decode stepping: positions continue from the carry offset
            pos = state["pos"]
            if getattr(pos, "ndim", 0) == 1:
                # per-slot vector positions (session decode): each row
                # gathers its own offsets; `mask` marks the valid prefix
                # of a padded chunk, which alone advances the position
                valid = None if mask is None else (mask > 0)
                n_new = (jnp.full(pos.shape, t, pos.dtype)
                         if valid is None
                         else valid.sum(axis=1).astype(pos.dtype))
                positions = pos[:, None] + jnp.arange(t)       # [B, t]
                p = jnp.take(params["P"],
                             jnp.minimum(positions, self.max_length - 1),
                             axis=0)                           # [B, t, d]
                # tracer-safe per-row overflow poison
                p = jnp.where((pos + n_new <= self.max_length)
                              [:, None, None], p, jnp.nan)
                return x + p, {"pos": pos + n_new}
            if (not isinstance(pos, jax.core.Tracer)
                    and int(pos) + t > self.max_length):
                raise ValueError(
                    f"decode position {int(pos)} + {t} > max_length "
                    f"{self.max_length}")
            p = jax.lax.dynamic_slice(
                params["P"], (pos, jnp.zeros((), pos.dtype)),
                (t, params["P"].shape[1]))
            # tracer-safe overflow poison (see MultiHeadAttention._decode)
            p = jnp.where(pos + t <= self.max_length, p, jnp.nan)
            return x + p[None], {"pos": pos + t}
        return x + params["P"][None, :t, :], state


@register_layer
@dataclasses.dataclass(frozen=True)
class TransformerEncoderBlock(Layer):
    """Pre-norm transformer block: x + MHA(norm(x)), then x + FFN(norm(x)).

    Modern extension (no reference counterpart — SURVEY §5 notes the
    reference predates attention). Composes the framework's own pieces:
    MultiHeadAttention (policy-dispatched attention core, ring attention
    under a seq mesh, GQA via num_kv_heads) and either a dense FFN or a
    MoEFeedForward (set n_experts > 0) for conditional compute.

    `norm="rms"` swaps LayerNorm for RMSNorm (no centering, no bias —
    one fewer reduction sweep per norm, the TPU-friendly modern choice)
    and `ffn_activation="swiglu"` swaps the GELU MLP for the gated
    SwiGLU variant; together with rope=True and num_kv_heads they make
    the block Llama-architecture-shaped.
    """

    CONSUMES = "rnn"   # [B, T, d] sequence activations

    n_in: Optional[int] = None
    num_heads: int = 4
    num_kv_heads: Optional[int] = None   # < num_heads -> GQA (see MHA)
    ffn_mult: int = 4
    causal: bool = True
    n_experts: int = 0            # 0 = dense FFN; >0 = MoE
    moe_k: int = 2
    max_cache: int = 1024         # KV-cache length for decode stepping
    rope: bool = False            # rotary position embedding on q/k
    norm: str = "layer"           # "layer" | "rms"
    ffn_activation: str = "gelu"  # "gelu" | "swiglu"
    window: Optional[int] = None  # sliding-window attention (see MHA)
    rolling_cache: bool = False   # ring-buffer decode cache (see MHA)

    def infer_n_in(self, input_type: InputType):
        if self.n_in is None:
            return dataclasses.replace(self, n_in=input_type.size)
        return self

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def _sub(self):
        d = self.n_in
        attn = MultiHeadAttention(
            n_in=d, n_out=d, num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads, causal=self.causal,
            activation="identity", weight_init=self.weight_init,
            max_cache=self.max_cache, rope=self.rope, window=self.window,
            rolling_cache=self.rolling_cache)
        if self.n_experts > 0:
            from deeplearning4j_tpu.parallel.moe import MoEFeedForward

            ffn = MoEFeedForward(
                n_in=d, n_experts=self.n_experts, k=self.moe_k,
                hidden_mult=self.ffn_mult, activation="gelu",
                weight_init=self.weight_init, residual=False)
        else:
            ffn = None
        return attn, ffn

    def init_params(self, key, input_type, dtype=jnp.float32):
        d = self.n_in
        if self.norm not in ("layer", "rms"):
            raise ValueError(f"norm must be 'layer' or 'rms', "
                             f"got {self.norm!r}")
        if self.ffn_activation not in ("gelu", "swiglu"):
            raise ValueError(f"ffn_activation must be 'gelu' or 'swiglu', "
                             f"got {self.ffn_activation!r}")
        if self.ffn_activation == "swiglu" and self.n_experts > 0:
            raise ValueError(
                "ffn_activation='swiglu' applies to the dense FFN; with "
                "n_experts > 0 the MoE experts define their own "
                "activation (a silently-ignored config must not serde "
                "round-trip as if it trained SwiGLU)")
        ks = jax.random.split(key, 4)
        attn, moe = self._sub()
        params = {"ln1_g": jnp.ones((d,), dtype),
                  "ln2_g": jnp.ones((d,), dtype)}
        if self.norm == "layer":    # RMSNorm is bias-free
            params["ln1_b"] = jnp.zeros((d,), dtype)
            params["ln2_b"] = jnp.zeros((d,), dtype)
        ap, _ = attn.init_params(ks[0], input_type, dtype)
        params.update({f"attn_{k}": v for k, v in ap.items()})
        if moe is not None:
            mp, _ = moe.init_params(ks[1], input_type, dtype)
            params.update({f"moe_{k}": v for k, v in mp.items()})
        else:
            h = self.ffn_mult * d
            winit = self._winit()
            params.update({
                "ffn_w1": winit(ks[1], (d, h), dtype),
                "ffn_b1": jnp.zeros((h,), dtype),
                "ffn_w2": winit(ks[2], (h, d), dtype),
                "ffn_b2": jnp.zeros((d,), dtype),
            })
            if self.ffn_activation == "swiglu":
                # gated branch: silu(x W1) * (x W3) -> W2 (bias-free
                # gate matrix, the standard SwiGLU parameterization)
                params["ffn_w3"] = winit(ks[3], (d, h), dtype)
        return params, {}

    def _norm_apply(self, x, params, prefix):
        g = params[f"{prefix}_g"]
        if self.norm == "rms":
            # no centering, no bias: one reduction sweep instead of two
            return rms_norm(x, g)
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * g \
            + params[f"{prefix}_b"]

    def decode_carry(self, batch: int, dtype=jnp.float32, *,
                     per_slot: bool = False, kv_dtype: str = None,
                     page_len: int = None, pages: int = None):
        attn, _ = self._sub()
        return {"attn": attn.decode_carry(batch, dtype, per_slot=per_slot,
                                          kv_dtype=kv_dtype,
                                          page_len=page_len, pages=pages)}

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None):
        attn, moe = self._sub()
        ap = {k[5:]: v for k, v in params.items() if k.startswith("attn_")}
        h = self._norm_apply(x, params, "ln1")
        attn_carry = state.get("attn") if state else None
        a, a_st = attn.apply(ap, h, state=attn_carry, train=train, rng=rng,
                             mask=mask)
        x = x + a
        h = self._norm_apply(x, params, "ln2")
        new_state = {}
        if attn_carry is not None:
            new_state["attn"] = a_st
        if moe is not None:
            mp = {k[4:]: v for k, v in params.items() if k.startswith("moe_")}
            b_, t_, d_ = h.shape
            y, st = moe.apply(mp, h.reshape(b_ * t_, d_), state=None,
                              train=train, rng=rng)
            y = y.reshape(b_, t_, d_)
            if "aux_loss" in st:
                new_state["aux_loss"] = st["aux_loss"]
        elif self.ffn_activation == "swiglu":
            gate = jax.nn.silu(h @ params["ffn_w1"] + params["ffn_b1"])
            y = (gate * (h @ params["ffn_w3"])) @ params["ffn_w2"] \
                + params["ffn_b2"]
        else:
            y = jax.nn.gelu(h @ params["ffn_w1"] + params["ffn_b1"])
            y = y @ params["ffn_w2"] + params["ffn_b2"]
        y = self._maybe_dropout(y, train, rng)
        return x + y, new_state


@register_layer
@dataclasses.dataclass(frozen=True)
class SandwichTransformerBlock(Layer):
    """A transformer block with a norm on both sides of each half:
    `h = x + norm(attention(norm(x)))`, then `h + norm(ffn(norm(h)))`, all
    four RMS norms with a gain and nothing else, no bias anywhere.

    The attention half is a `MultiHeadAttention` with this block's options
    handed through (GQA, `head_dim`, `qk_norm`, `output_gate`, `window`,
    `rope` at `rope_base`: a layer with `rope=False` sees no positions at
    all). The other
    half is a SwiGLU of `ffn_width`, or, with `n_experts`, an
    `ExpertFeedForward` (`parallel/moe.py`): a router over `n_experts`
    with `moe_k` a token, of which this device holds `experts_held`
    (first, count), experts and `n_shared` shared experts of
    `expert_width`. Leaves: `ln1_g` to `ln4_g`, `attn_*`, and `ffn_w1`,
    `ffn_w3`, `ffn_w2` or `moe_*`.

    Under `gradient_checkpointing` the block keeps both halves' outputs
    (two `[B, T, d]` tensors) beside its input: the norm after each reads
    it, and the recomputed forward then leaves out the product that made
    it (`ops/attention.name_block_residual`)."""

    CONSUMES = "rnn"   # [B, T, d] sequence activations

    n_in: Optional[int] = None
    num_heads: int = 4
    num_kv_heads: Optional[int] = None
    head_dim: Optional[int] = None
    qk_norm: bool = False
    output_gate: bool = False
    causal: bool = True
    rope: bool = False
    rope_base: float = 10000.0
    window: Optional[int] = None
    max_cache: int = 1024
    ffn_width: Optional[int] = None      # dense half; None -> 4 x n_in
    n_experts: int = 0                   # 0 = dense SwiGLU; >0 = experts
    experts_held: Optional[Any] = None   # (first, count); None -> all
    moe_k: int = 2
    expert_width: Optional[int] = None
    n_shared: int = 0
    score: str = "softmax"
    selection_bias: bool = False
    route_norm: bool = False
    route_scale: float = 1.0
    eps: float = 1e-5

    def infer_n_in(self, input_type: InputType):
        if self.n_in is None:
            return dataclasses.replace(self, n_in=input_type.size)
        return self

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def _sub(self):
        d = self.n_in
        attn = MultiHeadAttention(
            n_in=d, n_out=d, num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads, head_dim=self.head_dim,
            qk_norm=self.qk_norm, output_gate=self.output_gate, bias=False,
            causal=self.causal, activation="identity",
            weight_init=self.weight_init, max_cache=self.max_cache,
            rope=self.rope, rope_base=self.rope_base, window=self.window)
        moe = None
        if self.n_experts > 0:
            from deeplearning4j_tpu.parallel.moe import ExpertFeedForward

            held = self.experts_held
            moe = ExpertFeedForward(
                n_in=d, width=self.expert_width, n_experts=self.n_experts,
                held=None if held is None else tuple(held), k=self.moe_k,
                score=self.score, selection_bias=self.selection_bias,
                route_norm=self.route_norm, route_scale=self.route_scale,
                n_shared=self.n_shared, weight_init=self.weight_init)
        return attn, moe

    def init_params(self, key, input_type, dtype=jnp.float32):
        d = self.n_in
        ks = jax.random.split(key, 5)
        attn, moe = self._sub()
        params = {f"ln{i}_g": jnp.ones((d,), dtype) for i in (1, 2, 3, 4)}
        ap, _ = attn.init_params(ks[0], input_type, dtype)
        params.update({f"attn_{k}": v for k, v in ap.items()})
        if moe is None:
            h = self.ffn_width or 4 * d
            winit = self._winit()
            params.update(ffn_w1=winit(ks[1], (d, h), dtype),
                          ffn_w3=winit(ks[2], (d, h), dtype),
                          ffn_w2=winit(ks[3], (h, d), dtype))
            return params, {}
        mp, state = moe.init_params(ks[4], input_type, dtype)
        params.update({f"moe_{k}": v for k, v in mp.items()})
        return params, state

    def decode_carry(self, batch: int, dtype=jnp.float32, **kw):
        return {"attn": self._sub()[0].decode_carry(batch, dtype, **kw)}

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None):
        from deeplearning4j_tpu.ops.attention import name_block_residual

        attn, moe = self._sub()
        sub = lambda prefix: {k[len(prefix):]: v for k, v in params.items()
                              if k.startswith(prefix)}
        norm = lambda v, i: rms_norm(v, params[f"ln{i}_g"], self.eps)
        carry = state.get("attn") if state else None
        a, a_st = attn.apply(sub("attn_"), norm(x, 1), state=carry,
                             train=train, rng=rng, mask=mask)
        # both halves' outputs are named: the norm after each reads it, so
        # a checkpointed block that did not keep them would run `Wo`, the
        # down-projection and the experts' tier again only to have them
        x = x + norm(name_block_residual(a, "sublayer_out"), 2)
        h = norm(x, 3)
        new_state = {} if carry is None else {"attn": a_st}
        if moe is None:
            with jax.named_scope("ffn"):
                y = (jax.nn.silu(h @ params["ffn_w1"])
                     * (h @ params["ffn_w3"])) @ params["ffn_w2"]
        else:
            y, counters = moe.apply(sub("moe_"), h, train=train, rng=rng)
            new_state.update(counters)
        y = name_block_residual(y, "sublayer_out")
        return x + norm(y, 4), new_state


@register_layer
@dataclasses.dataclass(frozen=True)
class LinearAttention(MultiHeadAttention):
    """Decayed linear attention over [batch, time, features] (Lightning
    Attention-2; the `lightning-attn` mixer of MiniCPM-SALA): `S_t =
    lambda_h S_{t-1} + k_t^T v_t`, `o_t = q_t S_t / sqrt(Dh)`, no softmax.
    The projections, the per-head q and k norms, the positions, the gate
    and `Wo` are `MultiHeadAttention`'s; the core is
    `ops/linear_attention.py`'s chunked scan under the scope
    `linear_attention_core`. `lambda_h` is no parameter: head `h` of layer
    `decay_layer` among `decay_layers` decays by
    `exp(-2^(-8h/H) (1 - decay_layer / (decay_layers - 1) + 1e-5))`.
    `output_norm`: an RMS norm with a gain (leaf `o_norm`) over the
    concatenated heads before the gate. Training and scoring only."""

    causal: bool = True
    bias: bool = False
    output_norm: bool = False
    decay_layer: int = 0
    decay_layers: int = 1

    def init_params(self, key, input_type, dtype=jnp.float32):
        if not self.causal or self.window is not None or self.sparse:
            raise ValueError("LinearAttention is causal and takes neither "
                             "a window nor a block selection")
        params, _ = super().init_params(key, input_type, dtype)
        if self.output_norm:
            params["o_norm"] = jnp.ones(
                (self.num_heads * self._head_dim,), dtype)
        return params, {}

    def decode_carry(self, batch: int, dtype=jnp.float32, **kw):
        raise NotImplementedError(
            f"LinearAttention {self.name!r} has no decode carry yet: its "
            f"[Dh, Dh] state a head wants snapshots in the session "
            f"carries, which serving does not have")

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None):
        if mask is not None:
            raise ValueError("LinearAttention takes no padding mask")
        from deeplearning4j_tpu.ops.linear_attention import (
            decay_rates, linear_attention,
        )

        q, k, v, gate = self._qkv(params, x)
        q, k = self._positioned(q, k)
        with jax.named_scope("linear_attention_core"):
            o = linear_attention(
                q, k, v, decay_rates(self.num_heads, self.decay_layer,
                                     self.decay_layers))
        if self.output_norm:
            o = rms_norm(o.reshape(o.shape[0], o.shape[1], -1),
                         params["o_norm"], self.norm_eps).reshape(o.shape)
        return self._project_out(params, o, gate), state


@register_layer
@dataclasses.dataclass(frozen=True)
class LatentAttention(Layer):
    """Multi-head latent attention over [batch, time, features] (MLA, the
    attention of the `deepseek_v2` family), causal, no bias anywhere:

        c_q           = norm(x Wqa; q_norm)                 [T, q_lora_rank]
        [q_nope|q_pe] = c_q Wqb, a head at a time           [T, H, Dn | Dr]
        [c_kv | k_pe] = x Wkva                              [T, kv_lora | Dr]
        [k_nope | v]  = norm(c_kv; kv_norm) Wkvb, a head at a time
        s_h[i, j]     = (q_nope_h[i] . k_nope_h[j]
                         + rope(q_pe_h)[i] . rope(k_pe)[j]) * scale,  j <= i
        y             = concat_h(softmax_j(s_h) v_h) Wo

    The rope key `k_pe` is one for all heads and is not normed. The
    positions are rotary over the `Dr` lanes at `rope_theta`, with
    `rope_scaling` (type "yarn") at `yarn_inv_freq`'s frequencies, and
    `scale = (Dn + Dr)^-0.5` times `yarn_factors`' second. A head's pairs
    are its halves `(x1, x2)`, as `rope_rotate` takes them.

    `num_heads` is the published count; `heads_held` = (first, count)
    names the heads whose slices of `Wqb`, `Wkvb` and `Wo` this device
    has, all of them where None. A head's slices are initialised from its
    own published index, so the shares of one layer add up: what the
    absent heads would add to `y` is left out, as on a mesh their devices
    add it (`Wo`'s all-reduce). `Wqa`, `Wkva` and the two norms are whole
    on every device.

    The core runs under the scope `latent_attention_core`
    (`ops/latent_attention.py`'s kernels where
    `kernel_defaults.latent_policy` says so, else its dense form),
    everything else under `latent_projections`. Training and scoring
    only."""

    CONSUMES = "rnn"

    n_in: Optional[int] = None
    n_out: Optional[int] = None       # model dim (defaults to n_in)
    num_heads: int = 4
    heads_held: Optional[Any] = None  # (first, count); None -> all
    q_lora_rank: int = 64
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    rope_theta: float = 10000.0
    rope_scaling: Optional[Any] = None
    norm_eps: float = 1e-6

    def infer_n_in(self, input_type: InputType):
        upd = {}
        if self.n_in is None:
            upd["n_in"] = input_type.size
        if self.n_out is None:
            upd["n_out"] = upd.get("n_in", self.n_in)
        return dataclasses.replace(self, **upd) if upd else self

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    @property
    def _held(self):
        first, count = self.heads_held or (0, self.num_heads)
        if not (0 <= first and 1 <= count
                and first + count <= self.num_heads):
            raise ValueError(f"heads_held {self.heads_held} lies outside "
                             f"the {self.num_heads} heads")
        return int(first), int(count)

    @property
    def _rope(self):
        """(frequencies or None, factor on cos and sin, softmax scale)."""
        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        scaling = self.rope_scaling
        if scaling is None:
            return None, 1.0, scale
        if scaling.get("type") != "yarn":
            raise ValueError(f"rope_scaling type {scaling.get('type')!r} is "
                             f"not known (\"yarn\" is)")
        amplitude, sharper = yarn_factors(scaling)
        return (yarn_inv_freq(self.qk_rope_head_dim, self.rope_theta,
                              scaling), amplitude, scale * sharper)

    def init_params(self, key, input_type, dtype=jnp.float32):
        first, count = self._held
        self._rope      # an unknown scaling is refused here
        dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                      self.v_head_dim)
        ks = jax.random.split(key, 5)
        winit = self._winit()

        def heads(key, shape, axis):   # a head's slice from its own index
            return jnp.concatenate(
                [winit(jax.random.fold_in(key, first + i), shape, dtype)
                 for i in range(count)], axis=axis)

        return {
            "Wqa": winit(ks[0], (self.n_in, self.q_lora_rank), dtype),
            "q_norm": jnp.ones((self.q_lora_rank,), dtype),
            "Wqb": heads(ks[1], (self.q_lora_rank, dn + dr), 1),
            "Wkva": winit(ks[2], (self.n_in, self.kv_lora_rank + dr), dtype),
            "kv_norm": jnp.ones((self.kv_lora_rank,), dtype),
            "Wkvb": heads(ks[3], (self.kv_lora_rank, dn + dv), 1),
            "Wo": heads(ks[4], (dv, self.n_out), 0),
        }, {}

    def decode_carry(self, batch: int, dtype=jnp.float32, **kw):
        raise NotImplementedError(
            f"LatentAttention {self.name!r} has no decode carry yet: its "
            f"cache is the compressed latent and the rope key, "
            f"kv_lora_rank + qk_rope_head_dim a token, which serving's "
            f"pages do not hold")

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None):
        if mask is not None:
            raise ValueError("LatentAttention takes no padding mask")
        from deeplearning4j_tpu.ops.kernel_defaults import latent_policy
        from deeplearning4j_tpu.ops.latent_attention import (
            dense_latent_attention, latent_attention,
        )

        B, T, _ = x.shape
        H = self._held[1]
        dn, rank = self.qk_nope_head_dim, self.kv_lora_rank
        freqs, amplitude, scale = self._rope
        positions = jnp.arange(T)

        def rope(a):
            a = rope_rotate(a, positions, self.rope_theta, freqs)
            return a if amplitude == 1.0 else a * amplitude

        with jax.named_scope("latent_projections"):
            c_q = rms_norm(x @ params["Wqa"], params["q_norm"],
                           self.norm_eps)
            q = (c_q @ params["Wqb"]).reshape(B, T, H, -1)
            q = jnp.concatenate([q[..., :dn], rope(q[..., dn:])], axis=-1)
            kva = x @ params["Wkva"]
            k_rope = rope(kva[:, :, None, rank:])[:, :, 0]
            c_kv = rms_norm(kva[..., :rank], params["kv_norm"],
                            self.norm_eps)
            kv = (c_kv @ params["Wkvb"]).reshape(B, T, H, -1)
            k_nope, v = kv[..., :dn], kv[..., dn:]
        pol = latent_policy(T)
        with jax.named_scope("latent_attention_core"):
            if pol.kind == "kernel":
                o = latent_attention(q, k_nope, k_rope, v, scale,
                                     pol.block_q, pol.block_k, False)
            else:
                o = dense_latent_attention(q, k_nope, k_rope, v, scale)
        with jax.named_scope("latent_projections"):
            y = o.reshape(B, T, -1) @ params["Wo"]
        return self._act(y), state


@register_layer
@dataclasses.dataclass(frozen=True)
class SelectiveStateSpace(Layer):
    """A Mamba-2 mixer over [batch, time, features] (the `mamba` layers of
    the `granitemoehybrid` family, the `M` layers of `nemotron_h`), causal,
    no bias but the convolution's. With P = `head_dim`, N = `state_size`,
    G = `n_groups`, `a` the input:

        [z | xBC | dt] = a W_in        z [T, H P], xBC [T, H P + 2 G N], dt [T, H]
        xBC = silu(conv(xBC) + b_conv)     causal, depthwise, `conv_kernel`
                                           taps (t - K + 1 .. t, the last
                                           tap on the token itself)
        x [T, H, P], B [T, G, N], C [T, G, N] = split(xBC)
        dt = softplus(dt + dt_bias);  A_h = -exp(A_log_h)
        S_t = exp(dt_t,h A_h) S_{t-1} + dt_t,h x_t,h B_t^T     [P, N] a head
        y_t,h = S_t C_t + D_h x_t,h    head h reads group h // (H / G)'s B, C
        out = norm(y * silu(z); norm) W_out    the RMS norm PER GROUP: over
                                               each group's H P / G lanes on
                                               its own (all lanes where G = 1)

    `chunk` is the config's `chunk_size` (`mamba_chunk_size`: 256 in
    granite, 128 in nemotron_h). `num_heads` is the published count;
    `heads_held` = (first, count) names the heads whose z, x and dt columns
    of `in_proj`, x channels of the convolution, `dt_bias`, `A_log`, `D`,
    lanes of `norm` and rows of `out_proj` this device has, all of them
    where None. With one group B's and C's columns and channels are whole
    on every device. With more, a share is WHOLE GROUPS (`first` and
    `count` multiples of `num_heads / n_groups`) and takes those groups' B
    and C columns and channels with it, so nothing of the mixer is held
    twice. A head's slices (and, with more groups than one, a group's) are
    initialised from their own published index, so the shares of one layer
    add up. What crosses devices: `out_proj`'s partial sums (the caller's
    all-reduce, as `LatentAttention`'s `Wo`) and, with ONE group, the gated
    norm's mean square, which then runs over all heads' lanes: with
    `norm_axis` the norm sums its squares and its lane count over that
    mesh axis; without, the mean is over the lanes held, which is what a
    device has before the exchange. A share by groups norms each group it
    holds on its own and exchanges nothing there (`norm_axis` is refused).

    Initialised as Mamba-2 publishes: dt log-uniform in `DT_RANGE`
    with `dt_bias` its inverse softplus, A uniform in [1, 16], D 1. Scopes: `ssm_mixer` round everything, inside it `ssm_conv`,
    `ssm_core` (`ops/selective_scan.py`'s chunked scan alone) and
    `ssm_gate_norm`. State: `ssm_chunk_carry`, the last step's mean over
    heads and chunks of `exp(sum of dt A over a chunk)`, how much of a
    state survives `chunk` tokens, which `fit()` publishes as the gauge
    `ssm_chunk_carry{layer=}` where an epoch synchronises. Training and
    scoring only."""

    CONSUMES = "rnn"

    n_in: Optional[int] = None
    n_out: Optional[int] = None       # model dim (defaults to n_in)
    num_heads: int = 4
    heads_held: Optional[Any] = None  # (first, count); None -> all
    head_dim: int = 16
    state_size: int = 16
    n_groups: int = 1
    conv_kernel: int = 4
    chunk: int = 256
    norm_eps: float = 1e-5
    norm_axis: Optional[str] = None   # mesh axis the gated norm sums over

    DT_RANGE = (1e-3, 1e-1)           # of the initial step sizes

    # n_in and n_out from the input, [B, T, n_out] out: as the attention
    # layers', and the heads held as `LatentAttention` reads them
    infer_n_in = LatentAttention.infer_n_in
    output_type = LatentAttention.output_type

    @property
    def _held(self):
        first, count = LatentAttention._held.fget(self)
        return int(first), int(count)

    @property
    def _groups_held(self):
        """(first, count) of the groups of B and C held: the one group
        whole on every device, or the groups of the heads held."""
        first, count = self._held
        if self.num_heads % self.n_groups:
            raise ValueError(f"{self.num_heads} heads in {self.n_groups} "
                             f"groups")
        if self.n_groups == 1:
            return 0, 1
        per = self.num_heads // self.n_groups
        if first % per or count % per:
            raise ValueError(
                f"heads_held {(first, count)}: with {self.n_groups} groups "
                f"of B and C a share is whole groups of {per} heads")
        if self.norm_axis is not None:
            raise ValueError("the gated norm is per group and a share is "
                             "whole groups: there is nothing to sum over "
                             f"{self.norm_axis!r}")
        return first // per, count // per

    def init_params(self, key, input_type, dtype=jnp.float32):
        first, count = self._held
        g_first, g_count = self._groups_held
        p, n = self.head_dim, self.state_size
        gn = g_count * n
        ks = jax.random.split(key, 9)
        winit = self._winit()
        kernel = lambda k, s: winit(k, s, dtype)

        def own(key, shape, axis, make, first, count):
            return jnp.concatenate(
                [make(jax.random.fold_in(key, first + i), shape)
                 for i in range(count)], axis=axis)

        def heads(key, shape, axis, make=kernel):   # a head's own slices
            return own(key, shape, axis, make, first, count)

        def groups(key, rows, make):    # B's and C's columns, [rows, 2 gn]
            if self.n_groups == 1:
                return make(key, (rows, 2 * n))
            # a group's own, B's then C's: the shares add up
            return jnp.concatenate([
                own(jax.random.fold_in(key, half), (rows, n), 1, make,
                    g_first, g_count) for half in (0, 1)], axis=1)

        tap = lambda k, s: jax.random.uniform(
            k, s, dtype, -1.0, 1.0) / math.sqrt(self.conv_kernel)
        dt = jnp.exp(heads(ks[6], (1,), 0, lambda k, s: jax.random.uniform(
            k, s, jnp.float32, *map(math.log, self.DT_RANGE))))
        a = heads(ks[7], (1,), 0, lambda k, s: jax.random.uniform(
            k, s, jnp.float32, 1.0, 16.0))
        return {
            "in_proj": jnp.concatenate([
                heads(ks[0], (self.n_in, p), 1),                    # z
                heads(ks[1], (self.n_in, p), 1),                    # x
                groups(ks[2], self.n_in, kernel),                   # B, C
                heads(ks[3], (self.n_in, 1), 1)], axis=1),          # dt
            "conv_w": jnp.concatenate([
                heads(ks[4], (self.conv_kernel, p), 1, tap),
                groups(ks[5], self.conv_kernel, tap)], axis=1),
            "conv_b": jnp.zeros((count * p + 2 * gn,), dtype),
            # softplus(dt_bias) = dt
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "A_log": jnp.log(a).astype(dtype),
            "D": jnp.ones((count,), dtype),
            "norm": jnp.ones((count * p,), dtype),
            "out_proj": heads(ks[8], (p, self.n_out), 0),
        }, {"ssm_chunk_carry": jnp.zeros((), jnp.float32)}

    def decode_carry(self, batch: int, dtype=jnp.float32, **kw):
        raise NotImplementedError(
            f"SelectiveStateSpace {self.name!r} has no decode carry yet: "
            f"its [head_dim, state_size] state a head and the "
            f"convolution's last conv_kernel - 1 tokens want snapshots in "
            f"the session carries, which serving does not have")

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None):
        if mask is not None:
            raise ValueError("SelectiveStateSpace takes no padding mask")
        from deeplearning4j_tpu.ops.selective_scan import (
            chunk_carry, selective_scan,
        )

        B, T, _ = x.shape
        H, P, K = self._held[1], self.head_dim, self.conv_kernel
        G, N = self._groups_held[1], self.state_size
        inner = H * P
        f32 = jnp.float32
        with jax.named_scope("ssm_mixer"):
            zxbcdt = x @ params["in_proj"]
            z, xbc, dt = (zxbcdt[..., :inner],
                          zxbcdt[..., inner:2 * inner + 2 * G * N],
                          zxbcdt[..., 2 * inner + 2 * G * N:])
            with jax.named_scope("ssm_conv"):
                padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
                xbc = jax.nn.silu(sum(
                    padded[:, k:k + T] * params["conv_w"][k]
                    for k in range(K)) + params["conv_b"])
            dt = jax.nn.softplus(dt.astype(f32)
                                 + params["dt_bias"].astype(f32))
            a = -jnp.exp(params["A_log"].astype(f32))
            with jax.named_scope("ssm_core"):
                y = selective_scan(
                    xbc[..., :inner].reshape(B, T, H, P), dt, a,
                    xbc[..., inner:inner + G * N].reshape(B, T, G, N),
                    xbc[..., inner + G * N:].reshape(B, T, G, N),
                    params["D"], chunk=self.chunk)
            with jax.named_scope("ssm_gate_norm"):
                y = y.reshape(B, T, inner) * jax.nn.silu(z)
                if G == 1:
                    y = rms_norm(y, params["norm"], self.norm_eps,
                                 self.norm_axis)
                else:   # each group held over its own lanes
                    y = rms_norm(y.reshape(B, T, G, inner // G),
                                 params["norm"].reshape(G, inner // G),
                                 self.norm_eps).reshape(B, T, inner)
            out = y @ params["out_proj"]
            carried = chunk_carry(dt, a, chunk=self.chunk)
        return self._act(out), {**(state or {}), "ssm_chunk_carry": carried}


@register_layer
@dataclasses.dataclass(frozen=True)
class PreNormBlock(Layer):
    """A pre-norm decoder block around any sequence mixer:
    `h = x + r mixer(norm(x))`, then `h + r F(norm(h))` (always both
    halves; a model whose layers are ONE of them each, a mixer or a
    feed-forward part alone, is built of `PreNormSublayer`s), with
    `r = residual_scale`, both norms RMS with a gain, and F a bias-free
    SwiGLU of `ffn_width`. The mixer is a layer of its own
    (`MultiHeadAttention` with whatever options, `LinearAttention`,
    `LatentAttention`), given whole: the block hands none of its options
    through. With `ffn` F is that layer in the SwiGLU's place, given
    whole too (a `parallel/moe.ExpertFeedForward`: one device's share of
    an expert layer). Leaves: `ln1_g`, `ln2_g`, `mixer_*`, and `ffn_w1`,
    `ffn_w3`, `ffn_w2` or, with `ffn`, `moe_*`; the mixer's state and
    `ffn`'s (its routing counters) are the block's.

    Under `gradient_checkpointing` the block keeps `h` (one `[B, T, d]`
    tensor) beside its input: the second norm reads it, and the recomputed
    forward then leaves out the mixer's last product
    (`ops/attention.name_block_residual`)."""

    CONSUMES = "rnn"   # [B, T, d] sequence activations

    n_in: Optional[int] = None
    mixer: Optional[Any] = None
    ffn_width: Optional[int] = None      # None -> 4 x n_in
    ffn: Optional[Any] = None            # a layer in the SwiGLU's place
    residual_scale: float = 1.0
    eps: float = 1e-5

    def infer_n_in(self, input_type: InputType):
        if self.n_in is None:
            return dataclasses.replace(self, n_in=input_type.size)
        return self

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def _mixer(self):
        if self.mixer is None:
            raise ValueError("PreNormBlock needs a mixer layer")
        d = self.n_in
        return dataclasses.replace(
            self.mixer, n_in=d, n_out=d, activation="identity",
            weight_init=self.mixer.weight_init or self.weight_init,
            name=self.mixer.name or f"{self.name}.mixer")

    def _ffn(self):
        return dataclasses.replace(
            self.ffn, n_in=self.n_in,
            weight_init=self.ffn.weight_init or self.weight_init,
            name=self.ffn.name or f"{self.name}.ffn")

    def init_params(self, key, input_type, dtype=jnp.float32):
        d = self.n_in
        ks = jax.random.split(key, 4)
        params = {"ln1_g": jnp.ones((d,), dtype),
                  "ln2_g": jnp.ones((d,), dtype)}
        mp, state = self._mixer().init_params(ks[0], input_type, dtype)
        params.update({f"mixer_{k}": v for k, v in mp.items()})
        if self.ffn is not None:
            fp, counters = self._ffn().init_params(ks[1], input_type, dtype)
            params.update({f"moe_{k}": v for k, v in fp.items()})
            return params, {**state, **counters}
        h = self.ffn_width or 4 * d
        winit = self._winit()
        params.update(ffn_w1=winit(ks[1], (d, h), dtype),
                      ffn_w3=winit(ks[2], (d, h), dtype),
                      ffn_w2=winit(ks[3], (h, d), dtype))
        return params, state

    def decode_carry(self, batch: int, dtype=jnp.float32, **kw):
        return {"mixer": self._mixer().decode_carry(batch, dtype, **kw)}

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None):
        from deeplearning4j_tpu.ops.attention import name_block_residual

        mixer = self._mixer()
        mp = {k[6:]: v for k, v in params.items() if k.startswith("mixer_")}
        carry = state.get("mixer") if state else None
        a, m_st = mixer.apply(
            mp, rms_norm(x, params["ln1_g"], self.eps),
            state=state if carry is None else carry, train=train, rng=rng,
            mask=mask)
        # the stream between the halves is named: the second norm reads
        # it, and a checkpointed block that did not keep it would run the
        # mixer's last product again only to have it
        x = name_block_residual(x + self.residual_scale * a,
                                "residual_stream")
        h = rms_norm(x, params["ln2_g"], self.eps)
        new_state = (m_st or {}) if carry is None else {"mixer": m_st}
        if self.ffn is not None:
            y, counters = self._ffn().apply(
                {k[4:]: v for k, v in params.items()
                 if k.startswith("moe_")}, h, train=train, rng=rng)
            new_state = {**new_state, **counters}
        else:
            with jax.named_scope("ffn"):
                y = (jax.nn.silu(h @ params["ffn_w1"])
                     * (h @ params["ffn_w3"])) @ params["ffn_w2"]
        return x + self.residual_scale * y, new_state


@register_layer
@dataclasses.dataclass(frozen=True)
class PreNormSublayer(Layer):
    """One pre-norm residual sublayer, `x + f(norm(x))`: one RMS norm with
    a gain and ONE layer `f`, given whole (the layers of the `nemotron_h`
    family, each a Mamba-2 mixer, an attention or an expert layer alone:
    `SelectiveStateSpace`, `MultiHeadAttention`,
    `parallel/moe.ExpertFeedForward`). `PreNormBlock` is the two-halves
    form and keeps its leaves. Leaves: `ln_g` and `f_<leaf>` for every
    leaf of `f`; `f`'s state (routing counters, `ssm_chunk_carry`) is the
    sublayer's. Under `gradient_checkpointing` each sublayer is a
    checkpoint of its own and keeps its input alone."""

    CONSUMES = "rnn"   # [B, T, d] sequence activations

    n_in: Optional[int] = None
    layer: Optional[Any] = None
    eps: float = 1e-5

    infer_n_in = PreNormBlock.infer_n_in
    output_type = PreNormBlock.output_type

    def _f(self):
        if self.layer is None:
            raise ValueError("PreNormSublayer needs a layer")
        sizes = {"n_in": self.n_in}
        if "n_out" in {f.name for f in dataclasses.fields(self.layer)}:
            sizes.update(n_out=self.n_in, activation="identity")
        return dataclasses.replace(
            self.layer, **sizes,
            weight_init=self.layer.weight_init or self.weight_init,
            name=self.layer.name or f"{self.name}.f")

    def init_params(self, key, input_type, dtype=jnp.float32):
        fp, state = self._f().init_params(key, input_type, dtype)
        return {"ln_g": jnp.ones((self.n_in,), dtype),
                **{f"f_{k}": v for k, v in fp.items()}}, state

    def decode_carry(self, batch: int, dtype=jnp.float32, **kw):
        f = self._f()   # an expert layer carries nothing between tokens
        return ({"f": f.decode_carry(batch, dtype, **kw)}
                if hasattr(f, "decode_carry") else {})

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None):
        f = self._f()
        carry = state.get("f") if state else None
        y, st = f.apply(
            {k[2:]: v for k, v in params.items() if k.startswith("f_")},
            rms_norm(x, params["ln_g"], self.eps),
            state=state if carry is None else carry, train=train, rng=rng,
            mask=mask)
        return x + y, (st or {}) if carry is None else {"f": st}
