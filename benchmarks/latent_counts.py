"""Operations and HBM bytes of the latent-attention kernels
(`ops/latent_attention.py`: `latent_attention_fwd`, `_bwd_dq`,
`_bwd_dkdv`), from shapes, and their share of the roof. Beside
`kernel_counts.py`, whose reduction it uses.

What is counted is what the mathematics needs: two operations a
multiply-add over the `T (T + 1) / 2` causal (query, key) pairs of a head,
a pair's multiply-adds being the widths of the products it is in (scores
over `Dn + Dr` lanes, values over `Dv`), and every operand and result moved
once: q `[T, H, Dn + Dr]`, k_nope `[T, H, Dn]`, v and o `[T, H, Dv]`, the
rope key `[T, Dr]` ONCE (it is one for all heads), the row statistics
float32 and 128 lanes wide where a kernel reads them. The kernels do more:
the diagonal's tiles compute pairs they then mask, a K tile is fetched
again for every Q tile that needs it, and the dK/dV kernel writes the rope
key's gradient a head at a time in float32 for one reduction after it. So
the share can only read low.
"""

from __future__ import annotations

from benchmarks import kernel_counts


def latent_attention_calls(*, batch: int, t: int, heads: int, nope: int,
                           rope: int, value: int, itemsize: int = 2) -> dict:
    """{call name: (operations, HBM bytes)} per call. Multiply-adds a
    pair and head: the forward scores and values, `(Dn + Dr) + Dv`; dQ
    scores, dP and dQ, `(Dn + Dr) + Dv + (Dn + Dr)`; dK/dV scores, dP, dV
    and dK, `(Dn + Dr) + Dv + Dv + (Dn + Dr)`."""
    pairs = batch * heads * kernel_counts.causal_pairs(t)
    qk = nope + rope
    q = batch * t * heads * qk * itemsize
    k_nope = batch * t * heads * nope * itemsize
    v = batch * t * heads * value * itemsize        # o and its cotangent too
    k_rope = batch * t * rope * itemsize
    stats = batch * t * heads * 128 * 4
    return {
        "latent_attention_fwd":
            (2 * (qk + value) * pairs, q + k_nope + k_rope + 2 * v),
        "latent_attention_bwd_dq":
            (2 * (2 * qk + value) * pairs,
             2 * q + k_nope + k_rope + 2 * v + 2 * stats),
        "latent_attention_bwd_dkdv":
            (2 * (2 * qk + 2 * value) * pairs,
             q + 2 * k_nope + 2 * k_rope + 3 * v + 2 * stats),
    }


def latent_attention_roofline(facts):
    """`kernel_counts.family_share` of the three kernels in the run's
    configuration, over the heads held; None where nothing was traced, the
    configuration has no latent attention, or none of them ran."""
    run = facts["run"]
    if facts["trace"] is None or not facts["scopes"] or not run["peaks"]:
        return None
    cfg = kernel_counts.cell_config(run)
    if cfg is None or "kv_lora_rank" not in cfg:
        return None
    calls = latent_attention_calls(
        batch=run["global_batch"] // run["chips"], t=cfg["input_shape"][0],
        heads=cfg.get("heads_held", (0, cfg["num_attention_heads"]))[1],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        value=cfg["v_head_dim"])
    return kernel_counts.family_share(facts["scopes"], calls, run["peaks"])
