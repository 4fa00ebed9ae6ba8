"""Operations and HBM bytes of the block-sparse attention kernels
(`ops/sparse_attention.py`: `sparse_attention_fwd`, `_bwd_dq`,
`_bwd_dkdv`), from shapes, and their share of the roof. Beside
`kernel_counts.py`, whose reduction it uses.

What is counted is what the mathematics needs: two operations a
multiply-add over the (query, key) pairs of the blocks the selection
KEEPS (for each token and KV group `min(reachable, topk)` blocks, the
token's own block as its causal part), and every operand and result moved
once. The kernels do more: a Q tile walks the union of its tokens' lists
and masks per row, so on traffic whose neighbouring tokens choose
different blocks it computes nearly every causal tile; the product that
spreads the mask over a tile's keys and K/V tiles fetched again for every
Q tile are not counted either. So the share can only read low, and how low
says how far the union is from the lists.
"""

from __future__ import annotations

from benchmarks import harness, kernel_counts


def kept_pairs(t: int, block_size: int, topk: int) -> int:
    """(query, key) pairs one query head reads over `t` tokens."""
    return sum((min(i // block_size + 1, topk) - 1) * block_size
               + i % block_size + 1 for i in range(t))


def sparse_attention_calls(*, batch: int, t: int, heads: int, kv_heads: int,
                           head_dim: int, block_size: int, topk: int,
                           itemsize: int = 2) -> dict:
    """{call name: (operations, HBM bytes)} per call. Products over the
    kept pairs: the forward has two (scores, values), dQ three, dK/dV
    four. Bytes: q and o or their cotangents [T, H, Dh], k and v
    [T, Hkv, Dh], the mask [Hkv, T, blocks padded to 128 lanes] in the
    kernel's dtype, the row statistics float32 128 lanes wide where a
    kernel reads or writes them."""
    product = 2 * batch * heads * head_dim * kept_pairs(t, block_size, topk)
    wide = batch * t * heads * head_dim * itemsize
    narrow = batch * t * kv_heads * head_dim * itemsize
    lanes = -(-(-(-t // block_size)) // 128) * 128
    mask = batch * kv_heads * t * lanes * itemsize
    stats = batch * t * heads * 128 * 4
    return {
        "sparse_attention_fwd":
            (2 * product, 2 * wide + 2 * narrow + mask + stats),
        "sparse_attention_bwd_dq":
            (3 * product, 3 * wide + 2 * narrow + mask + 2 * stats),
        "sparse_attention_bwd_dkdv":
            (4 * product, 2 * wide + 4 * narrow + mask + 2 * stats),
    }


def sparse_attention_roofline(facts):
    """`kernel_counts.family_share` of the three kernels in the run's
    configuration; None where nothing was traced, the configuration has no
    `minicpm4` layer past its `dense_len`, or none of them ran."""
    run = facts["run"]
    if facts["trace"] is None or not facts["scopes"] or not run["peaks"]:
        return None
    cfg = kernel_counts.cell_config(run)
    if cfg is None or "minicpm4" not in cfg.get("mixer_types", ()):
        return None
    sizes = harness.load_module(
        "reference", cfg["reference"] + ".py").sparse_sizes(cfg)
    t = cfg["input_shape"][0]
    if t <= sizes["dense_len"]:
        return None
    calls = sparse_attention_calls(
        batch=run["global_batch"] // run["chips"], t=t,
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        block_size=sizes["block_size"], topk=sizes["topk"])
    return kernel_counts.family_share(facts["scopes"], calls, run["peaks"])
