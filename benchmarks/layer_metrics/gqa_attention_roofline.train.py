"""The flash kernels' share of the bf16 peak (or of the HBM peak, were
that nearer) at one chip's share of a GQA layer's heads:
`flash_attention_fwd`, `flash_attention_bwd_dq` and
`flash_attention_bwd_dkdv` together, as `flash_attention_roofline.train`
reads them, but with the heads HELD HERE (`attention_heads_held` and
`kv_heads_held` of the cell's configuration: 4 query heads of 128 over one
KV head) where that reader takes the published counts. Operations and
bytes from `kernel_counts.attention_calls` over the causal pairs, seconds
and calls from the trace (the trunk's attention layer and the prediction
module's call the same kernels at the same shapes). No value where none of
them ran or the configuration names no heads held."""

from benchmarks import kernel_counts


def read(facts):
    run = facts["run"]
    if facts["trace"] is None or not facts["scopes"] or not run["peaks"]:
        return None
    cfg = kernel_counts.cell_config(run)
    if cfg is None or "attention_heads_held" not in cfg:
        return None
    calls = kernel_counts.attention_calls(
        "flash_attention", batch=run["global_batch"] // run["chips"],
        t=cfg["input_shape"][0], heads=cfg["attention_heads_held"][1],
        kv_heads=cfg["kv_heads_held"][1], head_dim=cfg["head_dim"])
    return kernel_counts.family_share(facts["scopes"], calls, run["peaks"])
