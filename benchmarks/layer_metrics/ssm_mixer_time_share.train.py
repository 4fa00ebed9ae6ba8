"""Share of device op time under the scope `ssm_mixer`, which
`SelectiveStateSpace` opens round the whole Mamba-2 mixer: `in_proj`, the
causal convolution (`ssm_conv`), the scan (`ssm_core`, which
`ssm_time_share.train` reads alone), the gated norm (`ssm_gate_norm`) and
`out_proj`, forward, recomputed and backward. With `ssm_time_share.train`
it splits the scan from its projections, convolution and norm. No value
where no op carries that scope."""

from benchmarks import kernel_counts


def read(facts):
    if facts["trace"] is None or not facts["scopes"]:
        return None
    return kernel_counts.inner_share(facts["scopes"], ["ssm_mixer"])
