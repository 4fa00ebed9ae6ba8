"""Share of device op time under the scope `updater`: the update rule's own
elementwise passes. Under-read where the compiler fuses an update into the
weight-gradient convolution, which keeps the convolution's name. No value
where no op carries that scope."""

from benchmarks import span_reduce


def read(facts):
    if facts["trace"] is None or "updater" not in (facts["scopes"] or {}):
        return None
    return span_reduce.scope_shares(facts["scopes"])[1]
