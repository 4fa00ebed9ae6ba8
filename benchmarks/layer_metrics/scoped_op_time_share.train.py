"""Share of device op time that lies under some layer's `jax.named_scope`
(or `updater`, or an output layer's `loss`), over the cell's chips: what
the `layers` line can attribute. The rest are ops that carry no `op_name`,
such as the waits for asynchronous copies."""

from benchmarks import span_reduce


def read(facts):
    if facts["trace"] is None or not facts["scopes"]:
        return None
    return span_reduce.scope_shares(facts["scopes"])[0]
