"""Share of the traced window that the chip with most of it spent in
collectives (all-reduce, all-gather, reduce-scatter, ...: the trace's own
`hlo_category`): the gradient all-reduce and the sharded moments' traffic
of a data-parallel step. Whether other work hid it the trace's op line
does not say; this is the time the ops themselves took."""


def read(facts):
    trace = facts["trace"]
    if trace is None:
        return None
    worst = max(k["collective"] for k in trace["kind_s_by_device"].values())
    return 100.0 * worst / trace["window_s"] if worst else None
