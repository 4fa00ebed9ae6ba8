"""Mean of the program's `train_dispatch_ms` histogram over the window's
steps: host time to enqueue one step."""


def read(facts):
    h = facts["registry"]["train_dispatch_ms"]
    return h["sum"] / h["count"] if h["count"] else None
