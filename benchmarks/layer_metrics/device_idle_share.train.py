"""Share of the traced window in which no op ran on the idlest chip."""


def read(facts):
    trace = facts["trace"]
    return None if trace is None else 100.0 * trace["idle_share_worst"]
