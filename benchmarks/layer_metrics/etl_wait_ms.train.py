"""Mean of the program's `train_etl_ms` histogram over the window's steps:
how long the fit loop waited for its next batch."""


def read(facts):
    h = facts["registry"]["train_etl_ms"]
    return h["sum"] / h["count"] if h["count"] else None
