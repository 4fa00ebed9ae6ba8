"""Peak bytes held on the fullest chip of the cell after the window: the
larger of `peak_bytes_in_use` and `peak_bytes_reserved` (the runtime counts
a running step's temporaries under the second)."""


def read(facts):
    run = facts["run"]
    if run["platform"] != "tpu" or not run["memory_peak_bytes"]:
        return None
    return run["memory_peak_bytes"] / 2 ** 30
