"""Share of device op time in convolutions and convolution fusions, told
by the trace's own `hlo_category` (or op name), over the cell's chips."""


def read(facts):
    trace = facts["trace"]
    if trace is None:
        return None
    conv = sum(k["convolution"] for k in trace["kind_s_by_device"].values())
    total = sum(sum(k.values()) for k in trace["kind_s_by_device"].values())
    return 100.0 * conv / total if total else None
