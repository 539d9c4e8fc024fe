"""Share of the traced window in which no operation ran on the device,
mean over the cell's chips (``bench/trace_reduce.py``)."""


def read(m):
    if m.trace is None:
        return None
    busy = m.trace["busy_s"]
    return 100.0 * (1.0 - sum(busy.values()) / len(busy) / m.trace["window_s"])
