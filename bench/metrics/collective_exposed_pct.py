"""Share of the traced window in which a collective ran on the device and no
compute did, mean over the cell's chips (``bench/trace_reduce.py``)."""


def read(m):
    if m.trace is None:
        return None
    exposed = m.trace["collective_exposed_s"]
    return (100.0 * sum(exposed.values()) / len(exposed)
            / m.trace["window_s"])
