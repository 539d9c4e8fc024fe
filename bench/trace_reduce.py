"""From a profiler trace to per-layer numbers.

Two parts.  :func:`read_xplane` reads a JAX profiler ``.xplane.pb`` into
per-device operation events and host spans.  The rest is arithmetic on those
events: busy and idle time, collective time not hidden behind compute, time
per operation, and the longest idle gaps named by what the host was doing.

Events are ``(name, start_ns, duration_ns)``.  A device's busy time is the
union of its operation intervals inside the window, control-flow
containers left out.
"""

from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path

# control flow whose interval spans the operations it runs (a layer scan is
# one ``while``): left out of busy time, compute and the per-op times
CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")
COLLECTIVE = re.compile(
    r"all-gather|reduce-scatter|all-reduce|collective-permute|all-to-all"
    r"|psum|ppermute", re.I)
HOST_SPANS = ("dispatch", "next_batch", "loss_fetch")
WINDOW_SPAN = "window"


# ---------------------------------------------------------------------------
# reader
# ---------------------------------------------------------------------------

def read_xplane(path) -> dict:
    """{"devices": {plane name: [event]}, "host": [event]} of one trace.

    Device events are those of each TPU plane's "XLA Ops" line, named by
    their HLO instruction (``fusion.12``, ``while.3``); host events are
    every named event on the host planes' lines (the harness's
    ``TraceAnnotation`` spans among them)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = [
                        (_op_name(e.name), e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events)
    return {"devices": devices, "host": host}


def _op_name(text: str) -> str:
    """``fusion.12`` of an HLO line ``%fusion.12 = bf16[...] fusion(...)``."""
    return text.split(" = ", 1)[0].lstrip("%")


def find_xplane(directory) -> Path:
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------

def union(intervals) -> list:
    """Sorted disjoint [start, end) intervals covering ``intervals``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def intersect(a, b) -> list:
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def clip(events, window) -> list:
    """Intervals of ``events`` cut to the window (start, end)."""
    w0, w1 = window
    return [(max(s, w0), min(s + d, w1)) for _, s, d in events
            if s < w1 and s + d > w0]


def window_of(host, name: str = WINDOW_SPAN):
    """(start, end) of the host span ``name``."""
    spans = [(s, s + d) for n, s, d in host if n == name]
    if not spans:
        raise ValueError(f"no host span {name!r} in the trace")
    return spans[0]


def leaves(events) -> list:
    """Events that are no control-flow container."""
    return [e for e in events if not CONTAINER.match(e[0])]


def busy_ns(events, window) -> float:
    return length(union(clip(leaves(events), window)))


def collective_exposed_ns(events, window) -> float:
    """Time in which a collective runs on the device and no compute does."""
    ops = leaves(events)
    coll = union(clip([e for e in ops if COLLECTIVE.search(e[0])], window))
    comp = union(clip([e for e in ops if not COLLECTIVE.search(e[0])],
                      window))
    return length(coll) - length(intersect(coll, comp))


def op_seconds(devices: dict, window, top: int = 10) -> list:
    """[[name, seconds]] of the operations that took most device time,
    mean over devices."""
    tot = defaultdict(float)
    for events in devices.values():
        for name, s, d in leaves(events):
            iv = clip([(name, s, d)], window)
            if iv:
                tot[name] += iv[0][1] - iv[0][0]
    n = max(1, len(devices))
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v / n / 1e9] for k, v in ranked]


def idle_gaps(events, host, window, top: int = 10) -> list:
    """[[host span, seconds]] of the longest idle gaps of one device, each
    named by the harness span that overlaps it most ("other" if none)."""
    busy = union(clip(leaves(events), window))
    edges = [window[0]] + [x for iv in busy for x in iv] + [window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = [(n, s, s + d) for n, s, d in host if n in HOST_SPANS]
    named = []
    for g0, g1 in gaps:
        best, cover = "other", 0
        for n, s, e in spans:
            c = min(e, g1) - max(s, g0)
            if c > cover:
                best, cover = n, c
        named.append([best, (g1 - g0) / 1e9])
    return sorted(named, key=lambda x: -x[1])[:top]


def reduce(trace: dict) -> dict:
    """Per-device busy and exposed-collective seconds, the window, and the
    ``breakdown`` of the run's result line."""
    window = window_of(trace["host"])
    devs = trace["devices"]
    if not devs:
        raise ValueError("no device operations in the trace")
    busy = {k: busy_ns(v, window) / 1e9 for k, v in devs.items()}
    exposed = {k: collective_exposed_ns(v, window) / 1e9
               for k, v in devs.items()}
    first = sorted(devs)[0]
    return {"window_s": (window[1] - window[0]) / 1e9,
            "busy_s": busy, "collective_exposed_s": exposed,
            "breakdown": {
                "device_ops": op_seconds(devs, window),
                "idle_gaps": idle_gaps(devs[first], trace["host"], window)}}
