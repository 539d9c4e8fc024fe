"""Trace arithmetic on hand-made events, and the reader on a chip trace."""

from pathlib import Path

import pytest

from bench import trace_reduce as TR

DATA = Path(__file__).resolve().parent / "data"


def test_union_and_intersection():
    assert TR.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert TR.length(TR.union([(0, 2), (1, 3)])) == 3
    assert TR.intersect([[0, 3], [5, 8]], [[2, 6]]) == [[2, 3], [5, 6]]


def test_busy_idle_and_exposed_collectives():
    window = (0, 100)
    ops = [("while.9", 0, 100),             # a container: not counted
           ("fusion.1", 0, 30),             # compute
           ("all-gather-start.2", 20, 30),  # overlaps compute 20..30
           ("fusion.3", 60, 20),
           ("collective-permute-done.4", 90, 20)]   # cut to 90..100
    assert TR.busy_ns(ops, window) == 30 + 20 + 20 + 10
    # all-gather 30..50 and the permute 90..100 run with no compute
    assert TR.collective_exposed_ns(ops, window) == 20 + 10
    assert TR.collective_exposed_ns([("fusion", 0, 10)], window) == 0


def test_op_seconds_mean_over_devices():
    devs = {"/device:TPU:0": [("fusion.1", 0, 2e9), ("copy.2", 2e9, 1e9),
                              ("while.3", 0, 3e9)],
            "/device:TPU:1": [("fusion.1", 0, 4e9)]}
    got = TR.op_seconds(devs, (0, 10e9))
    assert got == [["fusion.1", 3.0], ["copy.2", 0.5]]
    assert TR._op_name("%fusion.12 = bf16[2]{0} fusion(%p.1)") == "fusion.12"


def test_idle_gaps_named_by_host_span():
    ops = [("a", 10, 10), ("b", 50, 10)]
    host = [("dispatch", 0, 12), ("loss_fetch", 20, 25), ("other", 60, 40)]
    gaps = TR.idle_gaps(ops, host, (0, 100))
    assert gaps == [["other", 40e-9], ["loss_fetch", 30e-9],
                    ["dispatch", 10e-9]]


def test_reduce_needs_the_window_span_and_device_ops():
    trace = {"devices": {"/device:TPU:0": [("fusion", 10, 80)]},
             "host": [("window", 0, 100), ("dispatch", 0, 10)]}
    red = TR.reduce(trace)
    assert red["window_s"] == pytest.approx(1e-7)
    assert red["busy_s"]["/device:TPU:0"] == pytest.approx(8e-8)
    assert red["breakdown"]["idle_gaps"][0][0] == "dispatch"
    with pytest.raises(ValueError, match="window"):
        TR.reduce({"devices": trace["devices"], "host": []})
    with pytest.raises(ValueError, match="no device"):
        TR.reduce({"devices": {}, "host": trace["host"]})


def test_reader_on_a_chip_trace():
    path = DATA / "qwen3-0.6b.train_1k.xplane.pb"
    trace = TR.read_xplane(path)
    assert list(trace["devices"]) == ["/device:TPU:0"]
    ops = trace["devices"]["/device:TPU:0"]
    assert len(ops) > 100 and all(d >= 0 for _, _, d in ops)
    names = {n for n, _, _ in trace["host"]}
    assert {"window", "dispatch", "next_batch", "loss_fetch"} <= names
    red = TR.reduce(trace)
    busy = red["busy_s"]["/device:TPU:0"]
    assert 0 < busy <= red["window_s"]
    assert red["breakdown"]["device_ops"][0][1] > 0
