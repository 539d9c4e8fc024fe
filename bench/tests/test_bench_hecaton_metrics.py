"""The four-chip cell's per-layer metrics: exposed collective time from a
reduced trace, and the count of fused collectives that fell back to the
ppermute ring."""

from types import SimpleNamespace

import pytest

from bench import spec
from bench import trace_reduce as TR

CELL = "granite-34b.hecaton2x2_4k"
READERS = ("collective_exposed_pct", "ring_fused_fallbacks")


def _m(trace=None, fallbacks=()):
    return SimpleNamespace(trace=trace, fallbacks=list(fallbacks))


def test_collective_exposed_pct_without_a_trace_is_none():
    assert spec.metric_reader("collective_exposed_pct")(_m()) is None


def test_collective_exposed_pct_of_a_reduced_trace():
    # window 0..1000 ns on two chips.  Chip 0: compute 0..400, an all-gather
    # 300..500 (100 ns of it exposed) and a permute 800..900 (all exposed).
    # Chip 1: compute 0..1000 hides its reduce-scatter 100..300 entirely.
    trace = {"host": [("window", 0, 1000)], "devices": {
        "/device:TPU:0": [("while.1", 0, 1000), ("fusion.2", 0, 400),
                          ("all-gather-start.3", 300, 200),
                          ("collective-permute-done.4", 800, 100)],
        "/device:TPU:1": [("fusion.2", 0, 1000),
                          ("reduce-scatter.5", 100, 200)]}}
    red = TR.reduce(trace)
    assert red["collective_exposed_s"] == {
        "/device:TPU:0": pytest.approx(200e-9),
        "/device:TPU:1": 0.0}
    got = spec.metric_reader("collective_exposed_pct")(_m(trace=red))
    # mean exposed 100 ns over a 1000 ns window
    assert got == pytest.approx(10.0)


@pytest.mark.parametrize("fallbacks", [
    [],
    [("ag_matmul", (2, 2048, 3072), (3072, 3072))],
    [("ag_matmul", (2, 2048, 3072), (3072, 64)),
     ("matmul_rs", (2, 4096, 3072), (3072, 12288)),
     ("ag_matmul_contract", (2, 256, 3072), (6144, 24576))]])
def test_ring_fused_fallbacks_counts_the_list(fallbacks):
    read = spec.metric_reader("ring_fused_fallbacks")
    assert read(_m(fallbacks=fallbacks)) == len(fallbacks)


def test_the_four_chip_cell_reports_both():
    cell = spec.cell(CELL)
    assert cell.chips == 4
    assert cell.traffic["mesh"] == {"data": 1, "mx": 2, "my": 2}
    assert cell.config["program"]["num_layers"] == 4
    names = {m["name"] for m in cell.per_layer}
    assert set(READERS) | {"device_idle_pct", "step_mfu",
                           "step_hbm_gib"} <= names
    for other in ("qwen3-0.6b.train_4k", "qwen3-0.6b.train_1k"):
        assert not set(READERS) & {m["name"]
                                   for m in spec.cell(other).per_layer}
