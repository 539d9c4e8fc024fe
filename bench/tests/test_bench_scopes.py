"""Time per layer and phase from the program's layer names
(``bench/scopes.py``): the names in a compiled step, the reduction on
hand-made HLO and events, and on a chip trace of ``qwen3-0.6b.train_1k``."""

import gzip
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from bench import scopes as S
from bench import spec, train_cell
from bench import trace_reduce as TR
from bench.tests.tiny import patched_registry, tiny_cell

DATA = Path(__file__).resolve().parent / "data"
TRACE = DATA / "qwen3-0.6b.train_1k.xplane.pb"
STEP = "jit_train_step"
METRICS = sorted(S.LAYER_METRICS) + [S.RECOMPUTE_METRIC]


def test_every_layer_is_named_in_the_compiled_step():
    cell, smoke = tiny_cell("qwen3-0.6b.train_4k")
    assert cell.traffic["parallel"]["remat"] == "full"
    with patched_registry(smoke):
        prog = train_cell.Program(cell, jax.devices()[:1])
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    p, o = jax.eval_shape(prog._init, key)
    b = jax.eval_shape(prog._batch, key, jnp.int32(0))
    text = prog._step.lower(p, o, b).compile().as_text()
    seen = {(S.layer_of(n), S.phase_of(n)) for n in S.op_names(text).values()}
    assert {layer for layer, _ in seen} >= set(S.LAYERS)
    for layer in ("attention", "ffn"):
        assert {ph for lay, ph in seen if lay == layer} == set(S.PHASES)
    assert S.module_name(text) == STEP


@pytest.mark.parametrize("op_name,layer,phase", [
    ("jit(s)/jvp()/while/body/closed_call/attention/sdpa/dot_general",
     "attention", "forward"),
    ("jit(s)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/norm/mul", "norm", "recompute"),
    ("jit(s)/transpose(jvp())/while/body/closed_call/checkpoint/ffn/dot",
     "ffn", "backward"),
    ("jit(s)/transpose(jvp(loss_head))/mul", "loss_head", "backward"),
    ("jit(s)/jvp(embed)/hecaton_embed_2d/shard_map/take", "embed",
     "forward"),
    # the innermost layer wins; a Hecaton primitive is no layer
    ("jit(s)/ffn/hecaton_ffn_block/attention/dot", "attention", "forward"),
    ("jit(s)/hecaton_mixer_in/shard_map/ppermute", S.UNSCOPED, "forward"),
    ("jit(s)/optimizer/mul", "optimizer", "forward"),
    ("jit(s)/while/body/dynamic_update_slice", S.UNSCOPED, "forward"),
    # whole components only
    ("jit(s)/ffnx/attention_sink/dot", S.UNSCOPED, "forward"),
    # of several merged paths, the first that names a layer
    ("jit(s)/while/body/add;jit(s)/transpose(jvp(grad_accum))/add",
     "grad_accum", "backward"),
    ("", S.UNSCOPED, "forward"),
])
def test_layer_and_phase_of_a_path(op_name, layer, phase):
    assert (S.layer_of(op_name), S.phase_of(op_name)) == (layer, phase)


HLO = """HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %dot.2 = f32[4]{0} dot(%param_0, %param_0), metadata={op_name="jit(s)/transpose(jvp())/while/body/closed_call/ffn/dot_general"}
  ROOT %dynamic-update-slice.3 = f32[4]{0} dynamic-update-slice(%dot.2, %param_0), metadata={op_name="jit(s)/transpose(jvp())/while/body/dynamic_update_slice"}
}

ENTRY %main.9 (p.1: f32[4]) -> f32[4] {
  %p.1 = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%p.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(s)/transpose(jvp())/while/body/dynamic_update_slice"}
  %copy.4 = f32[4]{0} copy(%fusion.1)
  %convert.5 = bf16[4]{0} convert(%p.1)
  %multiply.6 = bf16[4]{0} multiply(%convert.5, %convert.5), metadata={op_name="jit(s)/optimizer/mul"}
  %add.7 = f32[4]{0} add(%copy.4, %p.1), metadata={op_name="jit(s)/while/body/add"}
  ROOT %copy.8 = f32[4]{0} copy(%add.7)
}
"""


def test_op_names_look_through_fusions_and_compiler_copies():
    names = S.op_names(HLO)
    got = {n: S.layer_of(names.get(n, "")) for n in
           ("fusion.1", "copy.4", "convert.5", "add.7", "copy.8")}
    assert got == {
        "fusion.1": "ffn",        # its fused dot names the layer
        "copy.4": "ffn",          # no metadata: its operand's
        "convert.5": "optimizer",  # no metadata, operand a parameter: user's
        "add.7": S.UNSCOPED,      # its own path, which names no layer
        "copy.8": S.UNSCOPED}     # nothing near it names a layer
    assert S.phase_of(names["fusion.1"]) == "backward"
    assert S.module_name(HLO) == STEP
    with pytest.raises(ValueError):
        S.module_name("ENTRY %x")


MS = 1e6     # ns


def _trace():
    ops = [("while.3", 100 * MS, 100 * MS),        # container: left out
           ("fusion.1", 100 * MS, 30 * MS),        # attention forward
           ("fusion.2", 130 * MS, 20 * MS),        # ffn backward
           ("copy.4", 150 * MS, 50 * MS),          # no name: unscoped
           ("fusion.1", 255 * MS, 10 * MS),        # another module's op
           ("fusion.1", 300 * MS, 60 * MS),
           ("fusion.5", 360 * MS, 40 * MS),        # optimizer
           ("fusion.1", 900 * MS, 10 * MS)]        # after the window
    modules = [(f"{STEP}(123)", 100 * MS, 100 * MS),
               ("jit_make_batch(9)", 250 * MS, 20 * MS),
               (f"{STEP}(123)", 300 * MS, 100 * MS),
               (f"{STEP}(123)", 900 * MS, 100 * MS)]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": [("window", 50 * MS, 500 * MS), ("dispatch", 0, MS)]}


NAMES = {"fusion.1": "jit(s)/attention/sdpa/dot_general",
         "fusion.2": "jit(s)/transpose(jvp())/ffn/dot_general",
         "fusion.5": "jit(s)/optimizer/mul"}


def test_reduce_keeps_the_step_module_inside_the_window():
    red = S.reduce(NAMES, STEP, _trace())
    assert red["module"] == STEP and red["steps"] == 2
    assert red["ms_per_step"] == {
        "attention": {"forward": pytest.approx(45.0)},
        "ffn": {"backward": pytest.approx(10.0)},
        "optimizer": {"forward": pytest.approx(20.0)},
        S.UNSCOPED: {"forward": pytest.approx(25.0)}}
    assert red["busy_ms_per_step"] == pytest.approx(100.0)
    assert red["top_ops"][0] == ["fusion.1", "attention", "forward",
                                 pytest.approx(45.0)]
    assert red["metrics"] == {
        "attention_ms_per_step": pytest.approx(45.0),
        "ffn_ms_per_step": pytest.approx(10.0),
        "loss_head_ms_per_step": 0.0,
        "optimizer_ms_per_step": pytest.approx(20.0),
        "unscoped_ms_per_step": pytest.approx(25.0),
        "recompute_ms_per_step": 0.0}
    assert any("attention" in line for line in S.table_lines(red))


def test_reduce_cuts_operations_at_the_window():
    trace = _trace()
    trace["host"] = [("window", 310 * MS, 600 * MS)]
    red = S.reduce(NAMES, STEP, trace)
    # the execution at 300 started before the window: its ops count from
    # 310 on, and the one at 900 starts inside it
    assert red["steps"] == 1
    assert red["ms_per_step"]["attention"]["forward"] == pytest.approx(60.0)
    assert red["busy_ms_per_step"] == pytest.approx(100.0)


@pytest.mark.parametrize("module,host", [
    ("jit_other_step", [("window", 50 * MS, 500 * MS)]),
    (STEP, [("window", 1100 * MS, 10 * MS)])])
def test_reduce_needs_a_step_execution_in_the_window(module, host):
    trace = _trace()
    trace["host"] = host
    with pytest.raises(ValueError, match="no execution"):
        S.reduce(NAMES, module, trace)


def test_unnamed_program_on_a_chip_trace_is_all_unscoped():
    trace = S.read_trace(TRACE)
    red = S.reduce({}, STEP, trace)
    assert red["steps"] == 1
    assert list(red["ms_per_step"]) == [S.UNSCOPED]
    total = sum(red["ms_per_step"][S.UNSCOPED].values())
    assert total == pytest.approx(red["busy_ms_per_step"], rel=1e-9)
    # the step module holds the device's busy time in the window
    whole = TR.reduce(TR.read_xplane(TRACE))
    busy_ms = sum(whole["busy_s"].values()) * 1e3
    assert red["busy_ms_per_step"] == pytest.approx(busy_ms, rel=1e-3)


def test_named_program_on_a_chip_trace():
    """The same chip trace with the names of the step compiled for a v5e:
    the program is the same with and without names (metadata aside), so
    its instruction names match the trace's."""
    with gzip.open(DATA / "qwen3-0.6b.train_1k.hlo.txt.gz", "rt") as f:
        text = f.read()
    red = S.reduce(S.op_names(text), S.module_name(text),
                   S.read_trace(TRACE))
    busy = red["busy_ms_per_step"]
    m = red["metrics"]
    total = sum(sum(row.values()) for row in red["ms_per_step"].values())
    assert total == pytest.approx(busy, rel=1e-9)
    assert m["unscoped_ms_per_step"] < 0.05 * busy
    assert m["attention_ms_per_step"] > 0.5 * busy
    assert set(red["ms_per_step"]["attention"]) == set(S.PHASES)
    assert 0 < m["recompute_ms_per_step"] < m["attention_ms_per_step"]


@pytest.mark.parametrize("name", METRICS)
def test_metric_readers(name):
    read = spec.metric_reader(name)
    assert read(SimpleNamespace(trace=None)) is None
    red = S.reduce(NAMES, STEP, _trace())
    assert read(SimpleNamespace(scopes=red)) == red["metrics"][name]
