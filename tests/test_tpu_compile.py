"""The TPU compiler accepts the model path's Pallas kernels at qwen3-0.6b's
widths.

Each test compiles for a described (not attached) v5e 2x2 host: the fused
ring collectives of kernels/ring_matmul.py on the Hecaton mx=my=2 grid,
forward and ``jax.grad``, bf16 and int8 wire, the plain tile matmul, and
train steps with the flash attention kernel of kernels/flash_attention.py
(one chip, and a granite-shaped step on the 2x2 grid), then checks that the
compiled program holds the Pallas kernel (``tpu_custom_call``).  Nothing
runs, so this says nothing about results or speed; it catches what interpret mode cannot (tile alignment, VMEM, ref
shapes).  The topology is described only inside the module fixture, after a
test has started: only one process may load the TPU library.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro import compat
from repro.kernels import flash_attention as FA
from repro.kernels import ring_matmul as RM

D, F, S = 1024, 3072, 1024     # qwen3-0.6b d_model, d_ff; seq of the 2x2 smoke
AX = ("data", "mx", "my")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def mesh(topo):
    from repro.launch.mesh import make_small_mesh
    return make_small_mesh("hecaton", 1, 2, 2, devices=topo.devices)


def _cases(mesh):
    """name -> (per-die fn(args..., comm), global arg shapes + specs, out spec).

    The global shapes shard to qwen3-0.6b's per-die blocks on mx=my=2."""
    def sds(shape, spec):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                    sharding=NamedSharding(mesh, spec))
    x_tok, w_sq = P("data", "mx", "my"), P("my", "mx")
    return {
        "ag_matmul": (
            lambda x, w, c: RM.ag_matmul(x, w, "mx", dim=1, n=2, mesh_axes=AX,
                                         comm_dtype=c),
            [sds((1, S, D), x_tok), sds((D, D), w_sq)],
            P("data", None, ("mx", "my"))),
        "matmul_rs": (
            lambda x, w, c: RM.matmul_rs(x, w, "my", scatter_dim=2, n=2,
                                         mesh_axes=AX, comm_dtype=c),
            [sds((1, S, D), P("data", None, "my")), sds((D, 2 * D), w_sq)],
            P("data", None, ("mx", "my"))),
        "ag_matmul_contract": (
            lambda x, w, c: RM.ag_matmul_contract(x, w, "my", n=2,
                                                  mesh_axes=AX, comm_dtype=c),
            [sds((1, S, 2 * D), P("data", None, ("mx", "my"))),
             sds((2 * D, D), P("mx", "my"))],
            P("data", "mx", "my")),
        "matmul_rs_pair": (
            lambda x, w1, w2, c: RM.matmul_rs_pair(
                x, w1, w2, "my", scatter_dim=1, n=2, mesh_axes=AX,
                comm_dtype=c),
            [sds((1, S, D), x_tok), sds((D, F), w_sq), sds((D, F), w_sq)],
            (P("data", "my", "mx"), P("data", "my", "mx"))),
    }


def _sum(y):
    return sum(jnp.sum(v.astype(jnp.float32)) for v in jax.tree.leaves(y))


@pytest.mark.parametrize("mode", ["fwd", "grad"])
@pytest.mark.parametrize("comm", ["bf16", "int8"])
@pytest.mark.parametrize("op", ["ag_matmul", "matmul_rs", "ag_matmul_contract",
                                "matmul_rs_pair"])
def test_ring_kernel_compiles(mesh, monkeypatch, op, comm, mode):
    monkeypatch.setattr(compat, "remote_dma_supported", lambda: True)
    fn, args, out_spec = _cases(mesh)[op]
    sm = jax.shard_map(lambda *a: fn(*a, comm), mesh=mesh,
                       in_specs=tuple(a.sharding.spec for a in args),
                       out_specs=out_spec, check_vma=False)
    f = sm if mode == "fwd" else jax.grad(
        lambda *a: _sum(sm(*a)), argnums=tuple(range(len(args))))
    text = jax.jit(f).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m,k,n", [(S, D, F), (1000, 1000, D)],
                         ids=["aligned", "ragged"])
def test_tile_matmul_compiles(topo, monkeypatch, m, k, n):
    """Forward and grad: the ragged extents take full-dim or
    (8, 128)-aligned blocks, never a tile the compiler refuses."""
    monkeypatch.setattr(compat, "remote_dma_supported", lambda: True)
    one = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one)
    w = jax.ShapeDtypeStruct((k, n), jnp.bfloat16, sharding=one)
    f = jax.value_and_grad(lambda x, w: _sum(RM.tile_matmul(x, w)),
                           argnums=(0, 1))
    text = jax.jit(f).lower(x, w).compile().as_text()
    assert text.count("tpu_custom_call") >= 3      # y, dx and dw


def test_pick_block_is_tpu_tileable():
    for dim in (1000, 1024, 3072, 76032, 40, 8 * 127):
        for pref, align in ((RM.BLOCK_M, RM.SUBLANE), (RM.BLOCK_K, RM.LANE)):
            b = RM.pick_block(dim, pref, align)
            assert dim % b == 0
            assert b == dim or (b % align == 0 and b <= pref), (dim, b)


# ---------------------------------------------------------------------------
# flash attention on the one-chip train step
# ---------------------------------------------------------------------------

def _train_step(topo):
    """qwen3-0.6b cut to 2 layers, seq 4096 x batch 2, full remat, compiled
    for one described v5e: (compiled text, temporaries, traced paths)."""
    from repro.config import ParallelConfig, RunConfig, get_config
    from repro.models import attention as ATT
    from repro.models import lm
    from repro.optim import adamw
    from repro.train import step as TS

    one = SingleDeviceSharding(topo.devices[0])
    cfg = get_config("qwen3-0.6b").scaled(num_layers=2)
    pcfg = ParallelConfig(data=1, model=1, mx=1, my=1, microbatches=1,
                          remat="full")
    step = TS.build_train_step(cfg, pcfg, RunConfig("t", "train", 4096, 2),
                               None)
    p = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))
    o = jax.eval_shape(adamw.init, p)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)
    tok = jax.ShapeDtypeStruct((2, 4096), jnp.int32, sharding=one)
    ATT.sdpa_paths.clear()
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        on_chip(p), on_chip(o), {"tokens": tok, "labels": tok}).compile()
    paths = [(path, reason) for path, _, reason in ATT.sdpa_paths]
    return (compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes,
            paths)


@pytest.fixture(scope="module")
def flash_step(topo):
    return _train_step(topo)


def _custom_calls(hlo_text: str):
    """{instruction: op_name} of every tpu_custom_call.  An instruction's
    attributes may run over several lines (a kernel's JSON metadata)."""
    out = {}
    for chunk in re.split(r"\n(?=\s*(?:ROOT\s+)?%)", hlo_text):
        if 'custom_call_target="tpu_custom_call"' in chunk:
            name = re.match(r"\s*(?:ROOT\s+)?%([\w.\-]+)", chunk).group(1)
            op = re.search(r'op_name="([^"]*)"', chunk)
            out[name] = op.group(1) if op else ""
    return out


def test_train_step_records_flash(flash_step):
    _, _, paths = flash_step
    assert paths and set(paths) == {("flash", None)}


def test_train_step_holds_flash_kernels(flash_step):
    """Forward (and its remat recompute), dQ and dK/dV."""
    kinds = {re.sub(r"\.\d+$", "", n) for n in _custom_calls(flash_step[0])}
    assert kinds == {"splash_mha_fwd_residuals", "splash_mha_dq_no_residuals",
                     "splash_mha_dkv_no_residuals"}, kinds


def test_flash_kernels_carry_the_sdpa_scope(flash_step):
    calls = _custom_calls(flash_step[0])
    assert calls
    for name, op_name in calls.items():
        assert "attention/" in op_name and "/sdpa/" in op_name, (name, op_name)
    assert any("transpose(" in op for op in calls.values())     # backward


def test_flash_step_needs_less_memory_than_sdpa(topo, monkeypatch,
                                                flash_step):
    """Against the same step with the kernel refused: the parent's _sdpa."""
    monkeypatch.setattr(FA, "refusal", lambda seq, head_dim: "refused")
    text, temp, paths = _train_step(topo)
    assert set(paths) == {("jnp", "refused")}
    assert "tpu_custom_call" not in text
    assert flash_step[1] < temp, (flash_step[1], temp)


# ---------------------------------------------------------------------------
# flash attention on the Hecaton 2x2 train step
# ---------------------------------------------------------------------------

def _mesh_train_step(mesh):
    """A granite-shaped step cut to small widths (MQA: 4 query heads and one
    128-wide K/V head, 2 layers, seq 4096 x batch 2, full remat) on the
    described 2x2 grid: (compiled text, temporaries, traced paths)."""
    from repro.config import ModelConfig, ParallelConfig, RunConfig
    from repro.models import attention as ATT
    from repro.models import lm
    from repro.parallel import specs as SP
    from repro.optim import adamw
    from repro.train import step as TS

    cfg = ModelConfig(name="granite-shaped", family="dense", num_layers=2,
                      d_model=512, num_heads=4, num_kv_heads=1, head_dim=128,
                      d_ff=2048, vocab_size=1024, mlp_kind="gelu",
                      tie_embeddings=False)
    pcfg = ParallelConfig(strategy="hecaton", data=1, model=4, mx=2, my=2,
                          microbatches=1, remat="full")
    p = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))
    pspecs = SP.param_specs(p, mesh, pcfg)
    o = jax.eval_shape(adamw.init, p)
    ospecs = SP.opt_state_specs(pspecs, p, mesh, pcfg)

    def sds(tree, specs):
        return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=s), tree,
            SP.sharding_tree(specs, mesh))
    tok = jax.ShapeDtypeStruct((2, 4096), jnp.int32,
                               sharding=NamedSharding(mesh, P("data", "mx")))
    step = TS.build_train_step(cfg, pcfg, RunConfig("t", "train", 4096, 2),
                               mesh)
    ATT.sdpa_paths.clear()
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        sds(p, pspecs), sds(o, ospecs),
        {"tokens": tok, "labels": tok}).compile()
    paths = [(path, reason) for path, _, reason in ATT.sdpa_paths]
    return (compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes,
            paths)


@pytest.fixture(scope="module")
def mesh_flash_step(mesh):
    return _mesh_train_step(mesh)


def test_mesh_step_records_flash(mesh_flash_step):
    _, _, paths = mesh_flash_step
    assert paths and set(paths) == {("flash", None)}


def test_mesh_step_holds_flash_kernels(mesh_flash_step):
    """Inside the shard_map over the head axes: forward (and its remat
    recompute), dQ and dK/dV."""
    kinds = {re.sub(r"\.\d+$", "", n)
             for n in _custom_calls(mesh_flash_step[0])}
    assert kinds == {"splash_mha_fwd_residuals", "splash_mha_dq_no_residuals",
                     "splash_mha_dkv_no_residuals"}, kinds


def test_mesh_flash_step_needs_no_more_memory_than_sdpa(mesh, monkeypatch,
                                                         mesh_flash_step):
    """Against the same step with the kernel refused: the parent's _sdpa
    with the one K/V head repeated."""
    monkeypatch.setattr(FA, "refusal", lambda seq, head_dim: "refused")
    text, temp, paths = _mesh_train_step(mesh)
    assert set(paths) == {("jnp", "refused")}
    assert "tpu_custom_call" not in text
    assert mesh_flash_step[1] <= temp, (mesh_flash_step[1], temp)


def _site_a():
    def f(q):
        return FA.flash_attention(q, q, q)
    return f


def _site_b():
    def f(q):
        return FA.flash_attention(q, q, q)
    return f


def test_step_hlo_kernel_digest_ignores_call_site(topo):
    """``tools/step_hlo.py`` compares steps across checkouts and callers:
    the same kernel called from two places prints the same text, though
    each serialized body carries its call site's file, line and function."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "step_hlo", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "step_hlo.py"))
    step_hlo = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(step_hlo)
    one = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((1, 1, 256, 128), jnp.bfloat16, sharding=one)
    raw = []
    for site in (_site_a, _site_b):
        jax.clear_caches()          # else the kernel's first trace is reused
        raw.append(jax.jit(site()).lower(q).compile().as_text())
    bodies = [re.findall(r'"body":"([^"]*)"', t) for t in raw]
    assert bodies[0] and bodies[0] != bodies[1]
    a, b = (step_hlo.strip_metadata(t) for t in raw)
    assert a == b and '"body":"sha256:' in a
