"""The TPU compiler accepts the ring kernels at qwen3-0.6b's per-die widths.

Each test compiles for a described (not attached) v5e 2x2 host: the fused
ring collectives of kernels/ring_matmul.py on the Hecaton mx=my=2 grid,
forward and ``jax.grad``, bf16 and int8 wire, and the plain tile matmul,
then checks that the compiled program holds the Pallas kernel
(``tpu_custom_call``).  Nothing runs, so this says nothing about results or
speed; it catches what interpret mode cannot (tile alignment, VMEM, ref
shapes).  The topology is described only inside the module fixture, after a
test has started: only one process may load the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro import compat
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
