"""Fused Pallas ring-matmul kernels (kernels/ring_matmul.py).

Numerics (fused kernels vs the core/overlap.py ring reference vs bulk
collectives, fwd+grad, epilogues, gated pair, non-tile-aligned fallback) run
in a subprocess on a fake 8-device topology (tests/_mp style).  In-process
tests cover the block/gating logic, the degenerate single-device ring and
the ``"fused"`` mode plumbing.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "tests", "_mp",
                                                     script)],
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, f"\nSTDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


def test_ring_kernel_numerics():
    """Each fused kernel == ring reference == bulk (fwd+grad), epilogues,
    gated pair, and the non-tile-aligned fused→ring fallback."""
    out = _run("check_ring_kernels.py")
    assert "ALL RING KERNEL CHECKS PASSED" in out


# ---------------------------------------------------------------------------
# In-process: block selection / gating logic
# ---------------------------------------------------------------------------


def test_pick_block_and_aligned():
    from repro.kernels.ring_matmul import aligned, pick_block

    assert pick_block(64, 128) == 64          # dim fits: one tile
    assert pick_block(256, 128) == 128        # MXU-aligned fast path
    assert pick_block(320, 128) == 80         # degraded: largest divisor
    assert 320 % pick_block(320, 128) == 0
    for dim in (1, 7, 96, 128, 129, 512, 1000):
        assert dim % pick_block(dim, 128) == 0
    assert aligned(64, 128) and aligned(256, 128)
    assert not aligned(320, 128)              # fused gate refuses this


def test_fused_ok_gates():
    from repro.kernels import ring_matmul as RM

    assert RM.fused_ok_ag((2, 4, 12), (12, 8), 4)
    assert not RM.fused_ok_ag((2, 4, 12), (12, 8), 1)       # degenerate ring
    assert not RM.fused_ok_ag((2, 160, 24), (24, 8), 4)     # M=320 unaligned
    assert RM.fused_ok_rs((2, 16, 12), (12, 8), 4, 1)
    assert not RM.fused_ok_rs((2, 10, 12), (12, 8), 4, 1)   # 10 % 4 != 0
    assert RM.fused_ok_rs((2, 16, 12), (12, 8), 4, 2)       # cols: 8 % 4 == 0
    assert not RM.fused_ok_rs((2, 16, 12), (12, 6), 4, 2)   # 6 % 4 != 0
    assert RM.fused_ok_contract((2, 16, 3), (12, 8), 4)
    assert not RM.fused_ok_contract((2, 16, 3), (13, 8), 4)  # w rows mismatch


def test_single_device_ring_matches_matmul_kernel():
    """n=1 short-circuits to the local Pallas tile loop — epilogue parity
    with kernels/matmul.py."""
    from repro.kernels import matmul as MM
    from repro.kernels import ring_matmul as RM

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(k1, (2, 8, 16), jnp.float32)
    w = jax.random.normal(k2, (16, 24), jnp.float32) / 4
    b = jax.random.normal(k3, (24,), jnp.float32)
    y = RM.ag_matmul(x, w, "none_axis", dim=1, n=1, bias=b, act="gelu")
    ref = MM.matmul(x.reshape(16, 16), w, b, act="gelu", block_m=16,
                    block_n=24, block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(y).reshape(16, 24),
                               np.asarray(ref), rtol=2e-5, atol=2e-5)
    h, g = RM.matmul_rs_pair(x, w, w, "none_axis", scatter_dim=1, n=1)
    np.testing.assert_allclose(np.asarray(h), np.asarray(g), rtol=1e-6)


def test_tile_matmul_grad_matches_einsum():
    from repro.kernels.ring_matmul import tile_matmul

    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(k1, (8, 12), jnp.float32)
    w = jax.random.normal(k2, (12, 16), jnp.float32)
    g = jax.grad(lambda a, b: tile_matmul(a, b).sum(), argnums=(0, 1))(x, w)
    gr = jax.grad(lambda a, b: (a @ b).sum(), argnums=(0, 1))(x, w)
    for got, want in zip(g, gr):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# In-process: "fused" mode plumbing
# ---------------------------------------------------------------------------


def test_fused_mode_in_lattice():
    from repro.core.overlap import MODES, check_mode

    assert MODES == ("none", "ring", "bidir", "fused")
    assert check_mode("fused") == "fused"


def test_parallel_config_accepts_fused():
    from repro.config import ParallelConfig
    from repro.parallel.context import PCtx

    assert ParallelConfig(overlap="fused").overlap == "fused"
    pctx = PCtx(mesh=None, pcfg=ParallelConfig(overlap="fused"))
    assert pctx.overlap == "fused"


def test_mesh_none_ignores_fused():
    from repro.core import hecaton as H

    x = jnp.ones((2, 4, 8), jnp.float32)
    w = jnp.ones((8, 6), jnp.float32)
    y = H.linear_seq_scatter(x, w, mesh=None, t_ax="mx", h_ax="my",
                             overlap="fused")
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w), rtol=1e-6)


def test_remote_dma_shim():
    from repro import compat

    # the container has no TPU: the fused kernels must pick the
    # ppermute-emulated interpret path
    assert compat.remote_dma_supported() is False

