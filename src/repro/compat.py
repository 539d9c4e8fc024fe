"""Small backend shims shared by the model and kernel code.

``shard_map`` wraps ``jax.shard_map`` with replication checking off by
default, ``cost_analysis_dict`` flattens ``Compiled.cost_analysis()``, and
``enable_compile_cache`` gives every entry point the same persistent
compilation cache.

This module also hosts the *remote-DMA emulation shim* for the fused Pallas
ring kernels (kernels/ring_matmul.py): only a real TPU backend can execute
``pltpu.make_async_remote_copy`` between ring neighbours, so on every other
backend (CPU CI, interpret mode) the kernels replace each inter-chip hop with
a ``lax.ppermute`` ring step — identical data movement, same step count, local
compute still running through the Pallas tile loop in interpret mode."""

from __future__ import annotations

import os
from pathlib import Path

import jax
from jax import lax

# The persistent compilation cache's fallback home: fixed and inside the
# checkout (listed in .gitignore), because the path is part of the cache key.
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses it and
    nothing else is set here; otherwise the cache goes to :data:`CACHE_DIR`.
    Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def shard_map(f, mesh, in_specs, out_specs, check=False):
    """Uniform shard_map with replication checking disabled by default."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def remote_dma_supported() -> bool:
    """Can this runtime execute ``pltpu.make_async_remote_copy`` for real?

    True only on an actual TPU backend — the Pallas interpreter and the CPU/GPU
    backends have no inter-chip DMA engine.  The fused ring kernels use this to
    pick between the single-kernel remote-DMA path and the ppermute-emulated
    path (``ring_step_permute``).  A backend that fails to initialize raises
    here rather than silently selecting the emulation."""
    return jax.default_backend() == "tpu"


def ring_step_permute(x, axis_name: str, n: int, shift: int = 1):
    """One emulated fused-kernel ring hop: shard -> (rank + shift) % n.

    This is the ppermute-emulation shim for ``kernels/ring_matmul.py``: on
    backends without remote-DMA support, each ``make_async_remote_copy`` of the
    circulating VMEM buffer becomes one ``lax.ppermute`` step with the exact
    same ring permutation, so CPU CI covers the fused kernels' numerics (and
    their HLO stays a collective-permute chain)."""
    return lax.ppermute(x, axis_name, [(i, (i + shift) % n) for i in range(n)])


def cost_analysis_dict(compiled) -> dict:
    """``compiled.cost_analysis()`` as a flat dict (empty when unavailable)."""
    return dict(compiled.cost_analysis() or {})
