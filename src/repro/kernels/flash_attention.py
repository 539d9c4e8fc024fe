"""Flash attention for TPU, forward and backward: the S x S scores of a
self-attention never leave VMEM.

The kernel is the splash attention kernel that ships with JAX
(``jax.experimental.pallas.ops.tpu.splash_attention``): an online-softmax
forward that also returns the log-sum-exp, a dQ kernel and a dK/dV kernel,
joined by ``jax.custom_vjp``.  Blocks the mask leaves empty are skipped, in
the MXU and in the DMA; GQA maps each group of q-heads onto its kv-head, so
K and V are never repeated (the memory argument of docs/DESIGN.md §4).
Operands enter the MXU in their own dtype (bf16 on the model path) and
accumulate in float32; the running max, sum and log-sum-exp are float32.

``models/attention.py`` puts :func:`flash_attention` on the causal
self-attention of a training step, on one device or, on a mesh, on each
chip's whole heads inside a ``shard_map``; :func:`refusal` is the kernel's
part of that dispatch.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as SA

LANE = 128          # q/kv blocks and the head dim tile by the lane width


def refusal(seq: int, head_dim: int) -> Optional[str]:
    """Why the kernel's tiling refuses a sequence/head width, or None."""
    if seq % LANE or head_dim % LANE:
        return f"seq {seq} or head dim {head_dim} not a multiple of {LANE}"
    return None


def _block(seq: int, pref: int) -> int:
    """The largest multiple of LANE up to ``pref`` that divides ``seq``."""
    b = min(pref, seq) // LANE * LANE
    while seq % b:
        b -= LANE
    return b


def block_sizes(seq: int) -> SA.BlockSizes:
    """One q and kv block for every kernel: half the sequence, so that the
    causal mask leaves a block to skip, and at most 1024.  A sweep of 128,
    512 and 1024 on a v5e found 1024 fastest at S=4096 and 512 at S=1024
    (PERF.md section 6)."""
    b = _block(seq, max(LANE, min(1024, seq // 2)))
    return SA.BlockSizes(block_q=b, block_kv=b, block_kv_compute=b,
                         block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=b,
                         block_q_dq=b, block_kv_dq=b)


@functools.lru_cache(maxsize=None)
def _kernel(heads: int, seq: int, causal: bool, interpret: bool):
    mask = (SA.CausalMask if causal else SA.FullMask)((seq, seq))
    # the block tables are constants, made outside any trace so that the
    # cached kernel holds arrays and not one trace's tracers
    with jax.ensure_compile_time_eval():
        return SA.make_splash_mha(SA.MultiHeadMask([mask] * heads),
                                  block_sizes=block_sizes(seq), head_shards=1,
                                  q_seq_shards=1, interpret=interpret)


@jax.named_scope("sdpa")
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, interpret: bool = False) -> jax.Array:
    """q [B,nh,S,dh]; k,v [B,nkv,S,dh], nh % nkv == 0.  Returns [B,nh,S,dh]
    in q's dtype, softmax(q k^T / sqrt(dh)) v."""
    B, nh, S, dh = q.shape
    assert k.shape[2] == S and nh % k.shape[1] == 0, (q.shape, k.shape)
    q = (q.astype(jnp.float32) * dh ** -0.5).astype(q.dtype)
    return jax.vmap(_kernel(nh, S, causal, interpret))(q, k, v)
