"""Flash attention (online softmax) Pallas TPU kernel.

TPU-native rethinking of the standard GPU flash algorithm: instead of warp-level
shuffles, the sequential TPU grid carries running (max, sum, acc) statistics in
VMEM scratch across the KV-block axis; the MXU consumes (q_block x kv_block)
tiles.  Causal masking skips fully-masked KV blocks via pl.when.  GQA is
supported by mapping multiple q-heads onto one kv-head index (no KV repeat —
the memory argument from docs/DESIGN.md §4).

Grid: (batch*q_heads, Sq/bq, Sk/bk), KV axis innermost.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, bq: int, bk: int, n_k: int):
    kv_i = pl.program_id(2)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_i = pl.program_id(1)

    def _step():
        q = q_ref[0].astype(jnp.float32)                     # [bq, dh]
        k = k_ref[0].astype(jnp.float32)                     # [bk, dh]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = kv_i * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v_ref[0].astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:   # skip fully-masked KV blocks entirely
        pl.when(kv_i * bk <= q_i * bq + bq - 1)(_step)
    else:
        _step()

    @pl.when(kv_i == n_k - 1)
    def _done():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@jax.named_scope("sdpa")
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False) -> jax.Array:
    """q [B,nh,Sq,dh]; k,v [B,nkv,Sk,dh]; nh % nkv == 0.  Returns [B,nh,Sq,dh]."""
    B, nh, Sq, dh = q.shape
    _, nkv, Sk, _ = k.shape
    assert nh % nkv == 0
    g = nh // nkv
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0
    qf = q.reshape(B * nh, Sq, dh)
    kf = k.reshape(B * nkv, Sk, dh)
    vf = v.reshape(B * nkv, Sk, dh)
    grid = (B * nh, Sq // bq, Sk // bk)
    scale = dh ** -0.5

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, n_k=grid[2])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, dh), lambda h, i, j, g=g: (h // g, j, 0)),
            pl.BlockSpec((1, bk, dh), lambda h, i, j, g=g: (h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * nh, Sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # running max
            pltpu.VMEM((bq, 1), jnp.float32),     # running denom
            pltpu.VMEM((bq, dh), jnp.float32),    # running accumulator
        ],
        interpret=interpret,
    )(qf, kf, vf).reshape(B, nh, Sq, dh)
