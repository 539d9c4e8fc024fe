"""Attention mixers: GQA/MQA (qwen/nemotron/granite/grok/...), MLA (minicpm3),
cross-attention (whisper).  All projections route through PCtx so the Hecaton
§IV-C dataflow (sequence gathered, heads sharded, AG/RS only) applies uniformly.

The attention core of a causal self-attention over whole sequences (a
training step) is the flash kernel of kernels/flash_attention.py where the
step is lowered for TPU; on a mesh it runs in a shard_map on each chip's
whole heads.  Every other core (KV caches, cross- and non-causal attention,
MLA, K/V heads a mesh cannot split, other platforms) is ``_sdpa``, a softmax
chunked over q blocks (``lax.map``) so that no [Sq,Sk] score matrix is built
whole, or ``_sdpa_grouped_decode``.  ``sdpa_paths`` records which was taken.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.config import ModelConfig
from repro.core import quant as QU
from repro.kernels import flash_attention as FA
from repro.models import layers as L

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def init_attn(cfg: ModelConfig, key, cross: bool = False):
    dh = cfg.resolved_head_dim
    nh, nkv, H = cfg.num_heads, cfg.num_kv_heads, cfg.d_model
    ks = jax.random.split(key, 8)
    p = {
        "wq": L.normal_init(ks[0], (H, nh * dh)),
        "wk": L.normal_init(ks[1], (H, nkv * dh)),
        "wv": L.normal_init(ks[2], (H, nkv * dh)),
        "wo": L.normal_init(ks[3], (nh * dh, H), scale=1.0 / (nh * dh) ** 0.5),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), jnp.float32)
        p["k_norm"] = jnp.ones((dh,), jnp.float32)
    return p


def init_mla(cfg: ModelConfig, key):
    m = cfg.mla
    H, nh = cfg.d_model, cfg.num_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq_a": L.normal_init(ks[0], (H, m.q_lora_rank)),
        "q_norm": jnp.ones((m.q_lora_rank,), jnp.float32),
        "wq_b": L.normal_init(ks[1], (m.q_lora_rank, nh * (dn + dr))),
        "wkv_a": L.normal_init(ks[2], (H, m.kv_lora_rank + dr)),
        "kv_norm": jnp.ones((m.kv_lora_rank,), jnp.float32),
        "wkv_b": L.normal_init(ks[3], (m.kv_lora_rank, nh * (dn + dv))),
        "wo": L.normal_init(ks[4], (nh * dv, H), scale=1.0 / (nh * dv) ** 0.5),
    }


# ---------------------------------------------------------------------------
# KV caches
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    k: jax.Array          # [B, S_max, nkv, dh]
    v: jax.Array
    length: jax.Array     # [] int32 — tokens filled


class MLACache(NamedTuple):
    c_kv: jax.Array       # [B, S_max, kv_lora]
    k_rope: jax.Array     # [B, S_max, dr]
    length: jax.Array


class PagedKVCache(NamedTuple):
    """Block-paged KV cache (serving tier, docs/DESIGN.md §10).

    The arena is ONE pool of fixed-size blocks shared by every decode slot;
    slot b owns the blocks listed in ``block_table[b]`` (0 = the reserved
    null block that absorbs writes from padded/inactive slots and backs
    table entries beyond a slot's leased range).  ``lengths`` is per-slot —
    continuous batching means every row sits at a different position.
    """
    k: jax.Array            # [n_blocks, block, nkv, dh] shared arena
    v: jax.Array
    block_table: jax.Array  # [B, max_blocks] int32 block ids (0 = null)
    lengths: jax.Array      # [B] int32 tokens already written per slot


class PagedMLACache(NamedTuple):
    """Paged variant of :class:`MLACache` (same block-table protocol)."""
    c_kv: jax.Array         # [n_blocks, block, kv_lora]
    k_rope: jax.Array       # [n_blocks, block, dr]
    block_table: jax.Array  # [B, max_blocks] int32
    lengths: jax.Array      # [B] int32


class QuantPagedKVCache(NamedTuple):
    """Int8 block-paged KV arena (docs/DESIGN.md §11).

    Same block-table protocol as :class:`PagedKVCache`, but the payload
    arenas hold per-token-per-head symmetric int8 with a trailing-1 fp32
    scale arena alongside (scale = max|row| / 127 over the head dim, 1.0
    for all-zero rows so untouched blocks dequantize to exact zeros).
    Attention dequantizes into the compute dtype at gather time; the
    fp paged path is untouched when the arena is dense.
    """
    k: jax.Array            # int8 [n_blocks, block, nkv, dh]
    k_scale: jax.Array      # f32  [n_blocks, block, nkv, 1]
    v: jax.Array
    v_scale: jax.Array
    block_table: jax.Array  # [B, max_blocks] int32
    lengths: jax.Array      # [B] int32


class QuantPagedMLACache(NamedTuple):
    """Int8 paged variant of :class:`PagedMLACache` (docs/DESIGN.md §11)."""
    c_kv: jax.Array         # int8 [n_blocks, block, kv_lora]
    c_scale: jax.Array      # f32  [n_blocks, block, 1]
    k_rope: jax.Array       # int8 [n_blocks, block, dr]
    r_scale: jax.Array      # f32  [n_blocks, block, 1]
    block_table: jax.Array  # [B, max_blocks] int32
    lengths: jax.Array      # [B] int32


def init_kv_cache(cfg: ModelConfig, batch: int, s_max: int, dtype):
    dh = cfg.resolved_head_dim
    return KVCache(jnp.zeros((batch, s_max, cfg.num_kv_heads, dh), dtype),
                   jnp.zeros((batch, s_max, cfg.num_kv_heads, dh), dtype),
                   jnp.zeros((), jnp.int32))


def init_mla_cache(cfg: ModelConfig, batch: int, s_max: int, dtype):
    m = cfg.mla
    return MLACache(jnp.zeros((batch, s_max, m.kv_lora_rank), dtype),
                    jnp.zeros((batch, s_max, m.qk_rope_head_dim), dtype),
                    jnp.zeros((), jnp.int32))


def init_paged_kv(cfg: ModelConfig, num_blocks: int, block: int, batch: int,
                  max_blocks: int, dtype):
    dh = cfg.resolved_head_dim
    return PagedKVCache(
        jnp.zeros((num_blocks, block, cfg.num_kv_heads, dh), dtype),
        jnp.zeros((num_blocks, block, cfg.num_kv_heads, dh), dtype),
        jnp.zeros((batch, max_blocks), jnp.int32),
        jnp.zeros((batch,), jnp.int32))


def init_paged_mla(cfg: ModelConfig, num_blocks: int, block: int, batch: int,
                   max_blocks: int, dtype):
    m = cfg.mla
    return PagedMLACache(
        jnp.zeros((num_blocks, block, m.kv_lora_rank), dtype),
        jnp.zeros((num_blocks, block, m.qk_rope_head_dim), dtype),
        jnp.zeros((batch, max_blocks), jnp.int32),
        jnp.zeros((batch,), jnp.int32))


def _quant_arena_dtype(row_dim: int, dtype):
    """Degrade rule for arenas, mirroring the wire-side ``quant_ok`` gate:
    rows narrower than MIN_QUANT_DIM keep the dense dtype (a per-row scale
    would eat the byte win and the coarse scale hurts accuracy — DESIGN
    §11); the scale arena still exists but stays at its init value of 1.0
    and the write/gather dispatch on the arena dtype skips it."""
    return jnp.int8 if row_dim >= QU.MIN_QUANT_DIM else dtype


def init_paged_kv_quant(cfg: ModelConfig, num_blocks: int, block: int,
                        batch: int, max_blocks: int, dtype=jnp.float32):
    dh = cfg.resolved_head_dim
    nkv = cfg.num_kv_heads
    dt = _quant_arena_dtype(dh, dtype)
    return QuantPagedKVCache(
        jnp.zeros((num_blocks, block, nkv, dh), dt),
        jnp.ones((num_blocks, block, nkv, 1), jnp.float32),
        jnp.zeros((num_blocks, block, nkv, dh), dt),
        jnp.ones((num_blocks, block, nkv, 1), jnp.float32),
        jnp.zeros((batch, max_blocks), jnp.int32),
        jnp.zeros((batch,), jnp.int32))


def init_paged_mla_quant(cfg: ModelConfig, num_blocks: int, block: int,
                         batch: int, max_blocks: int, dtype=jnp.float32):
    m = cfg.mla
    return QuantPagedMLACache(
        jnp.zeros((num_blocks, block, m.kv_lora_rank),
                  _quant_arena_dtype(m.kv_lora_rank, dtype)),
        jnp.ones((num_blocks, block, 1), jnp.float32),
        jnp.zeros((num_blocks, block, m.qk_rope_head_dim),
                  _quant_arena_dtype(m.qk_rope_head_dim, dtype)),
        jnp.ones((num_blocks, block, 1), jnp.float32),
        jnp.zeros((batch, max_blocks), jnp.int32),
        jnp.zeros((batch,), jnp.int32))


def paged_write(arena, vals, block_table, lengths):
    """Scatter ``vals`` [B, S, ...] into the block arena.

    Token s of row b lands at absolute position ``lengths[b] + s``, i.e.
    block ``block_table[b, pos // block]`` offset ``pos % block``.  Positions
    past the table's leased range resolve to the null block (entry 0), so
    prompt padding and inactive decode slots write trash into block 0
    instead of corrupting a neighbour's lease; duplicate null-block indices
    scatter in unspecified order, which is fine — null-block contents are
    never read unmasked."""
    B, S = vals.shape[:2]
    block = arena.shape[1]
    pos = lengths[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    blk_slot = jnp.minimum(pos // block, block_table.shape[1] - 1)
    blk = jnp.take_along_axis(block_table, blk_slot, axis=1)       # [B,S]
    return arena.at[blk, pos % block].set(vals.astype(arena.dtype))


def paged_gather(arena, block_table):
    """Gather a slot-contiguous [B, max_blocks*block, ...] view of the pages.

    Positions beyond a slot's length read null-block / stale-lease garbage;
    every consumer masks with the per-slot ``lengths`` (exact-zero softmax
    weights — see the bit-exactness argument in docs/DESIGN.md §10)."""
    B, nblk = block_table.shape
    g = arena[block_table]                     # [B, nblk, block, ...]
    return g.reshape(B, nblk * arena.shape[1], *arena.shape[2:])


def quant_paged_write(arena, scales, vals, block_table, lengths):
    """Quantize ``vals`` [B, S, ...] per trailing-axis row and scatter the
    int8 payload and its fp32 scales at identical arena indices (same
    null-block semantics as :func:`paged_write`).  Degraded components
    (dense-dtype arena, MIN_QUANT_DIM rule) bypass quantization and leave
    the scale arena untouched."""
    if arena.dtype != jnp.int8:
        return paged_write(arena, vals, block_table, lengths), scales
    q, s = QU.quant_int8(vals)
    B, S = vals.shape[:2]
    block = arena.shape[1]
    pos = lengths[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    blk_slot = jnp.minimum(pos // block, block_table.shape[1] - 1)
    blk = jnp.take_along_axis(block_table, blk_slot, axis=1)       # [B,S]
    off = pos % block
    return arena.at[blk, off].set(q), scales.at[blk, off].set(s)


def quant_paged_gather(arena, scales, block_table, dtype):
    """Gather + dequantize the paged int8 view into ``dtype``.  The same
    lengths-masking argument as :func:`paged_gather` applies — garbage past
    a slot's length is finite (scale arenas init to 1.0) and masked out.
    Degraded (dense-dtype) components gather without dequantization."""
    if arena.dtype != jnp.int8:
        return paged_gather(arena, block_table).astype(dtype)
    B, nblk = block_table.shape
    g = QU.dequant_int8(arena[block_table], scales[block_table], dtype)
    return g.reshape(B, nblk * arena.shape[1], *arena.shape[2:])


# ---------------------------------------------------------------------------
# core attention math: the flash kernel, or chunked over q blocks
# ---------------------------------------------------------------------------

# (path, q shape, reason) of every attention core traced, appended at trace
# time: path "flash" (the kernel where the step is lowered for TPU, _sdpa on
# other platforms) or "jnp" (_sdpa, _sdpa_grouped_decode), with the reason the
# kernel was not taken.  A caller that must see the paths clears it first.
sdpa_paths: list = []


def _flash_ok(pctx, q, k, v, *, causal: bool, cache, layout=None) -> bool:
    """Whether the flash kernel takes this core (q [B,Sq,nh,dh], k [B,Sk,..],
    v [B,Sk,..,dv]), recorded in ``sdpa_paths``.  It takes a causal
    self-attention over whole sequences: no KV cache (decode,
    prefill-continue), v as wide as q and k, the kernel's tiling, and on a
    mesh a layout whose shards :func:`_on_heads` can cut."""
    reason = ("kv cache" if cache is not None else
              "not causal" if not causal else
              "Sq != Sk" if q.shape[1] != k.shape[1] else
              "v head dim != qk head dim" if v.shape[-1] != q.shape[-1] else
              FA.refusal(q.shape[1], q.shape[-1]) or
              _mesh_refusal(pctx, layout, q.shape[0], k.shape[2]))
    sdpa_paths.append(("jnp" if reason else "flash", tuple(q.shape), reason))
    return reason is None


def _kv_spec(pctx, layout, nkv: int):
    """K/V's spec beside ``layout.q_spec()``, K/V not repeated: on q's head
    axes where the kv heads divide over them, replicated over the model axes
    for one (MQA) kv head, else None."""
    qs = layout.q_spec()
    if nkv % pctx.ax.size(layout.head_axes) == 0:
        return qs
    return P(qs[0], None, None, None) if nkv == 1 else None


def _mesh_refusal(pctx, layout, batch: int, nkv: int):
    """Why a mesh's layout cannot give each chip whole heads of whole
    sequences of its own batch rows, or None (also without a mesh)."""
    if pctx.mesh is None:
        return None
    if layout is None:
        return "mesh without an attention layout"
    n_b = pctx.ax.size(layout.batch_axes)
    if batch % n_b:
        return f"batch {batch} not divisible over {n_b} batch shards"
    if _kv_spec(pctx, layout, nkv) is None:
        return (f"kv heads {nkv} not divisible over "
                f"{pctx.ax.size(layout.head_axes)} head shards")
    return None


def _on_heads(pctx, layout, core, q, k, v):
    """``core(q, k, v)`` on each chip's batch rows and whole heads: a
    shard_map over ``layout`` on a mesh (K/V by :func:`_kv_spec`; its
    transpose sums dK/dV over the chips that share a kv head), the call
    itself without one.  The sequence and the head width stay whole."""
    if pctx.mesh is None:
        return core(q, k, v)
    qs, kvs = layout.q_spec(), _kv_spec(pctx, layout, k.shape[2])
    return compat.shard_map(core, pctx.mesh, (qs, kvs, kvs), qs)(q, k, v)


def _flash(q, k, v, *, interpret: bool = False):
    """The kernel on q [B,S,nh,dh], k,v [B,S,nkv,dh]."""
    t = lambda x: x.transpose(0, 2, 1, 3)       # noqa: E731
    return t(FA.flash_attention(t(q), t(k), t(v), interpret=interpret))


def _causal_self_attention(q, k, v, q_block: int):
    """q [B,S,nh,dh]; k,v [B,S,nkv,dh] (GQA, not repeated).  The flash kernel
    where the step is lowered for TPU, ``_sdpa`` on any other platform."""
    def chunked(q, k, v):
        g = q.shape[2] // k.shape[2]
        return _sdpa(q, _repeat_kv(k, g), _repeat_kv(v, g), causal=True,
                     q_offset=0, q_block=q_block)
    return lax.platform_dependent(q, k, v, tpu=_flash, default=chunked)


@jax.named_scope("sdpa")
def _sdpa(q, k, v, *, causal: bool, q_offset, kv_len=None, q_block: int = 1024):
    """q [B,Sq,nh,dh]; k,v [B,Sk,nh,dh] (kv already repeated to nh).

    Chunked over Sq: scores per block are [B,nh,q_block,Sk] — never [Sq,Sk].
    ``q_offset`` is the absolute position of q[0] (decode / prefill-continue).
    ``kv_len`` masks the unfilled cache tail.
    """
    B, Sq, nh, dh = q.shape
    Sk = k.shape[1]
    scale = dh ** -0.5
    kt = k.transpose(0, 2, 3, 1)         # [B,nh,dh,Sk]
    vt = v.transpose(0, 2, 1, 3)         # [B,nh,Sk,dh]
    kv_pos = jnp.arange(Sk)

    def block(qb, qpos):
        # qb [B,nh,bq,dh]
        s = jnp.einsum("bhqd,bhdk->bhqk", qb.astype(jnp.float32),
                       kt.astype(jnp.float32)) * scale
        mask = jnp.ones((qpos.shape[0], Sk), bool)
        if causal:
            mask &= kv_pos[None, :] <= qpos[:, None]
        if kv_len is not None:
            mask &= kv_pos[None, :] < kv_len
        s = jnp.where(mask[None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, vt.astype(jnp.float32))

    qh = q.transpose(0, 2, 1, 3)         # [B,nh,Sq,dh]
    if Sq % q_block:                     # non-divisible (e.g. 1500 frames): direct
        q_block = Sq
    if Sq <= q_block:
        o = block(qh, q_offset + jnp.arange(Sq))
    else:
        nb = Sq // q_block
        qb = qh.reshape(B, nh, nb, q_block, dh).transpose(2, 0, 1, 3, 4)
        pos = (q_offset + jnp.arange(Sq)).reshape(nb, q_block)
        o = lax.map(lambda args: block(*args), (qb, pos))
        o = o.transpose(1, 2, 0, 3, 4).reshape(B, nh, Sq, -1)   # -1: v dh may differ
    return o.transpose(0, 2, 1, 3).astype(q.dtype)     # [B,Sq,nh,dh]


def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    B, S, nkv, dh = k.shape
    return jnp.repeat(k, n_rep, axis=2)


@jax.named_scope("sdpa")
def _sdpa_grouped_decode(q, k, v, *, kv_len):
    """Decode-step attention WITHOUT repeating KV (GQA grouped einsum).

    q [B,1,nkv,g,dh]; k,v [B,S,nkv,dh].  Keeps the KV cache sharded by kv-head
    — repeating to q-heads at decode would force XLA to materialize/all-gather
    the multi-GB cache across the grid.
    """
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqcgd,bscd->bcgqs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = jnp.arange(k.shape[1])[None, :] < kv_len
    s = jnp.where(mask[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bcgqs,bscd->bqcgd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------

@jax.named_scope("attention")
def apply_attn(pctx, cfg: ModelConfig, p, x, *, positions, causal: bool = True,
               cache: Optional[KVCache] = None, layout=None,
               q_block: int = 1024) -> Tuple[jax.Array, Optional[KVCache]]:
    """x [B,S,H] canonical -> (y [B,S,H] canonical, updated cache)."""
    dh = cfg.resolved_head_dim
    nh, nkv = cfg.num_heads, cfg.num_kv_heads
    B, S, _ = x.shape

    # one shared entry gather for the q/k/v trio (megatron seq layout
    # ring-gathers the token shard once; hecaton/replicated fall back)
    qp, kp, vp = pctx.mixer_in_many(x, p["wq"], p["wk"], p["wv"])
    q = qp.reshape(B, S, nh, dh)
    k = kp.reshape(B, S, nkv, dh)
    v = vp.reshape(B, S, nkv, dh)

    hspec = pctx.heads_spec(layout) if layout is not None else None
    q = pctx.constraint(q, hspec)

    if cfg.qk_norm:
        q = L.rms_head_norm(p["q_norm"], q)
        k = L.rms_head_norm(p["k_norm"], k)
    cos, sin = L.rope_cos_sin(positions, dh, cfg.rope_theta)
    q = L.apply_rope(q, cos, sin)
    k = L.apply_rope(k, cos, sin)

    new_cache, kv_len, q_off = None, None, jnp.zeros((), jnp.int32)
    if isinstance(cache, (PagedKVCache, QuantPagedKVCache)):
        # paged serving path: write the new tokens through the block table,
        # then attend over the gathered page view (per-slot lengths mask the
        # unwritten tail exactly — docs/DESIGN.md §10).  The int8 arena
        # variant quantizes at write time and dequantizes at gather time
        # (docs/DESIGN.md §11); attention math downstream is identical.
        if isinstance(cache, QuantPagedKVCache):
            kc, ksc = quant_paged_write(cache.k, cache.k_scale, k,
                                        cache.block_table, cache.lengths)
            vc, vsc = quant_paged_write(cache.v, cache.v_scale, v,
                                        cache.block_table, cache.lengths)
            new_cache = QuantPagedKVCache(kc, ksc, vc, vsc,
                                          cache.block_table,
                                          cache.lengths + S)
            k = quant_paged_gather(kc, ksc, cache.block_table, x.dtype)
            v = quant_paged_gather(vc, vsc, cache.block_table, x.dtype)
        else:
            kc = paged_write(cache.k, k, cache.block_table, cache.lengths)
            vc = paged_write(cache.v, v, cache.block_table, cache.lengths)
            new_cache = PagedKVCache(kc, vc, cache.block_table,
                                     cache.lengths + S)
            k = paged_gather(kc, cache.block_table)
            v = paged_gather(vc, cache.block_table)
        if S == 1:
            kv_len = (cache.lengths + S)[:, None]          # [B,1] per-slot
        else:
            # paged prefill is per-admission (one sequence): scalar offsets
            assert B == 1, "paged prefill runs one sequence at a time"
            kv_len, q_off = cache.lengths[0] + S, cache.lengths[0]
    elif cache is not None:
        kc = lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype),
                                      (0, cache.length, 0, 0))
        vc = lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype),
                                      (0, cache.length, 0, 0))
        new_cache = KVCache(kc, vc, cache.length + S)
        k, v = kc, vc
        kv_len, q_off = new_cache.length, cache.length

    if _flash_ok(pctx, q, k, v, causal=causal, cache=cache, layout=layout):
        o = _on_heads(pctx, layout,
                      lambda q, k, v: _causal_self_attention(q, k, v, q_block),
                      q, k.astype(q.dtype), v.astype(q.dtype))
    elif cache is not None and S == 1:
        # decode: grouped GQA, KV cache stays kv-head-sharded
        kv_lay = pctx.attn_layout(nkv, B)
        ba = None
        if pctx.mesh is not None and B % pctx.ax.n_data == 0:
            ba = kv_lay.batch_axes
        kvh = kv_lay.head_axes or None
        import jax.sharding as _js
        qspec = (None if pctx.mesh is None else
                 _js.PartitionSpec(ba if not ba or len(ba) > 1 else ba[0], None,
                                   kvh if not kvh or len(kvh) > 1 else kvh[0],
                                   None, None))
        kspec = (None if pctx.mesh is None else
                 _js.PartitionSpec(ba if not ba or len(ba) > 1 else ba[0], None,
                                   kvh if not kvh or len(kvh) > 1 else kvh[0],
                                   None))
        g = nh // nkv
        q5 = pctx.constraint(q.reshape(B, S, nkv, g, dh), qspec)
        k = pctx.constraint(k.astype(q.dtype), kspec)
        v = pctx.constraint(v.astype(q.dtype), kspec)
        o = _sdpa_grouped_decode(q5, k, v, kv_len=kv_len)
        o = o.reshape(B, S, nh, dh)
    else:
        k = pctx.constraint(_repeat_kv(k.astype(q.dtype), nh // nkv), hspec)
        v = pctx.constraint(_repeat_kv(v.astype(q.dtype), nh // nkv), hspec)
        o = _sdpa(q, k, v, causal=causal, q_offset=q_off, kv_len=kv_len,
                  q_block=q_block)
        o = pctx.constraint(o, hspec)
    y = pctx.mixer_out(o.reshape(B, S, nh * dh), p["wo"])
    return y, new_cache


@jax.named_scope("attention")
def apply_cross_attn(pctx, cfg: ModelConfig, p, x, memory_kv, *, layout=None):
    """Whisper cross-attention: q from decoder x, k/v precomputed from encoder."""
    dh = cfg.resolved_head_dim
    nh, nkv = cfg.num_heads, cfg.num_kv_heads
    B, S, _ = x.shape
    q = pctx.mixer_in(x, p["wq"]).reshape(B, S, nh, dh)
    hspec = pctx.heads_spec(layout) if layout is not None else None
    q = pctx.constraint(q, hspec)
    k, v = memory_kv
    _flash_ok(pctx, q, k, v, causal=False, cache=None)
    k = pctx.constraint(_repeat_kv(k.astype(q.dtype), nh // nkv), hspec)
    v = pctx.constraint(_repeat_kv(v.astype(q.dtype), nh // nkv), hspec)
    o = _sdpa(q, k, v, causal=False, q_offset=jnp.zeros((), jnp.int32))
    return pctx.mixer_out(o.reshape(B, S, nh * dh), p["wo"])


def cross_kv(pctx, cfg: ModelConfig, p, memory):
    """Precompute cross-attention K/V from encoder output (cached for decode)."""
    B, Sm, _ = memory.shape
    dh, nkv = cfg.resolved_head_dim, cfg.num_kv_heads
    kp, vp = pctx.mixer_in_many(memory, p["wk"], p["wv"])
    return kp.reshape(B, Sm, nkv, dh), vp.reshape(B, Sm, nkv, dh)


# ---------------------------------------------------------------------------
# MLA (minicpm3 / deepseek style)
# ---------------------------------------------------------------------------

@jax.named_scope("attention")
def apply_mla(pctx, cfg: ModelConfig, p, x, *, positions,
              cache: Optional[MLACache] = None, layout=None, q_block: int = 1024):
    m = cfg.mla
    nh, H = cfg.num_heads, cfg.d_model
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    B, S, _ = x.shape
    hspec = pctx.heads_spec(layout) if layout is not None else None

    ql, kv = pctx.mixer_in_many(x, p["wq_a"], p["wkv_a"])
    ql = L.apply_norm("rmsnorm", {"scale": p["q_norm"]}, ql)
    # ql is mixer-interior (full sequence already gathered): interior=True
    # keeps the megatron seq-sharded path from re-gathering a non-entry
    q = pctx.mixer_in(ql, p["wq_b"], interior=True).reshape(B, S, nh, dn + dr)
    q = pctx.constraint(q, hspec)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    c_kv, k_rope = kv[..., :m.kv_lora_rank], kv[..., m.kv_lora_rank:]
    c_kv = L.apply_norm("rmsnorm", {"scale": p["kv_norm"]}, c_kv)

    cos, sin = L.rope_cos_sin(positions, dr, cfg.rope_theta)
    q_rope = L.apply_rope(q_rope, cos, sin)
    k_rope = L.apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0, :]

    new_cache, kv_len, q_off = None, None, jnp.zeros((), jnp.int32)
    if isinstance(cache, (PagedMLACache, QuantPagedMLACache)):
        if isinstance(cache, QuantPagedMLACache):
            cc, csc = quant_paged_write(cache.c_kv, cache.c_scale, c_kv,
                                        cache.block_table, cache.lengths)
            kr, rsc = quant_paged_write(cache.k_rope, cache.r_scale, k_rope,
                                        cache.block_table, cache.lengths)
            new_cache = QuantPagedMLACache(cc, csc, kr, rsc,
                                           cache.block_table,
                                           cache.lengths + S)
            c_kv = quant_paged_gather(cc, csc, cache.block_table, x.dtype)
            k_rope = quant_paged_gather(kr, rsc, cache.block_table, x.dtype)
        else:
            cc = paged_write(cache.c_kv, c_kv, cache.block_table,
                             cache.lengths)
            kr = paged_write(cache.k_rope, k_rope, cache.block_table,
                             cache.lengths)
            new_cache = PagedMLACache(cc, kr, cache.block_table,
                                      cache.lengths + S)
            c_kv = paged_gather(cc, cache.block_table).astype(x.dtype)
            k_rope = paged_gather(kr, cache.block_table).astype(x.dtype)
        if S == 1:
            kv_len = (cache.lengths + S)[:, None]          # [B,1] per-slot
        else:
            assert B == 1, "paged prefill runs one sequence at a time"
            kv_len, q_off = cache.lengths[0] + S, cache.lengths[0]
    elif cache is not None:
        cc = lax.dynamic_update_slice(cache.c_kv, c_kv.astype(cache.c_kv.dtype),
                                      (0, cache.length, 0))
        kr = lax.dynamic_update_slice(cache.k_rope, k_rope.astype(cache.k_rope.dtype),
                                      (0, cache.length, 0))
        new_cache = MLACache(cc, kr, cache.length + S)
        c_kv, k_rope = cc.astype(x.dtype), kr.astype(x.dtype)
        kv_len, q_off = new_cache.length, cache.length

    if cache is not None and S == 1:
        # ---- absorbed decode (DeepSeek trick): never materialize per-head K/V.
        wkv = p["wkv_b"].reshape(m.kv_lora_rank, nh, dn + dv)
        wk_b, wv_b = wkv[..., :dn], wkv[..., dn:]
        q_abs = jnp.einsum("bshd,lhd->bshl", q_nope, wk_b)         # [B,1,nh,lora]
        s = (jnp.einsum("bshl,btl->bhst", q_abs.astype(jnp.float32),
                        c_kv.astype(jnp.float32))
             + jnp.einsum("bshd,btd->bhst", q_rope.astype(jnp.float32),
                          k_rope.astype(jnp.float32))) * ((dn + dr) ** -0.5)
        mask = jnp.arange(c_kv.shape[1])[None, :] < kv_len
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
        prob = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bhst,btl->bshl", prob, c_kv.astype(jnp.float32))
        o = jnp.einsum("bshl,lhd->bshd", o_lat, wv_b).astype(x.dtype)
    else:
        kv_up = jnp.einsum("btl,lo->bto", c_kv, p["wkv_b"].astype(c_kv.dtype),
                           preferred_element_type=jnp.float32).astype(x.dtype)
        kv_up = kv_up.reshape(B, -1, nh, dn + dv)
        k_nope, vv = kv_up[..., :dn], kv_up[..., dn:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (*k_nope.shape[:3], dr))], axis=-1)
        qq = jnp.concatenate([q_nope, q_rope], axis=-1)
        _flash_ok(pctx, qq, k, vv, causal=True, cache=cache)
        qq = pctx.constraint(qq, hspec)
        k = pctx.constraint(k, hspec)
        # Perf iteration 3b tried passing v at its native 64-dim head (saves
        # 2.5x SV flops) but GSPMD then relaid the whole SV chain with
        # per-layer collective-permutes (+678GB/chip, 40x the compute win) —
        # measured and REVERTED; see EXPERIMENTS.md. The padded-v form keeps
        # the qkv chain in one layout.
        vpad = jnp.pad(vv, ((0, 0), (0, 0), (0, 0), (0, dn + dr - dv)))
        o = _sdpa(qq, k, pctx.constraint(vpad, hspec), causal=True,
                  q_offset=q_off, kv_len=kv_len, q_block=q_block)[..., :dv]
    y = pctx.mixer_out(o.reshape(B, S, nh * dv), p["wo"])
    return y, new_cache
