"""Fused Pallas ring-matmul kernels — remote DMA double-buffered inside the tile loop.

PR 1 decomposed Hecaton's bulk AG/RS collectives into ``lax.ppermute`` rings
(core/overlap.py), which *exposes* the overlap to the XLA scheduler: each ring
step is still its own dispatch, and the permute for step ``k+1`` only hides
behind the matmul for step ``k`` if the scheduler cooperates.  This module is
the next rung (paper §III-B scheduling): the whole ring runs inside **one**
kernel, where a double-buffered VMEM pair receives the next peer's shard via
``pltpu.make_async_remote_copy`` while the MXU consumes the current shard
through the same MXU-aligned tile loop as ``kernels/matmul.py`` (fp32
accumulator scratch, fused bias/activation epilogue, gated variant reusing the
shared-x-tile trick).  Overlap is then guaranteed by construction — no
kernel-launch or VMEM-refill gap between ring steps.

Three collective-matmul shapes (mirroring core/overlap.py's ring primitives,
all called *inside* shard_map on per-device blocks):

  ``ag_matmul``           AG ⊕ matmul, gathered dim is a batch dim (tokens):
                          step *k*'s tile matmul fills its slot of the output
                          while the DMA for step *k+1* is in flight.
  ``matmul_rs``           matmul ⊕ RS: a per-destination accumulator tile
                          circulates through the VMEM pair; each step folds in
                          the local contribution straight from the MXU.
  ``ag_matmul_contract``  AG ⊕ matmul over the *contracted* dim: per-step
                          partial products accumulate in an fp32 VMEM scratch
                          that spans ring steps (epilogue on the last step).
  ``matmul_rs_pair``      gated variant: two circulating accumulators whose
                          per-step contributions read the SAME x tile from
                          VMEM (the shared-x-tile trick of
                          ``kernels/matmul.gated_matmul`` at ring scope).

Execution modes
---------------
* **TPU** (``compat.remote_dma_supported()``): single ``pallas_call`` per
  collective with ``make_async_remote_copy`` between ring neighbours,
  ``make_async_copy`` for the local prologue, per-slot DMA semaphores, and a
  REGULAR capacity semaphore providing back-pressure so a neighbour never
  lands a shard in a slot the MXU is still reading.
* **everywhere else** (CPU CI, interpret mode): the ppermute-emulation shim
  ``compat.ring_step_permute`` replaces each remote DMA hop with one
  ``lax.ppermute`` of the circulating buffer — identical data movement and
  step count — while per-step compute still runs through the Pallas tile loop
  with ``interpret=True``.  This is what the 4x2/2x2/4x1 grid numerics tests
  cover.

Autodiff: every public op carries a ``jax.custom_vjp`` whose backward is the
*transposed ring* — transpose(AG-matmul) is a matmul-RS over the reversed ring
and vice versa, exactly the pairing JAX derives automatically for the unrolled
ppermute rings in core/overlap.py.  The backward therefore stays fused /
ring-decomposed too.

Communication dtype (``comm_dtype``, docs/DESIGN.md §11): ``"bf16"`` ships
shards as-is; ``"int8"`` carries an ``(int8 payload, fp32 per-row scale)``
pair over every hop.  On the emulated path each ppermute hop routes through
``core/quant.ring_hop``; on the TPU path the double-buffered VMEM pair
becomes a quantized pair — for the AG/contract kernels the circulating shard
is quantized ONCE outside the kernel (the payload is invariant around the
ring) and dequantized per tile at the MXU dot, while the matmul-RS kernel
re-quantizes the circulating *accumulator* at each send (it changes every
hop): folds land in a full-width ``work`` staging buffer, whose whole-buffer
quantize happens right before the paired remote DMAs.  The fp32 accumulator
tiles themselves never quantize — only link traffic does.  Hops whose shard
cannot carry scales (``quant.quant_ok``) degrade per collective to the
full-width pair, mirroring the fused→ring→bulk lattice.

Fallback contract: callers gate on :func:`fused_ok` (MXU-tile-aligned dims and
ring-divisible extents).  Shapes that fail the gate are routed by
``core/overlap.py`` to the plain ``ring`` decomposition — same degradation
contract as ``bidir`` → ``ring`` for un-halvable shards.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat
from repro.core import quant as Q
from repro.kernels.matmul import _epilogue, _mm_bias_kernel, _mm_kernel

# MXU-aligned tile preferences (same defaults as kernels/matmul.py).
BLOCK_M, BLOCK_N, BLOCK_K = 128, 128, 512
# TPU vreg tiling: a block's second-to-last dim must be a multiple of SUBLANE
# and its last dim a multiple of LANE (or span the whole array dimension).
SUBLANE, LANE = 8, 128

# Per-core VMEM budget for the single-kernel scratch (double-buffered shard /
# accumulator pair + fp32 acc tiles); shapes whose scratch would exceed it are
# routed to the plain ring decomposition by the fused_ok_* gates.
VMEM_BUDGET = 12 * 2 ** 20


# ---------------------------------------------------------------------------
# Block selection / fused-mode gating
# ---------------------------------------------------------------------------


def pick_block(dim: int, pref: int, align: int = SUBLANE) -> int:
    """Largest multiple of ``align`` <= ``pref`` dividing ``dim``, else ``dim``.

    A dim no larger than the preference is its own (single) tile; otherwise
    prefer the MXU-aligned size and degrade to the largest aligned divisor,
    or to one full-extent tile when no aligned divisor exists.  Every block
    this returns is one the TPU compiler accepts (a multiple of the (8, 128)
    tiling or the whole dimension), so any shape that passes a
    :func:`fused_ok_ag`-style gate also compiles its transposed backward
    matmuls.  Pass ``align=LANE`` for a block's last (lane) dimension."""
    if dim <= pref:
        return max(dim, 1)
    for b in range(pref - pref % align, 0, -align):
        if dim % b == 0:
            return b
    return dim


def aligned(dim: int, pref: int) -> bool:
    """Tile-aligned in the fused-kernel sense: one tile, or MXU-tiled."""
    return dim <= pref or dim % pref == 0


def _blocks(m: int, n: int, k: int) -> Tuple[int, int, int]:
    """(bm, bn, bk) tiles of an [m,k] @ [k,n] matmul the TPU compiler accepts:
    bm indexes sublanes, bn and bk index lanes of some block."""
    return (pick_block(m, BLOCK_M), pick_block(n, BLOCK_N, LANE),
            pick_block(k, BLOCK_K, LANE))


def _mk(shape3) -> Tuple[int, int]:
    """(M, K) of the flattened per-step matmul for a [b, t, h] block."""
    b, t, h = shape3
    return b * t, h


def _prod(shape) -> int:
    p = 1
    for s in shape:
        p *= s
    return p


def _fits_vmem(*byte_counts) -> bool:
    return sum(byte_counts) <= VMEM_BUDGET


def _tile_bytes(itemsize: int) -> int:
    """fp32 acc tile + double-buffered operand/output tiles (upper bound)."""
    return (BLOCK_M * BLOCK_N * 4
            + 2 * (BLOCK_M * BLOCK_K + BLOCK_K * BLOCK_N
                   + BLOCK_M * BLOCK_N) * itemsize)


def _ag_fits(x_shape, w_shape, n: int, itemsize: int) -> bool:
    """The ``_ag_matmul_tpu`` kernel alone: aligned dims, shard pair in VMEM."""
    if n <= 1 or len(x_shape) != 3 or x_shape[-1] != w_shape[0]:
        return False
    m, k = _mk(x_shape)
    return (aligned(m, BLOCK_M) and aligned(k, BLOCK_K)
            and aligned(w_shape[-1], BLOCK_N)
            and _fits_vmem(2 * _prod(x_shape) * itemsize,
                           _tile_bytes(itemsize)))


def _rs_fits(x_shape, w_shape, n: int, scatter_dim: int,
             itemsize: int) -> bool:
    """The ``_matmul_rs_tpu`` kernel alone: accumulator pair in VMEM."""
    if n <= 1 or len(x_shape) != 3 or x_shape[-1] != w_shape[0]:
        return False
    last = scatter_dim == len(x_shape) - 1
    scattered = w_shape[-1] if last else x_shape[scatter_dim]
    if scattered % n:
        return False
    chunk = scattered // n
    if last:
        m, k, nn = x_shape[0] * x_shape[1], x_shape[-1], chunk
        out_elts = _prod(x_shape[:-1]) * chunk
    else:
        m, k, nn = x_shape[0] * chunk, x_shape[-1], w_shape[-1]
        out_elts = x_shape[0] * chunk * w_shape[-1]
    return (aligned(m, BLOCK_M) and aligned(k, BLOCK_K) and aligned(nn, BLOCK_N)
            and _fits_vmem(2 * out_elts * itemsize, _tile_bytes(itemsize)))


def _contract_fits(x_shape, w_shape, n: int, itemsize: int) -> bool:
    """The ``_ag_matmul_contract_tpu`` kernel alone: the fp32 accumulator
    spanning ring steps lives in VMEM whole, beside the shard pair."""
    if n <= 1 or len(x_shape) != 3 or w_shape[0] != n * x_shape[-1]:
        return False
    m, k = _mk(x_shape)
    return (aligned(m, BLOCK_M) and aligned(k, BLOCK_K)
            and aligned(w_shape[-1], BLOCK_N)
            and _fits_vmem(2 * _prod(x_shape) * itemsize,
                           m * w_shape[-1] * 4, _tile_bytes(itemsize)))


# The fused_ok_* gates admit a shape only if the forward kernel AND the fused
# kernel its custom-VJP backward runs (the transposed ring) both fit: a step
# that passes the gate compiles its gradient too.


def fused_ok_ag(x_shape, w_shape, n: int, dim: int = 1,
                itemsize: int = 4) -> bool:
    """Can ``ag_matmul`` run fused for x [b,t,h] (gather ``dim``), w [h,o]?
    Backward: a token-dim matmul-RS of dy [b,n·t,o] @ wᵀ."""
    if dim != 1 or not _ag_fits(x_shape, w_shape, n, itemsize):
        return False
    b, t, h = x_shape
    o = w_shape[-1]
    return _rs_fits((b, n * t, o), (o, h), n, 1, itemsize)


def fused_ok_rs(x_shape, w_shape, n: int, scatter_dim: int,
                itemsize: int = 4) -> bool:
    """Can ``matmul_rs`` run fused for x [b,t,h] @ w [h,o], scatter ``dim``?
    Backward: a contracted-dim AG-matmul (hidden scatter) or a token-dim
    AG-matmul (token scatter) of the output cotangent with wᵀ."""
    if not _rs_fits(x_shape, w_shape, n, scatter_dim, itemsize):
        return False
    b, t, h = x_shape
    o = w_shape[-1]
    if scatter_dim == len(x_shape) - 1:
        return _contract_fits((b, t, o // n), (o, h), n, itemsize)
    return _ag_fits((b, t // n, o), (o, h), n, itemsize)


def fused_ok_contract(x_shape, w_shape, n: int, itemsize: int = 4) -> bool:
    """Can ``ag_matmul_contract`` run fused (gathered dim contracted)?
    Backward: a hidden-dim matmul-RS of dy [b,t,o] @ wᵀ."""
    if not _contract_fits(x_shape, w_shape, n, itemsize):
        return False
    b, t, _ = x_shape
    o = w_shape[-1]
    return _rs_fits((b, t, o), (o, w_shape[0]), n, 2, itemsize)


def fused_ok_pair(x_shape, w1_shape, w1b_shape, n: int, scatter_dim: int,
                  itemsize: int = 4) -> bool:
    """Can ``matmul_rs_pair`` run fused?  Its forward is one matmul-RS over
    the column-concatenated weights; its backward is one token-dim AG-matmul
    per weight."""
    if scatter_dim != len(x_shape) - 2:
        return False
    cat = (w1_shape[0], w1_shape[-1] + w1b_shape[-1])
    return (_rs_fits(x_shape, cat, n, scatter_dim, itemsize)
            and fused_ok_rs(x_shape, w1_shape, n, scatter_dim, itemsize)
            and fused_ok_rs(x_shape, w1b_shape, n, scatter_dim, itemsize))


# ---------------------------------------------------------------------------
# Per-step tile matmul (the kernels/matmul.py loop with an out_dtype knob)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=512)
def _tile_mm_call(M: int, K: int, N: int, bm: int, bn: int, bk: int,
                  has_bias: bool, act: str, out_dtype_name: str,
                  interpret: bool):
    """Build (and CACHE) the ``pallas_call`` for one tile-matmul signature.

    The emulated ring loops invoke a tile matmul of the *same* shape once per
    ring step (and again per benchmark iteration); rebuilding the pallas_call
    closure each time re-traced the kernel per step, a pure-overhead cost on
    the interpret path.  Keyed on the full static signature, each distinct
    matmul shape is constructed exactly once per process and every ring step
    reuses the same compiled callable."""
    grid = (M // bm, N // bn, K // bk)
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
    ]
    if has_bias:
        kernel = functools.partial(_mm_bias_kernel, n_k=grid[2], act=act)
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
    else:
        kernel = functools.partial(_mm_kernel, n_k=grid[2], act=act)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.dtype(out_dtype_name)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="ring_tile_matmul",
    )


def _tile_mm_raw(x, w, bias=None, *, act: str = "none", out_dtype=None,
                 interpret: Optional[bool] = None):
    """y = act(x @ w + bias) through the Pallas tile loop; x [M,K], w [K,N].

    Blocks come from :func:`pick_block`, so any extent works (degraded tiles
    off the MXU-aligned fast path).  ``out_dtype`` keeps fp32 partials alive
    across ring steps for the contracted-gather accumulation.

    On the interpret path (CPU CI / emulated rings) the grid collapses to a
    SINGLE cell (bm, bn, bk) = (M, N, K): the Pallas interpreter pays a fixed
    overhead per grid cell and has no VMEM capacity to respect, so one cell
    per matmul removes nearly all of the emulation tax while still executing
    the exact kernel body (acc init → dot → epilogue).  Real-TPU tiling is
    unchanged."""
    if interpret is None:
        interpret = not compat.remote_dma_supported()
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    out_dtype = out_dtype or x.dtype
    if interpret:
        bm, bn, bk = M, N, K
        # XLA:CPU has no bf16 x bf16 -> f32 dot; the f32 upcast is exact
        x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    else:
        bm, bn, bk = _blocks(M, N, K)
    call = _tile_mm_call(M, K, N, bm, bn, bk, bias is not None, act,
                         jnp.dtype(out_dtype).name, interpret)
    if bias is None:
        return call(x, w)
    return call(x, w, bias.reshape(1, N))


@jax.custom_vjp
def tile_matmul(x, w):
    """Differentiable plain tile matmul (no epilogue), y in x.dtype.

    The backward runs through the same Pallas tile loop (dx = g wᵀ, dw = xᵀ g),
    so ring backwards stay on the kernel path too."""
    return _tile_mm_raw(x, w)


def _tile_matmul_f32(x, w):
    return _tile_mm_raw(x, w, out_dtype=jnp.float32)


def _tile_mm_fwd(x, w):
    return tile_matmul(x, w), (x, w)


def _tile_mm_bwd(res, g):
    x, w = res
    dx = _tile_mm_raw(g.astype(x.dtype), w.T.astype(x.dtype),
                      out_dtype=x.dtype)
    dw = _tile_mm_raw(x.T, g.astype(x.dtype), out_dtype=w.dtype)
    return dx, dw


tile_matmul.defvjp(_tile_mm_fwd, _tile_mm_bwd)


# ---------------------------------------------------------------------------
# small local helpers (kept self-contained: core/overlap.py imports this
# module at top level, so we must not import it back at module scope)
# ---------------------------------------------------------------------------


def _put(buf, part, dim: int, start):
    starts = [0] * buf.ndim
    starts[dim] = start
    return lax.dynamic_update_slice(buf, part.astype(buf.dtype), tuple(starts))


def _take(x, dim: int, start, size: int):
    starts = [0] * x.ndim
    starts[dim] = start
    sizes = list(x.shape)
    sizes[dim] = size
    return lax.dynamic_slice(x, tuple(starts), tuple(sizes))


def _flat(x3):
    b, t, h = x3.shape
    return x3.reshape(b * t, h)


def _unflat(x2, b):
    m, o = x2.shape
    return x2.reshape(b, m // b, o)


def _mm3(x3, w, out_dtype=None):
    """Per-step [b,t,h] @ [h,o] through the tile loop (differentiable)."""
    if out_dtype in (None, x3.dtype):
        return _unflat(tile_matmul(_flat(x3), w), x3.shape[0])
    return _unflat(_tile_matmul_f32(_flat(x3), w), x3.shape[0]).astype(
        out_dtype)


def _pure_ag(x, axis_name: str, dim: int, n: int, comm_dtype: str = "bf16"):
    """Plain ppermute ring all-gather (rank order), used by vjp helpers."""
    if n <= 1:
        return x
    idx = lax.axis_index(axis_name)
    chunk = x.shape[dim]
    shape = list(x.shape)
    shape[dim] = chunk * n
    out = jnp.zeros(tuple(shape), x.dtype)
    cur = x
    for s in range(n):
        out = _put(out, cur, dim, ((idx - s) % n) * chunk)
        if s < n - 1:
            cur = Q.ring_hop(cur, axis_name, n, 1, comm_dtype)
    return out


# ---------------------------------------------------------------------------
# Emulated fused loops (ppermute hops between Pallas tile-loop steps)
# ---------------------------------------------------------------------------


def _ag_mm_impl(x, w, axis_name: str, dim: int, n: int, bias, act: str,
                comm_dtype: str = "bf16"):
    """Ring AG-matmul: circulate x shards, tile-matmul each into its slot."""
    if n <= 1:
        return _unflat(_tile_mm_raw(_flat(x), w, bias, act=act), x.shape[0])
    idx = lax.axis_index(axis_name)
    chunk = x.shape[dim]
    shape = list(x.shape)
    shape[dim] = chunk * n
    shape[-1] = w.shape[-1]
    out = jnp.zeros(tuple(shape), x.dtype)
    cur = x
    for s in range(n):
        if bias is None and act == "none":
            y = _mm3(cur, w)
        else:   # fwd-only epilogue path (elementwise ⇒ valid per slot)
            y = _unflat(_tile_mm_raw(_flat(cur), w, bias, act=act),
                        cur.shape[0])
        out = _put(out, y, dim, ((idx - s) % n) * chunk)
        if s < n - 1:
            cur = Q.ring_hop(cur, axis_name, n, 1, comm_dtype)
    return out


def _mm_rs_impl(x, w, axis_name: str, scatter_dim: int, n: int, bias, act,
                comm_dtype: str = "bf16"):
    """Ring matmul-RS: per-destination tile folded into a circulating acc."""
    if n <= 1:
        return _unflat(_tile_mm_raw(_flat(x), w, bias, act=act), x.shape[0])
    idx = lax.axis_index(axis_name)
    last = scatter_dim == x.ndim - 1
    scattered = w.shape[-1] if last else x.shape[scatter_dim]
    assert scattered % n == 0, (
        f"fused matmul-RS: extent {scattered} does not chunk by ring {n}")
    chunk = scattered // n

    if last:                                # chunk w's output columns
        def contrib(d):
            return _mm3(x, _take(w, 1, d * chunk, chunk))
    else:                                   # chunk x's rows along scatter_dim
        def contrib(d):
            return _mm3(_take(x, scatter_dim, d * chunk, chunk), w)

    acc = contrib((idx - 1) % n)
    for s in range(1, n):
        acc = Q.ring_hop(acc, axis_name, n, 1, comm_dtype)
        acc = acc + contrib((idx + n - 1 - s) % n)
    if bias is None and act == "none":
        return acc
    return _epilogue(acc.astype(jnp.float32),
                     None if bias is None else bias, act).astype(acc.dtype)


def _ag_mm_contract_impl(x, w, axis_name: str, n: int, out_dtype, bias, act,
                         comm_dtype: str = "bf16"):
    """Ring AG-matmul over the contracted dim: fp32 acc spans ring steps."""
    dt = out_dtype or x.dtype
    if n <= 1:
        y = _tile_mm_raw(_flat(x), w, bias, act=act, out_dtype=dt)
        return _unflat(y, x.shape[0])
    idx = lax.axis_index(axis_name)
    h_loc = x.shape[-1]
    acc = jnp.zeros(x.shape[:-1] + (w.shape[-1],), jnp.float32)
    cur = x
    for s in range(n):
        src = (idx - s) % n
        acc = acc + _mm3(cur, _take(w, 0, src * h_loc, h_loc), jnp.float32)
        if s < n - 1:
            cur = Q.ring_hop(cur, axis_name, n, 1, comm_dtype)
    if bias is not None or act != "none":
        acc = _epilogue(acc, bias, act)
    return acc.astype(dt)


def _mm_rs_pair_impl(x, w1, w1b, axis_name: str, scatter_dim: int, n: int,
                     comm_dtype: str = "bf16"):
    """Two circulating accumulators; per-step contributions share the x tile
    (one Pallas call on the column-concatenated weights reads each x tile once
    for both products — gated_matmul's trick at ring scope)."""
    wc = jnp.concatenate([w1, w1b], axis=1)
    o1 = w1.shape[-1]
    if n <= 1:
        y = _mm3(x, wc)
        return y[..., :o1], y[..., o1:]
    idx = lax.axis_index(axis_name)
    assert scatter_dim != x.ndim - 1, "pair variant scatters the token dim"
    scattered = x.shape[scatter_dim]
    assert scattered % n == 0
    chunk = scattered // n

    def contrib(d):
        y = _mm3(_take(x, scatter_dim, d * chunk, chunk), wc)
        return y[..., :o1], y[..., o1:]

    acc, accb = contrib((idx - 1) % n)
    for s in range(1, n):
        acc = Q.ring_hop(acc, axis_name, n, 1, comm_dtype)
        accb = Q.ring_hop(accb, axis_name, n, 1, comm_dtype)
        c, cb = contrib((idx + n - 1 - s) % n)
        acc, accb = acc + c, accb + cb
    return acc, accb


# ---------------------------------------------------------------------------
# vjp helper rings (run in backward passes only)
# ---------------------------------------------------------------------------


def _contract_rows_ring(x, dy, axis_name: str, scatter_dim: int, n: int,
                        w_dtype, comm_dtype: str = "bf16"):
    """dw = Σ_d take(x, d·chunk)ᵀ @ dy_d — circulate dy, contract per step."""
    idx = lax.axis_index(axis_name)
    chunk = x.shape[scatter_dim] // n
    dw = None
    cur = dy
    for s in range(n):
        d = (idx - s) % n
        xd = _flat(_take(x, scatter_dim, d * chunk, chunk))
        term = _tile_mm_raw(xd.T, _flat(cur).astype(x.dtype),
                            out_dtype=jnp.float32)
        dw = term if dw is None else dw + term
        if s < n - 1:
            cur = Q.ring_hop(cur, axis_name, n, 1, comm_dtype)
    return dw.astype(w_dtype)


def _place_cols_ring(x, dy, axis_name: str, n: int, w_shape, w_dtype,
                     comm_dtype: str = "bf16"):
    """dw[:, d·chunk] = xᵀ @ dy_d — circulate dy, place column chunks."""
    idx = lax.axis_index(axis_name)
    chunk = w_shape[-1] // n
    dw = jnp.zeros(w_shape, jnp.float32)
    cur = dy
    for s in range(n):
        d = (idx - s) % n
        term = _tile_mm_raw(_flat(x).T, _flat(cur).astype(x.dtype),
                            out_dtype=jnp.float32)
        dw = _put(dw, term, 1, d * chunk)
        if s < n - 1:
            cur = Q.ring_hop(cur, axis_name, n, 1, comm_dtype)
    return dw.astype(w_dtype)


def _place_rows_ring(x, dy, axis_name: str, n: int, w_shape, w_dtype,
                     comm_dtype: str = "bf16"):
    """dw[d·h_loc, :] = x_dᵀ @ dy — circulate x, place row chunks."""
    idx = lax.axis_index(axis_name)
    h_loc = x.shape[-1]
    dw = jnp.zeros(w_shape, jnp.float32)
    cur = x
    for s in range(n):
        src = (idx - s) % n
        term = _tile_mm_raw(_flat(cur).T, _flat(dy).astype(x.dtype),
                            out_dtype=jnp.float32)
        dw = _put(dw, term, 0, src * h_loc)
        if s < n - 1:
            cur = Q.ring_hop(cur, axis_name, n, 1, comm_dtype)
    return dw.astype(w_dtype)


# ---------------------------------------------------------------------------
# Public ops (custom_vjp: the backward is the transposed ring, still fused)
# ---------------------------------------------------------------------------


def _use_tpu(n: int, mesh_axes) -> bool:
    """Take the single-kernel remote-DMA path?  Requires a real TPU backend,
    a non-degenerate ring, AND the caller having supplied the full mesh axis
    list (needed to address ring neighbours by mesh coordinates)."""
    return n > 1 and mesh_axes is not None and compat.remote_dma_supported()


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _ag_mm(x, w, axis_name: str, dim: int, n: int, mesh_axes, comm_dtype):
    if not _use_tpu(n, mesh_axes):
        return _ag_mm_impl(x, w, axis_name, dim, n, None, "none", comm_dtype)
    return _ag_matmul_tpu(x, w, axis_name=axis_name, dim=dim, n=n,
                          mesh_axes=mesh_axes, comm_dtype=comm_dtype)


def _ag_mm_fwd(x, w, axis_name, dim, n, mesh_axes, comm_dtype):
    return _ag_mm(x, w, axis_name, dim, n, mesh_axes, comm_dtype), (x, w)


def _ag_mm_bwd(axis_name, dim, n, mesh_axes, comm_dtype, res, dy):
    x, w = res
    # transpose(ring AG-matmul) = ring matmul-RS over the reversed ring
    dx = _mm_rs(dy, w.T, axis_name, dim, n, mesh_axes,
                comm_dtype).astype(x.dtype)
    xg = _pure_ag(x, axis_name, dim, n, comm_dtype)
    dw = _tile_mm_raw(_flat(xg).T, _flat(dy).astype(x.dtype),
                      out_dtype=jnp.float32).astype(w.dtype)
    return dx, dw


_ag_mm.defvjp(_ag_mm_fwd, _ag_mm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _mm_rs(x, w, axis_name: str, scatter_dim: int, n: int, mesh_axes,
           comm_dtype):
    if not _use_tpu(n, mesh_axes):
        return _mm_rs_impl(x, w, axis_name, scatter_dim, n, None, "none",
                           comm_dtype)
    return _matmul_rs_tpu(x, w, axis_name=axis_name, scatter_dim=scatter_dim,
                          n=n, mesh_axes=mesh_axes, comm_dtype=comm_dtype)


def _mm_rs_fwd(x, w, axis_name, scatter_dim, n, mesh_axes, comm_dtype):
    return (_mm_rs(x, w, axis_name, scatter_dim, n, mesh_axes, comm_dtype),
            (x, w))


def _mm_rs_bwd(axis_name, scatter_dim, n, mesh_axes, comm_dtype, res, dy):
    x, w = res
    if scatter_dim == x.ndim - 1:
        # y_chunk = x @ w[:, dᵢ]: dx = AG_cols(dy) ⊗ wᵀ (contracted ring)
        dx = _ag_mm_contract(dy, w.T, axis_name, n, x.dtype,
                             mesh_axes, comm_dtype).astype(x.dtype)
        dw = _place_cols_ring(x, dy, axis_name, n, w.shape, w.dtype,
                              comm_dtype)
    else:
        # transpose(ring matmul-RS) = ring AG-matmul
        dx = _ag_mm(dy.astype(x.dtype), w.T, axis_name, scatter_dim, n,
                    mesh_axes, comm_dtype)
        dw = _contract_rows_ring(x, dy, axis_name, scatter_dim, n, w.dtype,
                                 comm_dtype)
    return dx, dw


_mm_rs.defvjp(_mm_rs_fwd, _mm_rs_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _ag_mm_contract(x, w, axis_name: str, n: int, out_dtype, mesh_axes,
                    comm_dtype):
    if not _use_tpu(n, mesh_axes):
        return _ag_mm_contract_impl(x, w, axis_name, n, out_dtype, None,
                                    "none", comm_dtype)
    return _ag_matmul_contract_tpu(x, w, axis_name=axis_name, n=n,
                                   out_dtype=out_dtype, mesh_axes=mesh_axes,
                                   comm_dtype=comm_dtype)


def _ag_mm_contract_fwd(x, w, axis_name, n, out_dtype, mesh_axes, comm_dtype):
    return (_ag_mm_contract(x, w, axis_name, n, out_dtype, mesh_axes,
                            comm_dtype), (x, w))


def _ag_mm_contract_bwd(axis_name, n, out_dtype, mesh_axes, comm_dtype, res,
                        dy):
    x, w = res
    # y = Σ_src x_src @ w[src rows]: dx arrives as a matmul-RS over wᵀ columns
    dx = _mm_rs(dy.astype(x.dtype), w.T, axis_name, dy.ndim - 1, n,
                mesh_axes, comm_dtype).astype(x.dtype)
    dw = _place_rows_ring(x, dy, axis_name, n, w.shape, w.dtype, comm_dtype)
    return dx, dw


_ag_mm_contract.defvjp(_ag_mm_contract_fwd, _ag_mm_contract_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _mm_rs_pair(x, w1, w1b, axis_name: str, scatter_dim: int, n: int,
                mesh_axes, comm_dtype):
    if not _use_tpu(n, mesh_axes):
        return _mm_rs_pair_impl(x, w1, w1b, axis_name, scatter_dim, n,
                                comm_dtype)
    return _matmul_rs_pair_tpu(x, w1, w1b, axis_name=axis_name,
                               scatter_dim=scatter_dim, n=n,
                               mesh_axes=mesh_axes, comm_dtype=comm_dtype)


def _mm_rs_pair_fwd(x, w1, w1b, axis_name, scatter_dim, n, mesh_axes,
                    comm_dtype):
    return (_mm_rs_pair(x, w1, w1b, axis_name, scatter_dim, n, mesh_axes,
                        comm_dtype), (x, w1, w1b))


def _mm_rs_pair_bwd(axis_name, scatter_dim, n, mesh_axes, comm_dtype, res,
                    dys):
    x, w1, w1b = res
    dh, dg = dys
    dx = (_ag_mm(dh.astype(x.dtype), w1.T, axis_name, scatter_dim, n,
                 mesh_axes, comm_dtype)
          + _ag_mm(dg.astype(x.dtype), w1b.T, axis_name, scatter_dim, n,
                   mesh_axes, comm_dtype))
    dw1 = _contract_rows_ring(x, dh, axis_name, scatter_dim, n, w1.dtype,
                              comm_dtype)
    dw1b = _contract_rows_ring(x, dg, axis_name, scatter_dim, n, w1b.dtype,
                               comm_dtype)
    return dx, dw1, dw1b


_mm_rs_pair.defvjp(_mm_rs_pair_fwd, _mm_rs_pair_bwd)


# -- public wrappers --------------------------------------------------------


def ag_matmul(x, w, axis_name: str, *, dim: int = 1, n: int,
              bias=None, act: str = "none", mesh_axes=None,
              comm_dtype: str = "bf16"):
    """Fused all-gather ⊕ matmul; x [b,t,h] (gather ``dim``), w [h,o].

    Differentiable when no epilogue is requested; the bias/activation epilogue
    (fused into the last K step of each tile loop) is forward-only — hecaton's
    training path never uses it, serving and kernel tests do.  ``mesh_axes``
    is the enclosing mesh's full axis-name tuple, required for the TPU
    remote-DMA path to address ring neighbours by mesh coordinates; without
    it the ppermute-emulated path runs.  ``comm_dtype="int8"`` ships each hop
    as an (int8, fp32 per-row scale) pair (docs/DESIGN.md §11)."""
    if bias is None and act == "none":
        return _ag_mm(x, w, axis_name, dim, n, _axes_key(mesh_axes),
                      comm_dtype)
    return _ag_mm_impl(x, w, axis_name, dim, n, bias, act, comm_dtype)


def matmul_rs(x, w, axis_name: str, *, scatter_dim: int, n: int,
              bias=None, act: str = "none", mesh_axes=None,
              comm_dtype: str = "bf16"):
    """Fused matmul ⊕ reduce-scatter; epilogue fires on the final (fully
    reduced) accumulator only, preserving post-reduction semantics."""
    if bias is None and act == "none":
        return _mm_rs(x, w, axis_name, scatter_dim, n, _axes_key(mesh_axes),
                      comm_dtype)
    return _mm_rs_impl(x, w, axis_name, scatter_dim, n, bias, act, comm_dtype)


def ag_matmul_contract(x, w, axis_name: str, *, n: int, out_dtype=None,
                       bias=None, act: str = "none", mesh_axes=None,
                       comm_dtype: str = "bf16"):
    """Fused all-gather ⊕ matmul over the contracted dim (fp32 ring acc)."""
    if bias is None and act == "none":
        return _ag_mm_contract(x, w, axis_name, n, out_dtype,
                               _axes_key(mesh_axes), comm_dtype)
    return _ag_mm_contract_impl(x, w, axis_name, n, out_dtype, bias, act,
                                comm_dtype)


def matmul_rs_pair(x, w1, w1b, axis_name: str, *, scatter_dim: int, n: int,
                   mesh_axes=None, comm_dtype: str = "bf16"):
    """Gated fused matmul ⊕ RS: returns (x·w1, x·w1b) reduce-scattered, both
    per-step contributions reading the same x tile.  The caller applies the
    gate (``act(h) * g``) — keeping the nonlinearity outside lets model code
    pass arbitrary activation callables."""
    return _mm_rs_pair(x, w1, w1b, axis_name, scatter_dim, n,
                       _axes_key(mesh_axes), comm_dtype)


def _axes_key(mesh_axes):
    """Normalize to a hashable tuple (custom_vjp nondiff arg) or None."""
    return tuple(mesh_axes) if mesh_axes else None


# ---------------------------------------------------------------------------
# TPU single-kernel path: the whole ring inside one pallas_call.
#
# Synchronisation scheme (per ring collective):
#   * barrier semaphore handshake with both neighbours at kernel start;
#   * per-slot DMA send/recv semaphores for the double-buffered VMEM pair;
#   * a REGULAR capacity semaphore: the consumer signals its *upstream*
#     neighbour after the MXU finishes a step, and the sender consumes one
#     credit before overwriting that slot — a neighbour running one step
#     ahead can therefore never land a shard in a buffer still being read.
#
# ``device_id`` uses ``DeviceIdType.MESH``: a tuple of mesh coordinates over
# the *full* axis list of the enclosing mesh (``mesh_axes``, plumbed down
# from the hecaton/megatron call sites, which know ``mesh.axis_names``).  All
# coordinates are computed *outside* the kernel with lax.axis_index and
# handed in via scalar prefetch; only the ring axis differs between self and
# neighbours.
# ---------------------------------------------------------------------------


def _ring_ids(axis_name: str, n: int, mesh_axes):
    axes = tuple(mesh_axes)
    assert axis_name in axes, (axis_name, axes)
    coords = {a: lax.axis_index(a) for a in axes}
    me = coords[axis_name]
    right = [coords[a] if a != axis_name else (me + 1) % n for a in axes]
    left = [coords[a] if a != axis_name else (me - 1) % n for a in axes]
    return jnp.stack([me] + right + left).astype(jnp.int32), len(axes)


def _quant_lane_scales(x):
    """``quant.quant_int8`` with the per-row scales stored lane-major,
    ``x.shape[:-1]`` instead of ``x.shape[:-1] + (1,)``: the TPU's DMA
    cannot slice a buffer whose last dim is a single (padded) lane."""
    q, scale = Q.quant_int8(x)
    return q, jnp.max(scale, axis=-1)


def _row_scales(sbuf, slot, bi, start, size: int):
    """Scales of rows ``[start, start+size)`` of batch ``bi`` in ``slot`` of
    a lane-major scale buffer, as a ``[size, 1]`` column for the dequant."""
    return sbuf[slot, pl.ds(bi, 1), pl.ds(start, size)].reshape(size, 1)


def _final_step_block(s, n: int, *idx):
    """Output block index for kernels that emit only on the last ring step.

    Earlier steps park on block 0, so every output block is visited in one
    contiguous run (block 0's run ends at the last step's first tile) and
    nothing is written back to HBM before it holds its final value."""
    last = (s == n - 1).astype(jnp.int32)
    return tuple(v * last for v in idx)


def _nbr(ids_ref, n_axes: int, which: str):
    off = 1 if which == "right" else 1 + n_axes
    return tuple(ids_ref[off + i] for i in range(n_axes))


def _ag_matmul_tpu(x, w, *, axis_name: str, dim: int, n: int,
                   act: str = "none", mesh_axes=None,
                   collective_id: int = 0, comm_dtype: str = "bf16"):
    """Single-kernel ring AG-matmul: grid (step, batch, m, n, k); the remote
    DMA for step s+1 launches on step s's first tile and the MXU consumes the
    current slot through the tile loop meanwhile.

    ``comm_dtype="int8"``: the shard is quantized ONCE on the host side of
    the call (it circulates unchanged, so a single quantization serves every
    hop — strictly less error than the emulated path's per-hop roundtrip)
    and the double-buffered VMEM pair becomes an (int8 payload, fp32 per-row
    scale) pair moved by paired remote DMAs sharing one capacity credit;
    each MXU tile dequantizes its slice right before the dot."""
    assert dim == 1, "token-dim gather only"
    b, t, h = x.shape
    o = w.shape[-1]
    bm, bn, bk = _blocks(t, o, h)
    mt, nt, kt = t // bm, o // bn, h // bk
    ids, n_axes = _ring_ids(axis_name, n, mesh_axes)
    quant = comm_dtype == "int8" and Q.quant_ok(x.shape, x.dtype)

    def kernel(ids_ref, *refs):
        if quant:
            (xq_hbm, xs_hbm, w_ref, o_ref, buf, sbuf, acc, copy_sem,
             send_sem, recv_sem, send_s, recv_s, cap_sem) = refs
        else:
            (x_hbm, w_ref, o_ref, buf, acc, copy_sem,
             send_sem, recv_sem, cap_sem) = refs
        s, bi = pl.program_id(0), pl.program_id(1)
        i, j, k = pl.program_id(2), pl.program_id(3), pl.program_id(4)
        first = (bi == 0) & (i == 0) & (j == 0) & (k == 0)
        last = ((bi == b - 1) & (i == mt - 1) & (j == nt - 1)
                & (k == kt - 1))
        slot = lax.rem(s, 2)
        nxt = lax.rem(s + 1, 2)

        @pl.when((s == 0) & first)
        def _prologue():
            barrier = pltpu.get_barrier_semaphore()
            for which in ("left", "right"):
                pltpu.semaphore_signal(
                    barrier, inc=1, device_id=_nbr(ids_ref, n_axes, which),
                    device_id_type=pltpu.DeviceIdType.MESH)
            pltpu.semaphore_wait(barrier, 2)
            if quant:
                cp = pltpu.make_async_copy(xq_hbm, buf.at[0], copy_sem)
                cp.start()
                cp.wait()
                cp = pltpu.make_async_copy(xs_hbm, sbuf.at[0], copy_sem)
            else:
                cp = pltpu.make_async_copy(x_hbm, buf.at[0], copy_sem)
            cp.start()
            cp.wait()

        @pl.when((s > 0) & first)
        def _recv_wait():     # data for this step landed in buf[slot]
            pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                                  recv_sem.at[slot]).wait()
            if quant:
                pltpu.make_async_copy(sbuf.at[slot], sbuf.at[slot],
                                      recv_s.at[slot]).wait()

        @pl.when((s < n - 1) & first)
        def _send():          # forward the current shard to the right
            @pl.when(s > 0)
            def _credit():    # right neighbour freed the destination slot
                pltpu.semaphore_wait(cap_sem, 1)
            rdma = pltpu.make_async_remote_copy(
                src_ref=buf.at[slot], dst_ref=buf.at[nxt],
                send_sem=send_sem.at[slot], recv_sem=recv_sem.at[nxt],
                device_id=_nbr(ids_ref, n_axes, "right"),
                device_id_type=pltpu.DeviceIdType.MESH)
            rdma.start()
            if quant:
                rdma_s = pltpu.make_async_remote_copy(
                    src_ref=sbuf.at[slot], dst_ref=sbuf.at[nxt],
                    send_sem=send_s.at[slot], recv_sem=recv_s.at[nxt],
                    device_id=_nbr(ids_ref, n_axes, "right"),
                    device_id_type=pltpu.DeviceIdType.MESH)
                rdma_s.start()

        @pl.when(k == 0)
        def _init():
            acc[...] = jnp.zeros_like(acc)

        if quant:
            xt = (buf[slot, bi, pl.ds(i * bm, bm),
                      pl.ds(k * bk, bk)].astype(jnp.float32)
                  * _row_scales(sbuf, slot, bi, i * bm, bm)).astype(w_ref.dtype)
        else:
            xt = buf[slot, bi, pl.ds(i * bm, bm), pl.ds(k * bk, bk)]
        acc[...] += jnp.dot(xt, w_ref[...],
                            preferred_element_type=jnp.float32)

        @pl.when(k == kt - 1)
        def _done():
            o_ref[...] = _epilogue(acc[...], None, act).astype(o_ref.dtype)

        @pl.when((s < n - 1) & last)
        def _step_done():     # our outbound read of buf[slot] must be done
            pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                                  send_sem.at[slot]).wait()
            if quant:
                pltpu.make_async_copy(sbuf.at[slot], sbuf.at[slot],
                                      send_s.at[slot]).wait()

        # Credit the upstream neighbour: slot s%2 is free for the write its
        # step-(s+1) send performs.  Only sends at steps 1..n-2 consume a
        # credit, so only steps 0..n-3 issue one (the semaphore drains to 0).
        @pl.when((s < n - 2) & last)
        def _free_slot():
            pltpu.semaphore_signal(
                cap_sem, inc=1, device_id=_nbr(ids_ref, n_axes, "left"),
                device_id_type=pltpu.DeviceIdType.MESH)

    grid = (n, b, mt, nt, kt)
    if quant:
        xq, xs = _quant_lane_scales(x)
        in_specs = [
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((bk, bn), lambda s, bi, i, j, k, ids: (k, j)),
        ]
        scratch = [
            pltpu.VMEM((2, b, t, h), jnp.int8),
            pltpu.VMEM((2, b, t), jnp.float32),
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR,
        ]
        operands = (ids, xq, xs, w)
    else:
        in_specs = [
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((bk, bn), lambda s, bi, i, j, k, ids: (k, j)),
        ]
        scratch = [
            pltpu.VMEM((2, b, t, h), x.dtype),
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR,
        ]
        operands = (ids, x, w)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (None, bm, bn),
                lambda s, bi, i, j, k, ids:
                    (bi, ((ids[0] - s) % n) * mt + i, j)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((b, n * t, o), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            collective_id=collective_id, has_side_effects=True),
        name="ring_ag_matmul",
    )(*operands)
    return out


def _matmul_rs_tpu(x, w, *, axis_name: str, scatter_dim: int, n: int,
                   mesh_axes=None, collective_id: int = 1,
                   comm_dtype: str = "bf16"):
    """Single-kernel ring matmul-RS: the per-destination accumulator chunk
    circulates through the VMEM pair.

    Overlap structure: the inbound transfer for step *s* (started by the left
    neighbour at the end of its step *s-1*) flies while step *s*'s
    contribution tiles run on the MXU — the recv wait sits immediately before
    the first fold, not at the step boundary; the outbound send is started
    without an inline wait, its completion (and the capacity credit to the
    upstream neighbour) checked at the first tile of the NEXT step.  x and w
    stay in HBM and stream through double-buffered BlockSpec tiles whose
    index maps follow the per-step destination rank (scalar prefetch).

    ``comm_dtype="int8"``: unlike the AG kernels, the circulating object is
    the *accumulator*, which changes every hop — so the quantized pair must
    be rebuilt per send.  Folds land in a full-width ``work`` staging buffer
    (dequantize the received slot + add this step's fp32 tile); at the send
    point the whole ``work`` buffer is quantized into the (int8, fp32 scale)
    VMEM pair and both halves fly as paired remote DMAs under one capacity
    credit.  Only link traffic quantizes — ``work`` and the fp32 acc tiles
    stay full width."""
    b, t, h = x.shape
    o = w.shape[-1]
    last = scatter_dim == x.ndim - 1
    scattered = o if last else x.shape[scatter_dim]
    chunk = scattered // n
    if last:
        bm, bn, bk = _blocks(t, chunk, h)
        mt, nt, kt = t // bm, chunk // bn, h // bk
        out_shape = (b, t, chunk)
    else:
        bm, bn, bk = _blocks(chunk, o, h)
        mt, nt, kt = chunk // bm, o // bn, h // bk
        out_shape = (b, chunk, o)
    ids, n_axes = _ring_ids(axis_name, n, mesh_axes)
    quant = comm_dtype == "int8" and Q.quant_ok(out_shape, x.dtype)

    def _dest(s, ids_ref):                   # (me + n-1-s) % n; s=0 → me-1
        return (ids_ref[0] + n - 1 - s) % n

    if last:       # contribution = x @ w[:, dest·chunk + j·bn]
        x_spec = pl.BlockSpec((1, bm, bk),
                              lambda s, bi, i, j, k, ids: (bi, i, k))
        w_spec = pl.BlockSpec(
            (bk, bn),
            lambda s, bi, i, j, k, ids:
                (k, _dest(s, ids) * (chunk // bn) + j))
    else:          # contribution = x[dest·chunk + i·bm] @ w
        x_spec = pl.BlockSpec(
            (1, bm, bk),
            lambda s, bi, i, j, k, ids:
                (bi, _dest(s, ids) * (chunk // bm) + i, k))
        w_spec = pl.BlockSpec((bk, bn),
                              lambda s, bi, i, j, k, ids: (k, j))

    def kernel(ids_ref, *refs):
        if quant:
            (x_ref, w_ref, o_ref, buf, sbuf, work, acc,
             send_sem, recv_sem, send_s, recv_s, cap_sem) = refs
        else:
            (x_ref, w_ref, o_ref, buf, acc,
             send_sem, recv_sem, cap_sem) = refs
        s, bi = pl.program_id(0), pl.program_id(1)
        i, j, k = pl.program_id(2), pl.program_id(3), pl.program_id(4)
        first = (bi == 0) & (i == 0) & (j == 0) & (k == 0)
        lastt = ((bi == b - 1) & (i == mt - 1) & (j == nt - 1)
                 & (k == kt - 1))
        slot = lax.rem(s, 2)
        prev = lax.rem(s + 1, 2)

        @pl.when((s == 0) & first)
        def _prologue():
            barrier = pltpu.get_barrier_semaphore()
            for which in ("left", "right"):
                pltpu.semaphore_signal(
                    barrier, inc=1, device_id=_nbr(ids_ref, n_axes, which),
                    device_id_type=pltpu.DeviceIdType.MESH)
            pltpu.semaphore_wait(barrier, 2)

        @pl.when((s > 0) & first)
        def _prev_send_done():
            # the step-(s-1) send read buf[prev] to completion; the upstream
            # neighbour may now overwrite our slot (its next send lands here)
            pltpu.make_async_copy(buf.at[prev], buf.at[prev],
                                  send_sem.at[prev]).wait()
            if quant:
                pltpu.make_async_copy(sbuf.at[prev], sbuf.at[prev],
                                      send_s.at[prev]).wait()

        @pl.when((s > 0) & (s < n - 1) & first)
        def _free_slot():      # credits sends at steps 1..n-2 (drains to 0)
            pltpu.semaphore_signal(
                cap_sem, inc=1, device_id=_nbr(ids_ref, n_axes, "left"),
                device_id_type=pltpu.DeviceIdType.MESH)

        @pl.when(k == 0)
        def _init():
            acc[...] = jnp.zeros_like(acc)

        acc[...] += jnp.dot(x_ref[0], w_ref[...],
                            preferred_element_type=jnp.float32)

        # the inbound accumulator is needed only at fold time: waiting here —
        # after this step's first contribution tile has already run — lets
        # the transfer hide behind the MXU work above.
        @pl.when((s > 0) & (k == kt - 1) & (bi == 0) & (i == 0) & (j == 0))
        def _recv_wait():
            pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                                  recv_sem.at[slot]).wait()
            if quant:
                pltpu.make_async_copy(sbuf.at[slot], sbuf.at[slot],
                                      recv_s.at[slot]).wait()

        @pl.when(k == kt - 1)
        def _fold():
            if quant:
                tile = acc[...].astype(work.dtype)
                idxs = (bi, pl.ds(i * bm, bm), pl.ds(j * bn, bn))

                @pl.when(s == 0)
                def _set():
                    work[idxs] = tile

                @pl.when(s > 0)
                def _add():   # dequantize the received tile, fold this step's
                    got = (buf[(slot,) + idxs].astype(jnp.float32)
                           * _row_scales(sbuf, slot, bi, i * bm, bm))
                    work[idxs] = got.astype(work.dtype) + tile
            else:
                tile = acc[...].astype(buf.dtype)
                idxs = (slot, bi, pl.ds(i * bm, bm), pl.ds(j * bn, bn))

                @pl.when(s == 0)
                def _set():
                    buf[idxs] = tile

                @pl.when(s > 0)
                def _add():
                    buf[idxs] += tile

        if quant:   # the outbound pair is rebuilt from work at every send
            @pl.when((s < n - 1) & lastt)
            def _requant():
                buf[slot], sbuf[slot] = _quant_lane_scales(work[...])

        @pl.when((s < n - 1) & lastt)
        def _send():           # start only — completion checked next step
            @pl.when(s > 0)
            def _credit():     # right neighbour's destination slot is free
                pltpu.semaphore_wait(cap_sem, 1)
            rdma = pltpu.make_async_remote_copy(
                src_ref=buf.at[slot], dst_ref=buf.at[lax.rem(s + 1, 2)],
                send_sem=send_sem.at[slot],
                recv_sem=recv_sem.at[lax.rem(s + 1, 2)],
                device_id=_nbr(ids_ref, n_axes, "right"),
                device_id_type=pltpu.DeviceIdType.MESH)
            rdma.start()
            if quant:
                rdma_s = pltpu.make_async_remote_copy(
                    src_ref=sbuf.at[slot], dst_ref=sbuf.at[lax.rem(s + 1, 2)],
                    send_sem=send_s.at[slot],
                    recv_sem=recv_s.at[lax.rem(s + 1, 2)],
                    device_id=_nbr(ids_ref, n_axes, "right"),
                    device_id_type=pltpu.DeviceIdType.MESH)
                rdma_s.start()

        @pl.when((s == n - 1) & (k == kt - 1))
        def _emit():
            if quant:
                o_ref[...] = work[bi, pl.ds(i * bm, bm),
                                  pl.ds(j * bn, bn)].astype(o_ref.dtype)
            else:
                o_ref[...] = buf[slot, bi, pl.ds(i * bm, bm),
                                 pl.ds(j * bn, bn)].astype(o_ref.dtype)

    if quant:
        scratch = [
            pltpu.VMEM((2,) + out_shape, jnp.int8),
            pltpu.VMEM((2,) + out_shape[:-1], jnp.float32),
            pltpu.VMEM(out_shape, x.dtype),
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR,
        ]
    else:
        scratch = [
            pltpu.VMEM((2,) + out_shape, x.dtype),
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR,
        ]
    grid = (n, b, mt, nt, kt)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[x_spec, w_spec],
            out_specs=pl.BlockSpec(
                (None, bm, bn),
                lambda s, bi, i, j, k, ids: _final_step_block(s, n, bi, i, j)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct(out_shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            collective_id=collective_id, has_side_effects=True),
        name="ring_matmul_rs",
    )(ids, x, w)


def _ag_matmul_contract_tpu(x, w, *, axis_name: str, n: int, out_dtype=None,
                            mesh_axes=None, collective_id: int = 2,
                            comm_dtype: str = "bf16"):
    """Single-kernel contracted-dim ring: x shards circulate while an fp32
    accumulator spanning ring steps lives in VMEM; w row-blocks are indexed by
    the shard's source rank, epilogue/cast on the very last step.

    ``comm_dtype="int8"``: like the AG kernel, the payload is ring-invariant
    — quantized once outside the kernel, the (int8, fp32 scale) pair
    circulates through paired remote DMAs and every tile dequantizes its
    slice at the dot; the fp32 accumulator never quantizes."""
    b, t, h = x.shape
    o = w.shape[-1]
    m = b * t
    dt = out_dtype or x.dtype
    bm, bn, bk = _blocks(t, o, h)       # bm | t: a row tile never spans b
    tt = t // bm
    mt, nt, kt = b * tt, o // bn, h // bk
    ids, n_axes = _ring_ids(axis_name, n, mesh_axes)
    quant = comm_dtype == "int8" and Q.quant_ok(x.shape, x.dtype)

    def kernel(ids_ref, *refs):
        if quant:
            (xq_hbm, xs_hbm, w_ref, o_ref, buf, sbuf, acc, copy_sem,
             send_sem, recv_sem, send_s, recv_s, cap_sem) = refs
        else:
            (x_hbm, w_ref, o_ref, buf, acc, copy_sem,
             send_sem, recv_sem, cap_sem) = refs
        s = pl.program_id(0)
        i, j, k = pl.program_id(1), pl.program_id(2), pl.program_id(3)
        first = (i == 0) & (j == 0) & (k == 0)
        lastt = (i == mt - 1) & (j == nt - 1) & (k == kt - 1)
        slot = lax.rem(s, 2)
        nxt = lax.rem(s + 1, 2)

        @pl.when((s == 0) & first)
        def _prologue():
            barrier = pltpu.get_barrier_semaphore()
            for which in ("left", "right"):
                pltpu.semaphore_signal(
                    barrier, inc=1, device_id=_nbr(ids_ref, n_axes, which),
                    device_id_type=pltpu.DeviceIdType.MESH)
            pltpu.semaphore_wait(barrier, 2)
            if quant:
                cp = pltpu.make_async_copy(xq_hbm, buf.at[0], copy_sem)
                cp.start()
                cp.wait()
                cp = pltpu.make_async_copy(xs_hbm, sbuf.at[0], copy_sem)
            else:
                cp = pltpu.make_async_copy(x_hbm, buf.at[0], copy_sem)
            cp.start()
            cp.wait()
            acc[...] = jnp.zeros_like(acc)

        @pl.when((s > 0) & first)
        def _recv_wait():
            pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                                  recv_sem.at[slot]).wait()
            if quant:
                pltpu.make_async_copy(sbuf.at[slot], sbuf.at[slot],
                                      recv_s.at[slot]).wait()

        @pl.when((s < n - 1) & first)
        def _send():
            @pl.when(s > 0)
            def _credit():
                pltpu.semaphore_wait(cap_sem, 1)
            rdma = pltpu.make_async_remote_copy(
                src_ref=buf.at[slot], dst_ref=buf.at[nxt],
                send_sem=send_sem.at[slot],
                recv_sem=recv_sem.at[nxt],
                device_id=_nbr(ids_ref, n_axes, "right"),
                device_id_type=pltpu.DeviceIdType.MESH)
            rdma.start()
            if quant:
                rdma_s = pltpu.make_async_remote_copy(
                    src_ref=sbuf.at[slot], dst_ref=sbuf.at[nxt],
                    send_sem=send_s.at[slot], recv_sem=recv_s.at[nxt],
                    device_id=_nbr(ids_ref, n_axes, "right"),
                    device_id_type=pltpu.DeviceIdType.MESH)
                rdma_s.start()

        bi, rows = i // tt, pl.ds((i % tt) * bm, bm)
        if quant:
            xt = (buf[slot, bi, rows, pl.ds(k * bk, bk)].astype(jnp.float32)
                  * _row_scales(sbuf, slot, bi, (i % tt) * bm, bm)
                  ).astype(w_ref.dtype)
        else:
            xt = buf[slot, bi, rows, pl.ds(k * bk, bk)]
        acc[pl.ds(i * bm, bm), pl.ds(j * bn, bn)] += jnp.dot(
            xt, w_ref[...], preferred_element_type=jnp.float32)

        @pl.when((s == n - 1) & (k == kt - 1))
        def _emit():
            o_ref[...] = acc[pl.ds(i * bm, bm),
                             pl.ds(j * bn, bn)].astype(o_ref.dtype)

        @pl.when((s < n - 1) & lastt)
        def _step_done():     # our outbound read of buf[slot] must be done
            pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                                  send_sem.at[slot]).wait()
            if quant:
                pltpu.make_async_copy(sbuf.at[slot], sbuf.at[slot],
                                      send_s.at[slot]).wait()

        # Only sends at steps 1..n-2 consume a credit, so only steps 0..n-3
        # issue one — the capacity semaphore drains to zero at kernel end.
        @pl.when((s < n - 2) & lastt)
        def _free_slot():
            pltpu.semaphore_signal(
                cap_sem, inc=1, device_id=_nbr(ids_ref, n_axes, "left"),
                device_id_type=pltpu.DeviceIdType.MESH)

    if quant:
        xq, xs = _quant_lane_scales(x)
        in_specs = [
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            # w row-block follows the circulating shard's source rank
            pl.BlockSpec((h // kt, o // nt),
                         lambda s, i, j, k, ids:
                             (((ids[0] - s) % n) * kt + k, j)),
        ]
        scratch = [
            pltpu.VMEM((2, b, t, h), jnp.int8),
            pltpu.VMEM((2, b, t), jnp.float32),
            pltpu.VMEM((m, o), jnp.float32),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR,
        ]
        operands = (ids, xq, xs, w)
    else:
        in_specs = [
            pl.BlockSpec(memory_space=pl.ANY),
            # w row-block follows the circulating shard's source rank
            pl.BlockSpec((h // kt, o // nt),
                         lambda s, i, j, k, ids:
                             (((ids[0] - s) % n) * kt + k, j)),
        ]
        scratch = [
            pltpu.VMEM((2, b, t, h), x.dtype),
            pltpu.VMEM((m, o), jnp.float32),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR,
        ]
        operands = (ids, x, w)
    grid = (n, mt, nt, kt)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (bm, bn), lambda s, i, j, k, ids: _final_step_block(s, n, i, j)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((m, o), dt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            collective_id=collective_id, has_side_effects=True),
        name="ring_ag_matmul_contract",
    )(*operands)
    return out.reshape(b, t, o)


def _matmul_rs_pair_tpu(x, w1, w1b, *, axis_name: str, scatter_dim: int,
                        n: int, mesh_axes=None, collective_id: int = 3,
                        comm_dtype: str = "bf16"):
    """Gated single-kernel ring matmul-RS: the column-concatenated weights run
    through one `_matmul_rs_tpu`-shaped loop, so every x tile is read once for
    both products (shared-x-tile trick); the halves are split on emit."""
    wc = jnp.concatenate([w1, w1b], axis=1)
    y = _matmul_rs_tpu(x, wc, axis_name=axis_name, scatter_dim=scatter_dim,
                       n=n, mesh_axes=mesh_axes, collective_id=collective_id,
                       comm_dtype=comm_dtype)
    o1 = w1.shape[-1]
    return y[..., :o1], y[..., o1:]
