"""Ring-decomposed collective matmuls — overlap NoP communication with compute.

Hecaton's headline claim (paper §III-B(3), §IV) is that its schedule hides NoP
communication behind on-die compute, keeping the computation-to-communication
ratio constant under weak scaling.  The bulk-synchronous ops in
``core/hecaton.py`` (``lax.all_gather`` → full matmul → ``lax.psum_scatter``)
leave the links idle during the matmul and the MXU idle during the collectives.
This module provides the standard remedy — decomposed collective matmuls over
``lax.ppermute`` rings — selected by ``ParallelConfig.overlap``:

  * ``"none"``   — the bulk path (callers keep using lax.all_gather/psum_scatter).
  * ``"ring"``   — unidirectional ring: at step *k* each device matmuls the
                   shard it holds while the ``ppermute`` for step *k+1* is in
                   flight, so a latency-hiding scheduler (TPU/GPU async
                   collectives) fully overlaps the chain.
  * ``"bidir"``  — bidirectional ring: every shard is split in half and the two
                   halves circulate in opposite directions, halving per-step
                   bytes per link on full-duplex (torus) links.
  * ``"fused"``  — the whole ring inside ONE Pallas kernel
                   (kernels/ring_matmul.py): a double-buffered VMEM pair
                   receives the next peer's shard via remote DMA while the MXU
                   consumes the current shard through the tile loop — overlap
                   guaranteed by construction, no per-step dispatch gap.  On
                   backends without remote-DMA support the kernels emulate
                   each hop with ``lax.ppermute`` (compat.ring_step_permute)
                   and run the tile loops in interpret mode.

The mode lattice degrades left: ``fused`` falls back to ``ring`` per
collective when a shape is not tile-aligned (:func:`fused_ok_*` in
kernels/ring_matmul.py), exactly as ``bidir`` degrades to ``ring`` when a
shard cannot be halved; every mode falls back to the bulk collective for
extents a ring cannot chunk (``rs_ok``).  Numerics are identical across the
lattice (fp32-accumulation tolerance).

Primitives (all called *inside* shard_map, on per-device blocks):

  ``ring_all_gather``        AG as a ppermute chain (no fused compute).
  ``ring_reduce_scatter``    RS as a circulating-accumulator ppermute chain.
  ``ring_ag_matmul``         AG ⊕ matmul: circulate input shards, matmul each
                             on arrival into its slot of the output (the
                             gather dim is *not* contracted).
  ``ring_ag_matmul_contract``AG ⊕ matmul over the *contracted* dim: per-step
                             partial products accumulate in fp32 (one partial
                             per peer shard — same accumulation the MXU does).
  ``ring_matmul_rs``         matmul ⊕ RS: per-destination output tiles are
                             computed one ring step ahead of the accumulator
                             they are folded into.
  ``ring_linear``            RS(matmul(AG(x))) with the matmul fused into
                             whichever side moves more bytes.

Backward/transpose story: every loop is unrolled Python over linear primitives
(``ppermute``, ``dynamic_(update_)slice``, ``dot``), so JAX's transpose rules
yield the overlapped backward for free: the transpose of a ``ppermute`` ring is
the reversed ring, ``dynamic_update_slice`` transposes to ``dynamic_slice``,
and therefore transpose(ring-AG-matmul) *is* a ring-matmul-RS (and vice versa).
Under ``comm_dtype="bf16"`` no custom VJP is needed and grads flow as
collective-permute chains too.  Under ``comm_dtype="int8"`` each hop is
``core/quant.q_hop`` — a custom-VJP hop whose forward permutes the (int8
payload, fp32 scale) pair and whose backward runs the same quantized hop over
the inverse permutation, so cotangent shards cross the links quantized exactly
like activations do (docs/DESIGN.md §11).

Communication dtype (``ParallelConfig.comm_dtype``): every ``ppermute`` in
this module goes through ``core/quant.ring_hop``.  ``"bf16"`` (default) is
bit-identical to a bare ``lax.ppermute`` of the operand; ``"int8"`` quantizes
the shard being sent with per-row symmetric scales and dequantizes into the
existing fp32 accumulation on receipt, cutting per-hop bytes ~2x (bf16
compute) to ~4x (fp32).  Hops whose shard cannot carry scales — integer ids,
trailing extents below ``quant.MIN_QUANT_DIM`` — degrade per hop to the
full-width permute, mirroring the fused→ring→bulk mode lattice.

Shape constraints: ``bidir`` degrades to ``ring`` per collective when a shard
cannot be halved (checked inside each primitive — numerics are identical), and
a degenerate ring (axis size 1) short-circuits to the local op.  A ring
reduce-scatter needs the scattered extent to divide by the ring size — the
same divisibility the bulk ``psum_scatter(tiled=True)`` already enforces, so
the overlapped path never accepts less than the bulk path (``ring_linear``
routes the non-dividing case to the bulk collective, whose error message names
the offending shape).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from repro.core import quant as Q
from repro.kernels import ring_matmul as RM

MODES = ("none", "ring", "bidir", "fused")
COMM_DTYPES = Q.COMM_DTYPES
check_comm_dtype = Q.check_comm_dtype
_hop = Q.ring_hop


def _mm_f32(x, w):
    """bf16 matmul with fp32 accumulation (MXU semantics), fp32 result."""
    return jnp.einsum("bth,ho->bto", x, w, preferred_element_type=jnp.float32)


def _mm(x, w):
    return _mm_f32(x, w).astype(x.dtype)


def _shift_perm(n: int, shift: int):
    return [(i, (i + shift) % n) for i in range(n)]


def _put(buf, part, dim: int, start):
    starts = [0] * buf.ndim
    starts[dim] = start
    return lax.dynamic_update_slice(buf, part, tuple(starts))


def _take(x, dim: int, start, size: int):
    starts = [0] * x.ndim
    starts[dim] = start
    sizes = list(x.shape)
    sizes[dim] = size
    return lax.dynamic_slice(x, tuple(starts), tuple(sizes))


def check_mode(overlap: str) -> str:
    """Validate an overlap mode string (a typo must not silently mean ring)."""
    if overlap not in MODES:
        raise ValueError(f"overlap={overlap!r} not in {MODES}")
    return overlap


def rs_ok(extent: int, n: int) -> bool:
    """Can a ring reduce-scatter over an ``n``-ring chunk ``extent``?

    False routes the caller to the bulk collective: for ``n == 1`` that is the
    trivial no-op, and for a non-dividing extent the bulk ``psum_scatter``
    raises the same shape error the bulk path always has."""
    return n > 1 and extent % n == 0


# ---------------------------------------------------------------------------
# Pure ring collectives (ppermute chains, no fused compute)
# ---------------------------------------------------------------------------


def ring_all_gather(x, axis_name: str, *, dim: int, n: int,
                    bidir: bool = False, comm_dtype: str = "bf16"):
    """== lax.all_gather(x, axis_name, axis=dim, tiled=True), rank order."""
    if n <= 1:
        return x
    idx = lax.axis_index(axis_name)
    chunk = x.shape[dim]
    shape = list(x.shape)
    shape[dim] = chunk * n
    out = jnp.zeros(tuple(shape), x.dtype)
    if bidir and chunk % 2 == 0:
        half = chunk // 2
        curf = _take(x, dim, 0, half)
        curb = _take(x, dim, half, half)
        for s in range(n):
            out = _put(out, curf, dim, ((idx - s) % n) * chunk)
            out = _put(out, curb, dim, ((idx + s) % n) * chunk + half)
            if s < n - 1:
                curf = _hop(curf, axis_name, n, 1, comm_dtype)
                curb = _hop(curb, axis_name, n, -1, comm_dtype)
        return out
    cur = x
    for s in range(n):
        out = _put(out, cur, dim, ((idx - s) % n) * chunk)
        if s < n - 1:
            cur = _hop(cur, axis_name, n, 1, comm_dtype)
    return out


def ring_reduce_scatter(y, axis_name: str, *, dim: int, n: int,
                        bidir: bool = False, comm_dtype: str = "bf16"):
    """== lax.psum_scatter(y, axis_name, scatter_dimension=dim, tiled=True).

    A per-destination accumulator circulates the ring; each device folds in its
    local contribution as the accumulator passes through.  Destination of the
    accumulator held at device *i* after *s* hops: ``(i + n-1 - s) % n`` — at
    the final step every device holds its own fully reduced chunk.
    """
    if n <= 1:
        return y
    assert y.shape[dim] % n == 0, (
        f"ring RS: extent {y.shape[dim]} does not chunk by ring size {n}")
    idx = lax.axis_index(axis_name)
    chunk = y.shape[dim] // n
    if bidir and chunk % 2 == 0:
        half = chunk // 2

        def takef(d):
            return _take(y, dim, d * chunk, half)

        def takeb(d):
            return _take(y, dim, d * chunk + half, half)

        accf = takef((idx - 1) % n)
        accb = takeb((idx + 1) % n)
        for s in range(1, n):
            accf = _hop(accf, axis_name, n, 1, comm_dtype)
            accb = _hop(accb, axis_name, n, -1, comm_dtype)
            accf = accf + takef((idx + n - 1 - s) % n)
            accb = accb + takeb((idx - (n - 1) + s) % n)
        return jnp.concatenate([accf, accb], axis=dim)
    acc = _take(y, dim, ((idx - 1) % n) * chunk, chunk)
    for s in range(1, n):
        acc = _hop(acc, axis_name, n, 1, comm_dtype)
        acc = acc + _take(y, dim, ((idx + n - 1 - s) % n) * chunk, chunk)
    return acc


# ---------------------------------------------------------------------------
# Fused collective matmuls
# ---------------------------------------------------------------------------


def ring_ag_matmul(x, w, axis_name: str, *, dim: int, n: int,
                   bidir: bool = False, comm_dtype: str = "bf16"):
    """== _mm(ring_all_gather(x, dim), w) with per-step partial matmuls.

    The gather dim is a *batch* dim of the matmul (tokens), so each arriving
    shard is matmul'd independently into its slot of the output — step *k*'s
    matmul hides step *k+1*'s permute.
    """
    if n <= 1:
        return _mm(x, w)
    idx = lax.axis_index(axis_name)
    chunk = x.shape[dim]
    shape = list(x.shape)
    shape[dim] = chunk * n
    shape[-1] = w.shape[-1]
    out = jnp.zeros(tuple(shape), x.dtype)
    if bidir and chunk % 2 == 0:
        half = chunk // 2
        curf = _take(x, dim, 0, half)
        curb = _take(x, dim, half, half)
        for s in range(n):
            out = _put(out, _mm(curf, w), dim, ((idx - s) % n) * chunk)
            out = _put(out, _mm(curb, w), dim, ((idx + s) % n) * chunk + half)
            if s < n - 1:
                curf = _hop(curf, axis_name, n, 1, comm_dtype)
                curb = _hop(curb, axis_name, n, -1, comm_dtype)
        return out
    cur = x
    for s in range(n):
        out = _put(out, _mm(cur, w), dim, ((idx - s) % n) * chunk)
        if s < n - 1:
            cur = _hop(cur, axis_name, n, 1, comm_dtype)
    return out


def ring_ag_matmul_contract(x, w, axis_name: str, *, n: int,
                            bidir: bool = False, out_dtype=None,
                            comm_dtype: str = "bf16"):
    """== mm(ring_all_gather(x, dim=-1), w) where the gathered dim is the
    matmul's *contraction* dim: w's rows are chunked to match and the per-step
    partial products accumulate in fp32 (the same accumulation a single big
    matmul performs internally, so numerics track the bulk path)."""
    dt = out_dtype or x.dtype
    if n <= 1:
        return _mm_f32(x, w).astype(dt)
    idx = lax.axis_index(axis_name)
    h_loc = x.shape[-1]
    acc = jnp.zeros(x.shape[:-1] + (w.shape[-1],), jnp.float32)
    if bidir and h_loc % 2 == 0:
        half = h_loc // 2
        curf = _take(x, x.ndim - 1, 0, half)
        curb = _take(x, x.ndim - 1, half, half)
        for s in range(n):
            rf = ((idx - s) % n) * h_loc
            rb = ((idx + s) % n) * h_loc + half
            acc = acc + _mm_f32(curf, _take(w, 0, rf, half))
            acc = acc + _mm_f32(curb, _take(w, 0, rb, half))
            if s < n - 1:
                curf = _hop(curf, axis_name, n, 1, comm_dtype)
                curb = _hop(curb, axis_name, n, -1, comm_dtype)
        return acc.astype(dt)
    cur = x
    for s in range(n):
        acc = acc + _mm_f32(cur, _take(w, 0, ((idx - s) % n) * h_loc, h_loc))
        if s < n - 1:
            cur = _hop(cur, axis_name, n, 1, comm_dtype)
    return acc.astype(dt)


def ring_matmul_rs(x, w, axis_name: str, *, scatter_dim: int, n: int,
                   bidir: bool = False, comm_dtype: str = "bf16"):
    """== lax.psum_scatter(_mm(x, w), scatter_dimension=scatter_dim, tiled).

    The per-destination tile is produced by a *chunked* matmul right before it
    is folded into the circulating accumulator: rows of x are chunked when the
    scatter dim is the token dim (1), columns of w when it is the output
    feature dim (2) — either way each ring step has a matmul to hide its
    permute behind.
    """
    if n <= 1:
        return _mm(x, w)
    idx = lax.axis_index(axis_name)
    scattered = w.shape[-1] if scatter_dim == x.ndim - 1 else \
        x.shape[scatter_dim]
    assert scattered % n == 0, (
        f"ring matmul-RS: extent {scattered} does not chunk by ring size {n}")
    if scatter_dim == x.ndim - 1:          # chunk w's output columns
        chunk = w.shape[-1] // n

        def contrib(d, off=0, size=None):
            return _mm(x, _take(w, 1, d * chunk + off, size or chunk))
    else:                                   # chunk x's rows along scatter_dim
        chunk = x.shape[scatter_dim] // n

        def contrib(d, off=0, size=None):
            return _mm(_take(x, scatter_dim, d * chunk + off, size or chunk),
                       w)

    if bidir and chunk % 2 == 0:
        half = chunk // 2
        accf = contrib((idx - 1) % n, 0, half)
        accb = contrib((idx + 1) % n, half, half)
        for s in range(1, n):
            accf = _hop(accf, axis_name, n, 1, comm_dtype)
            accb = _hop(accb, axis_name, n, -1, comm_dtype)
            accf = accf + contrib((idx + n - 1 - s) % n, 0, half)
            accb = accb + contrib((idx - (n - 1) + s) % n, half, half)
        return jnp.concatenate([accf, accb], axis=scatter_dim)
    acc = contrib((idx - 1) % n)
    for s in range(1, n):
        acc = _hop(acc, axis_name, n, 1, comm_dtype)
        acc = acc + contrib((idx + n - 1 - s) % n)
    return acc


# ---------------------------------------------------------------------------
# Mode dispatchers: route one collective matmul to the single-kernel fused
# path (kernels/ring_matmul.py) when overlap="fused" and the shape is
# tile-aligned, else to the ppermute ring above.  These are the only places
# the fused/ring/bidir decision is made, so every hecaton primitive (and the
# MoE / megatron ring paths) inherits the same degradation contract.
# ---------------------------------------------------------------------------


# (op, x shape, w shape) of every collective that was asked to run fused but
# failed its fused_ok_* gate and took the ppermute ring instead, appended at
# trace time.  A caller that must see such a degrade clears it before tracing.
fused_fallbacks: list = []


def _fused(op: str, ok: bool, x, w, n: int) -> bool:
    if not ok and n > 1:
        fused_fallbacks.append((op, tuple(x.shape), tuple(w.shape)))
    return ok


def ag_matmul(x, w, axis_name: str, *, dim: int, n: int, overlap: str,
              mesh_axes=None, comm_dtype: str = "bf16"):
    """AG ⊕ matmul (gathered dim is a batch dim) under the given mode.

    ``mesh_axes`` (the enclosing mesh's full axis-name tuple) lets the TPU
    single-kernel path address ring neighbours by mesh coordinates; without
    it the fused mode still runs, via its ppermute-emulated path."""
    if overlap == "fused" and _fused(
            "ag_matmul", RM.fused_ok_ag(x.shape, w.shape, n, dim,
                                        x.dtype.itemsize), x, w, n):
        return RM.ag_matmul(x, w, axis_name, dim=dim, n=n,
                            mesh_axes=mesh_axes, comm_dtype=comm_dtype)
    return ring_ag_matmul(x, w, axis_name, dim=dim, n=n,
                          bidir=overlap == "bidir", comm_dtype=comm_dtype)


def matmul_rs(x, w, axis_name: str, *, scatter_dim: int, n: int,
              overlap: str, mesh_axes=None, comm_dtype: str = "bf16"):
    """matmul ⊕ RS under the given mode."""
    if overlap == "fused" and _fused(
            "matmul_rs", RM.fused_ok_rs(x.shape, w.shape, n, scatter_dim,
                                        x.dtype.itemsize), x, w, n):
        return RM.matmul_rs(x, w, axis_name, scatter_dim=scatter_dim, n=n,
                            mesh_axes=mesh_axes, comm_dtype=comm_dtype)
    return ring_matmul_rs(x, w, axis_name, scatter_dim=scatter_dim, n=n,
                          bidir=overlap == "bidir", comm_dtype=comm_dtype)


def ag_matmul_contract(x, w, axis_name: str, *, n: int, overlap: str,
                       out_dtype=None, mesh_axes=None,
                       comm_dtype: str = "bf16"):
    """AG ⊕ matmul over the contracted dim under the given mode."""
    if overlap == "fused" and _fused(
            "ag_matmul_contract", RM.fused_ok_contract(x.shape, w.shape, n,
                                                       x.dtype.itemsize),
            x, w, n):
        return RM.ag_matmul_contract(x, w, axis_name, n=n,
                                     out_dtype=out_dtype,
                                     mesh_axes=mesh_axes,
                                     comm_dtype=comm_dtype)
    return ring_ag_matmul_contract(x, w, axis_name, n=n,
                                   bidir=overlap == "bidir",
                                   out_dtype=out_dtype,
                                   comm_dtype=comm_dtype)


def matmul_rs_pair(x, w1, w1b, axis_name: str, *, scatter_dim: int, n: int,
                   overlap: str, mesh_axes=None, comm_dtype: str = "bf16"):
    """Gated pair: (x·w1, x·w1b) reduce-scattered, sharing the gathered x.

    Fused mode reads each x tile once for both products inside one kernel;
    the ring/bidir path runs two matmul-RS rings over the shared gather."""
    if overlap == "fused" and _fused(
            "matmul_rs_pair", RM.fused_ok_pair(x.shape, w1.shape, w1b.shape,
                                               n, scatter_dim,
                                               x.dtype.itemsize), x, w1, n):
        return RM.matmul_rs_pair(x, w1, w1b, axis_name,
                                 scatter_dim=scatter_dim, n=n,
                                 mesh_axes=mesh_axes, comm_dtype=comm_dtype)
    bidir = overlap == "bidir"
    return (ring_matmul_rs(x, w1, axis_name, scatter_dim=scatter_dim, n=n,
                           bidir=bidir, comm_dtype=comm_dtype),
            ring_matmul_rs(x, w1b, axis_name, scatter_dim=scatter_dim, n=n,
                           bidir=bidir, comm_dtype=comm_dtype))


# ---------------------------------------------------------------------------
# Composed linear: RS(matmul(AG(x))) with the matmul fused into the heavier side
# ---------------------------------------------------------------------------


def fuse_side(h_loc: int, o_loc: int) -> str:
    """Which collective the single matmul should fuse into.

    The AG moves the input (∝ h_loc per token), the RS moves the output
    (∝ o_loc per token); fusing the heavier side hides more bytes.  Ties go to
    the AG (circulating the smaller operand keeps ring messages small)."""
    return "rs" if o_loc > h_loc else "ag"


def ring_linear(x, w, *, g_ax: str, n_g: int, s_ax: str, n_s: int,
                gather_dim: int = 1, scatter_dim: int = 1, overlap: str,
                mesh_axes=None, comm_dtype: str = "bf16"):
    """Overlapped y = RS_{s_ax}( AG_{g_ax}(x, gather_dim) @ w, scatter_dim).

    One of the two collectives gets the matmul fused into its ring loop
    (``fuse_side``); the other runs as a pure ppermute ring — every NoP
    transfer in the chain is a collective-permute.  Under ``overlap="fused"``
    the matmul-carrying side runs as one Pallas ring kernel when tile-aligned
    (kernels/ring_matmul.py), degrading per collective to the ppermute ring
    otherwise.  A scattered extent the ring cannot chunk goes to the bulk
    ``psum_scatter`` instead (a no-op for a size-1 axis; for a genuinely
    non-dividing extent it raises the same shape error the bulk path always
    has) — the gather side stays overlapped.
    """
    check_mode(overlap)
    bidir = overlap == "bidir"
    scattered = (x.shape[gather_dim] * n_g if scatter_dim == gather_dim
                 else w.shape[-1])
    if fuse_side(x.shape[-1], w.shape[-1]) == "rs" and rs_ok(scattered, n_s):
        xg = ring_all_gather(x, g_ax, dim=gather_dim, n=n_g, bidir=bidir,
                             comm_dtype=comm_dtype)
        return matmul_rs(xg, w, s_ax, scatter_dim=scatter_dim, n=n_s,
                         overlap=overlap, mesh_axes=mesh_axes,
                         comm_dtype=comm_dtype)
    yp = ag_matmul(x, w, g_ax, dim=gather_dim, n=n_g, overlap=overlap,
                   mesh_axes=mesh_axes, comm_dtype=comm_dtype)
    if not rs_ok(scattered, n_s):           # cannot chunk: bulk reduce-scatter
        return lax.psum_scatter(yp, s_ax, scatter_dimension=scatter_dim,
                                tiled=True)
    return ring_reduce_scatter(yp, s_ax, dim=scatter_dim, n=n_s, bidir=bidir,
                               comm_dtype=comm_dtype)
