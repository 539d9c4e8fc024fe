"""Hecaton's distributed training method (paper §IV, Algorithm 1) as JAX ops.

The paper tiles every weight matrix over a 2D die grid (mx × my) and replaces the
global all-reduce of 1D tensor parallelism with two *local* collectives over √N-size
groups — an all-gather of the input along one grid axis and a reduce-scatter of the
output along the other.  Both collectives run at full ring bandwidth on a torus
(TPU ICI is a torus; the paper builds one from bypass links).

Two dataflow patterns from the paper:

* ``linear_seq_scatter``  (§IV-B, FFN blocks / fused linear chains)
    in : x  [B, T/t_ax, H/h_ax]   (tokens sharded over ``t_ax``, hidden over ``h_ax``)
         w  [H/h_ax, O/t_ax]      (paper's transposed tile placement W[j,i] on die (i,j))
    out: y  [B, T/h_ax, O/t_ax]   — tiling is the *transpose* of the input tiling, so
                                    the next (fused) layer runs with swapped axis roles
                                    and needs no extra communication (paper §IV-B).

* ``mixer_in`` / ``mixer_out``  (§IV-C, attention & other token mixers)
    ``mixer_in``  all-gathers the *sequence* (so every die sees all tokens) and
    reduce-scatters the output along *hidden* — each die ends up with a head-slice of
    Q/K/V over the full sequence, exploiting head parallelism with zero comm inside
    the attention itself.  ``mixer_out`` is the inverse: gather hidden, project, and
    reduce-scatter tokens back to the canonical tiling.

Backward faithfulness: we differentiate *through* ``shard_map``.  JAX's transpose
rules give exactly Algorithm 1's backward —
    transpose(all_gather)   = reduce-scatter (paper Step 4 of bwd)
    transpose(psum_scatter) = all-gather     (paper Step 3 of bwd: gather dY once,
                                              reuse for both dX and dW)
and the re-gather of X for dW (paper Steps 6-7, the SRAM-capacity trick) is obtained
by wrapping blocks in a remat policy that saves only the *sharded* activations and
recomputes gathers (core/schedule.py).

All functions are no-ops (plain einsums) when ``mesh is None`` so the same model code
runs single-device smoke tests.

Residual layout: the canonical inter-block activation contract
(``ParallelConfig.residual == "seq"``) is the SEQ-SHARDED residual stream —
for these ops that is simply Alg. 1's native tiling P(data, t_ax, h_ax):
every primitive here already accepts token-scattered inputs without an
up-front gather, which is why no block boundary carries a bulk collective.
The flag exists for the megatron baseline (parallel/megatron.py), whose
replicated layout is kept as the §V-A(b) comparison point.

Communication/compute overlap (``overlap=`` on every op, plumbed from
``ParallelConfig.overlap`` via ``parallel/context.py``):

  * ``"none"``  — bulk-synchronous collectives (lax.all_gather / psum_scatter),
                  the paper's Algorithm 1 verbatim.
  * ``"ring"``  — ring-decomposed collective matmuls (core/overlap.py): the
                  all-gather circulates shards with ``lax.ppermute`` while each
                  arriving shard is matmul'd (AG-matmul), and the
                  reduce-scatter folds per-destination matmul tiles into a
                  circulating accumulator (matmul-RS), so every NoP transfer is
                  a collective-permute hidden behind a partial matmul — the
                  paper's §III-B(3) overlap claim made explicit in the HLO.
  * ``"bidir"`` — same, with half-sized shards circulating in both ring
                  directions (full-duplex torus links).
  * ``"fused"`` — the whole ring inside one Pallas kernel
                  (kernels/ring_matmul.py): remote DMA into a double-buffered
                  VMEM pair overlapped with the MXU tile loop by construction,
                  removing the per-step dispatch gap the ``ring`` modes leave
                  to the XLA scheduler.  CPU/interpret backends emulate each
                  hop with ``lax.ppermute`` (same chain in the HLO).

The mode lattice degrades left (``fused → ring``, ``bidir → ring``, any →
bulk) per collective, decided entirely inside core/overlap.py's dispatchers:
``fused`` requires tile-aligned shapes, ``bidir`` requires halvable shards, a
ring reduce-scatter requires the scattered extent to chunk by the ring size,
and degenerate (size-1) ring axes short-circuit to the bulk op — numerics are
identical everywhere.

The backward pass stays overlapped for free: the ring loops are unrolled linear
primitives, and JAX transposes ring-AG-matmul into ring-matmul-RS (and vice
versa); the fused kernels carry ``custom_vjp``s implementing the same
transposed rings — see core/overlap.py and kernels/ring_matmul.py.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro import compat
from repro.core import overlap as OV

# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _shard_map(f, mesh, in_specs, out_specs):
    return compat.shard_map(f, mesh, in_specs, out_specs)


def _ag(x, axis_name: str, dim: int):
    """Tiled all-gather along ``dim`` over mesh axis ``axis_name``."""
    return lax.all_gather(x, axis_name, axis=dim, tiled=True)


def _rs(x, axis_name: str, dim: int):
    """Tiled reduce-scatter (psum_scatter) along ``dim`` over ``axis_name``."""
    return lax.psum_scatter(x, axis_name, scatter_dimension=dim, tiled=True)


def _mm(x, w, precision=None):
    """Local matmul in bf16 with fp32 accumulation (MXU semantics)."""
    return jnp.einsum("bth,ho->bto", x, w,
                      preferred_element_type=jnp.float32).astype(x.dtype)


# ---------------------------------------------------------------------------
# Pattern 1: fused-linear / FFN dataflow (Algorithm 1, seq-scatter)
# ---------------------------------------------------------------------------


@jax.named_scope("hecaton_linear_seq_scatter")
def linear_seq_scatter(x: jax.Array, w: jax.Array, *, mesh: Optional[Mesh],
                       t_ax: str, h_ax: str,
                       data_axes: Tuple[str, ...] = ("data",),
                       overlap: str = "none",
                       comm_dtype: str = "bf16") -> jax.Array:
    """One Hecaton linear layer (paper Alg. 1 forward, steps 2-5).

    x: [B, T_local*t, H_local*h] logically; sharded P(data_axes, t_ax, h_ax).
    w: [H, O] sharded P(h_ax, t_ax)  (the paper's W[j,i] -> die(i,j) placement).
    returns y sharded P(data_axes, h_ax, t_ax)  (transposed tiling).
    """
    OV.check_mode(overlap)
    if mesh is None:
        return _mm(x, w)
    n_t, n_h = mesh.shape[t_ax], mesh.shape[h_ax]

    def f(xl, wl):
        if overlap != "none":
            return OV.ring_linear(xl, wl, g_ax=t_ax, n_g=n_t, s_ax=h_ax,
                                  n_s=n_h, gather_dim=1, scatter_dim=1,
                                  overlap=overlap,
                                  mesh_axes=mesh.axis_names,
                                  comm_dtype=comm_dtype)
        xg = _ag(xl, t_ax, 1)           # Step 3: all-gather tokens within column
        yp = _mm(xg, wl)                # local tile matmul (partial over h_ax)
        return _rs(yp, h_ax, 1)         # Step 4: reduce-scatter tokens within row

    dspec = P(data_axes)
    return _shard_map(
        f, mesh,
        in_specs=(P(dspec[0] if len(data_axes) == 1 else data_axes, t_ax, h_ax),
                  P(h_ax, t_ax)),
        out_specs=P(data_axes if len(data_axes) > 1 else data_axes[0], h_ax, t_ax),
    )(x, w)


# ---------------------------------------------------------------------------
# Pattern 2: token-mixer dataflow (paper §IV-C)
# ---------------------------------------------------------------------------


@jax.named_scope("hecaton_mixer_in")
def mixer_in(x: jax.Array, w: jax.Array, *, mesh: Optional[Mesh],
             t_ax: str, h_ax: str,
             data_axes: Tuple[str, ...] = ("data",),
             overlap: str = "none",
             comm_dtype: str = "bf16") -> jax.Array:
    """Projection *into* a token mixer (QKV / mamba in_proj). Paper Fig. 7(b) steps 1-4+10.

    x: [B, T/t_ax, H/h_ax]  ->  out: [B, T(full), O/(t_ax,h_ax)]
    Sequence is gathered (every die sees all tokens of its data shard); output hidden
    is fully sharded over the whole 2D grid: head-sliced, comm-free attention.
    """
    OV.check_mode(overlap)
    if mesh is None:
        return _mm(x, w)
    n_t, n_h = mesh.shape[t_ax], mesh.shape[h_ax]

    def f(xl, wl):
        if overlap != "none":
            return OV.ring_linear(xl, wl, g_ax=t_ax, n_g=n_t, s_ax=h_ax,
                                  n_s=n_h, gather_dim=1, scatter_dim=2,
                                  overlap=overlap,
                                  mesh_axes=mesh.axis_names,
                                  comm_dtype=comm_dtype)
        xg = _ag(xl, t_ax, 1)           # gather sequence within column
        yp = _mm(xg, wl)                # [b, T, O/t_ax] partial over h_ax
        return _rs(yp, h_ax, 2)         # Step 10: reduce-scatter along *hidden*
    return _shard_map(
        f, mesh,
        in_specs=(P(data_axes if len(data_axes) > 1 else data_axes[0], t_ax, h_ax),
                  P(h_ax, t_ax)),
        out_specs=P(data_axes if len(data_axes) > 1 else data_axes[0], None,
                    (t_ax, h_ax)),
    )(x, w)


@jax.named_scope("hecaton_mixer_out")
def mixer_out(a: jax.Array, w: jax.Array, *, mesh: Optional[Mesh],
              t_ax: str, h_ax: str,
              data_axes: Tuple[str, ...] = ("data",),
              overlap: str = "none",
              comm_dtype: str = "bf16") -> jax.Array:
    """Projection *out of* a token mixer (attention O-proj / mamba out_proj).

    Paper Fig. 7(b) steps 12-14: all-gather hidden within row, project, then
    reduce-scatter the sequence back to the canonical tiling.

    a: [B, T(full), Hm/(t_ax,h_ax)]  ->  out: [B, T/t_ax, O/h_ax]

    Here the gathered dim is the matmul's *contraction* dim, so the overlapped
    gather accumulates per-step partial products (ring_ag_matmul_contract)
    instead of placing tiles.
    """
    OV.check_mode(overlap)
    if mesh is None:
        return _mm(a, w)
    n_t, n_h = mesh.shape[t_ax], mesh.shape[h_ax]

    def f(al, wl):
        if overlap != "none":
            bidir = overlap == "bidir"
            rs_ok = OV.rs_ok(al.shape[1], n_t)
            if OV.fuse_side(al.shape[-1], wl.shape[-1]) == "rs" and rs_ok:
                ag = OV.ring_all_gather(al, h_ax, dim=2, n=n_h, bidir=bidir,
                                        comm_dtype=comm_dtype)
                return OV.matmul_rs(ag, wl, t_ax, scatter_dim=1, n=n_t,
                                    overlap=overlap,
                                    mesh_axes=mesh.axis_names,
                                    comm_dtype=comm_dtype)
            yp = OV.ag_matmul_contract(al, wl, h_ax, n=n_h, overlap=overlap,
                                       mesh_axes=mesh.axis_names,
                                       comm_dtype=comm_dtype)
            if not rs_ok:
                return _rs(yp, t_ax, 1)
            return OV.ring_reduce_scatter(yp, t_ax, dim=1, n=n_t, bidir=bidir,
                                          comm_dtype=comm_dtype)
        ag = _ag(al, h_ax, 2)           # Step 12: gather hidden within row
        yp = _mm(ag, wl)                # [b, T, O/h_ax] partial over t_ax
        return _rs(yp, t_ax, 1)         # Step 14: reduce-scatter sequence
    return _shard_map(
        f, mesh,
        in_specs=(P(data_axes if len(data_axes) > 1 else data_axes[0], None,
                    (t_ax, h_ax)),
                  P(t_ax, h_ax)),
        out_specs=P(data_axes if len(data_axes) > 1 else data_axes[0], t_ax, h_ax),
    )(a, w)


# ---------------------------------------------------------------------------
# Fused FFN block (paper §IV-B "two rounds of transposition")
# ---------------------------------------------------------------------------


@jax.named_scope("hecaton_ffn_block")
def ffn_block(x, w1, w2, *, mesh, act_fn, t_ax: str, h_ax: str,
              data_axes: Tuple[str, ...] = ("data",),
              w1b=None, overlap: str = "none", comm_dtype: str = "bf16"):
    """Fused up/down FFN: two chained seq-scatter linears with swapped axis roles.

    After L1 the activation tiling is transposed (tokens on h_ax); L2 runs with the
    roles swapped and restores the canonical tiling — the paper's zero-communication
    layer fusion.  ``w1b`` is an optional second up-projection for gated MLPs
    (SwiGLU/GeGLU): both up-projections read the *same* gathered input, so gating
    adds zero extra communication (the gather is shared — layer fusion again).

    With ``overlap`` enabled the gated path ring-gathers the input once (both
    up-projections read it) and fuses each projection's reduce-scatter into its
    matmul loop; the ungated path uses the composed ``ring_linear`` twice.
    """
    OV.check_mode(overlap)
    if mesh is None:
        h = _mm(x, w1)
        if w1b is not None:
            h = act_fn(h) * _mm(x, w1b)
        else:
            h = act_fn(h)
        return _mm(h, w2)
    n_t, n_h = mesh.shape[t_ax], mesh.shape[h_ax]

    def f_ring(xl, w1l, w2l, *rest):
        bidir = overlap == "bidir"
        if rest:                                   # gated: share the gathered x
            xg = OV.ring_all_gather(xl, t_ax, dim=1, n=n_t, bidir=bidir,
                                    comm_dtype=comm_dtype)
            if OV.rs_ok(xg.shape[1], n_h):
                h, g = OV.matmul_rs_pair(xg, w1l, rest[0], h_ax,
                                         scatter_dim=1, n=n_h,
                                         overlap=overlap,
                                         mesh_axes=mesh.axis_names,
                                         comm_dtype=comm_dtype)
            else:
                h = _rs(_mm(xg, w1l), h_ax, 1)
                g = _rs(_mm(xg, rest[0]), h_ax, 1)
            h = act_fn(h) * g
        else:
            h = act_fn(OV.ring_linear(xl, w1l, g_ax=t_ax, n_g=n_t, s_ax=h_ax,
                                      n_s=n_h, overlap=overlap,
                                      mesh_axes=mesh.axis_names,
                                      comm_dtype=comm_dtype))
        return OV.ring_linear(h, w2l, g_ax=h_ax, n_g=n_h, s_ax=t_ax, n_s=n_t,
                              overlap=overlap, mesh_axes=mesh.axis_names,
                              comm_dtype=comm_dtype)

    def f(xl, w1l, w2l, *rest):
        if overlap != "none":
            return f_ring(xl, w1l, w2l, *rest)
        xg = _ag(xl, t_ax, 1)                      # gather tokens once
        hp = _mm(xg, w1l)
        h = _rs(hp, h_ax, 1)                       # tokens now tiled over h_ax
        if rest:
            gp = _mm(xg, rest[0])
            g = _rs(gp, h_ax, 1)
            h = act_fn(h) * g
        else:
            h = act_fn(h)
        hg = _ag(h, h_ax, 1)                       # L2 with swapped roles
        yp = _mm(hg, w2l)
        return _rs(yp, t_ax, 1)                    # canonical tiling restored

    dspec = data_axes if len(data_axes) > 1 else data_axes[0]
    in_specs = [P(dspec, t_ax, h_ax), P(h_ax, t_ax), P(t_ax, h_ax)]
    args = [x, w1, w2]
    if w1b is not None:
        in_specs.append(P(h_ax, t_ax))
        args.append(w1b)
    return _shard_map(f, mesh, in_specs=tuple(in_specs),
                      out_specs=P(dspec, t_ax, h_ax))(*args)


# ---------------------------------------------------------------------------
# Vocab-parallel embedding (paper §IV-B Step 2-3: scatter from DRAM, collect
# via NoP).  The table is 2D-tiled [V/t_ax, H/h_ax]; each die gathers its vocab
# slice for ALL tokens (masked) and a reduce-scatter over the token axis both
# sums the vocab partials and restores the canonical activation tiling.
# (Also works around an XLA GSPMD bug partitioning gathers from 2D-sharded
# tables: dynamic-slice verifier failure, observed jax 0.8.2 CPU backend.)
#
# With ``overlap`` != "none" the last bulk collective outside the hot paths
# honours the mode lattice too: the ids gather and the vocab-partial
# reduce-scatter run as ppermute rings, and ``"fused"`` additionally routes
# the collect through the single-kernel matmul-RS (the vocab partial is
# expressed as a one-hot matmul so there is a matmul to fuse the scatter
# into) when the local vocab slice is small enough for that to be a win —
# larger slices degrade to the ring reduce-scatter, per the lattice.
# ---------------------------------------------------------------------------

# local vocab slice above which the one-hot-matmul form of the vocab collect
# (the fused matmul-RS route) costs more MXU time than it hides — degrade to
# the plain ring reduce-scatter beyond it.
EMBED_FUSED_VMAX = 2048


@jax.named_scope("hecaton_embed_2d")
def embed_2d(ids: jax.Array, table: jax.Array, *, mesh: Optional[Mesh],
             t_ax: str, h_ax: str, data_axes: Tuple[str, ...] = ("data",),
             compute_dtype=jnp.bfloat16, seq_sharded: bool = True,
             batch_sharded: bool = True, overlap: str = "none",
             comm_dtype: str = "bf16") -> jax.Array:
    """ids [B,S] -> embeddings.

    seq_sharded=True (train/prefill): ids arrive tokens-over-t_ax, output is
    canonical [B, S/t_ax, H/h_ax] (for megatron callers ``h_ax=None``: the
    seq-sharded residual P(d, model, None)).  seq_sharded=False (decode): ids
    replicated, output [B, S, H/h_ax] with a psum over t_ax instead of the
    scatter.  ``overlap`` != "none" replaces the bulk ids-gather / vocab
    reduce-scatter with the ring forms (fused one-hot matmul-RS when cheap).
    """
    OV.check_mode(overlap)
    if mesh is None:
        return jnp.take(table, ids, axis=0).astype(compute_dtype)
    n_t = mesh.shape[t_ax]
    bidir = overlap == "bidir"

    def f(ids_l, tab_l):
        if seq_sharded and overlap != "none":
            # integer ids: quant_ok degrades these hops to full width
            idg = OV.ring_all_gather(ids_l, t_ax, dim=1, n=n_t, bidir=bidir,
                                     comm_dtype=comm_dtype)
        elif seq_sharded:
            idg = _ag(ids_l, t_ax, 1)
        else:
            idg = ids_l
        v_loc = tab_l.shape[0]
        off = lax.axis_index(t_ax) * v_loc
        lid = idg - off
        ok = (lid >= 0) & (lid < v_loc)
        if (seq_sharded and overlap == "fused" and v_loc <= EMBED_FUSED_VMAX
                and OV.rs_ok(idg.shape[1], n_t)):
            # one-hot form: emb_partial = onehot @ table_slice, which the
            # fused dispatcher can run as a single-kernel matmul ⊕ RS
            onehot = (jnp.where(ok, lid, v_loc)[..., None]
                      == jnp.arange(v_loc)[None, None, :]).astype(compute_dtype)
            tab = tab_l.astype(compute_dtype)
            return OV.matmul_rs(onehot, tab, t_ax, scatter_dim=1, n=n_t,
                                overlap=overlap, mesh_axes=mesh.axis_names,
                                comm_dtype=comm_dtype)
        emb = jnp.take(tab_l, jnp.clip(lid, 0, v_loc - 1), axis=0)
        emb = (emb * ok[..., None]).astype(compute_dtype)
        if seq_sharded:
            if overlap != "none" and OV.rs_ok(emb.shape[1], n_t):
                return OV.ring_reduce_scatter(emb, t_ax, dim=1, n=n_t,
                                              bidir=bidir,
                                              comm_dtype=comm_dtype)
            return _rs(emb, t_ax, 1)        # sums vocab partials + tiles tokens
        return lax.psum(emb, t_ax)

    d = data_axes if len(data_axes) > 1 else data_axes[0]
    bspec = d if batch_sharded else None
    in_ids = P(bspec, t_ax if seq_sharded else None)
    out = P(bspec, t_ax, h_ax) if seq_sharded else P(bspec, None, h_ax)
    return _shard_map(f, mesh, in_specs=(in_ids, P(t_ax, h_ax)),
                      out_specs=out)(ids, table)


# ---------------------------------------------------------------------------
# Fused chunked LM-head + cross-entropy (beyond-paper optimization, §Perf it.2)
#
# The baseline seq-scatter lm_head materializes [all-local-tokens, V/mx]
# partial logits (gigabytes in fp32) and its backward all-gathers fp32
# d-logits — by far the largest memory AND collective contributor for
# small/medium models.  Here the loss is computed inside ONE shard_map,
# scanning over sequence chunks:
#   * tokens stay tiled over t_ax (never gathered);
#   * the head weight is [H, V/h_ax] (vocab over h_ax, H unsharded — stored
#     FSDP-sharded over data);
#   * per chunk: AG x over h_ax (tiny), local [tc,H]@[H,V/h] matmul, stable
#     LSE via pmax/psum of per-token scalars over h_ax;
#   * nothing bigger than [tc, V/h_ax] ever exists, and the only collectives
#     are the tiny x-chunk gather + scalar reductions.
# ---------------------------------------------------------------------------


@jax.named_scope("loss_head")
@jax.named_scope("hecaton_fused_lm_loss")
def fused_lm_loss(x: jax.Array, w: jax.Array, labels: jax.Array,
                  loss_mask: Optional[jax.Array], *, mesh: Optional[Mesh],
                  t_ax: str, h_ax: str, data_axes: Tuple[str, ...] = ("data",),
                  n_chunks: int = 8,
                  overlap: str = "none",
                  comm_dtype: str = "bf16") -> Tuple[jax.Array, jax.Array]:
    """Returns (sum of masked NLL, mask count) — caller divides.

    x [B, S, H] canonical P(d, t_ax, h_ax); w [H, V] P(None, h_ax);
    labels/loss_mask [B, S] P(d, t_ax).
    """
    OV.check_mode(overlap)
    if loss_mask is None:
        loss_mask = jnp.ones(labels.shape, jnp.float32)

    if mesh is None:
        lf = jnp.einsum("bth,hv->btv", x, w.astype(x.dtype),
                        preferred_element_type=jnp.float32)
        m = lax.stop_gradient(jnp.max(lf, axis=-1, keepdims=True))
        lse = jnp.squeeze(m, -1) + jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1))
        gold = jnp.sum(lf * jax.nn.one_hot(labels, w.shape[1],
                                           dtype=jnp.float32), axis=-1)
        wmask = loss_mask.astype(jnp.float32)
        return jnp.sum((lse - gold) * wmask), jnp.sum(wmask)

    def f(xl, wl, ll, ml):
        b, s_loc, _ = xl.shape
        v_loc = wl.shape[1]
        v_off = lax.axis_index(h_ax) * v_loc
        nc = n_chunks
        while s_loc % nc:
            nc -= 1
        tc = s_loc // nc
        xs = (xl.reshape(b, nc, tc, -1).transpose(1, 0, 2, 3),
              ll.reshape(b, nc, tc).transpose(1, 0, 2),
              ml.reshape(b, nc, tc).transpose(1, 0, 2))

        n_h = mesh.shape[h_ax]

        def chunk(carry, inp):
            xc, lc, mc = inp
            if overlap != "none":
                # ring AG-matmul over the contracted hidden dim: the per-chunk
                # x gather circulates as collective-permutes hidden behind the
                # per-shard [tc,H/n]@[H/n,V/n] partial matmuls (fp32 accum);
                # "fused" runs the whole chunk ring inside one Pallas kernel.
                lg = OV.ag_matmul_contract(xc, wl, h_ax, n=n_h,
                                           overlap=overlap,
                                           out_dtype=jnp.float32,
                                           mesh_axes=mesh.axis_names,
                                           comm_dtype=comm_dtype)
            else:
                xg = _ag(xc, h_ax, 2)                 # [b, tc, H] (tiny AG)
                lg = jnp.einsum("bth,hv->btv", xg, wl,
                                preferred_element_type=jnp.float32)
            mloc = jnp.max(lg, axis=-1)
            # pmax has no AD rule: gather the per-shard maxima (tiny) instead
            mall = lax.all_gather(lax.stop_gradient(mloc), h_ax, axis=0)
            mglob = jnp.max(mall, axis=0)
            e = jnp.exp(lg - mglob[..., None])
            lse = mglob + jnp.log(lax.psum(jnp.sum(e, axis=-1), h_ax))
            onehot = ((lc[..., None] - v_off)
                      == jnp.arange(v_loc)[None, None, :])
            gold = lax.psum(jnp.sum(lg * onehot, axis=-1), h_ax)
            wm = mc.astype(jnp.float32)
            return carry + jnp.stack([jnp.sum((lse - gold) * wm),
                                      jnp.sum(wm)]), None

        chunk = jax.checkpoint(chunk)                 # recompute logits in bwd
        acc, _ = lax.scan(chunk, jnp.zeros((2,)), xs)
        nll = lax.psum(acc[0], data_axes + (t_ax,))
        cnt = lax.psum(acc[1], data_axes + (t_ax,))
        return nll, cnt

    d = data_axes if len(data_axes) > 1 else data_axes[0]
    return _shard_map(
        f, mesh,
        in_specs=(P(d, t_ax, h_ax), P(None, h_ax), P(d, t_ax), P(d, t_ax)),
        out_specs=(P(), P()),
    )(x, w.astype(x.dtype), labels, loss_mask)


# ---------------------------------------------------------------------------
# Weight / activation PartitionSpecs implied by the method
# ---------------------------------------------------------------------------


def canonical_act_spec(t_ax="mx", h_ax="my", data_axes=("data",)) -> P:
    """[B, T, H] tiling at block boundaries: tokens over t_ax, hidden over h_ax."""
    d = data_axes if len(data_axes) > 1 else data_axes[0]
    return P(d, t_ax, h_ax)


def mixer_act_spec(t_ax="mx", h_ax="my", data_axes=("data",)) -> P:
    """[B, T, Hm] inside a mixer: full sequence, hidden over the whole grid."""
    d = data_axes if len(data_axes) > 1 else data_axes[0]
    return P(d, None, (t_ax, h_ax))


def w_in_spec(t_ax="mx", h_ax="my") -> P:
    """Weight consumed by a canonical-layout input: W[H/h_ax, O/t_ax]."""
    return P(h_ax, t_ax)


def w_swapped_spec(t_ax="mx", h_ax="my") -> P:
    """Weight of the second fused layer (roles swapped): W[H/t_ax, O/h_ax]."""
    return P(t_ax, h_ax)
