"""Megatron-style 1D tensor parallelism — the paper's baseline ("F" in Fig. 8).

Column-parallel then row-parallel linears over a single ``model`` axis.  The
CANONICAL inter-block activation layout is the *sequence-sharded* residual
stream (``ParallelConfig.residual == "seq"``, Korthikanti et al.): between
blocks the [B, S, H] residual lives at P(data, model, None) — tokens sharded
over the model ring — so pre-norm, dropout and the residual add all run on the
local 1/n token shard, and per-die activation memory for the layer scan
shrinks by 1/n.  Column-parallel becomes *gather-at-entry* (the sequence
all-gather fuses into the matmul as a ring AG-matmul under ``overlap``) and
row-parallel becomes *scatter-at-exit* (the output all-reduce is replaced by a
matmul ⊕ reduce-scatter of the sequence dim) — same byte volume as the flat
all-reduce, 2·(n-1)/n per element, but no model-replicated activation ever
materializes between blocks.

``residual == "replicated"`` restores the classic layout (activations
replicated over the model axis between blocks; the row output is all-reduced)
— exactly the property the paper criticizes in §V-A(b): per-device activation
memory does NOT shrink with N, which our memory_analysis dry-runs surface.
Decode (S=1) and sequence extents the model ring cannot divide fall back to
the replicated layout per call.

Overlap (``ParallelConfig.overlap`` != "none"): the baseline's collectives are
ring-decomposed too, so per-mode comparisons against hecaton stay apples to
apples.  In the seq layout the entry gather runs as a ring AG-matmul and the
exit reduce as a ring matmul-RS (core/overlap.py dispatchers — ``"fused"``
routes tile-aligned collective matmuls through the single-kernel Pallas
path); the backwards are the transposed rings, derived automatically by
differentiating through the unrolled ring loops.  In the replicated layout
the row-parallel all-reduce becomes matmul-RS ⊕ ring-AG over the 1D ``model``
ring, and the column-parallel backward's dx all-reduce becomes the transposed
ring via a ``custom_vjp`` (needed there because the replicated operands leave
the model axis unmentioned in the shard_map specs).  Shapes the ring cannot
chunk (hidden extent not divisible by the ring size, multi-axis ``model``
meshes, decode) fall back to the bulk path — the same degradation contract as
the hecaton ops.

The LM loss is fused over sequence shards too (:func:`fused_lm_loss_seq`):
instead of gathering the sequence at the lm_head and bulk-gathering the
sharded labels for a replicated xent, the head's vocab chunks ring over the
model axis while each device online-softmaxes its LOCAL token shard — labels
stay sharded end to end, closing the last block-boundary bulk collective of
the seq residual layout (the ROADMAP megatron leftover).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import overlap as OV
from repro.core import quant as Q
from repro.parallel import sharding as shd


def _einsum(x, w):
    return jnp.einsum("...h,ho->...o", x, w,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def _model_axes(pctx):
    a = pctx.ax
    return a.model_axes if len(a.model_axes) > 1 else a.model_axes[0]


def _dax(pctx):
    a = pctx.ax
    return a.data_axes[0] if len(a.data_axes) == 1 else a.data_axes


def _ring_info(pctx, h_total: int):
    """(axis_name, n) when the 1D model ring can decompose this linear's
    all-reduce (single model axis, ring size > 1, hidden chunks evenly);
    None routes the caller to the bulk path."""
    a = pctx.ax
    if pctx.overlap == "none" or a is None or len(a.model_axes) != 1:
        return None
    ax = a.model_axes[0]
    n = a.size(ax)
    if not OV.rs_ok(h_total, n):
        return None
    return ax, n


def _seq_ring(pctx, seq_len: int):
    """(axis_name, n) when the seq-sharded residual layout applies to this
    projection's sequence extent; None keeps the replicated-residual path
    (decode, non-dividing S, multi-axis model meshes)."""
    a = pctx.ax
    if pctx.residual != "seq" or a is None:
        return None
    if not shd.seq_shardable(a, seq_len):
        return None
    ax = a.model_axes[0]
    return ax, a.size(ax)


def col_parallel(pctx, x, w, interior: bool = False):
    """y = x @ W with W's output dim sharded over the model axes.

    Seq-sharded residual layout (the canonical): x arrives token-sharded
    P(d, model, None) and the sequence is gathered AT ENTRY, fused into the
    matmul as a ring AG-matmul under ``overlap`` (bulk all-gather otherwise);
    the backward's dx reduce-scatter is the transposed ring, for free.

    Replicated layout (or ``interior=True`` for projections that consume a
    mixer-interior full-sequence tensor, e.g. MLA's second q projection):
    forward is communication-free (x model-replicated, W column-sharded);
    under overlap the backward's dx all-reduce runs as the transposed ring
    (matmul-RS ⊕ ring-AG over hidden chunks) instead of a bulk collective.
    """
    if not interior:
        seq = _seq_ring(pctx, x.shape[1])
        if seq is not None:
            return _col_seq(pctx, x, w, seq)
    m, d = _model_axes(pctx), _dax(pctx)
    ring = _ring_info(pctx, x.shape[-1])
    if ring is not None:
        return _col_ring(pctx, x, w, ring)
    x = pctx.constraint(x, P(d, None, None))
    w = pctx.constraint(w, P(None, m))
    y = _einsum(x, w)
    return pctx.constraint(y, P(d, None, m))


def _col_seq(pctx, x, w, ring):
    """Gather-at-entry column parallel: AG the token shard over the model
    ring, fused into the matmul (``overlap`` != none) or bulk (none).

    Unlike the replicated-layout ring, every operand mentions the model axis
    in its shard_map spec (x on the sequence dim, w on the output dim), so
    differentiating straight through the shard_map yields the correct
    transposed ring — transpose(AG-matmul) = matmul-RS — with no custom_vjp.
    """
    ax, n = ring
    d = _dax(pctx)
    mesh, ov = pctx.mesh, pctx.overlap
    cd = pctx.comm_dtype
    x_spec, w_spec, y_spec = P(d, ax, None), P(None, ax), P(d, None, ax)

    def f(xl, wl):
        if ov != "none":
            return OV.ag_matmul(xl, wl, ax, dim=1, n=n, overlap=ov,
                                mesh_axes=mesh.axis_names, comm_dtype=cd)
        xg = lax.all_gather(xl, ax, axis=1, tiled=True)
        return _einsum(xg, wl)

    x = pctx.constraint(x, x_spec)
    return compat.shard_map(f, mesh, (x_spec, w_spec), y_spec)(
        x, w.astype(x.dtype))


def _col_ring(pctx, x, w, ring):
    # The custom_vjp wraps the shard_map calls from OUTSIDE: shard_map's own
    # transpose would conservatively psum cotangents over the unmentioned
    # model axis (check_vma=False), double-counting the ring-reduced dx.
    ax, n = ring
    d = _dax(pctx)
    a = pctx.ax
    mesh = pctx.mesh
    ov = pctx.overlap
    cd = pctx.comm_dtype
    x_spec, w_spec, y_spec = P(d, None, None), P(None, ax), P(d, None, ax)

    @jax.custom_vjp
    def col(xg, wg):
        return compat.shard_map(_einsum, mesh, (x_spec, w_spec),
                                y_spec)(xg, wg)

    def col_fwd(xg, wg):
        return col(xg, wg), (xg, wg)

    def col_bwd(res, dy):
        xg, wg = res

        def fx(dyl, wl):
            # dx = Σ_j dy_j · w_jᵀ: ring reduce over hidden chunks, then ring
            # AG back to the model-replicated layout — the bulk all-reduce's
            # bytes moved entirely as collective-permutes (fused kernel when
            # tile-aligned).
            part = OV.matmul_rs(dyl.astype(wl.dtype), wl.T, ax,
                                scatter_dim=2, n=n, overlap=ov,
                                mesh_axes=mesh.axis_names, comm_dtype=cd)
            return OV.ring_all_gather(part, ax, dim=2, n=n,
                                      bidir=ov == "bidir", comm_dtype=cd)

        def fw(xl, dyl):
            dw = jnp.einsum("bsh,bso->ho", xl, dyl.astype(xl.dtype),
                            preferred_element_type=jnp.float32)
            return lax.psum(dw, a.data_axes) if a.data_axes else dw

        dx = compat.shard_map(fx, mesh, (y_spec, w_spec), x_spec)(dy, wg)
        dw = compat.shard_map(fw, mesh, (x_spec, y_spec), w_spec)(xg, dy)
        return dx.astype(xg.dtype), dw.astype(wg.dtype)

    col.defvjp(col_fwd, col_bwd)
    x = pctx.constraint(x, P(d, None, None))
    return col(x, w.astype(x.dtype))


def col_parallel_shared(pctx, x, ws):
    """Several column-parallel projections of the SAME residual entry (QKV,
    MLA's q/kv down-projections, mamba's z/x), sharing ONE sequence gather.

    Seq layout: one shard_map ring-gathers the token shard once (pure
    ppermute ring under overlap, bulk AG otherwise) and every projection
    reads the gathered xg — entry NoP bytes are 1x instead of len(ws)x.  The
    backward needs only a single reduce-scatter: each dy_i @ w_iᵀ is local
    (w is sharded on its *output* dim), the per-device contributions sum at
    xg, and transpose(ring-AG) reduce-scatters them back to the token shard.
    Other layouts fall back to per-weight :func:`col_parallel`."""
    seq = _seq_ring(pctx, x.shape[1])
    if seq is None or len(ws) == 1:
        return tuple(col_parallel(pctx, x, w) for w in ws)
    ax, n = seq
    d = _dax(pctx)
    mesh, ov = pctx.mesh, pctx.overlap
    cd = pctx.comm_dtype
    x_spec, w_spec, y_spec = P(d, ax, None), P(None, ax), P(d, None, ax)

    def f(xl, *wls):
        if ov != "none":
            xg = OV.ring_all_gather(xl, ax, dim=1, n=n, bidir=ov == "bidir",
                                    comm_dtype=cd)
        else:
            xg = lax.all_gather(xl, ax, axis=1, tiled=True)
        return tuple(_einsum(xg, wl) for wl in wls)

    x = pctx.constraint(x, x_spec)
    return compat.shard_map(f, mesh, (x_spec,) + (w_spec,) * len(ws),
                            (y_spec,) * len(ws))(
        x, *[w.astype(x.dtype) for w in ws])


def row_parallel(pctx, y, w):
    """out = y @ W with W's input dim sharded; partial outputs reduced.

    Seq-sharded residual layout (the canonical): the model-axis reduction is a
    *scatter-at-exit* — matmul ⊕ reduce-scatter of the sequence dim (ring
    matmul-RS under ``overlap``), returning the residual token-sharded
    P(d, model, None).  Half the bulk all-reduce's exit bytes, and no
    model-replicated [B, S, H] is ever materialized.

    Replicated layout: output all-reduced to replicated.  Under overlap the
    all-reduce is decomposed into matmul-RS (contribution tiles folded into a
    circulating accumulator) followed by a ring all-gather of the reduced
    hidden chunks; the backward is local."""
    seq = _seq_ring(pctx, y.shape[1])
    if seq is not None:
        return _row_seq(pctx, y, w, seq)
    m, d = _model_axes(pctx), _dax(pctx)
    ring = _ring_info(pctx, w.shape[-1])
    if ring is not None:
        return _row_ring(pctx, y, w, ring)
    y = pctx.constraint(y, P(d, None, m))
    w = pctx.constraint(w, P(m, None))
    out = _einsum(y, w)
    # constraining to model-replicated forces GSPMD's all-reduce (flat ring on ICI)
    return pctx.constraint(out, P(d, None, None))


def _row_seq(pctx, y, w, ring):
    """Scatter-at-exit row parallel: the partial-sum reduction over the model
    ring reduce-scatters the SEQUENCE dim, restoring the token-sharded
    residual.  transpose(matmul-RS) = AG-matmul, so the backward re-gathers
    the cotangent sequence as a ring too — all differentiate-through."""
    ax, n = ring
    d = _dax(pctx)
    mesh, ov = pctx.mesh, pctx.overlap
    cd = pctx.comm_dtype
    y_spec, w_spec, o_spec = P(d, None, ax), P(ax, None), P(d, ax, None)

    def f(yl, wl):
        if ov != "none" and OV.rs_ok(yl.shape[1], n):
            return OV.matmul_rs(yl, wl, ax, scatter_dim=1, n=n, overlap=ov,
                                mesh_axes=mesh.axis_names, comm_dtype=cd)
        return lax.psum_scatter(_einsum(yl, wl), ax, scatter_dimension=1,
                                tiled=True)

    y = pctx.constraint(y, y_spec)
    return compat.shard_map(f, mesh, (y_spec, w_spec), o_spec)(
        y, w.astype(y.dtype))


def _row_ring(pctx, y, w, ring):
    ax, n = ring
    d = _dax(pctx)
    a = pctx.ax
    mesh = pctx.mesh
    ov = pctx.overlap
    cd = pctx.comm_dtype
    y_spec, w_spec, o_spec = P(d, None, ax), P(ax, None), P(d, None, None)

    @jax.custom_vjp
    def row(yg, wg):
        def f(yl, wl):
            part = OV.matmul_rs(yl, wl, ax, scatter_dim=2, n=n, overlap=ov,
                                mesh_axes=mesh.axis_names, comm_dtype=cd)
            return OV.ring_all_gather(part, ax, dim=2, n=n,
                                      bidir=ov == "bidir", comm_dtype=cd)
        return compat.shard_map(f, mesh, (y_spec, w_spec), o_spec)(yg, wg)

    def row_fwd(yg, wg):
        return row(yg, wg), (yg, wg)

    def row_bwd(res, dout):
        # dout is model-replicated and w row-sharded ⇒ backward is comm-free
        # on the model axis (the bulk path pays nothing here either).
        yg, wg = res

        def fy(doutl, wl):
            return jnp.einsum("bsh,fh->bsf", doutl.astype(wl.dtype), wl,
                              preferred_element_type=jnp.float32)

        def fw(yl, doutl):
            dw = jnp.einsum("bsf,bsh->fh", yl, doutl.astype(yl.dtype),
                            preferred_element_type=jnp.float32)
            return lax.psum(dw, a.data_axes) if a.data_axes else dw

        dy = compat.shard_map(fy, mesh, (o_spec, w_spec), y_spec)(dout, wg)
        dw = compat.shard_map(fw, mesh, (y_spec, o_spec), w_spec)(yg, dout)
        return dy.astype(yg.dtype), dw.astype(wg.dtype)

    row.defvjp(row_fwd, row_bwd)
    y = pctx.constraint(y, P(d, None, ax))
    return row(y, w.astype(y.dtype))


def seq_loss_ok(pctx, seq_len: int, vocab: int) -> bool:
    """Gate for :func:`fused_lm_loss_seq`: the seq-sharded residual layout
    must apply to this sequence extent AND the (padded) vocab must chunk
    evenly over the model ring so the circulating head-weight shards stay
    equal-sized."""
    seq = _seq_ring(pctx, seq_len)
    if seq is None:
        return False
    _, n = seq
    return n > 1 and vocab % n == 0


def fused_lm_loss_seq(pctx, x, w, labels, loss_mask):
    """Sequence-sharded fused LM loss for the megatron baseline — labels (and
    the final-norm hidden) never leave their token shard.

    The classic path gathers the sequence at the lm_head (col_parallel) and
    bulk-gathers the sharded int32 labels for the replicated xent — the last
    block-boundary bulk collective left in the seq residual layout (ROADMAP
    megatron leftover).  Here each device keeps its LOCAL token shard
    x [B, S/n, H] and its LOCAL vocab shard of the head W [H, V/n], and the
    ring circulates the *weight* chunks instead: at step k a device holds
    vocab chunk (i+k) mod n, folds the partial logits into an online-softmax
    accumulator (running max / sum-exp, hecaton's fused_lm_loss trick), picks
    up the gold logit when the label lands in the current chunk's vocab
    range, and ppermutes the chunk onward.  After n steps every token has its
    full-vocab lse and gold without any [tokens, V] logits, sequence gather,
    or label gather materializing — the HLO carries only collective-permutes
    (asserted by tests/test_overlap.py + the CI residual smoke check).  The
    backward differentiates through the unrolled ring (operands all mention
    the model axis, as in ``_col_seq``), so transpose(w-ring) is the reversed
    w-ring and dx stays token-sharded.

    Returns (masked NLL sum, mask count) as replicated scalars — the caller
    divides.  Callers must check :func:`seq_loss_ok` first.
    """
    ax, n = _seq_ring(pctx, x.shape[1])
    d = _dax(pctx)
    mesh = pctx.mesh
    cd = pctx.comm_dtype
    if loss_mask is None:
        loss_mask = jnp.ones(labels.shape, jnp.float32)
    data_axes = pctx.ax.data_axes

    def f(xl, wl, ll, ml):
        v_loc = wl.shape[1]
        b, s_loc, _ = xl.shape
        i = lax.axis_index(ax)

        def body(carry, k):
            m_run, s_run, gold, wk = carry
            lg = jnp.einsum("bth,hv->btv", xl, wk,
                            preferred_element_type=jnp.float32)
            v_off = ((i + k) % n) * v_loc
            mloc = lax.stop_gradient(jnp.max(lg, axis=-1))
            new_m = jnp.maximum(m_run, mloc)
            s_run = (s_run * jnp.exp(m_run - new_m)
                     + jnp.sum(jnp.exp(lg - new_m[..., None]), axis=-1))
            onehot = ((ll[..., None] - v_off)
                      == jnp.arange(v_loc)[None, None, :])
            gold = gold + jnp.sum(lg * onehot, axis=-1)
            # the circulating head-weight chunk rides the same quantized
            # wire as the activation rings (trailing dim is V/n >= 16)
            wk = Q.ring_hop(wk, ax, n, shift=-1, comm_dtype=cd)
            return (new_m, s_run, gold, wk), None

        body = jax.checkpoint(body)          # recompute the logits in bwd
        # -1e30 (not -inf): new_m at step 0 equals mloc, and a finite floor
        # keeps exp(m_run - new_m) free of inf-inf NaNs under AD
        init = (jnp.full((b, s_loc), -1e30, jnp.float32),
                jnp.zeros((b, s_loc), jnp.float32),
                jnp.zeros((b, s_loc), jnp.float32),
                wl)
        (m_run, s_run, gold, _), _ = lax.scan(body, init, jnp.arange(n))
        lse = m_run + jnp.log(s_run)
        wm = ml.astype(jnp.float32)
        axes = data_axes + (ax,)
        return (lax.psum(jnp.sum((lse - gold) * wm), axes),
                lax.psum(jnp.sum(wm), axes))

    x_spec = P(d, ax, None)
    l_spec = P(d, ax)
    return compat.shard_map(
        f, mesh, (x_spec, P(None, ax), l_spec, l_spec), (P(), P()))(
        pctx.constraint(x, x_spec), w.astype(x.dtype),
        pctx.constraint(labels, l_spec),
        pctx.constraint(loss_mask.astype(jnp.float32), l_spec))


def ffn(pctx, x, w1, w2, act_fn, w1b=None):
    """Column→row FFN.  Seq layout runs the whole block in ONE shard_map so
    the gated variant's two up-projections share a single entry gather of the
    token shard (zero extra communication for the gate — the same layer-fusion
    property hecaton's ffn_block has)."""
    seq = _seq_ring(pctx, x.shape[1])
    if seq is not None:
        return _ffn_seq(pctx, x, w1, w2, act_fn, w1b, seq)
    h = col_parallel(pctx, x, w1)
    if w1b is not None:
        h = act_fn(h) * col_parallel(pctx, x, w1b)
    else:
        h = act_fn(h)
    return row_parallel(pctx, h, w2)


def _ffn_seq(pctx, x, w1, w2, act_fn, w1b, ring):
    """Seq-sharded FFN: entry AG (ring, shared by the gated pair) → local
    column matmuls → exit matmul-RS of the sequence dim.  One gather + one
    scatter per block, both collective-permute chains under overlap."""
    ax, n = ring
    d = _dax(pctx)
    mesh, ov = pctx.mesh, pctx.overlap
    cd = pctx.comm_dtype

    def f(xl, w1l, w2l, *rest):
        bidir = ov == "bidir"
        if rest:                                   # gated: share the gathered x
            if ov != "none":
                xg = OV.ring_all_gather(xl, ax, dim=1, n=n, bidir=bidir,
                                        comm_dtype=cd)
            else:
                xg = lax.all_gather(xl, ax, axis=1, tiled=True)
            h = act_fn(_einsum(xg, w1l)) * _einsum(xg, rest[0])
        elif ov != "none":
            h = act_fn(OV.ag_matmul(xl, w1l, ax, dim=1, n=n, overlap=ov,
                                    mesh_axes=mesh.axis_names, comm_dtype=cd))
        else:
            xg = lax.all_gather(xl, ax, axis=1, tiled=True)
            h = act_fn(_einsum(xg, w1l))
        if ov != "none" and OV.rs_ok(h.shape[1], n):
            return OV.matmul_rs(h, w2l, ax, scatter_dim=1, n=n, overlap=ov,
                                mesh_axes=mesh.axis_names, comm_dtype=cd)
        return lax.psum_scatter(_einsum(h, w2l), ax, scatter_dimension=1,
                                tiled=True)

    x_spec = P(d, ax, None)
    in_specs = [x_spec, P(None, ax), P(ax, None)]
    args = [pctx.constraint(x, x_spec), w1.astype(x.dtype), w2.astype(x.dtype)]
    if w1b is not None:
        in_specs.append(P(None, ax))
        args.append(w1b.astype(x.dtype))
    return compat.shard_map(f, mesh, tuple(in_specs), x_spec)(*args)
