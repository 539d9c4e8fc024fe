"""MLP / MoE blocks.

Dense MLPs run the Hecaton fused-FFN dataflow (core/hecaton.ffn_block).

MoE uses an EP×TP hybrid (docs/DESIGN.md §4): experts sharded over the grid's ``mx``
axis, each expert's FFN width sharded over ``my``; tokens are dispatched locally by
an argsort-based capacity router (gather/scatter-add, fully differentiable).  The
only collectives are an all-gather of the (hidden-sharded) input and a
reduce-scatter of the combined output — the same AG/RS-only property as the paper's
dense method, so MoE inherits the complexity bound.

With ``ParallelConfig.overlap`` != "none" those EP/TP gathers and scatters run
as ``lax.ppermute`` rings (core/overlap.py): the input gathers become ring
all-gathers and the two output reduce-scatters become circulating-accumulator
rings, so the MoE path has zero bulk AG/RS in its HLO just like the dense hot
path.  (The expert compute between them is gather/scatter-add dispatch, not a
single matmul, so the ``fused`` single-kernel mode contributes its ring
decomposition here rather than a fused matmul; extents a ring cannot chunk
fall back to the bulk collective per collective, as everywhere else.)
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.config import ModelConfig
from repro.core import overlap as OV
from repro.models import layers as L


def init_mlp(cfg: ModelConfig, key):
    H, F = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"w1": L.normal_init(ks[0], (H, F)),
         "w2": L.normal_init(ks[1], (F, H), scale=1.0 / F ** 0.5)}
    if L.GATED[cfg.mlp_kind]:
        p["w1b"] = L.normal_init(ks[2], (H, F))
    return p


@jax.named_scope("ffn")
def apply_mlp(pctx, cfg: ModelConfig, p, x):
    act = L.ACTIVATIONS[cfg.mlp_kind]
    return pctx.ffn(x, p["w1"], p["w2"], act, p.get("w1b"))


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def init_moe(cfg: ModelConfig, key):
    H, F, E = cfg.d_model, cfg.d_ff, cfg.moe.num_experts
    ks = jax.random.split(key, 4)
    p = {"router": L.normal_init(ks[0], (H, E), scale=0.02),
         "we1": L.normal_init(ks[1], (E, H, F)),
         "we2": L.normal_init(ks[2], (E, F, H), scale=1.0 / F ** 0.5)}
    if L.GATED[cfg.mlp_kind]:
        p["we1b"] = L.normal_init(ks[3], (E, H, F))
    return p


def _dispatch_indices(expert_of, n_local_experts: int, e_offset, capacity: int):
    """Argsort-based capacity dispatch for flattened (token,slot) assignments.

    expert_of: [A] global expert id per assignment (A = T * top_k).
    Returns (slot_token [E_loc, C] source assignment index, slot_valid [E_loc, C]).
    """
    A = expert_of.shape[0]
    local_e = expert_of - e_offset
    in_range = (local_e >= 0) & (local_e < n_local_experts)
    sort_key = jnp.where(in_range, local_e, n_local_experts)      # invalid last
    order = jnp.argsort(sort_key)                                 # stable
    sorted_e = sort_key[order]
    # position within its expert group
    pos = jnp.arange(A) - jnp.searchsorted(sorted_e, sorted_e, side="left")
    valid = (sorted_e < n_local_experts) & (pos < capacity)
    slot = jnp.where(valid, sorted_e * capacity + pos, n_local_experts * capacity)
    slot_token = jnp.full((n_local_experts * capacity + 1,), A, jnp.int32)
    slot_token = slot_token.at[slot].set(order.astype(jnp.int32), mode="drop")
    return slot_token[:-1].reshape(n_local_experts, capacity)


def _moe_local(p, x, *, cfg: ModelConfig, n_local_experts: int, e_offset,
               compute_dtype):
    """MoE over local tokens x [T, H] with experts [e_offset, e_offset+n_local).

    Returns (y [T,H] partial over expert shards, router_probs [T,E]).
    """
    mc = cfg.moe
    T, H = x.shape
    E, k = mc.num_experts, mc.top_k
    logits = jnp.einsum("th,he->te", x, p["router"],
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = lax.top_k(probs, k)                              # [T,k]
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    expert_of = idx.reshape(-1)                                   # [T*k]
    gates_flat = gate.reshape(-1)
    cap = max(1, int(k * T * mc.capacity_factor / E))
    slot_token = _dispatch_indices(expert_of, n_local_experts, e_offset, cap)
    tok_of_slot = jnp.minimum(slot_token // k, T - 1)
    slot_valid = slot_token < T * k

    xd = x[tok_of_slot] * slot_valid[..., None].astype(x.dtype)   # [E_loc,C,H]
    w1 = lax.dynamic_slice_in_dim(p["we1"], e_offset, n_local_experts, 0) \
        if p["we1"].shape[0] != n_local_experts else p["we1"]
    w2 = lax.dynamic_slice_in_dim(p["we2"], e_offset, n_local_experts, 0) \
        if p["we2"].shape[0] != n_local_experts else p["we2"]
    h = jnp.einsum("ech,ehf->ecf", xd, w1.astype(xd.dtype),
                   preferred_element_type=jnp.float32).astype(compute_dtype)
    act = L.ACTIVATIONS[cfg.mlp_kind]
    if "we1b" in p:
        w1b = lax.dynamic_slice_in_dim(p["we1b"], e_offset, n_local_experts, 0) \
            if p["we1b"].shape[0] != n_local_experts else p["we1b"]
        h = act(h) * jnp.einsum("ech,ehf->ecf", xd, w1b.astype(xd.dtype),
                                preferred_element_type=jnp.float32
                                ).astype(compute_dtype)
    else:
        h = act(h)
    yd = jnp.einsum("ecf,efh->ech", h, w2.astype(h.dtype),
                    preferred_element_type=jnp.float32).astype(compute_dtype)
    gd = gates_flat[slot_token.reshape(-1)] * slot_valid.reshape(-1)
    yd = yd.reshape(-1, H) * gd[:, None].astype(yd.dtype)
    y = jnp.zeros((T + 1, H), yd.dtype).at[
        jnp.minimum(tok_of_slot.reshape(-1), T)].add(
            yd, mode="drop")[:T]
    return y, probs


def moe_aux_losses(probs, idx_onehot_mean=None):
    """Load-balance + z-style losses from router probabilities [T,E]."""
    E = probs.shape[-1]
    me = jnp.mean(probs, axis=0)
    # fraction routed (approximated by prob mass argmax-free, Switch-style)
    return E * jnp.sum(me * me)


@jax.named_scope("ffn")
def apply_moe(pctx, cfg: ModelConfig, p, x):
    """x [B,S,H] canonical -> y canonical (+ aux loss scalar)."""
    mc = cfg.moe
    B, S, H = x.shape
    mesh = pctx.mesh
    if mesh is None or not pctx.use_hecaton:
        # single-device / megatron fallback: experts unsharded (megatron shards
        # handled by GSPMD through the einsums via constraints)
        y, probs = _moe_local(p, x.reshape(-1, H), cfg=cfg,
                              n_local_experts=mc.num_experts, e_offset=0,
                              compute_dtype=x.dtype)
        return y.reshape(B, S, H), moe_aux_losses(probs)

    a = pctx.ax
    ep_ax, tp_ax = a.t_ax, a.h_ax           # experts over mx, ffn width over my
    n_ep, n_tp = a.size(ep_ax), a.size(tp_ax)
    n_loc = mc.num_experts // n_ep
    dspec = a.data_axes if len(a.data_axes) > 1 else a.data_axes[0]
    all_axes = a.data_axes + (ep_ax, tp_ax)
    ov = pctx.overlap
    bidir = ov == "bidir"

    def f(xl, router, w1, w2, *rest):
        # xl [b, s_loc, H/my].  Gather hidden (full H for routing) AND sequence
        # (every expert shard must see every token of its data shard) — the
        # mixer-pattern gathers, after which expert compute is comm-free.
        # With overlap enabled both gathers (and the reduce-scatters below)
        # run as ppermute rings instead of bulk collectives.
        if ov != "none":
            xg = OV.ring_all_gather(xl, tp_ax, dim=2, n=n_tp, bidir=bidir)
            xg = OV.ring_all_gather(xg, ep_ax, dim=1, n=n_ep, bidir=bidir)
        else:
            xg = lax.all_gather(xl, tp_ax, axis=2, tiled=True)   # [b,s_loc,H]
            xg = lax.all_gather(xg, ep_ax, axis=1, tiled=True)   # [b,S,H]
        b, S, H = xg.shape
        e_off = lax.axis_index(ep_ax) * n_loc
        pl = {"router": router, "we1": w1, "we2": w2}
        if rest:
            pl["we1b"] = rest[0]
        y, probs = _moe_local(pl, xg.reshape(b * S, H), cfg=cfg,
                              n_local_experts=n_loc, e_offset=e_off,
                              compute_dtype=xl.dtype)
        # y [T,H] is partial over ep_ax (expert subsets) and tp_ax (F-contraction
        # partials): two reduce-scatters complete the sums and restore the
        # canonical tiling (tokens over mx, hidden over my).  The token scatter
        # must split the SEQUENCE dim per batch row — not the flattened (b*S)
        # dim, which would hand whole batch rows to different shards.
        y = y.reshape(b, S, H)
        if ov != "none" and OV.rs_ok(S, n_ep):
            y = OV.ring_reduce_scatter(y, ep_ax, dim=1, n=n_ep, bidir=bidir)
        else:
            y = lax.psum_scatter(y, ep_ax, scatter_dimension=1, tiled=True)
        if ov != "none" and OV.rs_ok(H, n_tp):
            y = OV.ring_reduce_scatter(y, tp_ax, dim=2, n=n_tp, bidir=bidir)
        else:
            y = lax.psum_scatter(y, tp_ax, scatter_dimension=2, tiled=True)
        aux = lax.pmean(moe_aux_losses(probs), all_axes)
        return y, aux

    in_specs = [P(dspec, a.t_ax, a.h_ax), P(),
                P(ep_ax, None, tp_ax), P(ep_ax, tp_ax, None)]
    # cast expert weights to activation dtype BEFORE the shard_map boundary so
    # any FSDP gather moves bf16, not fp32 (Perf iteration 1)
    args = [x, p["router"], p["we1"].astype(x.dtype), p["we2"].astype(x.dtype)]
    if "we1b" in p:
        in_specs.append(P(ep_ax, None, tp_ax))
        args.append(p["we1b"].astype(x.dtype))
    y, aux = compat.shard_map(
        f, mesh, tuple(in_specs),
        (P(dspec, a.t_ax, a.h_ax), P()))(*args)
    return y, aux
