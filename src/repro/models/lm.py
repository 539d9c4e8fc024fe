"""LM assembly: decoder-only / SSM / hybrid / encoder-decoder language models.

Layers are stacked ([L, ...] param arrays) and applied with ``lax.scan`` so the
compiled HLO is depth-independent — essential for dry-running 96-layer models.
Remat (core/schedule.py policies) wraps the scan body.

Sharding: all projections route through PCtx (Hecaton Alg. 1 or the Megatron
baseline); embeddings / norms / loss are jit-level ops under GSPMD constraints.
The residual stream stays in the canonical seq-sharded layout
(``ParallelConfig.residual``) across the whole layer scan: embedding output,
dropout, pre-norms, residual adds and the final norm all run on the local
token shard, so no block boundary carries a bulk collective.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.config import ModelConfig
from repro.core import schedule
from repro.models import attention as ATT
from repro.models import blocks as BLK
from repro.models import layers as L
from repro.models import ssm as SSM


# ---------------------------------------------------------------------------
# parameter counting (MODEL_FLOPS = 6*N*D uses these)
# ---------------------------------------------------------------------------

def _attn_params(cfg: ModelConfig) -> int:
    H, dh = cfg.d_model, cfg.resolved_head_dim
    if cfg.mla:
        m = cfg.mla
        dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
        return (H * m.q_lora_rank + m.q_lora_rank * cfg.num_heads * (dn + dr)
                + H * (m.kv_lora_rank + dr)
                + m.kv_lora_rank * cfg.num_heads * (dn + dv)
                + cfg.num_heads * dv * H)
    return (H * cfg.num_heads * dh + 2 * H * cfg.num_kv_heads * dh
            + cfg.num_heads * dh * H)


def _mlp_params(cfg: ModelConfig, active_only: bool) -> int:
    H, F = cfg.d_model, cfg.d_ff
    per = (3 if L.GATED[cfg.mlp_kind] else 2) * H * F
    if cfg.moe:
        E = cfg.moe.num_experts
        n = cfg.moe.top_k if active_only else E
        return per * n + H * E
    return per


def _mamba_params(cfg: ModelConfig) -> int:
    H, Di = cfg.d_model, SSM.d_inner(cfg)
    gs = cfg.ssm.n_groups * cfg.ssm.state_dim
    return (2 * H * Di + 2 * H * gs + H * SSM.n_heads(cfg)
            + cfg.ssm.conv_kernel * SSM.conv_channels(cfg) + Di + Di * H)


def count_params_analytic(cfg: ModelConfig, active_only: bool = False) -> int:
    H = cfg.d_model
    emb = cfg.vocab_size * H * (1 if cfg.tie_embeddings else 2)
    total = emb
    if cfg.family == "hybrid":
        Lm = cfg.num_layers
        total += Lm * (_mamba_params(cfg) + 2 * H)
        per_attn = _attn_params(cfg) + _mlp_params(cfg, active_only) + 4 * H
        every = max(1, cfg.shared_attn_every)
        n_apps = Lm // every
        n_sets = max(1, cfg.num_shared_attn_sets)
        total += (n_apps if active_only else n_sets) * per_attn
        return total
    if cfg.family == "ssm":
        return total + cfg.num_layers * (_mamba_params(cfg) + 2 * H)
    per_block = _attn_params(cfg) + _mlp_params(cfg, active_only) + 4 * H
    n_layers = cfg.num_layers + cfg.encoder_layers
    if cfg.is_encdec:   # decoder blocks also carry cross-attention
        per_cross = _attn_params(cfg) + 2 * H
        return total + cfg.encoder_layers * per_block + \
            cfg.num_layers * (per_block + per_cross)
    return total + n_layers * per_block


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    ks = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": L.init_embed(ks[0], cfg.padded_vocab, cfg.d_model),
        "final_norm": L.init_norm(cfg.norm_kind, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": L.normal_init(ks[1], (cfg.d_model, cfg.padded_vocab),
                                                scale=0.02)}
    fam = cfg.family
    if fam == "ssm":
        params["blocks"] = BLK.init_stacked(
            lambda k: BLK.init_mamba_block(cfg, k), cfg.num_layers, ks[2])
    elif fam == "hybrid":
        params["blocks"] = {
            "mamba": BLK.init_stacked(
                lambda k: BLK.init_mamba_block(cfg, k), cfg.num_layers, ks[2]),
            "shared": BLK.init_stacked(
                lambda k: BLK.init_attn_block(cfg, k),
                max(1, cfg.num_shared_attn_sets), ks[3]),
        }
    elif cfg.is_encdec:
        params["encoder"] = BLK.init_stacked(
            lambda k: BLK.init_attn_block(cfg, k), cfg.encoder_layers, ks[2])
        params["blocks"] = BLK.init_stacked(
            lambda k: BLK.init_attn_block(cfg, k, cross=True), cfg.num_layers, ks[3])
        params["enc_norm"] = L.init_norm(cfg.norm_kind, cfg.d_model)
    else:
        params["blocks"] = BLK.init_stacked(
            lambda k: BLK.init_attn_block(cfg, k), cfg.num_layers, ks[2])
    return params


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch: int, s_max: int, dtype):
    """Stacked per-layer decode caches (dense layout).

    Cache layout now lives in ``repro.serve.cache`` (docs/DESIGN.md §10);
    this delegates to the dense factory there so training-side callers are
    unchanged.  Lazy import: serve.cache imports the model modules."""
    from repro.serve import cache as CM
    return CM.init_dense(cfg, batch, s_max, dtype)


def cache_length(caches) -> jax.Array:
    if "attn" in caches:
        return jax.tree.leaves(caches["attn"])[-1].reshape(-1)[0]
    return jax.tree.leaves(caches)[0].shape[0] * 0   # ssm: caller tracks position


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

class LMOut(NamedTuple):
    logits: Any
    aux: jax.Array
    caches: Any
    hidden: Any = None


def _scan_attn_stack(pctx, cfg, stacked, x, *, positions, layout, causal,
                     caches, memory, remat: str):
    """Uniform attention stack via scan; caches may be None."""

    def body(carry, xs):
        x, aux = carry
        if caches is None and memory is None:
            p_l = xs
            cache_l, mem_kv = None, None
        elif memory is not None and caches is None:
            p_l = xs
            mem_kv = ATT.cross_kv(pctx, cfg, p_l["xattn"], memory)
            cache_l = None
        elif memory is None:
            p_l, cache_l = xs
            mem_kv = None
        else:
            p_l, cache_l, mem_kv = xs
        x, new_cache, aux_l = BLK.apply_attn_block(
            pctx, cfg, p_l, x, positions=positions, layout=layout,
            causal=causal, cache=cache_l, memory_kv=mem_kv)
        out = new_cache if new_cache is not None else 0
        return (x, aux + aux_l), out

    body = schedule.apply_remat(body, remat)
    if caches is None and memory is None:
        xs = stacked
    elif memory is not None and caches is None:
        xs = stacked
    elif memory is None:
        xs = (stacked, caches)
    else:
        xs = (stacked, caches, memory)
    (x, aux), new_caches = lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
    return x, aux, (new_caches if caches is not None else None)


def _scan_mamba_stack(pctx, cfg, stacked, x, *, layout, states, remat: str):
    def body(carry, xs):
        x = carry
        if states is None:
            p_l, st_l = xs, None
        else:
            p_l, st_l = xs
        x, new_st = BLK.apply_mamba_block(pctx, cfg, p_l, x, layout=layout,
                                          state=st_l)
        return x, (new_st if new_st is not None else 0)

    body = schedule.apply_remat(body, remat)
    xs = stacked if states is None else (stacked, states)
    x, new_states = lax.scan(body, x, xs)
    return x, (new_states if states is not None else None)


def _hybrid_forward(pctx, cfg, params, x, *, positions, layouts, caches, remat):
    """zamba2: groups of `every` mamba blocks + a shared-params attention block."""
    every = max(1, cfg.shared_attn_every)
    Lm = cfg.num_layers
    G = Lm // every
    tail = Lm % every
    n_sets = max(1, cfg.num_shared_attn_sets)
    mparams = params["blocks"]["mamba"]
    shared = params["blocks"]["shared"]
    m_lay, a_lay = layouts

    main = jax.tree.map(lambda a: a[:G * every].reshape(G, every, *a.shape[1:]),
                        mparams)
    m_states = None if caches is None else caches["mamba"]
    main_states = None if m_states is None else jax.tree.map(
        lambda a: a[:G * every].reshape(G, every, *a.shape[1:]), m_states)
    a_caches = None if caches is None else caches["attn"]
    aux0 = jnp.zeros((), jnp.float32)

    def group_body(carry, xs):
        x, aux = carry
        if caches is None:
            p_g, gi = xs
            st_g, kv_g = None, None
        else:
            p_g, st_g, kv_g, gi = xs
        x, new_st = _scan_mamba_stack(pctx, cfg, p_g, x, layout=m_lay,
                                      states=st_g, remat="none")
        sel = jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, gi % n_sets, 0, keepdims=False),
            shared)
        x, new_kv, aux_l = BLK.apply_attn_block(
            pctx, cfg, sel, x, positions=positions, layout=a_lay, causal=True,
            cache=kv_g)
        outs = (new_st if new_st is not None else 0,
                new_kv if new_kv is not None else 0)
        return (x, aux + aux_l), outs

    group_body = schedule.apply_remat(group_body, remat)
    gi = jnp.arange(G)
    xs = (main, gi) if caches is None else (main, main_states, a_caches, gi)
    (x, aux), (new_m, new_kv) = lax.scan(group_body, (x, aux0), xs)

    new_caches = None
    tail_states = None if m_states is None else jax.tree.map(
        lambda a: a[G * every:], m_states)
    if tail:
        tail_p = jax.tree.map(lambda a: a[G * every:], mparams)
        x, new_tail = _scan_mamba_stack(pctx, cfg, tail_p, x, layout=m_lay,
                                        states=tail_states, remat=remat)
    else:
        new_tail = tail_states
    if caches is not None:
        flat_m = jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), new_m)
        if tail:
            merged = jax.tree.map(lambda a, b: jnp.concatenate([a, b], 0),
                                  flat_m, new_tail)
        else:
            merged = flat_m
        new_caches = {"mamba": merged, "attn": new_kv}
    return x, aux, new_caches


def forward(pctx, cfg: ModelConfig, params, batch: Dict[str, jax.Array], *,
            caches=None, remat: str = "none", skip_head: bool = False) -> LMOut:
    """batch: tokens [B,S] (+ patches/frames for vlm/audio, positions optional)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    compute_dtype = batch.get("_dtype", jnp.bfloat16)
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    x = pctx.embed(params["embed"]["table"], tokens, compute_dtype)
    if cfg.family == "vlm" and "patches" in batch:
        P_len = batch["patches"].shape[1]
        pad = jnp.zeros((B, S - P_len, cfg.d_model), compute_dtype)
        patches_full = jnp.concatenate(
            [batch["patches"].astype(compute_dtype), pad], axis=1)
        is_prefix = (positions < P_len)[..., None]
        x = jnp.where(is_prefix, patches_full, x)
    x = pctx.canon(x)
    if cfg.embed_dropout and pctx.mode == "train":
        # shard-local: the mask is drawn on the canonical (seq-sharded)
        # residual, so no replicated [B,S,H] ever materializes
        x = pctx.dropout(x, cfg.embed_dropout, batch.get("dropout_rng"))

    layout = pctx.attn_layout(cfg.num_heads, B)   # B here is the global batch
    aux = jnp.zeros((), jnp.float32)
    new_caches = None

    if cfg.family == "ssm":
        states = None if caches is None else caches["mamba"]
        x, new_states = _scan_mamba_stack(pctx, cfg, params["blocks"], x,
                                          layout=layout, states=states,
                                          remat=remat)
        if caches is not None:
            new_caches = {"mamba": new_states}
    elif cfg.family == "hybrid":
        m_layout = pctx.attn_layout(SSM.n_heads(cfg), B)
        x, aux, new_caches = _hybrid_forward(
            pctx, cfg, params, x, positions=positions,
            layouts=(m_layout, layout), caches=caches, remat=remat)
    elif cfg.is_encdec:
        if caches is None:
            frames = batch["frames"].astype(compute_dtype)
            Bf, Fl, _ = frames.shape
            fpos = jnp.broadcast_to(jnp.arange(Fl, dtype=jnp.int32)[None],
                                    (Bf, Fl))
            mem = pctx.canon(frames)
            mem, _, _ = _scan_attn_stack(pctx, cfg, params["encoder"], mem,
                                         positions=fpos, layout=layout,
                                         causal=cfg.encoder_is_causal, caches=None,
                                         memory=None, remat=remat)
            mem = pctx.norm(cfg.norm_kind, params["enc_norm"], mem)
            x, aux, _ = _scan_attn_stack(pctx, cfg, params["blocks"], x,
                                         positions=positions, layout=layout,
                                         causal=True, caches=None, memory=mem,
                                         remat=remat)
        else:
            x, aux, attn_c = _scan_attn_stack(
                pctx, cfg, params["blocks"], x, positions=positions,
                layout=layout, causal=True, caches=caches["attn"],
                memory=caches["cross"], remat="none")
            new_caches = {"attn": attn_c, "cross": caches["cross"]}
    else:
        x, aux, attn_c = _scan_attn_stack(pctx, cfg, params["blocks"], x,
                                          positions=positions, layout=layout,
                                          causal=True, caches=caches and
                                          caches["attn"], memory=None,
                                          remat=remat)
        if caches is not None:
            new_caches = {"attn": attn_c}

    x = pctx.norm(cfg.norm_kind, params["final_norm"], x)
    if skip_head:
        return LMOut(None, aux, new_caches, hidden=x)
    head_w = (params["embed"]["table"].T.astype(compute_dtype)
              if cfg.tie_embeddings else
              params["lm_head"]["w"].astype(compute_dtype))
    logits = pctx.lm_head(x.astype(compute_dtype), head_w)
    logits = pctx.constraint(logits, pctx.logits_spec())
    return LMOut(logits, aux, new_caches)


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def xent_loss(pctx, logits, labels, loss_mask=None):
    """Stable softmax cross-entropy over (possibly vocab-sharded) logits.

    Uses the one-hot-contraction form so vocab-dim reductions lower to psum over
    vocab shards under GSPMD (no gather from a sharded axis).
    """
    lf = logits.astype(jnp.float32)
    m = lax.stop_gradient(jnp.max(lf, axis=-1, keepdims=True))
    lse = jnp.squeeze(m, -1) + jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1))
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    gold = jnp.sum(lf * onehot, axis=-1)
    nll = lse - gold
    if loss_mask is None:
        return jnp.mean(nll)
    w = loss_mask.astype(jnp.float32)
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)


def _loss_mask(cfg, batch):
    mask = batch.get("loss_mask")
    if mask is None and cfg.family == "vlm" and "patches" in batch:
        B, S = batch["tokens"].shape
        P_len = batch["patches"].shape[1]
        mask = jnp.broadcast_to(
            (jnp.arange(S) >= P_len)[None].astype(jnp.float32), (B, S))
    return mask


@jax.named_scope("loss_head")
def head_loss(pctx, cfg: ModelConfig, params, hidden, labels, *, mask=None,
              compute_dtype=jnp.bfloat16):
    """Post-final-norm hidden states -> mean masked NLL.

    The LM-head + cross-entropy tail of :func:`train_loss`, factored out so
    a pipeline's LAST stage (parallel/pipeline.py) can run it on its own
    sub-mesh.  Routes through the fused chunked losses where the layout
    allows (hecaton's ``fused_lm_loss``; megatron seq layout's
    ``fused_lm_loss_seq`` with sharded labels) and otherwise materializes
    (sharded) logits and runs :func:`xent_loss` — exactly what the
    pre-refactor ``train_loss`` inlined.  ``params`` needs only the head
    leaves (``lm_head`` or the tied ``embed`` table)."""
    from repro.parallel import megatron as meg
    use_fused = (pctx.mesh is None or pctx.use_hecaton) and \
        pctx.pcfg.fused_loss
    use_meg_fused = (not use_fused and pctx.mesh is not None
                     and pctx.pcfg.fused_loss
                     and meg.seq_loss_ok(pctx, hidden.shape[1],
                                         cfg.padded_vocab))
    head_w = (params["embed"]["table"].T.astype(compute_dtype)
              if cfg.tie_embeddings else
              params["lm_head"]["w"].astype(compute_dtype))
    hidden = hidden.astype(compute_dtype)
    if use_meg_fused:
        # megatron seq layout: labels stay sharded; the head's vocab
        # chunks ring over the model axis (fused_lm_loss_seq)
        nll, cnt = meg.fused_lm_loss_seq(pctx, hidden, head_w, labels, mask)
    elif use_fused:
        from repro.core import hecaton as hec
        a = pctx.ax
        nll, cnt = hec.fused_lm_loss(
            hidden, head_w, labels, mask,
            mesh=pctx.mesh, t_ax=a.t_ax if a else "mx",
            h_ax=a.h_ax if a else "my",
            data_axes=a.data_axes if a else ("data",),
            overlap=pctx.overlap, comm_dtype=pctx.comm_dtype)
    else:
        logits = pctx.lm_head(hidden, head_w)
        logits = pctx.constraint(logits, pctx.logits_spec())
        return xent_loss(pctx, logits, labels, mask)
    return nll / jnp.maximum(cnt, 1.0)


def train_loss(pctx, cfg: ModelConfig, params, batch, *, remat: str = "fusion"):
    mask = _loss_mask(cfg, batch)
    out = forward(pctx, cfg, params, batch, remat=remat, skip_head=True)
    loss = head_loss(pctx, cfg, params, out.hidden, batch["labels"],
                     mask=mask, compute_dtype=batch.get("_dtype",
                                                        jnp.bfloat16))
    aux_coef = cfg.moe.aux_loss if cfg.moe else 0.0
    total = loss + aux_coef * out.aux
    return total, {"loss": loss, "aux": out.aux}
