"""Parallel execution context — the dispatch hub between model code and strategies.

Model code never touches axis names or collectives directly; it calls the methods
here.  ``PCtx`` binds (mesh, ParallelConfig, mode) and routes every projection to:

  * ``hecaton``  — paper Alg. 1 shard_map ops (core/hecaton.py) for train/prefill;
  * ``megatron`` — 1D-TP column/row-parallel with GSPMD-inserted all-reduce
                   (the paper's baseline, parallel/megatron.py);
  * plain einsum when ``mesh is None`` (smoke tests) .

``ParallelConfig.overlap`` (none → ring → bidir → fused, core/overlap.py) is
plumbed through unchanged: the hecaton ops AND the megatron baseline both
ring-decompose their collectives per mode, ``fused`` additionally routing
tile-aligned collective matmuls through the single-kernel Pallas ring path
(kernels/ring_matmul.py) with automatic fallback to ``ring`` otherwise.

``ParallelConfig.residual`` ("seq" | "replicated") selects the canonical
inter-block activation layout.  The default "seq" keeps the residual stream
token-sharded over the model axes for the whole layer scan — hecaton's 2D
tiling natively, the Korthikanti sequence-parallel layout P(d, model, None)
for megatron — so the shard-local entry points here (:meth:`norm`,
:meth:`dropout`, residual adds via :meth:`canon`) run on 1/n_t of the tokens
and no block boundary carries a bulk collective: megatron's entry gathers /
exit scatters ride the same overlap lattice as the hecaton ops.

Decode mode always uses the 1D layout over the *combined* model axes: Alg. 1's
token-scatter needs >= sqrt(N) tokens per step, and the paper targets training /
finetuning (docs/DESIGN.md §4).  Decode therefore also forces the replicated
residual (S=1 cannot token-scatter).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.config import ParallelConfig
from repro.core import hecaton as hec
from repro.models import layers as _L
from repro.parallel import megatron as meg
from repro.parallel import sharding as shd


def _einsum(x, w):
    return jnp.einsum("...h,ho->...o", x, w,
                      preferred_element_type=jnp.float32).astype(x.dtype)


@dataclass(frozen=True)
class PCtx:
    mesh: Optional[Mesh]
    pcfg: ParallelConfig
    mode: str = "train"                    # train | prefill | decode

    # ------------------------------------------------------------------
    @property
    def ax(self) -> Optional[shd.AxisInfo]:
        return shd.axis_info(self.mesh, self.pcfg.strategy)

    @property
    def use_hecaton(self) -> bool:
        return (self.mesh is not None and self.pcfg.strategy == "hecaton"
                and self.mode in ("train", "prefill"))

    @property
    def data_axes(self) -> Tuple[str, ...]:
        a = self.ax
        return a.data_axes if a else ()

    @property
    def overlap(self) -> str:
        """NoP comm/compute overlap mode (core/overlap.py MODES lattice):
        none | ring | bidir | fused — consumed by the hecaton ops, the MoE
        EP/TP collectives, and the megatron ring paths alike."""
        return self.pcfg.overlap

    @property
    def comm_dtype(self) -> str:
        """Ring-collective wire dtype (core/quant.py): "bf16" | "int8".
        Every ring hop the overlap lattice issues goes through
        ``quant.ring_hop`` under this dtype; "bf16" is bit-identical to the
        bare ``lax.ppermute`` the rings always did."""
        return self.pcfg.comm_dtype

    @property
    def residual(self) -> str:
        """Effective residual-stream layout (sharding.RESIDUAL_LAYOUTS).

        ``pcfg.residual`` except in decode, which forces "replicated" (S=1
        cannot token-scatter).  hecaton's canonical tiling is seq-sharded by
        construction, so the flag only changes the megatron baseline."""
        if self.mode == "decode":
            return "replicated"
        return self.pcfg.residual

    def constraint(self, x, spec: Optional[P]):
        if self.mesh is None or spec is None:
            return x
        return lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, spec))

    # ------------------------------------------------------------------
    # canonical layouts
    # ------------------------------------------------------------------
    def canon(self, x):
        """Constrain [B,S,H] to the canonical block-boundary layout.

        Decode (S=1) cannot token-scatter: canonical is batch-over-data only,
        hidden replicated (1D-TP residual layout).  A megatron sequence the
        model ring cannot divide likewise stays replicated."""
        a = self.ax
        if a is None:
            return x
        if self.mode == "decode":
            d = a.data_axes[0] if len(a.data_axes) == 1 else a.data_axes
            return self.constraint(x, P(d, None, None))
        layout = self.residual
        if (layout == "seq" and a.t_ax is None
                and not shd.seq_shardable(a, x.shape[1])):
            layout = "replicated"
        return self.constraint(x, shd.act_canonical(a, layout))

    def mixer_spec(self) -> Optional[P]:
        return shd.act_mixer(self.ax)

    # ------------------------------------------------------------------
    # shard-local residual-stream ops (norm / dropout run on 1/n_t tokens)
    # ------------------------------------------------------------------
    @jax.named_scope("norm")
    def norm(self, kind: str, params, x, eps: float = 1e-6):
        """Pre-norm on the canonical residual layout.

        Norm statistics are over the (unsharded) hidden dim, so the whole op
        is computed on the local token shard — zero communication, and under
        the seq layout per-die norm work and activation bytes shrink by
        1/n_t (the redundancy sequence parallelism removes)."""
        return _L.apply_norm(kind, params, self.canon(x), eps=eps)

    def dropout(self, x, rate: float, rng=None):
        """Dropout on the local token shard of the canonical layout.

        ``rng=None`` (or rate 0) is the deterministic path.  The mask is
        generated under GSPMD on the sharded operand, so no replicated
        [B,S,H] mask ever materializes.  The seq layout reproduces the
        single-device mask bit-for-bit; the replicated megatron layout may
        draw a different (equally valid) mask for the same key, since a
        threefry lowering that GSPMD does not partition is not bit-stable
        across program structure.  Keep rate and values are exact in every
        layout."""
        if rate <= 0.0 or rng is None:
            return x
        return _L.dropout(self.canon(x), rate, rng)

    # ------------------------------------------------------------------
    # projections
    # ------------------------------------------------------------------
    def _cast(self, x, *ws):
        """Cast weights to the activation dtype BEFORE any gather/shard_map —
        fp32 weights entering collectives double the FSDP/ZeRO gather bytes and
        silently promote the matmuls to fp32 (Perf iteration 1, EXPERIMENTS.md)."""
        return tuple(w if w is None else w.astype(x.dtype) for w in ws)

    def ffn(self, x, w1, w2, act_fn: Callable, w1b=None):
        """Fused FFN (paper §IV-B)."""
        w1, w2, w1b = self._cast(x, w1, w2, w1b)
        if self.use_hecaton:
            a = self.ax
            return hec.ffn_block(x, w1, w2, mesh=self.mesh, act_fn=act_fn,
                                 t_ax=a.t_ax, h_ax=a.h_ax, data_axes=a.data_axes,
                                 w1b=w1b, overlap=self.overlap,
                                 comm_dtype=self.comm_dtype)
        if self.mesh is not None:
            return meg.ffn(self, x, w1, w2, act_fn, w1b)
        h = _einsum(x, w1)
        h = act_fn(h) * _einsum(x, w1b) if w1b is not None else act_fn(h)
        return _einsum(h, w2)

    def mixer_in(self, x, w, interior: bool = False):
        """Projection into a token mixer: out has full sequence, hidden over grid.

        ``interior=True`` marks inputs that are already mixer-interior
        (full-sequence, hidden-sharded — e.g. MLA's second q projection) so
        the megatron seq-sharded path does not re-gather an entry that never
        scattered."""
        (w,) = self._cast(x, w)
        if self.use_hecaton:
            a = self.ax
            return hec.mixer_in(x, w, mesh=self.mesh, t_ax=a.t_ax, h_ax=a.h_ax,
                                data_axes=a.data_axes, overlap=self.overlap,
                                comm_dtype=self.comm_dtype)
        if self.mesh is not None:
            return meg.col_parallel(self, x, w, interior=interior)
        return _einsum(x, w)

    def mixer_in_many(self, x, *ws):
        """Several mixer-in projections of the SAME residual entry (QKV and
        friends) sharing one entry gather where the layout allows it.

        megatron seq layout: routes through ``col_parallel_shared`` — the
        sequence is ring-gathered ONCE and every projection reads the shared
        gather (1x entry NoP bytes instead of len(ws)x; one reduce-scatter in
        the backward).  Everything else falls back to per-weight
        :meth:`mixer_in` (hecaton's identical per-op gathers CSE in XLA)."""
        ws = self._cast(x, *ws)
        if (self.mesh is not None and not self.use_hecaton
                and self.mode != "decode"):
            return meg.col_parallel_shared(self, x, ws)
        return tuple(self.mixer_in(x, w) for w in ws)

    def mixer_out(self, y, w):
        """Projection out of a token mixer back to canonical layout."""
        (w,) = self._cast(y, w)
        if self.use_hecaton:
            a = self.ax
            return hec.mixer_out(y, w, mesh=self.mesh, t_ax=a.t_ax, h_ax=a.h_ax,
                                 data_axes=a.data_axes, overlap=self.overlap,
                                 comm_dtype=self.comm_dtype)
        if self.mesh is not None:
            return meg.row_parallel(self, y, w)
        return _einsum(y, w)

    @jax.named_scope("embed")
    def embed(self, table, ids, compute_dtype):
        """Vocab-parallel embedding lookup (core/hecaton.embed_2d).

        The vocab-partial collect rides the overlap lattice too (satellite of
        the seq-residual PR): ring ids-gather + ring reduce-scatter of the
        embedding partials.  Under the megatron seq layout the scatter lands
        the output directly in the canonical token-sharded residual."""
        if self.mesh is None:
            return jnp.take(table, ids, axis=0).astype(compute_dtype)
        a = self.ax
        B, S = ids.shape
        batch_ok = B % a.n_data == 0
        if self.pcfg.strategy == "hecaton":
            seq_ok = (self.mode != "decode" and S % a.size(a.t_ax) == 0
                      and S > 1)
            return hec.embed_2d(ids, table, mesh=self.mesh, t_ax=a.t_ax,
                                h_ax=a.h_ax, data_axes=a.data_axes,
                                compute_dtype=compute_dtype,
                                seq_sharded=seq_ok, batch_sharded=batch_ok,
                                overlap=self.overlap,
                                comm_dtype=self.comm_dtype)
        seq_ok = self.residual == "seq" and shd.seq_shardable(a, S)
        return hec.embed_2d(ids, table, mesh=self.mesh, t_ax="model",
                            h_ax=None, data_axes=a.data_axes,
                            compute_dtype=compute_dtype, seq_sharded=seq_ok,
                            batch_sharded=batch_ok, overlap=self.overlap,
                            comm_dtype=self.comm_dtype)

    def small_proj(self, x, w):
        """Tiny projection (mamba dt/B/C, routers) whose output dim is too small
        to 2D-tile: plain einsum from canonical layout; GSPMD sums the h_ax
        partials; output replicated over model axes (it is broadcast anyway)."""
        (w,) = self._cast(x, w)
        y = _einsum(x, w)
        return self.constraint(y, self.replicated_bsh())

    def lm_head(self, x, w):
        """Final projection to (sharded) vocab logits.

        hecaton: one seq-scatter linear — logits come out tokens-over-h_ax,
        vocab-over-t_ax; the fused loss consumes that layout directly.
        """
        (w,) = self._cast(x, w)
        if self.use_hecaton:
            a = self.ax
            return hec.linear_seq_scatter(x, w, mesh=self.mesh, t_ax=a.t_ax,
                                          h_ax=a.h_ax, data_axes=a.data_axes,
                                          overlap=self.overlap,
                                          comm_dtype=self.comm_dtype)
        if self.mesh is not None:
            return meg.col_parallel(self, x, w)   # vocab over model axis
        return _einsum(x, w)

    def logits_spec(self) -> Optional[P]:
        a = self.ax
        if a is None:
            return None
        d = shd._one(a.data_axes)
        if self.use_hecaton:
            return P(d, a.h_ax, a.t_ax)
        return P(d, None, shd._one(a.model_axes))

    # ------------------------------------------------------------------
    # attention layout
    # ------------------------------------------------------------------
    def attn_layout(self, n_heads: int, global_batch: int) -> shd.AttnLayout:
        a = self.ax
        if a is None:
            return shd.AttnLayout((), (), "single device")
        return shd.solve_attn_layout(a, n_heads,
                                     max(1, global_batch // a.n_data),
                                     prefer=self.pcfg.attn_layout)

    def heads_spec(self, layout: shd.AttnLayout) -> Optional[P]:
        """Spec for [B, S, n_heads, head_dim]."""
        if self.mesh is None:
            return None
        return layout.q_spec()

    # ------------------------------------------------------------------
    # param specs
    # ------------------------------------------------------------------
    def w_in_spec(self) -> Optional[P]:
        """Weight [H, O] consumed from canonical layout (QKV, up-proj, lm head)."""
        a = self.ax
        if a is None:
            return None
        if self.pcfg.strategy == "hecaton":
            return P(a.h_ax, a.t_ax)
        return P(None, "model")

    def w_out_spec(self) -> Optional[P]:
        """Weight of a mixer-out / second fused linear (swapped roles)."""
        a = self.ax
        if a is None:
            return None
        if self.pcfg.strategy == "hecaton":
            return P(a.t_ax, a.h_ax)
        return P("model", None)

    def vocab_spec(self) -> Optional[P]:
        return shd.vocab_spec(self.ax)

    def replicated(self) -> Optional[P]:
        return None if self.mesh is None else P()

    def replicated_bsh(self) -> Optional[P]:
        """[B,S,*] with only batch sharded (small broadcast tensors: B/C/dt)."""
        a = self.ax
        if a is None:
            return None
        d = a.data_axes[0] if len(a.data_axes) == 1 else a.data_axes
        return P(d, None, None)
