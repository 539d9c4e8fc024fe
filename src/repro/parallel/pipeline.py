"""Inter-pod 1F1B pipeline parallelism (``ParallelConfig.pod_axis_role ==
"pipeline"``, docs/DESIGN.md §5).

The paper's weak-scaling argument (§V-B) holds *within* a package: the 2D
AG/RS collectives ride the on-package bypass rings.  Across packages the
off-package links are the slow tier, and the canonical strategy there is
pipeline parallelism — each pod owns a contiguous *stage* of the block stack
and microbatches stream through the stages under a 1F1B (one-forward-
one-backward) schedule, so the only inter-pod traffic is one boundary
activation (and its cotangent) per microbatch per stage boundary.

Two layers live here:

1. **The schedule itself** (:func:`schedule_1f1b`) — a pure-Python,
   tick-synchronous 1F1B table (warmup / steady 1F1B / cooldown per stage,
   Megatron-LM's non-interleaved PipeDream-flush).  It is data-free, so its
   properties (op order, dependency sanity, makespan ``2*(m+p-1)``, bubble
   ticks ``2*(p-1)`` per stage, peak in-flight ``min(p-s, m)``) are unit
   tested without devices, and ``core/theory.py``'s bubble-fraction
   prediction ``(p-1)/(m+p-1)`` is checked against the simulated table
   (tests/test_pipeline.py).

2. **The runner** (:class:`PipelineRunner`) — executes the table on a
   multi-pod mesh.  Each stage runs on its pod's sub-mesh
   (``launch/mesh.pod_submeshes``) with the FULL existing intra-pod
   machinery — hecaton 2D tiling or the megatron baseline, the
   ``overlap`` lattice, and the seq-sharded residual — composing unchanged,
   because inside a stage the world looks exactly like a single-pod run.
   Stage-boundary transfers move the canonical (seq-sharded) [B,S,H]
   residual shard-to-shard between neighbouring pods' sub-meshes via
   ``jax.device_put`` — the point-to-point off-package hop.  (A pod-axis
   ``shard_map``/``ppermute`` nested around the hecaton ops' own shard_maps
   did not lower when this was written, so the transfer is expressed as an
   explicit reshard instead of a pod-axis collective-permute; on one global
   mesh the two lower to the same device-to-device copies.)

Backward runs per-stage VJPs in the 1F1B order: a stage's backward
*recomputes* its forward from the stashed boundary input (stage-granular
remat — the stash per stage is bounded by the schedule's in-flight bound
``min(p-s, m)``, the 1F1B memory advantage over GPipe's ``m``).  Gradients
accumulate per stage exactly as train/step.py's microbatch scan does
(compress to ``grad_reduce_dtype``, accumulate fp32, divide by ``m``), and
the optimizer step stays bit-comparable to the single-program step: the
global-norm clip couples the stages, so per-stage square-sums are combined
into ONE global norm which every stage's AdamW update consumes
(``optim/adamw.update(grad_norm=...)``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import ModelConfig, ParallelConfig, RunConfig
from repro.optim import adamw
from repro.parallel import sharding as shd
from repro.parallel import specs as SP
from repro.parallel import zero
from repro.parallel.context import PCtx

# ---------------------------------------------------------------------------
# 1F1B schedule (pure Python — no jax below this line until the runner)
# ---------------------------------------------------------------------------

F = "F"
B = "B"


@dataclass(frozen=True)
class PipeTask:
    """One unit of stage work: forward or backward of one microbatch."""
    kind: str          # "F" | "B"
    mb: int            # microbatch index


def stage_order(stage: int, n_stages: int, n_micro: int) -> List[PipeTask]:
    """Per-stage 1F1B op order: warmup forwards, steady 1F1B, cooldown.

    Stage ``s`` warms up with ``min(p-1-s, m)`` forwards (the last stage
    warms up with zero and immediately alternates), then strictly
    alternates F, B until its forwards run out, then drains the remaining
    backwards — Megatron-LM's non-interleaved 1F1B.
    """
    p, m = n_stages, n_micro
    warmup = min(p - 1 - stage, m)
    order = [PipeTask(F, i) for i in range(warmup)]
    for i in range(m - warmup):
        order.append(PipeTask(F, warmup + i))
        order.append(PipeTask(B, i))
    for i in range(m - warmup, m):
        order.append(PipeTask(B, i))
    return order


@dataclass(frozen=True)
class PipeSchedule:
    """Tick-synchronous 1F1B table: ``ticks[t][s]`` is stage ``s``'s task at
    tick ``t`` (or None for a bubble).  F and B each take one tick; a task
    may only run when its dependency completed at a strictly earlier tick."""
    n_stages: int
    n_micro: int
    ticks: Tuple[Tuple[Optional[PipeTask], ...], ...]

    @property
    def makespan(self) -> int:
        return len(self.ticks)

    def bubble_ticks(self, stage: int) -> int:
        """Idle ticks of ``stage`` within the makespan."""
        return sum(1 for t in self.ticks if t[stage] is None)

    @property
    def bubble_fraction(self) -> float:
        """Simulated bubble fraction = idle/total of any stage (uniform in
        1F1B); theory predicts ``(p-1)/(m+p-1)`` (core/theory.py)."""
        return self.bubble_ticks(0) / self.makespan

    def peak_in_flight(self, stage: int) -> int:
        """Max simultaneously-stashed microbatches at ``stage`` (the
        activation-memory bound: ``min(p - stage, m)`` under 1F1B)."""
        peak = cur = 0
        for t in self.ticks:
            task = t[stage]
            if task is None:
                continue
            cur += 1 if task.kind == F else -1
            peak = max(peak, cur)
        return peak


def schedule_1f1b(n_stages: int, n_micro: int) -> PipeSchedule:
    """Simulate the 1F1B orders into a tick table.

    Dependencies: F(s, i) needs F(s-1, i); B(s, i) needs B(s+1, i) (and its
    own F(s, i), implied by the per-stage order).  Each stage executes its
    next op as soon as the dependency completed at an earlier tick.
    """
    p, m = n_stages, n_micro
    assert p >= 1 and m >= 1, (p, m)
    orders = [stage_order(s, p, m) for s in range(p)]
    pos = [0] * p                       # next-op index per stage
    done: Dict[Tuple[str, int, int], int] = {}   # (kind, stage, mb) -> tick
    ticks: List[Tuple[Optional[PipeTask], ...]] = []
    t = 0
    while any(pos[s] < len(orders[s]) for s in range(p)):
        row: List[Optional[PipeTask]] = []
        fired = []
        for s in range(p):
            if pos[s] >= len(orders[s]):
                row.append(None)
                continue
            task = orders[s][pos[s]]
            if task.kind == F:
                dep = None if s == 0 else (F, s - 1, task.mb)
            else:
                dep = None if s == p - 1 else (B, s + 1, task.mb)
            if dep is None or done.get(dep, t) < t:
                row.append(task)
                fired.append((task.kind, s, task.mb))
                pos[s] += 1
            else:
                row.append(None)
        assert fired, f"1F1B deadlock at tick {t} (p={p}, m={m})"
        for key in fired:
            done[key] = t
        ticks.append(tuple(row))
        t += 1
    return PipeSchedule(p, m, tuple(ticks))


# ---------------------------------------------------------------------------
# Stage partitioning of the model
# ---------------------------------------------------------------------------

def split_stage_layers(num_layers: int, n_stages: int) -> List[range]:
    """Contiguous per-stage layer ranges; the stack must divide evenly."""
    if num_layers % n_stages:
        raise ValueError(
            f"num_layers={num_layers} must divide evenly into "
            f"{n_stages} pipeline stages")
    lps = num_layers // n_stages
    return [range(s * lps, (s + 1) * lps) for s in range(n_stages)]


def validate_pipeline(cfg: ModelConfig, pcfg: ParallelConfig) -> None:
    """Raise on model/parallel combinations the 1F1B runner does not support."""
    if not pcfg.pipeline_enabled:
        raise ValueError("pod_axis_role='pipeline' requires pods > 1 "
                         f"(got pods={pcfg.pods})")
    if (cfg.family not in ("dense", "moe") or cfg.is_encdec
            or set(cfg.pattern()) != {"attn"} or cfg.frontend_stub_len):
        raise ValueError(
            f"pipeline stages support uniform token-only attention stacks "
            f"(dense/moe) only; {cfg.name!r} is family={cfg.family!r} with "
            f"pattern {sorted(set(cfg.pattern()))} (encdec={cfg.is_encdec}, "
            f"frontend_stub_len={cfg.frontend_stub_len}) — vlm patch "
            f"injection / audio frames / mamba states are not staged")
    if cfg.tie_embeddings:
        raise ValueError(
            "pipeline does not support tie_embeddings: the table would need "
            "to live on both the first and last stage with summed grads")
    split_stage_layers(cfg.num_layers, pcfg.pipeline_stages)


def stage_params(params, cfg: ModelConfig, stage: int, n_stages: int):
    """Slice the stacked param tree down to one stage's subtree.

    Stage 0 owns the embedding; the last stage owns the final norm and the
    LM head; every stage owns ``num_layers / n_stages`` contiguous blocks.
    """
    rng = split_stage_layers(cfg.num_layers, n_stages)[stage]
    sp: Dict[str, Any] = {
        "blocks": jax.tree.map(lambda a: a[rng.start:rng.stop],
                               params["blocks"]),
    }
    if stage == 0:
        sp["embed"] = params["embed"]
    if stage == n_stages - 1:
        sp["final_norm"] = params["final_norm"]
        if "lm_head" in params:
            sp["lm_head"] = params["lm_head"]
    return sp


def merge_stage_grads(stage_trees: Sequence[Any], cfg: ModelConfig):
    """Reassemble per-stage trees into one full-model tree (for tests /
    checkpoints of the combined view).  Inverse of :func:`stage_params`."""
    blocks = jax.tree.map(
        lambda *leaves: np.concatenate([np.asarray(l) for l in leaves], 0),
        *[t["blocks"] for t in stage_trees])
    out = {"blocks": blocks,
           "embed": jax.tree.map(np.asarray, stage_trees[0]["embed"]),
           "final_norm": jax.tree.map(np.asarray,
                                      stage_trees[-1]["final_norm"])}
    if "lm_head" in stage_trees[-1]:
        out["lm_head"] = jax.tree.map(np.asarray, stage_trees[-1]["lm_head"])
    return out


def stage_writer_map(n_writers: int):
    """Checkpoint shard→writer mapping for pipeline state (ISSUE 6).

    Pipeline train state is ``{"params": [per-stage trees], "opt_state":
    [...]}``, so a checkpoint leaf path's second segment is the stage index
    — the pod that already holds those shards in HBM.  Mapping ``stage %
    n_writers`` makes each pod persist its own stage (the natural failure
    domain: a pod death costs one writer, not the whole save), with the
    modulo covering ``n_writers < stages``.  Returns ``None`` for non-stage
    leaves (e.g. scalars at the tree root), which fall back to the
    manager's byte-balanced partition (checkpoint/manager.partition_shards).
    """
    def _map(name: str):
        parts = name.split("/")
        if len(parts) >= 2:
            try:
                return int(parts[1]) % n_writers
            except ValueError:
                return None
        return None
    return _map


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------

class PipelineRunner:
    """Executes the 1F1B table over per-pod sub-meshes.

    ``mesh`` is the global multi-pod mesh (leading ``"pod"`` axis,
    ``launch/mesh.make_small_mesh(..., pods=p)``).  Each stage gets the
    pod's sub-mesh and an inner single-pod ``ParallelConfig`` (same
    strategy / grid / overlap / residual), so hecaton's 2D collectives and
    the overlap lattice run inside the stage exactly as on a single pod.
    """

    def __init__(self, cfg: ModelConfig, pcfg: ParallelConfig, rc: RunConfig,
                 mesh: Mesh, *, total_steps: int = 10_000,
                 compute_dtype=jnp.bfloat16, guard=None):
        from repro.launch import mesh as M
        validate_pipeline(cfg, pcfg)
        if "pod" not in mesh.axis_names:
            raise ValueError(
                f"pipeline needs a mesh with a 'pod' axis; got "
                f"{mesh.axis_names} (use launch.mesh.make_small_mesh(..., "
                f"pods=n) or make_hecaton_mesh(multi_pod=True))")
        self.cfg, self.pcfg, self.rc = cfg, pcfg, rc
        self.total_steps = total_steps
        self.compute_dtype = compute_dtype
        self.n_stages = pcfg.pipeline_stages
        self.n_micro = pcfg.microbatches
        self.sched = schedule_1f1b(self.n_stages, self.n_micro)
        self.submeshes = M.pod_submeshes(mesh)
        assert len(self.submeshes) == self.n_stages, (
            len(self.submeshes), self.n_stages)
        inner = pcfg.with_(pods=1, pod_axis_role="data")
        self.pctxs = [PCtx(sm, inner, "train") for sm in self.submeshes]
        self.aux_coef = cfg.moe.aux_loss if cfg.moe else 0.0
        # per-stage canonical residual / token shardings for the boundary
        # transfers — with the same non-dividing-sequence fallback that
        # PCtx.canon / specs.batch_specs apply inside the stage
        self._canon = [NamedSharding(
            sm, shd.act_canonical(px.ax, self._residual_layout(px)))
            for sm, px in zip(self.submeshes, self.pctxs)]
        self._tok = [NamedSharding(sm, SP.batch_specs(
            sm, inner, microbatched=False, seq_len=rc.seq_len)["tokens"])
            for sm in self.submeshes]
        self.guard = guard
        self._build_stage_fns()
        self._gnorm_sq = jax.jit(adamw.global_norm_sq)
        # one jitted optimizer update serves every stage: jit re-traces per
        # stage tree structure/sharding and caches each specialization.
        # With a guard, every stage folds the SAME cross-stage scalar norm
        # into its update, so per-stage guard predicates and EWMAs stay
        # bitwise in sync — stages skip (or accept) a step in lockstep.
        self._upd = jax.jit(lambda q, g, st, gn: adamw.update(
            q, g, st, self.rc, self.total_steps, grad_norm=gn,
            guard=self.guard))
        # executed-op log (schedule-conformance assertions in tests)
        self.executed: List[List[PipeTask]] = []

    def _residual_layout(self, pctx: PCtx) -> str:
        ax = pctx.ax
        if ax.t_ax is not None:
            return "seq"               # hecaton tiling is seq-sharded natively
        if (pctx.pcfg.residual == "seq"
                and shd.seq_shardable(ax, self.rc.seq_len)):
            return "seq"
        return "replicated"

    # -- stage cores -------------------------------------------------------

    def _blocks(self, s: int, sparams, x):
        from repro.models import lm
        pctx = self.pctxs[s]
        Bsz, S = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                     (Bsz, S))
        layout = pctx.attn_layout(self.cfg.num_heads, Bsz)
        x, aux, _ = lm._scan_attn_stack(
            pctx, self.cfg, sparams["blocks"], x, positions=positions,
            layout=layout, causal=True, caches=None, memory=None,
            remat=self.pcfg.remat)
        return x, aux

    def _first_core(self, sparams, tokens, rng):
        pctx, cfg = self.pctxs[0], self.cfg
        x = pctx.embed(sparams["embed"]["table"], tokens, self.compute_dtype)
        x = pctx.canon(x)
        if cfg.embed_dropout and rng is not None:
            x = pctx.dropout(x, cfg.embed_dropout, rng)
        return self._blocks(0, sparams, x)

    def _mid_core(self, s: int, sparams, x):
        return self._blocks(s, sparams, self.pctxs[s].canon(x))

    def _last_core(self, sparams, x, labels, mask):
        from repro.models import lm
        s = self.n_stages - 1
        pctx, cfg = self.pctxs[s], self.cfg
        x, aux = self._blocks(s, sparams, pctx.canon(x))
        hidden = pctx.norm(cfg.norm_kind, sparams["final_norm"], x)
        loss = lm.head_loss(pctx, cfg, sparams, hidden, labels, mask=mask,
                            compute_dtype=self.compute_dtype)
        return loss, aux

    # -- jitted stage entry points ----------------------------------------

    def _build_stage_fns(self):
        coef = jnp.float32(self.aux_coef)
        p = self.n_stages

        def first_fwd(sp, tokens, rng):
            return self._first_core(sp, tokens, rng)

        def first_bwd(sp, tokens, rng, dy):
            _, pull = jax.vjp(lambda q: self._first_core(q, tokens, rng), sp)
            (dsp,) = pull((dy, coef))
            return dsp

        self.first_fwd = jax.jit(first_fwd)
        self.first_bwd = jax.jit(first_bwd)

        self.mid_fwd, self.mid_bwd = {}, {}
        for s in range(1, p - 1):
            def mid_fwd(sp, x, _s=s):
                return self._mid_core(_s, sp, x)

            def mid_bwd(sp, x, dy, _s=s):
                _, pull = jax.vjp(lambda q, xx: self._mid_core(_s, q, xx),
                                  sp, x)
                return pull((dy, coef))

            self.mid_fwd[s] = jax.jit(mid_fwd)
            self.mid_bwd[s] = jax.jit(mid_bwd)

        def last_total(sp, x, labels, mask):
            loss, aux = self._last_core(sp, x, labels, mask)
            return loss + self.aux_coef * aux, (loss, aux)

        def last_bwd(sp, x, labels, mask):
            grads, aux = jax.grad(last_total, argnums=(0, 1),
                                  has_aux=True)(sp, x, labels, mask)
            return grads, aux

        self.last_bwd = jax.jit(last_bwd)

    # -- state placement ---------------------------------------------------

    def place_params(self, params) -> List[Any]:
        """Full-model param tree -> per-stage trees sharded on the sub-meshes."""
        out = []
        for s in range(self.n_stages):
            sp = stage_params(params, self.cfg, s, self.n_stages)
            pspecs = SP.param_specs(sp, self.submeshes[s],
                                    self.pctxs[s].pcfg)
            out.append(jax.device_put(sp, SP.sharding_tree(
                pspecs, self.submeshes[s])))
        return out

    def init_opt(self, sparams: List[Any]) -> List[adamw.AdamState]:
        out = []
        for s, sp in enumerate(sparams):
            st = adamw.init(sp)
            pspecs = SP.param_specs(sp, self.submeshes[s], self.pctxs[s].pcfg)
            ospecs = SP.opt_state_specs(pspecs, sp, self.submeshes[s],
                                        self.pctxs[s].pcfg)
            out.append(jax.device_put(st, SP.sharding_tree(
                ospecs, self.submeshes[s])))
        return out

    # -- 1F1B execution ----------------------------------------------------

    _BATCH_KEYS = ("tokens", "labels", "loss_mask", "dropout_rng")

    def _split_batch(self, batch):
        from repro.train.step import microbatch_split
        unknown = [k for k in batch
                   if k not in self._BATCH_KEYS and hasattr(batch[k],
                                                            "shape")]
        if unknown:
            # e.g. custom "positions": the stages rebuild arange positions,
            # so silently dropping a caller-supplied key would mistrain
            raise ValueError(f"pipeline runner does not support batch keys "
                             f"{unknown}; supported: {self._BATCH_KEYS}")
        mbs = microbatch_split(batch, self.n_micro)
        tokens = [jax.device_put(mbs["tokens"][i], self._tok[0])
                  for i in range(self.n_micro)]
        rngs = ([mbs["dropout_rng"][i] for i in range(self.n_micro)]
                if "dropout_rng" in mbs else [None] * self.n_micro)
        last = self._tok[-1]
        labels = [jax.device_put(mbs["labels"][i], last)
                  for i in range(self.n_micro)]
        masks = ([jax.device_put(mbs["loss_mask"][i], last)
                  for i in range(self.n_micro)]
                 if "loss_mask" in mbs else [None] * self.n_micro)
        return tokens, rngs, labels, masks

    def loss_and_grads(self, sparams: List[Any], batch):
        """Run the full 1F1B table once: mean loss + per-stage mean grads.

        Mirrors train/step.py's accumulation bit-for-bit: per-microbatch
        grads are compressed to ``grad_reduce_dtype``, accumulated into an
        fp32 sum, and divided by the microbatch count at the end.
        """
        p, m = self.n_stages, self.n_micro
        tokens, rngs, labels, masks = self._split_batch(batch)
        # accumulators are seeded by the first backward's (compressed) grad,
        # so they inherit the stage sharding — no zero tree ever
        # materializes on the default device
        gsum: List[Any] = [None] * p
        acts: List[Dict[int, Any]] = [dict() for _ in range(p)]
        cots: List[Dict[int, Any]] = [dict() for _ in range(p)]
        inflight = [set() for _ in range(p)]
        losses, auxes = [], [[] for _ in range(p)]
        executed: List[List[PipeTask]] = [[] for _ in range(p)]
        self.max_stash = [0] * p

        def accumulate(s, dp):
            dp = zero.compress_grads(dp, self.pcfg.grad_reduce_dtype)
            if gsum[s] is None:
                gsum[s] = jax.tree.map(lambda b: b.astype(jnp.float32), dp)
            else:
                gsum[s] = jax.tree.map(lambda a, b: a + b.astype(a.dtype),
                                       gsum[s], dp)

        for row in self.sched.ticks:
            for s, task in enumerate(row):
                if task is None:
                    continue
                executed[s].append(task)
                i = task.mb
                if task.kind == F:
                    if s == 0:
                        y, aux = self.first_fwd(sparams[0], tokens[i],
                                                rngs[i])
                    elif s < p - 1:
                        y, aux = self.mid_fwd[s](sparams[s], acts[s][i])
                    # the last stage's fwd happens inside the fused bwd at
                    # its B tick (stage-granular remat): the F tick only
                    # admits the microbatch into the stash.
                    if s < p - 1:
                        acts[s + 1][i] = jax.device_put(y,
                                                        self._canon[s + 1])
                        auxes[s].append(aux)
                    inflight[s].add(i)
                    self.max_stash[s] = max(self.max_stash[s],
                                            len(inflight[s]))
                else:
                    if s == p - 1:
                        (dp, dx), (loss_i, aux_i) = self.last_bwd(
                            sparams[s], acts[s][i], labels[i], masks[i])
                        losses.append(loss_i)
                        auxes[s].append(aux_i)
                    elif s > 0:
                        dp, dx = self.mid_bwd[s](sparams[s], acts[s][i],
                                                 cots[s].pop(i))
                    else:
                        dp = self.first_bwd(sparams[0], tokens[i], rngs[i],
                                            cots[0].pop(i))
                        dx = None
                    if s > 0:
                        cots[s - 1][i] = jax.device_put(dx,
                                                        self._canon[s - 1])
                        acts[s].pop(i)
                    inflight[s].discard(i)
                    accumulate(s, dp)
        self.executed = executed
        grads = [jax.tree.map(lambda g: g / m, gs) for gs in gsum]
        loss = sum(losses[1:], losses[0]) / m
        aux_terms = [sum(a[1:], a[0]) / m for a in auxes if a]
        metrics = {"loss": loss,
                   "aux": float(np.sum([np.asarray(a) for a in aux_terms]))}
        return loss, grads, metrics

    # -- full train step ---------------------------------------------------

    def train_step(self, sparams: List[Any], sopt: List[Any], batch):
        """(stage params, stage opt states, batch) -> updated state + metrics.

        Bit-comparable to the single-program optimizer step: the global-norm
        clip consumes ONE norm combined across all stages.
        """
        loss, grads, metrics = self.loss_and_grads(sparams, batch)
        sq = [float(np.asarray(self._gnorm_sq(g))) for g in grads]
        gnorm = float(np.sqrt(np.sum(np.asarray(sq, np.float64))))
        new_p, new_o = [], []
        for s in range(self.n_stages):
            gn = jax.device_put(jnp.float32(gnorm),
                                NamedSharding(self.submeshes[s], P()))
            np_, no_, om = self._upd(sparams[s], grads[s], sopt[s], gn)
            new_p.append(np_)
            new_o.append(no_)
        metrics.update({"grad_norm": jnp.float32(gnorm), "lr": om["lr"]})
        if self.guard is not None:
            # identical across stages (same scalar norm, synced EWMAs);
            # surface the last stage's copy
            for k in ("update_ok", "update_skipped", "nonfinite"):
                metrics[k] = om[k]
        metrics["aux"] = jnp.float32(metrics["aux"])
        return new_p, new_o, metrics


def build_pipeline_train_step(cfg: ModelConfig, pcfg: ParallelConfig,
                              rc: RunConfig, mesh, *,
                              total_steps: int = 10_000,
                              compute_dtype=jnp.bfloat16, guard=None):
    """Pipeline counterpart of ``train/step.build_train_step``.

    Returns ``(runner, step_fn)``: the step takes (stage_params,
    stage_opt_states, batch) like the single-program step takes (params,
    opt_state, batch), so ``train/loop.train`` drives either one.  The step
    is a host-side 1F1B orchestrator — do NOT wrap it in ``jax.jit``.
    """
    runner = PipelineRunner(cfg, pcfg, rc, mesh, total_steps=total_steps,
                            compute_dtype=compute_dtype, guard=guard)
    return runner, runner.train_step
