"""Training step builder: microbatch gradient accumulation (the paper's
mini-batch scheduling, §III-B a), remat policy, grad clipping, AdamW + ZeRO-1.

``build_train_step`` returns a pure function
    (params, opt_state, batch) -> (params, opt_state, metrics)
suitable for ``jax.jit`` with the sharding trees from parallel/specs.py.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.config import ModelConfig, ParallelConfig, RunConfig
from repro.models import lm
from repro.optim import adamw
from repro.parallel import zero
from repro.parallel.context import PCtx


def microbatch_split(batch: Dict[str, jax.Array], n_micro: int):
    """[B, ...] -> [n_micro, B/n_micro, ...] for every array in the batch.

    A ``dropout_rng`` key is not batch-shaped: it is *split* into one
    independent PRNG key per microbatch instead (so every microbatch draws a
    distinct dropout mask), which keeps every leaf scannable over the leading
    microbatch dim."""
    def split(a):
        B = a.shape[0]
        assert B % n_micro == 0, f"batch {B} % microbatches {n_micro}"
        return a.reshape(n_micro, B // n_micro, *a.shape[1:])
    return {k: (jax.random.split(v, n_micro) if k == "dropout_rng"
                else split(v))
            for k, v in batch.items() if hasattr(v, "shape")}


def build_train_step(cfg: ModelConfig, pcfg: ParallelConfig, rc: RunConfig,
                     mesh, *, total_steps: int = 10_000,
                     compute_dtype=jnp.bfloat16, guard=None):
    """Single-program train step (grad-accumulation scan over microbatches).

    ``guard`` (a :class:`repro.config.GuardConfig`) arms the in-graph
    skip-update guard (docs/DESIGN.md §8): the AdamW update is applied under
    a ``jax.lax.cond`` on ``update_ok`` (all grads finite, no norm spike vs
    the EWMA in ``opt_state``), and metrics gain ``update_ok`` /
    ``update_skipped`` / ``nonfinite``.

    With ``pcfg.pipeline_enabled`` (pod_axis_role="pipeline") the step is
    instead the 1F1B orchestrator over per-pod stage sub-meshes — build it
    with ``parallel/pipeline.build_pipeline_train_step(...)`` (it takes the
    multi-pod mesh and returns (runner, step_fn); the step_fn must NOT be
    wrapped in ``jax.jit`` — it is a host-side schedule executor whose
    per-stage closures are jitted individually).
    """
    if pcfg.pipeline_enabled:
        raise ValueError(
            "pcfg.pipeline_enabled: use parallel/pipeline."
            "build_pipeline_train_step for the 1F1B pipeline step "
            "(state is per-stage; this single-program builder cannot "
            "express it)")
    pctx = PCtx(mesh, pcfg, "train")
    n_micro = pcfg.microbatches

    def loss_fn(params, mb):
        mb = dict(mb)
        mb["_dtype"] = compute_dtype
        return lm.train_loss(pctx, cfg, params, mb, remat=pcfg.remat)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(params, opt_state, batch):
        mbs = microbatch_split(batch, n_micro)

        def mb_body(carry, mb):
            gsum, lsum, asum = carry
            (loss, metrics), g = grad_fn(params, mb)
            with jax.named_scope("grad_accum"):
                g = zero.compress_grads(g, pcfg.grad_reduce_dtype)
                gsum = jax.tree.map(lambda a, b: a + b.astype(a.dtype),
                                    gsum, g)
            return (gsum, lsum + metrics["loss"], asum + metrics["aux"]), None

        gzero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (gsum, lsum, asum), _ = lax.scan(
            mb_body, (gzero, jnp.zeros(()), jnp.zeros(())), mbs)
        with jax.named_scope("grad_accum"):
            grads = jax.tree.map(lambda g: g / n_micro, gsum)
        new_params, new_opt, om = adamw.update(params, grads, opt_state, rc,
                                               total_steps, guard=guard)
        metrics = {"loss": lsum / n_micro, "aux": asum / n_micro, **om}
        return new_params, new_opt, metrics

    return train_step


def init_train_state(cfg: ModelConfig, key):
    params = lm.init_params(cfg, key)
    return params, adamw.init(params)
