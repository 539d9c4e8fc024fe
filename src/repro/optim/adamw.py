"""AdamW with warmup-cosine schedule, global-norm clipping, and ZeRO-1-ready
state layout (parallel/zero.py shards these states over the data axis).

Implemented from scratch (no optax in this environment): functional
(init, update) pair operating on pytrees.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.config import RunConfig


class AdamState(NamedTuple):
    step: jax.Array
    mu: Any
    nu: Any
    # EWMA of ACCEPTED (finite, non-spiking) gradient norms, consumed by the
    # in-graph skip-update guard (runtime/guard.py, docs/DESIGN.md §8).  It
    # lives in the optimizer state — not the guard object — so it
    # checkpoints, restores and re-shards with the rest of the state: a
    # restarted incarnation resumes with the same spike baseline it crashed
    # with.  0.0 means "unseeded" (norms are positive, so 0 is unambiguous).
    gnorm_ewma: jax.Array


def init(params) -> AdamState:
    zeros = lambda p: jax.tree.map(lambda a: jnp.zeros_like(a, jnp.float32), p)
    return AdamState(jnp.zeros((), jnp.int32), zeros(params), zeros(params),
                     jnp.zeros((), jnp.float32))


def lr_schedule(rc: RunConfig, step, total_steps: int = 10_000):
    warm = jnp.minimum(1.0, (step + 1) / max(1, rc.warmup_steps))
    prog = jnp.clip((step - rc.warmup_steps) /
                    max(1, total_steps - rc.warmup_steps), 0.0, 1.0)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return rc.lr * warm * (0.1 + 0.9 * cos)


def global_norm_sq(tree) -> jax.Array:
    """Sum of squared leaf elements (fp32).  Exposed separately so pipeline
    stages (parallel/pipeline.py) can combine per-stage partial sums into
    ONE global norm before clipping — the clip couples all stages."""
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sum(jnp.stack(leaves))


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(global_norm_sq(tree))


def clip_by_global_norm(grads, max_norm: float, norm=None):
    """Clip by global norm; ``norm`` substitutes a precomputed norm (the
    pipeline's cross-stage combined norm) for the local tree norm."""
    g = global_norm(grads) if norm is None else norm
    scale = jnp.minimum(1.0, max_norm / (g + 1e-6))
    return jax.tree.map(lambda a: (a * scale).astype(a.dtype), grads), g


def guard_predicate(gnorm, ewma, guard):
    """The in-graph skip-update predicate (runtime/guard.py tentpole,
    docs/DESIGN.md §8): ``ok = finite AND NOT spike``.

    Finiteness of EVERY grad leaf is read off ONE scalar — the global norm
    already computed for clipping.  ``global_norm_sq`` sums squares of all
    leaves in fp32: a NaN anywhere propagates through the sum; ±Inf squares
    to +Inf; squares are non-negative so no cancellation can hide either.
    The spike test compares against the EWMA of previously ACCEPTED norms
    (``AdamState.gnorm_ewma``); an unseeded EWMA (0.0) never flags a spike,
    and NaN compares false so a non-finite norm cannot double-fire.

    Returns ``(ok, finite)`` scalar bool arrays."""
    finite = jnp.isfinite(gnorm)
    spike = (ewma > 0.0) & (gnorm > guard.grad_spike_factor * ewma)
    return finite & ~spike, finite


@jax.named_scope("optimizer")
def update(params, grads, state: AdamState, rc: RunConfig,
           total_steps: int = 10_000, *,
           grad_norm=None, guard=None) -> Tuple[Any, AdamState, Dict]:
    """One AdamW step; with ``guard`` (a :class:`repro.config.GuardConfig`)
    the update is applied under a ``jax.lax.cond`` on the
    :func:`guard_predicate` — a bad microbatch costs a no-op step (params
    and every optimizer leaf pass through BIT-UNCHANGED, the step counter
    does not advance) instead of a crash or a retrace: both branches trace
    once, the predicate picks one at run time.  ``cond`` rather than
    per-leaf ``jnp.where`` selects because accepted steps (all of training)
    must not pay for the guard: XLA-CPU materializes the selects as extra
    full-state passes (~10% step time), while the cond's taken branch is
    exactly the unguarded update.  (Multiply-masking is not an option at
    all: NaN * 0 is NaN; the skipped path must be bit-clean.)
    ``guard=None`` reproduces the unguarded numerics exactly."""
    grads, gnorm = clip_by_global_norm(grads, rc.grad_clip, norm=grad_norm)
    ok = None
    if guard is not None:
        ok, finite = guard_predicate(gnorm, state.gnorm_ewma, guard)
    lr = lr_schedule(rc, state.step, total_steps)
    b1, b2, eps = rc.beta1, rc.beta2, 1e-8
    # the EWMA folds in the (unclipped) norm only on ACCEPTED steps — a
    # skipped spike must not drag its own baseline up (cf. StepTimer's
    # freeze-while-slow); first accepted norm seeds it
    a = jnp.float32(guard.grad_ewma_alpha if guard is not None else 0.1)

    def applied(_):
        step = state.step + 1

        def upd(p, g, m, v):
            gf = g.astype(jnp.float32)
            m2 = b1 * m + (1 - b1) * gf
            v2 = b2 * v + (1 - b2) * gf * gf
            mh = m2 / (1 - b1 ** step)
            vh = v2 / (1 - b2 ** step)
            delta = (mh / (jnp.sqrt(vh) + eps)
                     + rc.weight_decay * p.astype(jnp.float32))
            p2 = (p.astype(jnp.float32) - lr * delta).astype(p.dtype)
            return p2, m2, v2

        flat_p, treedef = jax.tree.flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_m = treedef.flatten_up_to(state.mu)
        flat_v = treedef.flatten_up_to(state.nu)
        out = [upd(p, g, m, v)
               for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
        new_p = treedef.unflatten([o[0] for o in out])
        new_m = treedef.unflatten([o[1] for o in out])
        new_v = treedef.unflatten([o[2] for o in out])
        seeded = state.gnorm_ewma > 0.0
        folded = jnp.where(seeded,
                           (1.0 - a) * state.gnorm_ewma + a * gnorm, gnorm)
        return new_p, AdamState(step, new_m, new_v, folded)

    if ok is None:
        new_p, new_state = applied(None)
        return new_p, new_state, {"grad_norm": gnorm, "lr": lr}

    new_p, new_state = jax.lax.cond(ok, applied,
                                    lambda _: (params, state), None)
    metrics = {"grad_norm": gnorm, "lr": lr, "update_ok": ok,
               "update_skipped": 1.0 - ok.astype(jnp.float32),
               "nonfinite": 1.0 - finite.astype(jnp.float32)}
    return new_p, new_state, metrics
