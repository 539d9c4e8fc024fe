"""Training loop: data prefetch, jit'd step, checkpointing, fault hooks.

Pure-state design: the loop is a fold of ``train_step`` over a seekable data
stream, so (checkpoint, step) fully determines the future — the property the
supervisor (runtime/fault.py) relies on for restart-exactness.

Checkpointing is non-blocking when the manager supports it
(checkpoint/manager.AsyncCheckpointManager): the boundary step only snapshots
state into the host staging arena via ``save_async`` — serialization and the
atomic publish happen on the manager's writer thread while the next steps
run.  The snapshot must happen here, synchronously at the boundary, because
the step function donates its buffers: by the next ``train_step`` call the
device memory behind ``params``/``opt_state`` may be reused.  On normal exit
the loop drains in-flight saves (``wait_until_finished``), which also
surfaces any writer error; on failure the supervisor aborts them instead
(``run_supervised(ckpt=...)``) so a restart never resumes from a
half-published step.

Each step is a ``StepTraceAnnotation("train")`` holding host spans
``next_batch``, ``dispatch``, ``loss_sync``, ``guard`` and ``ckpt_save``, so
a profile (``launch/train.py --profile_dir``) puts the device's idle gaps
down to what the host was doing.

The loop is agnostic to HOW the step runs: the single-program jitted step
(train/step.py) and the 1F1B pipeline orchestrator
(parallel/pipeline.build_pipeline_train_step) both fold ``(params,
opt_state, batch) -> (params, opt_state, metrics)``; under the pipeline the
state leaves are *lists of per-stage trees* (one per pod), which checkpoint
and restore like any other pytree.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Optional

import jax
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.checkpoint.manager import CheckpointManager
from repro.runtime.fault import FailureInjector, StepTimer


def train(train_step: Callable, state: Dict, data_iter, *,
          start_step: int = 0, num_steps: int = 100,
          ckpt: Optional[CheckpointManager] = None, ckpt_every: int = 50,
          log_every: int = 10, injector: Optional[FailureInjector] = None,
          timer: Optional[StepTimer] = None,
          on_straggler: Optional[Callable] = None,
          guard=None, watchdog=None,
          data_index_fn: Optional[Callable[[int], int]] = None,
          log_fn: Callable = print) -> Dict:
    """``guard`` is a :class:`repro.runtime.guard.TrainingGuard` — fed every
    synced per-step loss (+ the in-graph ``update_skipped`` metric), it
    raises ``DivergenceError`` on sustained divergence, BEFORE the boundary
    save that would persist the poisoned state.  ``watchdog`` is a
    :class:`repro.runtime.guard.Watchdog`, armed at the top of each step and
    checked once the loss syncs — a step that outlives ``hang_timeout``
    raises ``HangError``.  ``data_index_fn`` maps loop step -> data index
    (identity when None) so a blocklist-aware run reports the true poisoned
    ``batch_at`` indices (docs/DESIGN.md §8)."""
    params, opt_state = state["params"], state["opt_state"]
    history = state.setdefault("history", [])
    step_s = state.setdefault("step_s", [])      # per-step seconds
    if (ckpt is not None and injector is not None
            and hasattr(injector, "check_writer")
            and getattr(ckpt, "writer_fault", None) is None):
        # wire the writer-fault dimension: the injector can now kill one
        # logical writer inside the torn window (post shard-write, pre
        # partial-manifest publish) — checkpoint/manager.py quorum protocol
        ckpt.writer_fault = injector.check_writer
    if (ckpt is not None and injector is not None
            and hasattr(injector, "proc_fault")
            and getattr(ckpt, "writer_procs", False)
            and getattr(ckpt, "proc_fault", None) is None):
        # process-fleet sibling: the injector ships kill9/sigstop/slow/
        # corrupt specs into writer CHILD PROCESSES (runtime/procs.py) —
        # same torn window, process-level failure modes
        ckpt.proc_fault = injector.proc_fault
    for step in range(start_step, num_steps):
        with StepTraceAnnotation("train", step_num=step):
            with TraceAnnotation("next_batch"):
                batch = next(data_iter)
            if injector is not None:
                injector.check(step)
            if watchdog is not None:
                watchdog.arm(step)
            t0 = time.time()
            with TraceAnnotation("dispatch"):
                params, opt_state, metrics = train_step(params, opt_state,
                                                        batch)
            with TraceAnnotation("loss_sync"):
                jax.block_until_ready(metrics["loss"])
            dt = time.time() - t0
            step_s.append(dt)
            if watchdog is not None:
                watchdog.disarm()
                watchdog.check()                # raises HangError if tripped
            if timer is not None and timer.record(dt) and on_straggler:
                on_straggler(step, timer)
            # per-step history: the loss is already a synced scalar (the
            # block_until_ready above), so recording every step costs one
            # float append — and restart-exactness tests / the guard see the
            # full trajectory, not a log_every subsample
            loss = float(metrics["loss"])
            history.append((step, loss))
            if step % log_every == 0 or step == num_steps - 1:
                log_fn(f"step {step:5d} loss {loss:.4f} "
                       f"gnorm {float(metrics.get('grad_norm', 0)):.3f} "
                       f"{dt*1e3:.0f}ms")
            if guard is not None:
                # before the boundary save: a DivergenceError here must not
                # let the poisoned state publish
                with TraceAnnotation("guard"):
                    guard.observe(step, loss, metrics,
                                  data_index=(data_index_fn(step)
                                              if data_index_fn else step))
            if ckpt is not None and (step + 1) % ckpt_every == 0:
                # non-blocking on AsyncCheckpointManager; = save() on the
                # sync one
                with TraceAnnotation("ckpt_save"):
                    ckpt.save_async(step + 1, {"params": params,
                                               "opt_state": opt_state})
    if ckpt is not None:
        ckpt.wait_until_finished()          # drain async writes; raise errors
    state.update(params=params, opt_state=opt_state)
    return state
