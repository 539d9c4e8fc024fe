"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --steps 200 --batch 8 --seq 256 [--mesh-devices 8 --strategy hecaton]

With ``JAX_PLATFORMS=cpu`` a multi-device mesh (--mesh-devices, or
--pods > 1) runs on fake CPU devices, re-executed through XLA_FLAGS; on a TPU
host the same entry point builds the mesh from its chips.  The step is
compiled ahead of the first batch, so compile time and the step's memory
analysis print before training starts.  Enables checkpointing + fault
supervision.
"""

from __future__ import annotations

import argparse
import os
import sys


def _maybe_respawn(n: int):
    """Re-exec with ``n`` fake CPU devices — only when the CPU platform is
    requested (``JAX_PLATFORMS=cpu``); a chip host uses its real devices."""
    cpu = os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu"
    if n > 1 and cpu and "XLA_FLAGS" not in os.environ:
        os.environ["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={n}"
        os.execv(sys.executable, [sys.executable] + sys.argv)


def _guard_cfg(args):
    """GuardConfig from flags, or None when --guard is off (docs/DESIGN.md
    §8).  Passed to the step builder (arms the in-graph skip-update select)
    and to the TrainingGuard (loss-spike / skip-cap escalation)."""
    if not args.guard:
        return None
    from repro.config import GuardConfig
    return GuardConfig(grad_spike_factor=args.guard_spike_factor,
                       loss_spike_factor=args.guard_loss_spike,
                       patience=args.guard_patience,
                       skip_cap=args.guard_skip_cap,
                       hang_timeout=args.hang_timeout,
                       rollback=not args.no_rollback)


def _guard_runtime(args, gcfg, ckpt_dir, start, batch_at):
    """Loop-side guard surface: (TrainingGuard, Watchdog, data_index_fn,
    data stream).  The stream seeks to ``batch_at(data_index(start,
    blocklist))`` — a restored run consumes exactly the batches an
    uninterrupted (blocklist-filtered) run would have, instead of
    restarting the data at index 0."""
    from repro.runtime import guard as G
    tguard = G.TrainingGuard(gcfg) if gcfg is not None else None
    wd = G.Watchdog(args.hang_timeout) if args.hang_timeout > 0 else None
    bl = G.load_blocklist(ckpt_dir)
    if bl:
        print(f"blocklist: skipping poisoned data indices {bl}")
    stream = G.blocklisted_stream(batch_at, start, bl)
    return tguard, wd, (lambda s: G.data_index(s, bl)), stream


def _step_range(text: str):
    """``A:B`` of --profile_steps: steps A..B-1."""
    try:
        a, b = (int(x) for x in text.split(":"))
    except ValueError:
        raise argparse.ArgumentTypeError(f"not A:B: {text!r}") from None
    if not 0 <= a < b:
        raise argparse.ArgumentTypeError(f"needs 0 <= A < B: {text!r}")
    return a, b


def _fold(train_steps, start: int, args):
    """``train_steps(lo, hi)`` over steps [start, args.steps), the steps
    --profile_steps names captured by ``jax.profiler.trace`` into
    --profile_dir; returns the last call's state."""
    if not args.profile_dir:
        return train_steps(start, args.steps)
    import jax
    a, b = (min(max(x, start), args.steps) for x in args.profile_steps)
    train_steps(start, a)
    with jax.profiler.trace(args.profile_dir):
        train_steps(a, b)
    return train_steps(b, args.steps)


def _train_pipeline(cfg, pcfg, rc, mesh, args):
    """1F1B pipeline path: per-pod stage state, host-side schedule executor.

    The step function is NOT jitted (the per-stage closures inside the
    runner are); train/loop.py drives it unchanged because the state leaves
    (lists of per-stage trees) are ordinary pytrees.
    """
    import jax
    import jax.numpy as jnp
    from repro.checkpoint.manager import make_manager
    from repro.config import CheckpointConfig
    from repro.data.synthetic import Prefetcher, SyntheticLM
    from repro.models import lm
    from repro.parallel import pipeline as PP
    from repro.runtime.fault import StepTimer
    from repro.train import loop as train_loop

    gcfg = _guard_cfg(args)
    runner, step = PP.build_pipeline_train_step(
        cfg, pcfg, rc, mesh, total_steps=args.steps,
        compute_dtype=jnp.bfloat16, guard=gcfg)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    sparams = runner.place_params(params)
    sopt = runner.init_opt(sparams)
    del params

    # one checkpoint writer per pipeline stage/pod — each pod persists the
    # stage it already holds — unless --ckpt-writers overrides
    writers = args.ckpt_writers or pcfg.pipeline_stages
    ccfg = CheckpointConfig(every=args.ckpt_every, keep=args.ckpt_keep,
                            async_=not args.ckpt_sync, writers=writers,
                            quorum=args.ckpt_quorum or None,
                            verify=not args.ckpt_no_verify,
                            writer_procs=args.ckpt_procs,
                            writer_timeout=args.ckpt_writer_timeout)
    ckpt = (make_manager(args.ckpt_dir, ccfg,
                         writer_map=PP.stage_writer_map(writers))
            if args.ckpt_dir else None)
    start = 0
    if ckpt is not None and ckpt.latest_step() is not None:
        # per-stage state is an ordinary pytree (lists of stage trees), so
        # the manager restores it shard-for-shard onto the sub-meshes
        restored, start = ckpt.restore(
            {"params": sparams, "opt_state": sopt})
        sparams, sopt = restored["params"], restored["opt_state"]
        print(f"restored pipeline checkpoint at step {start}")

    ds = SyntheticLM(cfg.vocab_size, args.seq, args.batch)
    tguard, wd, dix, stream = _guard_runtime(args, gcfg, args.ckpt_dir,
                                             start, ds.batch_at)
    it = Prefetcher(stream)
    state = {"params": sparams, "opt_state": sopt}
    timer = StepTimer()
    try:
        state = _fold(lambda lo, hi: train_loop.train(
            step, state, it, start_step=lo, num_steps=hi, ckpt=ckpt,
            ckpt_every=ccfg.every, timer=timer, guard=tguard, watchdog=wd,
            data_index_fn=dix), start, args)
    finally:
        if wd is not None:
            wd.close()
        it.close()
    if ckpt is not None:
        ckpt.close()                 # train() already drained in-flight saves
    h = state["history"]
    print(f"pipeline[{pcfg.pods} stages x ({pcfg.mx}x{pcfg.my})] "
          f"final loss {h[-1][1]:.4f} (first {h[0][1]:.4f})")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--num-layers", type=int, default=None,
                    help="train this many of the architecture's layers at "
                         "its widths (default: the registry's depth), as "
                         "one pipeline stage of a deeper deployment holds")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--strategy", default="hecaton")
    ap.add_argument("--overlap", default="none",
                    choices=["none", "ring", "bidir", "fused"],
                    help="NoP comm/compute overlap of the collectives "
                         "(docs/DESIGN.md §1); fused runs the Pallas "
                         "remote-DMA ring kernels on a TPU")
    ap.add_argument("--comm-dtype", default="bf16", choices=["bf16", "int8"],
                    help="ring-collective wire dtype: int8 quantizes each "
                         "hop's shard (docs/DESIGN.md §11)")
    ap.add_argument("--mesh-devices", type=int, default=1)
    ap.add_argument("--data", type=int, default=2)
    ap.add_argument("--mx", type=int, default=2)
    ap.add_argument("--my", type=int, default=2)
    ap.add_argument("--pods", type=int, default=1,
                    help="number of packages; with --pod-role pipeline each "
                         "pod runs one 1F1B stage of the block stack")
    ap.add_argument("--pod-role", default="data",
                    choices=("data", "pipeline"))
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="fusion",
                    choices=["none", "fusion", "full"],
                    help="activation-recompute policy (core/schedule.py): "
                         "full keeps only block inputs for the backward")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--ckpt-keep", type=int, default=3)
    ap.add_argument("--ckpt-sync", action="store_true",
                    help="blocking saves (default: async double-buffered "
                         "writer that hides the persistence stall)")
    ap.add_argument("--ckpt-writers", type=int, default=0,
                    help="logical checkpoint writers (0 = auto: one per "
                         "pipeline stage, else 1)")
    ap.add_argument("--ckpt-quorum", type=int, default=0,
                    help="partial manifests required before a step "
                         "publishes (0 = all writers)")
    ap.add_argument("--ckpt-no-verify", action="store_true",
                    help="skip per-shard checksum verification on restore")
    ap.add_argument("--ckpt-procs", action="store_true",
                    help="run each logical checkpoint writer as its own OS "
                         "process (heartbeat leases + orphan-shard "
                         "reassignment; runtime/procs.py, docs/DESIGN.md §9)")
    ap.add_argument("--ckpt-writer-timeout", type=float, default=5.0,
                    help="heartbeat-lease deadline in seconds: a writer "
                         "process whose heartbeat stalls longer is SIGKILL-"
                         "fenced and its shard range reassigned")
    ap.add_argument("--guard", action="store_true",
                    help="arm the self-healing guard: in-graph NaN/spike "
                         "skip-update + loss-spike divergence detection "
                         "(docs/DESIGN.md §8)")
    ap.add_argument("--guard-spike-factor", type=float, default=10.0,
                    help="skip the update when grad norm exceeds this "
                         "multiple of its EWMA")
    ap.add_argument("--guard-loss-spike", type=float, default=2.0,
                    help="a step whose loss exceeds this multiple of the "
                         "loss EWMA counts toward divergence patience")
    ap.add_argument("--guard-patience", type=int, default=3,
                    help="consecutive spiking losses before DivergenceError")
    ap.add_argument("--guard-skip-cap", type=int, default=3,
                    help="consecutive in-graph skipped updates before "
                         "DivergenceError")
    ap.add_argument("--hang-timeout", type=float, default=0.0,
                    help="seconds before an armed step counts as hung "
                         "(0 = watchdog off)")
    ap.add_argument("--no-rollback", action="store_true",
                    help="on divergence, restart WITHOUT retiring poisoned "
                         "checkpoints / blocklisting the poison window")
    ap.add_argument("--profile_dir", default=None,
                    help="write a JAX profiler trace (.xplane.pb) of the "
                         "--profile_steps steps here: device ops beside "
                         "the loop's host spans (train/loop.py)")
    ap.add_argument("--profile_steps", type=_step_range, default=(1, 3),
                    metavar="A:B",
                    help="steps A..B-1 to capture with --profile_dir")
    return ap


def run(args, *, devices=None, on_start=None) -> dict:
    """Train as the command line ``args`` say; returns the run's record.

    ``devices`` (default ``jax.devices()``) are the chips the mesh is built
    from; without a mesh the state lives on the first of them.
    ``on_start(params, opt_state, batch)`` is called once before the first
    step with the initial fp32 master params, the AdamW state and the first
    batch (a reference check's hook: the step donates them).  The record
    holds ``history`` (per-step ``(step, loss)``), ``step_s`` (per-step
    seconds, compile excluded), ``compile_s``, the ``compiled`` step and
    ``fallbacks``, the ``core.overlap.fused_fallbacks`` of its trace."""
    import time
    from collections import Counter

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.checkpoint.manager import make_manager
    from repro.config import (CheckpointConfig, ParallelConfig, RunConfig,
                              get_config, get_smoke_config)
    from repro.core import overlap as OV
    from repro.data.synthetic import Prefetcher, SyntheticLM
    from repro.launch.mesh import make_small_mesh
    from repro.optim import adamw
    from repro.parallel import specs as SP
    from repro.runtime.fault import StepTimer
    from repro.train import loop as train_loop
    from repro.train import step as TS
    from repro.models import attention as ATT, lm

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.num_layers:
        cfg = cfg.scaled(num_layers=args.num_layers)
    rc = RunConfig("custom", "train", args.seq, args.batch, lr=args.lr)
    mesh = None
    pcfg = ParallelConfig(strategy=args.strategy, data=args.data,
                          model=args.mx * args.my, mx=args.mx, my=args.my,
                          pods=args.pods, pod_axis_role=args.pod_role,
                          microbatches=args.microbatches, zero1=True,
                          remat=args.remat, overlap=args.overlap,
                          comm_dtype=args.comm_dtype)
    if args.mesh_devices > 1 or args.pods > 1:
        mesh = make_small_mesh(args.strategy, args.data, args.mx, args.my,
                               pods=args.pods, devices=devices)

    if pcfg.pipeline_enabled:
        _train_pipeline(cfg, pcfg, rc, mesh, args)
        return {}

    extras = {}
    if cfg.family == "vlm":
        extras["patches"] = (cfg.frontend_stub_len, cfg.d_model)
    if cfg.family == "audio":
        extras["frames"] = (cfg.frontend_stub_len, cfg.d_model)
    ds = SyntheticLM(cfg.vocab_size, args.seq, args.batch, extras=extras)
    # params and AdamW state are made where they live, each device making
    # only its own shard: on a mesh no device ever holds the whole model
    abstract = jax.eval_shape(lambda k: lm.init_params(cfg, k),
                              jax.random.PRNGKey(0))
    bshard = None
    if mesh is None:
        pshard = oshard = jax.sharding.SingleDeviceSharding(
            (devices or jax.devices())[0])
    else:
        pspecs = SP.param_specs(abstract, mesh, pcfg)
        pshard = SP.sharding_tree(pspecs, mesh)
        oshard = SP.sharding_tree(
            SP.opt_state_specs(pspecs, abstract, mesh, pcfg), mesh)
        bshard = SP.sharding_tree(
            SP.batch_specs(mesh, pcfg, microbatched=False,
                           keys=tuple(ds.batch_at(0)), seq_len=args.seq),
            mesh)

    def init(key):
        p = lm.init_params(cfg, key)
        return p, adamw.init(p)

    params, opt_state = jax.jit(init, out_shardings=(pshard, oshard))(
        jax.random.PRNGKey(0))

    gcfg = _guard_cfg(args)
    # bf16 compute / fp32 master (ModelConfig.dtype_note) on any device count
    ts = TS.build_train_step(cfg, pcfg, rc, mesh, compute_dtype=jnp.bfloat16,
                             guard=gcfg)
    if mesh is None:
        ts = jax.jit(ts, donate_argnums=(0, 1))
    else:
        ts = jax.jit(ts, donate_argnums=(0, 1),
                     in_shardings=(pshard, oshard, bshard),
                     out_shardings=(pshard, oshard,
                                    NamedSharding(mesh, P())))

    ccfg = CheckpointConfig(every=args.ckpt_every, keep=args.ckpt_keep,
                            async_=not args.ckpt_sync,
                            writers=args.ckpt_writers or 1,
                            quorum=args.ckpt_quorum or None,
                            verify=not args.ckpt_no_verify,
                            writer_procs=args.ckpt_procs,
                            writer_timeout=args.ckpt_writer_timeout)
    ckpt = make_manager(args.ckpt_dir, ccfg) if args.ckpt_dir else None
    start = 0
    if ckpt is not None and ckpt.latest_step() is not None:
        restored, start = ckpt.restore(
            {"params": params, "opt_state": opt_state})
        params, opt_state = restored["params"], restored["opt_state"]
        print(f"restored checkpoint at step {start}")

    tguard, wd, dix, stream = _guard_runtime(args, gcfg, args.ckpt_dir,
                                             start, ds.batch_at)
    batch0 = ds.batch_at(dix(start))
    if on_start is not None:
        on_start(params, opt_state, batch0)
    t0 = time.perf_counter()
    ATT.sdpa_paths.clear()
    OV.fused_fallbacks.clear()
    compiled = ts.lower(params, opt_state, batch0).compile()
    compile_s = time.perf_counter() - t0
    print(f"compiled train step in {compile_s:.1f}s: "
          f"{compiled.memory_analysis()}")
    paths = Counter((path, reason) for path, _, reason in ATT.sdpa_paths)
    print("attention cores traced: " + ", ".join(
        f"{n} {path}" + (f" ({reason})" if reason else "")
        for (path, reason), n in sorted(paths.items(), key=str)))
    fallbacks = list(OV.fused_fallbacks)
    print(f"fused collectives that took the ppermute ring: {len(fallbacks)}"
          + "".join(f"; {op} x{x} w{w}" for op, x, w in fallbacks))
    it = Prefetcher(stream, sharding=bshard)
    state = {"params": params, "opt_state": opt_state}
    timer = StepTimer()
    try:
        state = _fold(lambda lo, hi: train_loop.train(
            compiled, state, it, start_step=lo, num_steps=hi, ckpt=ckpt,
            ckpt_every=ccfg.every, timer=timer, guard=tguard, watchdog=wd,
            data_index_fn=dix), start, args)
    finally:
        if wd is not None:
            wd.close()
        it.close()
    if ckpt is not None:
        ckpt.close()                 # train() already drained in-flight saves
    h = state["history"]
    print(f"final loss {h[-1][1]:.4f} (first {h[0][1]:.4f})")
    return {"history": h, "step_s": state["step_s"], "compile_s": compile_s,
            "compiled": compiled, "fallbacks": fallbacks}


def main(argv=None):
    args = build_parser().parse_args(argv)
    _maybe_respawn(max(args.mesh_devices,
                       args.pods * args.data * args.mx * args.my
                       if args.pods > 1 else args.mesh_devices))
    from repro import compat
    compat.enable_compile_cache()
    run(args)


if __name__ == "__main__":
    main()
