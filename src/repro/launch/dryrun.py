import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import (jax locks the device count
# on first init).  REPRO_DRYRUN_DEVICES overrides for scaled-down testing.
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

"""Multi-pod dry-run driver.

For every (arch x shape x mesh x strategy) cell:
  * builds the real train/prefill/decode step,
  * ``jax.jit(...).lower(**ShapeDtypeStructs).compile()`` on the production mesh
    (16x16 single pod / 2x16x16 multi-pod; hecaton refactors model=16 -> 4x4),
  * writes one JSON per cell: lower and compile seconds,
    ``memory_analysis()`` (proves it fits) and ``cost_analysis()``.

Usage:
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun
"""

import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro import config as C
from repro.config import ParallelConfig, get_config, shape_cells_for
from repro.core import schedule
from repro.launch import inputs as I
from repro.launch import mesh as M
from repro.models import lm
from repro.optim import adamw
from repro.parallel import sharding as shd
from repro.parallel import specs as SP
from repro.serve import step as serve_step
from repro.train import step as train_step
from jax.sharding import NamedSharding, PartitionSpec as P

FSDP_THRESHOLD_BYTES = 2e9      # params(fp32)/model-shard above this => FSDP


def make_pcfg(cfg, rc, strategy: str, multi_pod: bool) -> ParallelConfig:
    params_bytes = cfg.param_count() * 4
    fsdp = params_bytes / 16 > FSDP_THRESHOLD_BYTES
    micro, remat = 1, "none"
    n_data = 32 if multi_pod else 16      # pod axis is data-parallel
    if rc.mode == "train":
        micro, remat = schedule.choose_microbatches(
            rc.global_batch, rc.seq_len, cfg.d_model, n_data_shards=n_data,
            n_token_shards=16, num_layers=cfg.num_layers + cfg.encoder_layers,
            vocab=cfg.padded_vocab, act_budget_bytes=2e9)
    if os.environ.get("REPRO_MICRO_OVERRIDE"):
        micro = int(os.environ["REPRO_MICRO_OVERRIDE"])
    return ParallelConfig(strategy=strategy, data=16, model=16, mx=4, my=4,
                          pods=2 if multi_pod else 1, fsdp=fsdp,
                          microbatches=micro, remat=remat,
                          attn_layout=os.environ.get("REPRO_ATTN_LAYOUT",
                                                     "auto"))


def _batch_sharding(mesh, pcfg, batch_structs, *, global_batch):
    ax = shd.axis_info(mesh, pcfg.strategy)
    d = shd._one(ax.data_axes)
    if global_batch % ax.n_data:
        d = None                      # e.g. long_500k batch=1: data axis idle
    if pcfg.strategy == "hecaton":
        seq_ax = ax.t_ax
    elif pcfg.residual == "seq":
        # megatron seq-sharded residual: inputs arrive token-sharded over the
        # model axis so the embedding scatter lands in the canonical layout
        seq_ax = shd._one(ax.model_axes)
    else:
        seq_ax = None

    def s_ok(extent):
        # shard a sequence-like dim only when it divides the token ring
        # (e.g. whisper's 1500 frames do NOT divide a 16-way model ring)
        return (seq_ax is not None and extent > 1
                and extent % ax.size(seq_ax) == 0)

    out = {}
    for k, v in batch_structs.items():
        rank = len(v.shape)
        if k == "dropout_rng":
            spec = P()                # PRNG key: replicated, never sharded
        elif k in ("patches", "frames"):
            spec = P(d, seq_ax if s_ok(v.shape[1]) else None, None)
        elif rank == 2:
            spec = P(d, seq_ax if s_ok(v.shape[1]) else None)
        else:
            spec = P(d)
        out[k] = NamedSharding(mesh, spec)
    return out


def lower_cell(arch: str, shape: str, strategy: str, multi_pod: bool):
    cfg = get_config(arch)
    rc = C.SHAPES[shape]
    pcfg = make_pcfg(cfg, rc, strategy, multi_pod)
    mesh = M.make_mesh_for(strategy, multi_pod=multi_pod)
    chips = int(mesh.devices.size)
    mesh_name = "multi" if multi_pod else "single"

    pshape = I.params_shape(cfg)
    pspecs = SP.param_specs(pshape, mesh, pcfg)
    pshard = SP.sharding_tree(pspecs, mesh)

    if rc.mode == "train":
        ts = train_step.build_train_step(cfg, pcfg, rc, mesh)
        oshape = jax.eval_shape(adamw.init, pshape)
        ospecs = SP.opt_state_specs(pspecs, pshape, mesh, pcfg)
        oshard = SP.sharding_tree(ospecs, mesh)
        bstructs = I.train_input_specs(cfg, rc)
        bshard = _batch_sharding(mesh, pcfg, bstructs,
                                 global_batch=rc.global_batch)
        fn = jax.jit(ts, in_shardings=(pshard, oshard, bshard),
                     out_shardings=(pshard, oshard, None),
                     donate_argnums=(0, 1))
        lowered = fn.lower(pshape, oshape, bstructs)
    elif rc.mode == "prefill":
        pf = serve_step.build_prefill(cfg, pcfg, rc, mesh)
        bstructs = I.prefill_input_specs(cfg, rc)
        bshard = _batch_sharding(mesh, pcfg, bstructs,
                                 global_batch=rc.global_batch)
        fn = jax.jit(pf, in_shardings=(pshard, bshard))
        lowered = fn.lower(pshape, bstructs)
    else:
        ds = serve_step.build_decode_step(cfg, pcfg, rc, mesh)
        cstructs = I.decode_cache_specs(cfg, rc)
        cspecs = serve_step.cache_specs(cfg, pcfg, mesh, rc.global_batch)
        cshard = SP.sharding_tree(cspecs, mesh)
        bstructs = I.decode_input_specs(cfg, rc)
        bshard = _batch_sharding(mesh, pcfg, bstructs,
                                 global_batch=rc.global_batch)
        fn = jax.jit(ds, in_shardings=(pshard, cshard, bshard["tokens"],
                                       bshard["positions"]),
                     donate_argnums=(1,))
        lowered = fn.lower(pshape, cstructs, bstructs["tokens"],
                           bstructs["positions"])
    return lowered, dict(cfg=cfg, rc=rc, pcfg=pcfg, chips=chips,
                         mesh_name=mesh_name)


def run_cell(arch, shape, strategy, multi_pod, out_dir):
    t0 = time.time()
    tag = f"{arch}.{shape}.{strategy}.{'multi' if multi_pod else 'single'}"
    try:
        lowered, meta = lower_cell(arch, shape, strategy, multi_pod)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        ma = compiled.memory_analysis()
        from repro.compat import cost_analysis_dict
        ca = cost_analysis_dict(compiled)
        d = {"arch": arch, "shape": shape, "mesh": meta["mesh_name"],
             "strategy": strategy, "chips": meta["chips"],
             "note": f"fsdp={meta['pcfg'].fsdp} "
                     f"micro={meta['pcfg'].microbatches}"}
        d["lower_s"] = round(t_lower, 1)
        d["compile_s"] = round(t_compile, 1)
        d["xla_cost_analysis"] = {k: ca.get(k) for k in
                                  ("flops", "bytes accessed") if k in ca}
        d["memory_analysis"] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "peak_bytes_per_chip": ma.argument_size_in_bytes
            + ma.temp_size_in_bytes + ma.output_size_in_bytes,
        }
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(d, f, indent=1, default=str)
        print(f"[OK] {tag}: "
              f"args/chip={ma.argument_size_in_bytes/2**30:.2f}GiB "
              f"temp/chip={ma.temp_size_in_bytes/2**30:.2f}GiB "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)", flush=True)
        return True
    except Exception as e:
        print(f"[FAIL] {tag}: {type(e).__name__}: {e}", flush=True)
        traceback.print_exc()
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, tag + ".FAIL.txt"), "w") as f:
            f.write(traceback.format_exc())
        return False


ASSIGNED = ["mamba2-130m", "qwen3-0.6b", "nemotron-4-340b", "granite-34b",
            "minicpm3-4b", "paligemma-3b", "whisper-small",
            "granite-moe-3b-a800m", "grok-1-314b", "zamba2-1.2b"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--strategy", default="hecaton",
                    choices=["hecaton", "megatron"])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    archs = ASSIGNED if (args.all or args.arch is None) else [args.arch]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    ok = fail = 0
    for arch in archs:
        cfg = get_config(arch)
        shapes = ([args.shape] if args.shape
                  else list(shape_cells_for(cfg)))
        for shape in shapes:
            if shape not in shape_cells_for(cfg):
                print(f"[SKIP] {arch}.{shape}: long_500k skipped for "
                      f"full-attention arch (see docs/DESIGN.md §4)", flush=True)
                continue
            for mp in meshes:
                if run_cell(arch, shape, args.strategy, mp, args.out):
                    ok += 1
                else:
                    fail += 1
    print(f"dryrun done: {ok} ok, {fail} failed")
    sys.exit(1 if fail else 0)


if __name__ == "__main__":
    main()
