"""Compile a cell's programs for a described TPU topology, without a chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload <cell> \
        [--topology v5e:2x2] [--layers N] [--reference 0|1]

A cell that ``BENCHMARK.json`` does not list is built from its
configuration and traffic files.  Builds the cell's train step exactly as
a run does, on the first ``chips``
devices of the described topology, and compiles it with the TPU compiler;
then the reference's gradient and update programs.  Prints each program's
per-device ``memory_analysis`` (and whether it fits the chip's memory), the
count of Pallas kernels (``tpu_custom_call``) in the step and the
collectives that fell back from the fused ring kernels.  ``--layers``
compiles the cell at another depth, to find the deepest one that fits.
Nothing runs, so this says nothing of results or speed.
"""

import argparse
import copy
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


# what the v5e runtime lets one program hold: on the chip, a step that
# needed "16.16G of 15.75G HBM" was refused
USABLE_HBM = 15.75 * 2 ** 30


def mem(compiled) -> dict:
    ma = compiled.memory_analysis()
    out = {k: int(getattr(ma, f"{k}_size_in_bytes"))
           for k in ("argument", "output", "temp", "alias")}
    out["total"] = (out["argument"] + out["output"] + out["temp"]
                    - out["alias"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--reference", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from bench import reference, spec, train_cell

    jax.config.update("jax_enable_compilation_cache", False)
    try:
        cell = spec.cell(args.workload)
    except KeyError:
        cell = spec.unlisted_cell(args.workload)
    if args.layers:
        conf = copy.deepcopy(cell.config)
        conf["program"]["num_layers"] = args.layers
        for field, key in conf["program_from"].items():
            conf[key] = conf["program"][field]
        cell = spec.Cell(cell.name, conf, cell.traffic, cell.limits,
                         cell.chips, cell.end_to_end, cell.per_layer)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    devices = list(topo.devices)[:cell.chips]
    hbm = USABLE_HBM
    prog = train_cell.Program(cell, devices)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    p_abs, o_abs = jax.eval_shape(prog._init, key)
    b_abs = jax.eval_shape(prog._batch, key, jnp.int32(0))
    prog._fallbacks.clear()
    step = prog._step.lower(p_abs, o_abs, b_abs).compile()
    report = {"workload": cell.name, "topology": args.topology,
              "device_kind": devices[0].device_kind,
              "layers": cell.config["program"]["num_layers"],
              "step": mem(step),
              "tpu_custom_call": step.as_text().count("tpu_custom_call"),
              "fused_fallbacks": [list(map(str, f))
                                  for f in prog._fallbacks]}
    report["step"]["fits"] = report["step"]["total"] <= hbm
    report["change_norms"] = mem(prog._change.lower(p_abs, key).compile())
    if args.reference:
        ref = reference.Reference(prog.m, prog.opt,
                                  shardings=train_cell.ref_sharding(devices))
        rp = jax.eval_shape(ref._init, key)
        t = cell.traffic
        tok = jax.ShapeDtypeStruct(
            (t["batch"], t["seq_len"]), jnp.int32,
            sharding=train_cell.ref_sharding(devices)(
                (t["batch"], t["seq_len"])))
        grad = ref._grad.lower(rp, tok, tok).compile()
        f32 = jax.ShapeDtypeStruct((), jnp.float32)
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        upd = ref._update.lower(i32, f32, rp, rp, rp, rp).compile()
        report["reference_grad"] = mem(grad)
        report["reference_update"] = mem(upd)
        # the update runs with params, gradient and both moments resident
        report["reference_grad"]["fits"] = (
            report["reference_grad"]["total"]
            + 2 * report["reference_grad"]["argument"] <= hbm)
    print(json.dumps(report, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
