"""Readings that a cell's correctness limits are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 1,2,3 --out calib.json [--trace-out f]

In one process, for every seed of ``--seeds``: the program's first steps as
a run drives them (compiled once), then the plain reference's, and the
numbers ``bench/check.py`` compares.  For every seed of ``--control-seeds``
(a subset of ``--seeds``) the same numbers of:

- ``control``: the reference, its matmul operands rounded to float8 (e4m3),
  in the program's place: the precision below the bf16 the cell computes in;
- ``half_batch``: the reference on the first half of each batch, the mean
  taken over it;
- ``no_exchange`` (cells on more than one chip): the reference with each
  MLP's output reduction over the second half of the feed-forward width
  left out, as a 2x2 tile gives when the reduction between chips is skipped.

A state left unchanged reads 1 on the gradient and change numbers by their
definition, and needs no run.  ``--trace-out`` also records a profiler
trace of two steps of the window and copies its ``.xplane.pb`` there.
"""

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", required=True)
    ap.add_argument("--trace-out", default="")
    args = ap.parse_args(argv)

    from bench import run as bench_run
    from bench import spec
    import jax

    bench_run.enable_cache(jax)
    cell = spec.cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("calibrate: needs the cell's TPU chips", file=sys.stderr)
        return 2
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    calibrate(cell, devices[:cell.chips], seeds, controls, args.out,
              args.trace_out)
    return 0


def calibrate(cell, devices, seeds, controls, out_path, trace_out="") -> dict:
    """Writes and returns the readings of ``cell`` on ``devices``."""
    from bench import check, reference, train_cell, trace_reduce
    import jax
    import jax.numpy as jnp

    prog = train_cell.Program(cell, devices)
    n = cell.traffic["check_steps"]
    shard = train_cell.ref_sharding(devices)
    out = {"workload": cell.name, "device_kind": devices[0].device_kind,
           "program": {}, "control": {}, "half_batch": {},
           "no_exchange": {}, "seconds": {}}

    kinds = {"reference": {}, "control": {"dt": jnp.float8_e4m3fn},
             "half_batch": {}}
    if cell.chips > 1:
        kinds["no_exchange"] = {"drop_partial": True}
    refs = {k: reference.Reference(prog.m, prog.opt, shardings=shard, **kw)
            for k, kw in kinds.items()}

    def ref_run(kind, key, batch_fn):
        t0 = time.perf_counter()
        r = refs[kind].run(key, batch_fn, n)
        return r, time.perf_counter() - t0

    for seed in seeds:
        t0 = time.perf_counter()
        prog.setup(seed)
        got = prog.first_steps(n)
        if trace_out and seed == seeds[0]:
            tmp = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(tmp)
            train_cell.window(prog, n, 0.0)
            train_cell.window(prog, n + 1, 0.0)
            jax.profiler.stop_trace()
            shutil.copy(trace_reduce.find_xplane(tmp), trace_out)
            shutil.rmtree(tmp, ignore_errors=True)
        prog.free()
        t_prog = time.perf_counter() - t0
        batches = train_cell._ref_batches(prog, devices)
        want, t_ref = ref_run("reference", prog.pkey, batches)
        out["program"][seed] = {"numbers": check.numbers(got, want),
                                "program": got, "reference": want}
        out["seconds"][seed] = {"program": t_prog, "reference": t_ref}
        print(f"seed {seed}: {out['program'][seed]['numbers']} "
              f"(program {t_prog:.1f} s, reference {t_ref:.1f} s)",
              flush=True)
        if seed not in controls:
            continue
        for name in kinds:
            if name == "reference":
                continue
            fn = _half(batches) if name == "half_batch" else batches
            r, t = ref_run(name, prog.pkey, fn)
            out[name][seed] = {"numbers": check.numbers(r, want),
                               "reading": r, "seconds": t}
            print(f"seed {seed} {name}: {out[name][seed]['numbers']}",
                  flush=True)
        _write(out, out_path)
    _write(out, out_path)
    return out


def _write(out, path) -> None:
    with open(path, "w") as f:
        json.dump(out, f, indent=1)


def _half(batch_fn):
    def fn(i):
        tokens, labels = batch_fn(i)
        h = tokens.shape[0] // 2
        return tokens[:h], labels[:h]
    return fn


if __name__ == "__main__":
    sys.exit(main())
