"""Print a benchmark cell's train step as the TPU compiler builds it for a
described v5e chip, with its metadata taken out.  Needs no chip.

    JAX_PLATFORMS=cpu python tools/step_hlo.py --workload qwen3-0.6b.train_4k \
        [--step decode] [--root CHECKOUT] | sha256sum

Two checkouts whose steps print the same text compile to the same program:
a change that only names operations (``jax.named_scope``) leaves it as it
was.  ``--step decode`` prints instead the serve decode step of the cell's
model (one token for each of the cell's batch rows, against a dense KV cache
of the cell's sequence length): a change to the training path only leaves
it as it was.  Taken out: the debug tables between the ``HloModule`` line
and the first computation (source files, functions, stack frames), every
instruction's ``metadata={...}``, and the source locations that a Pallas
kernel's serialized body carries (each body is printed without them and
replaced by that text's sha256).
"""

import argparse
import base64
import hashlib
import os
import re
import sys
from pathlib import Path


def strip_metadata(hlo_text: str) -> str:
    lines = hlo_text.splitlines()
    first = next(i for i, line in enumerate(lines)
                 if line.startswith(("%", "ENTRY")))
    body = "\n".join(lines[:1] + lines[first:])
    body = re.sub(r",? metadata=\{[^}]*\}", "", body)
    return re.sub(r'"body":"([^"]*)"', _kernel_digest, body) + "\n"


def _kernel_digest(m) -> str:
    """A Mosaic kernel body (MLIR bytecode, base64) as the sha256 of its
    text without debug locations: the call site's file, line and function
    change with the checkout and the caller, the kernel does not."""
    from jax._src.lib import tpu
    from jaxlib.mlir import ir

    with ir.Context() as ctx:
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True
        asm = ir.Module.parse(base64.b64decode(m.group(1))).operation.get_asm(
            enable_debug_info=False)
    return f'"body":"sha256:{hashlib.sha256(asm.encode()).hexdigest()}"'


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--step", choices=("train", "decode"), default="train")
    ap.add_argument("--root", default=str(Path(__file__).resolve().parents[1]),
                    help="the checkout whose step to compile")
    args = ap.parse_args(argv)
    root = Path(args.root).resolve()
    sys.path[:0] = [str(root), str(root / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from bench import spec, train_cell

    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.cell(args.workload, root=root)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    if args.step == "decode":
        text = decode_step(train_cell.model_config(cell.config),
                           cell.traffic, topo.devices[0])
    else:
        prog = train_cell.Program(cell, list(topo.devices)[:cell.chips])
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        p_abs, o_abs = jax.eval_shape(prog._init, key)
        b_abs = jax.eval_shape(prog._batch, key, jnp.int32(0))
        text = prog._step.lower(p_abs, o_abs, b_abs).compile().as_text()
    sys.stdout.write(strip_metadata(text))
    return 0


def decode_step(cfg, traffic: dict, device) -> str:
    """The compiled text of the serve decode step on one device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from repro.config import ParallelConfig, RunConfig
    from repro.models import lm
    from repro.serve import step as SS

    B, S = traffic["batch"], traffic["seq_len"]
    one = SingleDeviceSharding(device)

    def on_device(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)
    step = SS.build_decode_step(cfg, ParallelConfig(data=1, model=1, mx=1,
                                                    my=1),
                                RunConfig("serve", "decode", S, B), None)
    params = jax.eval_shape(lambda: lm.init_params(cfg,
                                                   jax.random.PRNGKey(0)))
    caches = jax.eval_shape(lambda: lm.init_caches(cfg, B, S, jnp.bfloat16))
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one)
    return jax.jit(step).lower(on_device(params), on_device(caches), tok,
                               tok).compile().as_text()


if __name__ == "__main__":
    sys.exit(main())
