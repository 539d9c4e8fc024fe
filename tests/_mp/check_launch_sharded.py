"""Subprocess check: ``launch/train.py::run`` on a 2x2 Hecaton grid makes
every leaf of the params and AdamW state with the sharding that
``param_specs`` / ``opt_state_specs`` give it, and its step-0 loss matches
one device's.  granite-34b's smoke widths with ``--num-layers 3`` (the
registry's smoke depth is 2), ``overlap`` none and fused.

Run with XLA_FLAGS=--xla_force_host_platform_device_count=4.
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import math

import jax

from repro.config import ParallelConfig, get_smoke_config
from repro.launch import train
from repro.launch.mesh import make_small_mesh
from repro.models import lm
from repro.parallel import specs as SP

# Both sides compute in bf16 over fp32 master weights.  The 2x2 step sums
# its matmuls' partial products across chips, in another order and after
# other bf16 roundings than one device does, so the losses differ by
# rounding: a few 1e-5 relative at these widths.  1e-3 is a quarter of
# bf16's relative step (2^-8) and lies far below what a lost shard or a
# skipped exchange gives (the loss of a wrong model, off by 1e-2 or more).
LOSS_RTOL = 1e-3
LAYERS = 3
SHAPE = ("--smoke", "--arch", "granite-34b", "--num-layers", str(LAYERS),
         "--steps", "2", "--batch", "2", "--seq", "32", "--remat", "full")
GRID = ("--mesh-devices", "4", "--data", "1", "--mx", "2", "--my", "2")


def expected_shardings():
    cfg = get_smoke_config("granite-34b").scaled(num_layers=LAYERS)
    mesh = make_small_mesh("hecaton", 1, 2, 2)
    pcfg = ParallelConfig(strategy="hecaton", data=1, model=4, mx=2, my=2,
                          microbatches=1, zero1=True)
    abstract = jax.eval_shape(lambda k: lm.init_params(cfg, k),
                              jax.random.PRNGKey(0))
    pspecs = SP.param_specs(abstract, mesh, pcfg)
    return (SP.sharding_tree(pspecs, mesh),
            SP.sharding_tree(SP.opt_state_specs(pspecs, abstract, mesh,
                                                pcfg), mesh))


def run(*argv, devices=None):
    seen = {}

    def on_start(params, opt_state, batch):
        seen["state"] = (params, opt_state)
        seen["blocks"] = params["blocks"]["mlp"]["w1"].shape[0]

    rec = train.run(train.build_parser().parse_args(SHAPE + argv),
                    devices=devices, on_start=on_start)
    losses = [loss for _, loss in rec["history"]]
    assert len(losses) == 2 and all(math.isfinite(v) for v in losses), losses
    assert seen["blocks"] == LAYERS, seen["blocks"]
    # the smoke widths fit the fused kernels' gate: nothing falls back
    assert rec["fallbacks"] == [], rec["fallbacks"]
    return seen["state"], losses


def main():
    devices = jax.devices()
    assert len(devices) == 4, devices
    (p1, _), one = run(devices=devices[:1])
    for leaf in jax.tree.leaves(p1):
        assert leaf.sharding == jax.sharding.SingleDeviceSharding(devices[0])
    want = expected_shardings()
    for mode in ("none", "fused"):
        state, losses = run(*GRID, "--overlap", mode)
        assert jax.tree.structure(state) == jax.tree.structure(want)
        leaves = jax.tree.leaves(state)
        wrong = [(a.sharding, w) for a, w in zip(leaves, jax.tree.leaves(want))
                 if not a.sharding.is_equivalent_to(w, a.ndim)]
        assert not wrong, (mode, wrong)
        split = sum(a.sharding.shard_shape(a.shape) != a.shape
                    for a in leaves)
        err = abs(losses[0] - one[0]) / abs(one[0])
        assert err <= LOSS_RTOL, (mode, losses, one, err)
        print(f"{mode}: {len(leaves)} leaves as specified ({split} split "
              f"over the grid); step-0 loss {losses[0]!r} vs one device "
              f"{one[0]!r}, rel err {err!r}")
    print("LAUNCH SHARDED STATE OK")


if __name__ == "__main__":
    main()
