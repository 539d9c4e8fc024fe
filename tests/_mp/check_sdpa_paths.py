"""Subprocess check: a train step on a 2x2 Hecaton mesh records the jnp
``_sdpa`` for every attention core (reason ``mesh``), at a head width and
sequence the flash kernel would otherwise take.

Run with XLA_FLAGS=--xla_force_host_platform_device_count=4.
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import ModelConfig, ParallelConfig, RunConfig
from repro.launch.mesh import make_small_mesh
from repro.models import attention as ATT
from repro.models import lm
from repro.optim import adamw
from repro.parallel import specs as SP
from repro.train import step as TS


def main():
    assert len(jax.devices()) == 4, jax.devices()
    mesh = make_small_mesh("hecaton", 1, 2, 2)
    pcfg = ParallelConfig(strategy="hecaton", data=1, model=4, mx=2, my=2,
                          microbatches=1, zero1=True)
    cfg = ModelConfig(name="paths-test", family="dense", num_layers=2,
                      d_model=64, num_heads=4, num_kv_heads=2, head_dim=128,
                      d_ff=128, vocab_size=256, qk_norm=True)
    S, B = 256, 2
    rc = RunConfig("t", "train", S, B)
    params = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))
    pspecs = SP.param_specs(params, mesh, pcfg)
    ospecs = SP.opt_state_specs(pspecs, params, mesh, pcfg)

    def sds(tree, shard):
        return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=s), tree, shard)
    p = sds(params, SP.sharding_tree(pspecs, mesh))
    o = sds(jax.eval_shape(adamw.init, params),
            SP.sharding_tree(ospecs, mesh))
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32,
                               sharding=NamedSharding(mesh, P("data", "mx")))
    ATT.sdpa_paths.clear()
    jax.jit(TS.build_train_step(cfg, pcfg, rc, mesh)).lower(
        p, o, {"tokens": tok, "labels": tok})
    paths = {(path, reason) for path, _, reason in ATT.sdpa_paths}
    assert paths == {("jnp", "mesh")}, ATT.sdpa_paths
    print("MESH STEP KEEPS SDPA", sorted(ATT.sdpa_paths))


if __name__ == "__main__":
    main()
