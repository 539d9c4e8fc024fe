"""Subprocess check of the attention core on a 2x2 Hecaton mesh of four
virtual CPU devices.  Prints one JSON line:

* ``paths``: for each case of :data:`PATHS`, the (path, reason) pairs that a
  train step's attention cores recorded in ``sdpa_paths``;
* ``numerics``: for each case of :data:`NUMERICS`, the sharded flash core
  (``_on_heads`` around the kernel in interpret mode) against the unsharded
  ``_sdpa`` with K/V repeated: the forward's largest absolute gap and the
  q/k/v gradients' relative gaps.

Run with XLA_FLAGS=--xla_force_host_platform_device_count=4.
"""
import functools
import json
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
from repro.parallel.context import PCtx
from repro.train import step as TS

PCFG = ParallelConfig(strategy="hecaton", data=1, model=4, mx=2, my=2,
                      microbatches=1, zero1=True)
# (q heads, kv heads) of a train step's attention
PATHS = {"mqa": (4, 1), "gqa": (8, 4), "kv2": (4, 2)}
# (q heads, kv heads) of the sharded core against _sdpa
NUMERICS = {"mqa": (8, 1), "gqa": (8, 4)}


def step_paths(mesh, nh, nkv):
    cfg = ModelConfig(name="paths-test", family="dense", num_layers=2,
                      d_model=64, num_heads=nh, num_kv_heads=nkv, head_dim=128,
                      d_ff=128, vocab_size=256, qk_norm=True)
    S, B = 256, 2
    rc = RunConfig("t", "train", S, B)
    params = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))
    pspecs = SP.param_specs(params, mesh, PCFG)
    ospecs = SP.opt_state_specs(pspecs, params, mesh, PCFG)

    def sds(tree, shard):
        return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=s), tree, shard)
    p = sds(params, SP.sharding_tree(pspecs, mesh))
    o = sds(jax.eval_shape(adamw.init, params),
            SP.sharding_tree(ospecs, mesh))
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32,
                               sharding=NamedSharding(mesh, P("data", "mx")))
    ATT.sdpa_paths.clear()
    jax.jit(TS.build_train_step(cfg, PCFG, rc, mesh)).lower(
        p, o, {"tokens": tok, "labels": tok})
    return sorted({(path, reason) for path, _, reason in ATT.sdpa_paths},
                  key=str)


def core_gaps(mesh, nh, nkv):
    B, S, dh = 2, 256, 128
    pctx = PCtx(mesh, PCFG)
    layout = pctx.attn_layout(nh, B)
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (B, S, nh, dh), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, nkv, dh), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, nkv, dh), jnp.bfloat16)
    ct = jax.random.normal(ks[3], (B, S, nh, dh), jnp.float32)
    q = jax.device_put(q, NamedSharding(mesh, layout.q_spec()))

    def sharded(q, k, v):
        return ATT._on_heads(pctx, layout,
                             functools.partial(ATT._flash, interpret=True),
                             q, k, v)

    def dense(q, k, v):
        g = nh // nkv
        return ATT._sdpa(q, ATT._repeat_kv(k, g), ATT._repeat_kv(v, g),
                         causal=True, q_offset=0, q_block=128)

    def fwd_grads(core):
        loss = lambda q, k, v: jnp.sum(core(q, k, v).astype(jnp.float32) * ct)  # noqa: E731
        out = jax.jit(core)(q, k, v)
        grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
        return out.astype(jnp.float32), [g.astype(jnp.float32) for g in grads]

    o, gs = fwd_grads(sharded)
    o_ref, gs_ref = fwd_grads(dense)
    rel = [float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
           for g, r in zip(gs, gs_ref)]
    return {"fwd_abs": float(jnp.max(jnp.abs(o - o_ref))),
            "grad_rel": dict(zip("qkv", rel))}


def main():
    assert len(jax.devices()) == 4, jax.devices()
    mesh = make_small_mesh("hecaton", 1, 2, 2)
    out = {"paths": {c: step_paths(mesh, *h) for c, h in PATHS.items()},
           "numerics": {c: core_gaps(mesh, *h) for c, h in NUMERICS.items()}}
    print("SDPA " + json.dumps(out))


if __name__ == "__main__":
    main()
