"""Subprocess check: every ring hop of a Hecaton primitive carries the
primitive's ``jax.named_scope`` in its HLO ``op_name``, forward and
backward, under each overlap mode.

Run with XLA_FLAGS=--xla_force_host_platform_device_count=4.
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import re

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import hecaton as H
from repro.launch.mesh import make_small_mesh

B, T, HD, O, V = 2, 8, 16, 32, 64
AXES = dict(t_ax="mx", h_ax="my", data_axes=("data",))
# collectives a primitive issues itself, per overlap mode (the bulk mode's
# reduce-scatters lower to all-reduce + dynamic-slice on the CPU)
OPS = {"none": r"all-gather|all-reduce|reduce-scatter",
       "ring": r"collective-permute", "fused": r"collective-permute"}


def cases(mesh, overlap):
    def s(*spec):
        return NamedSharding(mesh, P(*spec))

    def arr(shape, *spec, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=s(*spec))

    kw = dict(mesh=mesh, overlap=overlap, **AXES)
    x = arr((B, T, HD), "data", "mx", "my")
    return {
        "hecaton_linear_seq_scatter": (
            lambda x, w: H.linear_seq_scatter(x, w, **kw),
            (x, arr((HD, O), "my", "mx"))),
        "hecaton_mixer_in": (
            lambda x, w: H.mixer_in(x, w, **kw),
            (x, arr((HD, O), "my", "mx"))),
        "hecaton_mixer_out": (
            lambda a, w: H.mixer_out(a, w, **kw),
            (arr((B, T, O), "data", None, ("mx", "my")),
             arr((O, HD), "mx", "my"))),
        "hecaton_ffn_block": (
            lambda x, w1, w2, w1b: H.ffn_block(
                x, w1, w2, act_fn=jax.nn.gelu, w1b=w1b, **kw),
            (x, arr((HD, O), "my", "mx"), arr((O, HD), "mx", "my"),
             arr((HD, O), "my", "mx"))),
        "hecaton_embed_2d": (
            lambda tab, ids: H.embed_2d(ids, tab, **kw),
            (arr((V, HD), "mx", "my"),
             arr((B, T), "data", "mx", dtype=jnp.int32))),
        "hecaton_fused_lm_loss": (
            lambda x, w, labels: H.fused_lm_loss(x, w, labels, None,
                                                 **kw)[0],
            (x, arr((HD, V), None, "my"),
             arr((B, T), "data", "mx", dtype=jnp.int32))),
    }


def with_vjp(f):
    """f's output and its vjp into the floating arguments, under a
    replicated cotangent of ones (no reduction outside the primitive)."""
    def g(*args):
        fl = [i for i, a in enumerate(args)
              if jnp.issubdtype(a.dtype, jnp.floating)]

        def h(*floats):
            full = list(args)
            for i, a in zip(fl, floats):
                full[i] = a
            return f(*full)
        y, back = jax.vjp(h, *(args[i] for i in fl))
        return y, back(jnp.ones_like(y))
    return g


def main():
    assert len(jax.devices()) == 4, jax.devices()
    mesh = make_small_mesh("hecaton", 1, 2, 2)
    for overlap, ops in OPS.items():
        for scope, (f, args) in cases(mesh, overlap).items():
            text = jax.jit(with_vjp(f)).lower(*args).compile().as_text()
            hops = [line for line in text.splitlines()
                    if re.search(rf"= [^=]*\b({ops})\(", line)]
            assert hops, (overlap, scope)
            for line in hops:
                name = re.search(r'op_name="([^"]*)"', line)
                assert name and scope in name.group(1), (overlap, line)
            print(f"{overlap:6s} {scope}: {len(hops)} collectives, all named")
    print("ALL SCOPE NAME CHECKS PASSED")


if __name__ == "__main__":
    main()
