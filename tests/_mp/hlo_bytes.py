"""Per-collective bytes and op counts of small compiled steps, read from the
HLO with ``roofline.hlo.analyze``: the structure behind the overlap,
residual-layout and int8-wire acceptance tests in tests/test_overlap.py.

    python tests/_mp/hlo_bytes.py overlap|residual|quant

prints one ``RESULT <json>`` line.  :func:`run` does this in a child process
pinned to the CPU with 8 fake devices (the device count is fixed at jax's
first import, and on a TPU host the child must not contend for the
parent's chip) and returns the parsed result.
"""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MODES = ("none", "ring", "bidir", "fused")


def _counts(compiled):
    from repro.roofline.hlo import analyze
    r = analyze(compiled.as_text())
    return {"bytes": dict(r.coll_bytes), "count": dict(r.coll_count)}


def overlap():
    """Per overlap mode: one Hecaton FFN block (fwd, fwd+bwd), one MoE block
    (fwd+bwd) and one megatron column/row FFN (fwd+bwd).

    Returns {mode: {path: {"bytes": {coll: B}, "count": {coll: n}}}} with
    paths "fwd" / "fwd_bwd" (hecaton FFN), "moe", "megatron"."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.config import ModelConfig, MoEConfig, ParallelConfig
    from repro.core import hecaton as H
    from repro.models import mlp as MLP
    from repro.parallel import megatron as MEG
    from repro.parallel.context import PCtx

    mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2),
                ("data", "mx", "my"))
    B, T, Hd, F = 4, 64, 128, 512
    sh = lambda s: jax.ShapeDtypeStruct(s, jnp.float32)
    shards = (NamedSharding(mesh, P("data", "mx", "my")),
              NamedSharding(mesh, P("my", "mx")),
              NamedSharding(mesh, P("mx", "my")))

    # MoE: experts over a 4-ring, FFN width over a 2-ring (data axis
    # degenerate so only the EP/TP collectives are counted).
    mesh_moe = Mesh(np.array(jax.devices()).reshape(1, 4, 2),
                    ("data", "mx", "my"))
    moe_cfg = ModelConfig(name="cmp-moe", family="moe", num_layers=1,
                          d_model=32, num_heads=4, num_kv_heads=4, d_ff=64,
                          vocab_size=64, mlp_kind="swiglu",
                          moe=MoEConfig(num_experts=8, top_k=2))
    moe_p = jax.eval_shape(lambda: MLP.init_moe(moe_cfg,
                                                jax.random.PRNGKey(0)))

    # Megatron 1D-TP: 8-way model ring, H=32 chunks evenly.
    mesh_meg = Mesh(np.array(jax.devices()).reshape(1, 8), ("data", "model"))
    Hm, Fm = 32, 64

    out = {}
    for ov in MODES:
        def ffn(x, w1, w2, _ov=ov):
            return H.ffn_block(x, w1, w2, mesh=mesh, act_fn=jax.nn.silu,
                               t_ax="mx", h_ax="my", overlap=_ov)

        def step(x, w1, w2, _f=ffn):
            return jax.grad(lambda *a: _f(*a).sum(),
                            argnums=(0, 1, 2))(x, w1, w2)
        res = {}
        for tag, fn in (("fwd", ffn), ("fwd_bwd", step)):
            res[tag] = _counts(jax.jit(fn, in_shardings=shards).lower(
                sh((B, T, Hd)), sh((Hd, F)), sh((F, Hd))).compile())

        moe_pctx = PCtx(mesh=mesh_moe, pcfg=ParallelConfig(
            strategy="hecaton", data=1, model=8, mx=4, my=2, overlap=ov,
            zero1=False))

        def moe_step(p, x, _pctx=moe_pctx):
            def loss(p, x):
                y, aux = MLP.apply_moe(_pctx, moe_cfg, p, x)
                return y.sum() + aux
            return jax.grad(loss, argnums=(0, 1))(p, x)
        res["moe"] = _counts(jax.jit(moe_step).lower(
            moe_p, sh((2, 8, 32))).compile())

        meg_pctx = PCtx(mesh=mesh_meg, pcfg=ParallelConfig(
            strategy="megatron", data=1, model=8, overlap=ov, zero1=False))

        def meg_step(x, w1, w2, _pctx=meg_pctx):
            def loss(x, w1, w2):
                return MEG.ffn(_pctx, x, w1, w2, jax.nn.silu).sum()
            return jax.grad(loss, argnums=(0, 1, 2))(x, w1, w2)
        res["megatron"] = _counts(jax.jit(meg_step).lower(
            sh((2, 8, Hm)), sh((Hm, Fm)), sh((Fm, Hm))).compile())
        out[ov] = res
    return out


# The 2-layer dense LM train step (fwd+bwd) on an 8-way megatron ring that
# the residual-layout and int8-wire counters compile.
LM_B, LM_S, LM_D, LM_N_MODEL = 4, 64, 64, 8


def _lm_step(**pcfg_kw):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from repro.config import ModelConfig, ParallelConfig
    from repro.models import lm
    from repro.parallel import specs as SP
    from repro.parallel.context import PCtx

    cfg = ModelConfig(name="res", family="dense", num_layers=2,
                      d_model=LM_D, num_heads=8, num_kv_heads=8, d_ff=128,
                      vocab_size=256, mlp_kind="swiglu")
    mesh = Mesh(np.array(jax.devices()).reshape(1, LM_N_MODEL),
                ("data", "model"))
    params = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))
    pcfg = ParallelConfig(strategy="megatron", data=1, model=LM_N_MODEL,
                          zero1=False, **pcfg_kw)
    pctx = PCtx(mesh, pcfg, "train")
    pshard = SP.sharding_tree(SP.param_specs(params, mesh, pcfg), mesh)
    bspec = SP.batch_specs(mesh, pcfg, microbatched=False, seq_len=LM_S)
    bshard = {k: NamedSharding(mesh, bspec[k]) for k in ("tokens", "labels")}
    bstruct = {k: jax.ShapeDtypeStruct((LM_B, LM_S), jnp.int32)
               for k in ("tokens", "labels")}

    def loss(p, b):
        return lm.train_loss(pctx, cfg, p, {**b, "_dtype": jnp.float32},
                             remat="none")[0]
    return _counts(jax.jit(jax.grad(loss), in_shardings=(pshard, bshard))
                   .lower(params, bstruct).compile())


def residual():
    """Per residual layout (replicated vs seq) and overlap mode: the LM
    step's collectives, and the analytic per-die residual-stream bytes
    carried across the layer scan.

    Returns {"n_model": n, layout: {mode: {"bytes", "count",
    "residual_bytes_per_die"}}}."""
    out = {"n_model": LM_N_MODEL}
    for layout in ("replicated", "seq"):
        out[layout] = {}
        for ov in MODES:
            row = _lm_step(overlap=ov, residual=layout)
            row["residual_bytes_per_die"] = (
                LM_B * LM_S * LM_D * 4
                // (LM_N_MODEL if layout == "seq" else 1))
            out[layout][ov] = row
    return out


def quant():
    """Per ring mode (ring/bidir/fused): the LM step's collectives under the
    seq residual with ``comm_dtype`` "bf16" vs "int8".

    Returns {"n_model": n, mode: {comm_dtype: {"bytes", "count"}}}."""
    out = {"n_model": LM_N_MODEL}
    for ov in MODES[1:]:
        out[ov] = {cd: _lm_step(overlap=ov, residual="seq", comm_dtype=cd)
                   for cd in ("bf16", "int8")}
    return out


COUNTERS = {"overlap": overlap, "residual": residual, "quant": quant}


def run(kind):
    """``COUNTERS[kind]()`` in a child pinned to the CPU with 8 devices."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    r = subprocess.run([sys.executable, os.path.abspath(__file__), kind],
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


if __name__ == "__main__":
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    print("RESULT " + json.dumps(COUNTERS[sys.argv[1]]()))
