"""Measured (compiled-HLO) per-step collective bytes: hecaton vs megatron on a
fake 8-device mesh — the empirical companion to comm_model.py's theory — plus
the overlap counter: per-mode (none/ring/bidir/fused) collective-permute vs
bulk all-gather/reduce-scatter bytes of one Hecaton FFN block (forward and
backward), one MoE block (EP/TP gathers + scatters), and one megatron
column/row FFN, proving the ring decomposition replaces every bulk AG/RS in
every hot path with a ppermute chain (the fused mode additionally runs its
matmuls through the Pallas ring kernels' emulated path on CPU).  Runs in
subprocesses (each needs its own XLA device-count flag)."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=32"
import json
import jax, jax.numpy as jnp
import numpy as np
from repro.config import ModelConfig, ParallelConfig, RunConfig
from repro.models import lm
from repro.optim import adamw
from repro.parallel import specs as SP
from repro.roofline.hlo import analyze
from repro.train import step as TS
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

cfg = ModelConfig(name="cmp", family="dense", num_layers=4, d_model=512,
                  num_heads=16, num_kv_heads=8, d_ff=2048, vocab_size=512,
                  mlp_kind="swiglu")
rc = RunConfig("t", "train", 256, 8, lr=1e-3)
out = {}
for strat, mesh in (("hecaton", Mesh(np.array(jax.devices()).reshape(2, 4, 4),
                                     ("data", "mx", "my"))),
                    ("megatron", Mesh(np.array(jax.devices()).reshape(2, 16),
                                      ("data", "model")))):
    pcfg = ParallelConfig(strategy=strat, data=2, model=16, mx=4, my=4,
                          microbatches=1, zero1=False)
    params = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))
    pspecs = SP.param_specs(params, mesh, pcfg)
    pshard = SP.sharding_tree(pspecs, mesh)
    opt = jax.eval_shape(adamw.init, params)
    oshard = SP.sharding_tree(SP.opt_state_specs(pspecs, params, mesh, pcfg),
                              mesh)
    seq_ax = "mx" if strat == "hecaton" else None
    bshard = {k: NamedSharding(mesh, P("data", seq_ax))
              for k in ("tokens", "labels")}
    bstruct = {k: jax.ShapeDtypeStruct((8, 256), jnp.int32)
               for k in ("tokens", "labels")}
    ts = TS.build_train_step(cfg, pcfg, rc, mesh)
    c = jax.jit(ts, in_shardings=(pshard, oshard, bshard)).lower(
        params, opt, bstruct).compile()
    r = analyze(c.as_text())
    out[strat] = {"coll_bytes": r.total_coll_bytes,
                  "breakdown": dict(r.coll_bytes), "flops": r.flops}
print("RESULT " + json.dumps(out))
'''


# Overlap counter: one Hecaton FFN block (fwd + grad), one MoE block, and one
# megatron column/row FFN compiled per overlap mode on fake 8-device meshes;
# reports per-collective bytes and op counts for each path.
SCRIPT_OVERLAP = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.config import ModelConfig, MoEConfig, ParallelConfig
from repro.core import hecaton as H
from repro.models import mlp as MLP
from repro.parallel import megatron as MEG
from repro.parallel.context import PCtx
from repro.roofline.hlo import analyze

mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2), ("data", "mx", "my"))
B, T, Hd, F = 4, 64, 128, 512
sh = lambda s: jax.ShapeDtypeStruct(s, jnp.float32)
shards = (NamedSharding(mesh, P("data", "mx", "my")),
          NamedSharding(mesh, P("my", "mx")), NamedSharding(mesh, P("mx", "my")))

# MoE: experts over a 4-ring, FFN width over a 2-ring (data axis degenerate so
# only the EP/TP collectives are counted).
mesh_moe = Mesh(np.array(jax.devices()).reshape(1, 4, 2), ("data", "mx", "my"))
moe_cfg = ModelConfig(name="cmp-moe", family="moe", num_layers=1, d_model=32,
                      num_heads=4, num_kv_heads=4, d_ff=64, vocab_size=64,
                      mlp_kind="swiglu", moe=MoEConfig(num_experts=8, top_k=2))
moe_p = MLP.init_moe(moe_cfg, jax.random.PRNGKey(0))
moe_x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32), jnp.float32)

# Megatron 1D-TP: 8-way model ring, H=32 chunks evenly.
mesh_meg = Mesh(np.array(jax.devices()).reshape(1, 8), ("data", "model"))
Hm, Fm = 32, 64

out = {}
for ov in ("none", "ring", "bidir", "fused"):
    def ffn(x, w1, w2, _ov=ov):
        return H.ffn_block(x, w1, w2, mesh=mesh, act_fn=jax.nn.silu,
                           t_ax="mx", h_ax="my", overlap=_ov)
    def step(x, w1, w2, _f=ffn):
        return jax.grad(lambda *a: _f(*a).sum(), argnums=(0, 1, 2))(x, w1, w2)
    res = {}
    for tag, fn in (("fwd", ffn), ("fwd_bwd", step)):
        c = jax.jit(fn, in_shardings=shards).lower(
            sh((B, T, Hd)), sh((Hd, F)), sh((F, Hd))).compile()
        r = analyze(c.as_text())
        res[tag] = {"bytes": dict(r.coll_bytes), "count": dict(r.coll_count)}

    moe_pctx = PCtx(mesh=mesh_moe, pcfg=ParallelConfig(
        strategy="hecaton", data=1, model=8, mx=4, my=2, overlap=ov,
        zero1=False))
    def moe_step(p, x, _pctx=moe_pctx):
        def loss(p, x):
            y, aux = MLP.apply_moe(_pctx, moe_cfg, p, x)
            return y.sum() + aux
        return jax.grad(loss, argnums=(0, 1))(p, x)
    c = jax.jit(moe_step).lower(
        jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                               moe_p),
        sh(moe_x.shape)).compile()
    r = analyze(c.as_text())
    res["moe"] = {"bytes": dict(r.coll_bytes), "count": dict(r.coll_count)}

    meg_pctx = PCtx(mesh=mesh_meg, pcfg=ParallelConfig(
        strategy="megatron", data=1, model=8, overlap=ov, zero1=False))
    def meg_step(x, w1, w2, _pctx=meg_pctx):
        def loss(x, w1, w2):
            return MEG.ffn(_pctx, x, w1, w2, jax.nn.silu).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(x, w1, w2)
    c = jax.jit(meg_step).lower(
        sh((2, 8, Hm)), sh((Hm, Fm)), sh((Fm, Hm))).compile()
    r = analyze(c.as_text())
    res["megatron"] = {"bytes": dict(r.coll_bytes), "count": dict(r.coll_count)}
    out[ov] = res
print("RESULT " + json.dumps(out))
'''


# Residual-layout counter: a 2-layer dense LM (train fwd+bwd) compiled on a
# megatron 1D-TP ring under BOTH residual layouts (replicated vs seq-sharded)
# per overlap mode.  Proves the seq layout removes every bulk AG/RS from the
# block boundaries under ring/bidir/fused (entry gathers / exit scatters ride
# the collective-permute lattice) and that the per-die residual-stream bytes
# carried across the layer scan shrink by 1/n_model.
SCRIPT_RESIDUAL = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.config import ModelConfig, ParallelConfig
from repro.models import lm
from repro.parallel import specs as SP
from repro.parallel.context import PCtx
from repro.roofline.hlo import analyze

cfg = ModelConfig(name="res", family="dense", num_layers=2, d_model=64,
                  num_heads=8, num_kv_heads=8, d_ff=128, vocab_size=256,
                  mlp_kind="swiglu")
B, S, n_model = 4, 64, 8
mesh = Mesh(np.array(jax.devices()).reshape(1, n_model), ("data", "model"))
params = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))
out = {"n_model": n_model}
for residual in ("replicated", "seq"):
    res_l = {}
    for ov in ("none", "ring", "bidir", "fused"):
        pcfg = ParallelConfig(strategy="megatron", data=1, model=n_model,
                              overlap=ov, residual=residual, zero1=False)
        pctx = PCtx(mesh, pcfg, "train")
        pshard = SP.sharding_tree(SP.param_specs(params, mesh, pcfg), mesh)
        bspec = SP.batch_specs(mesh, pcfg, microbatched=False, seq_len=S)
        bshard = {k: NamedSharding(mesh, bspec[k])
                  for k in ("tokens", "labels")}
        bstruct = {k: jax.ShapeDtypeStruct((B, S), jnp.int32)
                   for k in ("tokens", "labels")}
        def loss(p, b, _pctx=pctx):
            return lm.train_loss(_pctx, cfg, p, {**b, "_dtype": jnp.float32},
                                 remat="none")[0]
        c = jax.jit(jax.grad(loss), in_shardings=(pshard, bshard)).lower(
            params, bstruct).compile()
        r = analyze(c.as_text())
        row = {"bytes": dict(r.coll_bytes), "count": dict(r.coll_count)}
        try:                      # measured per-device temp memory (may be
            ma = c.memory_analysis()          # unavailable on some backends)
            row["temp_bytes"] = int(getattr(ma, "temp_size_in_bytes", 0))
        except Exception:
            row["temp_bytes"] = None
        # analytic per-die residual-stream bytes carried across the layer scan
        row["residual_bytes_per_die"] = (B * S * cfg.d_model * 4
                                         // (n_model if residual == "seq"
                                             else 1))
        res_l[ov] = row
    out[residual] = res_l
print("RESULT " + json.dumps(out))
'''


# Quantized-wire counter: the same 2-layer megatron LM (train fwd+bwd, seq
# residual) compiled under comm_dtype "bf16" vs "int8" per overlap mode.
# Proves the int8 rings actually move int8 bytes in compiled HLO — the
# collective-permute byte total must drop well below the 0.55x gate (payload
# shrinks 4x from the fp32 compute dtype; the per-row fp32 scales ride along
# as separate small permutes) — while the bulk AG/RS total stays zero (the
# wire dtype must not break the overlap lattice's degradation decisions).
SCRIPT_QUANT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.config import ModelConfig, ParallelConfig
from repro.models import lm
from repro.parallel import specs as SP
from repro.parallel.context import PCtx
from repro.roofline.hlo import analyze

cfg = ModelConfig(name="quant", family="dense", num_layers=2, d_model=64,
                  num_heads=8, num_kv_heads=8, d_ff=128, vocab_size=256,
                  mlp_kind="swiglu")
B, S, n_model = 4, 64, 8
mesh = Mesh(np.array(jax.devices()).reshape(1, n_model), ("data", "model"))
params = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))
out = {"n_model": n_model}
for ov in ("ring", "bidir", "fused"):
    row = {}
    for cd in ("bf16", "int8"):
        pcfg = ParallelConfig(strategy="megatron", data=1, model=n_model,
                              overlap=ov, residual="seq", zero1=False,
                              comm_dtype=cd)
        pctx = PCtx(mesh, pcfg, "train")
        pshard = SP.sharding_tree(SP.param_specs(params, mesh, pcfg), mesh)
        bspec = SP.batch_specs(mesh, pcfg, microbatched=False, seq_len=S)
        bshard = {k: NamedSharding(mesh, bspec[k])
                  for k in ("tokens", "labels")}
        bstruct = {k: jax.ShapeDtypeStruct((B, S), jnp.int32)
                   for k in ("tokens", "labels")}
        def loss(p, b, _pctx=pctx):
            return lm.train_loss(_pctx, cfg, p, {**b, "_dtype": jnp.float32},
                                 remat="none")[0]
        c = jax.jit(jax.grad(loss), in_shardings=(pshard, bshard)).lower(
            params, bstruct).compile()
        r = analyze(c.as_text())
        row[cd] = {"bytes": dict(r.coll_bytes), "count": dict(r.coll_count)}
    out[ov] = row
print("RESULT " + json.dumps(out))
'''


def _run_script(script):
    """Run ``script`` in a child pinned to the CPU: it counts HLO on fake CPU
    devices, and on a TPU host it must not contend for the parent's chip."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=900)
    if r.returncode != 0:
        return {"error": r.stderr[-500:]}
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


def run():
    return _run_script(SCRIPT)


def run_overlap():
    """Per-overlap-mode collective bytes/counts of one hecaton FFN block
    (fwd, fwd+bwd), one MoE block (fwd+bwd) and one megatron FFN (fwd+bwd).

    Returns {mode: {path: {"bytes": {coll: B}, "count": {coll: n}}}} with
    paths "fwd" / "fwd_bwd" (hecaton FFN), "moe", "megatron".  Every
    ring/bidir/fused mode must show zero bulk all-gather/reduce-scatter and a
    collective-permute chain instead (asserted by tests/test_overlap.py)."""
    return _run_script(SCRIPT_OVERLAP)


def run_residual():
    """Per-residual-layout (replicated vs seq) × per-overlap-mode collective
    bytes of a full 2-layer megatron LM train step (fwd+bwd).

    Returns {"n_model": n, layout: {mode: {"bytes", "count", "temp_bytes",
    "residual_bytes_per_die"}}}.  Acceptance (asserted by
    tests/test_overlap.py and the CI smoke check): the seq layout has ZERO
    bulk all-gather/reduce-scatter under overlap ∈ {ring, bidir, fused}, no
    more bulk bytes than the replicated layout anywhere, and its per-die
    residual bytes are 1/n_model of the replicated layout's."""
    return _run_script(SCRIPT_RESIDUAL)


def run_quant():
    """Per-overlap-mode (ring/bidir/fused) collective bytes of the 2-layer
    megatron LM train step under ``comm_dtype`` "bf16" vs "int8".

    Returns {"n_model": n, mode: {comm_dtype: {"bytes", "count"}}}.
    Acceptance (asserted by tests/test_overlap.py and the CI grep): int8's
    collective-permute bytes ≤ 0.55x the bf16 wire's on every mode, with the
    bulk all-gather/reduce-scatter total still zero — the byte cut comes from
    the wire dtype, never from silently re-bulking a ring."""
    return _run_script(SCRIPT_QUANT)


def main(emit):
    out = run()
    if "error" in out:
        emit("hlo_compare", 0.0, "ERROR")
    else:
        h, m = out["hecaton"]["coll_bytes"], out["megatron"]["coll_bytes"]
        emit("hlo_measured_bytes_hecaton", 0.0, f"{h/1e6:.1f}MB")
        emit("hlo_measured_bytes_megatron", 0.0, f"{m/1e6:.1f}MB")
        emit("hlo_measured_ratio_meg_over_hec", 0.0, f"{m/h:.2f}x")
    ov = run_overlap()
    if "error" in ov:
        emit("hlo_overlap", 0.0, "ERROR")
        return {"compare": out, "overlap": ov}
    for mode, res in ov.items():
        b = res["fwd_bwd"]["bytes"]
        cp = b.get("collective-permute", 0.0)
        bulk = b.get("all-gather", 0.0) + b.get("reduce-scatter", 0.0)
        n_cp = res["fwd_bwd"]["count"].get("collective-permute", 0)
        emit(f"hlo_overlap_{mode}_cp_bytes", 0.0,
             f"{cp/1e3:.1f}KB/{int(n_cp)}ops")
        emit(f"hlo_overlap_{mode}_bulk_bytes", 0.0, f"{bulk/1e3:.1f}KB")
        for path in ("moe", "megatron"):
            pb = res.get(path, {}).get("bytes", {})
            bulk_p = pb.get("all-gather", 0.0) + pb.get("reduce-scatter", 0.0)
            emit(f"hlo_overlap_{path}_{mode}_bulk_bytes", 0.0,
                 f"{bulk_p/1e3:.1f}KB")
    res_l = run_residual()
    if "error" in res_l:
        emit("hlo_residual", 0.0, "ERROR")
    else:
        for layout in ("replicated", "seq"):
            for mode, row in res_l[layout].items():
                b = row["bytes"]
                bulk = b.get("all-gather", 0.0) + b.get("reduce-scatter", 0.0)
                emit(f"hlo_residual_{layout}_{mode}_bulk_bytes", 0.0,
                     f"{bulk/1e3:.1f}KB")
            emit(f"hlo_residual_{layout}_act_bytes", 0.0,
                 f"{res_l[layout]['ring']['residual_bytes_per_die']/1e3:.1f}"
                 "KB/die")
    qt = run_quant()
    if "error" in qt:
        emit("hlo_quant", 0.0, "ERROR")
    else:
        for mode in ("ring", "bidir", "fused"):
            row = qt[mode]
            cp = {cd: row[cd]["bytes"].get("collective-permute", 0.0)
                  for cd in ("bf16", "int8")}
            ratio = cp["int8"] / max(cp["bf16"], 1.0)
            emit(f"hlo_quant_{mode}_cp_ratio", 0.0,
                 f"{ratio:.3f}x({cp['int8']/1e3:.1f}KB/{cp['bf16']/1e3:.1f}KB)")
    return {"compare": out, "overlap": ov, "residual": res_l, "quant": qt}
