"""Ring-overlapped collective matmuls (core/overlap.py).

Numerics run in a subprocess on a fake 8-device topology (tests/_mp style);
the HLO assertions use the tests/_mp/hlo_bytes.py counters to prove that
overlap="ring"/"bidir" replaces every bulk all-gather/reduce-scatter in the
FFN hot path (forward AND backward) with collective-permute chains.
In-process tests cover the pure dispatch/fallback logic and config plumbing.
"""

import os
import subprocess
import sys

import pytest

from _mp import hlo_bytes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "tests", "_mp",
                                                     script), *args],
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, f"\nSTDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


def test_overlap_numerics():
    """ring/bidir/fused fwd+grad == bulk == dense ref on 4x2 / 2x2 / 4x1
    grids, including odd-shard bidir fallback, the fused-loss contraction
    ring, the Pallas ring kernels' interpret path, the overlapped embed_2d
    vocab scatter, AND the megatron residual layouts (seq vs replicated,
    gather-at-entry / scatter-at-exit, 1x8 / 2x4 / 4x2 model rings plus a
    full-model loss+grad) against the dense reference, plus the
    sharded-label fused_lm_loss_seq on 1x8 / 2x4 / 4x2 grids."""
    out = _run("check_overlap.py")
    assert "ALL OVERLAP NUMERICS CHECKS PASSED" in out
    assert "ALL RESIDUAL LAYOUT CHECKS PASSED" in out
    assert "ALL FUSED SEQ LOSS CHECKS PASSED" in out


def test_overlap_hlo_collective_permute_replaces_bulk():
    """Acceptance: with overlap enabled, the compiled hot paths (hecaton FFN
    fwd AND bwd, MoE EP/TP gathers+scatters, megatron column/row FFN) have a
    collective-permute chain and ZERO bulk all-gather/reduce-scatter — while
    the bulk mode has the inverse on the FFN path."""
    out = hlo_bytes.run("overlap")
    for tag in ("fwd", "fwd_bwd"):
        none_b = out["none"][tag]["bytes"]
        assert none_b.get("all-gather", 0) > 0
        assert none_b.get("reduce-scatter", 0) > 0
        assert none_b.get("collective-permute", 0) == 0
        for mode in ("ring", "bidir", "fused"):
            b = out[mode][tag]["bytes"]
            assert b.get("all-gather", 0) == 0, (mode, tag, b)
            assert b.get("reduce-scatter", 0) == 0, (mode, tag, b)
            assert b.get("collective-permute", 0) > 0, (mode, tag, b)
    # MoE and megatron paths: the bulk mode has AG/RS, the ring modes none
    for path in ("moe", "megatron"):
        for mode in ("ring", "bidir", "fused"):
            b = out[mode][path]["bytes"]
            assert b.get("all-gather", 0) == 0, (mode, path, b)
            assert b.get("reduce-scatter", 0) == 0, (mode, path, b)
            assert b.get("collective-permute", 0) > 0, (mode, path, b)
    assert out["none"]["moe"]["bytes"].get("all-gather", 0) > 0
    # bidir halves per-step messages but doubles the permute count
    n_ring = out["ring"]["fwd"]["count"]["collective-permute"]
    n_bidir = out["bidir"]["fwd"]["count"]["collective-permute"]
    assert n_bidir == 2 * n_ring


def test_seq_residual_hlo_no_block_boundary_gather():
    """Acceptance (ISSUE 3 + ISSUE 4 label satellite): under the seq-sharded
    residual layout with overlap ∈ {ring, bidir, fused}, a full megatron LM
    train step (fwd+bwd) has ZERO bulk collectives — no reduce-scatter and
    ZERO all-gather bytes: since fused_lm_loss_seq rings the head's vocab
    chunks with the labels kept sharded, even the old sub-KB int32 label
    gather is gone — while the replicated layout pays a residual-sized bulk
    collective (AG+RS+AR; XLA may emit it as an all-reduce rather than an
    all-gather) in EVERY ring mode.  Per-die residual-stream bytes shrink by
    exactly 1/n_model, and the seq layout never moves more bulk bytes
    (AG+RS+AR) than the replicated one."""
    out = hlo_bytes.run("residual")
    n = out["n_model"]

    def bulk(row):
        b = row["bytes"]
        return (b.get("all-gather", 0.0) + b.get("reduce-scatter", 0.0)
                + b.get("all-reduce", 0.0))

    for mode in ("ring", "bidir", "fused"):
        b = out["seq"][mode]["bytes"]
        assert b.get("reduce-scatter", 0) == 0, (mode, b)
        # zero label bulk-gather bytes: labels stay sharded through the
        # fused seq loss, so NO all-gather of any size survives
        assert b.get("all-gather", 0) == 0, (mode, b)
        assert b.get("collective-permute", 0) > 0, (mode, b)
        # the replicated layout pays a residual-sized bulk collective
        rb = out["replicated"][mode]
        assert bulk(rb) > 1e5, (mode, rb["bytes"])
    for mode in ("none", "ring", "bidir", "fused"):
        assert bulk(out["seq"][mode]) <= bulk(out["replicated"][mode]), mode
        # per-die activation bytes for the layer scan shrink by 1/n_model
        assert (out["seq"][mode]["residual_bytes_per_die"] * n
                == out["replicated"][mode]["residual_bytes_per_die"])


# ---------------------------------------------------------------------------
# In-process: dispatch/fallback logic + config plumbing (no multi-device mesh)
# ---------------------------------------------------------------------------


def test_mode_fallback_logic():
    from repro.core.overlap import MODES, check_mode, rs_ok

    assert MODES == ("none", "ring", "bidir", "fused")
    for m in MODES:
        assert check_mode(m) == m
    with pytest.raises(ValueError):
        check_mode("diagonal")               # a typo must not mean "ring"
    assert rs_ok(12, 4)                      # chunks evenly: ring RS
    assert not rs_ok(10, 4)                  # cannot chunk: bulk collective
    assert not rs_ok(12, 1)                  # degenerate axis: bulk no-op


def test_hecaton_ops_reject_bad_overlap():
    import jax.numpy as jnp
    from repro.core import hecaton as H

    x = jnp.ones((2, 4, 8), jnp.float32)
    w = jnp.ones((8, 6), jnp.float32)
    with pytest.raises(ValueError):
        H.linear_seq_scatter(x, w, mesh=None, t_ax="mx", h_ax="my",
                             overlap="sprial")


def test_fuse_side_picks_heavier_collective():
    from repro.core.overlap import fuse_side

    assert fuse_side(h_loc=64, o_loc=256) == "rs"    # output heavier: fuse RS
    assert fuse_side(h_loc=256, o_loc=64) == "ag"    # input heavier: fuse AG
    assert fuse_side(h_loc=64, o_loc=64) == "ag"     # tie: circulate input


def test_shift_perm_is_a_ring():
    from repro.core.overlap import _shift_perm

    assert _shift_perm(4, 1) == [(0, 1), (1, 2), (2, 3), (3, 0)]
    assert _shift_perm(4, -1) == [(0, 3), (1, 0), (2, 1), (3, 2)]
    srcs, dsts = zip(*_shift_perm(8, 1))
    assert sorted(srcs) == sorted(dsts) == list(range(8))


def test_parallel_config_overlap_validation():
    from repro.config import ParallelConfig

    assert ParallelConfig().overlap == "none"
    assert ParallelConfig(overlap="ring").overlap == "ring"
    pc = ParallelConfig(overlap="ring").with_(overlap="bidir")
    assert pc.overlap == "bidir"
    with pytest.raises(AssertionError):
        ParallelConfig(overlap="spiral")


def test_pctx_plumbs_overlap():
    from repro.config import ParallelConfig
    from repro.parallel.context import PCtx

    pctx = PCtx(mesh=None, pcfg=ParallelConfig(overlap="ring"))
    assert pctx.overlap == "ring"


def test_residual_layout_config_plumbing():
    from repro.config import ParallelConfig
    from repro.parallel.context import PCtx

    assert ParallelConfig().residual == "seq"        # seq is the canonical
    assert ParallelConfig(residual="replicated").residual == "replicated"
    with pytest.raises(AssertionError):
        ParallelConfig(residual="diagonal")
    # decode forces the replicated residual (S=1 cannot token-scatter)
    pcfg = ParallelConfig(residual="seq")
    assert PCtx(mesh=None, pcfg=pcfg, mode="train").residual == "seq"
    assert PCtx(mesh=None, pcfg=pcfg, mode="decode").residual == "replicated"


def test_seq_shardable_gate():
    from repro.parallel import sharding as shd

    ax = shd.AxisInfo(("data",), None, None, ("model",),
                      {"data": 2, "model": 4})
    assert shd.seq_shardable(ax, 16)
    assert not shd.seq_shardable(ax, 15)     # does not divide the ring
    assert not shd.seq_shardable(ax, 1)      # decode
    hec = shd.AxisInfo(("data",), "mx", "my", ("mx", "my"),
                       {"data": 2, "mx": 2, "my": 2})
    assert not shd.seq_shardable(hec, 16)    # hecaton: own tiling handles it
    from jax.sharding import PartitionSpec as P
    assert shd.act_canonical(ax, "seq") == P("data", "model", None)
    assert shd.act_canonical(ax, "replicated") == P("data", None, None)
    assert shd.act_canonical(hec, "seq") == shd.act_canonical(hec, "replicated")
    with pytest.raises(ValueError):
        shd.act_canonical(ax, "spiral")


def test_shard_local_norm_and_dropout_entry_points():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.config import ParallelConfig
    from repro.models import layers as L
    from repro.parallel.context import PCtx

    pctx = PCtx(mesh=None, pcfg=ParallelConfig(data=1, model=1, mx=1, my=1))
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16), jnp.float32)
    p = L.init_norm("rmsnorm", 16)
    np.testing.assert_allclose(np.asarray(pctx.norm("rmsnorm", p, x)),
                               np.asarray(L.apply_norm("rmsnorm", p, x)))
    # rate 0 / missing rng are deterministic no-ops
    assert pctx.dropout(x, 0.0, jax.random.PRNGKey(1)) is x
    assert pctx.dropout(x, 0.5, None) is x
    y = pctx.dropout(x, 0.5, jax.random.PRNGKey(1))
    kept = np.asarray(y) != 0
    np.testing.assert_allclose(np.asarray(y)[kept],
                               (np.asarray(x) / 0.5)[kept], rtol=1e-6)
    assert 0.2 < kept.mean() < 0.8           # ~half the entries survive


def test_embed_dropout_microbatched_train_step():
    """embed_dropout end to end: the train step splits dropout_rng into one
    key per microbatch (distinct masks) and the loss stays finite."""
    import jax
    import jax.numpy as jnp
    from repro.config import ModelConfig, ParallelConfig, RunConfig
    from repro.train import step as TS

    cfg = ModelConfig(name="do-test", family="dense", num_layers=1,
                      d_model=16, num_heads=2, num_kv_heads=2, d_ff=32,
                      vocab_size=32, mlp_kind="gelu", embed_dropout=0.25)
    rc = RunConfig("t", "train", 8, 4, lr=1e-3)
    pcfg = ParallelConfig(data=1, model=1, mx=1, my=1, microbatches=2,
                          zero1=False)
    params, opt = TS.init_train_state(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, 32)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1),
             "dropout_rng": jax.random.PRNGKey(2)}
    ts = TS.build_train_step(cfg, pcfg, rc, None, compute_dtype=jnp.float32)
    _, _, m = jax.jit(ts)(params, opt, batch)
    assert jnp.isfinite(m["loss"])
    mbs = TS.microbatch_split(batch, 2)
    assert mbs["dropout_rng"].shape == (2, 2)        # one key per microbatch
    assert not bool((mbs["dropout_rng"][0] == mbs["dropout_rng"][1]).all())
    # spec builders treat the rng as replicated, never sharded
    from jax.sharding import PartitionSpec as P
    from repro.parallel import specs as SP
    assert SP.batch_specs(None, pcfg, microbatched=True,
                          keys=("tokens", "dropout_rng")) is not None


def test_mixer_in_many_matches_per_weight():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.config import ParallelConfig
    from repro.parallel.context import PCtx

    pctx = PCtx(mesh=None, pcfg=ParallelConfig(data=1, model=1, mx=1, my=1))
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16), jnp.float32)
    ws = [jax.random.normal(jax.random.PRNGKey(i), (16, 24), jnp.float32)
          for i in (1, 2, 3)]
    outs = pctx.mixer_in_many(x, *ws)
    assert len(outs) == 3
    for got, w in zip(outs, ws):
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(pctx.mixer_in(x, w)), rtol=1e-6)


def test_mesh_none_paths_ignore_overlap():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import hecaton as H

    x = jnp.ones((2, 4, 8), jnp.float32)
    w = jnp.ones((8, 6), jnp.float32)
    for ov in ("none", "ring", "bidir"):
        y = H.linear_seq_scatter(x, w, mesh=None, t_ax="mx", h_ax="my",
                                 overlap=ov)
        np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                                   rtol=1e-6)
    ffn = H.ffn_block(x, w, jnp.ones((6, 8), jnp.float32), mesh=None,
                      act_fn=jax.nn.silu, t_ax="mx", h_ax="my", overlap="ring")
    assert ffn.shape == (2, 4, 8)


# ---------------------------------------------------------------------------
# Int8-quantized ring collectives (core/quant.py, docs/DESIGN.md §11)
# ---------------------------------------------------------------------------


def test_quant_parity_gate():
    """Loss-parity gate: 2 optimizer steps of the 2-layer LM on 1x8 and 2x4
    megatron grids, ring/bidir/fused — the int8-comm loss curve tracks the
    bf16-comm curve within rtol and the grads within the documented looser
    relative-L2 bound (tests/_mp/check_overlap.py --quant-parity)."""
    out = _run("check_overlap.py", "--quant-parity")
    assert "ALL QUANT PARITY CHECKS PASSED" in out


def test_quant_hlo_byte_cut():
    """Acceptance: int8 rings move ≤ 0.55x the collective-permute bytes of
    the bf16 wire on the 2-layer megatron LM train step (fwd+bwd), on every
    overlap mode — and the bulk AG/RS total stays zero for BOTH wire dtypes
    (the wire dtype must never re-bulk a ring)."""
    out = hlo_bytes.run("quant")
    for mode in ("ring", "bidir", "fused"):
        row = out[mode]
        cp = {cd: row[cd]["bytes"].get("collective-permute", 0.0)
              for cd in ("bf16", "int8")}
        assert cp["bf16"] > 0, (mode, row)
        assert cp["int8"] <= 0.55 * cp["bf16"], (mode, cp)
        for cd in ("bf16", "int8"):
            b = row[cd]["bytes"]
            assert b.get("all-gather", 0) == 0, (mode, cd, b)
            assert b.get("reduce-scatter", 0) == 0, (mode, cd, b)
        # the scales ride as extra (small) permutes: more ops, fewer bytes
        assert (row["int8"]["count"]["collective-permute"]
                > row["bf16"]["count"]["collective-permute"]), mode


def test_comm_dtype_config_plumbing():
    from repro.config import ParallelConfig
    from repro.core import quant as Q
    from repro.core.overlap import COMM_DTYPES, check_comm_dtype
    from repro.parallel.context import PCtx

    assert COMM_DTYPES == ("bf16", "int8")
    assert ParallelConfig().comm_dtype == "bf16"     # default: today's wire
    assert ParallelConfig(comm_dtype="int8").comm_dtype == "int8"
    with pytest.raises(AssertionError):
        ParallelConfig(comm_dtype="int4")            # typo must not mean bf16
    with pytest.raises(ValueError):
        check_comm_dtype("fp8")
    pctx = PCtx(mesh=None, pcfg=ParallelConfig(comm_dtype="int8"))
    assert pctx.comm_dtype == "int8"
    # per-hop degradation gate: integer payloads and tiny trailing extents
    # stay full width; everything else quantizes
    import jax.numpy as jnp
    assert Q.quant_ok((4, 64), jnp.float32)
    assert Q.quant_ok((4, Q.MIN_QUANT_DIM), jnp.bfloat16)
    assert not Q.quant_ok((4, Q.MIN_QUANT_DIM - 1), jnp.float32)
    assert not Q.quant_ok((4, 64), jnp.int32)        # embedding ids
    assert not Q.quant_ok((), jnp.float32)


def test_quant_single_device_smoke():
    """Tier-1 single-device smoke: hecaton ops accept comm_dtype on the
    mesh=None path (no rings → bit-exact), and a 1-device mesh runs the
    int8 ring end to end (the self-hop is a quantize/dequantize roundtrip,
    bounded by scale/2 per element)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import hecaton as H
    from repro.core import quant as Q

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 32), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 16), jnp.float32)
    y = H.linear_seq_scatter(x, w, mesh=None, t_ax="mx", h_ax="my",
                             overlap="ring", comm_dtype="int8")
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w), rtol=1e-6)
    q, s = Q.quant_int8(x)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert s.shape == x.shape[:-1] + (1,)
    err = np.abs(np.asarray(Q.dequant_int8(q, s, x.dtype) - x))
    assert (err <= np.asarray(s) / 2 * (1 + 1e-6) + 1e-7).all()


def test_comm_model_wire_dtype_rows():
    """The paper tables' bytes-per-element flows from the comm dtype
    (comm_bytes_per_elt is the single source), and the SRAM minimal-unit
    check uses the ladder's element width instead of a hardcoded fp32."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "paper_tables", os.path.join(ROOT, "tools", "paper_tables.py"))
    paper_tables = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(paper_tables)
    comm_bytes_per_elt = paper_tables.comm_bytes_per_elt

    assert comm_bytes_per_elt("bf16", 4096) == 2.0
    assert comm_bytes_per_elt("int8", 4096) == pytest.approx(1 + 4 / 4096)
    # below MIN_QUANT_DIM the hop degrades to full width
    assert comm_bytes_per_elt("int8", 8) == 2.0
    with pytest.raises(ValueError):
        comm_bytes_per_elt("fp8", 4096)
    # the SRAM check is consistent with the ladder's own element width: the
    # paper's verdict rows (flat ring overflows, optimus+hecaton fit) hold
    verdict = {(r["package"], r["method"]): r["sram_ok"]
               for r in paper_tables.fig8_rows()
               if r["workload"] == "llama3.1-405b"}
    assert verdict[("standard", "hecaton")] and verdict[("standard", "optimus")]
    assert not verdict[("standard", "flat_ring")]
