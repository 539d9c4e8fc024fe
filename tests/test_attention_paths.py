"""Which attention core each call takes, from the trace-time record
``models.attention.sdpa_paths``: the flash kernel on a causal self-attention
over whole sequences (a training step; on a mesh, where each chip can hold
whole heads), the jnp ``_sdpa`` on every other call.  Tracing only
(``jax.eval_shape``): the record is made at trace time, and the platform is
picked when the step is lowered (``tests/test_tpu_compile.py`` lowers one
for a v5e)."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ParallelConfig, RunConfig, get_smoke_config
from repro.models import attention as ATT
from repro.models import lm
from repro.optim import adamw
from repro.parallel.context import PCtx
from repro.serve import step as SS
from repro.train import step as TS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PCFG = ParallelConfig(data=1, model=1, mx=1, my=1, microbatches=1)
PCTX = PCtx(None, PCFG)
# the smoke configs at the kernel's head width (a multiple of 128), so that
# the reason a call keeps _sdpa is the one under test, not the tiling
QWEN = get_smoke_config("qwen3-0.6b").scaled(head_dim=128)
MLA = get_smoke_config("minicpm3-4b")
MLA = MLA.scaled(mla=MLA.mla.__class__(
    **{**MLA.mla.__dict__, "qk_nope_head_dim": 96, "qk_rope_head_dim": 32,
       "v_head_dim": 64}))
KEY = jax.random.PRNGKey(0)


def _paths(fn, *args):
    ATT.sdpa_paths.clear()
    jax.eval_shape(fn, *args)
    return [(path, reason) for path, _, reason in ATT.sdpa_paths]


def _x(cfg, B, S):
    return jnp.zeros((B, S, cfg.d_model), jnp.bfloat16)


def _pos(B, S, start=0):
    return jnp.broadcast_to(start + jnp.arange(S, dtype=jnp.int32), (B, S))


def _attn(cfg, B, S, *, cache=None, causal=True):
    p = ATT.init_attn(cfg, KEY)
    return lambda: ATT.apply_attn(PCTX, cfg, p, _x(cfg, B, S),
                                  positions=_pos(B, S), causal=causal,
                                  cache=cache)


def _cross(cfg, B, S, frames):
    p = ATT.init_attn(cfg, KEY)
    kv = jnp.zeros((B, frames, cfg.num_kv_heads, cfg.resolved_head_dim),
                   jnp.bfloat16)
    return lambda: ATT.apply_cross_attn(PCTX, cfg, p, _x(cfg, B, S), (kv, kv))


def _mla(cfg, B, S):
    p = ATT.init_mla(cfg, KEY)
    return lambda: ATT.apply_mla(PCTX, cfg, p, _x(cfg, B, S),
                                 positions=_pos(B, S))


CASES = {
    "train": (lambda: _attn(QWEN, 2, 256), ("flash", None)),
    "not_causal": (lambda: _attn(QWEN, 2, 256, causal=False),
                   ("jnp", "not causal")),
    "seq_untiled": (lambda: _attn(QWEN, 2, 96),
                    ("jnp", "seq 96 or head dim 128 not a multiple of 128")),
    "head_dim_untiled": (
        lambda: _attn(get_smoke_config("qwen3-0.6b"), 2, 128),
        ("jnp", "seq 128 or head dim 16 not a multiple of 128")),
    "decode": (lambda: _attn(QWEN, 2, 1, cache=ATT.init_kv_cache(
        QWEN, 2, 256, jnp.bfloat16)), ("jnp", "kv cache")),
    "prefill_continue": (lambda: _attn(QWEN, 2, 128, cache=ATT.init_kv_cache(
        QWEN, 2, 256, jnp.bfloat16)), ("jnp", "kv cache")),
    "paged_prefill": (lambda: _attn(QWEN, 1, 128, cache=ATT.init_paged_kv(
        QWEN, 9, 32, 1, 8, jnp.bfloat16)), ("jnp", "kv cache")),
    "paged_decode": (lambda: _attn(QWEN, 2, 1, cache=ATT.init_paged_kv(
        QWEN, 17, 32, 2, 8, jnp.bfloat16)), ("jnp", "kv cache")),
    "cross": (lambda: _cross(QWEN, 2, 128, 128), ("jnp", "not causal")),
    "mla": (lambda: _mla(MLA, 2, 128), ("jnp", "v head dim != qk head dim")),
}


@pytest.mark.parametrize("case", list(CASES))
def test_attention_core_path(case):
    make, want = CASES[case]
    assert _paths(make()) == [want]


def test_train_step_records_flash():
    """A whole single-device train step: every attention layer (one scan
    body) takes the kernel."""
    rc = RunConfig("t", "train", 256, 2)
    step = TS.build_train_step(QWEN, PCFG, rc, None)
    p = jax.eval_shape(lambda: lm.init_params(QWEN, KEY))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 256), jnp.int32),
             "labels": jax.ShapeDtypeStruct((2, 256), jnp.int32)}
    paths = _paths(step, p, jax.eval_shape(adamw.init, p), batch)
    assert paths and set(paths) == {("flash", None)}


def test_serve_decode_step_records_jnp():
    rc = RunConfig("serve", "decode", 256, 2)
    step = SS.build_decode_step(QWEN, PCFG, rc, None)
    p = jax.eval_shape(lambda: lm.init_params(QWEN, KEY))
    caches = jax.eval_shape(lambda: lm.init_caches(QWEN, 2, 256,
                                                   jnp.bfloat16))
    tok = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    paths = _paths(step, p, caches, tok, tok)
    assert paths and set(paths) == {("jnp", "kv cache")}


def test_cpu_lowering_runs_sdpa():
    """Off TPU the kernel's call lowers to ``_sdpa``: the same numbers as
    the repeated-KV ``_sdpa`` the training path ran before."""
    B, S, nh, nkv, dh = 2, 256, 4, 2, 128
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, nh, dh), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, nkv, dh), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, nkv, dh), jnp.bfloat16)
    got = jax.jit(lambda q, k, v: ATT._causal_self_attention(q, k, v, 128))(
        q, k, v)
    want = jax.jit(lambda q, k, v: ATT._sdpa(
        q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2), causal=True,
        q_offset=0, q_block=128))(q, k, v)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.fixture(scope="module")
def mesh_run():
    """``tests/_mp/check_sdpa_paths.py`` on a 2x2 Hecaton mesh of four
    virtual CPU devices, run once for the tests below."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "_mp",
                                      "check_sdpa_paths.py")],
        capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, f"\n{r.stdout}\n{r.stderr[-3000:]}"
    line = next(ln for ln in r.stdout.splitlines() if ln.startswith("SDPA "))
    return json.loads(line[len("SDPA "):])


@pytest.mark.parametrize("case,want", [
    ("mqa", ["flash", None]),
    ("gqa", ["flash", None]),
    ("kv2", ["jnp", "kv heads 2 not divisible over 4 head shards"]),
])
def test_mesh_step_path(mesh_run, case, want):
    """A train step on the 2x2 mesh: the kernel takes every core whose K/V
    heads divide over the 4 head shards or are one (MQA), replicated; two
    kv heads keep _sdpa."""
    assert mesh_run["paths"][case] == [want]


@pytest.mark.parametrize("case", ["mqa", "gqa"])
def test_mesh_flash_core_matches_sdpa(mesh_run, case):
    """The shard_mapped kernel (interpret mode) against the unsharded _sdpa
    with K/V repeated, at bf16 tolerances: forward and q/k/v gradients (the
    transpose sums dK/dV over the head shards)."""
    got = mesh_run["numerics"][case]
    assert got["fwd_abs"] <= 2e-2, got
    assert max(got["grad_rel"].values()) <= 1e-2, got
