"""The launchers' callable entry points, the compile-cache helper and the
device grid, on the CPU at smoke widths."""

import math
import os
import subprocess
import sys
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro import compat
from repro.launch import mesh as M
from repro.launch import serve, train

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_train_run_smoke():
    seen = []
    args = train.build_parser().parse_args(
        ["--smoke", "--steps", "3", "--batch", "2", "--seq", "32",
         "--remat", "full"])
    rec = train.run(args, on_start=lambda p, o, b: seen.append(
        b["tokens"].shape))
    assert seen == [(2, 32)]
    assert [s for s, _ in rec["history"]] == [0, 1, 2]
    assert all(math.isfinite(loss) for _, loss in rec["history"])
    assert len(rec["step_s"]) == 3 and rec["compile_s"] > 0
    assert "tpu_custom_call" not in rec["compiled"].as_text()


def test_train_run_one_device_init_is_init_params():
    """The jitted init makes, on one device, the very params of
    ``lm.init_params`` and a zero AdamW state."""
    from repro.config import get_smoke_config
    from repro.models import lm

    seen = {}

    def on_start(params, opt_state, batch):
        seen["params"] = jax.tree.map(np.asarray, params)
        seen["moments"] = jax.tree.map(np.asarray, (opt_state.mu,
                                                    opt_state.nu))
        seen["devices"] = {a.sharding for a in jax.tree.leaves(params)}

    args = train.build_parser().parse_args(
        ["--smoke", "--arch", "granite-34b", "--num-layers", "3", "--steps",
         "1", "--batch", "2", "--seq", "32"])
    train.run(args, on_start=on_start)
    cfg = get_smoke_config("granite-34b").scaled(num_layers=3)
    want = lm.init_params(cfg, jax.random.PRNGKey(0))
    assert jax.tree.structure(want) == jax.tree.structure(seen["params"])
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(seen["params"])):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a), b)
    assert want["blocks"]["mlp"]["w1"].shape[0] == 3
    assert all(not m.any() for m in jax.tree.leaves(seen["moments"]))
    assert seen["devices"] == {
        jax.sharding.SingleDeviceSharding(jax.devices()[0])}


def test_train_run_makes_sharded_state_on_a_2x2_grid():
    """On 4 virtual CPU devices, ``overlap`` none and fused: every leaf of
    the params and AdamW state has the sharding of ``param_specs`` /
    ``opt_state_specs``, and the step-0 loss matches one device's
    (``tests/_mp/check_launch_sharded.py``, which states the tolerance)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "_mp",
                                      "check_launch_sharded.py")],
        capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "LAUNCH SHARDED STATE OK" in r.stdout


def test_serve_run_smoke():
    prefilled = []
    args = serve.build_parser().parse_args(
        ["--smoke", "--requests", "3", "--gen", "4", "--prompt-lens", "8,20"])
    rec = serve.run(args, on_prefill=lambda r, last: prefilled.append(
        (r.rid, last.shape)))
    assert sorted(rid for rid, _ in prefilled) == [0, 1, 2]
    assert all(shape[:2] == (1, 1) for _, shape in prefilled)
    assert {rid: len(f.tokens) for rid, f in rec["finished"].items()} == \
        {0: 4, 1: 4, 2: 4}


@pytest.mark.parametrize("env", [None, "/elsewhere/cache"])
def test_enable_compile_cache(monkeypatch, env):
    was = jax.config.jax_compilation_cache_dir
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    try:
        got = compat.enable_compile_cache()
        if env is None:
            assert got == str(compat.CACHE_DIR)
            assert jax.config.jax_compilation_cache_dir == got
            # a fixed path at the checkout's root
            assert compat.CACHE_DIR.name == ".jax_cache"
            assert (compat.CACHE_DIR.parent / "src/repro/compat.py").exists()
        else:
            assert got == env
            assert jax.config.jax_compilation_cache_dir == was
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("platforms,respawns", [("cpu", True), ("", False),
                                                ("tpu", False)])
def test_maybe_respawn_only_on_cpu(monkeypatch, platforms, respawns):
    # set-then-delete makes monkeypatch restore XLA_FLAGS however it started
    monkeypatch.setenv("XLA_FLAGS", "")
    monkeypatch.delenv("XLA_FLAGS")
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    calls = []
    monkeypatch.setattr(os, "execv", lambda *a: calls.append(a))
    train._maybe_respawn(4)
    assert bool(calls) == respawns
    assert ("XLA_FLAGS" in os.environ) == respawns


def _chips(nx, ny):
    coords = [(x, y, 0) for y in range(ny) for x in range(nx)]
    rng = np.random.default_rng(0)
    return [SimpleNamespace(coords=c, core_on_chip=0)
            for c in rng.permutation(np.array(coords, dtype=object))]


def test_grid_devices_follow_chip_coordinates():
    grid = M._grid_devices(_chips(2, 2), (1, 2, 2))
    assert [[tuple(d.coords[:2]) for d in row] for row in grid[0]] == \
        [[(0, 0), (0, 1)], [(1, 0), (1, 1)]]
    assert M._grid_devices(_chips(4, 1), (1, 2, 2)) is None
    assert M._grid_devices(jax.devices()[:1], (1, 1, 1)) is None


def test_train_run_profile_holds_the_step_spans(tmp_path):
    from jax.profiler import ProfileData

    args = train.build_parser().parse_args(
        ["--smoke", "--steps", "4", "--batch", "2", "--seq", "32",
         "--profile_dir", str(tmp_path), "--profile_steps", "1:3"])
    rec = train.run(args)
    assert [s for s, _ in rec["history"]] == [0, 1, 2, 3]
    found = sorted(tmp_path.rglob("*.xplane.pb"))
    assert len(found) == 1
    names = [e.name for p in ProfileData.from_file(str(found[0])).planes
             if p.name.startswith("/host:") for line in p.lines
             for e in line.events]
    assert names.count("train") == 2
    for span in ("next_batch", "dispatch", "loss_sync"):
        assert names.count(span) == 2, span


@pytest.mark.parametrize("text", ["3", "3:3", "a:b", "-1:2"])
def test_profile_steps_must_be_a_range(text):
    with pytest.raises(SystemExit):
        train.build_parser().parse_args(["--profile_steps", text])
