"""The check decides ``correct`` against the plain reference, and catches
the faults a training cell can have, on the CPU at the smoke widths.

Each run skips only the harness's look for a chip: it builds the cell's
program, drives its first steps, the window and the reference, as a run on
the chip does.  The timed path is then broken underneath: a step that
returns its state unchanged, half of each batch left out, and (on four
virtual devices, in a child process) the exchange between chips left out.
The lower-precision control, the reference in float8 in the program's
place, has to fail too.
"""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

from bench import check, reference, train_cell
from bench.tests.tiny import patched_registry, tiny_cell

HERE = Path(__file__).resolve().parent
ONE_CHIP = "qwen3-0.6b.train_4k"
SEED = 2**31 + 977


def run(name, wrap_step=None, seed=SEED):
    cell, smoke = tiny_cell(name)
    with patched_registry(smoke):
        return train_cell.run(cell, seed, 0.3, False, jax.devices()[:1],
                              time.perf_counter(), wrap_step=wrap_step)


def unchanged_state(step):
    def f(params, opt_state, batch):
        return params, opt_state, step(params, opt_state, batch)[2]
    return f


def half_batch(step):
    def f(params, opt_state, batch):
        return step(params, opt_state,
                    {k: v[: v.shape[0] // 2] for k, v in batch.items()})
    return f


def test_sound_run_is_correct():
    out = run(ONE_CHIP)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("fault", [unchanged_state, half_batch])
def test_fault_under_the_timed_path_is_not_correct(fault):
    out = run(ONE_CHIP, wrap_step=fault)
    assert not out["correct"], out["checks"]


def test_exchange_left_out_on_four_devices_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, str(HERE / "four_devices.py")],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["sound"]["correct"], got["sound"]["checks"]
    assert not got["no_exchange"]["correct"], got["no_exchange"]["checks"]


def test_float8_control_is_not_correct():
    cell, _ = tiny_cell(ONE_CHIP)
    m, opt = cell.config["program"], cell.traffic["optimizer"]
    pkey, dkey = train_cell.keys(SEED)
    t = cell.traffic

    def batches(i):
        b = train_cell.make_batch(dkey, i, t["batch"], t["seq_len"],
                                  m["vocab_size"])
        return b["tokens"], b["labels"]

    n = t["check_steps"]
    want = reference.Reference(m, opt).run(pkey, batches, n)
    control = reference.Reference(m, opt, dt=jax.numpy.float8_e4m3fn).run(
        pkey, batches, n)
    ok, checks = check.verdict(check.numbers(control, want), cell.limits)
    assert not ok, checks
