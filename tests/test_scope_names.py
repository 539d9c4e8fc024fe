"""The Hecaton primitives' names reach the compiled HLO: every ring hop
carries its primitive's ``jax.named_scope``.  Runs in a subprocess on four
virtual CPU devices (``tests/_mp/check_scope_names.py``)."""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_ring_ops_carry_the_primitive_scope():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "tests", "_mp", "check_scope_names.py")],
        capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, f"\n{r.stdout}\n{r.stderr[-3000:]}"
    assert "ALL SCOPE NAME CHECKS PASSED" in r.stdout
