"""tools/paper_tables.py prints the paper's analytic tables end to end."""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _x(v):
    return float(v.rstrip("x"))


def test_paper_tables_prints_every_figure():
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "paper_tables.py")],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-3000:]
    rows = dict(line.split(" ", 1) for line in r.stdout.splitlines())
    for prefix in ("fig8_", "fig9_", "fig10_", "fig11_", "tab4_"):
        assert any(k.startswith(prefix) for k in rows), prefix
    # analytic rows only: nothing is named as a measured time
    assert not any("us_per_call" in k or k.endswith("_us") for k in rows)
    # the fitted constants land near the paper's headline against Megatron
    # (5.29x latency, 3.46x energy; largest workload, standard package)
    assert 4.5 < _x(rows["fig8_speedup_vs_megatron_std"]) < 6.0
    assert 2.8 < _x(rows["fig8_energy_vs_megatron_std"]) < 4.0
    # weak scaling (§V-B): Hecaton stays about flat, the flat ring grows
    assert _x(rows["fig9_weakscale_hecaton_standard_k8"]) < 1.2
    assert _x(rows["fig9_weakscale_flat_ring_standard_k8"]) > 2.0
