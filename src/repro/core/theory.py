"""Analytical communication model — paper Table III, §V, and the Fig. 8-11 studies.

Implements closed-form NoP/ICI overheads for the four distributed-training methods the
paper compares:

  flat_ring  : 1D-TP + ring all-reduce            (Megatron, "F" in Fig. 8)
  torus_ring : 1D-TP + 2D-torus all-reduce        ("T")
  optimus    : 2D-TP + broadcast/reduce            ("O")
  hecaton    : this paper's 2D-TP + AG/RS          ("A")

Notation follows Table II/III:
  N      — number of devices participating in tensor parallelism
  alpha  — per-hop link latency [s]
  beta   — per-link bandwidth  [bytes/s]
  gamma  — b*s*h * bytes_per_elt / beta   (activation transfer unit, seconds)
  xi     — h^2  * bytes_per_elt / beta    (weight-tile transfer unit, seconds)

All returned times are seconds for ONE transformer layer's Attention or FFN block
(forward or backward), exactly the cells of Table III.  These formulas are the oracle
against which we test the *measured* collective bytes parsed from compiled HLO
(tests/test_roofline.py), closing the loop between the paper's theory and our
implementation.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict


@dataclass(frozen=True)
class CommParams:
    N: int                 # devices in the TP group
    alpha: float = 10e-9   # link latency (paper §VI-E uses 10ns)
    beta: float = 64e9     # D2D/ICI bandwidth per link [B/s]
    b: int = 8             # mini-batch size (samples)
    s: int = 2048          # sequence length
    h: int = 4096          # hidden size
    bytes_per_elt: int = 2

    @property
    def gamma(self) -> float:
        return self.b * self.s * self.h * self.bytes_per_elt / self.beta

    @property
    def xi(self) -> float:
        return self.h * self.h * self.bytes_per_elt / self.beta

    @property
    def rootN(self) -> float:
        r = math.isqrt(self.N)
        assert r * r == self.N, f"N={self.N} must be a perfect square for 2D methods"
        return r


# ---------------------------------------------------------------------------
# Table III rows.  Each function returns dict(link_latency=..., transmission=...).
# ---------------------------------------------------------------------------

def _cell(L, T):
    return {"link_latency": L, "transmission": T, "total": L + T}


def hecaton(p: CommParams, phase: str, block: str) -> Dict[str, float]:
    """Paper's method.  AG/RS over sqrt(N)-size rows/cols; bypass ring: 2*alpha/hop."""
    r = p.rootN
    L_unit = (r - 1) * 2 * p.alpha                       # eq. (2)
    coeff = {("fwd", "atten"): (4, 6), ("fwd", "ffn"): (4, 10),
             ("bwd", "atten"): (6, 8), ("bwd", "ffn"): (6, 15)}[(phase, block)]
    n_colls, t_coeff = coeff
    L = n_colls * L_unit / 2                             # Table III: 8/12 (sqrt(N)-1) a
    # Table III link-latency entries: fwd 8(√N−1)α, bwd 12(√N−1)α
    L = {("fwd"): 8, ("bwd"): 12}[phase] * (r - 1) * p.alpha
    T = t_coeff * (r - 1) / p.N * p.gamma
    return _cell(L, T)


def flat_ring(p: CommParams, phase: str, block: str) -> Dict[str, float]:
    """1D-TP + flat ring all-reduce (Megatron)."""
    n = {"fwd": 2, "bwd": 3}[phase]                      # #all-reduces per block
    L = n * (p.N - 1) * p.alpha
    T = n * (p.N - 1) / p.N * p.gamma
    return _cell(L, T)


def torus_ring(p: CommParams, phase: str, block: str) -> Dict[str, float]:
    """1D-TP + 2D-torus all-reduce: 2x links, 2x hops per step."""
    n = {"fwd": 2, "bwd": 3}[phase]
    L = 2 * n * (p.N - p.rootN) * p.alpha
    T = n * (p.N - 1) / (2 * p.N) * p.gamma
    return _cell(L, T)


def optimus(p: CommParams, phase: str, block: str) -> Dict[str, float]:
    """2D-TP with broadcast/reduce (recursive doubling), per Table III."""
    r = p.rootN
    logN = math.log2(p.N)
    L = {"fwd": 4 * (p.N - r), "bwd": 12 * (p.N - r)}[phase] * p.alpha
    coeff = {("fwd", "atten"): (2, 4), ("fwd", "ffn"): (5, 8),
             ("bwd", "atten"): (4, 8), ("bwd", "ffn"): (10, 16)}[(phase, block)]
    cg, cx = coeff
    T = logN / (2 * r) * (cg * p.gamma + cx * p.xi)
    return _cell(L, T)


METHODS = {"flat_ring": flat_ring, "torus_ring": torus_ring,
           "optimus": optimus, "hecaton": hecaton}


def layer_comm(method: str, p: CommParams) -> Dict[str, float]:
    """Total NoP comm (s) for one full transformer layer fwd+bwd."""
    f = METHODS[method]
    cells = [f(p, ph, bl) for ph in ("fwd", "bwd") for bl in ("atten", "ffn")]
    return {
        "link_latency": sum(c["link_latency"] for c in cells),
        "transmission": sum(c["transmission"] for c in cells),
        "total": sum(c["total"] for c in cells),
    }


# ---------------------------------------------------------------------------
# Compute / DRAM model (for Fig. 8-10 style studies and weak scaling)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SystemParams:
    comm: CommParams
    flops_per_device: float = 197e12 / 256    # per-"die" compute (scaled v5e default)
    dram_bw: float = 51.2e9                   # off-package bandwidth [B/s]
    dram_channels: int = 16
    sram_bytes: int = 8 * 2**20               # per-die activation/weight buffer
    act_stream_mult: float = 24.0             # streamed elements/token/h


def layer_flops(p: CommParams) -> float:
    """FLOPs of one transformer layer fwd+bwd (dense, 4h^2 attn + 8h^2 ffn weights)."""
    tokens = p.b * p.s
    fwd = 2 * tokens * (4 * p.h * p.h + 8 * p.h * p.h)   # matmul MACs*2
    fwd += 2 * tokens * p.s * p.h * 2                    # QK^T and SV
    return 3 * fwd                                       # bwd ~ 2x fwd


def pe_utilization(method: str, p: CommParams, array_dim: int = 64,
                   floor: float = 0.4) -> float:
    """Systolic-array utilization of the local weight tile (paper §VI-B: 1D-TP
    "exhibits increased computation time ... due to reduced PE array
    utilization"; 2D methods keep balanced input/output channel counts).

    1D-TP slices ONE weight dim N ways (tile h x h/N); 2D-TP slices both dims
    sqrt(N) ways.  Dims below the effective array width waste lanes; ``floor``
    models the vector/streaming units that stay busy regardless."""
    if method in ("flat_ring", "torus_ring"):
        tile = p.h / p.N
    else:
        tile = p.h / p.rootN
    return max(floor, min(1.0, max(tile, 1.0) / array_dim))


def layer_time(method: str, sp: SystemParams) -> Dict[str, float]:
    """Per-layer time decomposition {compute, nop, dram, total} with overlap.

    DRAM term models the paper's §III-B scheduling: activations stream on/off
    package overlapped with execution (latency hiding); weights amortized over
    the batch.  Activation stream = fwd save + bwd reload of the ~24*h live
    elements/token (unfused-layer boundaries, Fig. 6).
    """
    p = sp.comm
    comm = layer_comm(method, p)
    util = pe_utilization(method, p)
    compute = layer_flops(p) / (sp.flops_per_device * p.N) / util
    act_bytes = sp.act_stream_mult * p.b * p.s * p.h * p.bytes_per_elt
    dram = act_bytes / (sp.dram_channels * sp.dram_bw)
    on_pkg = compute + comm["total"]
    total = max(on_pkg, dram)                            # overlap (paper Fig. 6)
    return {"compute": compute, "nop": comm["total"], "dram": dram,
            "utilization": util,
            "nop_link": comm["link_latency"], "nop_tx": comm["transmission"],
            "exposed_dram": max(0.0, dram - on_pkg), "total": total}


def weak_scaling_series(method: str, base: CommParams, ks=(1, 2, 4, 8),
                        flops_per_device: float = 197e12 / 256,
                        dram_bw: float = 51.2e9):
    """Scale h by k and N by k^2 (paper §V-B); return normalized latency series."""
    out = []
    for k in ks:
        p = CommParams(N=base.N * k * k, alpha=base.alpha, beta=base.beta,
                       b=base.b, s=base.s, h=base.h * k,
                       bytes_per_elt=base.bytes_per_elt)
        sp = SystemParams(comm=p, dram_channels=int(16 * k),
                          flops_per_device=flops_per_device, dram_bw=dram_bw)
        out.append(layer_time(method, sp))
    norm = out[0]["total"]
    for o in out:
        o["normalized"] = o["total"] / norm
    return out


# ---------------------------------------------------------------------------
# Inter-pod pipeline model (PR 5: pod_axis_role="pipeline")
# ---------------------------------------------------------------------------

def pipeline_bubble_fraction(p: int, m: int) -> float:
    """Idle fraction of the non-interleaved 1F1B schedule: ``(p-1)/(m+p-1)``.

    ``p`` pipeline stages (pods), ``m`` microbatches; F and B take one tick
    each, so the makespan is ``2(m+p-1)`` ticks of which every stage idles
    ``2(p-1)`` — the classic PipeDream-flush / Megatron-LM bubble.  The
    simulated schedule (parallel/pipeline.schedule_1f1b) reproduces this
    exactly; tests/test_pipeline.py asserts the match.
    """
    if p <= 1:
        return 0.0
    return (p - 1) / (m + p - 1)


def pipeline_boundary_comm(p: CommParams, n_stages: int, n_micro: int,
                           pod_beta: float, pod_alpha: float = 1e-6
                           ) -> Dict[str, float]:
    """Per-step inter-pod transfer time of the 1F1B stage boundaries.

    Each microbatch crosses each of the ``p-1`` boundaries once forward
    (one [b/m, s, h] activation) and once backward (its cotangent) over the
    slow off-package links (``pod_beta`` bytes/s, ``pod_alpha`` latency).
    The residual stays seq-sharded *within* a pod, but the whole tensor
    must cross the package boundary, so the per-crossing bytes are the full
    microbatch activation.
    """
    bytes_per_mb = p.b / n_micro * p.s * p.h * p.bytes_per_elt
    crossings = 2 * (n_stages - 1) * n_micro
    T = crossings * bytes_per_mb / pod_beta
    L = crossings * pod_alpha
    return _cell(L, T)


def pipeline_step_time(sp: SystemParams, n_stages: int, n_micro: int,
                       layers: int, pod_beta: float) -> Dict[str, float]:
    """Whole-step time decomposition of a ``p``-pod 1F1B pipeline.

    Per-stage compute is ``layers/p`` layer times; the 1F1B bubble inflates
    the critical path by ``1/(1-bubble)``; boundary transfers hide behind
    compute when shorter than one stage's per-microbatch work (1F1B sends
    while the next microbatch computes), otherwise the excess is exposed.
    """
    p = sp.comm
    lt = layer_time("hecaton", sp)
    stage_layers = layers / n_stages
    work = lt["total"] * stage_layers * n_micro      # per-stage, all microbatches
    bubble = pipeline_bubble_fraction(n_stages, n_micro)
    comm = pipeline_boundary_comm(p, n_stages, n_micro, pod_beta)
    per_mb_compute = lt["total"] * stage_layers
    per_crossing = (comm["total"] / max(1, 2 * (n_stages - 1) * n_micro))
    exposed = max(0.0, per_crossing - per_mb_compute) * 2 * (n_stages - 1) \
        * n_micro
    total = work / (1.0 - bubble) + exposed
    return {"compute": work, "bubble_fraction": bubble,
            "boundary_comm": comm["total"], "exposed_boundary": exposed,
            "total": total}


# ---------------------------------------------------------------------------
# SRAM requirement model (paper §V-A b)
# ---------------------------------------------------------------------------

def peak_sram_bytes(method: str, p: CommParams) -> float:
    """Peak per-die activation-buffer bytes for the 4h FFN intermediate."""
    e = p.bytes_per_elt
    if method == "hecaton":
        return 4 * p.b * p.s * p.h / p.rootN * e          # Z gathered within a row
    if method in ("flat_ring", "torus_ring"):
        return 4 * p.b * p.s * p.h * e / 1                # full activations per die
    if method == "optimus":
        return 4 * p.b * p.s * p.h / p.rootN * e + p.h * p.h / p.N * e * 2
    raise KeyError(method)
