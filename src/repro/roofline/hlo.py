"""Mini HLO-text analyzer for roofline extraction.

XLA's ``compiled.cost_analysis()`` visits every op ONCE — it does not scale loop
bodies by trip count, so a scan-over-layers model reports ~1/L of its real FLOPs.
This module parses the optimized (post-SPMD) HLO text, recovers the computation
call graph (while bodies x trip counts, fusions, calls), and accumulates:

  * flops            — from dot/convolution ops (2 * prod(result) * contracted)
  * hbm_bytes        — fusion-boundary traffic model: operand + result bytes of
                       top-level (unfused) ops — XLA's fusion boundaries are
                       exactly where HBM round-trips happen
  * collective bytes — per collective type, ring-transfer model:
                       AG (g-1)*shard, RS (g-1)/g*operand, AR 2x that, CP 1x
                       (paper eq. (1): ring time ∝ (g-1)/g * S / bw)

Shapes in post-SPMD HLO are per-device, so every number is per-chip.
"""

from __future__ import annotations

import math
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
}

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def _shapes_in(s: str) -> List[Tuple[str, List[int]]]:
    out = []
    for m in _SHAPE_RE.finditer(s):
        if m.group(1) in DTYPE_BYTES:
            dims = [int(d) for d in m.group(2).split(",") if d]
            out.append((m.group(1), dims))
    return out


def _bytes_of(shapes: List[Tuple[str, List[int]]]) -> int:
    return sum(DTYPE_BYTES[dt] * math.prod(dims or [1]) for dt, dims in shapes)


@dataclass
class OpCost:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    s2_bytes: float = 0.0      # S^2-shaped attention intermediates (see below)
    coll_bytes: Dict[str, float] = field(default_factory=lambda: defaultdict(float))
    coll_count: Dict[str, float] = field(default_factory=lambda: defaultdict(float))

    def add(self, other: "OpCost", mult: float = 1.0):
        self.flops += other.flops * mult
        self.hbm_bytes += other.hbm_bytes * mult
        self.s2_bytes += other.s2_bytes * mult
        for k, v in other.coll_bytes.items():
            self.coll_bytes[k] += v * mult
        for k, v in other.coll_count.items():
            self.coll_count[k] += v * mult

    @property
    def total_coll_bytes(self) -> float:
        return sum(self.coll_bytes.values())

    @property
    def hbm_bytes_kernel_adjusted(self) -> float:
        """HBM bytes assuming attention runs as a fused flash kernel: the
        [*, Sq, Sk] score/prob intermediates the jnp ``_sdpa`` materializes
        never leave VMEM in kernels/flash_attention.py, so they are excluded
        (their Q/K/V/O boundary tensors remain counted)."""
        return self.hbm_bytes - self.s2_bytes


# metadata markers for attention score/prob tensors: the einsum strings from
# models/attention.py (scores 'bhqd,bhdk->bhqk', SV 'bhqk,bhkd->', grouped
# decode 'bcgqs') and the softmax that sits between them.
_ATTN_META = ("bhqk", "bcgqs", "bchqk", "softmax")


def _is_attn_line(line: str) -> bool:
    m = re.search(r'op_name="([^"]*)"', line)
    return bool(m) and any(t in m.group(1) for t in _ATTN_META)


def _is_s2(shapes: List[Tuple[str, List[int]]], line: str = "") -> bool:
    """Attention score/prob tensors: fp32, >=4MB, shaped either
    [*, q_block=1024, Sk>=1024] (models/attention.py chunks q at 1024) or
    square [*, S, S] (direct path, e.g. whisper's 1500 frames).

    Metadata (einsum names) would be the precise signal but XLA strips
    op_name from fused ops in optimized dumps; the fp32 requirement excludes
    bf16 activations, and the exact q-block width excludes norm/rope fp32
    upcasts of [*, S, H] activations."""
    for dt, dims in shapes:
        if dt != "f32" or len(dims) < 2:
            continue
        d1, d2 = dims[-2], dims[-1]
        big = math.prod(dims) * 4 >= 4 * 2 ** 20
        if big and ((d1 == 1024 and d2 >= 1024) or (d1 == d2 >= 1024)):
            return True
    return False


_GROUPS_BRACE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA = re.compile(r"replica_groups=\[([0-9,]+)\]<=")
_TRIP = re.compile(r'known_trip_count[\\"]*:\s*\{[\\"]*n[\\"]*:[\\"]*(\d+)')
_NAME = re.compile(r"^(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_WHILE_PARTS = re.compile(r"condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)")
_CALLEE = re.compile(r"(?:to_apply|calls)=%?([\w.\-]+)")
_OPRNDS = re.compile(r"\(([^()]*(?:\([^()]*\)[^()]*)*)\)")

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

# Ops whose standalone appearance in CPU-backend HLO would not round-trip HBM on
# a TPU (layout changes fuse into neighbors; converts fuse into the producer).
# Counting them would bias the memory term by the CPU backend's weaker fusion.
SKIP_BYTES_OPS = ("parameter(", "constant(", "get-tuple-element(", "tuple(",
                  "bitcast(", "copy(", " while(", "after-all(",
                  "opt-barrier(", "transpose(", "convert(", "reshape(",
                  "broadcast(", "iota(")


def group_size(line: str) -> int:
    m = _GROUPS_BRACE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS_IOTA.search(line)
    if m:
        dims = [int(d) for d in m.group(1).split(",")]
        total = math.prod(dims)
        return total // dims[0] if dims[0] else 1
    return 1


class HLOModule:
    """Parses an optimized HLO dump into computations + a module-wide symbol
    table (op name -> result shapes), then folds costs over the call graph."""

    def __init__(self, text: str):
        self.computations: Dict[str, List[str]] = {}
        self.entry: Optional[str] = None
        self.symbols: Dict[str, List[Tuple[str, List[int]]]] = {}
        self._parse(text)
        self._cost_cache: Dict[Tuple[str, bool], OpCost] = {}

    def _parse(self, text: str):
        cur = None
        for raw in text.splitlines():
            ls = raw.strip()
            if not ls or ls.startswith(("//", "#")):
                continue
            if ls.endswith("{") and "->" in ls and "=" not in ls.split("(")[0]:
                hdr = re.match(r"(ENTRY\s+)?%?([\w.\-]+)\s*\(", ls)
                if hdr:
                    cur = hdr.group(2)
                    self.computations[cur] = []
                    if hdr.group(1):
                        self.entry = cur
                    # header params: "name: f32[...]"
                    for pm in re.finditer(r"([\w.\-]+):\s*((?:\([^)]*\))|"
                                          r"(?:[a-z0-9]+\[[0-9,]*\]))", ls):
                        self.symbols[pm.group(1)] = _shapes_in(pm.group(2))
                    continue
            if ls == "}" or ls.startswith("}"):
                cur = None
                continue
            if cur is not None:
                m = _NAME.match(ls)
                if m:
                    self.computations[cur].append(ls)
                    rhs = m.group(2)
                    # result type = everything before the op name token
                    self.symbols[m.group(1)] = _shapes_in(rhs.split(")")[0]
                                                          if rhs.startswith("(")
                                                          else rhs.split(" ")[0])
        if self.entry is None and self.computations:
            self.entry = next((n for n in self.computations if "main" in n),
                              next(iter(self.computations)))

    # -----------------------------------------------------------------
    def _operand_names(self, line: str, op: str) -> List[str]:
        i = line.find(f" {op}(")
        if i < 0:
            return []
        m = _OPRNDS.search(line[i:])
        if not m:
            return []
        return re.findall(r"%([\w.\-]+)", m.group(1))

    def _operand_shapes(self, line: str, op: str):
        return [self.symbols.get(n, []) for n in self._operand_names(line, op)]

    def _result_shapes(self, line: str):
        m = _NAME.match(line)
        return self.symbols.get(m.group(1), []) if m else []

    def _dot_flops(self, line: str) -> float:
        rdims = self._result_shapes(line)
        rsize = sum(math.prod(d or [1]) for _, d in rdims)
        ops = self._operand_shapes(line, "dot")
        lhs = ops[0][0][1] if ops and ops[0] else []
        c = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", line)
        contract = 1
        if c and lhs:
            for d in c.group(1).split(","):
                if d and int(d) < len(lhs):
                    contract *= lhs[int(d)]
        return 2.0 * rsize * contract

    def _trip_count(self, line: str, cond: str) -> int:
        m = _TRIP.search(line)
        if m:
            return int(m.group(1))
        n = 1
        for l in self.computations.get(cond, ()):
            mm = re.search(r"constant\((\d+)\)", l)
            if mm:
                n = max(n, int(mm.group(1)))
        return n

    def _line_cost(self, line: str):
        """Returns (own OpCost, optional (callee, mult, flops_only))."""
        c = OpCost()
        if " while(" in line:
            m = _WHILE_PARTS.search(line)
            if m:
                cond, body = m.group(1), m.group(2)
                trips = self._trip_count(line, cond)
                sub = OpCost()
                sub.add(self.cost(body))
                sub.add(self.cost(cond))
                c.add(sub, trips)
            return c, None
        for coll in COLLECTIVES:
            if f" {coll}(" in line or f" {coll}-start(" in line:
                op = coll if f" {coll}(" in line else f"{coll}-start"
                g = group_size(line)
                ins = self._operand_shapes(line, op)
                in_b = sum(_bytes_of(s) for s in ins)
                out_b = _bytes_of(self._result_shapes(line))
                if coll == "all-gather":
                    t = in_b * (g - 1)
                elif coll == "reduce-scatter":
                    t = in_b * (g - 1) / max(g, 1)
                elif coll == "all-reduce":
                    t = 2 * in_b * (g - 1) / max(g, 1)
                elif coll == "all-to-all":
                    t = in_b * (g - 1) / max(g, 1)
                else:
                    t = in_b
                c.coll_bytes[coll] += t
                c.coll_count[coll] += 1
                c.hbm_bytes += in_b + out_b
                return c, None
        if " dot(" in line:
            c.flops += self._dot_flops(line)
            res = self._result_shapes(line)
            c.hbm_bytes += _bytes_of(res)
            if _is_s2(res, line):
                c.s2_bytes += _bytes_of(res)
            for s in self._operand_shapes(line, "dot"):
                c.hbm_bytes += _bytes_of(s)
                if _is_s2(s, line):      # SV dot reading [Sq,Sk] probs
                    c.s2_bytes += _bytes_of(s)
            return c, None
        if " convolution(" in line:
            rsize = sum(math.prod(d or [1]) for _, d in self._result_shapes(line))
            ops = self._operand_shapes(line, "convolution")
            ker = math.prod(ops[1][0][1][:-1]) if len(ops) > 1 and ops[1] else 1
            c.flops += 2.0 * rsize * max(1, ker)
            return c, None
        m = re.search(r"\b(fusion|call|map)\(", line)
        if m:
            kind = m.group(1)
            callee = _CALLEE.search(line)
            # fusion boundary: count the write (result) once; reads of its
            # operands belong to the producers on a TPU-grade fusion pipeline
            # (counting fan-in here would double-bill every residual edge).
            res = self._result_shapes(line)
            c.hbm_bytes += _bytes_of(res)
            if _is_s2(res, line):
                c.s2_bytes += _bytes_of(res)
            if callee:
                return c, (callee.group(1), 1.0, kind == "fusion")
            return c, None
        if " conditional(" in line:
            br = re.search(r"branch_computations=\{([^}]*)\}", line)
            if br:
                names = re.findall(r"%?([\w.\-]+)", br.group(1))
                if names:
                    return c, (names[0], 1.0, False)
            return c, None
        if " custom-call(" in line:
            callee = _CALLEE.search(line)
            c.hbm_bytes += _bytes_of(self._result_shapes(line))
            if callee:
                return c, (callee.group(1), 1.0, False)
            return c, None
        if not any(k in line for k in SKIP_BYTES_OPS):
            res = self._result_shapes(line)
            c.hbm_bytes += _bytes_of(res)
            if _is_s2(res, line):
                c.s2_bytes += _bytes_of(res)
        return c, None

    def cost(self, name: Optional[str] = None,
             flops_only: bool = False) -> OpCost:
        name = name or self.entry
        key = (name, flops_only)
        if key in self._cost_cache:
            return self._cost_cache[key]
        total = OpCost()
        self._cost_cache[key] = total           # cycle guard
        for line in self.computations.get(name, ()):
            own, callee = self._line_cost(line)
            if flops_only:
                own.hbm_bytes = 0.0
                own.s2_bytes = 0.0
            total.add(own)
            if callee:
                sub, mult, sub_fo = callee
                if sub in self.computations and sub != name:
                    total.add(self.cost(sub, flops_only or sub_fo), mult)
        return total


def analyze(text: str) -> OpCost:
    return HLOModule(text).cost()
