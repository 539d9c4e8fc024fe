"""Device time per layer and per phase of the train step, from the names
the program gives its layers.

The program wraps each layer of the step in ``jax.named_scope`` (the names
in ``LAYERS``).  The compiled step's ``as_text()`` carries, for every
instruction, the ``op_name`` path of the JAX operation it came from, such as
``jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/
rematted_computation/attention/sdpa/dot_general``; a profiler trace names
each device operation by its instruction.  This module joins the two:

- :func:`op_names` reads the HLO text into instruction -> ``op_name`` path;
  a fusion whose own path names no layer takes the first path among its
  fused instructions that does;
- :func:`layer_of` gives a path its innermost layer component (transform
  wrappers such as ``transpose(jvp(loss_head))`` are looked through), else
  ``unscoped``; :func:`phase_of` gives it ``recompute`` (under
  ``rematted_computation``), ``backward`` (under ``transpose(``) or
  ``forward``;
- :func:`read_trace` reads each device's "XLA Ops" and "XLA Modules" lines
  and the host spans;
- :func:`reduce` keeps the operations that ran inside the train step
  module's executions and inside the harness's ``window`` span, and sums
  their time per (layer, phase) over the step executions it counted.

Run as a script where JAX finds a TPU chip, it measures one cell the way a
traced benchmark run does (the same program, set-up and window, from
``bench/train_cell.py``), first untraced and then traced, and prints the
layer x phase table, the top operations with their layer and, last, one JSON
line.  It compiles the step afresh, past JAX's persistent cache::

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s>
"""

from __future__ import annotations

import bisect
import re
import sys
from collections import defaultdict
from pathlib import Path

if __package__ in (None, ""):            # run as a script
    ROOT = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace_reduce as TR  # noqa: E402

LAYERS = ("embed", "attention", "ffn", "norm", "loss_head", "optimizer",
          "grad_accum")
UNSCOPED = "unscoped"
PHASES = ("forward", "backward", "recompute")
# per-layer metrics (``bench/metrics/<name>.py``): layer -> all its phases
LAYER_METRICS = {"attention_ms_per_step": "attention",
                 "ffn_ms_per_step": "ffn",
                 "loss_head_ms_per_step": "loss_head",
                 "optimizer_ms_per_step": "optimizer",
                 "unscoped_ms_per_step": UNSCOPED}
RECOMPUTE_METRIC = "recompute_ms_per_step"

_HEADER = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s*\(.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")
_WRAPPED = re.compile(r"[\w\-]+\((.*)\)")


# ---------------------------------------------------------------------------
# names
# ---------------------------------------------------------------------------

def module_name(hlo_text: str) -> str:
    """``jit_train_step`` of the text's ``HloModule jit_train_step, ...``."""
    m = _MODULE.match(hlo_text)
    if m is None:
        raise ValueError("no HloModule line")
    return m.group(1)


def _innermost_layer(path: str):
    for comp in reversed(path.split("/")):
        while (m := _WRAPPED.fullmatch(comp)) is not None:
            comp = m.group(1)
        if comp in LAYERS:
            return comp
    return None


def _scoped_path(op_name: str):
    """The first ``;``-separated path of ``op_name`` that names a layer, and
    its layer; else (the first path, ``unscoped``)."""
    paths = op_name.split(";")
    for p in paths:
        layer = _innermost_layer(p)
        if layer is not None:
            return p, layer
    return paths[0], UNSCOPED


def layer_of(op_name: str) -> str:
    return _scoped_path(op_name)[1]


def phase_of(op_name: str) -> str:
    path = _scoped_path(op_name)[0]
    if "rematted_computation" in path:
        return "recompute"
    if "transpose(" in path:
        return "backward"
    return "forward"


def _parse(hlo_text: str):
    """Per instruction: its own op_name, the computation it calls, its
    operands; and each computation's instructions."""
    own, calls, operands, members, comp = {}, {}, {}, defaultdict(list), None
    for line in hlo_text.splitlines():
        h = _HEADER.match(line)
        if h is not None:
            comp = h.group(1)
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        name = m.group(1)
        members[comp].append(name)
        if (o := _OP_NAME.search(line)) is not None:
            own[name] = o.group(1)
        if (c := _CALLS.search(line)) is not None:
            calls[name] = c.group(1)
        operands[name] = _REF.findall(line[m.end():])
    for name, refs in operands.items():
        operands[name] = [r for r in refs if r in operands]
    return own, calls, operands, members


def op_names(hlo_text: str) -> dict:
    """{instruction: op_name path}.  An instruction keeps its own path where
    that names a layer.  Else a fusion takes the first path among its fused
    instructions that names one; and an instruction with no metadata at all
    (a copy, convert or async slice the compiler put in) takes the path of
    the nearest operand that names one, else of the nearest user, along a
    chain of such instructions."""
    own, calls, operands, members = _parse(hlo_text)
    base = dict(own)
    for name, comp in calls.items():
        if layer_of(own.get(name, "")) != UNSCOPED:
            continue
        for inner in members.get(comp, ()):
            if layer_of(own.get(inner, "")) != UNSCOPED:
                base[name] = own[inner]
                break
    users = defaultdict(list)
    for name, refs in operands.items():
        for r in refs:
            users[r].append(name)
    out = dict(base)
    for name in operands.keys() - base.keys():
        for graph in (operands, users):
            found, seen, todo = None, {name}, list(graph.get(name, ()))
            while todo and found is None:
                n = todo.pop(0)
                if n in seen:
                    continue
                seen.add(n)
                if layer_of(base.get(n, "")) != UNSCOPED:
                    found = base[n]
                elif n not in own:
                    todo.extend(graph.get(n, ()))
            if found is not None:
                out[name] = found
                break
    return out


# ---------------------------------------------------------------------------
# trace
# ---------------------------------------------------------------------------

def read_trace(path) -> dict:
    """{"devices": {plane: {"ops": [event], "modules": [event]}}, "host":
    [event]} of one ``.xplane.pb``; events are (name, start_ns,
    duration_ns), operations named by their instruction."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            devices[plane.name] = {
                key: [(TR._op_name(e.name), e.start_ns, e.duration_ns)
                      for e in lines[line].events] if line in lines else []
                for key, line in (("ops", "XLA Ops"),
                                  ("modules", "XLA Modules"))}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events)
    return {"devices": devices, "host": host}


def _executions(module: str, events) -> list:
    """``module``'s "XLA Modules" events (``<module>(<fingerprint>)``)."""
    return [e for e in events
            if e[0] == module or e[0].startswith(module + "(")]


def _inside(s, e, runs, starts) -> float:
    """Length of [s, e) that falls inside the sorted disjoint ``runs``."""
    i = max(0, bisect.bisect_right(starts, s) - 1)
    got = 0.0
    while i < len(runs) and runs[i][0] < e:
        got += max(0.0, min(e, runs[i][1]) - max(s, runs[i][0]))
        i += 1
    return got


def reduce(names: dict, module: str, trace: dict, top: int = 10) -> dict:
    """Milliseconds per step of each (layer, phase), mean over devices,
    from the operations inside both the window and the executions of
    ``module``; ``steps`` is the number of those executions that start in
    the window, ``busy_ms_per_step`` the union of those operations.  Control
    flow containers (``while``) are left out, as in ``trace_reduce``."""
    window = TR.window_of(trace["host"])
    per, ops, busy, n_steps = defaultdict(float), defaultdict(float), 0.0, 0
    devs = trace["devices"]
    for dev in devs.values():
        mine = _executions(module, dev["modules"])
        n_steps = max(n_steps, sum(window[0] <= s < window[1]
                                   for _, s, _ in mine))
        runs = TR.union(TR.clip(mine, window))
        starts = [r[0] for r in runs]
        kept = []
        for name, s, d in TR.leaves(dev["ops"]):
            t = _inside(s, s + d, runs, starts)
            if t > 0:
                path = names.get(name, "")
                per[(layer_of(path), phase_of(path))] += t
                ops[name] += t
                kept.append((s, s + d))
        busy += TR.length(TR.intersect(TR.union(kept), runs))
    if not n_steps:
        raise ValueError(f"no execution of {module!r} inside the window")
    scale = 1.0 / (len(devs) * n_steps * 1e6)
    table = {layer: {ph: per[(layer, ph)] * scale for ph in PHASES
                     if (layer, ph) in per}
             for layer in LAYERS + (UNSCOPED,)
             if any((layer, ph) in per for ph in PHASES)}
    ranked = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    out = {"module": module, "steps": n_steps,
           "busy_ms_per_step": busy * scale, "ms_per_step": table,
           "top_ops": [[n, layer_of(names.get(n, "")),
                        phase_of(names.get(n, "")), t * scale]
                       for n, t in ranked]}
    out["metrics"] = metrics(out)
    return out


def metrics(reduced: dict) -> dict:
    """The per-layer metrics of one :func:`reduce` result."""
    table = reduced["ms_per_step"]
    out = {name: sum(table.get(layer, {}).values())
           for name, layer in LAYER_METRICS.items()}
    out[RECOMPUTE_METRIC] = sum(row.get("recompute", 0.0)
                                for row in table.values())
    return out


def read_metric(m, name: str):
    """``name`` of the run's scope reduction (``m.scopes``, a
    :func:`reduce` result), or None where the run has none."""
    reduced = getattr(m, "scopes", None)
    return None if reduced is None else reduced["metrics"][name]


# ---------------------------------------------------------------------------
# one cell on the chip
# ---------------------------------------------------------------------------

def table_lines(reduced: dict) -> list:
    """The layer x phase table and the top operations, as text lines."""
    rows = [f"{'ms/step':<12}" + "".join(f"{p:>12}" for p in PHASES)
            + f"{'total':>12}"]
    for layer, row in reduced["ms_per_step"].items():
        rows.append(f"{layer:<12}"
                    + "".join(f"{row.get(p, 0.0):12.3f}" for p in PHASES)
                    + f"{sum(row.values()):12.3f}")
    rows.append(f"busy {reduced['busy_ms_per_step']:.3f} ms/step over "
                f"{reduced['steps']} executions of {reduced['module']}")
    rows += [f"  {t:10.3f} ms  {layer:<10} {phase:<9} {name}"
             for name, layer, phase, t in reduced["top_ops"]]
    return rows


def main(argv=None) -> int:
    import argparse
    import json
    import shutil
    import tempfile

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax
    from bench import spec, train_cell
    cell = spec.cell(args.workload)
    # the persistent cache's key leaves metadata out: a step cached from a
    # program that differs only in its names would bring its old names
    jax.config.update("jax_enable_compilation_cache", False)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"scopes: needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    devices = devices[:cell.chips]
    t = cell.traffic
    tokens = t["batch"] * t["seq_len"]
    prog = train_cell.Program(cell, devices)
    prog.setup(args.seed)
    n = t["check_steps"]
    prog.first_steps(n)
    text = prog.compiled.as_text()
    losses, window_s = train_cell.window(prog, n, args.seconds)
    untraced = len(losses) * tokens / window_s
    tmp = tempfile.mkdtemp(prefix="bench_scopes_")
    try:
        jax.profiler.start_trace(tmp)
        try:
            losses, window_s = train_cell.window(prog, n + len(losses),
                                                 args.seconds)
        finally:
            jax.profiler.stop_trace()
        path = TR.find_xplane(tmp)
        reduced = reduce(op_names(text), module_name(text), read_trace(path))
        whole = TR.reduce(TR.read_xplane(path))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    traced = len(losses) * tokens / window_s
    for line in table_lines(reduced):
        print(line, flush=True)
    busy = whole["busy_s"]
    print(json.dumps({
        "workload": cell.name, "seed": args.seed,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)},
        "train_tokens_per_s": {"untraced": untraced, "traced": traced},
        "traced_steps": len(losses), "scopes": reduced,
        "window_s": whole["window_s"],
        "busy_s": sum(busy.values()) / len(busy)}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
