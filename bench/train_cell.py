"""One run of a training cell: set-up, the measured window, the check.

Set-up makes the weights and AdamW state on the device from the seed, in one
jitted call with the program's shardings, compiles the program's train step
(``repro.train.step.build_train_step``, jitted as ``repro.launch.train``
does) and drives that compiled step through the cell's first steps on
batches drawn from the seed.  It reads, from the program's own state, the
losses, the first clipped gradient (AdamW's first moment over 1 - beta1)
and the parameters' change.  The window then runs the same compiled step on
the same state for the given seconds.  Once it has closed and the program's
state is freed, the plain reference (``bench/reference.py``) runs the same
first steps from the same seed, and ``bench/check.py`` compares the two.
"""

from __future__ import annotations

import gc
import math
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import check, flops, reference, spec, trace_reduce
from bench.peaks import peaks


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileCounter:
    """Counts backend compilations while ``active``."""

    def __init__(self):
        self.active, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.active and event.endswith("backend_compile_duration"):
            self.count += 1


def model_config(conf: dict):
    """The registry's ModelConfig cut to the file's depth, checked against
    every size the file states."""
    from repro.config import get_config

    m = conf["program"]
    for field, key in conf["program_from"].items():
        if m[field] != conf[key]:
            raise ValueError(f"program {field}={m[field]} but {key}="
                             f"{conf[key]}")
    cfg = get_config(conf["registry"]).scaled(num_layers=m["num_layers"])
    have = {"num_layers": cfg.num_layers, "d_model": cfg.d_model,
            "num_heads": cfg.num_heads, "num_kv_heads": cfg.num_kv_heads,
            "head_dim": cfg.resolved_head_dim, "d_ff": cfg.d_ff,
            "vocab_size": cfg.vocab_size, "mlp_kind": cfg.mlp_kind,
            "norm_kind": cfg.norm_kind, "qk_norm": cfg.qk_norm,
            "rope_theta": cfg.rope_theta,
            "tie_embeddings": cfg.tie_embeddings}
    diff = {k: (v, m[k]) for k, v in have.items() if v != m[k]}
    if (diff or cfg.family != "dense" or cfg.moe or cfg.mla
            or cfg.padded_vocab != reference.padded_vocab(m)):
        raise ValueError(f"registry {conf['registry']!r} departs from the "
                         f"configuration file: {diff}")
    return cfg


def _shape_tree(tree):
    if isinstance(tree, dict):
        return {k: _shape_tree(v) for k, v in tree.items()}
    return tuple(tree.shape)


def ref_sharding(devices):
    """Leaf shape -> sharding for the reference: the largest dimension that
    divides by the device count is split over all devices."""
    if len(devices) == 1:
        one = jax.sharding.SingleDeviceSharding(devices[0])
        return lambda shape: one
    mesh = Mesh(np.asarray(devices), ("x",))
    n = len(devices)

    def f(shape):
        dims = [i for i, d in enumerate(shape) if d % n == 0]
        if not dims:
            return NamedSharding(mesh, P())
        i = max(dims, key=lambda i: shape[i])
        return NamedSharding(mesh, P(*([None] * i + ["x"])))
    return f


def make_batch(key, i, batch: int, seq: int, vocab: int):
    """Step i's tokens and next-token labels [batch, seq], uniform ids."""
    ids = jax.random.randint(jax.random.fold_in(key, i), (batch, seq + 1), 0,
                             vocab, jnp.int32)
    return {"tokens": ids[:, :-1], "labels": ids[:, 1:]}


def keys(seed: int):
    root = jax.random.PRNGKey(seed)
    return jax.random.fold_in(root, 0), jax.random.fold_in(root, 1)


class Program:
    """The program's compiled train step with its state, built from a cell.

    ``wrap_step`` (tests) wraps the pure step function before it is jitted,
    to plant a fault under the timed path."""

    def __init__(self, cell: spec.Cell, devices, *, wrap_step=None):
        from repro.config import ParallelConfig, RunConfig
        from repro.core import overlap as OV
        from repro.launch.mesh import make_small_mesh
        from repro.models import lm
        from repro.optim import adamw
        from repro.parallel import specs as SP
        from repro.train import step as TS

        t, m = cell.traffic, cell.config["program"]
        self.m, self.opt = m, t["optimizer"]
        cfg = model_config(cell.config)
        par, mesh_t = t["parallel"], t["mesh"]
        grid = mesh_t or {"data": 1, "mx": 1, "my": 1}
        mesh = (make_small_mesh(par["strategy"], grid["data"], grid["mx"],
                                grid["my"], devices=devices)
                if mesh_t else None)
        pcfg = ParallelConfig(
            strategy=par["strategy"], data=grid["data"],
            model=grid["mx"] * grid["my"], mx=grid["mx"], my=grid["my"],
            microbatches=par["microbatches"], zero1=True, remat=par["remat"],
            overlap=par["overlap"], comm_dtype=par["comm_dtype"])
        o = self.opt
        rc = RunConfig("bench", "train", t["seq_len"], t["batch"], lr=o["lr"],
                       weight_decay=o["weight_decay"], beta1=o["beta1"],
                       beta2=o["beta2"], grad_clip=o["grad_clip"],
                       warmup_steps=o["warmup_steps"])
        abstract = jax.eval_shape(lambda k: lm.init_params(cfg, k),
                                  jax.random.PRNGKey(0))
        if _shape_tree(abstract) != reference.param_shapes(m):
            raise ValueError("the program's parameter tree differs from the "
                             "reference's")
        self.batch_args = dict(batch=t["batch"], seq=t["seq_len"],
                               vocab=m["vocab_size"])
        if mesh is None:
            dev = jax.sharding.SingleDeviceSharding(devices[0])
            pshard = oshard = bshard = rshard = dev
        else:
            pspecs = SP.param_specs(abstract, mesh, pcfg)
            pshard = SP.sharding_tree(pspecs, mesh)
            oshard = SP.sharding_tree(
                SP.opt_state_specs(pspecs, abstract, mesh, pcfg), mesh)
            bshard = SP.sharding_tree(
                SP.batch_specs(mesh, pcfg, microbatched=False,
                               keys=("tokens", "labels"),
                               seq_len=t["seq_len"]), mesh)
            rshard = NamedSharding(mesh, P())

        def init(key):
            p = reference.init_params(m, key)
            return p, adamw.init(p)

        self._init = jax.jit(init, out_shardings=(pshard, oshard))
        self._batch = jax.jit(
            lambda key, i: make_batch(key, i, **self.batch_args),
            out_shardings=bshard)
        step = TS.build_train_step(cfg, pcfg, rc, mesh,
                                   total_steps=o["total_steps"],
                                   compute_dtype=jnp.bfloat16)
        if wrap_step is not None:
            step = wrap_step(step)
        self._step = jax.jit(step, donate_argnums=(0, 1),
                             in_shardings=(pshard, oshard, bshard),
                             out_shardings=(pshard, oshard, rshard))
        self._norms = jax.jit(reference.leaf_norms)
        def change(p, key):
            p0 = jax.lax.with_sharding_constraint(
                reference.init_params(m, key), pshard)
            return reference.leaf_norms(jax.tree.map(jnp.subtract, p, p0))

        self._change = jax.jit(change)
        self._fallbacks = OV.fused_fallbacks
        self.compiled, self.fallbacks = None, []

    def setup(self, seed: int) -> None:
        """State from the seed, and the step compiled for it once."""
        self.pkey, self.dkey = keys(seed)
        self.params, self.opt_state = self._init(self.pkey)
        if self.compiled is None:
            self._fallbacks.clear()
            self.compiled = self._step.lower(
                self.params, self.opt_state, self.batch(0)).compile()
            self.fallbacks = list(self._fallbacks)

    def batch(self, i: int):
        return self._batch(self.dkey, jnp.int32(i))

    def step(self, i: int):
        """Runs step i on the held state; returns its loss (on device)."""
        self.params, self.opt_state, met = self.compiled(
            self.params, self.opt_state, self.batch(i))
        return met["loss"]

    def first_steps(self, n: int) -> dict:
        """Steps 0..n-1 and the readings the check compares."""
        losses, grad = [], None
        for i in range(n):
            losses.append(self.step(i))
            if i == 0:
                grad = self._norms(self.opt_state.mu)
        change = self._change(self.params, self.pkey)
        g = np.asarray(grad, np.float64) / (1.0 - self.opt["beta1"])
        return {"losses": [float(x) for x in losses],
                "grad_norms": g.tolist(),
                "change_norms": np.asarray(change, np.float64).tolist()}

    def step_bytes(self) -> int | None:
        """Per-device bytes of the compiled step: arguments, outputs and
        temporaries less what outputs alias."""
        ma = self.compiled.memory_analysis()
        if ma is None:
            return None
        return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
                   + ma.temp_size_in_bytes - ma.alias_size_in_bytes)

    def free(self) -> None:
        del self.params, self.opt_state
        gc.collect()


def window(prog: Program, start: int, seconds: float):
    """Steps from ``start`` on until ``seconds`` have passed, one step in
    flight behind the one whose loss is fetched.  Returns (losses, seconds
    from the first dispatch to the last loss on the host)."""
    from jax.profiler import TraceAnnotation

    losses, pending, i = [], None, start
    with TraceAnnotation(trace_reduce.WINDOW_SPAN):
        t0 = time.perf_counter()
        while True:
            with TraceAnnotation("next_batch"):
                batch = prog.batch(i)
            with TraceAnnotation("dispatch"):
                prog.params, prog.opt_state, met = prog.compiled(
                    prog.params, prog.opt_state, batch)
            i += 1
            if pending is not None:
                with TraceAnnotation("loss_fetch"):
                    losses.append(float(pending))
            pending = met["loss"]
            if time.perf_counter() - t0 >= seconds:
                break
        with TraceAnnotation("loss_fetch"):
            losses.append(float(pending))
        t1 = time.perf_counter()
    return losses, t1 - t0


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, devices,
        t_start: float, *, wrap_step=None) -> dict:
    """One run of ``cell``; returns the result line's object."""
    counter = CompileCounter()
    t = cell.traffic
    prog = Program(cell, devices, wrap_step=wrap_step)
    prog.setup(seed)
    n_check = t["check_steps"]
    got = prog.first_steps(n_check)
    setup_s = time.perf_counter() - t_start
    log(f"setup_s {setup_s!r}; fused fallbacks {len(prog.fallbacks)}: "
        f"{prog.fallbacks}")

    tmp = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        counter.active = True
        if trace:
            jax.profiler.start_trace(tmp)
        try:
            losses, window_s = window(prog, n_check, seconds)
        finally:
            if trace:
                jax.profiler.stop_trace()
            counter.active = False
        log(f"compilations inside the window: {counter.count}")
        reduced = (trace_reduce.reduce(trace_reduce.read_xplane(
            trace_reduce.find_xplane(tmp))) if trace else None)
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)

    steps = len(losses)
    tokens_per_step = t["batch"] * t["seq_len"]
    step_bytes = prog.step_bytes()
    peak = max(memory_peak(devices), step_bytes or 0)
    log(f"window: {steps} steps in {window_s!r} s; losses {losses[0]!r} .. "
        f"{losses[-1]!r}; step bytes per device {step_bytes}; "
        f"peak_bytes_in_use {memory_peak(devices)}")
    prog.compiled = None
    prog.free()

    ref = reference.Reference(prog.m, prog.opt,
                              shardings=ref_sharding(devices))
    batch_fn = _ref_batches(prog, devices)
    t_ref = time.perf_counter()
    want = ref.run(prog.pkey, batch_fn, n_check)
    log(f"reference: {time.perf_counter() - t_ref!r} s; losses "
        f"{want['losses']}; program {got['losses']}")
    values = check.numbers(got, want)
    ok, checks = check.verdict(values, cell.limits)
    failed = sum(1 for x in losses if not math.isfinite(x))
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": bool(ok and failed == 0), "attempted": steps,
           "failed": failed}
    if trace:
        m = SimpleNamespace(
            steps=steps, window_s=window_s, chips=len(devices),
            tokens_per_step=tokens_per_step,
            flops_per_token=flops.train_flops_per_token(prog.m, t["seq_len"]),
            peaks=peaks(d0.device_kind), step_bytes=step_bytes,
            fallbacks=prog.fallbacks, trace=reduced)
        metrics = {}
        for entry in cell.per_layer:
            v = spec.metric_reader(entry["name"])(m)
            if v is not None:
                metrics[entry["name"]] = {"value": v, "unit": entry["unit"]}
        busy = reduced["busy_s"]
        device.update(busy_s=sum(busy.values()) / len(busy),
                      window_s=reduced["window_s"])
        out.update(metrics=metrics, device=device,
                   breakdown=reduced["breakdown"])
    else:
        e2e = {"setup_s": setup_s,
               "train_tokens_per_s": steps * tokens_per_step / window_s}
        out.update(metrics={e["name"]: {"value": e2e[e["name"]],
                                        "unit": e["unit"]}
                            for e in cell.end_to_end},
                   device=device)
    out["checks"] = checks
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return out


def _ref_batches(prog: Program, devices):
    """Step i's (tokens, labels) for the reference, from the same generator
    on the reference's devices."""
    args = prog.batch_args
    put = jax.jit(lambda key, i: make_batch(key, i, **args),
                  out_shardings=ref_sharding(devices)((args["batch"],
                                                       args["seq"])))

    def fn(i):
        b = put(prog.dkey, jnp.int32(i))
        return b["tokens"], b["labels"]
    return fn
