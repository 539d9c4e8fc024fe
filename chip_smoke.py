"""Smoke run of the Hecaton trainer and server on TPU chips.

    python chip_smoke.py             # one chip: train, then serve
    python chip_smoke.py --chips 4   # four chips: the Hecaton 2x2 phase only

One chip: ``repro.launch.train`` trains qwen3-0.6b at full width for 10
steps at seq 4096 (batch 2, full remat: the largest batch whose compiled
step fits 16 GB), and the step-0 loss is checked against a float32 forward
of the same batch; then ``repro.launch.serve`` answers 6 requests of mixed
prompt length through the paged engine, and one prefill's last-position
logits are checked against a float32 ``lm.forward`` of the prompt.

Four chips: the same model on a Hecaton ``data=1, mx=2, my=2`` mesh for 3
steps with ``overlap="none"`` (bulk AG/RS) and ``overlap="fused"`` (the
remote-DMA ring kernels), each checked step by step against the same steps
on one of the four chips.

Weights are random, from the launchers' fixed seeds.  Every phase runs in
this one process, which holds the chips.  Progress and measurements go to
earlier lines; the last line is one JSON object naming the device.  Any
failed check exits non-zero; so does a host whose JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro import compat  # noqa: E402

ARCH = "qwen3-0.6b"
# bf16 compute (8-bit significand) against a float32 reference.  The step-0
# loss is a mean over 8192 tokens, so per-token rounding error averages out
# far below this bound.  A prefill's logits keep the per-element error of
# 28 bf16 layers (about 2% relative L2 at this width), so they get a looser
# bound; they are also the sharper check, since at random init every loss
# sits near ln(vocab) whatever the hidden states are.
LOSS_RTOL = 1e-3
LOGITS_RTOL = 5e-2
# Sharded vs one-chip steps: both compute in bf16; only the order in which
# partial sums reduce differs.
MESH_LOSS_RTOL = 1e-3


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def rel_l2(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def kernel_count(compiled) -> int:
    """Pallas (Mosaic) kernels in a compiled program."""
    return compiled.as_text().count("tpu_custom_call")


def peak_bytes(device) -> int:
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", -1))


def train_args(*extra: str):
    from repro.launch import train
    return train.build_parser().parse_args(["--arch", ARCH, *extra])


def f32_loss(params, batch) -> float:
    """Mean next-token loss of ``batch`` under a float32 forward at highest
    matmul precision, one sequence at a time (every row has as many
    tokens, so the mean of row means is the batch mean)."""
    import jax
    import jax.numpy as jnp
    from repro.config import ParallelConfig, get_config
    from repro.models import lm
    from repro.parallel.context import PCtx

    cfg = get_config(ARCH)
    pctx = PCtx(None, ParallelConfig(), "train")

    @jax.jit
    def row_loss(p, tokens, labels):
        mb = {"tokens": tokens, "labels": labels, "_dtype": jnp.float32}
        return lm.train_loss(pctx, cfg, p, mb, remat="full")[1]["loss"]

    with jax.default_matmul_precision("highest"):
        rows = [float(row_loss(params, batch["tokens"][i:i + 1],
                               batch["labels"][i:i + 1]))
                for i in range(batch["tokens"].shape[0])]
    return sum(rows) / len(rows)


def phase_train(device) -> None:
    from repro.launch import train

    log(f"== train: {ARCH} full width, seq 4096, batch 2, 10 steps, one chip")
    ref = {}

    def on_start(params, opt_state, batch):
        ref["loss"] = f32_loss(params, batch)
        log(f"float32 reference step-0 loss {ref['loss']!r}")

    rec = train.run(train_args("--steps", "10", "--batch", "2",
                               "--seq", "4096", "--remat", "full"),
                    on_start=on_start)
    losses = [loss for _, loss in rec["history"]]
    log(f"losses {losses}")
    log(f"compile_s {rec['compile_s']!r}")
    log(f"step_s {rec['step_s']}")
    log(f"peak_bytes_in_use {peak_bytes(device)}")
    log(f"pallas kernels in the compiled step: {kernel_count(rec['compiled'])}")
    if len(losses) != 10 or not all(math.isfinite(v) for v in losses):
        fail(f"train losses not 10 finite values: {losses}")
    err = abs(losses[0] - ref["loss"]) / abs(ref["loss"])
    log(f"step-0 loss {losses[0]!r} vs float32 {ref['loss']!r}: "
        f"rel err {err!r} (tol {LOSS_RTOL})")
    if not err <= LOSS_RTOL:
        fail("step-0 loss off the float32 reference")


def phase_serve(device) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.config import ParallelConfig
    from repro.launch import serve
    from repro.models import lm
    from repro.parallel.context import PCtx

    gen = 16
    log(f"== serve: {ARCH} full width, 6 requests, paged engine, one chip")
    args = serve.build_parser().parse_args(
        ["--arch", ARCH, "--slots", "4", "--requests", "6", "--gen", str(gen),
         "--prompt-lens", "37,300,128"])
    got = {}

    def on_prefill(req, last):
        if req.rid == 1 and "logits" not in got:      # the 300-token prompt
            got["prompt"] = np.asarray(req.prompt)
            got["logits"] = np.asarray(last, np.float32).reshape(-1)

    rec = serve.run(args, on_prefill=on_prefill)
    log(f"serve warmup_s {rec['warmup_s']!r}")
    log(f"prefill_s {rec['stats']['prefill_s']}")
    log(f"decode_s {rec['stats']['decode_s']!r} for "
        f"{rec['stats']['decode_tokens']} tokens")
    log(f"peak_bytes_in_use {peak_bytes(device)}")
    fin = rec["finished"]
    if sorted(fin) != [r.rid for r in rec["requests"]]:
        fail(f"finished {sorted(fin)} of {len(rec['requests'])} requests")
    short = {rid: len(f.tokens) for rid, f in fin.items()
             if len(f.tokens) != gen}
    if short:
        fail(f"requests without {gen} tokens: {short}")

    cfg = rec["cfg"]
    pctx = PCtx(None, ParallelConfig(), "prefill")
    fwd = jax.jit(lambda p, t: lm.forward(
        pctx, cfg, p, {"tokens": t, "_dtype": jnp.float32}).logits[0, -1])
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(fwd(rec["params"], jnp.asarray(got["prompt"])[None]),
                         np.float32)
    err = rel_l2(got["logits"][:ref.shape[0]], ref)
    same = int(np.argmax(got["logits"][:ref.shape[0]])) == int(np.argmax(ref))
    log(f"prefill logits (prompt {len(got['prompt'])}) vs float32 forward: "
        f"rel L2 {err!r} (tol {LOGITS_RTOL}), same argmax {same}")
    if not err <= LOGITS_RTOL:
        fail("prefill logits off the float32 forward")


def phase_mesh(devices) -> None:
    from repro.launch import train

    shape = ("--steps", "3", "--batch", "1", "--seq", "1024",
             "--remat", "full")
    log(f"== Hecaton 2x2: {ARCH} full width, seq 1024, batch 1, 3 steps")
    ref = train.run(train_args(*shape), devices=devices[:1])
    ref_losses = [loss for _, loss in ref["history"]]
    log(f"one chip losses {ref_losses}")
    for mode in ("none", "fused"):
        rec = train.run(train_args(*shape, "--mesh-devices", "4",
                                   "--data", "1", "--mx", "2", "--my", "2",
                                   "--overlap", mode, "--comm-dtype", "bf16"),
                        devices=devices)
        losses = [loss for _, loss in rec["history"]]
        n_k = kernel_count(rec["compiled"])
        log(f"{mode}: losses {losses}")
        log(f"{mode}: compile_s {rec['compile_s']!r} step_s {rec['step_s']}")
        log(f"{mode}: pallas kernels (tpu_custom_call) in the compiled step: "
            f"{n_k}")
        if mode == "fused":
            log(f"fused: collectives that failed their fused_ok gate and ran "
                f"the ppermute ring: {rec['fallbacks']}")
            if n_k == 0:
                fail("overlap=fused compiled no ring kernel")
        errs = [abs(a - b) / abs(b) for a, b in zip(losses, ref_losses)]
        log(f"{mode}: rel err vs one chip per step {errs} "
            f"(tol {MESH_LOSS_RTOL})")
        if len(losses) != len(ref_losses) or not all(
                e <= MESH_LOSS_RTOL for e in errs):
            fail(f"overlap={mode} losses off the one-chip reference")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: train and serve on one chip; 4: only the "
                         "Hecaton 2x2 none/fused phase and its one-chip "
                         "reference")
    args = ap.parse_args()
    cache = compat.enable_compile_cache()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{dev.platform!r}); run it on a TPU host")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX found "
                         f"{len(devices)} device(s)")
    log(f"devices: {len(devices)} x {dev.device_kind}; compile cache {cache}")

    if args.chips == 4:
        phase_mesh(devices[:4])
    else:
        phase_train(dev)
        phase_serve(dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
