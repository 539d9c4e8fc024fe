"""Serving launcher: continuous-batching decode over the paged cache pool.

Feeds the engine a synthetic arrival trace (more requests than slots,
mixed prompt lengths) and reports prefill latency and decode tok/s
SEPARATELY — both jitted functions are warmed up first so compile time
never pollutes the throughput number.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
        --slots 4 --requests 8 --gen 16 --sample top_p --eos-id 7
"""

from __future__ import annotations

import argparse
import time


def build_trace(rng, n_requests, vocab, prompt_lens, gen, arrival_every):
    """Deterministic synthetic arrival trace with mixed prompt lengths."""
    import numpy as np
    from repro.serve.engine import Request
    reqs = []
    for i in range(n_requests):
        plen = prompt_lens[i % len(prompt_lens)]
        prompt = rng.integers(0, vocab, size=plen).astype(np.int32)
        reqs.append(Request(rid=i, prompt=prompt, max_new=gen,
                            arrival=i // max(1, arrival_every)))
    return reqs


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--slots", type=int, default=4,
                    help="decode slots (fixed jit batch)")
    ap.add_argument("--block", type=int, default=16,
                    help="tokens per KV pool block")
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="pool blocks incl. the null block (0 = auto)")
    ap.add_argument("--max-seq", type=int, default=0,
                    help="per-sequence prompt+gen cap (0 = auto)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--arrival-every", type=int, default=2,
                    help="new arrivals per engine tick")
    ap.add_argument("--prompt-lens", default="8,24,16",
                    help="comma list cycled over the trace")
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--eos-id", type=int, default=-1,
                    help="stop generation at this token id (-1 = off)")
    ap.add_argument("--sample", default="greedy",
                    choices=["greedy", "temperature", "top_p"])
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--top-p", type=float, default=0.9)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quant-kv", action="store_true",
                    help="store paged K/V as int8 + per-row fp32 scales "
                         "(docs/DESIGN.md §11)")
    return ap


def run(args, *, on_prefill=None) -> dict:
    """Serve the synthetic trace the command line ``args`` describe.

    Computes in bf16 over fp32 master weights (ModelConfig.dtype_note).
    ``on_prefill(request, last_logits)`` is called after each admission's
    prefill with the logits at its last prompt position.  Returns the
    record: ``finished`` (rid -> Finished), ``requests``, ``params``,
    ``cfg``, ``warmup_s`` and the engine's ``stats``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.config import ParallelConfig, RunConfig, get_config, \
        get_smoke_config
    from repro.models import lm
    from repro.serve.cache import PoolConfig, blocks_for, dense_cache_bytes
    from repro.serve.engine import DecodeEngine

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    prompt_lens = [int(x) for x in args.prompt_lens.split(",") if x]
    max_seq = args.max_seq or max(prompt_lens) + args.gen
    num_blocks = args.num_blocks or \
        args.slots * blocks_for(max_seq, args.block) + 1
    pool = PoolConfig(slots=args.slots, block=args.block,
                      num_blocks=num_blocks, max_seq=max_seq)
    rc = RunConfig("serve", "decode", max_seq, args.slots)
    pcfg = ParallelConfig(strategy="hecaton", data=1, model=1, mx=1, my=1)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))

    eng = DecodeEngine(cfg, pcfg, rc, params, pool, compute_dtype=jnp.bfloat16,
                       eos_id=None if args.eos_id < 0 else args.eos_id,
                       method=args.sample, temperature=args.temperature,
                       top_p=args.top_p, seed=args.seed,
                       quant_kv=args.quant_kv, on_prefill=on_prefill)
    t0 = time.perf_counter()
    eng.warmup(prompt_lens=prompt_lens)  # compile BEFORE the clock starts
    warmup_s = time.perf_counter() - t0
    print(f"warmup (jit) {warmup_s:.2f}s")

    rng = np.random.default_rng(args.seed)
    reqs = build_trace(rng, args.requests, cfg.vocab_size, prompt_lens,
                       args.gen, args.arrival_every)
    fin = eng.run(reqs)

    pf = eng.stats["prefill_s"]
    dec_s = max(eng.stats["decode_s"], 1e-9)
    print(f"{len(fin)} sequences  ticks={eng.stats['decode_ticks']}  "
          f"preemptions={eng.stats['preemptions']}")
    print(f"prefill latency  mean {1e3 * sum(pf) / max(1, len(pf)):.1f} ms  "
          f"max {1e3 * max(pf):.1f} ms")
    print(f"decode           {eng.stats['decode_tokens']} tokens in "
          f"{dec_s:.2f}s  ({eng.stats['decode_tokens'] / dec_s:.1f} tok/s)")
    print(f"pool             peak {eng.pool.peak_blocks_in_use}/"
          f"{pool.leasable_blocks} blocks  "
          f"(dense arena equiv {pool.dense_equiv_blocks} blocks / "
          f"{dense_cache_bytes(cfg, args.slots, max_seq, jnp.bfloat16)} B)")
    for rid in sorted(fin)[:4]:
        f = fin[rid]
        print(f"  rid={rid} plen={f.prompt_len} {f.reason:7s} "
              f"tokens={f.tokens[:10]}")
    return {"finished": fin, "requests": reqs, "params": params, "cfg": cfg,
            "warmup_s": warmup_s, "stats": eng.stats}


def main(argv=None):
    args = build_parser().parse_args(argv)
    from repro import compat
    compat.enable_compile_cache()
    run(args)


if __name__ == "__main__":
    main()
