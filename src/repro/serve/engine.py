"""Continuous-batching decode engine over the paged cache pool
(docs/DESIGN.md §10).

The engine owns a fixed set of decode **slots** (the jit batch dimension)
and a :class:`repro.serve.cache.CachePool`.  Each tick it

1. **admits** queued requests whose arrival time has passed, one slot
   each, while the pool's admission gate says their prompt blocks fit —
   an admission runs a single-sequence prefill through the slot's block
   table and samples the first token;
2. runs one **decode step** over ALL slots at once — inactive slots
   carry token 0 / length 0, their K/V writes land in the reserved null
   block and their logits are ignored, so admission and completion never
   change the jitted shapes (**slot padding**: the decode function is
   traced once for ``[slots, 1]`` and never again);
3. **finishes** sequences on EOS or their per-request token budget,
   freeing their blocks so the next queued prompt can be admitted.

Out-of-blocks mid-decode triggers the **eviction protocol**: the
youngest running sequence is preempted — its blocks are freed and its
request is requeued to restart from the prompt.  Greedy decode is
deterministic, so a preempted sequence's final tokens are identical to
an uninterrupted run; for stochastic sampling the per-request PRNG is
folded from (seed, request id, step index), which restores the same
draws on re-run.

Prefill shapes: attention-family prompts are right-padded to the next
multiple of the pool block size (padded positions write into the leased
tail or the null block and stay masked — bounded retraces, one per
distinct block count).  SSM and hybrid prompts run at their exact length
because padding a recurrence would corrupt the carried conv/SSD state
(one retrace per distinct prompt length in the trace).
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig, ParallelConfig, RunConfig
from repro.serve import step as SRV
from repro.serve.cache import CachePool, PoolConfig, blocks_for


@dataclass
class Request:
    rid: int
    prompt: np.ndarray          # [plen] int32 token ids
    max_new: int                # generation budget (includes the EOS token)
    arrival: int = 0            # tick at which the request becomes visible


@dataclass
class Finished:
    rid: int
    prompt_len: int
    tokens: List[int]           # generated ids (EOS included when hit)
    reason: str                 # "eos" | "max_new"
    preemptions: int = 0


@dataclass
class _Running:
    req: Request
    slot: int
    admit_seq: int              # monotone admission counter (eviction order)
    pending: int                # next input token id
    generated: List[int] = field(default_factory=list)
    preemptions: int = 0


class DecodeEngine:
    def __init__(self, cfg: ModelConfig, pcfg: ParallelConfig, rc: RunConfig,
                 params, pool: PoolConfig, *, mesh=None,
                 compute_dtype=jnp.float32, eos_id: Optional[int] = None,
                 method: str = "greedy", temperature: float = 1.0,
                 top_p: float = 0.9, seed: int = 0,
                 prompt_pad: Optional[int] = None, quant_kv: bool = False,
                 on_prefill: Optional[Callable] = None):
        """``on_prefill(request, last_logits)`` is called after every
        admission's prefill with the ``[1, 1, V]`` logits it sampled from."""
        self.cfg, self.pcfg, self.rc = cfg, pcfg, rc
        self.on_prefill = on_prefill
        self.params = params
        self.pool = CachePool(cfg, pool, dtype=compute_dtype,
                              quant_kv=quant_kv)
        self.eos_id = eos_id
        self.method, self.temperature, self.top_p = method, temperature, top_p
        self.base_key = jax.random.PRNGKey(seed)
        # fixed prefill width; None -> pad to the next block multiple
        self.prompt_pad = prompt_pad
        self.exact_prefill = cfg.family in ("ssm", "hybrid")
        self._prefill = jax.jit(SRV.build_prefill_paged(
            cfg, pcfg, mesh, compute_dtype=compute_dtype))
        self._decode = jax.jit(SRV.build_decode_step(
            cfg, pcfg, rc, mesh, compute_dtype=compute_dtype))
        self.queue: deque = deque()
        self.running: Dict[int, _Running] = {}      # slot -> state
        self.finished: Dict[int, Finished] = {}
        self.tick = 0
        self._admit_seq = 0
        self._preempt_counts: Dict[int, int] = {}
        self.stats = {"prefill_s": [], "decode_ticks": 0, "decode_tokens": 0,
                      "decode_s": 0.0, "preemptions": 0}

    # -- submission ------------------------------------------------------
    def submit(self, req: Request) -> None:
        total = len(req.prompt) + req.max_new
        if total > self.pool.pool.max_seq:
            raise ValueError(f"request {req.rid}: prompt+max_new={total} "
                             f"exceeds max_seq={self.pool.pool.max_seq}")
        if blocks_for(total, self.pool.pool.block) > self.pool.pool.leasable_blocks:
            raise ValueError(f"request {req.rid}: needs more blocks than the "
                             "pool owns — it could never finish")
        self.queue.append(req)

    def warmup(self, prompt_lens=(1,)) -> None:
        """Trace both jitted functions before timing starts.

        ``prompt_lens``: prompt lengths expected in the trace — each
        distinct padded prefill width compiles once here instead of
        inside the first timed admission.  Safe against the live pool:
        warmup leases a slot, prefills, and frees it — block reuse is
        safe because reads are masked by each slot's committed length."""
        for plen_i in sorted(set(int(p) for p in prompt_lens)):
            slot = self.pool.admit(plen_i)
            assert slot is not None, "warmup needs an idle pool"
            tokens, plen = self._pad_prompt(np.zeros(plen_i, np.int32))
            last, tree = self._prefill(self.params,
                                       self.pool.prefill_tree(slot),
                                       tokens, plen)
            self.pool.absorb_prefill(slot, tree)
            self.pool.free_slot(slot)
        logits, tree = self._decode(self.params, self.pool.decode_tree(),
                                    jnp.zeros((self.pool.pool.slots, 1), jnp.int32),
                                    jnp.zeros((self.pool.pool.slots, 1), jnp.int32))
        self.pool.absorb_decode(tree)
        jax.block_until_ready(logits)
        self.pool.peak_blocks_in_use = 0            # warmup doesn't count

    # -- internals -------------------------------------------------------
    def _pad_prompt(self, prompt: np.ndarray):
        plen = len(prompt)
        if self.exact_prefill:
            pad = plen
        elif self.prompt_pad is not None:
            pad = self.prompt_pad
        else:
            bs = self.pool.pool.block
            pad = blocks_for(plen, bs) * bs
        assert pad >= plen, (pad, plen)
        buf = np.zeros(pad, np.int32)
        buf[:plen] = prompt
        return jnp.asarray(buf)[None, :], jnp.int32(plen)

    def _sample_key(self, rid: int, step: int):
        if self.method == "greedy":
            return None
        return jax.random.fold_in(jax.random.fold_in(self.base_key, rid), step)

    def _sample_one(self, logits_row, rid: int, step: int) -> int:
        tok = SRV.sample(logits_row, method=self.method,
                         key=self._sample_key(rid, step),
                         temperature=self.temperature, top_p=self.top_p)
        return int(np.asarray(tok).reshape(-1)[0])

    def _finish(self, slot: int, reason: str) -> None:
        st = self.running.pop(slot)
        self.pool.free_slot(slot)
        self.finished[st.req.rid] = Finished(
            st.req.rid, len(st.req.prompt), list(st.generated), reason,
            self._preempt_counts.get(st.req.rid, 0))

    def _record_token(self, st: _Running, tok: int) -> bool:
        """Append a sampled token; True if the sequence is done."""
        st.generated.append(tok)
        if self.eos_id is not None and tok == self.eos_id:
            self._finish(st.slot, "eos")
            return True
        if len(st.generated) >= st.req.max_new:
            self._finish(st.slot, "max_new")
            return True
        st.pending = tok
        return False

    def _admit_ready(self) -> None:
        while self.queue and self.queue[0].arrival <= self.tick:
            req = self.queue[0]
            slot = self.pool.admit(len(req.prompt))
            if slot is None:
                return
            self.queue.popleft()
            t0 = time.perf_counter()
            tokens, plen = self._pad_prompt(np.asarray(req.prompt, np.int32))
            last, tree = self._prefill(self.params,
                                       self.pool.prefill_tree(slot),
                                       tokens, plen)
            last = jax.block_until_ready(last)
            self.stats["prefill_s"].append(time.perf_counter() - t0)
            if self.on_prefill is not None:
                self.on_prefill(req, last)
            self.pool.absorb_prefill(slot, tree)
            self.pool.commit_prefill(slot, len(req.prompt))
            st = _Running(req, slot, self._admit_seq, pending=-1)
            self._admit_seq += 1
            self.running[slot] = st
            self._record_token(st, self._sample_one(last[0, 0], req.rid, 0))

    def _evict_youngest(self) -> None:
        slot = max(self.running, key=lambda s: self.running[s].admit_seq)
        st = self.running.pop(slot)
        self.pool.free_slot(slot)
        st.req.arrival = self.tick          # requeue: restart from the prompt
        self.queue.appendleft(st.req)
        self.stats["preemptions"] += 1
        self._preempt_counts[st.req.rid] = \
            self._preempt_counts.get(st.req.rid, 0) + 1

    def _ensure_appends(self) -> None:
        for slot in sorted(self.running, key=lambda s: self.running[s].admit_seq):
            while slot in self.running and not self.pool.ensure_append(slot):
                if len(self.running) == 1:
                    raise RuntimeError("pool exhausted with one sequence "
                                       "running — submit() sizing bug")
                self._evict_youngest()

    def _decode_tick(self) -> None:
        self._ensure_appends()
        if not self.running:
            return
        S = self.pool.pool.slots
        tokens = np.zeros((S, 1), np.int32)
        for slot, st in self.running.items():
            tokens[slot, 0] = st.pending
        positions = np.asarray(self.pool.lengths, np.int32)[:, None]
        t0 = time.perf_counter()
        logits, tree = self._decode(self.params, self.pool.decode_tree(),
                                    jnp.asarray(tokens), jnp.asarray(positions))
        logits = jax.block_until_ready(logits)
        self.stats["decode_s"] += time.perf_counter() - t0
        self.stats["decode_ticks"] += 1
        self.pool.absorb_decode(tree)
        logits_h = np.asarray(logits)
        for slot in list(self.running):
            st = self.running[slot]
            self.pool.advance(slot)
            self.stats["decode_tokens"] += 1
            tok = self._sample_one(logits_h[slot, 0], st.req.rid,
                                   len(st.generated))
            self._record_token(st, tok)

    # -- driving ---------------------------------------------------------
    def step(self) -> None:
        """One engine tick: admit what fits, then decode every slot once."""
        self._admit_ready()
        self._decode_tick()
        self.tick += 1

    def run(self, requests: List[Request]) -> Dict[int, Finished]:
        """Drive a whole arrival trace to completion."""
        for r in sorted(requests, key=lambda r: (r.arrival, r.rid)):
            self.submit(r)
        while self.queue or self.running:
            self.step()
        return self.finished
