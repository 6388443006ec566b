"""Continuous-batching serving engine.

The scheduling layer above serve/steps.py: requests arrive with a prompt
and a token budget; the engine maintains a fixed-width decode batch,
refilling freed slots by prefilling queued requests — vLLM-style
continuous batching on a dense per-slot cache, with the paged/tiered
cache manager (tpu/kv_cache.py) tracking page residency for the HERMES
eviction/prefetch policies.

Single-host reference implementation: correctness (prefill→decode
consistency, slot recycling, determinism) is what the tests pin down;
the dry-run lowers the same step functions at production shapes.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, RunConfig
from repro.models import model as mdl
from repro.serve.steps import (build_decode_step, build_prefill_step,
                               cast_floating)
from repro.tpu.kv_cache import PagedKVManager


def init_serving_params(cfg: ModelConfig, rc: RunConfig, seed: int):
    """Seeded weights, made on the device directly in the compute dtype:
    serving keeps no f32 master copy (at published widths the f32 tree
    alone would take most of one chip's HBM)."""
    return jax.jit(lambda key: cast_floating(mdl.init_params(cfg, key),
                                             rc.compute_dtype))(
        jax.random.PRNGKey(seed))


@dataclasses.dataclass
class Request:
    req_id: int
    prompt: np.ndarray           # (S,) or (S, nq) tokens
    max_new_tokens: int
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    #: the request was evicted (TTL expiry / step-budget drain), not
    #: completed — its partial output is still in out_tokens
    dropped: bool = False
    #: engine step at which the request was admitted (prefilled)
    born_step: Optional[int] = None


class ServingEngine:
    def __init__(self, cfg: ModelConfig, rc: RunConfig, params,
                 batch_slots: int = 4, max_seq: int = 512,
                 greedy: bool = True, page_size: Optional[int] = None,
                 hbm_frac: Optional[float] = None,
                 eos_id: Optional[int] = None,
                 request_ttl_steps: Optional[int] = None):
        self.cfg = cfg
        self.rc = rc
        # served weights live in the compute dtype (no master copy)
        self.params = cast_floating(params, rc.compute_dtype)
        self.slots = batch_slots
        self.max_seq = max_seq
        self.greedy = greedy
        self.prefill = jax.jit(build_prefill_step(cfg, rc, max_seq))
        self.decode = jax.jit(build_decode_step(cfg, rc))
        self.queue: List[Request] = []
        self.active: List[Optional[Request]] = [None] * batch_slots
        # one dense cache per slot (batch=1) so slots swap independently
        self.caches: List[Optional[Dict]] = [None] * batch_slots
        # page geometry from the RunConfig (the capacity planner's
        # paged_kv_offload rung): hbm_kv_budget_frac of the per-slot
        # pages stay in the bandwidth tier, the rest is host capacity
        if page_size is None:
            page_size = min(rc.kv_page_size, max(1, max_seq // 2))
        if hbm_frac is None:
            hbm_frac = rc.hbm_kv_budget_frac
        pages_per_seq = max(1, -(-max_seq // page_size))
        total = batch_slots * pages_per_seq
        hbm_pages = max(batch_slots, int(total * hbm_frac))
        self.pages = PagedKVManager(
            page_size=page_size,
            hbm_budget_pages=hbm_pages,
            host_budget_pages=max(total - hbm_pages, 0) + 4 * total)
        self.steps = 0
        # liveness: a request that never samples EOS (e.g. decoding off
        # a corrupted KV page) must not spin its slot forever —
        # request_ttl_steps bounds its residency, and anything still
        # live when the step budget runs out is drained, not lost
        self.eos_id = eos_id
        self.request_ttl_steps = request_ttl_steps
        self.dropped: List[Request] = []
        self.n_finished = 0

    # -- API --------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def run(self, max_steps: int = 10_000) -> List[Request]:
        finished: List[Request] = []
        while (any(self.active) or self.queue) and self.steps < max_steps:
            self._fill_slots()
            self._decode_once(finished)
            self.steps += 1
        # drain: requests still resident (or queued) when the step
        # budget runs out are dropped with their pages freed and
        # counted in stats — never silently leaked
        for slot in range(self.slots):
            if self.active[slot] is not None:
                self._drop(slot)
        while self.queue:
            req = self.queue.pop(0)
            req.dropped = True
            self.dropped.append(req)
        return finished

    @property
    def stats(self) -> Dict[str, object]:
        """Liveness counters: completed vs dropped requests."""
        return {"finished": self.n_finished,
                "dropped": len(self.dropped),
                "dropped_ids": [r.req_id for r in self.dropped],
                "steps": self.steps}

    # -- internals -----------------------------------------------------------------
    def _sample(self, logits: jax.Array) -> int:
        if self.cfg.family == "audio":
            # one token per codebook; engine tracks codebook 0 for stop
            return int(jnp.argmax(logits[0]))
        return int(jnp.argmax(logits))

    def _fill_slots(self) -> None:
        for slot in range(self.slots):
            if self.active[slot] is not None or not self.queue:
                continue
            req = self.queue.pop(0)
            req.born_step = self.steps
            toks = jnp.asarray(req.prompt)[None]
            logits, cache = self.prefill(self.params, toks)
            for t in range(len(req.prompt)):
                self.pages.append_token(req.req_id)
            first = self._sample(logits[0])
            req.out_tokens.append(first)
            self.active[slot] = req
            self.caches[slot] = cache
            if self.eos_id is not None and first == self.eos_id:
                req.done = True       # EOS at prefill: finish w/o decode

    def _finish(self, slot: int, finished: List[Request]) -> None:
        req = self.active[slot]
        req.done = True
        finished.append(req)
        self.n_finished += 1
        self.pages.free_seq(req.req_id)
        self.active[slot] = None
        self.caches[slot] = None

    def _drop(self, slot: int) -> None:
        req = self.active[slot]
        req.dropped = True
        self.dropped.append(req)
        self.pages.free_seq(req.req_id)
        self.active[slot] = None
        self.caches[slot] = None

    def _decode_once(self, finished: List[Request]) -> None:
        for slot in range(self.slots):
            req = self.active[slot]
            if req is None:
                continue
            if req.done:              # EOS sampled at prefill
                self._finish(slot, finished)
                continue
            if (self.request_ttl_steps is not None
                    and req.born_step is not None
                    and self.steps - req.born_step
                    >= self.request_ttl_steps):
                self._drop(slot)      # TTL expiry: evict, free pages
                continue
            self.pages.prefetch_for_decode(req.req_id)
            last = req.out_tokens[-1]
            if self.cfg.family == "audio":
                tok = jnp.full((1, 1, self.cfg.n_codebooks), last, jnp.int32)
            else:
                tok = jnp.asarray([[last]], jnp.int32)
            logits, cache = self.decode(self.params, self.caches[slot], tok)
            self.pages.append_token(req.req_id)
            nxt = self._sample(logits[0])
            req.out_tokens.append(nxt)
            self.caches[slot] = cache
            total = len(req.prompt) + len(req.out_tokens)
            if ((self.eos_id is not None and nxt == self.eos_id)
                    or len(req.out_tokens) >= req.max_new_tokens
                    or total >= self.max_seq - 1):
                self._finish(slot, finished)
