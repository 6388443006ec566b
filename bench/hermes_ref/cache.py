"""Set-associative cache model with pluggable replacement policies.

Implements the paper's cache substrate (gem5 analogue).  Two policies:

* ``lru``          — classic least-recently-used (baseline).
* ``tensor_aware`` — the paper's tensor-aware caching: victim selection
  prefers *streaming* tensor lines over *resident* (high-reuse) tensor
  lines, so weights / KV-like tensors survive bursts of streaming
  activations.  See ``tensor_cache.py`` for the policy itself.

The cache is write-back / write-allocate.  Lines carry MESI state (driven
externally by ``coherence.MESIDirectory``) plus tensor metadata used by the
tensor-aware policy.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from .params import CacheParams
from .tensor_cache import ReplacementPolicy, make_policy

# MESI states
INVALID, SHARED, EXCLUSIVE, MODIFIED = 0, 1, 2, 3


class Line:
    """One cache line's bookkeeping (tag store entry)."""

    __slots__ = ("tag", "state", "dirty", "tensor_id", "reuse_class",
                 "last_touch", "prefetched", "ready_time")

    def __init__(self, tag: int, tensor_id: int, reuse_class: int, now: int,
                 prefetched: bool = False, ready_time: float = 0.0):
        self.tag = tag
        self.state = EXCLUSIVE
        self.dirty = False
        self.tensor_id = tensor_id
        self.reuse_class = reuse_class
        self.last_touch = now
        self.prefetched = prefetched
        self.ready_time = ready_time


class Cache:
    """One cache level (a private L1/L2 or the shared L3)."""

    def __init__(self, params: CacheParams):
        self.params = params
        self.n_sets = params.n_sets
        self.assoc = params.assoc
        self.line_bits = params.line_size.bit_length() - 1
        self.set_mask = self.n_sets - 1
        # sets[i] maps tag -> Line; insertion order is irrelevant (policy
        # decides victims), dict gives O(1) lookup.
        self.sets: List[Dict[int, Line]] = [dict() for _ in range(self.n_sets)]
        self.policy: ReplacementPolicy = make_policy(params.policy, params.ta)
        # statistics
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.dirty_evictions = 0
        self.prefetch_fills = 0
        self.prefetch_useful = 0

    # -- address helpers ----------------------------------------------------
    def split(self, addr: int) -> Tuple[int, int]:
        block = addr >> self.line_bits
        return block & self.set_mask, block >> (self.n_sets.bit_length() - 1)

    # -- operations ---------------------------------------------------------
    def lookup(self, addr: int, now: int, is_write: bool) -> Optional[Line]:
        """Demand access.  Returns the Line on hit, None on miss."""
        set_idx, tag = self.split(addr)
        line = self.sets[set_idx].get(tag)
        if line is None or line.state == INVALID:
            self.misses += 1
            return None
        self.hits += 1
        self.policy.on_hit(line)
        if line.prefetched:
            self.prefetch_useful += 1
            line.prefetched = False
        line.last_touch = now
        if is_write:
            line.dirty = True
            line.state = MODIFIED
        return line

    def probe(self, addr: int) -> Optional[Line]:
        """Non-statistical peek (coherence snoops, invariant checks)."""
        set_idx, tag = self.split(addr)
        line = self.sets[set_idx].get(tag)
        if line is not None and line.state == INVALID:
            return None
        return line

    def insert(self, addr: int, tensor_id: int, reuse_class: int, now: int,
               is_write: bool = False, prefetched: bool = False,
               ready_time: float = 0.0) -> Optional[Tuple[int, Line]]:
        """Fill ``addr``; returns (victim_addr, victim_line) if one was evicted."""
        set_idx, tag = self.split(addr)
        sset = self.sets[set_idx]
        victim = None
        if tag in sset:            # refill over an INVALID stale entry
            del sset[tag]
        if len(sset) >= self.assoc:
            vtag = self.policy.victim(sset, now)
            vline = sset.pop(vtag)
            self.evictions += 1
            if vline.dirty:
                self.dirty_evictions += 1
            victim_addr = self._join(set_idx, vtag)
            victim = (victim_addr, vline)
        line = Line(tag, tensor_id, reuse_class, now, prefetched=prefetched,
                    ready_time=ready_time)
        if is_write:
            line.dirty = True
            line.state = MODIFIED
        if prefetched:
            self.prefetch_fills += 1
        sset[tag] = line
        self.policy.on_fill(line, addr >> self.line_bits)
        return victim

    def invalidate(self, addr: int) -> Optional[Line]:
        """MESI invalidation; returns the line if it was present & valid."""
        set_idx, tag = self.split(addr)
        line = self.sets[set_idx].pop(tag, None)
        if line is not None and line.state != INVALID:
            return line
        return None

    def _join(self, set_idx: int, tag: int) -> int:
        block = (tag << (self.n_sets.bit_length() - 1)) | set_idx
        return block << self.line_bits

    # -- metrics ------------------------------------------------------------
    @property
    def accesses(self) -> int:
        return self.hits + self.misses

    @property
    def hit_rate(self) -> float:
        return self.hits / self.accesses if self.accesses else 0.0

    def occupancy(self) -> int:
        return sum(len(s) for s in self.sets)
