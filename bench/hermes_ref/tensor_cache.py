"""Replacement policies, including the paper's tensor-aware caching.

The paper (§III.4, §IV "Tensor-Aware Caching") optimizes replacement and
layout for tensor reuse.  We realize it as a victim-selection policy with
two tensor-structured signals the hardware can cheaply maintain:

1. **Reuse class** — every trace record is tagged by the workload
   generator (``trace.py``) with the static class of its tensor:

   * REUSE_STREAMING (0) — touched once or twice, then dead (im2col
     patches, logits, activations-out).
   * REUSE_MEDIUM    (1) — sliding-window reuse (conv input halos,
     attention Q rows).
   * REUSE_RESIDENT  (2) — long-lived, repeatedly reused (weights,
     recurrent matrices, KV cache, embedding tables).

2. **Per-tensor utility monitor** (UMON-style) — a small table of
   (fills, hits) per tensor id at this cache.  ``utility = hits/fills``
   measures how often a cached line of that tensor is actually re-touched
   before eviction.  A cyclically re-walked tensor larger than the cache
   has utility ≈ 0 (its lines die before reuse) even though it is
   *resident class*, so the policy sheds it first and pins the tensors
   whose lines genuinely re-hit (embedding rows, KV pages, fitting
   weights).

Victim order: streaming < medium < resident; within the resident class,
lowest utility first, then LRU.  Utility tables decay periodically so the
policy adapts across workload phases.  This is the paper's "reduce
evictions of hot tensors / maximize reuse" behaviour, realized with
hardware-plausible mechanisms (reuse-class hint bits + UMON counters).
"""

from __future__ import annotations

from typing import Dict, Optional

from .params import TensorPolicyParams

REUSE_STREAMING = 0
REUSE_MEDIUM = 1
REUSE_RESIDENT = 2


class ReplacementPolicy:
    def victim(self, sset: Dict[int, "Line"], now: float) -> int:  # noqa: F821
        raise NotImplementedError

    # optional hooks (no-ops for LRU)
    def on_hit(self, line) -> None:
        pass

    def on_fill(self, line, block: int = -1) -> None:
        pass


class LRUPolicy(ReplacementPolicy):
    """Classic LRU over ``last_touch`` timestamps."""

    def victim(self, sset, now):
        return min(sset.items(), key=lambda kv: kv[1].last_touch)[0]


class TensorAwarePolicy(ReplacementPolicy):
    """Tensor-aware victim selection (paper §IV): reuse-class ranking with
    per-tensor utility monitoring inside the resident class.

    Utility cannot be measured from in-cache hits alone: a tensor whose
    lines are evicted *before* their reuse (LRU thrash) would show zero
    hits forever — a death spiral.  We therefore also monitor **refills**:
    a fill of a block that was already filled recently means the line was
    evicted and requested again, i.e. it *would have hit* had it been
    retained.  utility = (hits + refills) / fills.  Blocks are sampled
    1-in-``tp.sample`` to bound monitor state (UMON-style set sampling).
    All thresholds/rates come from :class:`TensorPolicyParams` so the
    design space is sweepable; defaults reproduce the original constants.
    """

    def __init__(self, tp: Optional[TensorPolicyParams] = None):
        self.tp = tp if tp is not None else TensorPolicyParams()
        self.fills: Dict[int, int] = {}
        self.hits: Dict[int, int] = {}
        self.refills: Dict[int, int] = {}
        self._shadow: Dict[int, None] = {}  # insertion-ordered set of blocks
        self._since_decay = 0

    # -- utility monitor ----------------------------------------------------
    def on_fill(self, line, block: int = -1) -> None:
        tp = self.tp
        t = line.tensor_id
        self.fills[t] = self.fills.get(t, 0) + 1
        if block >= 0 and (block * 2654435761) % tp.sample == 0:
            if block in self._shadow:
                self.refills[t] = self.refills.get(t, 0) + 1
            else:
                if len(self._shadow) >= tp.shadow_max:
                    self._shadow.pop(next(iter(self._shadow)))
                self._shadow[block] = None
        self._since_decay += 1
        if self._since_decay >= tp.decay_fills:
            self._since_decay = 0
            for d in (self.fills, self.hits, self.refills):
                for k in list(d):
                    d[k] >>= 1

    def on_hit(self, line) -> None:
        t = line.tensor_id
        self.hits[t] = self.hits.get(t, 0) + 1

    def utility(self, tensor_id: int) -> float:
        f = self.fills.get(tensor_id, 0)
        if f == 0:
            return 1.0  # unknown: optimistic, don't punish new tensors
        score = (self.hits.get(tensor_id, 0)
                 + self.tp.sample * self.refills.get(tensor_id, 0))
        return min(score / f, 4.0)

    # -- victim selection -----------------------------------------------------
    def victim(self, sset, now):
        """Streaming lines are always shed first; everything else ranks by
        a quantized utility bucket (so hot state and genuinely-reused
        resident tensors are both protected), LRU inside a bucket."""
        tp = self.tp
        best_key, best_rank = None, None
        for tag, line in sset.items():
            if line.prefetched:
                # prefetched-but-unused: the transfer is already paid for
                # and the demand is imminent — protect above dead tensors
                # (measured: ranking these at 0.5 lost 1.5pp aggregate
                # hit rate to LRU's recency ordering)
                rank = (tp.prefetch_rank, line.last_touch)
            elif line.reuse_class == REUSE_STREAMING:
                rank = (tp.stream_rank, line.last_touch)
            else:
                u = self.utility(line.tensor_id)
                bucket = (1.0 if u < tp.low_utility
                          else (2.0 if u < tp.high_utility else 3.0))
                rank = (bucket, line.last_touch)
            if best_rank is None or rank < best_rank:
                best_key, best_rank = tag, rank
        return best_key


def make_policy(name: str,
                tp: Optional[TensorPolicyParams] = None) -> ReplacementPolicy:
    if name == "lru":
        return LRUPolicy()
    if name == "tensor_aware":
        return TensorAwarePolicy(tp)
    raise ValueError(f"unknown replacement policy: {name!r}")
