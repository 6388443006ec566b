"""Advanced prefetching: stride prefetcher + ML-based (perceptron) unit.

Paper §II-B / §IV "Advanced Prefetching": HERMES combines classic *stride
prefetching* with *machine-learning-based prefetching*.  We implement both
as trainable-online hardware-plausible structures:

* ``StridePrefetcher`` — per-PC reference-prediction table (RPT): tracks
  (last_addr, stride, confidence); once confidence ≥ threshold, issues
  ``degree`` lines ahead along the stride.  This is the Chen/Baer RPT
  design used by the Intel prefetchers the paper cites.

* ``MLPrefetcher`` — delta-history Markov candidate generator *gated by an
  online perceptron* (the "ML-based prefetching" of [8]): features are the
  hashed PC and the recent delta history; the perceptron learns whether a
  candidate prefetch for this context tends to be useful, and suppresses
  issue when its score is below threshold.  Weights are trained online
  from prefetch-hit feedback, exactly like perceptron branch predictors.

Both units observe the *L1 miss stream* (standard placement) and fill into
L2 (+L3 when present) so that mispredictions never pollute L1.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from .params import PrefetchParams


class StridePrefetcher:
    #: suppress a PC once its measured accuracy drops below this (after a
    #: warmup) — adaptive prefetch throttling, as in Intel's PCU designs:
    #: pseudo-stride runs inside random gathers would otherwise waste DRAM
    #: energy on dead lines.
    MIN_ACCURACY = 0.4
    WARMUP = 32

    def __init__(self, p: PrefetchParams, line_size: int):
        self.p = p
        self.line = line_size
        # pc -> [last_addr, stride, confidence]
        self.table: Dict[int, List[int]] = {}
        self.issued = 0
        # accuracy filter: pc -> [issued, used]; block -> pc pending map
        self.acc: Dict[int, List[int]] = {}
        self._pending: Dict[int, int] = {}

    def observe(self, pc: int, addr: int) -> List[int]:
        block = addr // self.line
        src = self._pending.pop(block, None)
        if src is not None:                       # prediction came true
            a = self.acc.get(src)
            if a is not None:
                a[1] += 1
        t = self.table
        e = t.get(pc)
        out: List[int] = []
        if e is None:
            if len(t) >= self.p.stride_table_size:
                t.pop(next(iter(t)))  # FIFO replacement of RPT entries
            t[pc] = [addr, 0, 0]
            return out
        stride = addr - e[0]
        if stride != 0 and stride == e[1]:
            e[2] = min(e[2] + 1, 7)
        else:
            e[1] = stride
            e[2] = 0
        e[0] = addr
        if e[2] >= self.p.stride_confidence and e[1] != 0:
            a = self.acc.setdefault(pc, [0, 0])
            if a[0] >= self.WARMUP and a[1] / a[0] < self.MIN_ACCURACY:
                return out                        # throttled: inaccurate PC
            for k in range(1, self.p.degree + 1):
                target = addr + e[1] * k
                out.append(target)
                a[0] += 1
                if len(self._pending) > 4096:
                    self._pending.pop(next(iter(self._pending)))
                self._pending[target // self.line] = pc
            self.issued += len(out)
        return out


class MLPrefetcher:
    """Perceptron-gated delta prefetcher ("ML-based prefetching")."""

    N_FEATURES = 3

    def __init__(self, p: PrefetchParams, line_size: int):
        self.p = p
        self.line = line_size
        # PER-PC delta history: the global stream interleaves many access
        # streams, so global deltas are noise; PC-localized histories are
        # where the repeating patterns live (as in the SPP/DPC lineage).
        self.hist: Dict[int, List[int]] = {}
        # delta-transition table: (pc, d1, d2) -> {next_delta: count}
        self.markov: Dict[Tuple[int, int, int], Dict[int, int]] = {}
        # perceptron weight tables, one per feature, plus bias
        self.w_pc = [0.0] * p.ml_table_size
        self.w_d1 = [0.0] * p.ml_table_size
        self.w_d2 = [0.0] * p.ml_table_size
        self.bias = 0.0
        self.issued = 0
        self.trained = 0
        self._pending: Dict[int, Tuple[int, int, int]] = {}  # block -> feature idxs

    def _idx(self, v: int) -> int:
        return (v * 2654435761) % self.p.ml_table_size

    def _score(self, f: Tuple[int, int, int]) -> float:
        return self.w_pc[f[0]] + self.w_d1[f[1]] + self.w_d2[f[2]] + self.bias

    def _train(self, f: Tuple[int, int, int], useful: bool) -> None:
        lr = 0.5 if useful else -0.5
        self.w_pc[f[0]] = max(-8.0, min(8.0, self.w_pc[f[0]] + lr))
        self.w_d1[f[1]] = max(-8.0, min(8.0, self.w_d1[f[1]] + lr))
        self.w_d2[f[2]] = max(-8.0, min(8.0, self.w_d2[f[2]] + lr))
        self.bias = max(-8.0, min(8.0, self.bias + lr * 0.25))
        self.trained += 1

    def observe(self, pc: int, addr: int) -> List[int]:
        block = addr // self.line
        out: List[int] = []
        # feedback: was an earlier prediction for this block correct?
        f = self._pending.pop(block, None)
        if f is not None:
            self._train(f, useful=True)
        h = self.hist.setdefault(pc, [])
        if len(h) >= 2:
            d_new = block - h[-1]
            key = (pc, h[-2] - h[-3] if len(h) >= 3 else 0, h[-1] - h[-2])
            m = self.markov.setdefault(key, {})
            m[d_new] = m.get(d_new, 0) + 1
            if len(m) > 8:  # bound table entry size
                m.pop(min(m, key=m.get))
            # predict from the *current* context
            ckey = (pc, h[-1] - h[-2], d_new)
            cand = self.markov.get(ckey)
            if cand:
                best = max(cand, key=cand.get)
                if best != 0:
                    feats = (self._idx(pc), self._idx(ckey[1]),
                             self._idx(ckey[2]))
                    # ISSUE only when the perceptron trusts this context,
                    # but TRACK the prediction unconditionally — training
                    # on prediction correctness (not issuance) avoids the
                    # cold-start deadlock where zero weights mean no
                    # issues and hence no learning signal.
                    if self._score(feats) >= self.p.ml_threshold:
                        out.append((block + best) * self.line)
                        self.issued += 1
                    if len(self._pending) > 2048:
                        # stale predictions count as not-useful
                        stale_blk, stale_f = next(iter(self._pending.items()))
                        del self._pending[stale_blk]
                        self._train(stale_f, useful=False)
                    self._pending[block + best] = feats
        h.append(block)
        if len(h) > max(3, self.p.ml_history):
            h.pop(0)
        if len(self.hist) > 512:     # bound PC-history table
            self.hist.pop(next(iter(self.hist)))
        return out


class PrefetchUnit:
    """Composite unit the simulator talks to (stride + optional ML)."""

    def __init__(self, p: PrefetchParams, line_size: int):
        self.p = p
        self.stride = StridePrefetcher(p, line_size) if p.enabled else None
        self.ml = MLPrefetcher(p, line_size) if (p.enabled and p.ml_enabled) else None

    def observe_miss(self, pc: int, addr: int) -> List[Tuple[int, str]]:
        """Returns [(target_addr, unit)] — unit ∈ {"stride", "ml"}.

        The simulator routes fills by unit: stride targets are immediate-
        reuse stream continuations (fill L2); ML targets are longer-range
        reuse predictions (fill the shared L3 so L2 stays unpolluted)."""
        if not self.p.enabled:
            return []
        out: List[Tuple[int, str]] = []
        if self.stride is not None:
            out += [(a, "stride") for a in self.stride.observe(pc, addr)]
        if self.ml is not None:
            out += [(a, "ml") for a in self.ml.observe(pc, addr)]
        return out

    @property
    def issued(self) -> int:
        n = 0
        if self.stride:
            n += self.stride.issued
        if self.ml:
            n += self.ml.issued
        return n
