"""Hybrid DRAM + HBM main memory (the DRAMSim2 analogue).

Paper §III/§IV "Hybrid Memory Model": 8 GB DRAM (capacity tier) + 4 GB HBM
(bandwidth tier).  We model each tier as a channel group with:

* closed-row base latency + open-row hit latency (row-buffer model),
* a sustained-bandwidth bus that serializes transfers (``busy_until``),
  which is what produces queueing delay when a tier saturates — the
  mechanism behind the paper's bandwidth-bound baseline (Table I).

Pages (4 KiB) start in DRAM; a hot-page detector (access counts with
periodic decay) migrates hot pages to HBM, charging a migration cost.
When HBM fills, the coldest HBM page is demoted.  This is the classic
hybrid-memory page-placement scheme the paper cites ([7], [16]).
"""

from __future__ import annotations

from typing import Dict, Tuple

from .params import HybridMemParams, MemChannelParams, PAGE_SIZE


class Channel:
    def __init__(self, p: MemChannelParams):
        self.p = p
        self.busy_until = 0.0        # demand-traffic queue tail
        self.spec_busy_until = 0.0   # speculative (prefetch) queue tail
        self.bytes_transferred = 0
        self.accesses = 0
        self.row_hits = 0
        self._open_row: Dict[int, int] = {}  # bank -> row  (8 banks)

    def access(self, now: float, addr: int, nbytes: int,
               speculative: bool = False) -> Tuple[float, float]:
        """Returns (completion_time, service_latency_cycles).

        Bus-occupancy model: a row-buffer MISS also stalls the data bus
        for ``row_gap`` cycles (precharge/activate bubbles — tRP+tRCD in
        DRAMSim2 terms), so the EFFECTIVE bandwidth of a channel depends
        on access locality.  This is the mechanism behind the paper's
        bandwidth column: prefetching/tensor-aware placement create
        sequential row-hit trains and recover the bubbled bandwidth.

        Prioritized controller: SPECULATIVE (prefetch) transfers queue
        behind both demand traffic and earlier speculation, but do NOT
        advance the demand queue — they occupy idle bus slots only, the
        standard low-priority prefetch channel class.
        """
        self.accesses += 1
        self.bytes_transferred += nbytes
        bank = (addr // self.p.row_buffer_bytes) % 8
        row = addr // (self.p.row_buffer_bytes * 8)
        if self._open_row.get(bank) == row:
            lat = self.p.row_hit_latency
            gap = 0.0
            self.row_hits += 1
        else:
            lat = self.p.base_latency
            gap = self.p.row_gap
            self._open_row[bank] = row
        xfer = nbytes / self.p.bandwidth_bytes_per_cycle + gap
        if speculative:
            start = max(now, self.busy_until, self.spec_busy_until)
            self.spec_busy_until = start + xfer
        else:
            start = max(now, self.busy_until)
            self.busy_until = start + xfer
            self.spec_busy_until = max(self.spec_busy_until,
                                       self.busy_until)
        done = start + lat + xfer
        return done, done - now

    @property
    def spec_backlog(self) -> float:
        return max(0.0, self.spec_busy_until - self.busy_until)


class HybridMemory:
    """DRAM + optional HBM with hot-page migration."""

    def __init__(self, dram: MemChannelParams, hbm: MemChannelParams | None,
                 hp: HybridMemParams):
        self.dram = Channel(dram)
        self.hbm = Channel(hbm) if (hbm is not None and hp.enabled) else None
        self.hp = hp
        self.page_loc: Dict[int, int] = {}   # page -> 0 (DRAM) | 1 (HBM)
        self.page_heat: Dict[int, int] = {}
        self.page_persist: Dict[int, int] = {}  # hot-across-windows counter
        self.hbm_pages_max = (hbm.capacity_bytes // PAGE_SIZE) if hbm else 0
        self.hbm_pages = 0
        self.migrations = 0
        self.migration_bytes = 0
        self._since_decay = 0
        self.migration_stall_cycles = 0.0

    def _maybe_migrate(self, page: int, now: float) -> None:
        """Persistent-heat promotion: a page must stay hot across ≥2 decay
        windows before it migrates, so one-shot streaming bursts (which
        look hot inside a single window) never churn the HBM."""
        heat = self.page_heat.get(page, 0) + 1
        self.page_heat[page] = heat
        self._since_decay += 1
        if self._since_decay >= self.hp.window:
            self._since_decay = 0
            for p, h in list(self.page_heat.items()):
                if h >= self.hp.hot_threshold // 2:
                    self.page_persist[p] = self.page_persist.get(p, 0) + 1
                nh = h >> 1
                if nh:
                    self.page_heat[p] = nh
                else:
                    del self.page_heat[p]
                    self.page_persist.pop(p, None)
        if (heat >= self.hp.hot_threshold
                and self.page_persist.get(page, 0) >= 2
                and self.page_loc.get(page, 0) == 0
                and self.hbm is not None):
            if self.hbm_pages >= self.hbm_pages_max:
                # demote the coldest known HBM page
                coldest, _ = min(
                    ((p, self.page_heat.get(p, 0)) for p, loc in self.page_loc.items()
                     if loc == 1), key=lambda kv: kv[1], default=(None, 0))
                if coldest is None:
                    return
                self.page_loc[coldest] = 0
                self.hbm_pages -= 1
            self.page_loc[page] = 1
            self.hbm_pages += 1
            self.migrations += 1
            self.migration_stall_cycles += self.hp.migration_cost_cycles
            # the page move occupies both buses; counted separately so the
            # energy model can charge it at bulk-transfer (row-streaming)
            # rates rather than random-access rates
            self.migration_bytes += PAGE_SIZE
            self.dram.busy_until = max(self.dram.busy_until, now) + \
                PAGE_SIZE / self.dram.p.bandwidth_bytes_per_cycle
            self.hbm.busy_until = max(self.hbm.busy_until, now) + \
                PAGE_SIZE / self.hbm.p.bandwidth_bytes_per_cycle

    def access(self, now: float, addr: int, nbytes: int,
               speculative: bool = False) -> Tuple[float, float]:
        page = addr // PAGE_SIZE
        if self.hbm is not None:
            self._maybe_migrate(page, now)
        ch = self.hbm if (self.hbm is not None
                          and self.page_loc.get(page, 0) == 1) else self.dram
        return ch.access(now, addr, nbytes, speculative=speculative)

    # -- metrics ------------------------------------------------------------
    @property
    def total_bytes(self) -> int:
        return (self.dram.bytes_transferred + self.migration_bytes
                + (self.hbm.bytes_transferred if self.hbm else 0))

    @property
    def hbm_fraction(self) -> float:
        t = self.total_bytes
        return (self.hbm.bytes_transferred / t) if (self.hbm and t) else 0.0
