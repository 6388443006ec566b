"""Trace-driven memory-hierarchy simulator (the gem5+DRAMSim2 analogue).

Models, per requestor (4 in-order RISC-V cores + the Gemmini port):

    L1 (private) → L2 (private) → [shared L3] → hybrid DRAM/HBM

with MESI between the private domains, optional stride/ML prefetching
observing the L1 miss stream, and a busy-bus main-memory model whose
queueing produces the bandwidth-bound behaviour of the paper's baseline.

Timing model: in-order cores with limited memory-level parallelism
(``mlp`` outstanding misses).  A hit advances the core by the hit latency
of the level that served it (pipelined: ≥1 cycle); a miss advances it by
``service_cycles / mlp``.  Reported latency is the full service latency of
each access (what the paper's Table I measures); reported bandwidth is
line-bytes delivered to requestors per unit time.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

from .cache import Cache, MODIFIED, SHARED
from .coherence import MESIDirectory
from .energy import EnergyModel
from .hybrid_memory import HybridMemory
from .params import (LINE_SIZE, MemChannelParams, SystemParams)
from .prefetch import PrefetchUnit

#: limited memory-level parallelism (MSHR count): small for the in-order
#: RISC-V cores, large for the Gemmini DMA engine (requestor 4).
CORE_MLP = 6.0
ACCEL_MLP = 48.0
#: latency of one interconnect hop / cache-to-cache transfer (cycles)
C2C_LATENCY = 40
INV_LATENCY = 12
#: drop prefetches when the target channel queue exceeds this depth (cycles)
PREFETCH_THROTTLE = 200.0

DRAM_CHANNEL = MemChannelParams(
    name="ddr4", capacity_bytes=8 << 30, base_latency=150,
    bandwidth_bytes_per_cycle=12.8, row_hit_latency=55, row_gap=8.0)
HBM_CHANNEL = MemChannelParams(
    name="hbm2", capacity_bytes=4 << 30, base_latency=100,
    bandwidth_bytes_per_cycle=64.0, row_hit_latency=36, row_gap=2.0)


@dataclasses.dataclass
class Metrics:
    name: str
    workload: str
    avg_latency_ns: float
    bandwidth_gbps: float
    hit_rate: float            # fraction of accesses served by ANY cache
    l1_hit_rate: float
    l2_hit_rate: float
    l3_hit_rate: float
    energy_uj_per_op: float
    elapsed_ns: float
    dram_lines: int
    hbm_lines: int
    hbm_fraction: float
    invalidations: int
    c2c_transfers: int
    prefetches_issued: int
    prefetch_useful: int
    migrations: int

    def row(self) -> Dict:
        return dataclasses.asdict(self)


class HierarchySim:
    """The object engine: one Python object per cache, line and table.

    ``real`` is the type the timing model accumulates in: ``float``
    (IEEE binary64, what the configuration states) or a lower precision
    such as ``numpy.float32``, which is the benchmark's control.  Every
    time, latency and queue tail starts as ``real`` and stays so under
    NumPy's promotion rules.
    """

    def __init__(self, sp: SystemParams, real=float):
        self.sp = sp
        self.real = real
        self.n_req = sp.n_cores + (1 if sp.accel_port else 0)
        self.l1 = [Cache(sp.l1) for _ in range(self.n_req)]
        self.l2 = [Cache(sp.l2) for _ in range(self.n_req)]
        self.l3 = Cache(sp.l3) if sp.l3 is not None else None
        self.dir = MESIDirectory(self.n_req) if sp.coherence == "mesi" else None
        self.mem = HybridMemory(
            DRAM_CHANNEL, HBM_CHANNEL if sp.hybrid.enabled else None, sp.hybrid)
        self.pf = [PrefetchUnit(sp.prefetch, LINE_SIZE)
                   for _ in range(self.n_req)]
        for ch in (self.mem.dram, self.mem.hbm):
            if ch is not None:
                ch.busy_until = ch.spec_busy_until = real(0.0)
        self.mem.migration_stall_cycles = real(0.0)
        self.time = [real(0.0)] * self.n_req
        self.lat_sum = real(0.0)
        self.n_acc = 0
        self.wb_lines = 0
        self.pf_dropped = 0
        self.line_bits = LINE_SIZE.bit_length() - 1

    # -- helpers -------------------------------------------------------------
    def _invalidate_others(self, block: int, requestor: int) -> int:
        """MESI write: invalidate the line in all other private domains."""
        n = 0
        addr = block << self.line_bits
        for r in range(self.n_req):
            if r == requestor:
                continue
            if self.l1[r].invalidate(addr) is not None:
                n += 1
            if self.l2[r].invalidate(addr) is not None:
                n += 1
            if self.dir is not None:
                self.dir.on_evict(block, r)
        return n

    def _mem_fetch(self, now: float, addr: int, nbytes: int = LINE_SIZE):
        return self.mem.access(now, addr, nbytes)

    def _writeback(self, now: float, addr: int) -> None:
        """Dirty eviction → main memory (low-priority bus traffic)."""
        self.wb_lines += 1
        self.mem.access(now, addr, LINE_SIZE, speculative=True)

    def _promote_wait(self, r: int, addr: int, now: float, line) -> float:
        """Demand hits an in-flight prefetch: the controller promotes the
        transfer to demand priority.  The wait is the smaller of the
        remaining speculative completion and a promoted fetch — row
        already open (the prefetch opened it), data possibly in the
        controller buffer — estimated at row-hit latency + one transfer
        slot.  No second bus transfer is charged: the line moves once.
        """
        remaining = line.ready_time - now
        page = addr // 4096
        ch = (self.mem.hbm if (self.mem.hbm is not None
                               and self.mem.page_loc.get(page, 0) == 1)
              else self.mem.dram)
        promoted = (ch.p.row_hit_latency
                    + LINE_SIZE / ch.p.bandwidth_bytes_per_cycle)
        line.ready_time = 0.0
        return min(max(0.0, remaining), promoted)

    def _fill_shared(self, addr: int, tensor: int, reuse: int, now: float,
                     prefetched: bool = False, is_write: bool = False) -> None:
        if self.l3 is None:
            return
        # tensor-aware layout: STREAMING reads whose tensor has MEASURED
        # zero reuse bypass the shared level — dead-on-arrival lines would
        # only evict the resident tensors the L3 exists to protect (the
        # paper's "optimize data layout for tensor reuse").  WRITES still
        # fill (producer→consumer handover), and the utility monitor keeps
        # the bypass adaptive: tensors start optimistic and only lose
        # fill rights once their lines demonstrably die unused.
        if (self.l3.params.policy == "tensor_aware"
                and reuse == 0 and not prefetched
                and not is_write                     # 0 = REUSE_STREAMING
                and getattr(self.l3.policy, "utility",
                            lambda t: 1.0)(tensor)
                < self.l3.params.ta.bypass_utility):
            return
        victim = self.l3.insert(addr, tensor, reuse, now, prefetched=prefetched)
        if victim is not None and victim[1].dirty:
            self._writeback(now, victim[0])

    def _fill_private(self, r: int, addr: int, tensor: int, reuse: int,
                      now: float, is_write: bool) -> None:
        for cache in (self.l2[r], self.l1[r]):
            victim = cache.insert(addr, tensor, reuse, now, is_write=is_write)
            if victim is not None:
                vaddr, vline = victim
                if self.dir is not None and cache is self.l2[r]:
                    # leaving the private domain entirely only if not in L1
                    if self.l1[r].probe(vaddr) is None:
                        self.dir.on_evict(vaddr >> self.line_bits, r)
                if vline.dirty:
                    if cache is self.l1[r]:
                        l2line = self.l2[r].probe(vaddr)
                        if l2line is not None:
                            l2line.dirty = True
                        else:
                            self._writeback(now, vaddr)
                    else:
                        self._writeback(now, vaddr)

    # -- the access path ------------------------------------------------------
    def access(self, r: int, pc: int, addr: int, is_write: bool,
               tensor: int, reuse: int) -> float:
        """Simulate one access; returns its service latency in cycles."""
        sp = self.sp
        now = self.time[r]
        block = addr >> self.line_bits
        lat = self.real(sp.l1.hit_latency)

        line = self.l1[r].lookup(addr, now, is_write)
        if line is not None:
            if is_write and self.dir is not None and line.state != MODIFIED:
                # upgrade: invalidate remote sharers
                n_inv = self.dir.on_write(block, r)
                if n_inv:
                    self._invalidate_others(block, r)
                    lat += INV_LATENCY
                line.state = MODIFIED
            if line.ready_time > now:   # in-flight prefetch: partial hit
                lat += self._promote_wait(r, addr, now, line)
            self._finish(r, lat, hit=True)
            return lat

        # L1 miss → prefetcher observes the miss stream.  Candidates are
        # ISSUED only if the demand also misses L2 (the true prefetch
        # frontier): covered lines hitting L2 keep training the tables
        # but don't re-issue — redundant issues were 64% of traffic.
        pf_candidates = self.pf[r].observe_miss(pc, addr)

        lat += sp.l2.hit_latency
        line = self.l2[r].lookup(addr, now, is_write)
        if line is not None:
            if is_write and self.dir is not None and line.state != MODIFIED:
                n_inv = self.dir.on_write(block, r)
                if n_inv:
                    self._invalidate_others(block, r)
                    lat += INV_LATENCY
                line.state = MODIFIED
            if line.ready_time > now:   # in-flight prefetch: partial hit
                lat += self._promote_wait(r, addr, now, line)
            self.l1[r].insert(addr, tensor, reuse, now, is_write=is_write)
            self._finish(r, lat, hit=True)
            return lat

        for pf_addr, unit in pf_candidates:
            self._prefetch(r, pf_addr, tensor, reuse, now, unit)

        # leaving the private domain: coherence action
        if self.dir is not None:
            if is_write:
                n_inv = self.dir.on_write(block, r)
                if n_inv:
                    self._invalidate_others(block, r)
                    lat += INV_LATENCY
            else:
                provider = self.dir.on_read(block, r)
                if provider is not None:
                    # cache-to-cache transfer through the shared level (or
                    # through memory when there is no shared L3)
                    if self.l3 is not None:
                        lat += C2C_LATENCY
                        self._fill_shared(addr, tensor, reuse, now)
                    else:
                        done, mlat = self._mem_fetch(now + lat, addr)
                        lat += mlat
                    self._fill_private(r, addr, tensor, reuse, now, is_write)
                    self._finish(r, lat, hit=True)
                    return lat

        if self.l3 is not None:
            lat += sp.l3.hit_latency
            l3line = self.l3.lookup(addr, now, is_write)
            if l3line is not None:
                self._fill_private(r, addr, tensor, reuse, now, is_write)
                self._finish(r, lat, hit=True)
                return lat

        # main memory
        done, mlat = self._mem_fetch(now + lat, addr)
        lat += mlat
        self._fill_shared(addr, tensor, reuse, now, is_write=is_write)
        self._fill_private(r, addr, tensor, reuse, now, is_write)
        self._finish(r, lat, hit=False)
        return lat

    def _prefetch(self, r: int, addr: int, tensor: int, reuse: int,
                  now: float, unit: str = "stride") -> None:
        """Background fill; never stalls the core.

        Fill routing by unit: STRIDE targets are immediate stream
        continuations → private L2 (used within a few hundred cycles);
        ML targets are longer-range reuse predictions → shared L3 (big
        and associativity-rich, so speculation never pollutes L2).

        Timeliness: a prefetched line is usable only once the memory system
        has actually delivered it (``ready_time``); an early demand access
        waits for the remainder (late-prefetch partial hit).

        Bandwidth-aware throttling: when the target channel's queue is
        deeper than PREFETCH_THROTTLE cycles, the prefetch is dropped —
        speculative traffic only uses idle bus slots (low-priority
        prefetching), so it cannot starve demand misses.
        """
        if self.l2[r].probe(addr) is not None:
            return
        if self.l3 is not None and self.l3.probe(addr) is not None:
            if unit == "stride":
                # shared-level hit: promote into private L2 cheaply
                victim = self.l2[r].insert(
                    addr, tensor, reuse, now, prefetched=True,
                    ready_time=now + self.sp.l3.hit_latency)
                if victim is not None and victim[1].dirty:
                    self._writeback(now, victim[0])
            return
        # finite prefetch-buffer model: drop when the speculative queue
        # is too deep (the controller's prefetch FIFO is full)
        page = addr // 4096
        ch = (self.mem.hbm if (self.mem.hbm is not None
                               and self.mem.page_loc.get(page, 0) == 1)
              else self.mem.dram)
        if ch.spec_backlog > PREFETCH_THROTTLE:
            self.pf_dropped += 1
            return
        done, _ = self.mem.access(now, addr, LINE_SIZE, speculative=True)
        if unit == "ml" and self.l3 is not None:
            victim = self.l3.insert(addr, tensor, reuse, now,
                                    prefetched=True, ready_time=done)
        else:
            victim = self.l2[r].insert(addr, tensor, reuse, now,
                                       prefetched=True, ready_time=done)
        if victim is not None and victim[1].dirty:
            self._writeback(now, victim[0])

    def _finish(self, r: int, lat: float, hit: bool) -> None:
        """Advance the requestor clock.

        L1 hits are fully pipelined (1 cycle/issue).  Anything that misses
        L1 allocates an MSHR and overlaps with up to MLP outstanding
        requests (CORE_MLP for the in-order cores, ACCEL_MLP for the
        Gemmini DMA port), so the requestor advances by lat/MLP (≥ 2 cyc).
        """
        self.lat_sum += lat
        self.n_acc += 1
        if hit and lat <= self.sp.l1.hit_latency + INV_LATENCY:
            self.time[r] += 1.0
        else:
            mlp = ACCEL_MLP if r >= self.sp.n_cores else CORE_MLP
            self.time[r] += max(2.0, lat / mlp)

    # -- driver ----------------------------------------------------------------
    def run(self, trace: Dict) -> Metrics:
        core = trace["core"]
        pc = trace["pc"]
        addr = trace["addr"]
        write = trace["write"]
        tensor = trace["tensor"]
        reuse = trace["reuse"]
        n = len(core)
        acc = self.access
        for i in range(n):
            acc(int(core[i]), int(pc[i]), int(addr[i]), bool(write[i]),
                int(tensor[i]), int(reuse[i]))
        return compute_metrics(self, trace)


def compute_metrics(sim, trace: Dict) -> Metrics:
    """Build the Metrics row from a finished simulation's counters."""
    sp = sim.sp
    elapsed = max(sim.time) if sim.time else 1.0
    l1_acc = sum(c.accesses for c in sim.l1)
    l1_hits = sum(c.hits for c in sim.l1)
    l2_acc = sum(c.accesses for c in sim.l2)
    l2_hits = sum(c.hits for c in sim.l2)
    l3_acc = sim.l3.accesses if sim.l3 else 0
    l3_hits = sim.l3.hits if sim.l3 else 0
    c2c = sim.dir.c2c_transfers if sim.dir else 0
    served_by_cache = l1_hits + l2_hits + l3_hits + c2c
    dram_lines = sim.mem.dram.bytes_transferred // LINE_SIZE
    hbm_lines = (sim.mem.hbm.bytes_transferred // LINE_SIZE
                 if sim.mem.hbm else 0)
    counters = {
        "l1_accesses": l1_acc,
        "l2_accesses": l2_acc,
        "l3_accesses": l3_acc,
        "dram_lines": dram_lines,
        "dram_row_hits": sim.mem.dram.row_hits,
        "hbm_lines": hbm_lines,
        "hbm_row_hits": (sim.mem.hbm.row_hits if sim.mem.hbm else 0),
        "coherence_msgs": (sim.dir.invalidations + c2c) if sim.dir else 0,
        "prefetches": sum(p.issued for p in sim.pf),
        "migrations": sim.mem.migrations,
        "migration_lines": sim.mem.migration_bytes // LINE_SIZE,
    }
    em = EnergyModel()
    elapsed_ns = sp.cycles_to_ns(elapsed)
    return Metrics(
        name=sp.name,
        workload=trace["name"],
        avg_latency_ns=sp.cycles_to_ns(sim.lat_sum / max(1, sim.n_acc)),
        # paper Table I bandwidth = rate at which data is transferred
        # between the memory system and the processor/accelerator:
        # request-granularity words (8 B) on L1 hits, full line
        # transfers (64 B) for everything that moves through the
        # hierarchy.  Rises as caching/prefetching shortens the run.
        bandwidth_gbps=(l1_hits * 8 + (sim.n_acc - l1_hits) * LINE_SIZE)
                       / max(elapsed_ns, 1e-9),
        hit_rate=served_by_cache / max(1, sim.n_acc),
        l1_hit_rate=l1_hits / max(1, l1_acc),
        l2_hit_rate=l2_hits / max(1, l2_acc),
        l3_hit_rate=l3_hits / max(1, l3_acc) if l3_acc else 0.0,
        energy_uj_per_op=em.uj_per_op(counters,
                                      trace["meta"]["n_macro_ops"],
                                      elapsed_ns=elapsed_ns),
        elapsed_ns=elapsed_ns,
        dram_lines=dram_lines,
        hbm_lines=hbm_lines,
        hbm_fraction=sim.mem.hbm_fraction,
        invalidations=sim.dir.invalidations if sim.dir else 0,
        c2c_transfers=c2c,
        prefetches_issued=sum(p.issued for p in sim.pf),
        prefetch_useful=(sum(c.prefetch_useful for c in sim.l2)
                         + (sim.l3.prefetch_useful if sim.l3 else 0)),
        migrations=sim.mem.migrations,
    )
