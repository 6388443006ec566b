"""Analytical energy model (paper Table III reproduction).

Per-access energies follow the usual CACTI-style ordering (small SRAM ≪
large SRAM ≪ DRAM; HBM ≈ 0.6× DRAM pJ/bit thanks to TSV interfaces — the
paper's hybrid-memory efficiency argument).  Absolute µJ/operation matches
the paper's scale through ``EnergyModel.UJ_PER_OP_SCALE``, calibrated ONCE
against the baseline row of Table III and then held fixed for all HERMES
configurations — identical to how the paper normalizes per "memory
operation" (one workload macro-op).
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class EnergyParams:
    base_pj: float = 14.0       # per access: AGU/TLB/tag/interconnect floor
    l1_pj: float = 1.2          # per line access
    l2_pj: float = 4.5
    l3_pj: float = 16.0
    dram_pj: float = 160.0      # per 64B line, closed row (act+rd+IO)
    dram_open_pj: float = 60.0  # per 64B line on an OPEN row (rd+IO only;
                                # activation energy dominates DRAM access)
    hbm_pj: float = 95.0        # per 64B line (TSV interface), closed row
    hbm_open_pj: float = 40.0
    coherence_pj: float = 6.0   # per invalidation/c2c message
    prefetch_pj: float = 2.0    # per issued prefetch (tag probes etc.)
    migration_pj: float = 500.0       # per-migration control overhead
    migration_line_pj: float = 45.0   # bulk (row-streaming) line transfer


class EnergyModel:
    #: converts summed pJ / macro-op into the paper's µJ/operation scale.
    #: Calibrated so the baseline configuration reproduces Table III row 1
    #: (50 µJ/op) on the paper's workload suite; see calibration.py.
    UJ_PER_OP_SCALE = 3400.0
    #: static (leakage + clock-tree) power of the simulated SoC in watts;
    #: charged per elapsed ns, so configurations that FINISH FASTER spend
    #: less static energy — the paper's prefetch/TA rows improve energy
    #: mostly through runtime, exactly this term.
    STATIC_W = 6.0

    def __init__(self, p: EnergyParams = EnergyParams()):
        self.p = p

    def total_pj(self, counters: dict) -> float:
        p = self.p
        return (counters.get("l1_accesses", 0) * p.base_pj
                + counters.get("l1_accesses", 0) * p.l1_pj
                + counters.get("l2_accesses", 0) * p.l2_pj
                + counters.get("l3_accesses", 0) * p.l3_pj
                + (counters.get("dram_lines", 0)
                   - counters.get("dram_row_hits", 0)) * p.dram_pj
                + counters.get("dram_row_hits", 0) * p.dram_open_pj
                + (counters.get("hbm_lines", 0)
                   - counters.get("hbm_row_hits", 0)) * p.hbm_pj
                + counters.get("hbm_row_hits", 0) * p.hbm_open_pj
                + counters.get("coherence_msgs", 0) * p.coherence_pj
                + counters.get("prefetches", 0) * p.prefetch_pj
                + counters.get("migrations", 0) * p.migration_pj
                + counters.get("migration_lines", 0) * p.migration_line_pj)

    def uj_per_op(self, counters: dict, n_macro_ops: int,
                  elapsed_ns: float = 0.0) -> float:
        if n_macro_ops <= 0:
            return 0.0
        dynamic = self.total_pj(counters) / n_macro_ops \
            * self.UJ_PER_OP_SCALE / 1e6
        static = self.STATIC_W * 1e-3 * elapsed_ns / n_macro_ops
        return dynamic + static
