"""Configuration dataclasses for the HERMES memory-hierarchy simulator.

Track A of the reproduction (see DESIGN.md §1): these mirror the paper's
"Simulation Configuration" section —

    * 4-core in-order RISC-V processor
    * L1: 32 KB / core, 8-way
    * L2: 256 KB / core, 8-way
    * Shared L3: 8 MB, 16-way
    * Hybrid memory: 8 GB DRAM + 4 GB HBM
    * MESI coherence

Timing/energy constants live in ``calibration.py`` and are held fixed across
all four paper configurations.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


LINE_SIZE = 64  # bytes, fixed across the hierarchy (gem5 default)
PAGE_SIZE = 4096  # bytes, hybrid-memory migration granularity


@dataclasses.dataclass(frozen=True)
class TensorPolicyParams:
    """Tunable knobs of the tensor-aware replacement policy.

    Defaults reproduce the original hard-wired constants bit-for-bit
    (tensor_cache.TensorAwarePolicy / engine_soa._TAState /
    _sim_kernel.c), so existing presets are unchanged; the
    ``repro.sweep`` explorer varies them to search the policy
    design space.
    """

    sample: int = 16            # 1-in-N block sampling for the refill shadow
    shadow_max: int = 16384     # sampled blocks remembered per policy
    decay_fills: int = 16384    # fills between utility-table halvings
    low_utility: float = 0.05   # below: "dead" bucket, shed first
    high_utility: float = 0.5   # above: "hot" bucket, protected
    prefetch_rank: float = 2.5  # victim rank of unused prefetched lines
    bypass_utility: float = 0.05  # L3 fill bypass for dead streaming tensors
    stream_rank: float = 0.0    # victim rank of STREAMING-class lines:
                                # 0.0 sheds them before everything (the
                                # original hard-wired order); raising it
                                # above 1.0 protects a recently-touched
                                # stream over dead resident tensors

    def __post_init__(self) -> None:
        if self.sample < 1 or self.shadow_max < 1 or self.decay_fills < 1:
            raise ValueError("sample/shadow_max/decay_fills must be >= 1")
        if not (0.0 <= self.low_utility <= self.high_utility):
            raise ValueError("need 0 <= low_utility <= high_utility")


@dataclasses.dataclass(frozen=True)
class CacheParams:
    """One cache level."""

    name: str
    size_bytes: int
    assoc: int
    hit_latency: int  # cycles
    policy: str = "lru"  # "lru" | "tensor_aware"
    line_size: int = LINE_SIZE
    ta: TensorPolicyParams = dataclasses.field(
        default_factory=TensorPolicyParams)

    @property
    def n_sets(self) -> int:
        n = self.size_bytes // (self.assoc * self.line_size)
        if n & (n - 1):
            raise ValueError(f"{self.name}: set count {n} not a power of two")
        return n


@dataclasses.dataclass(frozen=True)
class MemChannelParams:
    """One main-memory channel (DRAM or HBM), DRAMSim2-style bus model."""

    name: str
    capacity_bytes: int
    base_latency: int        # cycles: closed-row access latency
    bandwidth_bytes_per_cycle: float  # sustained transfer rate
    row_hit_latency: int     # cycles when the access hits an open row
    row_buffer_bytes: int = 2048
    row_gap: float = 0.0     # bus bubble cycles on a row miss (tRP+tRCD)


@dataclasses.dataclass(frozen=True)
class PrefetchParams:
    enabled: bool = False
    stride_table_size: int = 256
    stride_confidence: int = 3      # hits on same stride before issuing
    degree: int = 2                 # lines fetched ahead per trigger
    ml_enabled: bool = False        # perceptron-gated delta ("ML-based") unit
    ml_history: int = 4
    ml_table_size: int = 512
    ml_threshold: float = 0.5       # perceptron issue threshold


@dataclasses.dataclass(frozen=True)
class HybridMemParams:
    enabled: bool = False
    hot_threshold: int = 8          # accesses within window to promote a page
    window: int = 4096              # accesses per decay window
    migration_cost_cycles: int = 600


@dataclasses.dataclass(frozen=True)
class SystemParams:
    """Full simulated system = one paper configuration row."""

    name: str
    n_cores: int = 4
    clock_ghz: float = 2.0
    l1: CacheParams = dataclasses.field(
        default_factory=lambda: CacheParams("L1", 32 * 1024, 8, hit_latency=4)
    )
    l2: CacheParams = dataclasses.field(
        default_factory=lambda: CacheParams("L2", 256 * 1024, 8, hit_latency=14)
    )
    l3: Optional[CacheParams] = None      # None = no shared L3 (baseline)
    prefetch: PrefetchParams = dataclasses.field(default_factory=PrefetchParams)
    hybrid: HybridMemParams = dataclasses.field(default_factory=HybridMemParams)
    coherence: str = "mesi"               # "mesi" | "none"
    # Gemmini accelerator port: modeled as core index n_cores (an extra
    # requestor that shares the L3 but has no private caches of its own
    # beyond a small L1-like scratch filter).
    accel_port: bool = True

    def cycles_to_ns(self, cycles: float) -> float:
        return cycles / self.clock_ghz


# ---------------------------------------------------------------------------
# jax-engine lane lowering: presets + dotted overrides → padded parameter
# arrays.  The batched engine (`core/engine_jax.py`) vmaps one compiled
# program over a stacked axis of configs; everything listed here may
# differ per lane without recompiling, everything else is structural and
# keys the compile cache (`engine_jax.StaticConfig`).  numpy-only on
# purpose — importable without jax (CLI validation, tests, docs).
# ---------------------------------------------------------------------------

#: per-lane integer scalars (stride-prefetch confidence, hot-page
#: promotion knobs, tensor-table decay)
LANE_INT_FIELDS = ("st_conf", "hp_hot", "hp_window", "ta_decay")
#: per-lane float scalars (ML-prefetch threshold, migration cost,
#: tensor-aware utility cutoffs/ranks, per-level hit latencies)
LANE_FLOAT_FIELDS = ("ml_thresh", "migcost", "ta_low", "ta_high",
                     "ta_pref", "ta_stream", "ta_bypass",
                     "hl1", "hl2", "hl3")


def lane_pad(n: int) -> int:
    """Pad a lane count up to the next power of two so nearby batch
    sizes reuse one compiled program (B is baked into the vmapped
    executable's shapes; without padding every distinct group size
    triggers a fresh compile)."""
    if n <= 1:
        return n
    return 1 << (n - 1).bit_length()


def stack_lanes(cfgs, pad: bool = True):
    """Stack per-lane config dicts into parameter arrays.

    Returns ``(arrays, n)`` where ``arrays`` maps each LANE_*_FIELDS
    name to a numpy array of length ``lane_pad(len(cfgs))`` (lanes past
    ``n`` replicate lane 0 — valid work whose outputs the caller
    discards) and ``n`` is the real lane count.
    """
    import numpy as np
    n = len(cfgs)
    if n == 0:
        raise ValueError("stack_lanes needs at least one lane")
    total = lane_pad(n) if pad else n
    idx = list(range(n)) + [0] * (total - n)
    arrays = {}
    for k in LANE_INT_FIELDS:
        arrays[k] = np.asarray([cfgs[i][k] for i in idx], dtype=np.int64)
    for k in LANE_FLOAT_FIELDS:
        arrays[k] = np.asarray([cfgs[i][k] for i in idx],
                               dtype=np.float64)
    return arrays, n
