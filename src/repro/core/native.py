"""Loader for the compiled SoA simulation kernel (_sim_kernel.c).

The kernel is the compiled twin of ``engine_soa``'s scalar path: same SoA
state layout, same float-operation order, same tie-breaking.  It is built
on demand with the system C compiler (``cc``/``gcc``) into
``core/_build/`` keyed by a hash of the source, and loaded via ctypes —
no packaging machinery, no third-party deps.  When no compiler is
available the engine transparently falls back to the pure-Python SoA
path, so the repo stays fully portable; ``REPRO_SIM_NATIVE=0`` forces
the fallback (the equivalence suite tests both).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from pathlib import Path
from typing import Dict, Optional, Tuple

import numpy as np

_SRC = Path(__file__).resolve().parent / "_sim_kernel.c"
_BUILD = Path(__file__).resolve().parent / "_build"

# int-config indices (mirror _sim_kernel.c)
(CI_NREQ, CI_NCORES, CI_S1, CI_A1, CI_S2, CI_A2, CI_S3, CI_A3,
 CI_HASL3, CI_MESI, CI_PFON, CI_MLON, CI_TA1, CI_TA2, CI_TA3,
 CI_HYBRID, CI_NTEN, CI_ST_TSIZE, CI_ST_CONF, CI_ST_DEG,
 CI_ML_TSIZE, CI_ML_HIST, CI_HP_HOT, CI_HP_WINDOW, CI_HL1, CI_HL2,
 CI_HL3, CI_HBM_PAGES_MAX, CI_TA_SAMPLE, CI_TA_SHADOW, CI_TA_DECAY,
 CI_COUNT) = range(32)

(CD_ML_THRESH, CD_HP_MIGCOST, CD_D_BL, CD_D_RHL, CD_D_BW, CD_D_GAP,
 CD_D_RBB, CD_H_BL, CD_H_RHL, CD_H_BW, CD_H_GAP, CD_H_RBB,
 CD_CORE_MLP, CD_ACCEL_MLP, CD_C2C, CD_INV, CD_PF_THROTTLE,
 CD_TA_LOW, CD_TA_HIGH, CD_TA_PREF, CD_TA_BYPASS, CD_TA_STREAM,
 CD_COUNT) = range(23)

_lib = None
_lib_tried = False


def _build_lib() -> Optional[ctypes.CDLL]:
    src = _SRC.read_bytes()
    # REPRO_SIM_CFLAGS: extra compile/link flags (the sanitizer CI leg
    # passes -fsanitize=address,undefined); part of the cache key so a
    # sanitized .so never shadows the plain one
    extra = os.environ.get("REPRO_SIM_CFLAGS", "").split()
    tag = hashlib.sha256(src + " ".join(extra).encode()).hexdigest()[:16]
    so = _BUILD / f"sim_kernel_{tag}.so"
    if not so.exists():
        _BUILD.mkdir(exist_ok=True)
        cc = os.environ.get("CC", "cc")
        # per-process tmp: concurrent builders (run_suite_parallel
        # workers on a fresh checkout) must not write the same file; the
        # atomic replace then publishes identical content whoever wins
        tmp = so.with_suffix(f".{os.getpid()}.tmp")
        # -ffp-contract=off: no FMA fusing — float ops must round exactly
        # like the Python engine's
        cmd = [cc, "-O2", "-ffp-contract=off", "-fPIC", "-shared",
               *extra, str(_SRC), "-o", str(tmp)]
        try:
            subprocess.run(cmd, check=True, capture_output=True,
                           timeout=120)
            os.replace(tmp, so)
        finally:
            if tmp.exists():
                tmp.unlink()
    lib = ctypes.CDLL(str(so))
    i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
    u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
    f64p = np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")
    lib.run_trace.argtypes = [i64p, f64p, i32p, i64p, i64p, u8p, i32p,
                              u8p, ctypes.c_int64, i64p, f64p]
    lib.run_trace.restype = None
    return lib


def get_lib() -> Optional[ctypes.CDLL]:
    """The compiled kernel, or None when unavailable/disabled."""
    global _lib, _lib_tried
    if _lib_tried:
        return _lib
    _lib_tried = True
    if os.environ.get("REPRO_SIM_NATIVE", "1") == "0":
        return None
    try:
        _lib = _build_lib()
    except Exception as e:
        import sys
        detail = ""
        stderr = getattr(e, "stderr", None)
        if stderr:
            detail = ": " + stderr.decode(errors="replace").strip()[:300]
        sys.stderr.write(
            f"[repro.core.native] sim kernel unavailable "
            f"({type(e).__name__}: {e}){detail} — falling back to the "
            f"pure-Python SoA path (slower; see BENCH_sim.json 'native' "
            f"field)\n")
        _lib = None
    return _lib


def pack_config_sp(sp, nten: int):
    """Lower a ``SystemParams`` + tensor-id count to the kernel's flat
    ``(ci, cd)`` config arrays, or ``None`` when the configuration is
    outside the array-kernel envelope.  Single source of truth for the
    knob lowering shared by the compiled C kernel and the jax engine."""
    from repro.core.params import LINE_SIZE, PAGE_SIZE
    from repro.core.simulator import (ACCEL_MLP, C2C_LATENCY, CORE_MLP,
                                      DRAM_CHANNEL, HBM_CHANNEL,
                                      INV_LATENCY, PREFETCH_THROTTLE)
    n_req = sp.n_cores + (1 if sp.accel_port else 0)
    pp = sp.prefetch
    if (LINE_SIZE != 64 or PAGE_SIZE != 4096 or n_req > 8
            or pp.degree > 16 or max(3, pp.ml_history) > 8
            or DRAM_CHANNEL.row_buffer_bytes != HBM_CHANNEL.row_buffer_bytes
            or sp.l1.line_size != 64 or sp.l2.line_size != 64
            or (sp.l3 is not None and sp.l3.line_size != 64)):
        return None
    # one TA-knob set in the kernel: levels running the tensor-aware
    # policy must agree on it, else fall back to the Python SoA path
    from repro.core.params import TensorPolicyParams
    levels = [sp.l1, sp.l2] + ([sp.l3] if sp.l3 is not None else [])
    ta_sets = {lv.ta for lv in levels if lv.policy == "tensor_aware"}
    if len(ta_sets) > 1:
        return None
    tp = ta_sets.pop() if ta_sets else TensorPolicyParams()

    ci = np.zeros(CI_COUNT, np.int64)
    ci[CI_NREQ] = n_req
    ci[CI_NCORES] = sp.n_cores
    ci[CI_S1], ci[CI_A1] = sp.l1.n_sets, sp.l1.assoc
    ci[CI_S2], ci[CI_A2] = sp.l2.n_sets, sp.l2.assoc
    if sp.l3 is not None:
        ci[CI_S3], ci[CI_A3] = sp.l3.n_sets, sp.l3.assoc
        ci[CI_HASL3] = 1
        ci[CI_TA3] = sp.l3.policy == "tensor_aware"
        ci[CI_HL3] = sp.l3.hit_latency
    ci[CI_MESI] = sp.coherence == "mesi"
    ci[CI_PFON] = pp.enabled
    ci[CI_MLON] = pp.ml_enabled
    ci[CI_TA1] = sp.l1.policy == "tensor_aware"
    ci[CI_TA2] = sp.l2.policy == "tensor_aware"
    ci[CI_HYBRID] = sp.hybrid.enabled
    ci[CI_NTEN] = nten
    ci[CI_ST_TSIZE] = pp.stride_table_size
    ci[CI_ST_CONF] = pp.stride_confidence
    ci[CI_ST_DEG] = pp.degree
    ci[CI_ML_TSIZE] = pp.ml_table_size
    ci[CI_ML_HIST] = max(3, pp.ml_history)
    ci[CI_HP_HOT] = sp.hybrid.hot_threshold
    ci[CI_HP_WINDOW] = sp.hybrid.window
    ci[CI_HL1] = sp.l1.hit_latency
    ci[CI_HL2] = sp.l2.hit_latency
    ci[CI_HBM_PAGES_MAX] = HBM_CHANNEL.capacity_bytes // PAGE_SIZE
    ci[CI_TA_SAMPLE] = tp.sample
    ci[CI_TA_SHADOW] = tp.shadow_max
    ci[CI_TA_DECAY] = tp.decay_fills

    cd = np.zeros(CD_COUNT, np.float64)
    cd[CD_ML_THRESH] = pp.ml_threshold
    cd[CD_HP_MIGCOST] = sp.hybrid.migration_cost_cycles
    d, h = DRAM_CHANNEL, HBM_CHANNEL
    cd[CD_D_BL], cd[CD_D_RHL], cd[CD_D_BW] = d.base_latency, \
        d.row_hit_latency, d.bandwidth_bytes_per_cycle
    cd[CD_D_GAP], cd[CD_D_RBB] = d.row_gap, d.row_buffer_bytes
    cd[CD_H_BL], cd[CD_H_RHL], cd[CD_H_BW] = h.base_latency, \
        h.row_hit_latency, h.bandwidth_bytes_per_cycle
    cd[CD_H_GAP], cd[CD_H_RBB] = h.row_gap, h.row_buffer_bytes
    cd[CD_CORE_MLP], cd[CD_ACCEL_MLP] = CORE_MLP, ACCEL_MLP
    cd[CD_C2C], cd[CD_INV] = C2C_LATENCY, INV_LATENCY
    cd[CD_PF_THROTTLE] = PREFETCH_THROTTLE
    cd[CD_TA_LOW] = tp.low_utility
    cd[CD_TA_HIGH] = tp.high_utility
    cd[CD_TA_PREF] = tp.prefetch_rank
    cd[CD_TA_STREAM] = tp.stream_rank
    cd[CD_TA_BYPASS] = (sp.l3.ta.bypass_utility
                        if sp.l3 is not None else 0.0)
    return ci, cd


def resolve_engine(requested: str = "soa") -> str:
    """The effective engine label for provenance: what will actually run
    a cell, honoring ``REPRO_SIM_NATIVE`` and the ``--engine`` flag.

    ``soa`` resolves to ``native`` when the compiled kernel is available
    (``SoAHierarchySim.run`` tries it first) and, symmetrically,
    ``native`` degrades to ``soa`` when it isn't (the chunked Python
    path runs instead, bit-identical); ``reference`` is the registry
    alias for ``object``; ``object``/``jax`` run what they say.
    """
    if requested in ("soa", "native"):
        return "native" if get_lib() is not None else "soa"
    if requested == "reference":
        return "object"
    return requested


def run_arrays(sp, trace: Dict) -> Optional[Tuple[np.ndarray,
                                                   np.ndarray]]:
    """Run the trace through the compiled kernel; returns its flat
    counter vectors ``(oi[98], od[10])``, or None when the kernel is
    unavailable or the configuration falls outside its envelope."""
    lib = get_lib()
    if lib is None:
        return None
    tensor = np.ascontiguousarray(trace["tensor"], np.int32)
    nten = int(tensor.max()) + 1 if len(tensor) else 1
    packed = pack_config_sp(sp, nten)
    if packed is None:
        return None
    ci, cd = packed

    core = np.ascontiguousarray(trace["core"], np.int32)
    pc = np.ascontiguousarray(trace["pc"], np.int64)
    addr = np.ascontiguousarray(trace["addr"], np.int64)
    write = np.ascontiguousarray(np.asarray(trace["write"], bool)
                                 .view(np.uint8))
    reuse = np.ascontiguousarray(trace["reuse"], np.int32) \
        .astype(np.uint8)
    oi = np.zeros(98, np.int64)
    od = np.zeros(10, np.float64)
    lib.run_trace(ci, cd, core, pc, addr, write, tensor,
                  np.ascontiguousarray(reuse), ctypes.c_int64(len(core)),
                  oi, od)
    return oi, od


def run_native(sim, trace: Dict) -> bool:
    """Run the trace through the compiled kernel, depositing all counters
    on ``sim`` (a SoAHierarchySim).  Returns False when the kernel is
    unavailable or the configuration falls outside its envelope."""
    if not getattr(sim, "native", True):
        return False
    out = run_arrays(sim.sp, trace)
    if out is None:
        return False
    deposit_counters(sim, *out)
    return True


def deposit_counters(sim, oi: np.ndarray, od: np.ndarray) -> None:
    """Deposit a kernel's flat counter vectors (``oi``[98]/``od``[10],
    the layout exported by ``_sim_kernel.c`` and ``engine_jax``) on a
    SoAHierarchySim — the same surface the Python path fills."""
    nr = sim.n_req
    sim.n_acc = int(oi[0])
    sim.wb_lines = int(oi[1])
    sim.pf_dropped = int(oi[2])
    if sim.dir is not None:
        sim.dir.invalidations = int(oi[3])
        sim.dir.c2c_transfers = int(oi[4])
        sim.dir.upgrades = int(oi[5])
    mem = sim.mem
    mem.migrations = int(oi[6])
    mem.migration_bytes = int(oi[7])
    mem.dram.bytes_transferred = int(oi[8])
    mem.dram.row_hits = int(oi[9])
    mem.dram.accesses = int(oi[10])
    if mem.hbm is not None:
        mem.hbm.bytes_transferred = int(oi[11])
        mem.hbm.row_hits = int(oi[12])
        mem.hbm.accesses = int(oi[13])
    L1, L2, L3 = sim.l1, sim.l2, sim.l3
    L1.evictions, L1.dirty_evictions, L1.prefetch_fills = \
        int(oi[14]), int(oi[15]), int(oi[16])
    L2.evictions, L2.dirty_evictions, L2.prefetch_fills = \
        int(oi[17]), int(oi[18]), int(oi[19])
    l1h = oi[26:26 + nr].tolist()
    l1m = oi[34:34 + nr].tolist()
    l1pu = oi[42:42 + nr].tolist()
    l2h = oi[50:50 + nr].tolist()
    l2m = oi[58:58 + nr].tolist()
    l2pu = oi[66:66 + nr].tolist()
    L1.hits, L1.misses, L1.prefetch_useful = \
        sum(l1h), sum(l1m), sum(l1pu)
    L2.hits, L2.misses, L2.prefetch_useful = \
        sum(l2h), sum(l2m), sum(l2pu)
    if L3 is not None:
        L3.evictions, L3.dirty_evictions, L3.prefetch_fills = \
            int(oi[20]), int(oi[21]), int(oi[22])
        L3.hits, L3.misses, L3.prefetch_useful = \
            int(oi[23]), int(oi[24]), int(oi[25])
    for r in range(nr):
        if sim._strides[r] is not None:
            sim._strides[r].issued = int(oi[74 + r])
        if sim._mls[r] is not None:
            sim._mls[r].issued = int(oi[82 + r])
            sim._mls[r].trained = int(oi[90 + r])
    sim.time = od[:nr].tolist()
    sim.lat_sum = float(od[8])
    mem.migration_stall_cycles = float(od[9])
    sim._native_counts = (l1h, l1m, l1pu, l2h, l2m, l2pu)
