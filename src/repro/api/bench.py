"""Engine-throughput benchmark (reference vs SoA) + ``BENCH_sim.json``.

Moved here from ``benchmarks/tables.py`` so the ``python -m repro``
front door can run it from any working directory;
``benchmarks.tables.bench_engines`` remains as a thin delegate.
"""

from __future__ import annotations

import json
import time
from pathlib import Path
from typing import Dict, List

from repro.core import trace as trace_mod
from repro.core.presets import CONFIGS
from repro.core.simulator import HierarchySim

BENCH_PATH = Path(__file__).resolve().parents[3] / "BENCH_sim.json"
#: the ISSUE's acceptance criterion is measured at this scale; ad-hoc
#: scales print but never overwrite the canonical artifact
BENCH_CANONICAL_SCALE = 0.05


def _slice_trace(tr: Dict, n: int) -> Dict:
    out = dict(tr)
    for k in ("core", "pc", "addr", "write", "tensor", "reuse"):
        out[k] = tr[k][:n]
    return out


def bench_jax(tr: Dict, scale: float, workload: str,
              single_n: int = 4096, batch_n: int = 2048,
              big_batch_n: int = 512) -> List[Dict]:
    """jax-engine rows: single-config throughput plus batched-sweep
    throughput — ``configs_per_sec`` at 32 and 256 design points, each
    batch one vmapped device program.

    The scan's per-access cost is length-independent after compile, so
    each row runs a bounded slice of the trace and reports steady-state
    accesses/sec (and, for batches, configs/sec over that slice);
    ``accesses`` records the slice actually timed.  The big batch gets
    the shortest slice — on a CPU device vmap lanes are executed
    sequentially, so its wall cost scales with batch size.
    """
    import jax

    from repro.core import engine_jax
    from repro.core.presets import BASELINE
    from repro.sweep.grid import apply_point

    dev = jax.devices()[0]
    records: List[Dict] = []

    def lanes(b: int) -> List:
        # distinct configs in one shape bucket: the L2 hit latency is a
        # vmapped scalar, so every lane still shares the compiled code
        return [apply_point(BASELINE, {"l2.hit_latency": 12 + i})
                for i in range(b)]

    for label, sps, n in (
            ("jax", [BASELINE], single_n),
            ("jax_batch32", lanes(32), batch_n),
            ("jax_batch256", lanes(256), big_batch_n)):
        sub = _slice_trace(tr, n)
        t0 = time.perf_counter()
        if len(sps) == 1:
            engine_jax.run_single(sps[0], sub)
        else:
            engine_jax.run_batch(sps, sub)
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()           # warm: compile cache hit
        if len(sps) == 1:
            engine_jax.run_single(sps[0], sub)
        else:
            engine_jax.run_batch(sps, sub)
        dt = time.perf_counter() - t0
        records.append({
            "name": f"sim_{label}",
            "engine": "jax",
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            "native": False,
            "config": BASELINE.name,
            "workload": workload,
            "scale": scale,
            "batch": len(sps),
            "accesses": len(sub["core"]) * len(sps),
            "accesses_per_sec": round(len(sub["core"]) * len(sps) / dt, 1),
            "configs_per_sec": round(len(sps) / dt, 2),
            "compile_s": round(cold - dt, 1),
        })
    return records


def bench_engines(scale: float = 0.05, workload: str = "cnn",
                  save: bool = True, repeats: int = 2,
                  native: bool = True) -> List[Dict]:
    """Measure reference vs SoA engine throughput per preset and write
    ``BENCH_sim.json`` (the ≥10× acceptance artifact).

    ``native=False`` forces the pure-Python SoA path (benching the
    fallback even where a C compiler exists).  Best-of-``repeats`` per
    cell: wall times on small shared boxes vary ~2×, and min-of-N is
    the standard de-noising for throughput."""
    tr = trace_mod.WORKLOADS[workload](scale=scale)
    n = len(tr["core"])
    records: List[Dict] = []
    tot = {"object": 0.0, "soa": 0.0}
    for sp in CONFIGS:
        for engine in ("object", "soa"):
            dt = float("inf")
            nat = False
            for _ in range(max(1, repeats)):
                sim = HierarchySim(sp, engine=engine)
                if not native:
                    sim.native = False
                t0 = time.perf_counter()
                sim.run(tr)
                dt = min(dt, time.perf_counter() - t0)
                # distinguishes the compiled kernel from the pure-Python
                # SoA fallback in the perf record
                nat = getattr(sim, "_native_counts", None) is not None
            tot[engine] += dt
            records.append({
                "name": f"sim_{engine}",
                "engine": engine,
                "native": nat,
                "config": sp.name,
                "workload": workload,
                "scale": scale,
                "accesses": n,
                "accesses_per_sec": round(n / dt, 1),
            })
    records.extend(bench_jax(tr, scale=scale, workload=workload))
    agg = {
        "name": "sim_engine_speedup",
        "workload": workload,
        "scale": scale,
        "config": "aggregate(4 presets)",
        "accesses_per_sec": round(4 * n / tot["soa"], 1),
        "reference_accesses_per_sec": round(4 * n / tot["object"], 1),
        "speedup": round(tot["object"] / tot["soa"], 2),
    }
    records.append(agg)
    for r in records:
        line = ",".join(f"{k}={v}" for k, v in r.items())
        print(f"  bench,{line}")
    if save and native and scale == BENCH_CANONICAL_SCALE \
            and workload == "cnn":
        BENCH_PATH.write_text(json.dumps(records, indent=1))
        print(f"[bench] wrote {BENCH_PATH}")
    elif save:
        print(f"[bench] non-canonical cell (scale={scale}, "
              f"workload={workload}); {BENCH_PATH.name} not overwritten "
              f"(canonical: scale={BENCH_CANONICAL_SCALE}, cnn)")
    return records
