#!/usr/bin/env python3
"""Smoke run of both HERMES systems on one TPU chip.

    python chip_smoke.py

Phase 1, the simulator.  The paper's four-row ladder (baseline,
shared_l3, prefetch, tensor_aware: the jax engine's four shape buckets)
runs through the Runner's batched backend, the path that
``python -m repro table --backend batched`` takes, over the cnn, rnn and
transformer traces at the paper's scale, cut to their first TRACE_CAP
accesses; each bucket scans the three traces side by side.  Every row
must equal the native C kernel's row on the same trace, bit for bit.
A design-space sweep over the smoke grid, widened to 32 tensor-aware
points, then runs one lane-batched bucket; its artifact must equal the
same sweep run by the C kernel, as the CI sweep gate requires.

Phase 2, the model stack.  gemma-2b at its published widths, with seeded
random weights held in bf16, serves 4 requests (a 32-token prompt and 16
new tokens each) through the serve launcher and ServingEngine.

It exits non-zero, printing no result line, unless JAX finds a TPU and
both phases pass.  The last line of standard output is one JSON object
naming the device.  It runs in one process and starts no other.
Compiled programs are kept where ``JAX_COMPILATION_CACHE_DIR`` says,
else in the checkout's ``.jax_cache/``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: the paper's trace size (694,880-865,880 accesses per workload)
SCALE = 1.0
#: accesses kept of each trace (its first ones).  One scan step takes
#: 0.36 ms (baseline) to 3.3 ms (tensor_aware) on a v5e at one lane
#: (PERF.md), so whole paper-size traces would take hours: the smoke
#: runs their prefixes, through ``REPRO_TRACE_CAP``
TRACE_CAP = 4096
#: workloads of the ladder campaign, scanned side by side as lanes
LADDER_WORKLOADS = ("cnn", "rnn", "transformer")
#: the smoke grid with its lane-valued axis widened and its structural
#: axes pinned, so that all 32 tensor-aware points share one bucket
SWEEP_AXES = {
    "prefetch.degree": [3],
    "l2.policy": ["lru"],
    "ta.prefetch_rank": [1.5, 2.0, 2.5, 3.0, 3.5, 4.0, 4.5, 5.0],
    "ta.low_utility": [0.05, 0.1, 0.2, 0.3],
}
SWEEP_WORKLOADS = ("cnn",)

SERVE_ARGS = ["--arch", "gemma-2b", "--full", "--requests", "4",
              "--prompt-len", "32", "--max-new", "16"]
#: gemma-2b's published widths (configs/gemma_2b.py)
SERVE_WIDTHS = {"n_layers": 18, "d_model": 2048, "vocab_size": 256000}


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Seconds jax spends in backend compiles (cache reads included)."""

    def __init__(self) -> None:
        import jax.monitoring
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += duration


def _counter_diff(oi, od, ref) -> str:
    """Name the first counter of the export layout that differs."""
    import numpy as np
    names = {0: "n_acc", 1: "wb_lines", 2: "pf_dropped",
             3: "invalidations", 4: "c2c_transfers", 5: "upgrades",
             6: "migrations", 7: "migration_bytes", 8: "dram.bytes",
             9: "dram.row_hits", 10: "dram.accesses", 11: "hbm.bytes",
             12: "hbm.row_hits", 13: "hbm.accesses"}
    for i in np.flatnonzero(oi != ref[0]):
        return (f"oi[{i}] ({names.get(int(i), 'per-level counter')}): "
                f"jax {oi[i]} vs C {ref[0][i]}")
    for i in np.flatnonzero(od.view(np.int64) != ref[1].view(np.int64)):
        name = ("time[%d]" % i if i < 8 else
                "lat_sum" if i == 8 else "migration_stall_cycles")
        return f"od[{i}] ({name}): jax {od[i]!r} vs C {ref[1][i]!r}"
    return "no counter differs"


def _print_buckets(log, start: int) -> int:
    """Print the scan records from ``start`` on; returns the next index."""
    for r in log[start:]:
        print(f"[smoke] bucket {r['bucket']:>14s} lanes={r['lanes']:3d} "
              f"traces={r['traces']} steps={r['steps']:7d} "
              f"compile={r['compile_s']:6.1f}s scan={r['scan_s']:7.2f}s  "
              f"{r['steps'] / r['scan_s']:,.0f} steps/s, "
              f"{r['accesses'] / r['scan_s']:,.0f} simulated accesses/s",
              flush=True)
    return len(log)


def phase_sim(clock: CompileClock, tmp: Path) -> None:
    from repro.api.registry import SWEEP_GRIDS
    from repro.api.runner import Runner
    from repro.api.spec import Experiment, ladder_specs
    from repro.cli import run_sweep
    from repro.core import engine_jax, native
    from repro.core import trace as trace_mod
    from repro.core.simulator import HierarchySim

    check(native.get_lib() is not None,
          "the C kernel, the reference of this phase, did not build")
    t0 = time.perf_counter()
    traces = {wl: trace_mod.WORKLOADS[wl](scale=SCALE)
              for wl in LADDER_WORKLOADS + SWEEP_WORKLOADS}
    check(all(len(t["core"]) == TRACE_CAP for t in traces.values()),
          "traces are not cut to their first TRACE_CAP accesses")
    # one layout for every trace of this phase: each bucket compiles once
    caps = engine_jax.Caps.cover([engine_jax.Caps.of(t)
                                  for t in traces.values()])
    runner = Runner(retries=0, batched_caps=caps, preemptible=False)
    print(f"[smoke] traces: the first {TRACE_CAP} accesses of "
          f"{', '.join(traces)} at scale {SCALE:g}; prepared in "
          f"{time.perf_counter() - t0:.1f}s; {caps}", flush=True)

    # -- the ladder, through the table path ----------------------------
    c0, t0 = clock.total, time.perf_counter()
    matched = 0
    exp = Experiment(name="chip_smoke", hierarchies=ladder_specs(),
                     workloads=LADDER_WORKLOADS, scale=SCALE,
                     backend="batched")
    art = runner.run(exp, kind="table", tool="chip_smoke.py",
                     journal_dir=tmp)
    shown = _print_buckets(engine_jax.SCAN_LOG, 0)
    check(not art["provenance"].get("failures"),
          f"degraded ladder campaign: {art['provenance'].get('failures')}")
    by_name = {sp.name: sp for sp in exp.build_configs()}
    by_trace = {traces[wl]["name"]: traces[wl] for wl in LADDER_WORKLOADS}
    check(len(art["rows"]) == len(by_name) * len(LADDER_WORKLOADS),
          f"{len(art['rows'])} ladder rows, expected "
          f"{len(by_name) * len(LADDER_WORKLOADS)}")
    for row in art["rows"]:
        sp, tr = by_name[row["name"]], by_trace[row["workload"]]
        ref = HierarchySim(sp, engine="soa")
        want = ref.run(tr).row()
        check(ref._native_counts is not None,
              f"{sp.name}: the reference did not run the C kernel")
        if row != want:
            field = next(k for k in want if want[k] != row.get(k))
            (oi, od), = engine_jax.run_batch([sp], tr, caps=caps)
            raise SmokeFailure(
                f"{sp.name} × {row['workload']}: Metrics.{field} jax "
                f"{row.get(field)!r} vs C {want[field]!r}; first counter: "
                f"{_counter_diff(oi, od, native.run_arrays(sp, tr))}")
        matched += 1
    ladder_s = time.perf_counter() - t0
    print(f"[smoke] ladder: {matched} rows bit-identical to the C kernel "
          f"in {ladder_s:.1f}s wall, {clock.total - c0:.1f}s compiling",
          flush=True)

    # -- one lane-batched bucket: a design-space sweep ------------------
    c0, t0 = clock.total, time.perf_counter()
    axes = dict(SWEEP_GRIDS["smoke"], **SWEEP_AXES)
    arts = {}
    for engine, backend, run in (
            ("jax", "batched", runner),
            ("soa", "pool", Runner(processes=1, retries=0,
                                   preemptible=False))):
        arts[engine] = run_sweep(
            SCALE, axes, "chip_smoke", engine=engine,
            backend=backend, processes=1, retries=0,
            out=str(tmp / f"sweep_{engine}.json"), journal_dir=str(tmp),
            workloads=list(SWEEP_WORKLOADS), runner=run,
            tool="chip_smoke.py")
    _print_buckets(engine_jax.SCAN_LOG, shown)
    jx, ref = arts["jax"], arts["soa"]
    check(not jx["provenance"].get("failures"), "degraded sweep campaign")
    check(jx["result"]["n_points"] >= 32,
          f"{jx['result']['n_points']} sweep points, expected >= 32")
    check(jx["rows"] == ref["rows"] and jx["result"] == ref["result"]
          and jx["provenance"]["fingerprint"]
          == ref["provenance"]["fingerprint"],
          "sweep artifact of the jax engine differs from the C kernel's")
    print(f"[smoke] sweep: {jx['result']['n_points']} points, "
          f"{len(jx['rows'])} rows and the artifact identical to the C "
          f"kernel's in {time.perf_counter() - t0:.1f}s wall, "
          f"{clock.total - c0:.1f}s compiling", flush=True)

    widest = max(r["lanes"] for r in engine_jax.SCAN_LOG)
    check(widest >= 32, f"widest bucket ran {widest} lanes, expected >= 32")


def phase_serve(clock: CompileClock) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import serve

    c0, t0 = clock.total, time.perf_counter()
    engine, done = serve.main(SERVE_ARGS)
    wall = time.perf_counter() - t0
    cfg = engine.cfg
    check(all(getattr(cfg, k) == v for k, v in SERVE_WIDTHS.items()),
          f"not the published widths: {cfg}")
    dtypes = {str(a.dtype) for a in jax.tree.leaves(engine.params)}
    check(dtypes == {"bfloat16"}, f"served weight dtypes {dtypes}")
    check(len(done) == 4 and not engine.dropped,
          f"{len(done)} of 4 requests finished, {len(engine.dropped)} "
          f"dropped")
    for req in done:
        toks = np.asarray(req.out_tokens)
        check(len(toks) == 16, f"request {req.req_id}: {len(toks)} tokens")
        check(bool(np.all((toks >= 0) & (toks < cfg.vocab_size))),
              f"request {req.req_id}: token out of range {toks}")
    # the first decode step once more: finite logits, and the same
    # greedy tokens the engine produced
    req = done[0]
    logits, cache = engine.prefill(engine.params,
                                   jnp.asarray(req.prompt)[None])
    first = int(jnp.argmax(logits[0]))
    logits, _ = engine.decode(engine.params, cache,
                              jnp.asarray([[first]], jnp.int32))
    check(bool(jnp.all(jnp.isfinite(logits))),
          "first decode step: non-finite logits")
    check([first, int(jnp.argmax(logits[0]))] == req.out_tokens[:2],
          "first decode step: tokens differ from the served ones")
    peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
    print(f"[smoke] serve: gemma-2b {cfg.param_count():,} params in "
          f"bfloat16, 4 requests × 16 tokens in {wall:.1f}s wall, "
          f"{clock.total - c0:.1f}s compiling; peak HBM {peak} bytes "
          f"({peak / 2**30:.2f} GiB)", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("[smoke] FAILED: src/repro is not beside this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    os.environ["REPRO_TRACE_CAP"] = str(TRACE_CAP)
    from repro.runtime import compile_cache
    cache_dir = compile_cache.configure()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[smoke] FAILED: JAX finds no TPU (platform "
              f"{dev.platform!r})", file=sys.stderr)
        return 2
    from repro.kernels import ops
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        check(not ops.interpret(), "Pallas kernels would run interpreted")
        print(f"[smoke] device {dev.platform} {dev.device_kind} × "
              f"{len(jax.devices())}; compile cache {cache_dir}",
              flush=True)
        with tempfile.TemporaryDirectory() as tmp:
            t1 = time.perf_counter()
            phase_sim(clock, Path(tmp))
            print(f"[smoke] phase 1 (simulator): "
                  f"{time.perf_counter() - t1:.1f}s", flush=True)
        t1 = time.perf_counter()
        phase_serve(clock)
        print(f"[smoke] phase 2 (serving): {time.perf_counter() - t1:.1f}s",
              flush=True)
    except Exception as e:  # noqa: BLE001 — any failure fails the smoke
        import traceback
        traceback.print_exc()
        print(f"[smoke] FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    print(f"[smoke] total {time.perf_counter() - t0:.1f}s, of which "
          f"{clock.total:.1f}s compiling", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
