"""Process-parallel sweep execution over the SoA simulation engine.

Two sweep shapes:

* :func:`run_config_sweep` — run N arbitrary ``SystemParams`` over the
  paper workload suite and aggregate per config.  The generic primitive.

* :func:`run_ladder_sweep` — the preset-ladder explorer: each grid point
  rebuilds the paper's cumulative four-row ladder (baseline → shared_l3
  → prefetch′ → tensor_aware′) where ``prefetch.*`` overrides apply to
  BOTH HERMES rows (the narrative is cumulative) and cache/TA overrides
  apply to the tensor_aware row only.  Per point it reports the four
  aggregates plus the strict-monotonicity verdict
  (``calibration.trend_ok``) — the tool that retunes the paper table.

Execution is delegated to the ``repro.api`` Runner — the one
execute path (config dedup by value, spawn pool with per-chunk
trace reuse, native-kernel detection, failure isolation) shared with
``benchmarks.tables`` and the ``python -m repro`` CLI.  With
``backend="batched"`` the Runner routes whole config batches through
one vmapped jax device program (``core/engine_jax.py``) instead of the
process pool — same cells, same journal identity, bit-identical rows.
"""

from __future__ import annotations

import copy
import os
import sys
from pathlib import Path
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

from repro.api.schema import AGG_COLUMNS
from repro.api.schema import LADDER  # noqa: F401  (canonical row order)
from repro.core.calibration import trend_ok
from repro.core.params import SystemParams
from repro.core.presets import BASELINE, PREFETCH, SHARED_L3, TENSOR_AWARE
from repro.sweep.grid import apply_point, point_label
from repro.sweep.pareto import OBJECTIVES, pareto_front


#: cross-call shared-row memo: a config's aggregate depends only on
#: (config value, workloads, scale, engine) — ladders sharing a row
#: (every ladder shares baseline/shared_l3, retuned ladders share the
#: prefetch row) reuse it across *successive* sweep calls in one
#: process, not just within one Runner chunk.  Only fully-completed
#: rows are keyed: a ``degraded`` result (failed cells after the retry
#: budget) must be re-attempted by the next sweep, never replayed.
#: Chaos campaigns (``REPRO_CHAOS``) bypass the memo entirely — fault
#: injection is per-(cell, attempt) and reuse would dodge it.
_SWEEP_MEMO: Dict[Tuple, Dict[str, Any]] = {}


def _memo_key(sp: SystemParams, workloads, scale: float,
              engine: str, native: bool, backend: str) -> Tuple:
    # engine AND backend key the memo even though results are
    # bit-identical by contract: the CI equivalence gates re-run the
    # same configs across engines/backends precisely to PROVE that
    # contract, and a memo hit would make them vacuous
    wls = tuple(workloads) if workloads is not None else None
    return (sp, wls, float(scale), engine, bool(native), backend)


def clear_sweep_memo() -> None:
    """Drop all memoized rows (tests, or to force re-execution)."""
    _SWEEP_MEMO.clear()


def run_config_sweep(configs: Sequence[SystemParams], scale: float = 1.0,
                     engine: str = "soa",
                     processes: Optional[int] = None,
                     native: bool = True,
                     workloads: Optional[Sequence[str]] = None,
                     strict: bool = True,
                     retries: Optional[int] = None,
                     cell_timeout: Optional[float] = None,
                     journal_path: Optional[Path] = None,
                     resume: bool = False,
                     backend: str = "pool",
                     runner: Any = None) -> List[Dict[str, Any]]:
    """Run every config over the workload suite; one aggregate per config.

    Returns, in input order::

        {"name": ..., "aggregate": {latency_ns, bandwidth_gbps, hit_rate,
         energy_uj, per_workload}, "accesses_per_sec": {workload: rate}}

    The resilience knobs (``retries`` / ``cell_timeout`` /
    ``journal_path`` + ``resume`` / ``strict=False`` degradation) pass
    straight through to ``Runner.run_configs``; ``runner`` supplies a
    configured :class:`~repro.api.runner.Runner` in place of a default
    one.
    """
    # lazy: this module loads with the sweep package __init__; the
    # Runner (and its multiprocessing machinery) only at execution time
    from repro.api.runner import Runner

    use_memo = not os.environ.get("REPRO_CHAOS")
    keys = [_memo_key(sp, workloads, scale, engine, native, backend)
            for sp in configs]
    todo: List[SystemParams] = []
    todo_keys = set()
    for sp, key in zip(configs, keys):
        if not (use_memo and key in _SWEEP_MEMO) and key not in todo_keys:
            todo_keys.add(key)
            todo.append(sp)

    fresh: Dict[Tuple, Dict[str, Any]] = {}
    if todo:
        rows = (runner or Runner(processes=processes)).run_configs(
            todo, workloads=workloads, scale=scale, engine=engine,
            native=native, strict=strict, retries=retries,
            cell_timeout=cell_timeout, journal_path=journal_path,
            resume=resume, backend=backend)
        for sp, res in zip(todo, rows):
            key = _memo_key(sp, workloads, scale, engine, native,
                            backend)
            fresh[key] = res
            # degraded rows (failed cells) are excluded from the memo:
            # the next sweep must re-attempt them, not replay the hole
            if use_memo and not res.get("errors"):
                _SWEEP_MEMO[key] = copy.deepcopy(res)

    return [copy.deepcopy(fresh[key]) if key in fresh
            else copy.deepcopy(_SWEEP_MEMO[key]) for key in keys]


def _split_overrides(point: Mapping[str, Any]) -> Tuple[Dict, Dict]:
    """(prefetch-row overrides, tensor_aware-row overrides).

    ``prefetch.*`` paths shift both HERMES rows (cumulative ladder);
    everything else refines only the tensor_aware row.
    """
    pf = {k: v for k, v in point.items() if k.startswith("prefetch.")}
    return pf, dict(point)


def run_ladder_sweep(points: Sequence[Mapping[str, Any]],
                     scale: float = 1.0, engine: str = "soa",
                     processes: Optional[int] = None,
                     native: bool = True,
                     objectives=OBJECTIVES,
                     retries: Optional[int] = None,
                     cell_timeout: Optional[float] = None,
                     journal_path: Optional[Path] = None,
                     resume: bool = False,
                     backend: str = "pool",
                     workloads: Optional[Sequence[str]] = None,
                     runner: Any = None) -> Dict[str, Any]:
    """Evaluate the paper's four-row ladder for every grid point.

    Returns an artifact-shaped dict: per point the four row aggregates,
    ``trend_ok``, and the tensor_aware row's metrics; plus the Pareto
    front (over tensor_aware rows) and the recommended point — the
    trend-passing Pareto member with the highest hit rate (hit rate is
    the regressed metric this explorer exists to fix), latency as the
    tie-break.

    Degradation policy: cells the Runner could not complete (after its
    retry budget) do NOT abort the sweep — every ladder point touching
    a failed config is marked ``degraded_rows``, forced trend-fail, and
    excluded from the Pareto front; the structured failure rows surface
    in the payload's ``failures`` for artifact provenance.
    """
    # -- dedupe configs across ladders ----------------------------------
    cfgs: List[SystemParams] = [BASELINE, SHARED_L3]
    cfg_index: Dict[SystemParams, int] = {BASELINE: 0, SHARED_L3: 1}
    ladders: List[Tuple[Mapping, int, int]] = []  # (point, pf_i, ta_i)
    for i, point in enumerate(points):
        pf_over, ta_over = _split_overrides(point)
        sp_pf = apply_point(PREFETCH, pf_over)
        sp_ta = apply_point(TENSOR_AWARE, ta_over)
        for sp in (sp_pf, sp_ta):
            if sp not in cfg_index:
                cfg_index[sp] = len(cfgs)
                cfgs.append(sp)
        ladders.append((point, cfg_index[sp_pf], cfg_index[sp_ta]))

    results = run_config_sweep(cfgs, scale=scale, engine=engine,
                               processes=processes, native=native,
                               strict=False, retries=retries,
                               cell_timeout=cell_timeout,
                               journal_path=journal_path, resume=resume,
                               backend=backend, workloads=workloads,
                               runner=runner)

    # structured failure rows, deduped (aliased configs share them)
    failures: List[Dict[str, Any]] = []
    seen = set()
    for res in results:
        for wl, fr in res.get("errors", {}).items():
            if (fr["config_hash"], wl) not in seen:
                seen.add((fr["config_hash"], wl))
                failures.append(fr)

    def _agg(i: int) -> Dict[str, float]:
        return {k: v for k, v in results[i]["aggregate"].items()
                if k != "per_workload"}

    rows_out: List[Dict[str, Any]] = []
    ta_rows: List[Dict[str, float]] = []
    for point, pf_i, ta_i in ladders:
        ladder = {"baseline": _agg(0), "shared_l3": _agg(1),
                  "prefetch": _agg(pf_i), "tensor_aware": _agg(ta_i)}
        degraded = sorted(name for name, agg in ladder.items()
                          if any(c not in agg for c in AGG_COLUMNS))
        row = {
            "point": dict(point),
            "label": point_label(point),
            "rows": ladder,
            "trend_ok": False if degraded else trend_ok(ladder),
        }
        if degraded:
            row["degraded_rows"] = degraded
            print(f"[sweep] point {row['label']}: ladder rows "
                  f"{degraded} incomplete (cells permanently failed) — "
                  f"excluded from Pareto/trend", file=sys.stderr)
        rows_out.append(row)
        ta_rows.append(ladder["tensor_aware"])

    # Pareto only over fully-evaluated points (a degraded tensor_aware
    # row has no comparable metrics)
    ok_idx = [i for i, r in enumerate(rows_out)
              if "degraded_rows" not in r]
    front = sorted(ok_idx[j] for j in
                   pareto_front([ta_rows[i] for i in ok_idx],
                                objectives)) if ok_idx else []
    for i, r in enumerate(rows_out):
        r["pareto"] = i in front

    # recommend from the Pareto front OF THE TREND-OK SUBSET: a trend-ok
    # point dominated only by trend-failing points is still the best
    # usable retune, and discarding it would report "no trend-restoring
    # point" while n_trend_ok > 0
    recommended = None
    trend_idx = [i for i, r in enumerate(rows_out) if r["trend_ok"]]
    if trend_idx:
        sub = pareto_front([ta_rows[i] for i in trend_idx], objectives)
        candidates = [trend_idx[j] for j in sub]
        best = max(candidates,
                   key=lambda i: (ta_rows[i]["hit_rate"],
                                  -ta_rows[i]["latency_ns"]))
        recommended = rows_out[best]
    # NB: engine/backend are deliberately NOT part of the payload — all
    # engines are bit-identical by contract, so the sweep *result* is
    # engine-independent (CI asserts soa and jax artifact fingerprints
    # match); which engine actually ran is recorded in artifact
    # provenance by the CLI layer.
    return {
        "scale": scale,
        "n_points": len(rows_out),
        "n_unique_configs": len(cfgs),
        "objectives": [list(o) for o in objectives],
        "points": rows_out,
        "pareto_front": front,
        "n_trend_ok": sum(r["trend_ok"] for r in rows_out),
        "n_degraded_points": len(rows_out) - len(ok_idx),
        "recommended": recommended,
        "failures": failures,
    }
