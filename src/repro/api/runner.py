"""The one process-parallel execute path for simulator experiments.

Before PR 5 every entry point (``benchmarks.tables``,
``benchmarks.sweep``, the examples) hand-rolled its own spawn pool,
config dedup, and result reshaping.  :class:`Runner` owns that path
once; since PR 6 it is also the *resilience* layer every campaign
inherits:

* **cell dedup** — configs are deduplicated by value (frozen
  dataclasses hash), so ladder sweeps sharing rows never re-simulate;
* **process parallelism** — per-cell (workload × config) tasks over a
  spawn-based worker pool (spawn keeps workers from inheriting jax/XLA
  state); each worker caches generated traces per (workload, scale)
  and the dispatcher prefers workers that already hold the trace;
* **per-cell deadlines** — a rolling-median deadline per workload
  (``runtime.fault.StragglerMonitor`` × a safety factor) plus an
  optional explicit ``cell_timeout``; an overdue cell's worker is
  killed and the cell retried;
* **retry with backoff** — transient failures (exceptions, corrupt
  rows, timeouts, worker deaths) are retried up to ``retries`` times
  with exponential backoff and deterministic jitter
  (``runtime.chaos.backoff_delay``);
* **worker-crash isolation** — a dead worker (OOM-kill, segfault) is
  respawned and its in-flight cell requeued instead of hanging or
  aborting the campaign;
* **structured failure rows** — a permanently-failed cell is recorded
  as a ``schema.failure_row`` (config hash, attempt count, error, full
  traceback, duration) — never a silent drop, never a bare string;
* **journaled resume** — with a ``journal_path``, every completed cell
  is appended (flushed + fsynced) to a ``repro.journal.v1`` JSONL
  file; ``resume=True`` seeds completed cells from it, so a campaign
  killed at any point (SIGTERM, OOM, ``kill -9``) restarts where it
  stopped and produces a final ArtifactV1 whose deterministic content
  is bit-identical to an uninterrupted run
  (``schema.artifact_fingerprint``);
* **preemption** — SIGTERM/SIGINT (``runtime.fault.PreemptionHandler``)
  stops dispatch at the next cell boundary and raises
  :class:`RunnerInterrupted` naming the journal to resume from;
* **deterministic chaos** — a ``runtime.chaos.FaultSpec`` (explicit or
  via the ``REPRO_CHAOS`` env var) injects crash / hang / slow /
  corrupt-row / OOM-kill faults into the workers on a seeded,
  replayable schedule — the harness the chaos CI gate drives.

``Runner.run(experiment)`` returns (and optionally writes) a validated
ArtifactV1; ``Runner.run_configs`` is the lower-level primitive the
legacy entry points delegate to; ``Runner.map`` is the serial
failure-isolated (and now retry-capable) map the dry-run/plan matrix
loops share.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import queue as queue_mod
import sys
import time
import traceback
from collections import deque
from pathlib import Path
from typing import (Any, Callable, Dict, List, Optional, Sequence, Set,
                    Tuple)

from repro.api import schema as schema_mod
from repro.api.spec import Experiment
from repro.core import trace as trace_mod
from repro.core.params import SystemParams
from repro.runtime.chaos import FaultSpec, backoff_delay
from repro.runtime.fault import PreemptionHandler, StragglerMonitor


class RunnerError(RuntimeError):
    """One or more cells failed; the message lists every failing cell."""


class RunnerInterrupted(RunnerError):
    """The campaign was preempted (SIGTERM/SIGINT) mid-run.

    Completed cells are safe in the journal (when one was configured);
    re-running with ``resume=True`` / ``--resume`` continues from them.
    """

    def __init__(self, msg: str, journal_path: Optional[Path] = None,
                 done: int = 0, total: int = 0) -> None:
        super().__init__(msg)
        self.journal_path = journal_path
        self.done = done
        self.total = total


def config_hash(sp: SystemParams) -> str:
    """Stable 12-hex value hash of a config — the journal/failure-row
    key (config *names* are not unique across sweep points)."""
    return schema_mod.spec_hash(dataclasses.asdict(sp))[7:19]


# ---------------------------------------------------------------------------
# cell execution body (shared by the serial path and the pool workers)
# ---------------------------------------------------------------------------
#: per-process trace cache — pool workers persist across tasks, so each
#: worker generates a given (workload, scale) trace at most once
_TRACE_CACHE: Dict[Tuple[str, float], Any] = {}


def _get_trace(wl: str, scale: float) -> Any:
    key = (wl, scale)
    if key not in _TRACE_CACHE:
        _TRACE_CACHE[key] = trace_mod.WORKLOADS[wl](scale=scale)
    return _TRACE_CACHE[key]


def _run_cell_body(task: Tuple,
                   in_worker: bool = True) -> Tuple[Dict, float, bool,
                                                    float]:
    """Simulate one (config × workload) cell; returns
    ``(row, accesses_per_sec, native_used, duration_s)``.

    Applies the chaos fault scheduled for this (cell, attempt), if any:
    crash raises, oom exits the process, hang/slow sleep, corrupt
    poisons the returned row (the coordinator detects and retries it).
    On the serial executor (``in_worker=False``) oom/hang degrade to a
    catchable ChaosFault — they must not take down the coordinator.
    """
    key, wl, scale, engine, native, sp, attempt, chaos = task
    from repro.core.simulator import HierarchySim

    if chaos is None:
        chaos = FaultSpec.from_env()
    fault = chaos.inject(key, attempt, in_worker=in_worker) \
        if chaos is not None else None
    tr = _get_trace(wl, scale)
    sim = HierarchySim(sp, engine=engine)
    if not native:
        sim.native = False
    t0 = time.perf_counter()
    metrics = sim.run(tr)
    dt = time.perf_counter() - t0
    row = metrics.row()
    if fault == "corrupt":
        row = chaos.corrupt_row(row)
    native_used = getattr(sim, "_native_counts", None) is not None
    return row, len(tr["core"]) / max(dt, 1e-9), native_used, dt


def _pool_worker_main(task_q: Any, result_q: Any,
                      worker_id: int, coordinator: int) -> None:
    """Worker loop: execute tasks until a ``None`` sentinel.

    Top-level so it pickles under the spawn start method.  The worker
    never decides policy — every failure (including an injected chaos
    crash) is shipped to the coordinator as an ``("err", …)`` message
    with its full traceback; an injected OOM-kill simply dies here and
    the coordinator reaps the process.  A worker whose coordinator
    (pid ``coordinator``) died — ``kill -9``, possibly before this worker
    finished starting — exits too, instead of idling as an orphan that
    holds the coordinator's inherited output pipes open.
    """
    import signal as signal_mod
    import threading
    try:                                 # the coordinator owns Ctrl-C
        signal_mod.signal(signal_mod.SIGINT, signal_mod.SIG_IGN)
    except ValueError:
        pass
    def watch_parent() -> None:
        while os.getppid() == coordinator:
            time.sleep(0.5)
        os._exit(0)

    threading.Thread(target=watch_parent, daemon=True).start()
    while True:
        msg = task_q.get()
        if msg is None:
            break
        task_id, task = msg
        try:
            row, rate, native_used, dt = _run_cell_body(task)
            result_q.put(("ok", worker_id, task_id, row, rate,
                          native_used, dt))
        except BaseException as e:  # noqa: BLE001 — ship it, don't die
            result_q.put(("err", worker_id, task_id,
                          f"{type(e).__name__}: {e}",
                          traceback.format_exc()[-4000:]))


def _row_nonfinite(row: Dict[str, Any]) -> bool:
    return any(isinstance(v, (int, float)) and not isinstance(v, bool)
               and not math.isfinite(v) for v in row.values())


def _fault_kind_of(error: str) -> Optional[str]:
    if not error.startswith("ChaosFault"):
        return None
    if "injected oom" in error:
        return "oom"
    if "injected hang" in error:
        return "hang"
    return "crash"


class _Worker:
    __slots__ = ("wid", "proc", "task_q", "task", "started", "traces")

    def __init__(self, wid: int, proc: Any, task_q: Any) -> None:
        self.wid = wid
        self.proc = proc
        self.task_q = task_q
        self.task: Optional[Tuple[int, Dict]] = None   # (task_id, rec)
        self.started = 0.0
        self.traces: Set[str] = set()


# ---------------------------------------------------------------------------
# journal I/O
# ---------------------------------------------------------------------------
def _read_journal(path: Path, campaign: str,
                  ) -> Tuple[Dict[Tuple[str, str], Dict], bool]:
    """Parse a resume journal; returns ``(completed, header_matched)``.

    Tolerates a torn final line (the run died mid-append) and ignores
    the whole file when the header's campaign hash does not match —
    a stale journal must never seed a different campaign.
    """
    completed: Dict[Tuple[str, str], Dict] = {}
    try:
        lines = path.read_text().splitlines()
    except OSError:
        return completed, False
    if not lines:
        return completed, False
    try:
        header = json.loads(lines[0])
    except json.JSONDecodeError:
        return completed, False
    if (header.get("journal") != schema_mod.JOURNAL_SCHEMA
            or header.get("campaign") != campaign):
        return completed, False
    for line in lines[1:]:
        try:
            e = json.loads(line)
            completed[(e["config_hash"], e["workload"])] = e
        except (json.JSONDecodeError, KeyError, TypeError):
            continue                      # torn tail write: skip
    return completed, True


class Runner:
    """Owns the single (now chaos-hardened) execute path over the
    HERMES simulator."""

    def __init__(self, processes: Optional[int] = None,
                 progress: bool = False, retries: int = 2,
                 cell_timeout: Optional[float] = None,
                 backoff_s: float = 0.1, deadline_factor: float = 4.0,
                 chaos: Optional[FaultSpec] = None,
                 preemptible: bool = True,
                 batched_caps: Any = None) -> None:
        self.processes = processes
        self.progress = progress
        self.retries = retries
        self.cell_timeout = cell_timeout
        self.backoff_s = backoff_s
        self.deadline_factor = deadline_factor
        #: explicit FaultSpec wins; else the REPRO_CHAOS env var applies
        self.chaos = chaos
        self.preemptible = preemptible
        #: array extents (``engine_jax.Caps``) the batched backend lays
        #: every trace out at; None = the envelope of each campaign's
        #: own traces.  Campaigns run at equal caps share compiles.
        self.batched_caps = batched_caps
        #: resilience counters of the most recent run_configs call
        self.last_stats: Dict[str, Any] = {}

    # -- the parallel primitive ----------------------------------------
    def run_configs(self, configs: Sequence[SystemParams],
                    workloads: Optional[Sequence[str]] = None,
                    scale: float = 1.0, engine: str = "soa",
                    native: bool = True, strict: bool = True,
                    processes: Optional[int] = None,
                    retries: Optional[int] = None,
                    cell_timeout: Optional[float] = None,
                    journal_path: Optional[Path] = None,
                    resume: bool = False, backend: str = "pool",
                    ) -> List[Dict[str, Any]]:
        """Run every config over the workload suite.

        Returns, in input order (duplicated configs share one
        simulation)::

            {"name": …, "aggregate": {latency_ns, bandwidth_gbps,
             hit_rate, energy_uj, per_workload}, "rows": {workload: row},
             "accesses_per_sec": {workload: rate}, "native": bool}

        With ``strict=True`` (default) any permanently-failed cell
        raises :class:`RunnerError` naming every failure; with
        ``strict=False`` each failure lands as a structured
        ``schema.failure_row`` in the result's ``"errors"`` entry —
        the graceful-degradation path ``Runner.run`` uses.

        ``journal_path`` + ``resume`` give kill-anywhere restartability
        (see the class docstring); ``retries`` / ``cell_timeout``
        override the Runner-level defaults for this call.
        """
        from repro.core.calibration import aggregate_rows

        wls = list(workloads) if workloads is not None \
            else list(trace_mod.WORKLOADS)
        retries = self.retries if retries is None else retries
        cell_timeout = (self.cell_timeout if cell_timeout is None
                        else cell_timeout)
        chaos = self.chaos if self.chaos is not None \
            else FaultSpec.from_env()

        # -- dedup by value: identical configs simulate once -----------
        uniq: List[SystemParams] = []
        uidx: Dict[SystemParams, int] = {}
        alias: List[int] = []
        for sp in configs:
            if sp not in uidx:
                uidx[sp] = len(uniq)
                uniq.append(sp)
            alias.append(uidx[sp])
        hashes = [config_hash(sp) for sp in uniq]

        campaign = schema_mod.spec_hash({
            "cells": sorted(hashes), "workloads": wls, "scale": scale,
            "engine": engine, "native": native})
        cells = [{"key": f"{hashes[ci]}:{wl}", "cfg_idx": ci, "wl": wl,
                  "hash": hashes[ci], "sp": uniq[ci]}
                 for ci in range(len(uniq)) for wl in wls]

        # -- journal: seed completed cells, open for append ------------
        completed: Dict[Tuple[str, str], Dict] = {}
        jfh = None
        if journal_path is not None:
            journal_path = Path(journal_path)
            journal_path.parent.mkdir(parents=True, exist_ok=True)
            matched = False
            if resume and journal_path.exists():
                completed, matched = _read_journal(journal_path, campaign)
                if not matched:
                    print(f"[runner] journal {journal_path} does not "
                          f"match this campaign; starting fresh",
                          file=sys.stderr)
            if matched:
                jfh = open(journal_path, "a")
            else:
                jfh = open(journal_path, "w")
                jfh.write(json.dumps(
                    {"journal": schema_mod.JOURNAL_SCHEMA,
                     "campaign": campaign, "n_cells": len(cells)}) + "\n")
                jfh.flush()

        outcomes: Dict[Tuple[int, str], Dict[str, Any]] = {}
        to_run: List[Dict] = []
        for cell in cells:
            e = completed.get((cell["hash"], cell["wl"]))
            if e is not None and not _row_nonfinite(e.get("row", {})):
                outcomes[(cell["cfg_idx"], cell["wl"])] = {
                    "status": "ok", "row": e["row"], "rate": e["rate"],
                    "native": e["native"],
                    "attempts": e.get("attempts", 1), "resumed": True}
            else:
                to_run.append(cell)

        journaled = 0

        def on_ok(cell: Dict, row: Dict, rate: float, native_used: bool,
                  attempts: int) -> None:
            nonlocal journaled
            if jfh is None:
                return
            jfh.write(json.dumps({
                "config": cell["sp"].name, "config_hash": cell["hash"],
                "workload": cell["wl"], "row": row,
                "rate": round(rate, 1), "native": native_used,
                "attempts": attempts}) + "\n")
            jfh.flush()
            os.fsync(jfh.fileno())
            journaled += 1
            if (chaos is not None and chaos.kill_after_cells is not None
                    and journaled >= chaos.kill_after_cells):
                # the campaign-level chaos fault: die as if kill -9'd.
                # The journal is already fsynced — that is the point.
                os._exit(137)

        if processes is None:
            processes = self.processes
        if processes is None:
            processes = min(len(wls) * max(1, len(uniq) // 4) or 1,
                            os.cpu_count() or 1)

        stats = {"timeouts": 0, "worker_deaths": 0, "retried": 0,
                 "failed": 0}
        preempt = PreemptionHandler(install=True) if self.preemptible \
            else None
        try:
            if to_run:
                common = (scale, engine, native)
                if backend == "batched":
                    self._execute_batched(to_run, common, retries, chaos,
                                          outcomes, on_ok, preempt,
                                          stats, journal_path, len(cells))
                elif processes > 1 and len(to_run) > 1 \
                        and engine != "jax":
                    # the jax engine never fans out: every worker would
                    # claim the one accelerator, so its cells run in
                    # this process (the serial branch below)
                    self._execute_pool(to_run, common, processes,
                                       retries, cell_timeout, chaos,
                                       outcomes, on_ok, preempt, stats,
                                       journal_path, len(cells))
                else:
                    self._execute_serial(to_run, common, retries, chaos,
                                         outcomes, on_ok, preempt, stats,
                                         journal_path, len(cells))
        finally:
            if preempt is not None:
                preempt.uninstall()
            if jfh is not None:
                jfh.close()

        # -- reshape into per-config results ---------------------------
        rows: Dict[int, Dict[str, Dict]] = {i: {} for i in
                                            range(len(uniq))}
        rates: Dict[int, Dict[str, float]] = {i: {} for i in
                                              range(len(uniq))}
        errors: Dict[int, Dict[str, Dict]] = {i: {} for i in
                                              range(len(uniq))}
        native_used: Dict[int, bool] = {i: True for i in range(len(uniq))}
        n_resumed = 0
        for (ci, wl), oc in outcomes.items():
            if oc["status"] == "ok":
                rows[ci][wl] = oc["row"]
                rates[ci][wl] = round(oc["rate"], 1)
                native_used[ci] = native_used[ci] and oc["native"]
                n_resumed += 1 if oc.get("resumed") else 0
            else:
                errors[ci][wl] = oc["failure"]

        self.last_stats = {
            "cells": len(cells), "resumed": n_resumed,
            "completed": sum(1 for oc in outcomes.values()
                             if oc["status"] == "ok"),
            "retries": retries, "cell_timeout": cell_timeout,
            "journal": str(journal_path) if journal_path else None,
            "chaos": chaos.as_dict() if chaos is not None else None,
            **stats}

        failures = [f"{uniq[i].name} × {wl}: {fr['error']}"
                    for i in range(len(uniq))
                    for wl, fr in errors[i].items()]
        if failures and strict:
            raise RunnerError(f"{len(failures)} cell(s) failed:\n  "
                              + "\n  ".join(failures))

        out = []
        for ui in alias:
            sp = uniq[ui]
            # aggregate in canonical workload order
            ordered = [rows[ui][wl] for wl in wls if wl in rows[ui]]
            res: Dict[str, Any] = {
                "name": sp.name,
                "aggregate": aggregate_rows(ordered) if ordered else {},
                "rows": {wl: rows[ui][wl] for wl in wls
                         if wl in rows[ui]},
                "accesses_per_sec": rates[ui],
                "native": native_used[ui],
            }
            if errors[ui]:
                res["errors"] = dict(errors[ui])
            out.append(res)
        return out

    # -- executors ------------------------------------------------------
    def _deadline_for(self, cell_timeout: Optional[float],
                      mon: Optional[StragglerMonitor]) -> Optional[float]:
        """Effective per-cell deadline: the explicit timeout and/or the
        rolling-median adaptive deadline (× safety factor), whichever
        is tighter; None while neither is available (cold start without
        an explicit timeout)."""
        cands = []
        if cell_timeout:
            cands.append(float(cell_timeout))
        if mon is not None:
            dl = mon.deadline()
            if dl is not None:
                cands.append(dl * self.deadline_factor)
        return min(cands) if cands else None

    def _permanent_failure(self, cell: Dict, attempts: int, error: str,
                           tb: str, fault: Optional[str], elapsed: float,
                           outcomes: Dict, stats: Dict) -> None:
        stats["failed"] += 1
        outcomes[(cell["cfg_idx"], cell["wl"])] = {
            "status": "failed",
            "failure": schema_mod.failure_row(
                cell["sp"].name, cell["hash"], cell["wl"], error,
                traceback_text=tb, attempts=attempts,
                duration_s=elapsed, fault=fault)}
        print(f"[runner] cell {cell['sp'].name} × {cell['wl']} FAILED "
              f"permanently after {attempts} attempt(s): {error}",
              file=sys.stderr)

    def _check_preempt(self, preempt: Optional[PreemptionHandler],
                       outcomes: Dict, journal_path: Optional[Path],
                       n_cells: int) -> None:
        if preempt is not None and preempt.should_stop:
            done = sum(1 for oc in outcomes.values()
                       if oc["status"] == "ok")
            hint = (f"; resume from {journal_path}" if journal_path
                    else "")
            raise RunnerInterrupted(
                f"preempted after {done}/{n_cells} cells{hint}",
                journal_path=journal_path, done=done, total=n_cells)

    def _execute_serial(self, cells: List[Dict], common: Tuple,
                        retries: int, chaos: Optional[FaultSpec],
                        outcomes: Dict, on_ok: Callable,
                        preempt: Optional[PreemptionHandler],
                        stats: Dict, journal_path: Optional[Path],
                        n_cells: int) -> None:
        """In-process execution with the same retry/failure-row
        semantics as the pool (deadlines cannot preempt the current
        process, so hangs are only reaped under the pool path)."""
        scale, engine, native = common
        for cell in cells:
            attempt = 0
            while True:
                self._check_preempt(preempt, outcomes, journal_path,
                                    n_cells)
                if attempt:
                    time.sleep(backoff_delay(self.backoff_s, attempt,
                                             cell["key"]))
                task = (cell["key"], cell["wl"], scale, engine, native,
                        cell["sp"], attempt, chaos)
                t0 = time.monotonic()
                error = tb = fault = None
                try:
                    row, rate, nat, _dt = _run_cell_body(
                        task, in_worker=False)
                    if _row_nonfinite(row):
                        error, fault = ("corrupt row: non-finite "
                                        "metrics"), "corrupt"
                except Exception as e:  # noqa: BLE001 — isolate the cell
                    error = f"{type(e).__name__}: {e}"
                    tb = traceback.format_exc()[-4000:]
                    fault = _fault_kind_of(error)
                elapsed = time.monotonic() - t0
                if error is None:
                    outcomes[(cell["cfg_idx"], cell["wl"])] = {
                        "status": "ok", "row": row, "rate": rate,
                        "native": nat, "attempts": attempt + 1}
                    on_ok(cell, row, rate, nat, attempt + 1)
                    break
                attempt += 1
                if attempt > retries:
                    self._permanent_failure(cell, attempt, error,
                                            tb or "", fault, elapsed,
                                            outcomes, stats)
                    break
                stats["retried"] += 1
            if self.progress:
                print(f"[runner] {len(outcomes)}/{n_cells} cells done",
                      file=sys.stderr)

    def _execute_batched(self, cells: List[Dict], common: Tuple,
                         retries: int, chaos: Optional[FaultSpec],
                         outcomes: Dict, on_ok: Callable,
                         preempt: Optional[PreemptionHandler],
                         stats: Dict, journal_path: Optional[Path],
                         n_cells: int) -> None:
        """One vmapped jax device program per shape bucket, scanning
        every workload of the campaign side by side, instead of one
        process per cell.

        The journal cell identity (``config_hash`` × workload) stays
        the unit of resume: every lane of a batch lands as its own
        journal row via the shared ``on_ok``, and chaos/retry are
        consulted per cell per attempt — a cell whose fault schedule
        fires this attempt is excluded from the batch and retried on
        the next round, exactly as a pool worker crash would be.
        """
        from repro.core import engine_jax
        scale, _engine, _native = common
        # one layout for every trace of the campaign: each shape bucket
        # compiles once, not once per workload
        caps = self.batched_caps or engine_jax.Caps.cover(
            [engine_jax.Caps.of(_get_trace(wl, scale))
             for wl in sorted({cell["wl"] for cell in cells})])
        remaining: List[Dict] = [
            {"cell": cell, "attempt": 0} for cell in cells]
        while remaining:
            self._check_preempt(preempt, outcomes, journal_path, n_cells)
            attempt_max = max(r["attempt"] for r in remaining)
            if attempt_max:
                time.sleep(max(backoff_delay(self.backoff_s,
                                             r["attempt"],
                                             r["cell"]["key"])
                               for r in remaining))
            # chaos gate: a cell whose schedule injects a fault this
            # attempt errors out of the batch (catchable on the
            # coordinator — in_worker=False degrades oom/hang)
            runnable: List[Tuple[Dict, Optional[str]]] = []
            errored: List[Tuple[Dict, str, str]] = []
            for rec in remaining:
                key = rec["cell"]["key"]
                try:
                    fault = chaos.inject(key, rec["attempt"],
                                         in_worker=False) \
                        if chaos is not None else None
                    runnable.append((rec, fault))
                except Exception as e:  # noqa: BLE001 — isolate the cell
                    errored.append((rec, f"{type(e).__name__}: {e}",
                                    traceback.format_exc()[-4000:]))
            # one run_batch for the round: lanes grouped by shape bucket,
            # each lane scanning its own workload's trace
            if runnable:
                self._run_lanes(runnable, scale, caps, chaos, outcomes,
                                on_ok, errored)
            remaining = []
            for rec, error, tb in errored:
                rec["attempt"] += 1
                if rec["attempt"] > retries:
                    self._permanent_failure(
                        rec["cell"], rec["attempt"], error, tb,
                        _fault_kind_of(error), 0.0, outcomes, stats)
                else:
                    stats["retried"] += 1
                    remaining.append(rec)

    def _run_lanes(self, group: List[Tuple[Dict, Optional[str]]],
                   scale: float, caps: Any, chaos: Optional[FaultSpec],
                   outcomes: Dict, on_ok: Callable,
                   errored: List[Tuple[Dict, str, str]]) -> None:
        """Run one batch of cells on the jax engine; a failed batch
        lands every cell in ``errored`` for the retry round."""
        from repro.core import engine_jax
        trs = [_get_trace(rec["cell"]["wl"], scale) for rec, _ in group]
        t0 = time.monotonic()
        try:
            outs = engine_jax.run_batch(
                [rec["cell"]["sp"] for rec, _ in group], trs, caps=caps)
        except engine_jax.JaxEngineError:
            # an overflowed table or an input outside the engine's
            # envelope is deterministic, not transient: retrying cannot
            # help and a failure row would hide it
            raise
        except Exception as e:  # noqa: BLE001 — retry the batch
            tb = traceback.format_exc()[-4000:]
            errored.extend((rec, f"{type(e).__name__}: {e}", tb)
                           for rec, _ in group)
            return
        wall = max(time.monotonic() - t0, 1e-9)
        # aggregate throughput, attributed per lane
        rate = sum(len(tr["core"]) for tr in trs) / wall
        for (rec, fault), tr, (oi, od) in zip(group, trs, outs):
            cell = rec["cell"]
            row = engine_jax.metrics_from_outputs(
                cell["sp"], tr, oi, od).row()
            if fault == "corrupt":
                row = chaos.corrupt_row(row)
            if _row_nonfinite(row):
                errored.append((rec, "corrupt row: non-finite metrics",
                                ""))
                continue
            outcomes[(cell["cfg_idx"], cell["wl"])] = {
                "status": "ok", "row": row, "rate": rate,
                "native": False, "attempts": rec["attempt"] + 1}
            on_ok(cell, row, rate, False, rec["attempt"] + 1)
        if self.progress:
            print(f"[runner] batched: {len(group)} lanes over "
                  f"{len({id(t) for t in trs})} traces in {wall:.1f}s",
                  file=sys.stderr)

    def _execute_pool(self, cells: List[Dict], common: Tuple,
                      processes: int, retries: int,
                      cell_timeout: Optional[float],
                      chaos: Optional[FaultSpec], outcomes: Dict,
                      on_ok: Callable,
                      preempt: Optional[PreemptionHandler],
                      stats: Dict, journal_path: Optional[Path],
                      n_cells: int) -> None:
        """The resilient spawn pool: per-cell dispatch with trace
        affinity, deadline reaping, crash requeue, retry scheduling."""
        import multiprocessing as mp
        scale, engine, native = common
        ctx = mp.get_context("spawn")
        result_q = ctx.Queue()
        workers: Dict[int, _Worker] = {}
        next_wid = 0
        next_tid = 0
        in_flight: Dict[int, Tuple[int, Dict]] = {}  # tid → (wid, rec)
        mons: Dict[str, StragglerMonitor] = {}

        def spawn() -> _Worker:
            nonlocal next_wid
            wid = next_wid
            next_wid += 1
            tq = ctx.SimpleQueue()
            proc = ctx.Process(target=_pool_worker_main,
                               args=(tq, result_q, wid, os.getpid()),
                               daemon=True)
            proc.start()
            w = _Worker(wid, proc, tq)
            workers[wid] = w
            return w

        def requeue_or_fail(rec: Dict, error: str, tb: str,
                            fault: Optional[str], elapsed: float) -> None:
            rec["attempt"] += 1
            if rec["attempt"] > retries:
                self._permanent_failure(rec["cell"], rec["attempt"],
                                        error, tb, fault, elapsed,
                                        outcomes, stats)
                return
            stats["retried"] += 1
            rec["not_before"] = time.monotonic() + backoff_delay(
                self.backoff_s, rec["attempt"], rec["cell"]["key"])
            pending.append(rec)

        pending: deque = deque(
            {"cell": cell, "attempt": 0, "not_before": 0.0}
            for cell in cells)
        target = len(outcomes) + len(cells)
        n_workers = max(1, min(processes, len(cells)))
        for _ in range(n_workers):
            spawn()

        try:
            while len(outcomes) < target:
                self._check_preempt(preempt, outcomes, journal_path,
                                    n_cells)
                now = time.monotonic()

                # 1. reap dead workers (chaos OOM-kill, real crashes)
                for wid in [w for w, h in workers.items()
                            if h.proc.exitcode is not None]:
                    h = workers.pop(wid)
                    if h.task is not None:
                        tid, rec = h.task
                        in_flight.pop(tid, None)
                        stats["worker_deaths"] += 1
                        requeue_or_fail(
                            rec, f"worker died mid-cell (exit "
                            f"{h.proc.exitcode})", "", "worker-death",
                            now - h.started)

                # 2. reap overdue cells (hangs) — kill + requeue
                for wid, h in list(workers.items()):
                    if h.task is None:
                        continue
                    tid, rec = h.task
                    dl = self._deadline_for(
                        cell_timeout, mons.get(rec["cell"]["wl"]))
                    if dl is not None and now - h.started > dl:
                        h.proc.kill()
                        h.proc.join(1.0)
                        workers.pop(wid, None)
                        in_flight.pop(tid, None)
                        stats["timeouts"] += 1
                        requeue_or_fail(
                            rec, f"cell deadline exceeded "
                            f"({now - h.started:.2f}s > {dl:.2f}s)", "",
                            "timeout", now - h.started)

                # 3. keep the pool at strength while work remains
                outstanding = len(pending) + len(in_flight)
                while outstanding and len(workers) < min(n_workers,
                                                         outstanding):
                    spawn()

                # 4. dispatch ready cells to idle workers, preferring a
                #    worker that already generated the cell's trace
                ready = [r for r in pending if r["not_before"] <= now]
                for h in workers.values():
                    if h.task is not None or not ready:
                        continue
                    rec = next((r for r in ready
                                if r["cell"]["wl"] in h.traces),
                               ready[0])
                    ready.remove(rec)
                    pending.remove(rec)
                    tid = next_tid
                    next_tid += 1
                    cell = rec["cell"]
                    task = (cell["key"], cell["wl"], scale, engine,
                            native, cell["sp"], rec["attempt"], chaos)
                    h.task = (tid, rec)
                    h.started = time.monotonic()
                    h.traces.add(cell["wl"])
                    in_flight[tid] = (h.wid, rec)
                    h.task_q.put((tid, task))

                # 5. collect one result (short timeout keeps the
                #    reap/dispatch loop responsive)
                try:
                    msg = result_q.get(timeout=0.05)
                except queue_mod.Empty:
                    continue
                tid = msg[2]
                if tid not in in_flight:
                    continue                   # stale (already reaped)
                wid, rec = in_flight.pop(tid)
                h = workers.get(wid)
                if h is not None and h.task is not None \
                        and h.task[0] == tid:
                    h.task = None
                elapsed = time.monotonic() - (h.started if h else now)
                cell = rec["cell"]
                if msg[0] == "ok":
                    _kind, _wid, _tid, row, rate, nat, _dt = msg
                    if _row_nonfinite(row):
                        requeue_or_fail(rec, "corrupt row: non-finite "
                                        "metrics", "", "corrupt",
                                        elapsed)
                        continue
                    mons.setdefault(
                        cell["wl"], StragglerMonitor()
                    ).end_step(elapsed=elapsed)
                    outcomes[(cell["cfg_idx"], cell["wl"])] = {
                        "status": "ok", "row": row, "rate": rate,
                        "native": nat, "attempts": rec["attempt"] + 1}
                    on_ok(cell, row, rate, nat, rec["attempt"] + 1)
                    if self.progress:
                        print(f"[runner] {len(outcomes)}/{target} "
                              f"cells done", file=sys.stderr)
                else:
                    _kind, _wid, _tid, error, tb = msg
                    requeue_or_fail(rec, error, tb,
                                    _fault_kind_of(error), elapsed)
        finally:
            for h in workers.values():
                if h.task is None and h.proc.is_alive():
                    try:
                        h.task_q.put(None)
                    except (OSError, ValueError):
                        pass
            deadline = time.monotonic() + 2.0
            for h in workers.values():
                h.proc.join(max(0.0, deadline - time.monotonic()))
                if h.proc.is_alive():
                    h.proc.kill()
                    h.proc.join(1.0)
            result_q.close()

    # -- the experiment front door -------------------------------------
    def run(self, exp: Experiment, kind: str = "table",
            tool: str = "repro.api", journal_dir: Optional[Path] = None,
            resume: bool = False,
            keep_journal: bool = False) -> Dict[str, Any]:
        """Execute an Experiment; returns a validated ArtifactV1.

        When ``exp.out_dir`` is set the artifact is also written there
        as ``<kind>_<experiment name>.json``.

        Resilience semantics: permanently-failed cells do NOT abort the
        campaign — the artifact is emitted with those cells recorded as
        structured failure rows under ``provenance.failures`` (and the
        affected configs listed in ``result.degraded``); only a
        campaign with zero successful cells raises.  With
        ``journal_dir`` the campaign journals under
        ``<journal_dir>/<spec_hash12>.journal.jsonl`` and
        ``resume=True`` continues a killed run; the journal is removed
        after a fully-successful artifact unless ``keep_journal``.
        """
        # repro: lint-ok[DT002] wall_s baseline only; lands in VOLATILE_PROVENANCE, excluded from fingerprints
        t0 = time.time()
        configs = exp.build_configs()
        spec = exp.as_dict()
        shash = schema_mod.spec_hash(spec)
        journal_path: Optional[Path] = None
        jdir = journal_dir if journal_dir is not None else exp.out_dir
        if jdir is not None:
            journal_path = Path(jdir) / f"{shash[7:19]}.journal.jsonl"
        # the spec's parallelism applies unless the Runner was
        # constructed with an explicit override
        procs = self.processes if self.processes is not None \
            else exp.processes
        results = self.run_configs(configs, workloads=exp.workloads,
                                   scale=exp.scale, engine=exp.engine,
                                   native=exp.native, processes=procs,
                                   strict=False,
                                   journal_path=journal_path,
                                   resume=resume, backend=exp.backend)
        rows = [res["rows"][wl]
                for res in results for wl in exp.workloads
                if wl in res["rows"]]
        if not rows:
            raise RunnerError(
                "every cell failed permanently; no artifact to emit "
                "(see the failure rows printed above)")
        aggregates = {
            res["name"]: {k: v for k, v in res["aggregate"].items()
                          if k != "per_workload"}
            for res in results if res["rows"]}
        # structured failure rows: config value-dedup means aliased
        # results share error dicts — dedup by (config_hash, workload)
        failures: List[Dict[str, Any]] = []
        seen: Set[Tuple[str, str]] = set()
        degraded: Dict[str, List[str]] = {}
        for res in results:
            for wl, fr in res.get("errors", {}).items():
                degraded.setdefault(res["name"], []).append(wl)
                if (fr["config_hash"], wl) not in seen:
                    seen.add((fr["config_hash"], wl))
                    failures.append(fr)
        result: Dict[str, Any] = {"aggregates": aggregates}
        if degraded:
            result["degraded"] = {k: sorted(v)
                                  for k, v in sorted(degraded.items())}
            print(f"[runner] campaign degraded: {len(failures)} cell(s) "
                  f"permanently failed — artifact marks them in "
                  f"result.degraded / provenance.failures",
                  file=sys.stderr)
        from repro.core.native import resolve_engine
        provenance = {
            "tool": tool,
            "engine": exp.engine,
            "engine_resolved": ("jax" if exp.backend == "batched"
                                else resolve_engine(exp.engine)),
            "backend": exp.backend,
            "native_kernel": all(res["native"] for res in results
                                 if res["rows"]),
            "python": sys.version.split()[0],
            # repro: lint-ok[DT002] wall_s is VOLATILE_PROVENANCE — fingerprints exclude it
            "wall_s": round(time.time() - t0, 2),
            # repro: lint-ok[DT002] created_unix is VOLATILE_PROVENANCE — fingerprints exclude it
            "created_unix": int(time.time()),
            # throughput is a measurement of the run, not the result:
            # keeping it out of `result` is what makes a resumed
            # artifact bit-identical to an uninterrupted one
            "accesses_per_sec": {res["name"]: res["accesses_per_sec"]
                                 for res in results},
            "resilience": dict(self.last_stats),
        }
        if failures:
            provenance["failures"] = failures
        art = schema_mod.artifact_v1(kind, spec, rows,
                                     result=result, provenance=provenance)
        art["provenance"]["fingerprint"] = \
            schema_mod.artifact_fingerprint(art)
        if exp.out_dir is not None:
            path = Path(exp.out_dir) / f"{kind}_{exp.name}.json"
            path.parent.mkdir(parents=True, exist_ok=True)
            path.write_text(json.dumps(art, indent=1))
            art["result"]["artifact_path"] = str(path)
        if (journal_path is not None and journal_path.exists()
                and not failures and not keep_journal):
            journal_path.unlink()         # campaign complete: journal
        return art                        # has served its purpose

    # -- serial failure-isolated map (dry-run / plan matrix loops) -----
    def map(self, fn: Callable[..., Dict[str, Any]],
            items: Sequence[Tuple], label: str = "cells",
            retries: int = 0) -> List[Dict[str, Any]]:
        """Apply ``fn(*item)`` serially with failure isolation.

        Cells that must share one process (jax lowering against the
        512-device host platform) cannot fan out; this gives them the
        Runner's progress + isolation + retry semantics.  Returns one
        ``{"status": "ok", "value": …, "attempts": …}`` or ``{"status":
        "error", "item": …, "error": …, "traceback": …, "attempts": …,
        "failure": schema.failure_row}`` per item — the same structured
        failure shape the pool path records, full traceback preserved.
        A SIGTERM/SIGINT stops the loop at the next item boundary
        (processed items keep their on-disk artifacts, so a re-run
        resumes from cache).
        """
        preempt = PreemptionHandler(install=True) if self.preemptible \
            else None
        out: List[Dict[str, Any]] = []
        try:
            for i, item in enumerate(items):
                if preempt is not None and preempt.should_stop:
                    print(f"[runner] {label} preempted after {i}/"
                          f"{len(items)} items; re-run to continue "
                          f"(completed cells are cached)",
                          file=sys.stderr)
                    break
                attempt = 0
                while True:
                    t0 = time.monotonic()
                    try:
                        out.append({"status": "ok",
                                    "value": fn(*item),
                                    "attempts": attempt + 1})
                        break
                    except Exception as e:  # noqa: BLE001 — isolate
                        error = f"{type(e).__name__}: {e}"
                        tb = traceback.format_exc()[-4000:]
                        attempt += 1
                        if attempt > retries:
                            # repro: lint-ok[SC001] internal worker status record, not an artifact row — the canonical failure row is nested under "failure"
                            out.append({
                                "status": "error", "item": repr(item),
                                "error": error, "traceback": tb,
                                "attempts": attempt,
                                "failure": schema_mod.failure_row(
                                    f"{label}[{i}]", "", repr(item),
                                    error, traceback_text=tb,
                                    attempts=attempt,
                                    duration_s=time.monotonic() - t0)})
                            print(f"[runner] {label} {i + 1}/"
                                  f"{len(items)} FAILED after "
                                  f"{attempt} attempt(s): {error}",
                                  file=sys.stderr)
                            break
                        time.sleep(backoff_delay(self.backoff_s,
                                                 attempt, f"{label}:{i}"))
                if self.progress:
                    print(f"[runner] {label} {i + 1}/{len(items)} done",
                          file=sys.stderr)
        finally:
            if preempt is not None:
                preempt.uninstall()
        return out
