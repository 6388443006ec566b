"""One run of one benchmark cell: set-up, the measured window, the check
against the plain reference, and the result line.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a
configuration (``bench/configs/<config>.json``, found through the
``configs`` entry of that name) under a traffic mix
(``bench/traffic/<traffic>.json``), whose loop nests are those of
``traffic/loopnests.py`` or files ``bench/traffic/nests/<name>.py``
(``traffic/generator.py``).  Its per-layer metrics are readers
``bench/metrics/<metric>.py``.  Everything is found by name, so a cell,
mix, configuration or metric is added as files of its own.

The window drives the program's batched simulator entry,
``repro.core.engine_jax.run_batch``, one *campaign* (one call: every
design point of the mix as a lane, over slices of its traces) after the
other, each followed by the ``Metrics`` rows of its real lanes.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

import hermes_ref  # noqa: E402  (bench/ is on sys.path)
from traffic.generator import Traffic, point_label  # noqa: E402

#: jax events of a compile or of a read from the persistent cache
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class BenchError(RuntimeError):
    """The cell cannot be run as its files describe it."""


class CapsExceeded(BenchError):
    """A slice needs more table room than the configuration's caps."""


# ---------------------------------------------------------------------------
# the cell, found by name
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    mix: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: Path

    def reader(self, metric: str) -> Callable:
        """``read(run)`` of ``bench/metrics/<metric>.py``."""
        path = self.root / "bench" / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric.replace('.', '_')}", path)
        if spec is None or not path.is_file():
            raise BenchError(f"metric {metric!r}: no reader at {path}")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    def traffic(self, seed: int) -> Traffic:
        """The cell's mix for ``seed``, with its nest files found under
        the cell's own root."""
        return Traffic(self.mix, seed,
                       self.root / "bench" / "traffic" / "nests")


def _for_cell(metrics: List[Dict], cell: str) -> List[Dict]:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no cell {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    mix_path = root / "bench" / "traffic" / f"{w['traffic']}.json"
    if not mix_path.is_file():
        raise BenchError(f"traffic {w['traffic']!r}: no mix at {mix_path}")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                mix=json.loads(mix_path.read_text()),
                end_to_end=_for_cell(spec["end_to_end"], name),
                per_layer=_for_cell(spec["per_layer"], name), root=root)


# ---------------------------------------------------------------------------
# the program side
# ---------------------------------------------------------------------------
class CompileClock:
    """Seconds and number of jax compiles and persistent-cache reads."""

    def __init__(self) -> None:
        import jax.monitoring
        self.seconds = 0.0
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event in COMPILE_EVENTS:
            self.seconds += duration
            self.events += 1


def program_system(system: Dict[str, Any]):
    """The configuration as the program's ``SystemParams``."""
    from repro.core import params
    return hermes_ref.build_system(params, system)


@dataclasses.dataclass
class Campaign:
    """One ``run_batch`` call of the window and what it returned."""

    t0: float                   # perf_counter at the call
    t1: float                   # after the last lane's Metrics row
    slices: Dict[str, Dict]     # workload -> the slice scanned
    outs: List[tuple]           # per real lane: (oi, od)
    rows: List[Dict]            # per real lane: the Metrics row
    log: Dict[str, Any]         # the campaign's SCAN_LOG record


class Program:
    """The cell's design points on the program's batched entry."""

    def __init__(self, cell: Cell, traffic: Traffic):
        from repro.core import engine_jax
        from repro.sweep.grid import apply_point
        self.ej = engine_jax
        self.traffic = traffic
        base = program_system(cell.config["system"])
        self.sps = [apply_point(base, traffic.points[p],
                                name=point_label(traffic.points[p]))
                    for p, _ in traffic.lanes]
        self.caps = engine_jax.Caps(**cell.config["caps"])
        system = cell.config["system"]
        requesters = system["n_cores"] + bool(system["accel_port"])
        for wl, tr in traffic.traces.items():
            if tr["core"].max() >= requesters:
                raise BenchError(
                    f"{wl}: core {tr['core'].max()} of the trace is beyond "
                    f"the configuration's {requesters} requesters")

    def check_caps(self, slices: Dict[str, Dict]) -> None:
        for wl, sl in slices.items():
            need = self.ej.Caps.of(sl)
            over = {f: (getattr(need, f), getattr(self.caps, f))
                    for f in ("blk", "pg", "n_pc", "mk", "nten")
                    if getattr(need, f) > getattr(self.caps, f)}
            if over:
                raise CapsExceeded(
                    f"{wl} slice at offset {sl['meta']['offset']} needs "
                    + ", ".join(f"{f} {n} > caps {c}"
                                for f, (n, c) in over.items()))

    def campaign(self, annotate: Callable) -> Campaign:
        slices = self.traffic.draw()
        self.check_caps(slices)
        traces = [slices[wl] for _, wl in self.traffic.lanes]
        n_log = len(self.ej.SCAN_LOG)
        t0 = time.perf_counter()
        with annotate("bench.run_batch"):
            outs = self.ej.run_batch(self.sps, traces, caps=self.caps)
        with annotate("bench.metrics_rows"):
            rows = [self.ej.metrics_from_outputs(sp, tr, oi, od).row()
                    for sp, tr, (oi, od) in zip(self.sps, traces, outs)]
        t1 = time.perf_counter()
        logs = self.ej.SCAN_LOG[n_log:]
        if len(logs) != 1:
            raise BenchError(f"a campaign ran {len(logs)} shape buckets, "
                             "not one: its grid varies a structural knob")
        return Campaign(t0, t1, slices, outs, rows, logs[0])


# ---------------------------------------------------------------------------
# the check against the plain reference
# ---------------------------------------------------------------------------
def _bits(x) -> int:
    return int(np.float64(x).view(np.int64))


def mismatches(oi, od, row, ref) -> int:
    """How many numbers of one lane differ from the reference's
    ``(oi, od, row)``: every counter, every double bit for bit, every
    field of the Metrics row."""
    r_oi, r_od, r_row = ref
    n = int(np.count_nonzero(np.asarray(oi) != r_oi))
    n += int(np.count_nonzero(np.asarray(od).view(np.int64)
                              != np.asarray(r_od).view(np.int64)))
    for k in set(row) | set(r_row):
        a, b = row.get(k), r_row.get(k)
        if isinstance(a, float) or isinstance(b, float):
            same = (a is not None and b is not None
                    and _bits(a) == _bits(b))
        else:
            same = a == b
        n += not same
    return n


def reference_points(cell: Cell, traffic: Traffic) -> list:
    base = hermes_ref.system_params(cell.config["system"])
    return [hermes_ref.apply_point(base, traffic.points[p],
                                   point_label(traffic.points[p]))
            for p, _ in traffic.lanes]


def distinct_lanes(campaign: Campaign) -> int:
    """How many different results (counters and doubles) the real lanes
    of a campaign returned."""
    return len({np.asarray(oi).tobytes() + np.asarray(od).tobytes()
                for oi, od in campaign.outs})


def min_distinct_lanes(cell: Cell, traffic: Traffic) -> int:
    """The fewest distinct lane results a campaign of the cell may
    return: the mix's ``min_distinct_lanes``, else 2 where it has two
    lanes or more.  A grid whose knobs do not act on its slices returns
    equal lanes, and equal lanes make a comparison that cannot tell one
    lane's results from another's."""
    return int(cell.mix.get("min_distinct_lanes", min(2, len(traffic.lanes))))


def check(cell: Cell, traffic: Traffic,
          campaigns: List[Campaign]) -> Dict[str, int]:
    """Compare every real lane of ``campaigns`` with the reference run
    on the same slice and design point."""
    ref_sps = reference_points(cell, traffic)
    lanes = bad = numbers = 0
    for c in campaigns:
        for (_, wl), sp, (oi, od), row in zip(traffic.lanes, ref_sps,
                                              c.outs, c.rows):
            ref = hermes_ref.run(sp, c.slices[wl])
            n = mismatches(oi, od, row, ref)
            lanes += 1
            bad += n > 0
            numbers += n
    return {"lanes": lanes, "bad_lanes": bad, "mismatched": numbers,
            "distinct_lanes": min(map(distinct_lanes, campaigns))}


def control_check(cell: Cell, traffic: Traffic,
                  campaigns: List[Campaign]) -> Dict[str, int]:
    """The control: the reference in float32, put in the program's place
    and compared with the reference as the program is."""
    ref_sps = reference_points(cell, traffic)
    swapped = []
    for c in campaigns:
        outs, rows = [], []
        for (_, wl), sp in zip(traffic.lanes, ref_sps):
            oi, od, row = hermes_ref.run(sp, c.slices[wl], real=np.float32)
            outs.append((oi, od))
            rows.append(row)
        swapped.append(dataclasses.replace(c, outs=outs, rows=rows))
    return check(cell, traffic, swapped)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class RunRecord:
    """What the per-layer readers read."""

    cell: Cell
    campaigns: List[Campaign]             # the window
    traced: List[Campaign]                # those the profiler recorded
    setup_compile_s: float
    setup_compile_events: int
    profile: Any = None                   # profile_reduce.Reduced
    peaks: Optional[Dict[str, Any]] = None


def device_info(chips: int) -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(f"no TPU with {chips} chip(s): jax finds "
                         f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def peaks_for(kind: str, root: Path = ROOT) -> Dict[str, Any]:
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


def no_annotation(name):
    return contextlib.nullcontext()


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float) -> Dict[str, Any]:
    """Set up, measure, check; returns the result object.  ``t_start``
    is the ``perf_counter`` reading at process start."""
    import jax
    clock = CompileClock()
    device = device_info(cell.chips)
    traffic = cell.traffic(seed)
    program = Program(cell, traffic)

    # set-up: one campaign at the cell's shapes compiles the scan and the
    # export or reads them from the persistent cache
    program.campaign(no_annotation)
    setup_compile = (clock.seconds, clock.events)

    # the window; with tracing, the profiler records its first campaign
    # only (a scan step runs thousands of device operations, so a whole
    # window would be a trace of gigabytes)
    window: List[Campaign] = []
    w0 = time.perf_counter()
    if trace:
        trace_dir = Path(tempfile.mkdtemp(prefix="bench_trace_"))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        span = jax.profiler.TraceAnnotation
        with span("bench.window"), span("bench.campaign"):
            window.append(program.campaign(span))
        jax.profiler.stop_trace()
    while not window or window[-1].t1 - w0 < seconds:
        window.append(program.campaign(no_annotation))
    compiled_in_window = clock.events - setup_compile[1]
    if compiled_in_window:
        raise BenchError(f"{compiled_in_window} compiles or cache reads "
                         "inside the measured window")
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))

    record = RunRecord(cell, window, window[:1] if trace else [],
                       *setup_compile)
    if trace:
        import profile_reduce
        try:
            record.profile = profile_reduce.reduce_profile(
                profile_reduce.load(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        record.peaks = peaks_for(device["kind"], cell.root)
        device["busy_s"] = record.profile.busy_s
        device["window_s"] = record.profile.window_s

    accesses = sum(c.log["accesses"] for c in window)
    wall = window[-1].t1 - window[0].t0
    metrics: Dict[str, Dict[str, Any]] = {}
    if trace:
        for m in cell.per_layer:
            v = cell.reader(m["name"])(record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = {"sim_accesses_per_s": accesses / wall,
               "setup_s": window[0].t0 - t_start}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    # the program's state is gone (results are host arrays); the
    # reference runs now, after the window and the memory reading
    got = check(cell, traffic, window)
    need_distinct = min_distinct_lanes(cell, traffic)
    result = {"correct": (got["mismatched"] == 0
                          and got["distinct_lanes"] >= need_distinct),
              "attempted": got["lanes"], "failed": got["bad_lanes"],
              "metrics": metrics, "device": device}
    if trace:
        p = record.profile
        result["breakdown"] = {
            "device_ops": [[n, ns * 1e-9] for n, ns in p.ops],
            "idle_gaps": [[n, ns * 1e-9] for n, ns in p.idle_gaps]}
    result["window"] = {
        "campaigns": len(window), "accesses": accesses, "seconds": wall,
        "lanes": len(program.sps), "slice": traffic.slice,
        "campaign_s": [c.t1 - c.t0 for c in window],
        "scan_s": [c.log["scan_s"] for c in window]}
    # the numbers compared, each beside its limit, as the last key
    result["checks"] = {
        "mismatched_values": {"value": got["mismatched"], "limit": 0,
                              "holds": "<="},
        "mismatched_lanes": {"value": got["bad_lanes"], "limit": 0,
                             "holds": "<="},
        "distinct_lanes": {"value": got["distinct_lanes"],
                           "limit": need_distinct, "holds": ">="}}
    return result


def emit(result: Dict[str, Any]) -> None:
    """The checks as the last lines of stderr, the result as the last
    line of stdout."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['holds']} "
              f"{c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
