"""CPU tests of the benchmark under ``bench/``: the trace reduction, the
work count of the roofline, finding a cell by name, the caps guard, the
reference against the program's own host engine, the control, and the
faults that must make ``correct`` false.

Importing this file loads no jax and no TPU library: every test imports
what it needs itself.
"""

from __future__ import annotations

import hashlib
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = Path(__file__).resolve().parent / "data"
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------
def test_interval_union_and_gaps_hand_counted():
    import profile_reduce as pr
    ivs = [(10, 20), (15, 30), (40, 50), (50, 55), (70, 60), (80, 90)]
    merged = pr.merge(ivs)
    assert merged == [(10, 30), (40, 55), (80, 90)]
    assert pr.total(merged) == 20 + 15 + 10
    assert pr.clip(merged, 25, 85) == [(25, 30), (40, 55), (80, 85)]
    assert pr.gaps(pr.clip(merged, 0, 100), 0, 100) == [
        (0, 10), (30, 40), (55, 80), (90, 100)]


class _Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Profile:
    def __init__(self, planes):
        self.planes = planes


def test_reduce_profile_hand_built():
    """Busy time is the union of the device's program runs inside the
    window span; markers and the ops inside programs do not count; gaps
    carry the innermost host span."""
    import profile_reduce as pr
    host = _Plane("/host:CPU", [_Line("python", [
        _Ev("bench.window", 100, 1000),
        _Ev("bench.campaign", 100, 600),
        _Ev("bench.run_batch", 100, 300),
        _Ev("bench.metrics_rows", 400, 300),
        _Ev("unrelated", 0, 5000)])])
    dev = _Plane("/device:TPU:0", [
        _Line("Steps", [_Ev("step", 0, 5000)]),
        _Line("XLA Ops", [_Ev("fusion.9", 0, 5000)]),
        _Line("XLA Modules", [_Ev("jit_scan(1)", 50, 100),   # from 100
                              _Ev("jit_gather(7)", 120, 80),  # overlaps
                              _Ev("jit_export(3)", 300, 100),
                              _Ev("jit_scan(2)", 900, 400)])])  # to 1100
    r = pr.reduce_profile(_Profile([host, dev]))
    assert r.window == (100, 1100)
    assert r.busy_ns == [100 + 100 + 200]
    assert r.busy_s == pytest.approx(400e-9)
    assert r.window_s == pytest.approx(1000e-9)
    assert dict(r.ops) == {"jit_scan": 50 + 200, "jit_gather": 80,
                           "jit_export": 100}
    # gaps: 200-300 (inside run_batch), 400-900 (its midpoint 650 lies
    # in metrics_rows, the innermost span there)
    assert r.idle_gaps == [("bench.metrics_rows", 500),
                           ("bench.run_batch", 100)]


def test_reduce_recorded_chip_trace():
    """A trace recorded on a TPU v5e (``small.xplane.pb``): three runs
    of one jitted program of 3,573, 3,732 and 3,711 ns, each inside a
    ``bench.run_batch`` span and followed by a 3 ms host sleep inside
    ``bench.metrics_rows``, all inside one ``bench.window`` span of
    15,536,590 ns."""
    import profile_reduce as pr
    r = pr.reduce_profile(pr.load(DATA))
    assert r.window == (40773660.0, 40773660.0 + 15536590.0)
    assert r.busy_ns == [3573.0 + 3732.0 + 3711.0]
    assert r.ops == [("jit__lambda", 3573.0 + 3732.0 + 3711.0)]
    names = [n for n, _ in r.idle_gaps]
    assert names.count("bench.metrics_rows") == 3
    assert len(r.idle_gaps) == 4     # before, between and after the runs
    assert sum(ns for _, ns in r.idle_gaps) == pytest.approx(
        15536590.0 - r.busy_ns[0])


# ---------------------------------------------------------------------------
# the work count of scan_roofline
# ---------------------------------------------------------------------------
def test_work_count_hand_counted():
    import workcount
    system = {"n_cores": 4, "accel_port": True,
              "l1": {"assoc": 8}, "l2": {"assoc": 8}, "l3": {"assoc": 16}}
    oi = np.zeros(98, np.int64)
    oi[26:31] = [10, 0, 0, 0, 5]       # L1 hits per requester
    oi[34:39] = [2, 1, 0, 0, 3]        # L1 misses
    oi[50:55] = [1, 0, 0, 0, 0]        # L2 hits
    oi[58:63] = [1, 1, 0, 0, 3]        # L2 misses
    oi[23], oi[24] = 2, 3              # L3 hits, misses
    assert workcount.probes(oi, 5) == {"l1": 21, "l2": 6, "l3": 5}
    steps, traces = 7, 2
    want = (steps * traces * 66
            + 21 * 9 * 8 + 6 * 9 * 8 + 5 * 17 * 8)
    assert workcount.campaign_bytes(system, steps, traces, [oi]) == want
    # no shared level: its probes are not counted
    assert workcount.campaign_bytes(dict(system, l3=None), steps, traces,
                                    [oi, oi]) == (
        steps * traces * 66 + 2 * (21 * 9 * 8 + 6 * 9 * 8))


# ---------------------------------------------------------------------------
# cells, configurations and mixes found by name
# ---------------------------------------------------------------------------
def _digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def _tmp_root(tmp_path: Path) -> Path:
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def _add_cell(root: Path, cell: str, config: dict, mix: dict,
              metric_src: str = None) -> None:
    """Add a cell as data: a configuration file, a mix file, optionally a
    metric reader, and entries appended to BENCHMARK.json."""
    (root / "bench/configs" / f"{cell}_cfg.json").write_text(
        json.dumps(config))
    (root / "bench/traffic" / f"{cell}_mix.json").write_text(json.dumps(mix))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": f"{cell}_cfg", "source": "x",
                            "file": f"bench/configs/{cell}_cfg.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": cell, "config": f"{cell}_cfg",
                              "traffic": f"{cell}_mix", "chips": 1,
                              "why": "x"})
    if metric_src is not None:
        (root / "bench/metrics" / "lanes_seen.py").write_text(metric_src)
        spec["per_layer"].append({
            "name": "lanes_seen", "unit": "lanes", "better": "higher",
            "source": "program_counter", "layer": "device scan",
            "moves": "sim_accesses_per_s", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def test_cell_added_as_data_is_found_by_name(tmp_path):
    import harness
    from traffic.generator import Traffic
    root = _tmp_root(tmp_path)
    before = _digest(root)
    config = json.loads(
        (BENCH / "configs/hermes_baseline.json").read_text())
    mix = {"workloads": ["rnn", "cnn"], "slice": 128,
           "grid": {"l1.hit_latency": [3, 4], "l2.hit_latency": [9, 11, 13]}}
    _add_cell(root, "new.cell", config, mix,
              "def read(run):\n    return float(len(run.campaigns))\n")
    after = _digest(root)
    changed = {k for k in before if after[k] != before[k]}
    assert changed == {"BENCHMARK.json"}
    cell = harness.load_cell("new.cell", root)
    assert cell.config == config and cell.mix == mix
    assert [m["name"] for m in cell.end_to_end] == [
        "sim_accesses_per_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["lanes_seen"]
    assert cell.reader("lanes_seen")(
        harness.RunRecord(cell, [1, 2, 3], [], 0.0, 0)) == 3.0
    traffic = Traffic(cell.mix, seed=2**31 + 11)
    assert len(traffic.lanes) == 12
    assert traffic.lanes[:3] == [(0, "rnn"), (0, "cnn"), (1, "rnn")]
    first = traffic.draw()
    again = Traffic(cell.mix, seed=2**31 + 11).draw()
    for wl in ("rnn", "cnn"):
        assert len(first[wl]["addr"]) == 128
        assert first[wl]["meta"]["offset"] == again[wl]["meta"]["offset"]
        np.testing.assert_array_equal(first[wl]["addr"], again[wl]["addr"])


def test_committed_cells_load():
    import harness
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.per_layer and len(cell.end_to_end) == 2
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]))


@pytest.mark.parametrize("name,preset", [
    ("hermes_tensor_aware", "tensor_aware"), ("hermes_baseline", "baseline")])
def test_config_files_state_the_presets(name, preset):
    import dataclasses
    import harness
    import hermes_ref
    from repro.core import presets
    config = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    sp = harness.program_system(config["system"])
    assert sp == presets.PRESETS[preset]
    ref = hermes_ref.system_params(config["system"])
    assert dataclasses.asdict(ref) == dataclasses.asdict(sp)


def test_slice_beyond_caps_is_refused():
    import harness
    from traffic.generator import Traffic
    cell = harness.load_cell("base.sweep32.cnn")
    cell.config = dict(cell.config, caps=dict(cell.config["caps"], blk=1024))
    traffic = Traffic(cell.mix, seed=7)
    program = harness.Program(cell, traffic)
    with pytest.raises(harness.CapsExceeded, match="blk"):
        program.check_caps(traffic.draw())
    # at the file's caps the same slices pass
    harness.Program(harness.load_cell("base.sweep32.cnn"),
                    traffic).check_caps(Traffic(cell.mix, seed=7).draw())


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------
def test_reference_imports_nothing_of_the_program():
    for path in (BENCH / "hermes_ref").glob("*.py"):
        for line in path.read_text().splitlines():
            if line.startswith(("import ", "from ")):
                assert "repro" not in line, f"{path.name}: {line}"


@pytest.mark.parametrize("config", ["hermes_tensor_aware", "hermes_baseline"])
def test_reference_equals_the_c_kernel(config):
    """The reference copy and its export equal the program's C kernel on
    slices of every workload: its counters, doubles and Metrics rows."""
    import harness
    import hermes_ref
    from repro.core import native
    from repro.core.simulator import simulate
    from traffic.generator import Traffic
    if native.get_lib() is None:
        pytest.skip("the C kernel does not build here")
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    traffic = Traffic({"workloads": ["cnn", "rnn", "transformer"],
                       "slice": 1500}, seed=41)
    prog_sp = harness.program_system(cfg["system"])
    ref_sp = hermes_ref.system_params(cfg["system"])
    for wl, sl in traffic.draw().items():
        oi, od, row = hermes_ref.run(ref_sp, sl)
        c_oi, c_od = native.run_arrays(prog_sp, sl)
        np.testing.assert_array_equal(oi, c_oi)
        np.testing.assert_array_equal(od.view(np.int64),
                                      c_od.view(np.int64))
        assert row == simulate(prog_sp, sl, engine="soa").row()


def _reference_campaigns(cell, traffic, n):
    import harness
    import hermes_ref
    sps = harness.reference_points(cell, traffic)
    out = []
    for _ in range(n):
        slices = traffic.draw()
        res = [hermes_ref.run(sp, slices[wl])
               for sp, (_, wl) in zip(sps, traffic.lanes)]
        out.append(harness.Campaign(0.0, 0.0, slices,
                                    [(oi, od) for oi, od, _ in res],
                                    [row for *_, row in res], {}))
    return out


@pytest.mark.parametrize("seed", [5, 2**31 + 3, 3_000_000_019])
def test_control_reads_not_correct(seed):
    """The control, the reference in float32 in the program's place,
    fails the comparison that the reference in float64 passes."""
    import harness
    from traffic.generator import Traffic
    cell = harness.load_cell("ta.table.3wl")
    cell.mix = dict(cell.mix, slice=400)
    traffic = Traffic(cell.mix, seed)
    camps = _reference_campaigns(cell, traffic, 1)
    assert harness.check(cell, traffic, camps)["mismatched"] == 0
    ctl = harness.control_check(cell, traffic, camps)
    assert ctl["mismatched"] > 0 and ctl["bad_lanes"] == 3


# ---------------------------------------------------------------------------
# a whole run with the timed path broken underneath
# ---------------------------------------------------------------------------
def _tiny_cell(tmp_path, config="hermes_baseline", grid=None):
    import harness
    root = _tmp_root(tmp_path)
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    cfg["caps"] = {"blk": 4096, "pg": 4096, "n_pc": 22, "mk": 4096,
                   "nten": 6}
    _add_cell(root, "tiny", cfg,
              {"workloads": ["cnn"], "slice": 48,
               "grid": grid or {"l1.hit_latency": [3, 4],
                                "l2.hit_latency": [10, 14]}})
    return harness.load_cell("tiny", root)


def _on_a_chip(monkeypatch):
    """The run's look for a chip, answered as a one-chip TPU would."""
    import harness
    monkeypatch.setattr(harness, "device_info", lambda chips: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": chips})


def _half_batch(run_batch):
    def broken(sps, traces, caps=None):
        half = len(sps) // 2
        outs = run_batch(sps[:half], traces[:half], caps=caps)
        return outs + outs[:len(sps) - half]
    return broken


def _state_unchanged(S):
    return lambda consts, cfg, st, x: st


def _altered_export(export):
    def broken(S, st):
        oi, od, flags = export(S, st)
        return oi.at[1].add(1), od, flags
    return broken


@pytest.mark.parametrize("fault", [None, "state_unchanged", "half_batch",
                                   "altered_answer"])
def test_faults_make_correct_false(tmp_path, monkeypatch, fault):
    import harness
    from repro.core import engine_jax as ej
    cell = _tiny_cell(tmp_path)
    _on_a_chip(monkeypatch)
    monkeypatch.setattr(ej, "_COMPILED", {})
    if fault == "state_unchanged":
        monkeypatch.setattr(ej, "_make_step", _state_unchanged)
    elif fault == "half_batch":
        monkeypatch.setattr(ej, "run_batch", _half_batch(ej.run_batch))
    elif fault == "altered_answer":
        monkeypatch.setattr(ej, "_export_arrays",
                            _altered_export(ej._export_arrays))
    res = harness.run(cell, 2**31 + 5, 0.2, False, time.perf_counter())
    assert res["attempted"] >= 4
    assert res["correct"] is (fault is None)
    if fault is not None:
        assert res["checks"]["mismatched_values"]["value"] > 0


@pytest.mark.parametrize("fault", [None, "half_batch"])
def test_half_batch_fault_in_tensor_aware_cell(tmp_path, monkeypatch, fault):
    """The tensor-aware cell's kind of grid (shared-L3 latency x stride
    confidence): its lanes differ, so lanes that carry the other half's
    results fail the comparison."""
    import harness
    from repro.core import engine_jax as ej
    cell = _tiny_cell(tmp_path, "hermes_tensor_aware",
                      {"l3.hit_latency": [34, 42],
                       "prefetch.stride_confidence": [1, 2]})
    _on_a_chip(monkeypatch)
    monkeypatch.setattr(ej, "_COMPILED", {})
    if fault == "half_batch":
        monkeypatch.setattr(ej, "run_batch", _half_batch(ej.run_batch))
    res = harness.run(cell, 2**31 + 9, 0.2, False, time.perf_counter())
    assert res["attempted"] >= 4
    assert res["correct"] is (fault is None)
    distinct = res["checks"]["distinct_lanes"]
    if fault is None:
        assert distinct["value"] == 4
    else:
        assert distinct["value"] < 4
        assert res["checks"]["mismatched_lanes"]["value"] >= 2


def test_equal_lanes_make_correct_false(tmp_path, monkeypatch):
    """A grid whose knobs do not act on its slices (the shared L3's
    tensor-policy ranks, which a cold slice never reaches) returns equal
    lanes: the run is not correct, although each lane equals the
    reference."""
    import harness
    from repro.core import engine_jax as ej
    cell = _tiny_cell(tmp_path, "hermes_tensor_aware",
                      {"ta.prefetch_rank": [1.5, 5.0],
                       "ta.low_utility": [0.05, 0.3]})
    _on_a_chip(monkeypatch)
    monkeypatch.setattr(ej, "_COMPILED", {})
    res = harness.run(cell, 2**31 + 9, 0.2, False, time.perf_counter())
    checks = res["checks"]
    assert checks["mismatched_values"]["value"] == 0
    assert checks["distinct_lanes"] == {"value": 1, "limit": 2,
                                        "holds": ">="}
    assert res["correct"] is False


@pytest.mark.parametrize("name", ["ta.sweep32.cnn", "base.sweep32.cnn",
                                  "ta.table.3wl"])
def test_committed_grids_give_distinct_lanes(name):
    """On a slice of the cell's own size the reference gives at least
    the mix's ``min_distinct_lanes`` different lane results, and half
    the lanes carrying the other half's results give fewer."""
    import dataclasses
    import harness
    from traffic.generator import Traffic
    cell = harness.load_cell(name)
    traffic = Traffic(cell.mix, seed=2**31 + 17)
    need = harness.min_distinct_lanes(cell, traffic)
    camp = _reference_campaigns(cell, traffic, 1)[0]
    assert harness.distinct_lanes(camp) >= need
    half = len(camp.outs) // 2
    halved = camp.outs[:half] + camp.outs[:len(camp.outs) - half]
    assert harness.distinct_lanes(
        dataclasses.replace(camp, outs=halved)) < need
