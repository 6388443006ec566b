"""CPU tests of the benchmark under ``bench/``: the trace reduction, the
work count of the roofline, finding a cell by name, the caps guard, the
reference against the program's own host engine, the control, and the
faults that must make ``correct`` false.

Importing this file loads no jax and no TPU library: every test imports
what it needs itself.
"""

from __future__ import annotations

import functools
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
              metric_src: str = None, nests: dict = None) -> None:
    """Add a cell as data: a configuration file, a mix file, optionally a
    metric reader and nest files (``{name: source}``), and entries
    appended to BENCHMARK.json."""
    for name, src in (nests or {}).items():
        (root / "bench/traffic/nests" / f"{name}.py").write_text(src)
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


# the traffic of the committed cells, pinned: sha256 (first 16 hex
# digits) of every column of the paper workloads' traces at scale 1.0 as
# the mix generator seeds them, and of their name and meta; and the first
# three slice offsets of each committed mix
_TRACE_SHA = {
    ("cnn", 7): dict(
        core="6a545245ad8798f6", pc="dbc303f9323c6e0b",
        addr="3ae7eca778f62f51", write="0a4529dc77fe8b2d",
        tensor="b396da5ea88abacb", reuse="dd950a9fc9b7944e",
        meta="24cc6c061d759e74"),
    ("rnn", 7): dict(
        core="ad3117c8925778e9", pc="893f743b43148d40",
        addr="e31138e356a4ccb9", write="c2daa2e94109ce48",
        tensor="5dd5581eb13c381c", reuse="52bf0402f1b6b7b0",
        meta="717cb9e8ee1ae03b"),
    ("transformer", 7): dict(
        core="a18a7e1441b83a60", pc="9106c148153050ae",
        addr="74afed72240d00d5", write="429d87bfc30025f9",
        tensor="7e13c86304589032", reuse="4007d1619145f6d5",
        meta="f1f3e9d730b92662"),
    ("cnn", 2**31 + 17): dict(
        core="c192d649d117ac36", pc="32022b12785204d4",
        addr="186cd69375d3094e", write="3de55daf573bc3ff",
        tensor="f1123bd016909436", reuse="d1c24163724ea0c0",
        meta="24cc6c061d759e74"),
    ("rnn", 2**31 + 17): dict(
        core="bb0a4103a308c393", pc="d20878a76a27b595",
        addr="2c511a6b64905549", write="b2e79163ff930266",
        tensor="22a890acf43f1139", reuse="471fa34d40670625",
        meta="717cb9e8ee1ae03b"),
    ("transformer", 2**31 + 17): dict(
        core="9a79a462b075e6b7", pc="60fa9d776c191442",
        addr="1266541db1fa8695", write="e3c7d22990ded61b",
        tensor="7065cacb8b9db1e2", reuse="d1921c4284461ec2",
        meta="f1f3e9d730b92662"),
}
_FIRST_OFFSETS = {
    ("stride_l3_grid32.cnn", 7): [
        {"cnn": 656475}, {"cnn": 434286}, {"cnn": 475336}],
    ("stride_l3_grid32.cnn", 2**31 + 17): [
        {"cnn": 7095}, {"cnn": 20228}, {"cnn": 420523}],
    ("lat_grid32.cnn", 7): [
        {"cnn": 652726}, {"cnn": 431806}, {"cnn": 472621}],
    ("lat_grid32.cnn", 2**31 + 17): [
        {"cnn": 7054}, {"cnn": 20112}, {"cnn": 418122}],
    ("table.3wl", 7): [
        {"cnn": 656112, "rnn": 540938, "transformer": 550201},
        {"cnn": 622997, "rnn": 500436, "transformer": 623788},
        {"cnn": 578861, "rnn": 194887, "transformer": 44657}],
    ("table.3wl", 2**31 + 17): [
        {"cnn": 7091, "rnn": 25195, "transformer": 486756},
        {"cnn": 400663, "rnn": 109330, "transformer": 674415},
        {"cnn": 446698, "rnn": 767104, "transformer": 134463}],
}
_PINNED = sorted(
    [("trace", wl, seed, col, sha)
     for (wl, seed), cols in _TRACE_SHA.items() for col, sha in cols.items()],
    key=lambda case: case[2]) + [
    ("offsets", mix, seed, None, offs)
    for (mix, seed), offs in _FIRST_OFFSETS.items()]


@functools.lru_cache(maxsize=1)
def _paper_traces(seed: int) -> dict:
    from traffic.generator import Traffic
    return Traffic({"workloads": ["cnn", "rnn", "transformer"], "slice": 1},
                   seed).traces


@pytest.mark.parametrize(
    "kind,name,seed,column,want", _PINNED,
    ids=[f"{k}-{n}-{s}" + (f"-{c}" if c else "") for k, n, s, c, _ in _PINNED])
def test_committed_traffic_is_pinned(kind, name, seed, column, want):
    """The committed cells read the same traces, bit for bit, and the
    same slice offsets for a seed as when their bounds were set."""
    from traffic.generator import Traffic
    if kind == "offsets":
        mix = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
        traffic = Traffic(mix, seed)
        assert [{wl: sl["meta"]["offset"] for wl, sl in traffic.draw().items()}
                for _ in range(3)] == want
        return
    tr = _paper_traces(seed)[name]
    if column == "meta":
        raw = json.dumps([tr["name"], tr["meta"]["n_macro_ops"],
                          [list(t) for t in tr["meta"]["tensors"]]]).encode()
    else:
        raw = np.ascontiguousarray(tr[column]).tobytes()
    assert hashlib.sha256(raw).hexdigest()[:16] == want


# a loop nest in a file of its own: two tensors, a few hundred accesses;
# ``fault`` is a statement that spoils the finished trace ``tr``
_TOY_NEST = """
import numpy as np
from traffic.loopnests import (GEMMINI, REUSE_RESIDENT, REUSE_STREAMING,
                               Builder, finish)

SEED_OFFSET = {offset}


def trace(scale, seed, tokens=4):
    b = Builder("toy_decode", seed)
    rows = max(8, int(64 * scale))
    w_id, w_base = b.alloc.tensor(rows * 64, REUSE_RESIDENT)
    x_id, x_base = b.alloc.tensor(tokens << 12, REUSE_STREAMING)
    for core in range(2):
        b.add(core, 10 + core, w_id, REUSE_RESIDENT, False,
              b.walk(w_base, rows * 64, reps=tokens))
    b.add(0, 12, w_id, REUSE_RESIDENT, False,
          b.hot(w_base, rows * 64, 32 * tokens))
    b.add(GEMMINI, 20, x_id, REUSE_STREAMING, True,
          b.stream(x_base, 16 * tokens))
    b.n_macro = tokens
    tr = finish(b)
    {fault}
    return tr
"""


def _toy_nest(offset: int = 3, fault: str = "pass") -> str:
    return _TOY_NEST.format(offset=offset, fault=fault)


def test_nest_file_added_as_data_is_found_by_name(tmp_path):
    """A cell whose loop nest is a new file under ``traffic/nests/``
    loads, draws slices and passes the caps guard, with no edit to any
    file but entries appended to BENCHMARK.json; the mix's ``params``
    reach the nest, which is seeded with ``--seed`` plus its
    ``SEED_OFFSET``, while a loop nest of ``loopnests`` keeps its own."""
    import harness
    from traffic import loopnests
    from traffic.generator import trace_function
    root = _tmp_root(tmp_path)
    before = _digest(root)
    config = json.loads(
        (BENCH / "configs/hermes_tensor_aware.json").read_text())
    mix = {"workloads": ["toy_decode", "rnn"], "slice": 256,
           "params": {"toy_decode": {"tokens": 3}},
           "grid": {"l3.hit_latency": [30, 42]}}
    _add_cell(root, "toy.cell", config, mix,
              nests={"toy_decode": _toy_nest()})
    after = _digest(root)
    assert {k for k in before if after[k] != before[k]} == {"BENCHMARK.json"}
    cell = harness.load_cell("toy.cell", root)
    seed = 2**31 + 23
    traffic = cell.traffic(seed)
    toy = traffic.traces["toy_decode"]
    assert len(toy["core"]) == 2 * 64 * 3 + 32 * 3 + 16 * 3
    assert len(toy["meta"]["tensors"]) == 2
    fn, offset = trace_function("toy_decode",
                                root / "bench" / "traffic" / "nests")
    assert offset == 3
    np.testing.assert_array_equal(toy["addr"],
                                  fn(1.0, seed + 3, tokens=3)["addr"])
    assert not np.array_equal(toy["addr"], fn(1.0, seed + 4, tokens=3)["addr"])
    assert len(fn(1.0, seed + 3)["core"]) == 2 * 64 * 4 + 32 * 4 + 16 * 4
    np.testing.assert_array_equal(traffic.traces["rnn"]["addr"],
                                  loopnests.rnn_trace(1.0, seed + 1)["addr"])
    assert traffic.lanes == [(0, "toy_decode"), (0, "rnn"),
                             (1, "toy_decode"), (1, "rnn")]
    program = harness.Program(cell, traffic)
    for _ in range(3):
        slices = traffic.draw()
        assert {wl: len(sl["addr"]) for wl, sl in slices.items()} == {
            "toy_decode": 256, "rnn": 256}
        program.check_caps(slices)


@pytest.mark.parametrize("case,match", [
    ("unknown_name", r"'toy_decod' is neither in loopnests\.WORKLOADS "
                     r"\(cnn, rnn, transformer\) nor a nest file"),
    ("duplicate_offset", r"toy_decode\.py: SEED_OFFSET 3 is also "
                         r"nests/other\.py's"),
    ("offset_of_a_loop_nest", r"toy_decode\.py: SEED_OFFSET must be an int "
                              r"of at least 3, not 2"),
    ("stray_params", "params for toy_decodx, which the mix's workloads"),
    ("tensor_out_of_range", r"toy_decode: tensor ids 0\.\.5 outside the "
                            "table of 2 tensors"),
    ("reuse_out_of_set", r"toy_decode: reuse class 3 is not one of"),
    ("wrong_dtype", "toy_decode: column pc must be a 1-d int32 array, "
                    "not int64"),
    ("core_beyond_requesters", "toy_decode: core 6 of the trace is beyond "
                               "the configuration's 5 requesters"),
])
def test_malformed_traffic_is_refused(tmp_path, case, match):
    """A mix or nest file the simulator cannot take is refused at set-up,
    with a message that names the workload or the file."""
    import harness
    nests = {"toy_decode": _toy_nest(**{
        "offset_of_a_loop_nest": {"offset": 2},
        "tensor_out_of_range": {"fault": 'tr["tensor"][7] = 5'},
        "reuse_out_of_set": {"fault": 'tr["reuse"][7] = 3'},
        "wrong_dtype": {"fault": 'tr["pc"] = tr["pc"].astype(np.int64)'},
        "core_beyond_requesters": {"fault": 'tr["core"][7] = 6'},
    }.get(case, {}))}
    if case == "duplicate_offset":
        nests["other"] = _toy_nest()
    mix = {"workloads": ["toy_decod" if case == "unknown_name"
                         else "toy_decode"], "slice": 64}
    if case == "stray_params":
        mix["params"] = {"toy_decodx": {"tokens": 2}}
    root = _tmp_root(tmp_path)
    _add_cell(root, "bad.cell",
              json.loads((BENCH / "configs/hermes_baseline.json").read_text()),
              mix, nests=nests)
    cell = harness.load_cell("bad.cell", root)
    with pytest.raises((ValueError, harness.BenchError), match=match):
        harness.Program(cell, cell.traffic(5))


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
