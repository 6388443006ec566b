"""CPU tests of ``bench/program_trace.py``, the benchmark's reading of
what the program records of itself: its host spans and the component
scopes of its scan step.  The hand-built profiles use the fakes of
``test_perfbench.py``; ``scoped/`` holds a trace recorded on a TPU v5e
and the HLO text of its program.
"""

from __future__ import annotations

import collections
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
#: a trace recorded on a TPU v5e and its program's HLO text (a directory
#: of its own: ``data/`` holds exactly one trace)
SCOPED = Path(__file__).resolve().parent / "scoped"
for p in (str(BENCH), str(ROOT / "src"), str(Path(__file__).parent)):
    if p not in sys.path:
        sys.path.insert(0, p)

from test_perfbench import (_Ev, _Line, _Plane, _Profile,  # noqa: E402
                            _on_a_chip, _tiny_cell)

HLO = """HloModule jit_scan, is_scheduled=true

%body (p: s64[]) -> s64[] {
  %fusion.2 = s64[2]{0} fusion(%p), kind=kLoop, calls=%f.2, metadata={op_name="jit(scan)/while/body/hermes.l1/add" stack_frame_id=3}
  %copy.3 = s64[2]{0} copy(%fusion.2)
  %fusion.4 = s64[2]{0} fusion(%copy.3), kind=kLoop, calls=%f.4, metadata={op_name="jit(scan)/while/body/hermes.memory/hermes.l3/and"}
  %while.5 = (s64[2]{0}) while(%t), condition=%c, body=%b, metadata={op_name="jit(scan)/while/body/hermes.prefetch_issue/while"}
  %fusion.6 = s64[2]{0} fusion(%x), kind=kLoop, calls=%f.6, metadata={op_name="jit(scan)/while/body/hermes.prefetch_issue/while/body/hermes.ta_shadow/add"}
  ROOT %fusion.7 = s64[2]{0} fusion(%y), kind=kLoop, calls=%f.7, metadata={op_name="jit(scan)/while/body/hermes.retire/select_n"}
}

ENTRY %main (a: s64[2]) -> s64[2] {
  %while.1 = (s64[2]{0}) while(%a), condition=%c1, body=%body, metadata={op_name="jit(scan)/while"}
}
"""


def _hand_built():
    """A window of 1,000 ns: host spans of the benchmark and the
    program, one scan run of 160 ns whose operations nest (two loops),
    and an export run of 5 ns."""
    host = _Plane("/host:CPU", [_Line("python", [
        _Ev("bench.window", 100, 1000),
        _Ev("bench.run_batch", 100, 500),
        _Ev("hermes.run_batch", 110, 480),
        _Ev("hermes.prepare_trace", 120, 80),
        _Ev("hermes.scan", 300, 200),
        _Ev("bench.metrics_rows", 600, 100),
        _Ev("hermes.metrics_from_outputs", 610, 40),
        _Ev("hermes.metrics_from_outputs", 650, 40),
        _Ev("unrelated", 0, 5000)])])
    dev = _Plane("/device:TPU:0", [
        _Line("XLA Modules", [_Ev("jit_scan(1)", 320, 160),
                              _Ev("jit_export(2)", 490, 5)]),
        _Line("XLA Ops", [
            _Ev("%while.1 = (s64[2]{0}) while(%a)", 320, 150),
            _Ev("%fusion.2 = s64[2]{0} fusion(%p)", 330, 20),
            _Ev("%copy.3 = s64[2]{0} copy(%fusion.2)", 350, 30),
            _Ev("%fusion.4 = s64[2]{0} fusion(%copy.3)", 380, 20),
            _Ev("%while.5 = (s64[2]{0}) while(%t)", 400, 40),
            _Ev("%fusion.6 = s64[2]{0} fusion(%x)", 405, 20),
            _Ev("%fusion.7 = s64[2]{0} fusion(%y)", 470, 10),
            _Ev("%fusion.9 = s64[2]{0} fusion(%z)", 490, 5)])])
    return _Profile([host, dev])


def test_op_scopes_take_the_innermost_component():
    import program_trace as pt
    assert pt.op_scopes([HLO]) == {
        "fusion.2": "hermes.l1", "fusion.4": "hermes.l3",
        "while.5": "hermes.prefetch_issue",
        "fusion.6": "hermes.ta_shadow", "fusion.7": "hermes.retire"}


def test_scopes_split_own_time_hand_counted():
    """An operation's own time excludes the operations nested in it:
    ``while.1`` 150 - (20 + 30 + 20 + 40) = 40, ``while.5`` 40 - 20 =
    20; the copy and the outer loop have no component scope."""
    import program_trace as pt
    prof = _hand_built()
    scopes, kinds = pt.reduce_scopes(prof.planes[1], (320, 480),
                                     pt.op_scopes([HLO]))
    assert dict(scopes) == {"unscoped": 40 + 30, "hermes.l1": 20,
                            "hermes.l3": 20, "hermes.prefetch_issue": 20,
                            "hermes.ta_shadow": 20, "hermes.retire": 10}
    assert sum(ns for _, ns in scopes) == 160
    assert ("unscoped", "copy", 30) in kinds
    assert ("unscoped", "while", 40) in kinds


def test_scopes_honour_the_event_budget(monkeypatch):
    """Three events: the sample ends where the fourth starts (380), and
    the open loop is clipped there: 60 - 20 - 30 = 10 of its own."""
    import program_trace as pt
    monkeypatch.setattr(pt, "SCOPE_BUDGET", 3)
    prof = _hand_built()
    scopes, _ = pt.reduce_scopes(prof.planes[1], (320, 480),
                                 pt.op_scopes([HLO]))
    assert dict(scopes) == {"unscoped": 10 + 30, "hermes.l1": 20}


def test_breakdown_hand_counted():
    """Idle gaps [100, 320), [480, 490) and [495, 1100) of the window:
    ``idle_by_span`` splits each at the span edges it crosses."""
    import program_trace as pt
    out = pt.breakdown(_hand_built(), [HLO])
    ns = {n: round(s * 1e9, 3) for n, s in out["idle_by_span"]}
    assert ns == {
        "outside bench spans": 400,
        "hermes.run_batch": 10 + 100 + 90,
        "hermes.metrics_from_outputs": 40 + 40,
        "hermes.prepare_trace": 80,
        "hermes.scan": 20 + 10 + 5,
        "bench.run_batch": 10 + 10,
        "bench.metrics_rows": 10 + 10}
    assert sum(ns.values()) == 1000 - 160 - 5
    scopes = {n: (round(s * 1e9, 3), round(share, 6))
              for n, s, share in out["scopes"]}
    assert scopes["unscoped"] == (70, round(100 * 70 / 160, 6))
    assert scopes["hermes.retire"] == (10, round(100 * 10 / 160, 6))
    assert sum(share for _, _, share in out["scopes"]) == pytest.approx(100)
    # compiled programs without component scopes: no scope table
    assert pt.breakdown(_hand_built(), ["HloModule jit_scan"])["scopes"] \
        == []


def test_reduce_recorded_scoped_chip_trace():
    """A trace recorded on a TPU v5e (``scoped/``): two runs of
    a jitted function, ``sin(x) * 2`` under ``hermes.l1``, a 40-step
    ``fori_loop`` of ``cos(v) + 1`` under ``hermes.l2``, and a transpose
    (a copy) under neither, each run inside ``hermes.run_batch`` >
    ``hermes.scan`` after a 1 ms ``hermes.upload`` and followed by a
    2 ms ``hermes.metrics_from_outputs``, in a ``bench.window`` of
    11,886,778 ns.  ``program.hlo.txt`` is the function compiled for a
    described v5e by the same compiler: the trace's instruction names
    are its own.  The device's clock runs about 1 ms ahead of the
    host's here, so each run falls inside ``hermes.upload``."""
    import program_trace as pt
    import profile_reduce as pr
    out = pt.breakdown(pr.load(SCOPED),
                       [(SCOPED / "program.hlo.txt").read_text()])
    # the first run: [50262658, 50330628); the loop (64,792 ns) holds
    # its 40 steps, every one of them under hermes.l2
    scopes = {n: s * 1e9 for n, s, _ in out["scopes"]}
    assert scopes == pytest.approx({"hermes.l2": 64792.0,
                                    "hermes.l1": 2312.0,
                                    "unscoped": 857.0})
    assert sum(share for _, _, share in out["scopes"]) == pytest.approx(100)
    # the loop's own 293 ns: 64,792 less its 40 steps' 64,499
    assert [(sc, kind, round(ns * 1e9)) for sc, kind, ns
            in out["scope_ops"]] == [
        ("hermes.l2", "cosine_add_fusion", 64499),
        ("hermes.l1", "sine_multiply_fusion", 2312),
        ("unscoped", "copy", 857), ("hermes.l2", "while", 293)]
    # idle: the window less the two runs (67,970 and 67,978 ns), split
    # at every span edge
    idle = {n: s * 1e9 for n, s in out["idle_by_span"]}
    assert idle == pytest.approx({
        "outside bench spans": 2930510 + 2280 + 4510 + 2540 + 930,
        "bench.run_batch": 5050 + 670 + 2760 + 390,
        "hermes.run_batch": 1340 + 66420 + 1320 + 1140 + 6149 + 1780,
        "hermes.upload": 160708 + 846572 + 214007 + 859335,
        "hermes.scan": 1061669 + 719550,
        "bench.metrics_rows": 1450 + 2820 + 1560 + 1750,
        "hermes.metrics_from_outputs": 2227080 + 2626540}, abs=1.0)
    assert sum(idle.values()) == pytest.approx(11886778 - 67970 - 67978)


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------
def _record(campaigns, traced=1):
    """A run record whose window is ``campaigns``, the first ``traced``
    of them recorded by the profiler."""
    import harness
    cell = harness.load_cell("ta.table.3wl")
    return harness.RunRecord(cell, campaigns, campaigns[:traced], 0.0, 0)


def _campaign(t0, t1, log=None):
    import harness
    return harness.Campaign(t0, t1, {}, [], [], log or {})


def _store(monkeypatch, spans):
    from repro.runtime import spans as mod
    store = collections.deque(
        (mod.Span(i, n, t0, t1, None) for i, (n, t0, t1) in enumerate(spans)),
        maxlen=mod.SPANS.maxlen)
    monkeypatch.setattr(mod, "SPANS", store)


SPAN_READERS = ("prepare_ms_per_campaign", "stage_ms_per_campaign",
                "metrics_ms_per_campaign")
#: set-up, then two window campaigns: [10, 20] and [30, 40]
STORE = [("prepare_trace", 5, 8),
         ("prepare_trace", 11, 12), ("prepare_trace", 13, 15),
         ("init_state", 16, 16.25), ("upload", 16.25, 17),
         ("metrics_from_outputs", 18, 18.5),
         ("metrics_from_outputs", 18.5, 19),
         ("prepare_trace", 31, 31.5), ("upload", 32, 33),
         ("metrics_from_outputs", 35, 39)]


@pytest.mark.parametrize("traced, want", [
    (1, {"prepare_ms_per_campaign": 3.0e3, "stage_ms_per_campaign": 1.0e3,
         "metrics_ms_per_campaign": 1.0e3}),
    (2, {"prepare_ms_per_campaign": (3.0 + 0.5) / 2 * 1e3,
         "stage_ms_per_campaign": (1.0 + 1.0) / 2 * 1e3,
         "metrics_ms_per_campaign": (1.0 + 4.0) / 2 * 1e3})],
    ids=["one_traced", "two_traced"])
def test_span_readers_hand_counted(monkeypatch, traced, want):
    """Spans inside each traced campaign, summed per campaign, mean over
    the traced campaigns.  A span outside them (set-up, or the stall of
    the campaign after the trace) is not read."""
    _store(monkeypatch, STORE)
    run = _record([_campaign(10, 20), _campaign(30, 40)], traced)
    read = {m: run.cell.reader(m)(run) for m in SPAN_READERS}
    assert read == pytest.approx(want)


def test_span_readers_read_nothing_without_spans(monkeypatch):
    """No such span, or a program that records none: no value."""
    run = _record([_campaign(10, 20)])
    _store(monkeypatch, [("prepare_trace", 5, 8)])
    assert run.cell.reader("prepare_ms_per_campaign")(run) is None
    monkeypatch.setitem(sys.modules, "repro.runtime.spans", None)
    for m in SPAN_READERS:
        assert run.cell.reader(m)(run) is None


def test_upload_reader():
    """The traced campaign's ``upload_bytes`` in MB; nothing where the
    program does not count it."""
    run = _record([_campaign(0, 1, {"upload_bytes": 74_123_456}),
                   _campaign(1, 2, {"upload_bytes": 1})])
    assert run.cell.reader("upload_mb_per_campaign")(run) \
        == pytest.approx(74.123456)
    run = _record([_campaign(0, 1, {"scan_s": 1.0})])
    assert run.cell.reader("upload_mb_per_campaign")(run) is None


def test_traced_campaign_on_the_cpu(tmp_path, monkeypatch):
    """The whole path on the CPU: a campaign of a tiny cell traced under
    the profiler; the program's spans reach the trace's host plane and
    the compiled scan's text carries the component scopes.  The CPU
    trace has no device plane, which ``profile_reduce`` refuses."""
    import harness
    import program_trace as pt
    from repro.core import engine_jax as ej
    from traffic.generator import Traffic
    cell = _tiny_cell(tmp_path)
    _on_a_chip(monkeypatch)
    monkeypatch.setattr(ej, "_COMPILED", {})
    program = harness.Program(cell, Traffic(cell.mix, 2**31 + 5))
    program.campaign(harness.no_annotation)
    seen, breakdown = {}, pt.breakdown
    monkeypatch.setattr(pt, "breakdown", lambda pd, hlo: seen.update(
        pd=pd, hlo=list(hlo)) or {})
    assert pt.traced_campaign(program) == {}
    names = {ev.name for p in seen["pd"].planes if p.name.startswith("/host:")
             for line in p.lines for ev in line.events}
    assert {"bench.window", "hermes.run_batch", "hermes.scan",
            "hermes.upload", "hermes.metrics_from_outputs",
            "bench.metrics_rows"} <= names
    assert "hermes.l1" in set(pt.op_scopes(seen["hlo"]).values())
    with pytest.raises(ValueError, match="no device plane"):
        breakdown(seen["pd"], seen["hlo"])
