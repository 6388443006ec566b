"""The simulator's host spans (``repro.runtime.spans``), the counters of
each ``engine_jax.SCAN_LOG`` record, and the component scopes of the
scan step.  Tiny traces on the CPU; nothing here asserts a time."""

import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import trace as trace_mod  # noqa: E402
from repro.core.presets import BASELINE, TENSOR_AWARE  # noqa: E402
from repro.runtime import spans  # noqa: E402
from repro.sweep.grid import apply_point  # noqa: E402

N = 40

PHASES = ("prepare_trace", "init_state", "upload", "compile", "scan",
          "fetch")


@pytest.fixture(scope="module")
def tiny_trace():
    tr = trace_mod.WORKLOADS["cnn"](scale=0.012)
    return {k: (v[:N] if k in ("core", "pc", "addr", "write", "tensor",
                               "reuse") else v)
            for k, v in tr.items()}


@pytest.fixture
def fresh_engine(monkeypatch):
    """The engine with empty program and trace caches."""
    from repro.core import engine_jax
    monkeypatch.setattr(engine_jax, "_COMPILED", {})
    monkeypatch.setattr(engine_jax, "_PREP_CACHE", {})
    return engine_jax


def _since(index):
    return [s for s in spans.SPANS if s.index > index]


def test_run_batch_records_its_phases(tiny_trace, fresh_engine):
    """Every phase of the table is a span nested under
    ``hermes.run_batch``, inside its parent; each lane's Metrics export
    is a span of its own."""
    E = fresh_engine
    with spans.span("mark") as mark:
        pass
    sps = [apply_point(BASELINE, {"l2.hit_latency": 12 + i})
           for i in range(3)]
    outs = E.run_batch(sps, tiny_trace)
    for sp, (oi, od) in zip(sps, outs):
        E.metrics_from_outputs(sp, tiny_trace, oi, od)
    got = _since(mark.index)
    by_index = {s.index: s for s in got}
    root, = [s for s in got if s.name == "run_batch"]
    assert root.parent is None
    inner = [s for s in got if s.parent is not None]
    assert sorted(s.name for s in inner) == sorted(PHASES)
    for s in inner:
        parent = by_index[s.parent]
        assert parent.t0 <= s.t0 <= s.t1 <= parent.t1
        while parent.parent is not None:
            parent = by_index[parent.parent]
        assert parent is root
    exports = [s for s in got if s.name == "metrics_from_outputs"]
    assert len(exports) == 3
    assert all(s.parent is None and s.t0 >= root.t1 for s in exports)


@pytest.mark.parametrize("two", [False, True],
                         ids=["one_trace", "two_traces"])
def test_scan_log_upload_bytes(tiny_trace, fresh_engine, monkeypatch, two):
    """``upload_bytes`` is the bytes of the arrays put on the device:
    each trace's block table, trace columns padded to whole chunks and
    state (one state per trace, gathered per lane on the device), and
    the lane trace indices; a trace seen before counts again."""
    E = fresh_engine
    monkeypatch.setattr(E, "CHUNK", 16)
    traces = [tiny_trace]
    if two:
        traces.append(dict(tiny_trace,
                           addr=np.asarray(tiny_trace["addr"]) + 4096))
    sps = [apply_point(BASELINE, {"l2.hit_latency": 12 + i})
           for i in range(3)]
    lane_traces = [traces[i % len(traces)] for i in range(3)]
    E.run_batch(sps, lane_traces)
    first = E.SCAN_LOG[-1]
    copies = {id(t): dict(t) for t in traces}         # equal traces
    E.run_batch(sps, [copies[id(t)] for t in lane_traces])
    second = E.SCAN_LOG[-1]

    lanes = 4                                  # 3 padded to a power of two
    steps = -(-N // 16) * 16
    caps = E.Caps.cover([E.Caps.of(t) for t in traces])
    want = 4 * lanes                           # lane trace indices
    with jax.enable_x64(True):
        static, _ = E.split_config(BASELINE, caps.nten)
        for t in traces:
            prep = E.prepare_trace(static, t, caps)
            want += (prep.blk_tab.nbytes
                     + sum(np.asarray(v).nbytes
                           for v in E.init_state(static, prep).values())
                     + sum(steps * v.dtype.itemsize
                           for v in prep.xs.values()))
    assert first["traces"] == len(traces)
    assert first["upload_bytes"] == second["upload_bytes"] == want


def test_span_store_is_bounded():
    cap = spans.SPANS.maxlen
    assert cap == 65536
    with spans.span("outer") as outer:
        for _ in range(cap + 10):
            with spans.span("inner"):
                pass
    assert len(spans.SPANS) == cap
    assert spans.SPANS[-1].index == outer.index
    assert spans.SPANS[-1].name == "outer"
    assert spans.SPANS[-2].parent == outer.index
    assert spans.SPANS[0].index == outer.index + 12
    assert outer.seconds >= 0.0


def _expected_scopes(static):
    want = {"hermes.l1", "hermes.l2", "hermes.memory", "hermes.retire"}
    if static.pf_on:
        want |= {"hermes.prefetch_observe", "hermes.prefetch_issue"}
    if static.mesi:
        want.add("hermes.coherence")
    if static.has_l3:
        want.add("hermes.l3")
    if static.ta1 or static.ta2 or static.ta3:
        want.add("hermes.ta_shadow")
    return want


@pytest.mark.parametrize("preset", [BASELINE, TENSOR_AWARE],
                         ids=["baseline", "tensor_aware"])
def test_lowered_step_carries_its_scopes(tiny_trace, preset):
    """The lowered scan carries a named scope for each component its
    StaticConfig enables, and no other; the export carries its own."""
    from repro.core import engine_jax as E
    with jax.enable_x64(True):
        caps = E.Caps.of(tiny_trace)
        static, cfg = E.split_config(preset, caps.nten)
        prep = E.prepare_trace(static, tiny_trace, caps)
        st = {k: np.broadcast_to(v, (2,) + np.shape(v))
              for k, v in E.init_state(static, prep).items()}
        xs = {k: v[:, None] for k, v in prep.xs.items()}
        scan, export = E._make_run(static, batched=True)
        text = scan.lower({"blk": prep.blk_tab[None]},
                          E._cfg_stack([cfg] * 2), st, xs,
                          np.zeros(2, np.int32)).as_text(debug_info=True)
        exported = export.lower(st).as_text(debug_info=True)
    assert set(re.findall(r"hermes\.\w+", text)) == _expected_scopes(static)
    assert set(re.findall(r"hermes\.\w+", exported)) == {"hermes.export"}
