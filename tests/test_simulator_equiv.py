"""Engine equivalence: the SoA engine (compiled kernel AND pure-Python
chunked path) must be bit-identical to the object reference engine —
same cache/coherence/prefetch counters and the same Metrics floats — on
every preset for every workload.  This is what licenses benchmarks and
tests to run on the fast engine."""

import dataclasses

import pytest

from repro.core import trace as trace_mod
from repro.core.presets import CONFIGS
from repro.core.simulator import HierarchySim

SCALE = 0.012
#: the jax leg runs a prefix of each trace: compile cost dominates and
#: the scan's per-step work is identical at any length.  5000 keeps all
#: three workloads in ONE table-capacity shape bucket (blk 16384 /
#: page-group 1024), so presets share compiled programs across
#: workloads instead of recompiling per trace.
JAX_SLICE = 5000


def _counters_ref(sim):
    return {
        "l1_hits": sum(c.hits for c in sim.l1),
        "l1_misses": sum(c.misses for c in sim.l1),
        "l2_hits": sum(c.hits for c in sim.l2),
        "l2_misses": sum(c.misses for c in sim.l2),
        "l3": ((sim.l3.hits, sim.l3.misses, sim.l3.evictions,
                sim.l3.dirty_evictions, sim.l3.prefetch_fills,
                sim.l3.prefetch_useful) if sim.l3 else None),
        "evictions": (sum(c.evictions for c in sim.l1),
                      sum(c.evictions for c in sim.l2)),
        "dirty_evictions": (sum(c.dirty_evictions for c in sim.l1),
                            sum(c.dirty_evictions for c in sim.l2)),
        "prefetch_useful": (sum(c.prefetch_useful for c in sim.l2)),
        "prefetch_fills": (sum(c.prefetch_fills for c in sim.l2)),
        "invalidations": sim.dir.invalidations if sim.dir else 0,
        "c2c": sim.dir.c2c_transfers if sim.dir else 0,
        "upgrades": sim.dir.upgrades if sim.dir else 0,
        "prefetches": sum(p.issued for p in sim.pf),
        "migrations": sim.mem.migrations,
        "migration_bytes": sim.mem.migration_bytes,
        "dram": (sim.mem.dram.bytes_transferred, sim.mem.dram.row_hits,
                 sim.mem.dram.accesses),
        "hbm": ((sim.mem.hbm.bytes_transferred, sim.mem.hbm.row_hits,
                 sim.mem.hbm.accesses) if sim.mem.hbm else None),
        "wb_lines": sim.wb_lines,
        "pf_dropped": sim.pf_dropped,
        "n_acc": sim.n_acc,
        "lat_sum": sim.lat_sum,
        "time": tuple(sim.time),
    }


def _counters_soa(sim):
    return {
        "l1_hits": sim.l1.hits,
        "l1_misses": sim.l1.misses,
        "l2_hits": sim.l2.hits,
        "l2_misses": sim.l2.misses,
        "l3": ((sim.l3.hits, sim.l3.misses, sim.l3.evictions,
                sim.l3.dirty_evictions, sim.l3.prefetch_fills,
                sim.l3.prefetch_useful) if sim.l3 else None),
        "evictions": (sim.l1.evictions, sim.l2.evictions),
        "dirty_evictions": (sim.l1.dirty_evictions,
                            sim.l2.dirty_evictions),
        "prefetch_useful": sim.l2.prefetch_useful,
        "prefetch_fills": sim.l2.prefetch_fills,
        "invalidations": sim.dir.invalidations if sim.dir else 0,
        "c2c": sim.dir.c2c_transfers if sim.dir else 0,
        "upgrades": sim.dir.upgrades if sim.dir else 0,
        "prefetches": sum(p.issued for p in sim.pf),
        "migrations": sim.mem.migrations,
        "migration_bytes": sim.mem.migration_bytes,
        "dram": (sim.mem.dram.bytes_transferred, sim.mem.dram.row_hits,
                 sim.mem.dram.accesses),
        "hbm": ((sim.mem.hbm.bytes_transferred, sim.mem.hbm.row_hits,
                 sim.mem.hbm.accesses) if sim.mem.hbm else None),
        "wb_lines": sim.wb_lines,
        "pf_dropped": sim.pf_dropped,
        "n_acc": sim.n_acc,
        "lat_sum": sim.lat_sum,
        "time": tuple(sim.time),
    }


@pytest.fixture(scope="module", params=list(trace_mod.WORKLOADS))
def workload(request):
    return request.param, trace_mod.WORKLOADS[request.param](scale=SCALE)


@pytest.fixture(scope="module")
def reference(workload):
    name, tr = workload
    out = {}
    for sp in CONFIGS:
        sim = HierarchySim(sp)
        metrics = sim.run(tr)
        out[sp.name] = (_counters_ref(sim), metrics)
    return out


def _check(tr, reference, native):
    for sp in CONFIGS:
        sim = HierarchySim(sp, engine="soa")
        sim.native = native
        metrics = sim.run(tr)
        want_ctr, want_metrics = reference[sp.name]
        got_ctr = _counters_soa(sim)
        assert got_ctr == want_ctr, (sp.name, {
            k: (want_ctr[k], got_ctr[k])
            for k in want_ctr if want_ctr[k] != got_ctr[k]})
        for f in dataclasses.fields(want_metrics):
            a = getattr(want_metrics, f.name)
            b = getattr(metrics, f.name)
            assert a == b, (sp.name, f.name, a, b)


def test_python_soa_engine_bit_identical(workload, reference):
    """Pure-Python chunked SoA path (always available)."""
    _, tr = workload
    _check(tr, reference, native=False)


def test_native_kernel_bit_identical(workload, reference):
    """Compiled kernel — skipped when no C compiler is present."""
    from repro.core import native as native_mod
    if native_mod.get_lib() is None:
        pytest.skip("no C compiler / kernel unavailable")
    _, tr = workload
    _check(tr, reference, native=True)


def _prefix(tr):
    sub = dict(tr)
    for k in ("core", "pc", "addr", "write", "tensor", "reuse"):
        sub[k] = tr[k][:JAX_SLICE]
    return sub


@pytest.fixture(scope="module")
def jax_caps():
    """One layout for the three workload prefixes, as a batched campaign
    lays out its traces: each preset compiles once for all three."""
    pytest.importorskip("jax")
    from repro.core import engine_jax
    return engine_jax.Caps.cover([
        engine_jax.Caps.of(_prefix(gen(scale=SCALE)))
        for gen in trace_mod.WORKLOADS.values()])


@pytest.fixture(scope="module")
def jax_reference(workload):
    """Counters+metrics on the JAX_SLICE prefix via the SoA engine —
    itself asserted bit-identical to the object reference above, so the
    jax leg inherits the full chain jax == soa == object."""
    _, tr = workload
    sub = _prefix(tr)
    out = {}
    for sp in CONFIGS:
        sim = HierarchySim(sp, engine="soa")
        metrics = sim.run(sub)
        out[sp.name] = (_counters_soa(sim), metrics)
    return sub, out


def test_jax_engine_bit_identical(workload, jax_reference, jax_caps):
    """Functional jax scan engine, batched as a campaign runs it —
    every preset, bit-identical counters and Metrics floats on the
    shared trace prefix."""
    from repro.core import engine_jax, native
    from repro.core.engine_soa import SoAHierarchySim
    sub, want = jax_reference
    outs = engine_jax.run_batch(CONFIGS, sub, caps=jax_caps)
    for sp, (oi, od) in zip(CONFIGS, outs):
        metrics = engine_jax.metrics_from_outputs(sp, sub, oi, od)
        sim = SoAHierarchySim(sp)
        native.deposit_counters(sim, oi, od)
        want_ctr, want_metrics = want[sp.name]
        got_ctr = _counters_soa(sim)
        assert got_ctr == want_ctr, (sp.name, {
            k: (want_ctr[k], got_ctr[k])
            for k in want_ctr if want_ctr[k] != got_ctr[k]})
        for f in dataclasses.fields(want_metrics):
            a = getattr(want_metrics, f.name)
            b = getattr(metrics, f.name)
            assert a == b, (sp.name, f.name, a, b)


def test_jax_engine_bit_identical_off_preset():
    """Sampled sweep point off the preset manifold (same pattern the C
    kernel used in test_sweep.py): knob values the preset suite never
    reaches must agree bit-for-bit too."""
    pytest.importorskip("jax")
    from repro.core.presets import TENSOR_AWARE
    from repro.sweep.grid import apply_point
    point = {
        "prefetch.degree": 3,
        "prefetch.stride_confidence": 4,
        "l2.policy": "lru",
        "ta.low_utility": 0.2,
        "ta.high_utility": 0.8,
        "ta.prefetch_rank": 1.5,
        "ta.stream_rank": 1.0,
        "ta.sample": 8,
        "ta.bypass_utility": 0.1,
    }
    sp = apply_point(TENSOR_AWARE, point, name="sampled")
    tr = trace_mod.WORKLOADS["transformer"](scale=SCALE)
    sub = dict(tr)
    for k in ("core", "pc", "addr", "write", "tensor", "reuse"):
        sub[k] = tr[k][:JAX_SLICE]
    ref = HierarchySim(sp, engine="soa")
    want_metrics = ref.run(sub)
    got = HierarchySim(sp, engine="jax")
    metrics = got.run(sub)
    want_ctr, got_ctr = _counters_soa(ref), _counters_soa(got)
    assert got_ctr == want_ctr, {
        k: (want_ctr[k], got_ctr[k])
        for k in want_ctr if want_ctr[k] != got_ctr[k]}
    for f in dataclasses.fields(want_metrics):
        assert getattr(want_metrics, f.name) == getattr(metrics, f.name), \
            f.name


def test_engine_factory_dispatch():
    sp = CONFIGS[0]
    from repro.core.engine_soa import SoAHierarchySim
    from repro.core.simulator import available_engines
    assert isinstance(HierarchySim(sp, engine="soa"), SoAHierarchySim)
    assert isinstance(HierarchySim(sp), HierarchySim)
    # registry aliases: "reference" is the object engine, "native" the
    # SoA engine with the compiled kernel preferred
    assert isinstance(HierarchySim(sp, engine="reference"), HierarchySim)
    nat = HierarchySim(sp, engine="native")
    assert isinstance(nat, SoAHierarchySim) and nat.native
    assert set(available_engines()) >= {"object", "reference", "soa",
                                        "native", "jax"}
    with pytest.raises(ValueError):
        HierarchySim(sp, engine="warp")


def test_engine_factory_dispatch_jax():
    pytest.importorskip("jax")
    sp = CONFIGS[0]
    from repro.core.engine_jax import JaxHierarchySim
    from repro.core.engine_soa import SoAHierarchySim
    sim = HierarchySim(sp, engine="jax")
    assert isinstance(sim, JaxHierarchySim)
    assert isinstance(sim, SoAHierarchySim)  # drop-in: same surface
