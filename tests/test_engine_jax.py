"""jax engine internals: vmapped batch == per-config loop, and the
Pallas tag-probe kernel vs its pure-jnp oracle (interpret mode on CPU).

Full preset×workload bit-identity vs the reference engine lives in
``test_simulator_equiv.py``; this module covers the batching and
kernel layers underneath it on deliberately tiny inputs."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import trace as trace_mod  # noqa: E402
from repro.core.presets import BASELINE, SHARED_L3  # noqa: E402
from repro.sweep.grid import apply_point  # noqa: E402

N = 1500  # trace prefix: enough to exercise evictions + coherence


@pytest.fixture(scope="module")
def tiny_trace():
    tr = trace_mod.WORKLOADS["cnn"](scale=0.012)
    sub = dict(tr)
    for k in ("core", "pc", "addr", "write", "tensor", "reuse"):
        sub[k] = tr[k][:N]
    return sub


def test_run_batch_matches_run_single(tiny_trace):
    """One vmapped program over B lanes == B independent single runs,
    bit for bit, including lanes that differ in vmapped scalars.  Three
    lanes on purpose: the lane axis pads to the next power of two
    (params.stack_lanes), so this also proves padded lanes don't bleed
    into real ones."""
    from repro.core import engine_jax
    sps = [apply_point(BASELINE, {"l2.hit_latency": 12 + i})
           for i in range(3)]
    batch = engine_jax.run_batch(sps, tiny_trace)
    assert len(batch) == len(sps)
    for sp, (oi, od) in zip(sps, batch):
        oi1, od1 = engine_jax.run_single(sp, tiny_trace)
        assert np.array_equal(oi, oi1), sp.name
        assert np.array_equal(od, od1), sp.name


def test_run_batch_mixed_shape_buckets(tiny_trace):
    """Configs landing in different StaticConfig buckets (shared_l3
    changes the structure, not just scalars) still come back in input
    order with per-lane-correct outputs."""
    from repro.core import engine_jax
    sps = [BASELINE, SHARED_L3, apply_point(BASELINE,
                                            {"l2.hit_latency": 19})]
    batch = engine_jax.run_batch(sps, tiny_trace)
    for sp, (oi, od) in zip(sps, batch):
        oi1, od1 = engine_jax.run_single(sp, tiny_trace)
        assert np.array_equal(oi, oi1), sp.name
        assert np.array_equal(od, od1), sp.name


def test_run_batch_lanes_over_traces(tiny_trace):
    """Lanes that scan different traces of unequal length in one
    program (the shorter padded with no-op steps) == each config run
    alone on its own trace; a bucket with one lane runs beside them."""
    from repro.core import engine_jax
    rnn = trace_mod.WORKLOADS["rnn"](scale=0.012)
    short = {k: (v[:N // 2] if k in ("core", "pc", "addr", "write",
                                     "tensor", "reuse") else v)
             for k, v in rnn.items()}
    sps = [BASELINE, apply_point(BASELINE, {"l2.hit_latency": 19}),
           BASELINE, SHARED_L3]
    trs = [tiny_trace, tiny_trace, short, short]
    batch = engine_jax.run_batch(sps, trs)
    for sp, tr, (oi, od) in zip(sps, trs, batch):
        oi1, od1 = engine_jax.run_single(sp, tr)
        assert np.array_equal(oi, oi1), sp.name
        assert np.array_equal(od, od1), sp.name


def test_batch_metrics_match_soa_engine(tiny_trace):
    """metrics_from_outputs on a batch lane == the drop-in
    JaxHierarchySim.run row for the same config."""
    import dataclasses

    from repro.core import engine_jax
    from repro.core.simulator import HierarchySim
    (oi, od), = engine_jax.run_batch([BASELINE], tiny_trace)
    got = engine_jax.metrics_from_outputs(BASELINE, tiny_trace, oi, od)
    want = HierarchySim(BASELINE, engine="jax").run(tiny_trace)
    for f in dataclasses.fields(want):
        assert getattr(got, f.name) == getattr(want, f.name), f.name


def test_chunked_scan_matches_one_scan(tiny_trace, monkeypatch):
    """A trace scanned in chunks — the state carried on the device from
    call to call, the tail padded with no-op steps — gives the counters
    of one scan over the whole trace."""
    from repro.core import engine_jax
    sps = [BASELINE, SHARED_L3]
    whole = engine_jax.run_batch(sps, tiny_trace)
    monkeypatch.setattr(engine_jax, "CHUNK", 512)
    chunked = engine_jax.run_batch(sps, tiny_trace)
    for sp, (oi, od), (oi1, od1) in zip(sps, whole, chunked):
        assert np.array_equal(oi, oi1), sp.name
        assert np.array_equal(od, od1), sp.name


def test_caps_envelope_leaves_counters_unchanged(tiny_trace):
    """Laying a trace out at a campaign's larger extents (what lets all
    its traces share one compiled program per bucket) changes nothing."""
    import dataclasses

    from repro.core import engine_jax
    caps = engine_jax.Caps.of(tiny_trace)
    wide = dataclasses.replace(caps, blk=2 * caps.blk, pg=2 * caps.pg,
                               n_pc=caps.n_pc + 3, mk=2 * caps.mk,
                               nten=caps.nten + 2)
    (oi, od), = engine_jax.run_batch([SHARED_L3], tiny_trace)
    (oi1, od1), = engine_jax.run_batch([SHARED_L3], tiny_trace, caps=wide)
    assert np.array_equal(oi, oi1)
    assert np.array_equal(od, od1)


# ---------------------------------------------------------------- Pallas


def _random_sets(key, B, A):
    """Random cache-set snapshots with realistic degeneracies: duplicate
    tags, invalid ways, tied last-touch stamps."""
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    tags = jax.random.randint(k1, (B, A), 0, 7, dtype=jnp.int32)
    valid = (jax.random.uniform(k2, (B, A)) < 0.7).astype(jnp.int32)
    last = jax.random.randint(k3, (B, A), 0, 4, dtype=jnp.int32)
    seq = jax.random.randint(k4, (B, A), 0, 1 << 20, dtype=jnp.int32)
    query = jax.random.randint(k5, (B,), 0, 7, dtype=jnp.int32)
    return tags, valid, last, seq, query


@pytest.mark.parametrize("B,A", [(7, 8), (256, 16), (1000, 4)])
def test_tag_probe_kernel_vs_oracle(B, A):
    from repro.kernels import ref
    from repro.kernels.tag_probe import tag_probe
    args = _random_sets(jax.random.PRNGKey(B * 31 + A), B, A)
    out = tag_probe(*args, interpret=True)
    want = ref.tag_probe_ref(*args)
    assert out.shape == (B, 3) and out.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_tag_probe_tie_breaks():
    """All-tied LRU stamps: victim must be the lowest-sequence way;
    a hit must win over eviction; empty set fills the first free way."""
    from repro.kernels.tag_probe import tag_probe
    tags = jnp.array([[5, 6, 7, 8], [5, 6, 7, 8], [0, 0, 0, 0]],
                     jnp.int32)
    valid = jnp.array([[1, 1, 1, 1], [1, 1, 1, 1], [0, 0, 0, 0]],
                      jnp.int32)
    last = jnp.zeros((3, 4), jnp.int32)              # every way tied
    seq = jnp.array([[9, 3, 3, 7], [9, 3, 3, 7], [0, 0, 0, 0]],
                    jnp.int32)
    query = jnp.array([7, 4, 4], jnp.int32)
    out = np.asarray(tag_probe(tags, valid, last, seq, query,
                               interpret=True))
    np.testing.assert_array_equal(out[0], [1, 2, 0])  # hit way 2
    np.testing.assert_array_equal(out[1], [0, 1, 1])  # evict 1st min-seq
    np.testing.assert_array_equal(out[2], [0, 0, 0])  # fill free way 0
