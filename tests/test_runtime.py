"""Fault-tolerance runtime logic: stragglers, elastic topology, preemption,
and the trainer-loop integration (resume from checkpoint after preempt)."""

import numpy as np
import pytest

from repro.runtime.fault import (ElasticTopology, PreemptionHandler,
                                 StragglerMonitor)


class TestStragglerMonitor:
    def _warm(self, mon, n=16, t=0.1):
        for i in range(n):
            mon.start_step(i)
            mon.end_step(elapsed=t)

    def test_normal_steps_not_flagged(self):
        mon = StragglerMonitor()
        self._warm(mon)
        mon.start_step(99)
        assert mon.end_step(elapsed=0.11) is False

    def test_outlier_flagged(self):
        mon = StragglerMonitor(floor_s=0.01)
        self._warm(mon)
        mon.start_step(99)
        assert mon.end_step(elapsed=5.0) is True
        assert 99 in mon.straggled_steps

    def test_rebuild_after_patience(self):
        mon = StragglerMonitor(floor_s=0.01, patience=3)
        self._warm(mon)
        for s in (50, 51):
            mon.start_step(s)
            mon.end_step(elapsed=5.0)
        assert not mon.should_rebuild
        mon.start_step(52)
        mon.end_step(elapsed=5.0)
        assert mon.should_rebuild

    def test_straggled_steps_do_not_poison_baseline(self):
        mon = StragglerMonitor(floor_s=0.01)
        self._warm(mon, t=0.1)
        mon.start_step(1)
        mon.end_step(elapsed=50.0)
        dl = mon.deadline()
        assert dl < 10                   # baseline still ~0.1s-scale


class TestElasticTopology:
    def test_full_fleet(self):
        et = ElasticTopology(model_parallel=16)
        assert et.propose(512, chips_per_pod=256) == (2, 16, 16)
        assert et.propose(256, chips_per_pod=256) == (1, 16, 16)

    def test_shrunk_fleet(self):
        et = ElasticTopology(model_parallel=16)
        pods, data, model = et.propose(384, chips_per_pod=256)
        assert pods * data * model <= 384
        assert model == 16 and data >= 8

    def test_too_small_raises(self):
        et = ElasticTopology(model_parallel=16)
        with pytest.raises(ValueError):
            et.propose(8)

    def test_batch_scales_with_topology(self):
        et = ElasticTopology(model_parallel=16)
        full = et.batch_for((2, 16, 16))
        small = et.batch_for((1, 8, 16))
        assert full == 4 * small


class TestPreemption:
    def test_flag_set_on_request(self):
        h = PreemptionHandler(install=False)
        assert not h.should_stop
        h.request_stop()
        assert h.should_stop


def test_train_loop_preemption_and_resume(tmp_path):
    """Integration: preempt mid-run → checkpoint written → resume
    continues from the next step with the same loss trajectory."""
    import jax
    from repro.configs.base import RunConfig
    from repro.configs.registry import SMOKES
    from repro.train.loop import train

    cfg = SMOKES["gemma-2b"]
    rc = RunConfig(microbatches=1, remat="none", learning_rate=1e-3)

    class StopAt(PreemptionHandler):
        def __init__(self, at):
            super().__init__(install=False)
            self.at = at
            self.n = 0

        @property
        def should_stop(self):
            self.n += 1
            return self.n > self.at

    r1 = train(cfg, rc, batch=4, seq=16, steps=20,
               ckpt_dir=str(tmp_path), ckpt_every=5,
               preempt=StopAt(6), log_every=1000)
    assert r1.stopped_by == "preempted"
    assert r1.last_step < 19

    r2 = train(cfg, rc, batch=4, seq=16, steps=12,
               ckpt_dir=str(tmp_path), ckpt_every=100, log_every=1000)
    assert r2.stopped_by == "completed"
    assert r2.last_step == 11
    # uninterrupted reference must match the resumed trajectory's tail
    r_ref = train(cfg, rc, batch=4, seq=16, steps=12, log_every=1000)
    np.testing.assert_allclose(r2.losses[-1], r_ref.losses[-1],
                               rtol=5e-2)


class TestCompileCache:
    def test_env_dir_is_honoured(self, tmp_path, monkeypatch):
        from repro.runtime import compile_cache
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.configure() == str(tmp_path)

    def test_default_dir_is_fixed_and_gitignored(self):
        from pathlib import Path

        from repro.runtime import compile_cache
        repo = Path(__file__).resolve().parents[1]
        assert compile_cache.DEFAULT_DIR == repo / ".jax_cache"
        ignored = (repo / ".gitignore").read_text().split()
        assert ".jax_cache/" in ignored
