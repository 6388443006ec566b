"""The PR-6 resilience layer: deterministic chaos, retry/deadline/
requeue convergence, structured failure rows, graceful degradation,
and journaled resume equivalence.

The contract under test: a campaign that crashes, hangs, OOMs, or gets
killed outright must either converge to the *same bits* an undisturbed
run produces, or emit a valid artifact that says exactly which cells it
lost — never a crash, never a silent drop.
"""

import dataclasses
import json
import math
import subprocess
import sys
from pathlib import Path

import pytest

from repro.api import schema as schema_mod
from repro.api.runner import Runner, RunnerError, config_hash
from repro.api.spec import Experiment
from repro.core.presets import PRESETS
from repro.runtime.chaos import (ChaosFault, FaultSpec, backoff_delay,
                                 _unit_hash)

TINY = 0.01
REPO = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# FaultSpec: the deterministic schedule
# ---------------------------------------------------------------------------
class TestFaultSpecDeterminism:
    KEYS = [f"cfg{i:02d}:wl{j}" for i in range(40) for j in range(3)]

    def test_same_seed_identical_schedule(self):
        mk = lambda: FaultSpec(seed=7, p_crash=0.2, p_hang=0.1,
                               p_oom=0.05, p_corrupt=0.1, p_slow=0.1)
        a = mk().schedule(self.KEYS, attempts=3)
        b = mk().schedule(self.KEYS, attempts=3)
        assert a == b
        assert a, "a 55% fault rate over 360 draws cannot be empty"
        assert set(a.values()) <= set(
            ("crash", "hang", "oom", "corrupt", "slow"))

    def test_different_seed_different_schedule(self):
        a = FaultSpec(seed=1, p_crash=0.5).schedule(self.KEYS)
        b = FaultSpec(seed=2, p_crash=0.5).schedule(self.KEYS)
        assert a != b

    def test_schedule_is_order_independent(self):
        spec = FaultSpec(seed=9, p_crash=0.3, p_hang=0.2)
        fwd = spec.schedule(self.KEYS)
        rev = spec.schedule(list(reversed(self.KEYS)))
        assert fwd == rev

    def test_unit_hash_uniform_range(self):
        us = [_unit_hash("x", i) for i in range(1000)]
        assert all(0.0 <= u < 1.0 for u in us)
        assert 0.4 < sum(us) / len(us) < 0.6

    def test_probabilities_validated(self):
        with pytest.raises(ValueError, match="p_crash"):
            FaultSpec(p_crash=1.5)
        with pytest.raises(ValueError, match="sum"):
            FaultSpec(p_crash=0.6, p_hang=0.6)

    def test_max_faults_bounds_attempts(self):
        spec = FaultSpec(seed=0, p_crash=1.0, max_faults=1)
        assert spec.draw("k", 0) == "crash"
        assert spec.draw("k", 1) is None       # the retry is clean
        unbounded = FaultSpec(seed=0, p_crash=1.0, max_faults=None)
        assert all(unbounded.draw("k", a) == "crash" for a in range(5))

    def test_env_round_trip(self):
        spec = FaultSpec(seed=3, p_crash=0.2, p_hang=0.1, hang_s=12.0,
                         max_faults=2, kill_after_cells=7)
        again = FaultSpec.from_env({"REPRO_CHAOS": spec.to_env()})
        assert again == spec
        assert FaultSpec.from_env({}) is None

    def test_from_env_rejects_unknown_keys(self):
        with pytest.raises(ValueError, match="unknown keys"):
            FaultSpec.from_json('{"p_crush": 0.2}')

    def test_backoff_deterministic_bounded_growing(self):
        d1 = backoff_delay(0.1, 1, "cell")
        d2 = backoff_delay(0.1, 2, "cell")
        assert d1 == backoff_delay(0.1, 1, "cell")   # replayable
        assert 0.075 <= d1 <= 0.125                  # ±25 % jitter
        assert d2 > d1                               # exponential
        assert backoff_delay(0.1, 50, "cell") <= 5.0  # capped
        assert backoff_delay(0.1, 0, "cell") == 0.0
        assert backoff_delay(0.1, 1, "a") != backoff_delay(0.1, 1, "b")

    def test_corrupt_row_poisons_first_numeric(self):
        row = {"name": "x", "hit_rate": 0.9, "latency_ns": 100.0}
        bad = FaultSpec.corrupt_row(row)
        assert math.isnan(bad["hit_rate"])
        assert bad["latency_ns"] == 100.0 and bad["name"] == "x"
        assert row["hit_rate"] == 0.9                # input untouched


# ---------------------------------------------------------------------------
# chaos → retry convergence (serial executor; pool covered below)
# ---------------------------------------------------------------------------
class TestChaosConvergence:
    @pytest.fixture(scope="class")
    def clean_rows(self):
        res = Runner(processes=1).run_configs(
            [PRESETS["baseline"]], workloads=["cnn"], scale=TINY)
        return res[0]["rows"]

    def test_crash_retries_to_identical_rows(self, clean_rows):
        r = Runner(processes=1, retries=1, backoff_s=0.01,
                   chaos=FaultSpec(seed=5, p_crash=1.0, max_faults=1))
        res = r.run_configs([PRESETS["baseline"]], workloads=["cnn"],
                            scale=TINY)
        assert res[0]["rows"] == clean_rows
        assert r.last_stats["retried"] >= 1
        assert r.last_stats["failed"] == 0

    def test_corrupt_row_detected_and_retried(self, clean_rows):
        r = Runner(processes=1, retries=1, backoff_s=0.01,
                   chaos=FaultSpec(seed=5, p_corrupt=1.0, max_faults=1))
        res = r.run_configs([PRESETS["baseline"]], workloads=["cnn"],
                            scale=TINY)
        assert res[0]["rows"] == clean_rows      # the NaN never escaped
        assert r.last_stats["retried"] >= 1

    def test_inline_oom_degrades_to_fault_not_exit(self, clean_rows):
        # on the serial executor an injected OOM-kill must NOT take the
        # coordinator down (single-CPU hosts auto-select serial)
        r = Runner(processes=1, retries=1, backoff_s=0.01,
                   chaos=FaultSpec(seed=5, p_oom=1.0, max_faults=1))
        res = r.run_configs([PRESETS["baseline"]], workloads=["cnn"],
                            scale=TINY)
        assert res[0]["rows"] == clean_rows

    def test_permanent_failure_is_structured(self):
        r = Runner(processes=1, retries=1, backoff_s=0.01,
                   chaos=FaultSpec(seed=1, p_crash=1.0, max_faults=None))
        res = r.run_configs([PRESETS["baseline"]], workloads=["cnn"],
                            scale=TINY, strict=False)
        fr = res[0]["errors"]["cnn"]
        assert set(schema_mod.FAILURE_ROW_KEYS) <= set(fr)
        assert fr["fault"] == "crash"
        assert fr["attempts"] == 2               # 1 try + 1 retry
        assert "ChaosFault" in fr["traceback"]
        assert fr["config_hash"] == config_hash(PRESETS["baseline"])
        with pytest.raises(RunnerError, match="baseline × cnn"):
            Runner(processes=1, retries=0, chaos=FaultSpec(
                seed=1, p_crash=1.0, max_faults=None)).run_configs(
                [PRESETS["baseline"]], workloads=["cnn"], scale=TINY)


# ---------------------------------------------------------------------------
# graceful degradation: a partially-failed campaign still emits a
# valid, marked artifact — and its consumers warn instead of crash
# ---------------------------------------------------------------------------
def _seed_with_partial_failures(exp):
    """A seed whose unbounded crash schedule kills SOME (not all) cells
    of the experiment — searched deterministically, so the test never
    depends on luck."""
    keys = [f"{config_hash(sp)}:{wl}" for sp in exp.build_configs()
            for wl in exp.workloads]
    for seed in range(200):
        spec = FaultSpec(seed=seed, p_crash=0.5, max_faults=None)
        hit = [k for k in keys if spec.draw(k, 0) == "crash"]
        # unbounded ⇒ attempt 1+ redraws identically (same cell key)
        if hit and len(hit) < len(keys) and all(
                spec.draw(k, a) == "crash" for k in hit for a in (1, 2)):
            return seed
    raise AssertionError("no partial-failure seed in range")


class TestGracefulDegradation:
    def test_degraded_artifact_valid_and_marked(self):
        exp = Experiment(name="degraded", workloads=("cnn",),
                         scale=TINY, processes=1)
        seed = _seed_with_partial_failures(exp)
        r = Runner(processes=1, retries=1, backoff_s=0.01,
                   chaos=FaultSpec(seed=seed, p_crash=0.5,
                                   max_faults=None))
        art = r.run(exp, kind="table")
        art = schema_mod.validate_artifact(art)   # still a valid V1
        failures = art["provenance"]["failures"]
        degraded = art["result"]["degraded"]
        assert failures and degraded
        assert 0 < len(art["rows"]) < 4           # partial, not empty
        for fr in failures:
            assert set(schema_mod.FAILURE_ROW_KEYS) <= set(fr)
            assert fr["fault"] == "crash"
        # the degraded map names exactly the failed (config, workload)s
        assert sorted(degraded) == sorted(
            {fr["config"] for fr in failures})
        assert "fingerprint" in art["provenance"]

    def test_degraded_table_exits_nonzero(self, tmp_path, monkeypatch):
        """`repro table` still writes the marked artifact of a degraded
        campaign, but exits 1: a failed cell must fail the command."""
        from repro.cli import main
        from repro.core import trace as trace_mod
        keys = [f"{config_hash(PRESETS['baseline'])}:{wl}"
                for wl in trace_mod.WORKLOADS]
        seed = next(s for s in range(200) if 0 < sum(
            FaultSpec(seed=s, p_crash=0.5).draw(k, 0) == "crash"
            for k in keys) < len(keys))
        monkeypatch.setenv("REPRO_CHAOS", json.dumps(
            {"seed": seed, "p_crash": 0.5}))
        out = tmp_path / "table.json"
        rc = main(["table", "--scale", str(TINY), "--preset", "baseline",
                   "--processes", "1", "--retries", "0", "--out", str(out),
                   "--journal-dir", str(tmp_path)])
        assert rc == 1
        art = json.loads(out.read_text())
        assert art["provenance"]["failures"] and art["rows"]

    def test_jax_engine_never_fans_out(self, monkeypatch):
        """`--engine jax` under the pool backend runs its cells in the
        coordinator: pool workers would each claim the one chip."""
        monkeypatch.setenv("REPRO_TRACE_CAP", "600")

        def no_pool(*_a, **_k):
            raise AssertionError("jax-engine cells reached the pool")

        monkeypatch.setattr(Runner, "_execute_pool", no_pool)
        res = Runner(processes=4).run_configs(
            [PRESETS["baseline"]], workloads=["cnn", "rnn"],
            scale=0.0123, engine="jax")
        assert set(res[0]["rows"]) == {"cnn", "rnn"}

    def test_all_cells_failed_raises(self):
        exp = Experiment(name="doomed", workloads=("cnn",),
                         scale=TINY, processes=1)
        r = Runner(processes=1, retries=0,
                   chaos=FaultSpec(seed=0, p_crash=1.0, max_faults=None))
        with pytest.raises(RunnerError, match="every cell failed"):
            r.run(exp, kind="table")

    def test_trend_ok_skips_incomplete_ladder(self, capsys):
        from repro.core.calibration import trend_ok
        complete = {name: {"latency_ns": 100.0 - i,
                           "bandwidth_gbps": 25.0 + i,
                           "hit_rate": 0.6 + i / 10,
                           "energy_uj": 50.0 - i}
                    for i, name in enumerate(schema_mod.LADDER)}
        assert trend_ok(complete) is True
        missing_row = {k: v for k, v in complete.items()
                       if k != "prefetch"}
        assert trend_ok(missing_row) is False     # warns, no KeyError
        missing_col = json.loads(json.dumps(complete))
        del missing_col["tensor_aware"]["hit_rate"]
        assert trend_ok(missing_col) is False
        assert "degraded" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# the resilient pool: deadline reaping + worker-death requeue
# (processes forced to 2 so the pool engages even on 1-CPU hosts)
# ---------------------------------------------------------------------------
class TestResilientPool:
    def test_hung_cell_reaped_and_retried(self):
        ch = FaultSpec(seed=2, p_hang=1.0, hang_s=300.0, max_faults=1)
        r = Runner(processes=2, retries=1, backoff_s=0.01, chaos=ch,
                   cell_timeout=6.0)
        res = r.run_configs([PRESETS["baseline"]],
                            workloads=["cnn", "rnn"], scale=TINY)
        assert "errors" not in res[0]
        assert set(res[0]["rows"]) == {"cnn", "rnn"}
        assert r.last_stats["timeouts"] >= 1

    def test_oom_killed_worker_requeued(self):
        ch = FaultSpec(seed=2, p_oom=1.0, max_faults=1)
        r = Runner(processes=2, retries=1, backoff_s=0.01, chaos=ch)
        res = r.run_configs([PRESETS["baseline"]],
                            workloads=["cnn", "rnn"], scale=TINY)
        assert "errors" not in res[0]
        assert r.last_stats["worker_deaths"] >= 1

    def test_pool_rows_identical_to_serial(self):
        serial = Runner(processes=1).run_configs(
            [PRESETS["baseline"]], workloads=["cnn", "rnn"], scale=TINY)
        pool = Runner(processes=2).run_configs(
            [PRESETS["baseline"]], workloads=["cnn", "rnn"], scale=TINY)
        assert serial[0]["rows"] == pool[0]["rows"]


# ---------------------------------------------------------------------------
# journaled resume
# ---------------------------------------------------------------------------
class TestJournalResume:
    CFGS = [PRESETS["baseline"], PRESETS["shared_l3"]]

    def test_truncated_journal_resumes_identically(self, tmp_path):
        jp = tmp_path / "c.journal.jsonl"
        base = Runner(processes=1).run_configs(
            self.CFGS, scale=TINY, journal_path=jp)
        lines = jp.read_text().splitlines()
        assert len(lines) == 1 + 6               # header + 2 cfg × 3 wl
        # simulate a kill -9 after 3 cells (plus a torn partial line)
        jp.write_text("\n".join(lines[:4]) + "\n" + lines[4][:17])
        r = Runner(processes=1)
        res = r.run_configs(self.CFGS, scale=TINY, journal_path=jp,
                            resume=True)
        assert r.last_stats["resumed"] == 3
        for a, b in zip(base, res):
            assert a["rows"] == b["rows"]
            assert a["aggregate"] == b["aggregate"]

    def test_mismatched_journal_ignored(self, tmp_path, capsys):
        jp = tmp_path / "c.journal.jsonl"
        Runner(processes=1).run_configs(self.CFGS, workloads=["cnn"],
                                        scale=TINY, journal_path=jp)
        # same journal file, different campaign (another workload set)
        r = Runner(processes=1)
        r.run_configs(self.CFGS, workloads=["rnn"], scale=TINY,
                      journal_path=jp, resume=True)
        assert r.last_stats["resumed"] == 0
        assert "does not match" in capsys.readouterr().err

    def test_journal_resume_entries_keyed_by_value_hash(self, tmp_path):
        # two sweep points named identically ("prefetch") must not
        # collide in the journal — identity is the config value hash
        import dataclasses as dc
        a = PRESETS["prefetch"]
        b = dc.replace(a, prefetch=dc.replace(a.prefetch, degree=4))
        assert a.name == b.name and a != b
        jp = tmp_path / "c.journal.jsonl"
        res = Runner(processes=1).run_configs(
            [a, b], workloads=["cnn"], scale=TINY, journal_path=jp)
        entries = [json.loads(line) for line
                   in jp.read_text().splitlines()[1:]]
        assert len({e["config_hash"] for e in entries}) == 2
        assert res[0]["rows"]["cnn"] != res[1]["rows"]["cnn"]


# ---------------------------------------------------------------------------
# Runner.map: unified structured failure path with retries
# ---------------------------------------------------------------------------
class TestMapResilience:
    def test_map_retry_then_success(self):
        calls = {"n": 0}

        def flaky(x):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("transient")
            return x * 2

        r = Runner(backoff_s=0.01)
        out = r.map(flaky, [(21,)], label="flaky", retries=1)
        assert out[0] == {"status": "ok", "value": 42, "attempts": 2}

    def test_map_failure_is_structured(self):
        def boom(x):
            raise ValueError(f"bad {x}")

        out = Runner(backoff_s=0.01).map(boom, [(1,)], label="boom",
                                         retries=1)
        assert out[0]["status"] == "error"
        assert out[0]["attempts"] == 2
        assert "ValueError: bad 1" in out[0]["error"]
        assert "Traceback" in out[0]["traceback"]
        fr = out[0]["failure"]
        assert set(schema_mod.FAILURE_ROW_KEYS) <= set(fr)
        assert fr["config"] == "boom[0]"


# ---------------------------------------------------------------------------
# the acceptance drill: kill -9 mid-campaign, --resume, bit-identical
# ---------------------------------------------------------------------------
def test_kill_resume_e2e():
    """Runs tests/e2e_kill_resume.py — the same script the CI chaos
    gate executes: baseline sweep, a run hard-killed mid-campaign via
    REPRO_CHAOS kill_after_cells, then --resume; the resumed artifact's
    fingerprint/rows/result must equal the baseline's bit-for-bit."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "tests" / "e2e_kill_resume.py")],
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    assert "KILL-RESUME E2E PASS" in proc.stdout
