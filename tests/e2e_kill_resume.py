"""Kill-and-resume acceptance drill (the CI chaos gate's second leg).

Stages the crash the journal exists for, end-to-end through the real
CLI, and asserts the recovery is *bit-identical*:

1. an undisturbed ``repro sweep --smoke`` produces the reference
   artifact (its journal retires on success);
2. the same campaign is re-run with ``REPRO_CHAOS`` set to
   ``kill_after_cells`` — the coordinator hard-exits with code 137
   (``kill -9`` semantics) mid-campaign, leaving a journal behind;
3. the campaign is re-run with ``--resume`` — it must pick up the
   journal, run only the missing cells, exit 0, retire the journal,
   and emit an artifact whose ``provenance.fingerprint`` / ``rows`` /
   ``result`` equal the reference bit-for-bit.

Standalone on purpose (``python tests/e2e_kill_resume.py``): CI runs it
directly, and tests/test_chaos.py wraps it as a pytest case.  The
``__main__`` guard is load-bearing — the sweep spawns worker processes.
"""

import json
import os
import shutil
import subprocess
import sys
import tempfile
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
SWEEP_ARGS = ["sweep", "--smoke", "--scale", "0.004"]
KILL_AFTER = 9


def run_cli(argv, chaos=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    env.pop("REPRO_CHAOS", None)
    if chaos is not None:
        env["REPRO_CHAOS"] = json.dumps(chaos)
    proc = subprocess.run([sys.executable, "-m", "repro", *argv],
                          cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=600)
    sys.stdout.write(proc.stdout[-1500:])
    sys.stderr.write(proc.stderr[-1500:])
    return proc.returncode


def load(path):
    art = json.loads(Path(path).read_text())
    return (art["provenance"]["fingerprint"], art["rows"], art["result"])


def main():
    tmp = Path(tempfile.mkdtemp(prefix="kill_resume_"))
    try:
        drill(tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def drill(tmp):
    ref_path = tmp / "reference.json"
    res_path = tmp / "resumed.json"
    # a private journal directory: nothing else writes there, and the
    # drill leaves nothing in the repository's artifacts/
    journal_dir = tmp / "journal"
    sweep_args = SWEEP_ARGS + ["--journal-dir", str(journal_dir)]

    print("[e2e] 1/3 undisturbed reference run", flush=True)
    rc = run_cli(sweep_args + ["--out", str(ref_path)])
    assert rc == 0, f"reference run failed: exit {rc}"
    assert not list(journal_dir.glob("*.journal.jsonl")), \
        "journal must retire after a fully-successful campaign"

    print(f"[e2e] 2/3 kill -9 after {KILL_AFTER} cells", flush=True)
    rc = run_cli(sweep_args + ["--out", str(tmp / "never_written.json")],
                 chaos={"seed": 5, "kill_after_cells": KILL_AFTER})
    assert rc == 137, f"expected hard-kill exit 137, got {rc}"
    journals = list(journal_dir.glob("*.journal.jsonl"))
    assert len(journals) == 1, f"expected one orphan journal: {journals}"
    n_done = len(journals[0].read_text().splitlines()) - 1  # minus header
    assert n_done == KILL_AFTER, \
        f"journal holds {n_done} cells, expected {KILL_AFTER}"
    assert not (tmp / "never_written.json").exists(), \
        "killed run must not emit an artifact"

    print("[e2e] 3/3 --resume from the orphan journal", flush=True)
    rc = run_cli(sweep_args + ["--resume", "--out", str(res_path)])
    assert rc == 0, f"resume run failed: exit {rc}"
    assert not list(journal_dir.glob("*.journal.jsonl")), \
        "journal must retire after the resumed campaign completes"

    ref_fp, ref_rows, ref_result = load(ref_path)
    res_fp, res_rows, res_result = load(res_path)
    assert res_rows == ref_rows, "resumed rows differ from reference"
    assert res_result == ref_result, "resumed result differs"
    assert res_fp == ref_fp, \
        f"fingerprint mismatch: {ref_fp[:16]}… vs {res_fp[:16]}…"
    print(f"[e2e] fingerprints equal ({ref_fp[:16]}…), "
          f"{n_done} cells resumed from journal")
    print("KILL-RESUME E2E PASS")


if __name__ == "__main__":
    main()
