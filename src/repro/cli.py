"""``python -m repro`` — one front door over sim, sweep, plan, launch.

Subcommands (shared flags: ``--smoke`` / ``--scale`` / ``--preset`` /
``--set k=v`` / ``--engine`` / ``--processes`` / ``--no-native`` /
``--out``):

    repro table    paper Tables I–III over the preset ladder
    repro sweep    design-space grid sweep (Pareto front + retune hint)
    repro plan     capacity pass (mitigation ladder) over dry-run cells
    repro dryrun   lower + compile the (arch × shape × mesh) matrix
    repro train    training launcher (delegates to repro.launch.train)
    repro serve    serving launcher (delegates to repro.launch.serve)
    repro bench    engine throughput; ``--smoke`` = the CI gate bundle
                   (table + sweep + plan smokes)
    repro lint     invariant-enforcing static analysis (engine parity,
                   determinism, schema, jax trace hygiene); exits
                   nonzero on unsuppressed findings

Every artifact written lands under ``artifacts/`` as a validated
ArtifactV1 (see ``repro.api.schema``).  The legacy module entry points
(``python -m benchmarks.run`` / ``benchmarks.sweep`` /
``repro.launch.dryrun``) still work but are thin shims over this CLI.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

REPO_ROOT = Path(__file__).resolve().parents[2]
ARTIFACTS = REPO_ROOT / "artifacts"

SMOKE_SCALE = 0.02


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------
def _add_sim_flags(ap: argparse.ArgumentParser,
                   preset_flag: bool = True) -> None:
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-scale CI run (seconds)")
    ap.add_argument("--scale", type=float, default=None,
                    help=f"workload scale (default 1.0; {SMOKE_SCALE} "
                         f"under --smoke)")
    ap.add_argument("--engine", default="soa",
                    choices=["reference", "object", "soa", "native",
                             "jax"])
    ap.add_argument("--backend", default="pool",
                    choices=["pool", "batched"],
                    help="execution backend: 'pool' fans cells out over "
                         "worker processes (the jax engine runs in this "
                         "process instead: one process per chip); "
                         "'batched' runs whole config batches as one "
                         "vmapped jax device program")
    ap.add_argument("--processes", type=int, default=None,
                    help="worker processes (default: auto)")
    ap.add_argument("--no-native", action="store_true",
                    help="force the pure-Python SoA path")
    ap.add_argument("--set", dest="sets", action="append", default=[],
                    metavar="PATH=VALUE",
                    help="dotted-path override, e.g. prefetch.degree=3 "
                         "or ta.low_utility=0.2 (repeatable)")
    ap.add_argument("--out", default=None, help="artifact path override")
    ap.add_argument("--retries", type=int, default=None,
                    help="retry budget per cell (default 2); transient "
                         "failures back off exponentially with jitter")
    ap.add_argument("--cell-timeout", type=float, default=None,
                    help="explicit per-cell wall-clock deadline in "
                         "seconds (the adaptive rolling-median deadline "
                         "applies regardless)")
    ap.add_argument("--resume", action="store_true",
                    help="resume an interrupted campaign from its "
                         "journal (<journal-dir>/"
                         "<spec_hash>.journal.jsonl)")
    ap.add_argument("--journal-dir", default=None,
                    help="directory of the campaign journal (default "
                         "artifacts/<kind>/)")
    if preset_flag:
        ap.add_argument("--preset", default=None,
                        help="run one hierarchy preset instead of the "
                             "full ladder")


def _resolve_scale(args: argparse.Namespace) -> float:
    if args.scale is not None:
        return args.scale
    return SMOKE_SCALE if args.smoke else 1.0


def _write_artifact(art: Dict[str, Any], default_path: Path,
                    out: Optional[str]) -> Path:
    path = Path(out) if out else default_path
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(art, indent=1))
    print(f"[repro] wrote {path}")
    return path


# ---------------------------------------------------------------------------
# repro table
# ---------------------------------------------------------------------------
def _print_aggregate_table(aggregates: Dict[str, Dict[str, float]]) -> None:
    from repro.api.schema import AGG_COLUMNS
    from repro.core.presets import PAPER_TABLE

    print(f"\n{'config':14s} " + "".join(f"{m:>26s}" for m in AGG_COLUMNS))
    for cfg, agg in aggregates.items():
        cells = []
        for m in AGG_COLUMNS:
            pub = PAPER_TABLE.get(cfg, {}).get(m)
            cells.append(f"{agg[m]:9.2f} (paper {pub:7.2f})" if pub
                         else f"{agg[m]:9.2f} {'':15s}")
        print(f"{cfg:14s} " + "".join(f"{c:>26s}" for c in cells))


def run_table(scale: float, engine: str = "soa", native: bool = True,
              processes: Optional[int] = None,
              preset: Optional[str] = None,
              overrides: Optional[Dict[str, Any]] = None,
              out: Optional[str] = None,
              retries: Optional[int] = None,
              cell_timeout: Optional[float] = None,
              resume: bool = False, backend: str = "pool",
              journal_dir: Optional[str] = None,
              tool: str = "python -m repro table") -> Dict[str, Any]:
    """The `repro table` body — also the programmatic front door."""
    from repro.api.runner import Runner
    from repro.api.schema import LADDER
    from repro.api.spec import Experiment, HierarchySpec, ladder_specs
    from repro.core.calibration import report_vs_paper

    if preset is not None:
        hierarchies = (HierarchySpec.from_preset(preset,
                                                 overrides=overrides),)
    else:
        hierarchies = ladder_specs(overrides)
    name = f"scale{scale:g}" + (f"_{preset}" if preset else "")
    exp = Experiment(name=name, hierarchies=hierarchies, scale=scale,
                     engine=engine, native=native, processes=processes,
                     backend=backend)
    t0 = time.time()
    runner = Runner(processes=processes, cell_timeout=cell_timeout,
                    **({} if retries is None else {"retries": retries}))
    art = runner.run(exp, kind="table", tool=tool,
                     journal_dir=Path(journal_dir or ARTIFACTS / "table"),
                     resume=resume)
    aggregates = art["result"]["aggregates"]
    _print_aggregate_table(aggregates)

    degraded = art["result"].get("degraded")
    if degraded:
        print(f"[repro] WARNING: degraded campaign — failed cells "
              f"{degraded}; skipping the paper comparison "
              f"(provenance.failures has the structured rows)",
              file=sys.stderr)
    elif tuple(aggregates) == LADDER and len(exp.workloads) == 3:
        # full ladder × full suite: trend verdict + full-scale hard
        # gate + paper comparison (one definition in core.calibration)
        report_vs_paper(aggregates, scale, engine=engine,
                        elapsed_s=time.time() - t0)
    _write_artifact(art, ARTIFACTS / "table" / f"table_{name}.json", out)
    return art


def _degraded_rc(art: Dict[str, Any], cmd: str) -> int:
    """Exit status of a campaign: 1 when any cell failed permanently."""
    failures = art["provenance"].get("failures", [])
    if failures:
        print(f"[{cmd}] FAILED: degraded campaign, {len(failures)} "
              f"cell(s) permanently failed", file=sys.stderr)
        return 1
    return 0


def cmd_table(args: argparse.Namespace) -> int:
    from repro.api.registry import parse_set
    art = run_table(_resolve_scale(args), engine=args.engine,
                    native=not args.no_native, processes=args.processes,
                    preset=args.preset,
                    overrides=parse_set(args.sets) or None,
                    out=args.out, retries=args.retries,
                    cell_timeout=args.cell_timeout, resume=args.resume,
                    backend=args.backend, journal_dir=args.journal_dir)
    return _degraded_rc(art, "table")


# ---------------------------------------------------------------------------
# repro sweep
# ---------------------------------------------------------------------------
def run_sweep(scale: float, axes: Dict[str, list], tag: str,
              engine: str = "soa", native: bool = True,
              processes: Optional[int] = None, out: Optional[str] = None,
              retries: Optional[int] = None,
              cell_timeout: Optional[float] = None,
              resume: bool = False, backend: str = "pool",
              journal_dir: Optional[str] = None,
              workloads: Optional[List[str]] = None,
              runner: Any = None,
              tool: str = "python -m repro sweep") -> Dict[str, Any]:
    """Grid sweep of the four-row ladder; writes an ArtifactV1 whose
    ``result`` is the full sweep payload (points, Pareto front,
    recommended retune).

    The campaign journals under ``<journal_dir>/<spec_hash>
    .journal.jsonl`` (default ``artifacts/sweep/``); an interrupted run restarts with ``resume=True``
    and yields an artifact whose deterministic content (fingerprint) is
    bit-identical to an uninterrupted run.
    """
    from repro.api.schema import (AGG_COLUMNS, artifact_fingerprint,
                                  artifact_v1, spec_hash)
    from repro.sweep.driver import run_ladder_sweep
    from repro.sweep.grid import enumerate_grid, grid_size

    points = enumerate_grid(axes)
    # engine/native/backend are execution strategy, not result identity:
    # they live in provenance, so the same grid swept by any engine
    # yields the same spec_hash AND the same artifact fingerprint (all
    # engines are bit-identical by contract; CI asserts it)
    spec = {"name": tag, "grid": {k: list(v) for k, v in axes.items()},
            "scale": scale}
    if workloads is not None:
        spec["workloads"] = list(workloads)
    journal_path = (Path(journal_dir or ARTIFACTS / "sweep")
                    / f"{spec_hash(spec)[7:19]}.journal.jsonl")
    print(f"[sweep] {grid_size(axes)} points × 4-row ladder @ "
          f"scale={scale}, engine={engine}, backend={backend}")
    t0 = time.time()
    payload = run_ladder_sweep(points, scale=scale, engine=engine,
                               processes=processes, native=native,
                               retries=retries, cell_timeout=cell_timeout,
                               journal_path=journal_path, resume=resume,
                               backend=backend, workloads=workloads,
                               runner=runner)
    dt = time.time() - t0
    # failures and wall time are measurements of the run, not the
    # result — they live in provenance so resumed artifacts fingerprint
    # identically to uninterrupted ones
    failures = payload.pop("failures", [])
    payload["axes"] = spec["grid"]

    n_front = len(payload["pareto_front"])
    print(f"[sweep] {payload['n_points']} ladders "
          f"({payload['n_unique_configs']} unique configs) in {dt:.1f}s — "
          f"{payload['n_trend_ok']} trend-ok, {n_front} on the Pareto "
          f"front")
    for i in payload["pareto_front"]:
        r = payload["points"][i]
        ta = r["rows"]["tensor_aware"]
        print(f"  pareto{'*' if r['trend_ok'] else ' '} "
              f"lat={ta['latency_ns']:7.3f} bw={ta['bandwidth_gbps']:7.3f} "
              f"hit={ta['hit_rate']:.4f} en={ta['energy_uj']:7.3f}  "
              f"{r['label']}")
    rec = payload["recommended"]
    if rec is not None:
        print(f"[sweep] recommended (trend-ok, max hit rate): "
              f"{rec['label']}")
    else:
        print("[sweep] no trend-restoring point in this grid")

    # degraded points have no complete tensor_aware row — they cannot
    # appear as metric rows (the validator requires finite values);
    # they stay in result.points marked degraded_rows
    rows = [{"label": r["label"], "trend_ok": r["trend_ok"],
             "pareto": r["pareto"],
             **{m: r["rows"]["tensor_aware"][m] for m in AGG_COLUMNS}}
            for r in payload["points"] if "degraded_rows" not in r]
    from repro.core.native import resolve_engine
    provenance = {"tool": tool, "engine": engine,
                  "engine_resolved": ("jax" if backend == "batched"
                                      else resolve_engine(engine)),
                  "backend": backend,
                  "wall_s": round(dt, 2),
                  "created_unix": int(time.time())}
    if failures:
        provenance["failures"] = failures
        print(f"[sweep] WARNING: degraded campaign — "
              f"{payload['n_degraded_points']} point(s) incomplete, "
              f"{len(failures)} cell(s) permanently failed "
              f"(provenance.failures has the structured rows)",
              file=sys.stderr)
    art = artifact_v1("sweep", spec, rows, result=payload,
                      provenance=provenance)
    art["provenance"]["fingerprint"] = artifact_fingerprint(art)
    _write_artifact(art, ARTIFACTS / "sweep" / f"sweep_{tag}.json", out)
    if journal_path.exists() and not failures:
        journal_path.unlink()     # campaign complete: journal retired
    return art


def cmd_sweep(args: argparse.Namespace) -> int:
    import math

    from repro.api.registry import SWEEP_GRIDS, parse_set
    from repro.sweep.grid import grid_size

    if args.grid:
        axes = dict(SWEEP_GRIDS[args.grid])
    else:
        axes = dict(SWEEP_GRIDS["smoke" if args.smoke else "full"])
    sets = parse_set(args.sets)
    for path, value in sets.items():
        axes[path] = value if isinstance(value, list) else [value]
    scale = _resolve_scale(args)
    tag = (f"{args.grid}_scale{scale:g}" if args.grid
           else "smoke" if args.smoke else f"scale{scale:g}")
    art = run_sweep(scale, axes, tag, engine=args.engine,
                    native=not args.no_native, processes=args.processes,
                    out=args.out, retries=args.retries,
                    cell_timeout=args.cell_timeout, resume=args.resume,
                    backend=args.backend, journal_dir=args.journal_dir)
    rc = _degraded_rc(art, "sweep")
    if rc:
        return rc
    if args.smoke:
        # acceptance gate: every grid point evaluated, every ladder row
        # carries finite positive metrics (a NaN/garbage regression in
        # the sweep path must fail CI, and a non-empty front alone
        # cannot — one always exists)
        payload = art["result"]
        assert payload["n_points"] == grid_size(axes), payload["n_points"]
        for r in payload["points"]:
            for cfg, row in r["rows"].items():
                assert all(math.isfinite(v) and v > 0
                           for v in row.values()), (r["label"], cfg, row)
        assert payload["pareto_front"], "empty Pareto front"
    return 0


# ---------------------------------------------------------------------------
# repro plan / dryrun  (jax: import repro.launch.dryrun FIRST — it sets
# the 512-device XLA host platform before jax initializes)
# ---------------------------------------------------------------------------
def _plan_smoke() -> int:
    """The CI capacity gate: the smallest known over-budget cell must
    plan under the 16 GiB/device budget via re-lowered mitigations."""
    from repro.launch.dryrun import plan_cell_pass
    from repro.plan.capacity import BUDGET_BYTES

    rec = plan_cell_pass("gemma-2b", "prefill_32k", False, save=False)
    plan = rec["plan"]
    print(f"[plan] smoke verdict: {plan['verdict']} | after GiB: "
          f"{plan['after_peak_bytes'] / 2**30:.2f} | rungs: "
          f"{plan['rungs']}")
    assert plan["verdict"] == "fits", plan
    assert plan["after_peak_bytes"] <= BUDGET_BYTES, plan
    return 0


def _dryrun_argv(args: argparse.Namespace, plan: bool) -> List[str]:
    argv: List[str] = ["--plan"] if plan else []
    if args.all:
        argv.append("--all")
    if args.arch:
        argv += ["--arch", args.arch]
    if args.shape:
        argv += ["--shape", args.shape]
    argv += ["--mesh", args.mesh]
    if getattr(args, "force", False):
        argv.append("--force")
    return argv


def cmd_plan(args: argparse.Namespace) -> int:
    if args.smoke:
        return _plan_smoke()
    from repro.launch.dryrun import main as dryrun_main
    dryrun_main(_dryrun_argv(args, plan=True))
    return 0


def cmd_dryrun(args: argparse.Namespace) -> int:
    from repro.launch.dryrun import main as dryrun_main
    dryrun_main(_dryrun_argv(args, plan=False))
    return 0


def _add_cell_flags(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: plan the gemma-2b × prefill_32k cell")


# ---------------------------------------------------------------------------
# repro train / serve (thin delegations)
# ---------------------------------------------------------------------------
def run_launcher(cmd: str, rest: List[str]) -> int:
    """``repro train|serve …`` — everything after the subcommand goes
    verbatim to the launcher's own argparse (so ``repro train --help``
    shows the launcher's flags)."""
    if cmd == "train":
        from repro.launch.train import main as launcher_main
    else:
        from repro.launch.serve import main as launcher_main
    launcher_main(rest)
    return 0


# ---------------------------------------------------------------------------
# repro bench
# ---------------------------------------------------------------------------
def cmd_bench(args: argparse.Namespace) -> int:
    from repro.api.bench import bench_engines

    if not args.smoke:
        scale = args.scale if args.scale is not None else 0.05
        bench_engines(scale=scale, native=not args.no_native)
        return 0

    # --smoke: the CI gate bundle — table + sweep + plan, one command.
    scale = args.scale if args.scale is not None else SMOKE_SCALE
    print(f"[bench] gate 1/3: table --smoke (scale={scale:g})")
    art = run_table(scale, engine=args.engine, native=not args.no_native,
                    processes=args.processes,
                    tool="python -m repro bench --smoke")
    rc = _degraded_rc(art, "bench")
    if rc:
        return rc
    print(f"\n== engine throughput (reference vs soa) ==")
    bench_engines(scale=scale, native=not args.no_native)

    print(f"\n[bench] gate 2/3: sweep --smoke (scale={scale:g})")
    # through the real sweep parser, so the gate can never drift from
    # what `repro sweep --smoke` itself accepts
    sweep_argv = ["sweep", "--smoke", "--scale", str(scale),
                  "--engine", args.engine, "--backend", args.backend]
    if args.no_native:
        sweep_argv.append("--no-native")
    if args.processes is not None:
        sweep_argv += ["--processes", str(args.processes)]
    rc = main(sweep_argv)
    if rc:
        return rc

    if args.skip_plan:
        print("\n[bench] gate 3/3: plan --smoke SKIPPED (--skip-plan)")
        return 0
    # the plan gate lowers on the 512-device CPU host platform, which
    # must be set before jax starts — hence its own process, which pins
    # itself to the CPU (launch/dryrun.py) and so never claims a chip
    print("\n[bench] gate 3/3: plan --smoke (subprocess: needs the "
          "512-device XLA host platform)")
    import subprocess
    env = dict(os.environ)
    src = str(REPO_ROOT / "src")
    env["PYTHONPATH"] = src + (os.pathsep + env["PYTHONPATH"]
                               if env.get("PYTHONPATH") else "")
    proc = subprocess.run([sys.executable, "-m", "repro", "plan",
                           "--smoke"], env=env)
    if proc.returncode != 0:
        print("[bench] plan gate FAILED", file=sys.stderr)
        return proc.returncode
    print("[bench] all gates passed")
    return 0


# ---------------------------------------------------------------------------
# repro lint
# ---------------------------------------------------------------------------
def run_lint_cli(rules: Optional[List[str]] = None,
                 as_json: bool = False, out: Optional[str] = None,
                 src_root: Optional[Path] = None,
                 tool: str = "python -m repro lint") -> int:
    """The ``repro lint`` body: run the rule catalog over ``src/``,
    print findings, write the lint ArtifactV1, exit nonzero on any
    unsuppressed finding."""
    from repro.analysis import RULES, run_lint
    from repro.analysis.base import ProjectContext
    from repro.api.schema import artifact_v1

    root = Path(src_root) if src_root else REPO_ROOT / "src"
    ctx = ProjectContext(root)
    try:
        findings = run_lint(ctx, only=rules or None)
    except KeyError as e:
        print(f"[lint] {e.args[0]}", file=sys.stderr)
        return 2
    rows = [f.as_row() for f in findings]
    unsuppressed = [f for f in findings if not f.suppressed]
    suppressed = [f for f in findings if f.suppressed]

    if as_json:
        print(json.dumps(rows, indent=1))
    else:
        for f in unsuppressed:
            print(f"{f.location()}: {f.severity}[{f.rule}] {f.message}")
        print(f"[lint] {len(list(RULES if not rules else rules))} "
              f"rule(s) over {len(ctx.loaded_files())} file(s): "
              f"{len(unsuppressed)} finding(s), "
              f"{len(suppressed)} suppressed")

    spec = {"name": "lint", "root": "src",
            "rules": sorted(rules) if rules else sorted(RULES)}
    by_sev = {"error": 0, "warning": 0}
    for f in unsuppressed:
        by_sev[f.severity] = by_sev.get(f.severity, 0) + 1
    art = artifact_v1(
        "lint", spec, rows,
        result={"n_findings": len(unsuppressed),
                "n_suppressed": len(suppressed),
                "by_severity": by_sev,
                "clean": not unsuppressed},
        provenance={"tool": tool})
    _write_artifact(art, ARTIFACTS / "lint" / "lint.json", out)
    return 1 if unsuppressed else 0


def cmd_lint(args: argparse.Namespace) -> int:
    return run_lint_cli(rules=args.rule, as_json=args.json,
                        out=args.out)


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------
def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:]) if argv is None else list(argv)
    # pass-through launchers: argparse REMAINDER cannot forward leading
    # optionals (`repro train --arch …`), so intercept before parsing
    if argv and argv[0] in ("train", "serve"):
        return run_launcher(argv[0], argv[1:])

    ap = argparse.ArgumentParser(
        prog="repro",
        description="HERMES reproduction — one front door over sim, "
                    "sweep, plan, and launch")
    sub = ap.add_subparsers(dest="cmd", required=True)

    t = sub.add_parser("table", help="paper Tables I–III over the "
                                     "preset ladder")
    _add_sim_flags(t)
    t.set_defaults(func=cmd_table)

    s = sub.add_parser("sweep", help="design-space grid sweep")
    _add_sim_flags(s, preset_flag=False)
    s.add_argument("--grid", default=None, choices=[None, "full", "smoke",
                                                    "stream_rank"],
                   help="named grid (--set path=[v1,v2] adds/overrides "
                        "an axis)")
    s.set_defaults(func=cmd_sweep)

    p = sub.add_parser("plan", help="capacity pass over dry-run cells")
    _add_cell_flags(p)
    p.set_defaults(func=cmd_plan)

    d = sub.add_parser("dryrun", help="lower + compile the "
                                      "(arch × shape × mesh) matrix")
    _add_cell_flags(d)
    d.set_defaults(func=cmd_dryrun)

    # stubs so `repro --help` lists them; parsing is intercepted above
    sub.add_parser("train", add_help=False,
                   help="training launcher (args pass through)")
    sub.add_parser("serve", add_help=False,
                   help="serving launcher (args pass through)")

    ln = sub.add_parser("lint", help="invariant-enforcing static "
                                     "analysis; exits nonzero on "
                                     "unsuppressed findings")
    ln.add_argument("--rule", action="append", default=[],
                    metavar="ID",
                    help="run only this rule id (repeatable, e.g. "
                         "--rule EP001); default: full catalog")
    ln.add_argument("--json", action="store_true",
                    help="print findings as JSON rows instead of text")
    ln.add_argument("--out", default=None,
                    help="artifact path override "
                         "(default artifacts/lint/lint.json)")
    ln.set_defaults(func=cmd_lint)

    b = sub.add_parser("bench", help="engine throughput bench; --smoke "
                                     "= table+sweep+plan CI gates")
    b.add_argument("--smoke", action="store_true",
                   help="run the CI gate bundle instead of the bench")
    b.add_argument("--scale", type=float, default=None,
                   help="workload scale (default 0.05; "
                        f"{SMOKE_SCALE} under --smoke)")
    b.add_argument("--engine", default="soa",
                   choices=["reference", "object", "soa", "native",
                            "jax"],
                   help="engine for the --smoke table/sweep gates (the "
                        "throughput bench always measures both)")
    b.add_argument("--backend", default="pool",
                   choices=["pool", "batched"],
                   help="execution backend for the --smoke sweep gate "
                        "and the jax rows of the throughput bench")
    b.add_argument("--processes", type=int, default=None,
                   help="worker processes for the --smoke gates")
    b.add_argument("--no-native", action="store_true",
                   help="force the pure-Python SoA path")
    b.add_argument("--skip-plan", action="store_true",
                   help="under --smoke: skip the (slow, jax-lowering) "
                        "plan gate")
    b.set_defaults(func=cmd_bench)

    args = ap.parse_args(argv)
    from repro.api.runner import RunnerInterrupted
    try:
        return args.func(args)
    except RunnerInterrupted as e:
        hint = (f" — resume with --resume (journal: {e.journal_path})"
                if e.journal_path else "")
        print(f"[repro] interrupted: {e}{hint}", file=sys.stderr)
        return 130


if __name__ == "__main__":
    raise SystemExit(main())
