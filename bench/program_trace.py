"""What the program records of itself, read for the benchmark: its host
spans (``repro.runtime.spans``, named ``hermes.<phase>``) per traced
campaign, and, from a profiler trace, the device time of its scan step
per component scope (``jax.named_scope("hermes.<part>")``) and the idle
time per innermost host span, the benchmark's and the program's.

    python bench/program_trace.py --workload <cell> --seed <n>

runs on the chip one campaign of the cell to compile it, then one more
under the profiler, and prints that campaign's scope table and its idle
time per span.
The device's clock and the host's agree to about a millisecond, so
idle time that near a span's edge may be put on the neighbouring span.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import re
import shutil
import sys
import tempfile
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

import profile_reduce as pr  # noqa: E402  (bench/ is on sys.path)

#: prefixes of the host spans: the benchmark's and the program's
SPAN_PREFIXES = ("bench.", "hermes.")
#: the program's component scopes in op names
SCOPE_PREFIX = "hermes."
UNSCOPED = "unscoped"
OUTSIDE = "outside bench spans"
#: lines of a device plane whose events are the operations inside
#: programs, in time order; a control-flow operation (``while``) spans
#: the operations of its body
OP_EVENT_LINES = ("XLA Ops",)
#: operation events read from the scan program's first run
SCOPE_BUDGET = 200_000


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------
def span_ms_per_campaign(run, names: Sequence[str]) -> Optional[float]:
    """Milliseconds of the program's spans named ``names`` (without the
    ``hermes.`` prefix) inside each traced campaign's ``[t0, t1]``,
    summed per campaign, mean over the traced campaigns; None where the
    program records no such span.  The traced campaign is the one whose
    device trace the other per-layer metrics read; the campaigns after
    it are left out, because the first of them runs on the heels of the
    profiler's stop, and a stall there falls in whichever span is
    open."""
    try:
        from repro.runtime.spans import SPANS
    except ImportError:
        return None
    spans = [s for s in SPANS if s.name in names]
    per, seen = [], False
    for c in run.traced:
        inside = [s.t1 - s.t0 for s in spans
                  if c.t0 <= s.t0 and s.t1 <= c.t1]
        seen = seen or bool(inside)
        per.append(sum(inside))
    return sum(per) / len(per) * 1e3 if seen else None


def _innermost(spans: Sequence[Tuple[str, float, float]], t: float) -> str:
    """The name of the shortest span covering the instant ``t``."""
    cover = [(e - s, n) for n, s, e in spans if s <= t < e]
    return min(cover)[1] if cover else OUTSIDE


def idle_by_span(idle: Sequence[pr.Interval],
                 spans: Sequence[Tuple[str, float, float]]
                 ) -> List[Tuple[str, float]]:
    """Every idle instant put down to the innermost span covering it, the
    idle ns summed per span name, longest first.  A gap that crosses
    spans is split at their edges (a gap's midpoint alone would give
    all of it to one span)."""
    ns: collections.Counter = collections.Counter()
    for lo, hi in idle:
        cuts = sorted({lo, hi} | {t for _, s, e in spans for t in (s, e)
                                  if lo < t < hi})
        for a, b in zip(cuts, cuts[1:]):
            ns[_innermost(spans, (a + b) / 2)] += b - a
    return sorted(ns.items(), key=lambda x: -x[1])


# ---------------------------------------------------------------------------
# component scopes
# ---------------------------------------------------------------------------
def op_scopes(hlo_texts: Iterable[str]) -> Dict[str, str]:
    """Instruction name -> the innermost component scope
    (``hermes.<part>``) of its ``op_name``, from compiled programs' HLO
    text (``Compiled.as_text()``).  A fusion carries its root's
    ``op_name``, so a fused operation counts to its root's scope.  An
    instruction whose ``op_name`` names no component, or that has none
    (copies and loop glue the compiler inserts), is left out."""
    out: Dict[str, str] = {}
    for text in hlo_texts:
        for name, op_name in re.findall(
                r'^\s*(?:ROOT )?%([\w.\-]+) = [^\n]*?'
                r'metadata=\{op_name="([^"]*)"', text, re.M):
            scopes = [c for c in op_name.split("/")
                      if c.startswith(SCOPE_PREFIX)]
            if scopes:
                out.setdefault(name, scopes[-1])
    return out


def _op_kind(instruction: str) -> str:
    """``copy.12`` -> ``copy``; ``and_select_fusion.3`` ->
    ``and_select_fusion``."""
    return re.sub(r"(\.\d+)+$", "", instruction)


def reduce_scopes(plane, run: pr.Interval, scope_of: Dict[str, str]
                  ) -> Tuple[List[Tuple[str, float]],
                             List[Tuple[str, str, float]]]:
    """Device ns per component scope in the first :data:`SCOPE_BUDGET`
    operation events of one program run on ``plane``, and per (scope,
    operation kind), longest first.

    An operation's own time is its duration less that of the operations
    nested in it (a ``while`` spans its body), every interval clipped to
    the sampled prefix of the run, so the scopes sum to that prefix.
    Operations not in ``scope_of`` count as :data:`UNSCOPED`.  A vmapped
    scan runs every branch every step, so a prefix of its steps stands
    for the run."""
    lo, hi = run
    evs: List[Tuple[float, float, str]] = []
    cut = hi
    for line in plane.lines:
        if line.name not in OP_EVENT_LINES:
            continue
        for ev in line.events:
            s = float(ev.start_ns)
            if lo <= s < hi:
                if len(evs) == SCOPE_BUDGET:
                    cut = s
                    break
                evs.append((s, s + float(ev.duration_ns), ev.name))
    evs.sort(key=lambda x: (x[0], -x[1]))
    own = [min(e, cut) - s for s, e, _ in evs]
    stack: List[int] = []
    for i, (s, e, _) in enumerate(evs):
        while stack and evs[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, cut) - s
        stack.append(i)
    by_scope: collections.Counter = collections.Counter()
    by_kind: collections.Counter = collections.Counter()
    for (_, _, name), ns in zip(evs, own):
        inst = name.split(" = ")[0].lstrip("%")
        scope = scope_of.get(inst, UNSCOPED)
        by_scope[scope] += ns
        by_kind[scope, _op_kind(inst)] += ns
    return (by_scope.most_common(),
            [(sc, kind, ns) for (sc, kind), ns in by_kind.most_common()])


# ---------------------------------------------------------------------------
# one traced campaign
# ---------------------------------------------------------------------------
def breakdown(pd, hlo_texts: Iterable[str]) -> Dict[str, list]:
    """From a ``ProfileData`` with one ``bench.window`` span: the
    window's idle time per innermost ``bench.`` or ``hermes.`` span
    (:func:`idle_by_span`), and the scan program's first run reduced to
    component scopes (:func:`reduce_scopes`).  The window, the device
    planes' busy intervals and the scan program (the one with the most
    device time) are ``profile_reduce``'s."""
    red = pr.reduce_profile(pd)
    lo, hi = red.window
    devices = [p for p in pd.planes if pr.DEVICE_PLANE.fullmatch(p.name)]
    spans = [ev for p in pd.planes if p.name.startswith("/host:")
             for line in p.lines for ev in pr._events(line)
             if ev[0].startswith(SPAN_PREFIXES) and ev[0] != "bench.window"]
    busy = [(s, e) for p in devices for line in pr._device_lines(p)
            for _, s, e in pr._events(line)]
    idle = pr.gaps(pr.merge(pr.clip(busy, lo, hi)), lo, hi)
    out: Dict[str, list] = {
        "idle_by_span": [[n, ns * 1e-9]
                         for n, ns in idle_by_span(idle, spans)],
        "scopes": [], "scope_ops": []}
    scope_of = op_scopes(hlo_texts)
    scan = red.ops[0][0] if red.ops else None
    runs = [(s, e) for line in pr._device_lines(devices[0])
            for n, s, e in pr._events(line)
            if n.split("(")[0] == scan and e > lo and s < hi]
    if scope_of and runs:
        scopes, kinds = reduce_scopes(devices[0], min(runs), scope_of)
        if any(sc != UNSCOPED for sc, _ in scopes):
            total = sum(ns for _, ns in scopes)
            out["scopes"] = [[sc, ns * 1e-9, 100.0 * ns / total]
                             for sc, ns in scopes]
            out["scope_ops"] = [[sc, kind, ns * 1e-9]
                                for sc, kind, ns in kinds[:10]]
    return out


def traced_campaign(program) -> Dict[str, list]:
    """Run the next campaign of ``program`` (a ``harness.Program`` whose
    bucket is compiled) under the profiler, and return its
    :func:`breakdown`."""
    import jax
    trace_dir = tempfile.mkdtemp(prefix="bench_scopes_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        span = jax.profiler.TraceAnnotation
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            with span("bench.window"), span("bench.campaign"):
                program.campaign(span)
        finally:
            jax.profiler.stop_trace()
        return breakdown(pr.load(Path(trace_dir)),
                         [scan.as_text()
                          for scan, _ in program.ej._COMPILED.values()])
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    # the compile cache of bench/run.py
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    import harness
    cell = harness.load_cell(args.workload, ROOT)
    device = harness.device_info(cell.chips)     # no TPU: exits
    program = harness.Program(cell, cell.traffic(args.seed))
    program.campaign(harness.no_annotation)      # compiles the bucket
    out = traced_campaign(program)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "device": device, **out}))
    return 0


if __name__ == "__main__":
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    sys.exit(main())
