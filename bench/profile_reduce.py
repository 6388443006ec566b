"""Reduce a profiler trace (``.xplane.pb``) to device busy time, idle
gaps and per-program totals.

Devices are found by plane name (``/device:<platform>:<n>``), never by
kernel name, so a rewrite of the program keeps the numbers.  The busy
time of a device is the union of the intervals in which it executes a
compiled program: the events of its :data:`OP_LINES`, one per program
run (a plane without that line falls back to all its lines but the
markers).  The HLO operations inside a program are not read: a scan
step runs thousands of them, millions to a campaign, and the time
between them inside a running program is the program's own, not idle
time the host causes.  Host spans the benchmark writes
(``jax.profiler.TraceAnnotation`` names starting ``bench.``) are read
from the host planes on the same clock, to bound the window and to name
what the host did in each gap.
"""

from __future__ import annotations

import collections
import dataclasses
import re
from pathlib import Path
from typing import List, Sequence, Tuple

Interval = Tuple[float, float]          # [start, end) in ns

#: lines of a device plane whose events are program runs
OP_LINES = ("XLA Modules",)
#: lines never counted as device work: markers spanning idle time, and
#: the operations inside programs (read through their program instead)
MARKER_LINES = ("Steps", "XLA Ops", "Async XLA Ops", "Source code",
                "Framework Ops", "Framework Name Scope", "Host Offload Ops",
                "Scalar Unit", "TC Overlay")
SPAN_PREFIX = "bench."
#: a chip's plane ("/device:TPU:0"), not a custom one
#: ("/device:CUSTOM:Megascale Trace")
DEVICE_PLANE = re.compile(r"/device:(?!CUSTOM:)\w+:\d+")


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """The union of ``intervals`` as sorted, disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of ``[lo, hi)`` between merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class Reduced:
    """What the benchmark reads from one traced window."""

    window: Interval                      # ns, on the trace's clock
    busy_ns: List[float]                  # per device plane, in window
    ops: List[Tuple[str, float]]          # (program name, ns), all devices
    idle_gaps: List[Tuple[str, float]]    # (host span, ns), longest first

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the traced devices."""
        return sum(self.busy_ns) / len(self.busy_ns) * 1e-9


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns)


def _device_lines(plane):
    lines = list(plane.lines)
    ops = [ln for ln in lines if ln.name in OP_LINES]
    return ops or [ln for ln in lines if ln.name not in MARKER_LINES]


def reduce_profile(pd, window_span: str = SPAN_PREFIX + "window",
                   top: int = 10) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData`` over the host span named
    ``window_span``."""
    spans: List[Tuple[str, float, float]] = []
    devices = []
    for plane in pd.planes:
        if DEVICE_PLANE.fullmatch(plane.name):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(ev for ev in _events(line)
                             if ev[0].startswith(SPAN_PREFIX))
    wins = [(s, e) for n, s, e in spans if n == window_span]
    if len(wins) != 1:
        raise ValueError(f"expected one {window_span!r} span, "
                         f"found {len(wins)}")
    lo, hi = wins[0]
    if not devices:
        raise ValueError("the trace has no device plane")
    busy_ns, op_ns = [], collections.Counter()
    all_busy: List[Interval] = []
    for plane in devices:
        ivs = []
        for line in _device_lines(plane):
            for name, s, e in _events(line):
                if e > lo and s < hi:
                    ivs.append((s, e))
                    # "jit_scan(6115664618691709137)": the hash says
                    # which compile, not which program
                    op_ns[name.split("(")[0]] += min(e, hi) - max(s, lo)
        merged = merge(clip(ivs, lo, hi))
        busy_ns.append(total(merged))
        all_busy.extend(merged)
    # an idle gap is time in which no traced device is busy; it is named
    # after the innermost benchmark span that covers its midpoint
    inner = [(n, s, e) for n, s, e in spans if n != window_span]
    named = []
    for s, e in gaps(merge(all_busy), lo, hi):
        mid = (s + e) / 2
        cover = [(e2 - s2, n) for n, s2, e2 in inner if s2 <= mid < e2]
        named.append((min(cover)[1] if cover else "outside bench spans",
                      e - s))
    named.sort(key=lambda x: -x[1])
    return Reduced(window=(lo, hi), busy_ns=busy_ns,
                   ops=op_ns.most_common(top), idle_gaps=named[:top])


def load(trace_dir: Path):
    """The ``ProfileData`` of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(found)}")
    return ProfileData.from_file(str(found[0]))
