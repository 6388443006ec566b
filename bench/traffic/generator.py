"""Campaigns from a traffic mix file.

A mix (``bench/traffic/<traffic>.json``) holds:

* ``workloads``: the loop nests whose traces a campaign scans (names in
  :data:`loopnests.WORKLOADS`), each generated once per run at ``scale``
  with the seed ``--seed`` plus the loop nest's index there;
* ``slice``: S, the consecutive accesses each campaign cuts from each
  trace, at an offset drawn from ``--seed``; the modelled caches start
  empty at every slice;
* ``grid``: the design points, as ``{dotted knob: [values]}`` axes whose
  product (last axis fastest) is the list of points.  Every point runs
  on every workload's slice, so a campaign has
  ``points x workloads`` lanes;
* ``min_distinct_lanes`` (optional): the fewest different lane results
  a campaign may return (``harness.min_distinct_lanes``).

The same seed gives the same traces and the same offsets.
"""

from __future__ import annotations

import itertools
from typing import Any, Dict, List, Mapping, Tuple

import numpy as np

from traffic import loopnests

_COLUMNS = ("core", "pc", "addr", "write", "tensor", "reuse")


def grid_points(axes: Mapping[str, List[Any]]) -> List[Dict[str, Any]]:
    names = list(axes)
    return [dict(zip(names, combo))
            for combo in itertools.product(*(axes[n] for n in names))]


def point_label(point: Mapping[str, Any]) -> str:
    return "|".join(f"{k}={point[k]}" for k in sorted(point)) or "base"


class Traffic:
    """The traces, lanes and offset stream of one run of a mix."""

    def __init__(self, mix: Mapping[str, Any], seed: int):
        order = list(loopnests.WORKLOADS)
        self.workloads: List[str] = list(mix["workloads"])
        self.slice = int(mix["slice"])
        scale = float(mix.get("scale", 1.0))
        self.traces = {wl: loopnests.WORKLOADS[wl](scale, seed + order.index(wl))
                       for wl in self.workloads}
        for wl, tr in self.traces.items():
            if len(tr["core"]) < self.slice:
                raise ValueError(f"{wl}: trace of {len(tr['core'])} accesses "
                                 f"is shorter than the slice {self.slice}")
        self.points = grid_points(mix.get("grid", {}))
        #: (point index, workload) of every lane, point-major
        self.lanes: List[Tuple[int, str]] = [
            (p, wl) for p in range(len(self.points)) for wl in self.workloads]
        self._rng = np.random.default_rng(seed)

    def draw(self) -> Dict[str, Dict]:
        """The next campaign: one slice of S accesses per workload."""
        out = {}
        for wl in self.workloads:
            tr = self.traces[wl]
            n = len(tr["core"])
            off = int(self._rng.integers(0, n - self.slice + 1))
            sl = {k: tr[k][off:off + self.slice] for k in _COLUMNS}
            meta = tr["meta"]
            sl["name"] = tr["name"]
            sl["meta"] = {"n_macro_ops": max(1, meta["n_macro_ops"]
                                             * self.slice // n),
                          "tensors": meta["tensors"], "offset": off}
            out[wl] = sl
        return out
