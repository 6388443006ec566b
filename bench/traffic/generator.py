"""Campaigns from a traffic mix file.

A mix (``bench/traffic/<traffic>.json``) holds:

* ``workloads``: the loop nests whose traces a campaign scans, each
  generated once per run at ``scale``.  A name in
  :data:`loopnests.WORKLOADS` is seeded with ``--seed`` plus its index
  there (0, 1, 2); any other name is a file ``nests/<name>.py`` of its
  own, seeded with ``--seed`` plus the file's ``SEED_OFFSET``;
* ``params`` (optional): ``{workload: {keyword: value}}``, the keyword
  arguments of that workload's trace function, so one nest file serves
  several traffic shapes as data;
* ``slice``: S, the consecutive accesses each campaign cuts from each
  trace, at an offset drawn from ``--seed``; the modelled caches start
  empty at every slice;
* ``grid``: the design points, as ``{dotted knob: [values]}`` axes whose
  product (last axis fastest) is the list of points.  Every point runs
  on every workload's slice, so a campaign has
  ``points x workloads`` lanes;
* ``min_distinct_lanes`` (optional): the fewest different lane results
  a campaign may return (``harness.min_distinct_lanes``).

A nest file ``nests/<name>.py`` defines ``SEED_OFFSET``, an int of at
least ``len(loopnests.WORKLOADS)`` that no other nest file has, and
``trace(scale, seed, **params)``, which returns a trace as
:mod:`loopnests` does (:func:`check_trace` says what one holds); it
builds it with ``loopnests.Builder`` and ``loopnests.finish``.

The same seed gives the same traces and the same offsets.
"""

from __future__ import annotations

import importlib.util
import itertools
import re
from pathlib import Path
from typing import Any, Callable, Dict, List, Mapping, Tuple

import numpy as np

from traffic import loopnests

#: the columns of a trace and their dtypes
_COLUMNS = {"core": np.int8, "pc": np.int32, "addr": np.int64,
            "write": np.bool_, "tensor": np.int16, "reuse": np.int8}
_REUSE_CLASSES = (loopnests.REUSE_STREAMING, loopnests.REUSE_MEDIUM,
                  loopnests.REUSE_RESIDENT)
#: where the nest files are, in this checkout
NESTS = Path(__file__).resolve().parent / "nests"
_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]*")


def grid_points(axes: Mapping[str, List[Any]]) -> List[Dict[str, Any]]:
    names = list(axes)
    return [dict(zip(names, combo))
            for combo in itertools.product(*(axes[n] for n in names))]


def point_label(point: Mapping[str, Any]) -> str:
    return "|".join(f"{k}={point[k]}" for k in sorted(point)) or "base"


def _nest_files(nests: Path) -> Dict[str, Any]:
    """Every nest file under ``nests``, loaded by path, with its
    ``SEED_OFFSET`` checked against the loop nests and the other files."""
    mods: Dict[str, Any] = {}
    owner: Dict[int, str] = {}
    for path in sorted(nests.glob("*.py")):
        name = path.stem
        spec = importlib.util.spec_from_file_location(
            "bench_nest_" + re.sub(r"\W", "_", name), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        offset = getattr(mod, "SEED_OFFSET", None)
        if name in loopnests.WORKLOADS:
            raise ValueError(f"{path}: {name!r} is a name in "
                             "loopnests.WORKLOADS")
        if not isinstance(offset, int) or offset < len(loopnests.WORKLOADS):
            raise ValueError(f"{path}: SEED_OFFSET must be an int of at least "
                             f"{len(loopnests.WORKLOADS)}, not {offset!r}")
        if offset in owner:
            raise ValueError(f"{path}: SEED_OFFSET {offset} is also "
                             f"{owner[offset]}'s")
        owner[offset] = f"nests/{name}.py"
        mods[name] = mod
    return mods


def trace_function(name: str, nests: Path = NESTS
                   ) -> Tuple[Callable[..., Dict], int]:
    """The trace function of a workload name and the offset added to the
    run's seed for it."""
    order = list(loopnests.WORKLOADS)
    if name in loopnests.WORKLOADS:
        return loopnests.WORKLOADS[name], order.index(name)
    path = nests / f"{name}.py"
    if not _NAME.fullmatch(name) or not path.is_file():
        raise ValueError(f"workload {name!r} is neither in "
                         f"loopnests.WORKLOADS ({', '.join(order)}) nor a "
                         f"nest file {path}")
    mod = _nest_files(nests)[name]
    return mod.trace, mod.SEED_OFFSET


def check_trace(name: str, tr: Mapping[str, Any]) -> None:
    """Refuse a trace whose columns, tensor ids or reuse classes the
    simulator cannot take; the message names the workload."""
    missing = [k for k in (*_COLUMNS, "name", "meta") if k not in tr]
    if missing or not {"n_macro_ops", "tensors"} <= set(tr["meta"]):
        raise ValueError(f"{name}: a trace needs the columns "
                         f"{', '.join(_COLUMNS)}, name and meta "
                         "(n_macro_ops, tensors)")
    n = len(tr["core"])
    for k, dt in _COLUMNS.items():
        col = tr[k]
        if (not isinstance(col, np.ndarray) or col.dtype != dt
                or col.ndim != 1):
            raise ValueError(f"{name}: column {k} must be a 1-d "
                             f"{np.dtype(dt)} array, not "
                             f"{getattr(col, 'dtype', type(col).__name__)}")
        if len(col) != n:
            raise ValueError(f"{name}: column {k} has {len(col)} rows, "
                             f"core has {n}")
    n_tensors = len(tr["meta"]["tensors"])
    tensor = tr["tensor"]
    if n and (tensor.min() < 0 or tensor.max() >= n_tensors):
        raise ValueError(f"{name}: tensor ids {tensor.min()}..{tensor.max()} "
                         f"outside the table of {n_tensors} tensors")
    bad = ~np.isin(tr["reuse"], _REUSE_CLASSES)
    if bad.any():
        raise ValueError(f"{name}: reuse class {tr['reuse'][bad][0]} is not "
                         f"one of {_REUSE_CLASSES}")
    if n and tr["core"].min() < 0:
        raise ValueError(f"{name}: negative core id {tr['core'].min()}")


class Traffic:
    """The traces, lanes and offset stream of one run of a mix; nest
    files are looked for under ``nests``."""

    def __init__(self, mix: Mapping[str, Any], seed: int,
                 nests: Path = NESTS):
        self.workloads: List[str] = list(mix["workloads"])
        self.slice = int(mix["slice"])
        scale = float(mix.get("scale", 1.0))
        params = mix.get("params", {})
        stray = set(params) - set(self.workloads)
        if stray:
            raise ValueError(f"params for {', '.join(sorted(stray))}, which "
                             "the mix's workloads do not name")
        self.traces = {}
        for wl in self.workloads:
            fn, offset = trace_function(wl, nests)
            tr = fn(scale, seed + offset, **params.get(wl, {}))
            check_trace(wl, tr)
            if len(tr["core"]) < self.slice:
                raise ValueError(f"{wl}: trace of {len(tr['core'])} accesses "
                                 f"is shorter than the slice {self.slice}")
            self.traces[wl] = tr
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
