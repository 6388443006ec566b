"""The plain reference of the HERMES simulator, kept with the benchmark.

A copy of the repository's object engine (``HierarchySim`` and the cache,
coherence, prefetch, tensor-aware, hybrid-memory and energy models it is
built from), so that a change to the program cannot move the yardstick.
It imports nothing of the program.  :func:`run` simulates one design
point over one trace and returns the counters in the export layout of
the program's engines (``oi[98]``: integer counters, ``od[10]``: the
doubles) with the ``Metrics`` row derived from them.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Mapping, Tuple

import numpy as np

from . import params
from .simulator import HierarchySim, compute_metrics

#: cache levels a ``ta.<knob>`` design-point axis applies to
_TA_LEVELS = ("l1", "l2", "l3")


def build_system(classes, system: Mapping[str, Any]):
    """A ``SystemParams`` of the module ``classes`` (this package's
    ``params``, or another with the same dataclasses) from its JSON form:
    nested groups as objects, ``null`` for an absent level."""
    nested = {"l1": classes.CacheParams, "l2": classes.CacheParams,
              "l3": classes.CacheParams, "ta": classes.TensorPolicyParams,
              "prefetch": classes.PrefetchParams,
              "hybrid": classes.HybridMemParams}

    def build(cls, d):
        return cls(**{k: (build(nested[k], v)
                          if k in nested and v is not None else v)
                      for k, v in d.items()})
    return build(classes.SystemParams, system)


def system_params(system: Mapping[str, Any]) -> params.SystemParams:
    return build_system(params, system)


def _replace_path(obj, parts, value):
    if len(parts) == 1:
        if not hasattr(obj, parts[0]):
            raise AttributeError(f"no field {parts[0]!r}")
        return dataclasses.replace(obj, **{parts[0]: value})
    return dataclasses.replace(
        obj, **{parts[0]: _replace_path(getattr(obj, parts[0]), parts[1:],
                                        value)})


def apply_point(sp: params.SystemParams, point: Mapping[str, Any],
                name: str) -> params.SystemParams:
    """``sp`` with a design point's dotted overrides; ``ta.<knob>`` sets
    the tensor-policy knob at every cache level the system has."""
    for path, value in point.items():
        if path.startswith("ta."):
            paths = [f"{lv}.{path}" for lv in _TA_LEVELS
                     if getattr(sp, lv) is not None]
        else:
            paths = [path]
        for p in paths:
            sp = _replace_path(sp, tuple(p.split(".")), value)
    return dataclasses.replace(sp, name=name)


def export(sim: HierarchySim) -> Tuple[np.ndarray, np.ndarray]:
    """A finished simulation's counters in the ``oi``/``od`` layout."""
    nr = sim.n_req
    oi = np.zeros(98, np.int64)
    oi[0], oi[1], oi[2] = sim.n_acc, sim.wb_lines, sim.pf_dropped
    if sim.dir is not None:
        oi[3:6] = (sim.dir.invalidations, sim.dir.c2c_transfers,
                   sim.dir.upgrades)
    mem = sim.mem
    oi[6:11] = (mem.migrations, mem.migration_bytes,
                mem.dram.bytes_transferred, mem.dram.row_hits,
                mem.dram.accesses)
    if mem.hbm is not None:
        oi[11:14] = (mem.hbm.bytes_transferred, mem.hbm.row_hits,
                     mem.hbm.accesses)
    for base, caches in ((14, sim.l1), (17, sim.l2)):
        oi[base:base + 3] = (sum(c.evictions for c in caches),
                             sum(c.dirty_evictions for c in caches),
                             sum(c.prefetch_fills for c in caches))
    if sim.l3 is not None:
        l3 = sim.l3
        oi[20:26] = (l3.evictions, l3.dirty_evictions, l3.prefetch_fills,
                     l3.hits, l3.misses, l3.prefetch_useful)
    for base, caches, attr in ((26, sim.l1, "hits"), (34, sim.l1, "misses"),
                               (42, sim.l1, "prefetch_useful"),
                               (50, sim.l2, "hits"), (58, sim.l2, "misses"),
                               (66, sim.l2, "prefetch_useful")):
        oi[base:base + nr] = [getattr(c, attr) for c in caches]
    for r, pf in enumerate(sim.pf):
        if pf.stride is not None:
            oi[74 + r] = pf.stride.issued
        if pf.ml is not None:
            oi[82 + r] = pf.ml.issued
            oi[90 + r] = pf.ml.trained
    od = np.zeros(10, np.float64)
    od[:nr] = [float(t) for t in sim.time]
    od[8] = float(sim.lat_sum)
    od[9] = float(mem.migration_stall_cycles)
    return oi, od


def run(sp: params.SystemParams, trace: Dict,
        real=float) -> Tuple[np.ndarray, np.ndarray, Dict]:
    """Simulate ``sp`` over ``trace``: ``(oi, od, metrics row)``."""
    sim = HierarchySim(sp, real=real)
    core, pc, addr = trace["core"], trace["pc"], trace["addr"]
    write, tensor, reuse = trace["write"], trace["tensor"], trace["reuse"]
    for i in range(len(core)):
        sim.access(int(core[i]), int(pc[i]), int(addr[i]), bool(write[i]),
                   int(tensor[i]), int(reuse[i]))
    oi, od = export(sim)
    return oi, od, compute_metrics(sim, trace).row()
