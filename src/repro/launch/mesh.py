"""Production mesh construction.

Axes (DESIGN §4):

  * ``pod``   — pure data parallelism across pods over DCN (the slowest
                links carry the lowest-frequency collective: one grad
                all-reduce per step, optionally int8-compressed);
  * ``data``  — FSDP + batch data parallelism over intra-pod ICI;
  * ``model`` — tensor / expert parallelism (highest-frequency
                collectives on the fastest links).

A FUNCTION, not a module constant, so importing this module never touches
jax device state (smoke tests must keep seeing 1 CPU device).
"""

from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over the real local devices (tests / examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, max(1, n // data))
    return _make_mesh((data, model), ("data", "model"))
