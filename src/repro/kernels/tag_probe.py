"""Pallas tag-store probe: tag compare + LRU victim select per set.

The per-access inner loop of the memory-hierarchy engines (reference,
SoA, C kernel, and the jnp closure inside ``core/engine_jax.py``) is a
set probe: compare the lookup tag against every way, pick the hit way,
and — for fills — pick the victim way as "first free, else the
least-recently-touched line, fill order breaking ties".  This kernel is
that probe over a *batch* of independent sets (one grid row block per
``bb`` sets), the shape it takes inside a vmapped design-space sweep
where N configs probe their tag stores against the same trace window.

Layout: ways are the minor axis (A is 8/16 for the HERMES hierarchies),
rows are batched sets.  All selects are first-index (argmax/argmin on
the row), matching the dict-insertion tie-breaks of the reference
engine — bit-identity with ``kernels/ref.py``'s sequential oracle is
asserted in tests/test_engine_jax.py.

Outputs per row: ``hit`` (0/1), ``way`` (hit way if hit, else victim or
free way — the slot a fill would write), ``evict`` (0/1: the fill would
displace a valid line).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _tag_probe_kernel(tag_ref, vld_ref, last_ref, seq_ref, q_ref, out_ref,
                      *, ways: int):
    tags = tag_ref[...]                      # (bb, A) int32
    vld = vld_ref[...] != 0                  # (bb, A)
    last = last_ref[...]                     # (bb, A) float
    seq = seq_ref[...]                       # (bb, A) int32
    q = q_ref[...]                           # (bb, 1) int32

    # first-index selects as a min over way indices: Mosaic reduces
    # int32 by min/max but has no argmax/argmin over integers
    iota = jax.lax.broadcasted_iota(jnp.int32, tags.shape, 1)

    def first(mask):                         # first true way, else A
        return jnp.min(jnp.where(mask, iota, ways), axis=1, keepdims=True)

    hitw = first(vld & (tags == q))
    hit = hitw < ways
    freew = first(~vld)
    full = freew == ways

    # LRU among the stalest `last` stamps; fill sequence breaks ties
    # (first index on equal seq)
    stale = last == jnp.min(last, axis=1, keepdims=True)
    big = jnp.iinfo(jnp.int32).max
    sq = jnp.where(stale, seq, big)
    vicw = first(stale & (sq == jnp.min(sq, axis=1, keepdims=True)))

    way = jnp.where(hit, hitw, jnp.where(full, vicw, freew))
    evict = ~hit & full
    out_ref[...] = jnp.concatenate(
        [hit.astype(jnp.int32), way, evict.astype(jnp.int32)], axis=1)


@functools.partial(jax.jit, static_argnames=("bb", "interpret"))
def tag_probe(tags: jax.Array, valid: jax.Array, last: jax.Array,
              seq: jax.Array, query: jax.Array, bb: int = 256,
              interpret: bool = False) -> jax.Array:
    """Probe B sets of A ways.  tags/valid/last/seq (B, A), query (B,).

    Returns (B, 3) int32: [hit, way, evict] per set.
    """
    B, A = tags.shape
    bb = min(bb, B)
    if B % bb:                       # pad rows to a whole grid
        pad = bb - B % bb
        tags = jnp.pad(tags, ((0, pad), (0, 0)))
        valid = jnp.pad(valid, ((0, pad), (0, 0)))
        last = jnp.pad(last, ((0, pad), (0, 0)))
        seq = jnp.pad(seq, ((0, pad), (0, 0)))
        query = jnp.pad(query, (0, pad))
    Bp = tags.shape[0]

    out = pl.pallas_call(
        functools.partial(_tag_probe_kernel, ways=A),
        grid=(Bp // bb,),
        in_specs=[
            pl.BlockSpec((bb, A), lambda i: (i, 0)),
            pl.BlockSpec((bb, A), lambda i: (i, 0)),
            pl.BlockSpec((bb, A), lambda i: (i, 0)),
            pl.BlockSpec((bb, A), lambda i: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bb, 3), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, 3), jnp.int32),
        interpret=interpret,
    )(tags.astype(jnp.int32), valid.astype(jnp.int32), last,
      seq.astype(jnp.int32), query.astype(jnp.int32)[:, None])
    return out[:B]
