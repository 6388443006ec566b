"""jit'd public wrappers for the Pallas kernels.

Each wrapper runs its kernel in interpret mode on the CPU backend (the
kernel body then runs in Python — bit-identical tiling/masking logic,
no Mosaic; this is how the tests run) and compiles it for the chip
everywhere else,
handles GQA head-group reshapes, and is the integration point the model
layers call when ``rc.use_flash_kernel`` is on.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import mamba_scan as _ms
from repro.kernels import matmul_prefetch as _mm
from repro.kernels import paged_attention as _pa
from repro.kernels import tag_probe as _tp


def interpret() -> bool:
    """Whether the kernels run in Pallas interpret mode: on the CPU
    backend only, never on an accelerator."""
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def matmul(a: jax.Array, b: jax.Array, bm: int = 256, bn: int = 256,
           bk: int = 512) -> jax.Array:
    return _mm.matmul_prefetch(a, b, bm=bm, bn=bn, bk=bk,
                               interpret=interpret())


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bkv"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, bq: int = 512,
                    bkv: int = 512) -> jax.Array:
    """GQA flash attention.  q (B,S,Hq,D), k/v (B,T,Hkv,D) → (B,S,Hq,D).

    Heads are flattened into the kernel's leading grid dim; GQA queries
    of one KV head are stacked along the S axis so each (kv-head) slice
    attends against its own KV stream.
    """
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    # (B,S,Hkv,g,D) → (B,Hkv,g,S,D) → (B·Hkv·g, S, D)
    qf = (q.reshape(B, S, Hkv, g, D).transpose(0, 2, 3, 1, 4)
          .reshape(B * Hkv * g, S, D))
    kf = jnp.repeat(k.transpose(0, 2, 1, 3).reshape(B * Hkv, T, D),
                    g, axis=0)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3).reshape(B * Hkv, T, D),
                    g, axis=0)
    of = _fa.flash_attention_fwd(qf, kf, vf, causal=causal, bq=bq,
                                 bkv=bkv, interpret=interpret())
    return (of.reshape(B, Hkv, g, S, D).transpose(0, 3, 1, 2, 4)
            .reshape(B, S, Hq, D))


@jax.jit
def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    page_tbl: jax.Array, seq_lens: jax.Array) -> jax.Array:
    return _pa.paged_attention(q, k_pool, v_pool, page_tbl, seq_lens,
                               interpret=interpret())


@functools.partial(jax.jit, static_argnames=("bd", "chunk"))
def mamba_scan(a: jax.Array, bx: jax.Array, c: jax.Array,
               bd: int = 256, chunk: int = 128) -> jax.Array:
    return _ms.mamba_scan(a, bx, c, bd=bd, chunk=chunk,
                          interpret=interpret())


@jax.jit
def tag_probe(tags: jax.Array, valid: jax.Array, last: jax.Array,
              seq: jax.Array, query: jax.Array) -> jax.Array:
    """Batched set probe: (B, A) ways -> (B, 3) [hit, way, evict]."""
    return _tp.tag_probe(tags, valid, last, seq, query,
                         interpret=interpret())
