"""Serving step functions: prefill (flash, cache-filling) and decode.

``prefill_32k`` lowers ``prefill_step`` (B=32 × S=32768 self-attention
through the chunked flash path, writing the dense KV cache); ``decode_32k``
and ``long_500k`` lower ``decode_step`` (one new token against a cache of
``seq_len``, the KV cache sharded per dist/sharding.cache_specs).

The paged / tiered KV cache (HERMES tensor-aware caching on TPU) lives in
tpu/kv_cache.py and is used by serve/engine.py; these dense-cache steps
are the GSPMD-lowered production path the dry-run compiles.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig
from repro.dist import sharding as shd
from repro.models import model as mdl


def cast_floating(params, dtype) -> Any:
    """Floating leaves in ``dtype``; leaves already in it stay as they
    are, so weights held in the compute dtype cost no per-call cast."""
    dtype = jnp.dtype(dtype)
    return jax.tree.map(
        lambda p: p.astype(dtype)
        if jnp.issubdtype(p.dtype, jnp.floating) and p.dtype != dtype
        else p, params)


def build_prefill_step(cfg: ModelConfig, rc: RunConfig, max_seq: int):
    """(params, tokens[, img_embed]) → (last_logits, cache).

    The cache is created inside (zeros) at ``max_seq`` capacity so the
    lowered computation owns its KV buffers — memory_analysis() then
    reports the true serving footprint.

    Two repro.plan capacity mitigations lower the live working set:

    * ``rc.logits_mode == "last"`` — unembed only the final position
      (prefill never consumes more), skipping the (B, S, V) tensor;
    * ``rc.prefill_chunks > 1`` — scan the batch in B/chunks slices,
      each writing its rows of the shared cache in place, so live
      activations and attention temps belong to one chunk at a time.
    """
    cdt = jnp.dtype(rc.compute_dtype)
    last = rc.logits_mode == "last"

    def prefill_step(params, tokens, img_embed=None):
        B = tokens.shape[0]
        nch = max(1, rc.prefill_chunks)
        params_c = cast_floating(params, cdt)
        cache = mdl.init_cache(cfg, B, max_seq, dtype=cdt,
                               img_tokens=cfg.n_img_tokens)
        if nch <= 1 or B % nch:
            logits, cache, _ = mdl.forward(params_c, cfg, rc, tokens,
                                           cache=cache, img_embed=img_embed,
                                           last_logits_only=last)
            return logits[:, -1], cache

        bc = B // nch
        bpos = shd.cache_batch_positions(cfg, cache)

        # statically-unrolled chunk loop: slice offsets must be
        # compile-time constants so GSPMD keeps shard-aligned slices of
        # the batch-sharded cache local (a scan's traced offsets force
        # cross-shard gathers and trip the partitioner)
        outs = []
        for i in range(nch):
            start = i * bc
            tok = jax.lax.slice_in_dim(tokens, start, start + bc, axis=0)
            img = (jax.lax.slice_in_dim(img_embed, start, start + bc,
                                        axis=0)
                   if img_embed is not None else None)
            sub = jax.tree.map(
                lambda leaf, p: (leaf if p < 0 else
                                 jax.lax.slice_in_dim(
                                     leaf, start, start + bc, axis=p)),
                cache, bpos)
            logits, new_sub, _ = mdl.forward(params_c, cfg, rc, tok,
                                             cache=sub, img_embed=img,
                                             last_logits_only=last)
            cache = jax.tree.map(
                lambda leaf, new, p: (new if p < 0 else
                                      jax.lax.dynamic_update_slice_in_dim(
                                          leaf, new, start, axis=p)),
                cache, new_sub, bpos)
            outs.append(logits[:, -1])
        return jnp.concatenate(outs, axis=0), cache

    return prefill_step


def build_decode_step(cfg: ModelConfig, rc: RunConfig):
    """(params, cache, tokens (B,1[,nq])) → (logits (B,V...), cache)."""
    cdt = jnp.dtype(rc.compute_dtype)

    def decode_step(params, cache, tokens):
        params_c = cast_floating(params, cdt)
        logits, cache, _ = mdl.forward(params_c, cfg, rc, tokens,
                                       cache=cache)
        return logits[:, 0], cache

    return decode_step


def decode_cache_specs(cfg: ModelConfig, batch: int, mesh,
                       seq_shard: bool = False) -> Any:
    """PartitionSpec tree for the decode cache (mirrors init_cache)."""
    return shd.cache_specs(cfg, batch, mesh, seq_shard=seq_shard)


def cache_shape(cfg: ModelConfig, batch: int, max_seq: int,
                dtype=jnp.bfloat16) -> Any:
    """ShapeDtypeStruct tree of the decode cache (no allocation)."""
    return jax.eval_shape(
        lambda: mdl.init_cache(cfg, batch, max_seq, dtype=dtype,
                               img_tokens=cfg.n_img_tokens))
