"""Core transformer layers: RMSNorm, RoPE, GQA/MQA attention, gated MLPs.

Pure-functional JAX (no flax): parameters are nested dicts of arrays,
``init_*`` builds them, ``apply`` fns consume them.  Sharding is expressed
with ``constrain`` — a with_sharding_constraint that is a no-op when no
mesh is installed (CPU smoke tests) so every model runs unmodified on one
device and on the 512-chip production mesh.

Axis conventions (activations): (batch, seq, d_model) constrained to
(DATA, None, None) or (DATA, None, MODEL) after projections; parameters
are 2-D sharded (FSDP on DATA × TP on MODEL) by dist/sharding.py.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig

DATA = ("pod", "data")   # activation batch axes (pod-DP × data-DP/FSDP)
MODEL = "model"          # TP / EP axis

#: Megatron-style sequence parallelism (set by model.forward at trace
#: time from rc.act_seq_shard): activations BETWEEN blocks keep their
#: sequence dim sharded over MODEL; attention/mlp all-gather on entry
#: and REDUCE-SCATTER on exit — same wire bytes as the TP all-reduce
#: they replace, but the norm/residual segments run 16× cheaper and the
#: separate remat-buffer reshard disappears (EXPERIMENTS §Perf).
SEQ_PARALLEL = False


def ambient_mesh():
    """The mesh installed by ``jax.sharding.set_mesh``, or None."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def constrain(x: jax.Array, *spec) -> jax.Array:
    """with_sharding_constraint that is a no-op without a mesh context."""
    mesh = ambient_mesh()
    if mesh is None:
        return x
    names = set(mesh.axis_names)

    def keep(s):
        if s is None:
            return None
        if isinstance(s, str):
            return s if s in names else None
        return tuple(a for a in s if a in names) or None

    clean = tuple(keep(s) for s in spec)
    return jax.lax.with_sharding_constraint(x, P(*clean))


# -- initializers -----------------------------------------------------------
def _dense_init(key, fan_in: int, shape, dtype) -> jax.Array:
    scale = (1.0 / max(1, fan_in)) ** 0.5
    return jax.random.normal(key, shape, dtype) * scale


# -- RMSNorm ------------------------------------------------------------------
def init_rmsnorm(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rms_norm(params, x: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * params["scale"].astype(jnp.float32)).astype(dt)


# -- RoPE ---------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) int32."""
    freqs = rope_freqs(x.shape[-1], theta)                      # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (B, S, D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# -- attention ----------------------------------------------------------------
def init_attention(key, cfg: ModelConfig, dtype=jnp.float32,
                   cross: bool = False):
    d, hd = cfg.d_model, cfg.head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    return {
        "wq": _dense_init(ks[0], d, (d, nq * hd), dtype),
        "wk": _dense_init(ks[1], d, (d, nkv * hd), dtype),
        "wv": _dense_init(ks[2], d, (d, nkv * hd), dtype),
        "wo": _dense_init(ks[3], nq * hd, (nq * hd, d), dtype),
    }


@dataclasses.dataclass
class KVCache:
    """Dense KV cache: k/v (B, S_max, n_kv, head_dim); length (B,)."""

    k: jax.Array
    v: jax.Array
    length: jax.Array  # (B,) current fill

    @staticmethod
    def zeros(batch: int, max_seq: int, n_kv: int, head_dim: int,
              dtype=jnp.bfloat16) -> "KVCache":
        return KVCache(
            k=jnp.zeros((batch, max_seq, n_kv, head_dim), dtype),
            v=jnp.zeros((batch, max_seq, n_kv, head_dim), dtype),
            length=jnp.zeros((batch,), jnp.int32),
        )


jax.tree_util.register_dataclass(
    KVCache, data_fields=["k", "v", "length"], meta_fields=[])


def _sdpa(q, k, v, mask) -> jax.Array:
    """q: (B,S,Hq,D), k/v: (B,T,Hkv,D) grouped-query attention."""
    b, s, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    qg = q.reshape(b, s, hkv, group, d)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores / (d ** 0.5)
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, hq, d)


#: self-attention switches to the chunked flash path at this seq length
#: (below it the dense O(S²) scores are cheaper than scan overhead).
FLASH_MIN_SEQ = 512


def attention(params, x: jax.Array, cfg: ModelConfig, positions: jax.Array,
              kv_cache: Optional[KVCache] = None,
              kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,
              causal: bool = True,
              ) -> Tuple[jax.Array, Optional[KVCache]]:
    """GQA attention.

    * training / prefill: kv_cache None or empty → full self-attention
      (chunked flash path for S ≥ FLASH_MIN_SEQ — O(S·chunk) memory);
      prefill writes and returns the filled cache.  Prefill assumes an
      EMPTY cache (length 0), which serve/engine guarantees.
    * decode: x is (B, 1, D), kv_cache holds history (dense matvec).
    * cross-attention (VLM): kv_override = precomputed (k, v) of the image
      tokens; no cache, no causal mask.
    """
    from repro.models.flash import flash_attention

    if SEQ_PARALLEL:
        x = constrain(x, DATA, None, None)        # AG over seq (enter TP)
    b, s, d = x.shape
    hd, nq, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    q = (x @ params["wq"].astype(x.dtype)).reshape(b, s, nq, hd)
    q = constrain(q, DATA, None, MODEL, None)
    if kv_override is not None:
        k, v = kv_override
        mask = jnp.ones((b, s, k.shape[1]), dtype=bool)
        out = _sdpa(q, k, v, mask)
        out = constrain(out, DATA, None, MODEL, None)
        out = out.reshape(b, s, nq * hd) @ params["wo"].astype(x.dtype)
        if SEQ_PARALLEL:
            out = constrain(out, DATA, MODEL, None)   # RS (exit TP)
        return out, None

    k = (x @ params["wk"].astype(x.dtype)).reshape(b, s, nkv, hd)
    v = (x @ params["wv"].astype(x.dtype)).reshape(b, s, nkv, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    new_cache = None
    if kv_cache is not None and s == 1:
        # decode: append one token, attend densely over the cache
        start = kv_cache.length[:, None]
        idx = start + jnp.arange(s)[None, :]
        bidx = jnp.arange(b)[:, None]
        ck = kv_cache.k.at[bidx, idx].set(k.astype(kv_cache.k.dtype))
        cv = kv_cache.v.at[bidx, idx].set(v.astype(kv_cache.v.dtype))
        new_len = kv_cache.length + s
        new_cache = KVCache(ck, cv, new_len)
        t = ck.shape[1]
        kpos = jnp.arange(t)[None, :]                       # (1,T)
        qpos = (start + jnp.arange(s)[None, :])             # (B,S)
        mask = kpos[:, None, :] <= qpos[:, :, None]         # causal vs cache
        mask &= (kpos < new_len[:, None])[:, None, :]
        out = _sdpa(q, ck.astype(q.dtype), cv.astype(q.dtype), mask)
    else:
        if kv_cache is not None:
            # prefill-into-cache (from position 0; engine guarantees empty)
            idx = jnp.arange(s)[None, :] + jnp.zeros((b, 1), jnp.int32)
            bidx = jnp.arange(b)[:, None]
            ck = kv_cache.k.at[bidx, idx].set(k.astype(kv_cache.k.dtype))
            cv = kv_cache.v.at[bidx, idx].set(v.astype(kv_cache.v.dtype))
            new_cache = KVCache(ck, cv, kv_cache.length + s)
        if causal and s >= FLASH_MIN_SEQ:
            out = flash_attention(q, k, v, causal=True)
        else:
            if causal:
                mask = jnp.tril(jnp.ones((s, s), dtype=bool))[None]
            else:
                mask = jnp.ones((b, s, s), dtype=bool)
            mask = jnp.broadcast_to(mask, (b, s, s))
            out = _sdpa(q, k, v, mask)
    out = constrain(out, DATA, None, MODEL, None)
    out = out.reshape(b, s, nq * hd) @ params["wo"].astype(x.dtype)
    if SEQ_PARALLEL:
        return constrain(out, DATA, MODEL, None), new_cache  # RS (exit TP)
    return constrain(out, DATA, None, None), new_cache


# -- gated MLP ------------------------------------------------------------------
def init_mlp(key, d: int, d_ff: int, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    return {
        "w_gate": _dense_init(ks[0], d, (d, d_ff), dtype),
        "w_up": _dense_init(ks[1], d, (d, d_ff), dtype),
        "w_down": _dense_init(ks[2], d_ff, (d_ff, d), dtype),
    }


def mlp(params, x: jax.Array, act: str) -> jax.Array:
    if SEQ_PARALLEL:
        x = constrain(x, DATA, None, None)        # AG over seq (enter TP)
    g = x @ params["w_gate"].astype(x.dtype)
    u = x @ params["w_up"].astype(x.dtype)
    g = constrain(g, DATA, None, MODEL)
    u = constrain(u, DATA, None, MODEL)
    h = (jax.nn.silu(g) if act == "silu" else
         jax.nn.gelu(g, approximate=True)) * u
    out = h @ params["w_down"].astype(x.dtype)
    if SEQ_PARALLEL:
        return constrain(out, DATA, MODEL, None)  # RS (exit TP)
    return constrain(out, DATA, None, None)


# -- embedding / unembedding ----------------------------------------------------
def init_embedding(key, vocab: int, d: int, dtype=jnp.float32):
    return {"table": jax.random.normal(key, (vocab, d), dtype) * 0.02}


def embed(params, tokens: jax.Array) -> jax.Array:
    return jnp.take(params["table"], tokens, axis=0)


def unembed(params, x: jax.Array) -> jax.Array:
    logits = x @ params["table"].T.astype(x.dtype)
    return constrain(logits, DATA, None, MODEL)
