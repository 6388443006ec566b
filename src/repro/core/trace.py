"""Workload trace generation: CNN / RNN / Transformer address streams.

Paper §IV "Workloads": ResNet/VGG-style CNNs, LSTM/GRU RNNs, BERT/GPT
Transformers.  Traces are generated from the loop nests of those models,
preserving the properties the paper's techniques exploit:

* small hot state (accumulators, h/c vectors, softmax rows) — L1-resident;
* mid-size resident tensors (weights, KV) that exceed the private L2 but
  fit the shared L3 — the shared-L3 win;
* sequential tile streams (im2col, activations) — stride-prefetchable;
* irregular-but-reused gathers (embedding rows) — invisible to both
  prefetchers and LRU (reuse distance exceeds the L3), but pinnable by
  tensor-aware caching — the TA win;
* producer→consumer tiles between CPU cores and the Gemmini port —
  coherence traffic for the shared-L3/MESI study.

Streams are combined with a *proportional interleave* (every stream is
spread uniformly over the trace), which is what makes reuse distances
well-defined: between two touches of an embedding line, all other
circulating footprints intervene.

A trace is a dict of parallel numpy arrays (core, pc, addr, write, tensor,
reuse) plus ``meta`` (n_macro_ops, tensor table).
"""

from __future__ import annotations

import os
from typing import Dict, List

import numpy as np

from repro.core.tensor_cache import (REUSE_MEDIUM, REUSE_RESIDENT,
                                     REUSE_STREAMING)

LINE = 64
GEMMINI = 4  # requestor id of the accelerator port


class _Alloc:
    """Bump allocator handing out page-aligned tensor regions."""

    def __init__(self):
        self.next = 1 << 22
        self.table: List[tuple] = []  # (id, base, size, reuse)

    def tensor(self, size: int, reuse: int) -> tuple:
        tid = len(self.table)
        base = self.next
        self.next = (self.next + size + 4095) & ~4095
        self.table.append((tid, base, size, reuse))
        return tid, base


class _Builder:
    def __init__(self, name: str, seed: int):
        self.name = name
        self.rng = np.random.default_rng(seed)
        self.alloc = _Alloc()
        self.streams: List[Dict] = []
        self.n_macro = 0

    def add(self, core: int, pc: int, tensor: int, reuse: int, write: bool,
            addrs: np.ndarray) -> None:
        if len(addrs) == 0:
            return
        self.streams.append(dict(core=core, pc=pc, tensor=tensor, reuse=reuse,
                                 write=write, addrs=addrs.astype(np.int64)))

    # -- access-pattern builders --------------------------------------------
    def hot(self, base: int, footprint: int, n: int) -> np.ndarray:
        """Random word-granularity touches over a small hot region."""
        lines = max(1, footprint // LINE)
        idx = self.rng.integers(0, lines, size=n)
        word = self.rng.integers(0, LINE // 8, size=n) * 8
        return base + idx * LINE + word

    def walk(self, base: int, footprint: int, reps: int,
             step_lines: int = 1) -> np.ndarray:
        """Cyclic sequential re-walk (weight matrix GEMM re-reads)."""
        lines = np.arange(0, footprint // LINE, step_lines)
        return base + np.tile(lines, reps) * LINE

    def gather(self, base: int, footprint: int, n: int) -> np.ndarray:
        """Zipf-like random row gathers (embedding lookups): a hot head of
        the vocabulary is reused heavily (pinnable by tensor-aware
        caching), a cold tail is touched compulsorily."""
        lines = max(1, footprint // LINE)
        u = self.rng.random(n)
        hot = (u ** 2.2 * lines).astype(np.int64)          # concentrated head
        cold = self.rng.integers(0, lines, size=n)         # uniform tail
        pick = self.rng.random(n) < 0.8
        idx = np.where(pick, hot, cold)
        return base + idx * LINE

    def stream(self, base: int, n: int, block: int = 24,
               jump: int = 37) -> np.ndarray:
        """Tile streams: sequential within a block, jumping between blocks
        (tile-major order) — partially stride-prefetchable."""
        i = np.arange(n)
        return base + (i + (i // block) * jump) * LINE


def _finish(b: _Builder) -> Dict:
    order_pos = np.concatenate([
        (np.arange(len(s["addrs"])) + 0.5) / len(s["addrs"])
        + b.rng.uniform(0, 1e-6)  # tie-break
        for s in b.streams])
    order = np.argsort(order_pos, kind="stable")
    core = np.concatenate([np.full(len(s["addrs"]), s["core"], np.int8)
                           for s in b.streams])[order]
    pc = np.concatenate([np.full(len(s["addrs"]), s["pc"], np.int32)
                         for s in b.streams])[order]
    addr = np.concatenate([s["addrs"] for s in b.streams])[order]
    write = np.concatenate([np.full(len(s["addrs"]), s["write"], bool)
                            for s in b.streams])[order]
    tensor = np.concatenate([np.full(len(s["addrs"]), s["tensor"], np.int16)
                             for s in b.streams])[order]
    reuse = np.concatenate([np.full(len(s["addrs"]), s["reuse"], np.int8)
                            for s in b.streams])[order]
    out = {"name": b.name, "core": core, "pc": pc, "addr": addr,
           "write": write, "tensor": tensor, "reuse": reuse,
           "meta": {"n_macro_ops": b.n_macro, "tensors": b.alloc.table}}
    # REPRO_TRACE_CAP=N truncates every generated trace to its first N
    # accesses.  Stream interleaving floors trace length around ~120k
    # accesses regardless of ``scale``; the cap is how CI gates run the
    # REAL sweep/CLI path of the jax engine on a bounded input (on the
    # CPU backend one scan step of a prefetching bucket costs
    # milliseconds, so a floor-length trace takes minutes per lane).
    # Both sides of an equivalence gate see identical capped traces, so
    # bit-identity / fingerprint comparisons are unaffected.
    cap = os.environ.get("REPRO_TRACE_CAP")
    if cap:
        n = int(cap)
        if n > 0 and n < len(out["core"]):
            for k in ("core", "pc", "addr", "write", "tensor", "reuse"):
                out[k] = out[k][:n]
    return out


# --------------------------------------------------------------------------
# CNN — ResNet-style conv + classifier.  Cores produce im2col tiles that the
# Gemmini GEMM consumes (producer→consumer coherence); conv weights + the
# classifier head form the L3-resident working set.
# --------------------------------------------------------------------------
def cnn_trace(scale: float = 1.0, seed: int = 0) -> Dict:
    b = _Builder("cnn_resnet", seed)
    al = b.alloc
    n = lambda k: max(64, int(k * scale))

    w_id, w_base = al.tensor(5 << 20, REUSE_RESIDENT)     # conv+fc weights 5 MB
    acc_id, acc_base = al.tensor(24 << 10, REUSE_MEDIUM)  # PE accumulators
    halo_id, halo_base = al.tensor(48 << 10, REUSE_MEDIUM)
    im_id, im_base = al.tensor(96 << 20, REUSE_STREAMING)
    out_id, out_base = al.tensor(64 << 20, REUSE_STREAMING)

    for core in range(4):
        b.add(core, 100 + core, acc_id, REUSE_MEDIUM, False,
              b.hot(acc_base, 24 << 10, n(70_000)))
        b.add(core, 110 + core, halo_id, REUSE_MEDIUM, False,
              b.hot(halo_base, 48 << 10, n(50_000)))
        # each core re-walks its quarter of the weights (3 epochs)
        q = (5 << 20) // 4
        b.add(core, 120 + core, w_id, REUSE_RESIDENT, False,
              b.walk(w_base + core * q, q, reps=2, step_lines=2))
        # im2col tiles produced by the cores (writes)...
        b.add(core, 130 + core, im_id, REUSE_STREAMING, True,
              b.stream(im_base + core * (24 << 20), n(10_000)))
    # ...and consumed by Gemmini (reads; c2c sharing through L3)
    for core in range(4):
        b.add(GEMMINI, 200 + core, im_id, REUSE_STREAMING, False,
              b.stream(im_base + core * (24 << 20), n(10_000)))
    # Gemmini also re-reads the full weight tensor for the GEMM
    b.add(GEMMINI, 210, w_id, REUSE_RESIDENT, False,
          b.walk(w_base, 5 << 20, reps=1, step_lines=2))
    b.add(GEMMINI, 220, out_id, REUSE_STREAMING, True,
          b.stream(out_base, n(12_000)))
    b.n_macro = n(4_000)
    return _finish(b)


# --------------------------------------------------------------------------
# RNN — LSTM: recurrent weights re-walked every timestep (exceed private L2,
# fit shared L3); token-embedding gathers (irregular, TA-pinnable); h vector
# written by core 0 every step → MESI invalidations at the sharers.
# --------------------------------------------------------------------------
def rnn_trace(scale: float = 1.0, seed: int = 1) -> Dict:
    b = _Builder("rnn_lstm", seed)
    al = b.alloc
    n = lambda k: max(64, int(k * scale))

    w_id, w_base = al.tensor(3 << 20, REUSE_RESIDENT)      # W+U, 3 MB
    emb_id, emb_base = al.tensor(5 << 20, REUSE_RESIDENT)  # embeddings, 5 MB
    h_id, h_base = al.tensor(8 << 10, REUSE_MEDIUM)
    gate_id, gate_base = al.tensor(16 << 10, REUSE_MEDIUM)
    x_id, x_base = al.tensor(48 << 20, REUSE_STREAMING)
    y_id, y_base = al.tensor(48 << 20, REUSE_STREAMING)

    for core in range(4):
        b.add(core, 300 + core, gate_id, REUSE_MEDIUM, False,
              b.hot(gate_base, 16 << 10, n(92_000)))
        b.add(core, 310 + core, h_id, REUSE_MEDIUM, False,
              b.hot(h_base, 8 << 10, n(45_000)))
        q = (3 << 20) // 4
        b.add(core, 320 + core, w_id, REUSE_RESIDENT, False,
              b.walk(w_base + core * q, q, reps=3, step_lines=2))
        b.add(core, 330 + core, emb_id, REUSE_RESIDENT, False,
              b.gather(emb_base, 5 << 20, n(30_000)))
    # core 0 writes h every step → invalidates the other sharers
    b.add(0, 340, h_id, REUSE_MEDIUM, True, b.hot(h_base, 8 << 10, n(20_000)))
    b.add(GEMMINI, 400, w_id, REUSE_RESIDENT, False,
          b.walk(w_base, 3 << 20, reps=2, step_lines=2))
    b.add(GEMMINI, 410, x_id, REUSE_STREAMING, False,
          b.stream(x_base, n(30_000)))
    b.add(GEMMINI, 420, y_id, REUSE_STREAMING, True,
          b.stream(y_base, n(25_000)))
    b.n_macro = n(4_400)
    return _finish(b)


# --------------------------------------------------------------------------
# Transformer — BERT/GPT block: KV cache + FFN weights resident (fit L3 only
# together with the embedding table at ~9 MB > 8 MB — the TA policy must
# arbitrate); attention row walks sequential (prefetchable); embedding
# gathers irregular (TA-pinnable); activation tiles streaming.
# --------------------------------------------------------------------------
def transformer_trace(scale: float = 1.0, seed: int = 2) -> Dict:
    b = _Builder("transformer_bert", seed)
    al = b.alloc
    n = lambda k: max(64, int(k * scale))

    kv_id, kv_base = al.tensor(1536 << 10, REUSE_RESIDENT)   # KV cache 1.5 MB
    wf_id, wf_base = al.tensor(2560 << 10, REUSE_RESIDENT)   # FFN W1+W2 2.5 MB
    emb_id, emb_base = al.tensor(5 << 20, REUSE_RESIDENT)    # embeddings 5 MB
    q_id, q_base = al.tensor(32 << 10, REUSE_MEDIUM)         # live Q rows
    sm_id, sm_base = al.tensor(24 << 10, REUSE_MEDIUM)       # score rows
    act_id, act_base = al.tensor(64 << 20, REUSE_STREAMING)

    for core in range(4):
        b.add(core, 500 + core, q_id, REUSE_MEDIUM, False,
              b.hot(q_base, 32 << 10, n(70_000)))
        b.add(core, 510 + core, sm_id, REUSE_MEDIUM, False,
              b.hot(sm_base, 24 << 10, n(55_000)))
        # attention: sequential K/V row walk per query block
        quarter = (1536 << 10) // 4
        b.add(core, 520 + core, kv_id, REUSE_RESIDENT, False,
              b.walk(kv_base + core * quarter, quarter, reps=3))
        b.add(core, 530 + core, emb_id, REUSE_RESIDENT, False,
              b.gather(emb_base, 5 << 20, n(28_000)))
        b.add(core, 540 + core, act_id, REUSE_STREAMING, True,
              b.stream(act_base + core * (12 << 20), n(14_000)))
    # Gemmini: FFN GEMM re-walks W1+W2 for every token tile
    b.add(GEMMINI, 600, wf_id, REUSE_RESIDENT, False,
          b.walk(wf_base, 2560 << 10, reps=2, step_lines=2))
    b.add(GEMMINI, 610, act_id, REUSE_STREAMING, False,
          b.stream(act_base + (48 << 20), n(22_000)))
    b.n_macro = n(4_800)
    return _finish(b)


WORKLOADS = {
    "cnn": cnn_trace,
    "rnn": rnn_trace,
    "transformer": transformer_trace,
}


def suite(scale: float = 1.0) -> List[Dict]:
    return [gen(scale) for gen in WORKLOADS.values()]
