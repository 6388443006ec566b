"""JAX-native batched twin of the SoA simulation engine.

A functional, array-state port of ``SoAHierarchySim``/``_sim_kernel.c``:
all simulator state (tag stores, MESI directory, stride/ML prefetcher
tables, tensor-aware reuse buckets, hybrid-memory heat counters) lives
in fixed-shape integer arrays threaded through a ``lax.scan`` over
the trace columns.  Numeric policy knobs are packed into a flat
``ConfigArrays`` pytree of scalars so ``jax.vmap`` evaluates N
hierarchy points against one trace in a single jitted device program;
structural knobs (set counts, associativity, feature flags, prefetch
degree, replacement policy) are Python-static and select the compiled
"shape bucket".

Bit-identity with the reference engine is the contract
(tests/test_simulator_equiv.py): every double operation of the C
kernel happens in the same order and rounds the same way — on int64
bit patterns in exact integer arithmetic (``core/f64bits.py``), since
an accelerator's float64 need not be IEEE (x64 is enabled for the
duration of a run) — and every Python-dict tie-break is reproduced, using the same devices as
the C kernel (fill-sequence numbers, insertion-ordered linked dicts,
first-index argmin/argmax).  Dict-shaped state maps onto arrays via:

* a *frozen* open-addressing table of all trace blocks (built offline
  in numpy) that gives every directory lookup a precomputed slot —
  the directory itself is two dense columns with (mask=0, owner=-1)
  doubling as "absent", which is exactly the C kernel's
  created-then-emptied state;
* an insertable page table for the hybrid-memory heat/persist/location
  maps, with the per-window decay applied *lazily* per page in closed
  form (epoch counting) — exact because the C decay is independent
  per key;
* bounded linked dicts (slot pool + hash with backshift deletion) for
  the prefetcher pending tables, replicating FIFO-of-still-present
  eviction.

Capacity ceilings that the dict engines do not have are guarded two
ways: statically where the trace bounds them (dense per-PC prefetcher
tables never evict because traces carry only a handful of PCs per
requester) and by runtime overflow flags checked after the scan —
a full table raises ``JaxEngineOverflow`` instead of silently
diverging.
"""

from __future__ import annotations

import dataclasses
import hashlib
import time
from typing import Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from repro.core import f64bits as fb
from repro.core import native as _native
from repro.core import params as params_mod
from repro.core.params import LINE_SIZE, PAGE_SIZE
from repro.runtime import compile_cache

compile_cache.configure()

_EMPTY = np.int64(-(1 << 62))      # hash-slot "no key" sentinel
_K1, _K2, _K3 = (fb.bits(v) for v in (1.0, 2.0, 3.0))  # bucket keys
_PROBE = 32                        # linear-probe window (overflow-flagged)

# overflow-flag bits (checked after the scan)
_F_PAGE, _F_MK, _F_LD, _F_SHADOW, _F_BLK, _F_POOL, _F_RANGE = (
    1, 2, 4, 8, 16, 32, 64)
_FLAG_NAMES = {_F_PAGE: "page table", _F_MK: "markov table",
               _F_LD: "pending-dict hash", _F_SHADOW: "shadow hash",
               _F_BLK: "block table probe", _F_POOL: "pending pool",
               _F_RANGE: "utility counts beyond 2**40"}


class JaxEngineError(RuntimeError):
    pass


class JaxEngineUnsupported(JaxEngineError):
    """Configuration/trace outside the jax engine's static envelope."""


class JaxEngineOverflow(JaxEngineError):
    """A fixed-capacity table overflowed at runtime (never silent)."""


# ---------------------------------------------------------------------------
# static / batched config split
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class StaticConfig:
    """Structural knobs: one compiled program per distinct value."""

    n_req: int
    n_cores: int
    s1: int
    a1: int
    s2: int
    a2: int
    s3: int
    a3: int
    has_l3: bool
    mesi: bool
    pf_on: bool
    ml_on: bool
    ta1: bool
    ta2: bool
    ta3: bool
    hybrid: bool
    nten: int
    st_tsize: int
    st_deg: int
    ml_tsize: int
    ml_hist: int
    hbm_pages_max: int
    ta_sample: int
    ta_shadow: int
    # channel / timing constants (identical across presets, kept static)
    d_bl: float
    d_rhl: float
    d_bw: float
    d_gap: float
    d_rbb: int
    h_bl: float
    h_rhl: float
    h_bw: float
    h_gap: float
    h_rbb: int
    core_mlp: float
    accel_mlp: float
    c2c_lat: float
    inv_lat: float
    pf_throttle: float

    @property
    def s1b(self) -> int:
        return (self.s1 - 1).bit_length()

    @property
    def s2b(self) -> int:
        return (self.s2 - 1).bit_length()

    @property
    def s3b(self) -> int:
        return (self.s3 - 1).bit_length() if self.has_l3 else 0


#: batched per-lane scalars (ConfigArrays pytree); everything here can
#: differ across vmap lanes without recompiling.  The field lists and
#: the numpy stacking/padding live in ``core/params.py`` (importable
#: without jax); this module only converts the stacked arrays to jnp.
_CFG_I = params_mod.LANE_INT_FIELDS
_CFG_F = params_mod.LANE_FLOAT_FIELDS


def split_config(sp, nten: int) -> Tuple[StaticConfig, Dict[str, float]]:
    """Lower a SystemParams to (StaticConfig, ConfigArrays row) via the
    same ci/cd packing the C kernel consumes (single source of truth)."""
    packed = _native.pack_config_sp(sp, nten)
    if packed is None:
        raise JaxEngineUnsupported(
            f"{sp.name}: outside the array-kernel envelope "
            f"(see core/native.py pack_config_sp)")
    ci, cd = packed
    N = _native
    static = StaticConfig(
        n_req=int(ci[N.CI_NREQ]), n_cores=int(ci[N.CI_NCORES]),
        s1=int(ci[N.CI_S1]), a1=int(ci[N.CI_A1]),
        s2=int(ci[N.CI_S2]), a2=int(ci[N.CI_A2]),
        s3=int(ci[N.CI_S3]), a3=int(ci[N.CI_A3]),
        has_l3=bool(ci[N.CI_HASL3]), mesi=bool(ci[N.CI_MESI]),
        pf_on=bool(ci[N.CI_PFON]), ml_on=bool(ci[N.CI_MLON]),
        ta1=bool(ci[N.CI_TA1]), ta2=bool(ci[N.CI_TA2]),
        ta3=bool(ci[N.CI_TA3]), hybrid=bool(ci[N.CI_HYBRID]),
        nten=int(ci[N.CI_NTEN]), st_tsize=int(ci[N.CI_ST_TSIZE]),
        st_deg=int(ci[N.CI_ST_DEG]), ml_tsize=int(ci[N.CI_ML_TSIZE]),
        ml_hist=int(ci[N.CI_ML_HIST]),
        hbm_pages_max=int(ci[N.CI_HBM_PAGES_MAX]),
        ta_sample=int(ci[N.CI_TA_SAMPLE]),
        ta_shadow=int(ci[N.CI_TA_SHADOW]),
        d_bl=float(cd[N.CD_D_BL]), d_rhl=float(cd[N.CD_D_RHL]),
        d_bw=float(cd[N.CD_D_BW]), d_gap=float(cd[N.CD_D_GAP]),
        d_rbb=int(cd[N.CD_D_RBB]),
        h_bl=float(cd[N.CD_H_BL]), h_rhl=float(cd[N.CD_H_RHL]),
        h_bw=float(cd[N.CD_H_BW]), h_gap=float(cd[N.CD_H_GAP]),
        h_rbb=int(cd[N.CD_H_RBB]),
        core_mlp=float(cd[N.CD_CORE_MLP]),
        accel_mlp=float(cd[N.CD_ACCEL_MLP]),
        c2c_lat=float(cd[N.CD_C2C]), inv_lat=float(cd[N.CD_INV]),
        pf_throttle=float(cd[N.CD_PF_THROTTLE]),
    )
    for c in (static.core_mlp, static.accel_mlp):
        if not fb.exact_divisor(c):
            raise JaxEngineUnsupported(
                f"{sp.name}: MLP {c!r} is not a small odd multiple of a "
                f"power of two (f64bits.div_const)")
    cfg = {
        "st_conf": int(ci[N.CI_ST_CONF]),
        "hp_hot": int(ci[N.CI_HP_HOT]),
        "hp_window": int(ci[N.CI_HP_WINDOW]),
        "ta_decay": int(ci[N.CI_TA_DECAY]),
        "ml_thresh": float(cd[N.CD_ML_THRESH]),
        "migcost": float(cd[N.CD_HP_MIGCOST]),
        "ta_low": float(cd[N.CD_TA_LOW]),
        "ta_high": float(cd[N.CD_TA_HIGH]),
        "ta_pref": float(cd[N.CD_TA_PREF]),
        "ta_stream": float(cd[N.CD_TA_STREAM]),
        "ta_bypass": float(cd[N.CD_TA_BYPASS]),
        "hl1": float(ci[N.CI_HL1]),
        "hl2": float(ci[N.CI_HL2]),
        "hl3": float(ci[N.CI_HL3]),
    }
    return static, cfg


# ---------------------------------------------------------------------------
# offline trace preparation (numpy): pc ids, frozen block table, page table
# ---------------------------------------------------------------------------
def _np_hash64(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint64)
    with np.errstate(over="ignore"):
        x ^= x >> np.uint64(33)
        x *= np.uint64(0xFF51AFD7ED558CCD)
        x ^= x >> np.uint64(33)
        x *= np.uint64(0xC4CEB9FE1A85EC53)
        x ^= x >> np.uint64(33)
    return x


def _pow2_at_least(n: int) -> int:
    c = 16
    while c < n:
        c <<= 1
    return c


def _build_table(keys: np.ndarray, cap: int) -> np.ndarray:
    """Open-addressing insert of ``keys`` (unique) into a power-of-two
    table; grows until the longest occupied run stays < _PROBE so the
    in-scan windowed probe is exact for present *and* absent keys."""
    while True:
        tab = np.full(cap, _EMPTY, np.int64)
        mask = cap - 1
        homes = (_np_hash64(keys) & np.uint64(mask)).astype(np.int64)
        ok = True
        for k, i in zip(keys.tolist(), homes.tolist()):
            steps = 0
            while tab[i] != _EMPTY:
                i = (i + 1) & mask
                steps += 1
                if steps >= _PROBE:
                    ok = False
                    break
            if not ok:
                break
            tab[i] = k
        if ok:
            # longest circular run of occupied slots must leave the
            # windowed probe room to reach an empty slot (this makes
            # absent-key probes exact too)
            empties = np.flatnonzero(tab == _EMPTY)
            if len(empties):
                runs = np.diff(empties) - 1
                wrap = empties[0] + (cap - 1 - empties[-1])
                longest = int(max(runs.max(initial=0), wrap))
                if longest < _PROBE - 1:
                    return tab
        cap <<= 1


def _lookup_slots(tab: np.ndarray, keys: np.ndarray) -> np.ndarray:
    slot_of = {int(k): i for i, k in enumerate(tab.tolist())
               if k != _EMPTY}
    return np.array([slot_of[k] for k in keys.tolist()], np.int64)


@dataclasses.dataclass(frozen=True)
class Caps:
    """Array extents a trace is prepared at.  Every array shape of the
    compiled scan derives from a StaticConfig, these extents and the lane
    count, so traces prepared at equal caps share one compiled program
    per shape bucket: a campaign prepares all its traces at their common
    envelope (:meth:`cover`).  Larger extents never change a result."""

    blk: int    # frozen block-table slots (MESI directory columns)
    pg: int     # page-table slots (hybrid heat / persist / location)
    n_pc: int   # dense per-PC prefetcher rows
    mk: int     # markov-table slots per requester
    nten: int   # tensor-aware rows

    @staticmethod
    def of(trace: Dict) -> "Caps":
        """The extents this trace needs on its own."""
        addr = np.asarray(trace["addr"], np.int64)
        n = len(addr)
        return Caps(
            blk=_pow2_at_least(max(1024, 3 * len(np.unique(addr >> 6)))),
            pg=_pow2_at_least(max(2048, 8 * len(np.unique(addr >> 12)))),
            n_pc=len(np.unique(np.asarray(trace["pc"]))),
            # markov capacity scales with trace length; overflow-flagged
            mk=_pow2_at_least(min(max(4096, n // 4), 65536)),
            nten=_nten(trace))

    @staticmethod
    def cover(caps: List["Caps"]) -> "Caps":
        return Caps(*(max(getattr(c, f.name) for c in caps)
                      for f in dataclasses.fields(Caps)))


class PreparedTrace:
    """Trace columns + offline-derived slot columns and frozen tables,
    laid out at ``caps``."""

    def __init__(self, static: StaticConfig, trace: Dict, caps: Caps):
        core = np.asarray(trace["core"], np.int64)
        pc = np.asarray(trace["pc"], np.int64)
        addr = np.asarray(trace["addr"], np.int64)
        write = np.asarray(trace["write"], bool)
        tensor = np.asarray(trace["tensor"], np.int64)
        reuse = np.asarray(trace["reuse"], np.int64)
        n = len(core)
        if np.any(addr < 0):
            raise JaxEngineUnsupported("negative addresses unsupported")
        if n >= 1 << 31:
            # per-page heat counts accesses and is kept in int32
            raise JaxEngineUnsupported(
                f"{n} accesses: traces are bounded at 2**31 - 1")

        upc, pc_id = np.unique(pc, return_inverse=True)
        if static.pf_on and len(upc) > min(static.st_tsize, 512):
            # dense per-PC prefetcher tables rely on the FIFO caps
            # (stride table / ML history dict) never firing
            raise JaxEngineUnsupported(
                f"{len(upc)} distinct PCs exceeds the dense prefetcher "
                f"table bound {min(static.st_tsize, 512)}")
        self.n_pc = max(caps.n_pc, len(upc))
        self.mk_cap = caps.mk

        blocks = addr >> 6
        self.blk_tab = _build_table(np.unique(blocks), caps.blk)
        blk_slot = _lookup_slots(self.blk_tab, blocks)

        pages = addr >> 12
        self.pg_tab = _build_table(np.unique(pages), caps.pg)
        self.pg_cap = len(self.pg_tab)
        pg_slot = _lookup_slots(self.pg_tab, pages)
        if static.hybrid and static.hbm_pages_max <= self.pg_cap:
            raise JaxEngineUnsupported(
                "HBM capacity within page-table reach: the cold-page "
                "eviction path would be live (unported)")

        # per-entry perceptron pc feature (exact python ints)
        if static.pf_on and static.ml_on:
            f1 = np.array([(int(p) * 2654435761) % static.ml_tsize
                           for p in pc.tolist()], np.int64)
        else:
            f1 = np.zeros(n, np.int64)

        self.n = n
        self.xs = {
            "r": core, "a": addr, "w": write, "ten": tensor, "reu": reuse,
            "pc": pc_id.astype(np.int64), "f1": f1,
            "blk_slot": blk_slot, "pg_slot": pg_slot,
            "valid": np.ones(n, bool),
        }

    def padded(self, m: int) -> Dict[str, np.ndarray]:
        """The step columns padded to ``m`` steps with no-op
        (``valid=False``) steps."""
        return {k: np.concatenate([v, np.zeros(m - self.n, v.dtype)])
                for k, v in self.xs.items()}


_PREP_CACHE: Dict[tuple, PreparedTrace] = {}


def _trace_digest(trace: Dict) -> str:
    h = hashlib.blake2b(digest_size=16)
    for k in ("core", "pc", "addr", "write", "tensor", "reuse"):
        h.update(np.ascontiguousarray(trace[k]).tobytes())
    return h.hexdigest()


def prepare_trace(static: StaticConfig, trace: Dict,
                  caps: Caps) -> PreparedTrace:
    key = (_trace_digest(trace), caps,
           static.pf_on, static.ml_on, static.ml_tsize, static.st_tsize,
           static.hybrid, static.hbm_pages_max)
    hit = _PREP_CACHE.get(key)
    if hit is None:
        hit = PreparedTrace(static, trace, caps)
        if len(_PREP_CACHE) > 32:
            _PREP_CACHE.clear()
        _PREP_CACHE[key] = hit
    return hit


# ---------------------------------------------------------------------------
# state construction
# ---------------------------------------------------------------------------
_SP_POOL, _SP_HASH = 4100, 16384       # stride pending: cap 4096 (+put slack)
_MP_POOL, _MP_HASH = 2052, 8192        # ML pending: cap 2048


def _cache_arrays(prefix: str, inst: int, S: int, A: int) -> Dict:
    n = inst * S * A
    return {
        prefix + "t": np.zeros(n, np.int64),
        prefix + "v": np.zeros(n, bool),
        prefix + "d": np.zeros(n, bool),
        prefix + "p": np.zeros(n, bool),
        prefix + "u": np.zeros(n, np.int64),
        prefix + "n": np.zeros(n, np.int64),
        prefix + "l": np.zeros(n, np.int64),     # doubles as bit patterns
        prefix + "r": np.zeros(n, np.int64),
        prefix + "q": np.zeros(n, np.int64),
        prefix + "_ctr": np.int64(0),
        prefix + "_ev": np.int64(0),
        prefix + "_dev": np.int64(0),
        prefix + "_pf": np.int64(0),
    }


def _ta_arrays(prefix: str, inst: int, nten: int, shadow: int) -> Dict:
    shcap = _pow2_at_least(4 * shadow)
    return {
        # bucket as the order key of 1.0 / 2.0 / 3.0; utility as the
        # exact quotient utn / utd (utd = 0: no fills, utility 1.0)
        prefix + "_bkt": np.full((inst, nten), _K3, np.int64),
        prefix + "_utn": np.zeros((inst, nten), np.int64),
        prefix + "_utd": np.zeros((inst, nten), np.int64),
        prefix + "_fil": np.zeros((inst, nten), np.int64),
        prefix + "_hit": np.zeros((inst, nten), np.int64),
        prefix + "_ref": np.zeros((inst, nten), np.int64),
        prefix + "_sin": np.zeros(inst, np.int64),
        prefix + "_shr": np.zeros((inst, shadow), np.int64),
        prefix + "_shl": np.zeros(inst, np.int64),
        prefix + "_shh": np.zeros(inst, np.int64),
        prefix + "_shk": np.full((inst, shcap), _EMPTY, np.int64),
    }


def _ldict_arrays(prefix: str, R: int, pool: int, hcap: int,
                  nv: int) -> Dict:
    return {
        prefix + "pk": np.zeros((R, pool), np.int64),
        prefix + "pv": np.zeros((R, pool, nv), np.int64),
        prefix + "prv": np.full((R, pool), -1, np.int64),
        prefix + "nxt": np.full((R, pool), -1, np.int64),
        prefix + "hd": np.full(R, -1, np.int64),
        prefix + "tl": np.full(R, -1, np.int64),
        prefix + "cnt": np.zeros(R, np.int64),
        prefix + "fs": np.tile(np.arange(pool, dtype=np.int64), (R, 1)),
        prefix + "ft": np.full(R, pool, np.int64),
        prefix + "hk": np.full((R, hcap), _EMPTY, np.int64),
        prefix + "hv": np.zeros((R, hcap), np.int64),
    }


def init_state(S: StaticConfig, prep: PreparedTrace) -> Dict:
    R, P = S.n_req, prep.n_pc
    st = {}
    st.update(_cache_arrays("l1", R, S.s1, S.a1))
    st.update(_cache_arrays("l2", R, S.s2, S.a2))
    if S.has_l3:
        st.update(_cache_arrays("l3", 1, S.s3, S.a3))
        st.update({"l3h": np.int64(0), "l3m": np.int64(0),
                   "l3pu": np.int64(0)})
    for lv, ta, inst in (("l1", S.ta1, R), ("l2", S.ta2, R),
                         ("l3", S.ta3, 1)):
        if ta:
            st.update(_ta_arrays(lv, inst, S.nten, S.ta_shadow))
    for k in ("l1h", "l1m", "l1pu", "l2h", "l2m", "l2pu"):
        st[k] = np.zeros(R, np.int64)
    if S.mesi:
        st["dirm"] = np.zeros(len(prep.blk_tab), np.int64)
        st["diro"] = np.full(len(prep.blk_tab), -1, np.int64)
        st.update({"dinv": np.int64(0), "dc2c": np.int64(0),
                   "dupg": np.int64(0)})
    # memory channels
    # every double of the state is held as its int64 bit pattern (f64bits)
    st.update({"db": np.int64(0), "ds": np.int64(0),
               "dby": np.int64(0), "dac": np.int64(0),
               "drh": np.int64(0), "dop": np.full(8, -1, np.int64)})
    if S.hybrid:
        st.update({"hb": np.int64(0), "hs": np.int64(0),
                   "hby": np.int64(0), "hac": np.int64(0),
                   "hrh": np.int64(0), "hop": np.full(8, -1, np.int64),
                   "pgk": prep.pg_tab.copy(),
                   "pgh": np.zeros(prep.pg_cap, np.int64),
                   "pgp": np.zeros(prep.pg_cap, np.int64),
                   "pge": np.zeros(prep.pg_cap, np.int64),
                   "pgl": np.zeros(prep.pg_cap, np.int64),
                   "epoch": np.int64(0), "sdec": np.int64(0),
                   "hpg": np.int64(0)})
    st.update({"mig": np.int64(0), "migb": np.int64(0),
               "migs": np.int64(0)})
    if S.pf_on:
        for k in ("sta", "sts", "stc", "sai", "sau"):
            st[k] = np.zeros((R, P), np.int64)
        st["stp"] = np.zeros((R, P), bool)
        st["sti"] = np.zeros(R, np.int64)
        st.update(_ldict_arrays("sp", R, _SP_POOL, _SP_HASH, 1))
        if S.ml_on:
            st["mhl"] = np.zeros((R, P), np.int64)
            st["mhb"] = np.zeros((R, P, 9), np.int64)
            mk = prep.mk_cap
            st.update({"mk1": np.full((R, mk), -1, np.int64),
                       "mk2": np.zeros((R, mk), np.int64),
                       "mk3": np.zeros((R, mk), np.int64),
                       "mkc": np.zeros((R, mk), np.int64),
                       "mkd": np.zeros((R, mk, 9), np.int32),
                       "mko": np.zeros((R, mk, 9), np.int32)})
            # perceptron weights in exact units of 1/8
            for k in ("wpc", "wd1", "wd2"):
                st[k] = np.zeros((R, S.ml_tsize), np.int64)
            st["wbs"] = np.zeros(R, np.int64)
            st.update(_ldict_arrays("mp", R, _MP_POOL, _MP_HASH, 3))
            st["mli"] = np.zeros(R, np.int64)
            st["mlt"] = np.zeros(R, np.int64)
    st.update({"time": np.zeros(R, np.int64), "lat": np.int64(0),
               "nacc": np.int64(0), "wbl": np.int64(0),
               "pfd": np.int64(0), "flags": np.int64(0)})
    return st


# ---------------------------------------------------------------------------
# the step function
# ---------------------------------------------------------------------------
def _h64j(x):
    x = x.astype(jnp.uint64)
    x = x ^ (x >> jnp.uint64(33))
    x = x * jnp.uint64(0xFF51AFD7ED558CCD)
    x = x ^ (x >> jnp.uint64(33))
    x = x * jnp.uint64(0xC4CEB9FE1A85EC53)
    x = x ^ (x >> jnp.uint64(33))
    return x


# repro: lint-ok[TH002] known copy-insertion hazard, ROADMAP open item 1 — pre-update gathers on the dict carry; its cost on the chip is not measured yet, accepted until the fused-update rewrite lands
def _make_step(S: StaticConfig):
    i64 = jnp.int64
    R, NC = S.n_req, S.n_cores
    # the static doubles of the timing model, computed on the host in
    # the C kernel's operation order and carried as bit patterns
    B = fb.bits
    XFER = {ch: (B(64.0 / bw), B(64.0 / bw + gap))
            for ch, bw, gap in (("d", S.d_bw, S.d_gap),
                                ("h", S.h_bw, S.h_gap))}
    LATC = {"d": (B(float(S.d_rhl)), B(float(S.d_bl))),
            "h": (B(float(S.h_rhl)), B(float(S.h_bl)))}
    PROMOTED = {"d": B(float(S.d_rhl) + 64.0 / S.d_bw),
                "h": B(float(S.h_rhl) + 64.0 / S.h_bw)}
    PAGE_XFER = {"d": B(4096.0 / S.d_bw), "h": B(4096.0 / S.h_bw)}
    INF, ONE, TWO, TWELVE = B(float("inf")), B(1.0), B(2.0), B(12.0)
    S1, A1, s1b = S.s1, S.a1, S.s1b
    S2, A2, s2b = S.s2, S.a2, S.s2b
    S3, A3, s3b = S.s3, S.a3, S.s3b
    LVL = {"l1": (A1, S1, s1b, S.ta1), "l2": (A2, S2, s2b, S.ta2),
           "l3": (A3, S3, s3b, S.ta3)}
    BIG_I = jnp.int64(1 << 62)

    # 64-bit integer division, and shifts by traced amounts, are emulated
    # on the TPU and dominate its compile time: divide by static powers
    # of two with shifts and masks (exact for the floor semantics of
    # Python and the C kernel), and keep traced shifts in int32
    def div_c(v, d):  # v // d, d static > 0
        return v >> (d.bit_length() - 1) if d & (d - 1) == 0 else v // d

    def mod_c(v, d):  # v % d (floor), d static > 0
        return v & (d - 1) if d & (d - 1) == 0 else jnp.mod(v, d)

    def pmod(v, m):  # Python (v * 2654435761) % m, m static > 0
        return mod_c(v * jnp.int64(2654435761), m)

    def probe(keys, key, row=None):
        """Windowed linear probe of a 1-D key table (``_EMPTY`` = free),
        or of row ``row`` of a 2-D one.
        Returns (slot, found, insert_slot_ok, window_exhausted)."""
        cap = keys.shape[-1]
        home = (_h64j(key) & jnp.uint64(cap - 1)).astype(i64)
        idx = (home + jnp.arange(_PROBE, dtype=i64)) & (cap - 1)
        ks = keys[idx] if row is None else keys[row, idx]
        match = ks == key
        empty = ks == _EMPTY
        stop = match | empty
        any_stop = jnp.any(stop)
        first = jnp.argmax(stop)
        slot = idx[first]
        found = any_stop & match[first]
        can_ins = any_stop & empty[first]
        return slot, found, can_ins, ~any_stop

    def backshift(hk, hv, slot, pred):
        """C map_del: backshift deletion keeping probe chains intact.
        Operates on one hash row (keys + value column), masked."""
        cap = hk.shape[0]
        mask = cap - 1

        def body(c):
            hk_, hv_, i, j, run = c
            j2 = (j + 1) & mask
            kj = hk_[j2]
            empty = kj == _EMPTY
            home = (_h64j(kj) & jnp.uint64(mask)).astype(i64)
            d_cur = (j2 - home) & mask
            d_new = (i - home) & mask
            move = run & (~empty) & (d_new <= d_cur)
            hk_ = hk_.at[i].set(jnp.where(move, kj, hk_[i]))
            if hv_ is not None:
                hv_ = hv_.at[i].set(jnp.where(move, hv_[j2], hv_[i]))
            i = jnp.where(move, j2, i)
            return hk_, hv_, i, j2, run & ~empty

        if hv is None:
            def body1(c):
                a, i, j, run = c
                a, _, i, j, run = body((a, None, i, j, run))
                return a, i, j, run
            hk, i, _, _ = lax.while_loop(
                lambda c: c[3], body1, (hk, slot, slot, pred))
            hk = hk.at[i].set(jnp.where(pred, _EMPTY, hk[i]))
            return hk, None
        hk, hv, i, _, _ = lax.while_loop(
            lambda c: c[4], body, (hk, hv, slot, slot, pred))
        hk = hk.at[i].set(jnp.where(pred, _EMPTY, hk[i]))
        return hk, hv

    def popcount(x):
        t = jnp.int64(0)
        for k in range(R):
            t = t + ((x >> k) & 1)
        return t

    def step(consts, cfg, st_in, x):
        st = dict(st_in)

        def flag(cond, bit):
            st["flags"] = st["flags"] | jnp.where(cond, i64(bit), i64(0))

        def rolled(n, body):
            """``for k in range(n): body(k)`` as one rolled loop, so the
            body is traced and compiled once instead of n times.  The
            carry is the part of the state ``body`` writes (found by a
            trial trace); the rest stays read-only, so the compiler has
            no reason to copy those buffers into the loop."""
            outer = dict(st)
            body(i64(0))
            keys = [k for k in st if st[k] is not outer[k]]
            st.clear()
            st.update(outer)

            def it(k, carry):
                st.update(carry)
                body(k)
                return {key: st[key] for key in keys}
            out = lax.fori_loop(0, n, it, {k: st[k] for k in keys})
            st.clear()
            st.update(outer)
            st.update(out)

        # ---- linked dict (FIFO-capped map: C Fifo) ----------------------
        def ld_len(p, rr):
            return st[p + "cnt"][rr]

        def ld_pop(p, rr, key, pred):
            slot, found, _, ovf = probe(st[p + "hk"][rr], key)
            flag(pred & ovf, _F_LD)
            act = pred & found
            pi = jnp.where(found, st[p + "hv"][rr, slot], 0)
            val = st[p + "pv"][rr, pi]
            hk, hv = backshift(st[p + "hk"][rr], st[p + "hv"][rr],
                               slot, act)
            st[p + "hk"] = st[p + "hk"].at[rr].set(hk)
            st[p + "hv"] = st[p + "hv"].at[rr].set(hv)
            _ld_unlink(p, rr, pi, act)
            return act, val

        def _ld_unlink(p, rr, pi, pred):
            prv = st[p + "prv"][rr, pi]
            nxt = st[p + "nxt"][rr, pi]
            has_p, has_n = prv >= 0, nxt >= 0
            ip = jnp.maximum(prv, 0)
            inx = jnp.maximum(nxt, 0)
            st[p + "nxt"] = st[p + "nxt"].at[rr, ip].set(
                jnp.where(pred & has_p, nxt, st[p + "nxt"][rr, ip]))
            st[p + "hd"] = st[p + "hd"].at[rr].set(
                jnp.where(pred & ~has_p, nxt, st[p + "hd"][rr]))
            st[p + "prv"] = st[p + "prv"].at[rr, inx].set(
                jnp.where(pred & has_n, prv, st[p + "prv"][rr, inx]))
            st[p + "tl"] = st[p + "tl"].at[rr].set(
                jnp.where(pred & ~has_n, prv, st[p + "tl"][rr]))
            ft = st[p + "ft"][rr]
            ift = jnp.minimum(ft, st[p + "fs"].shape[1] - 1)
            st[p + "fs"] = st[p + "fs"].at[rr, ift].set(
                jnp.where(pred, pi, st[p + "fs"][rr, ift]))
            st[p + "ft"] = st[p + "ft"].at[rr].set(
                ft + jnp.where(pred, 1, 0))
            st[p + "cnt"] = st[p + "cnt"].at[rr].add(
                jnp.where(pred, -1, 0))

        def ld_put(p, rr, key, vals, pred):
            slot, found, can_ins, ovf = probe(st[p + "hk"][rr], key)
            flag(pred & (ovf | (~found & ~can_ins)), _F_LD)
            new = pred & ~found
            ft = st[p + "ft"][rr]
            flag(new & (ft <= 0), _F_POOL)
            pi_new = st[p + "fs"][rr, jnp.maximum(ft - 1, 0)]
            pi = jnp.where(found, st[p + "hv"][rr, slot], pi_new)
            st[p + "ft"] = st[p + "ft"].at[rr].set(
                jnp.where(new, ft - 1, ft))
            st[p + "pk"] = st[p + "pk"].at[rr, pi].set(
                jnp.where(pred, key, st[p + "pk"][rr, pi]))
            row = st[p + "pv"][rr, pi]
            st[p + "pv"] = st[p + "pv"].at[rr, pi].set(
                jnp.where(pred, jnp.stack(vals), row))
            tl = st[p + "tl"][rr]
            has_t = tl >= 0
            itl = jnp.maximum(tl, 0)
            st[p + "prv"] = st[p + "prv"].at[rr, pi].set(
                jnp.where(new, tl, st[p + "prv"][rr, pi]))
            st[p + "nxt"] = st[p + "nxt"].at[rr, pi].set(
                jnp.where(new, -1, st[p + "nxt"][rr, pi]))
            st[p + "nxt"] = st[p + "nxt"].at[rr, itl].set(
                jnp.where(new & has_t, pi, st[p + "nxt"][rr, itl]))
            st[p + "hd"] = st[p + "hd"].at[rr].set(
                jnp.where(new & ~has_t, pi, st[p + "hd"][rr]))
            st[p + "tl"] = st[p + "tl"].at[rr].set(
                jnp.where(new, pi, st[p + "tl"][rr]))
            st[p + "cnt"] = st[p + "cnt"].at[rr].add(
                jnp.where(new, 1, 0))
            st[p + "hk"] = st[p + "hk"].at[rr, slot].set(
                jnp.where(new, key, st[p + "hk"][rr, slot]))
            st[p + "hv"] = st[p + "hv"].at[rr, slot].set(
                jnp.where(new, pi, st[p + "hv"][rr, slot]))

        def ld_evict(p, rr, pred):
            act = pred & (st[p + "cnt"][rr] > 0)
            pi = jnp.maximum(st[p + "hd"][rr], 0)
            key = st[p + "pk"][rr, pi]
            val = st[p + "pv"][rr, pi]
            slot, found, _, ovf = probe(st[p + "hk"][rr], key)
            flag(act & (ovf | ~found), _F_LD)
            hk, hv = backshift(st[p + "hk"][rr], st[p + "hv"][rr],
                               slot, act & found)
            st[p + "hk"] = st[p + "hk"].at[rr].set(hk)
            st[p + "hv"] = st[p + "hv"].at[rr].set(hv)
            _ld_unlink(p, rr, pi, act)
            return act, key, val

        # ---- tensor-aware shadow / bucket machinery ---------------------
        def util_lt(n, d, t):
            """The C kernel's ``util < t`` with util = min(n / d, 4.0),
            or 1.0 without fills (d = 0), decided exactly (f64bits)."""
            return jnp.where(d == 0, t["one"] == 1,
                             fb.ratio_lt(n, jnp.maximum(d, 1), t)
                             | (t["four"] == 1))

        def ta_bucket_upd(lv, inst, pred, t, all_rows):
            """Recompute utility+bucket; one tensor row (pred) or all
            rows (all_rows, used after a decay halving)."""
            f_ = st[lv + "_fil"][inst]
            num = st[lv + "_hit"][inst] + S.ta_sample * st[lv + "_ref"][inst]
            flag(pred & jnp.any(num >= 1 << 40), _F_RANGE)
            b_ = jnp.where(util_lt(num, f_, cfg["ta_low"]), _K1,
                           jnp.where(util_lt(num, f_, cfg["ta_high"]),
                                     _K2, _K3))
            rows = jnp.arange(S.nten)
            m = jnp.where(all_rows, jnp.ones(S.nten, bool), rows == t)
            m = m & pred
            st[lv + "_utn"] = st[lv + "_utn"].at[inst].set(
                jnp.where(m, num, st[lv + "_utn"][inst]))
            st[lv + "_utd"] = st[lv + "_utd"].at[inst].set(
                jnp.where(m, f_, st[lv + "_utd"][inst]))
            st[lv + "_bkt"] = st[lv + "_bkt"].at[inst].set(
                jnp.where(m, b_, st[lv + "_bkt"][inst]))

        def ta_hit(lv, inst, pred, t):
            st[lv + "_hit"] = st[lv + "_hit"].at[inst, t].add(
                jnp.where(pred, 1, 0))
            ta_bucket_upd(lv, inst, pred, t, jnp.bool_(False))

        def ta_fill(lv, inst, pred, t, blk):
            st[lv + "_fil"] = st[lv + "_fil"].at[inst, t].add(
                jnp.where(pred, 1, 0))
            sampled = pred & (blk >= 0) & (pmod(blk, S.ta_sample) == 0)
            slot, found, _, ovf = probe(st[lv + "_shk"][inst], blk)
            flag(sampled & ovf, _F_SHADOW)
            member = sampled & found
            st[lv + "_ref"] = st[lv + "_ref"].at[inst, t].add(
                jnp.where(member, 1, 0))
            do_put = sampled & ~found
            # evict FIFO-oldest from the shadow ring when full
            ev = do_put & (st[lv + "_shl"][inst] >= S.ta_shadow)
            hd = st[lv + "_shh"][inst]
            evk = st[lv + "_shr"][inst, hd]
            es, ef, _, eovf = probe(st[lv + "_shk"][inst], evk)
            flag(ev & (eovf | ~ef), _F_SHADOW)
            shk, _ = backshift(st[lv + "_shk"][inst], None, es, ev & ef)
            st[lv + "_shk"] = st[lv + "_shk"].at[inst].set(shk)
            st[lv + "_shh"] = st[lv + "_shh"].at[inst].set(
                jnp.where(ev, mod_c(hd + 1, S.ta_shadow), hd))
            st[lv + "_shl"] = st[lv + "_shl"].at[inst].add(
                jnp.where(ev, -1, 0))
            # append at ring tail + hash insert (re-probe: backshift may
            # have moved the insertion hole)
            ln = st[lv + "_shl"][inst]
            hd2 = st[lv + "_shh"][inst]
            pos = mod_c(hd2 + ln, S.ta_shadow)
            st[lv + "_shr"] = st[lv + "_shr"].at[inst, pos].set(
                jnp.where(do_put, blk, st[lv + "_shr"][inst, pos]))
            s2_, f2_, ci2, ovf2 = probe(st[lv + "_shk"][inst], blk)
            flag(do_put & (ovf2 | ~ci2 | f2_), _F_SHADOW)
            st[lv + "_shk"] = st[lv + "_shk"].at[inst, s2_].set(
                jnp.where(do_put, blk, st[lv + "_shk"][inst, s2_]))
            st[lv + "_shl"] = st[lv + "_shl"].at[inst].add(
                jnp.where(do_put, 1, 0))
            # periodic decay: halve all three rows, re-bucket everything
            st[lv + "_sin"] = st[lv + "_sin"].at[inst].add(
                jnp.where(pred, 1, 0))
            dec = pred & (st[lv + "_sin"][inst] >= cfg["ta_decay"])
            st[lv + "_sin"] = st[lv + "_sin"].at[inst].set(
                jnp.where(dec, 0, st[lv + "_sin"][inst]))
            for k in ("_fil", "_hit", "_ref"):
                row = st[lv + k][inst]
                st[lv + k] = st[lv + k].at[inst].set(
                    jnp.where(dec, row >> 1, row))
            ta_bucket_upd(lv, inst, pred, t, dec)

        # ---- set-associative cache primitives ---------------------------
        def c_probe(lv, si, tag):
            A = LVL[lv][0]
            idx = si * A + jnp.arange(A)
            m = st[lv + "v"][idx] & (st[lv + "t"][idx] == tag)
            return jnp.any(m), jnp.argmax(m), idx

        def c_insert(lv, pred, si, sset, tag, blk, ten, reu, now,
                     is_w, prefd, ready):
            """Insert (or refresh) a line; returns (victim_dirty,
            victim_addr) for writeback by the caller."""
            A, S_sets, sb, ta_on = LVL[lv]
            idx = si * A + jnp.arange(A)
            tags = st[lv + "t"][idx]
            vld = st[lv + "v"][idx]
            m = vld & (tags == tag)
            hit_any = jnp.any(m)
            hitw = jnp.argmax(m)
            freew = jnp.argmax(~vld)
            full = jnp.sum(vld) >= A
            last = st[lv + "l"][idx]
            seq = st[lv + "q"][idx]
            if ta_on:
                inst = div_c(si, S_sets)
                bkt = st[lv + "_bkt"][inst]
                bvals = jnp.where(
                    st[lv + "p"][idx], cfg["ta_pref"],
                    jnp.where(st[lv + "u"][idx] == 0, cfg["ta_stream"],
                              bkt[st[lv + "n"][idx]]))
                m1 = bvals == jnp.min(bvals)
                lmask = jnp.where(m1, last, INF)
            else:
                inst = div_c(si, S_sets)
                lmask = last
            m2 = lmask == jnp.min(lmask)
            sq = jnp.where(m2, seq, BIG_I)
            vicw = jnp.argmin(sq)
            way = jnp.where(hit_any, hitw,
                            jnp.where(full, vicw, freew))
            sl = si * A + way
            victim = pred & ~hit_any & full
            vdirty = victim & st[lv + "d"][sl]
            vaddr = ((st[lv + "t"][sl] << sb) | sset) << 6
            st[lv + "_ev"] = st[lv + "_ev"] + jnp.where(victim, 1, 0)
            st[lv + "_dev"] = st[lv + "_dev"] + jnp.where(vdirty, 1, 0)
            ctr = st[lv + "_ctr"]
            for col, val in (("v", jnp.bool_(True)), ("t", tag),
                             ("d", is_w), ("n", ten), ("u", reu),
                             ("l", now), ("p", prefd), ("r", ready),
                             ("q", ctr)):
                old = st[lv + col][sl]
                st[lv + col] = st[lv + col].at[sl].set(
                    jnp.where(pred, val, old))
            st[lv + "_ctr"] = ctr + jnp.where(pred, 1, 0)
            st[lv + "_pf"] = st[lv + "_pf"] + jnp.where(pred & prefd, 1, 0)
            if ta_on:
                ta_fill(lv, inst, pred, ten, blk)
            return victim, vdirty, vaddr

        # ---- memory channels + hybrid page heat -------------------------
        def chan_access(ch, pred, now, addr, spec):
            rbb = S.d_rbb if ch == "d" else S.h_rbb
            st[ch + "ac"] = st[ch + "ac"] + jnp.where(pred, 1, 0)
            st[ch + "by"] = st[ch + "by"] + jnp.where(pred, 64, 0)
            bank = mod_c(div_c(addr, rbb), 8)
            row = div_c(addr, rbb * 8)
            op = st[ch + "op"][bank]
            rowhit = op == row
            st[ch + "rh"] = st[ch + "rh"] + jnp.where(pred & rowhit, 1, 0)
            st[ch + "op"] = st[ch + "op"].at[bank].set(
                jnp.where(pred & ~rowhit, row, op))
            latc = jnp.where(rowhit, *LATC[ch])
            xfer = jnp.where(rowhit, *XFER[ch])
            busy = st[ch + "b"]
            sb_ = st[ch + "s"]
            # times are non-negative: max/min on their bit patterns
            if spec:
                start = jnp.maximum(jnp.maximum(now, busy), sb_)
                st[ch + "s"] = jnp.where(pred, fb.add(start, xfer), sb_)
            else:
                start = jnp.maximum(now, busy)
                nb = fb.add(start, xfer)
                st[ch + "b"] = jnp.where(pred, nb, busy)
                st[ch + "s"] = jnp.where(pred, jnp.maximum(sb_, nb), sb_)
            done = fb.add(fb.add(start, latc), xfer)
            return done, fb.sub(done, now)

        def decay_closed(h, p, k, half):
            """k lazy decay rounds in closed form: h halves each round;
            persist bumps while h (pre-halving) >= half, i.e. for
            bitlen(h // half) rounds; persist dies with the heat entry."""
            # heat is bounded by the trace length (< 2**31, checked in
            # PreparedTrace), so the bit arithmetic runs in int32:
            # bitlen(h // half) = j + [h >= half << j], where j is the
            # difference of the bit lengths of h and half (0 if j < 0)
            i32 = jnp.int32
            h32 = h.astype(i32)
            half32 = jnp.maximum(half, 1).astype(i32)
            hf = (h32 >> jnp.clip(k, 0, 31).astype(i32)).astype(i64)
            j = lax.clz(half32) - lax.clz(h32)
            ge = h32 >= (half32 << jnp.clip(j, 0, 31))
            bl_ = jnp.where(j < 0, 0, j + ge.astype(i32)).astype(i64)
            bumps = jnp.minimum(k, bl_)
            pf = jnp.where(hf > 0, p + bumps, i64(0))
            return hf, pf

        def mem_access(pred, now, addr, spec, pg_slot):
            if not S.hybrid:
                return chan_access("d", pred, now, addr, spec)
            half = cfg["hp_hot"] >> 1
            if pg_slot is None:
                page = addr >> 12
                slot, found, can_ins, ovf = probe(st["pgk"], page)
                flag(pred & (ovf | (~found & ~can_ins)), _F_PAGE)
                st["pgk"] = st["pgk"].at[slot].set(
                    jnp.where(pred & ~found, page, st["pgk"][slot]))
            else:
                slot = pg_slot
            k = st["epoch"] - st["pge"][slot]
            h0, p0 = decay_closed(st["pgh"][slot], st["pgp"][slot],
                                  k, half)
            h1 = h0 + jnp.where(pred, 1, 0)
            sd = st["sdec"] + jnp.where(pred, 1, 0)
            fired = pred & (sd >= cfg["hp_window"])
            st["sdec"] = jnp.where(fired, 0, sd)
            st["epoch"] = st["epoch"] + jnp.where(fired, 1, 0)
            h2, p2 = decay_closed(h1, p0, jnp.where(fired, 1, 0), half)
            st["pgh"] = st["pgh"].at[slot].set(
                jnp.where(pred, h2, st["pgh"][slot]))
            st["pgp"] = st["pgp"].at[slot].set(
                jnp.where(pred, p2, st["pgp"][slot]))
            st["pge"] = st["pge"].at[slot].set(
                jnp.where(pred, st["epoch"], st["pge"][slot]))
            loc = st["pgl"][slot]
            # promotion check: pre-fire heat, post-fire persist (C order)
            promote = pred & (h1 >= cfg["hp_hot"]) & (p2 >= 2) & (loc != 1)
            st["pgl"] = st["pgl"].at[slot].set(
                jnp.where(promote, 1, loc))
            st["hpg"] = st["hpg"] + jnp.where(promote, 1, 0)
            st["mig"] = st["mig"] + jnp.where(promote, 1, 0)
            st["migb"] = st["migb"] + jnp.where(promote, 4096, 0)
            st["migs"] = jnp.where(
                promote, fb.add(st["migs"], cfg["migcost"]), st["migs"])
            for ch in ("d", "h"):
                st[ch + "b"] = jnp.where(
                    promote,
                    fb.add(jnp.maximum(st[ch + "b"], now), PAGE_XFER[ch]),
                    st[ch + "b"])
            use_h = st["pgl"][slot] == 1
            dd, dv = chan_access("d", pred & ~use_h, now, addr, spec)
            hd_, hv_ = chan_access("h", pred & use_h, now, addr, spec)
            return (jnp.where(use_h, hd_, dd), jnp.where(use_h, hv_, dv))

        def wb(pred, now, vaddr):
            st["wbl"] = st["wbl"] + jnp.where(pred, 1, 0)
            mem_access(pred, now, vaddr, True, None)

        def promote_wait(lv, pred, sl, pg_slot, now):
            remaining = fb.sub_clamp(st[lv + "r"][sl], now)
            if S.hybrid:
                use_h = st["pgl"][pg_slot] == 1
                promoted = jnp.where(use_h, PROMOTED["h"], PROMOTED["d"])
            else:
                promoted = i64(PROMOTED["d"])
            st[lv + "r"] = st[lv + "r"].at[sl].set(
                jnp.where(pred, 0, st[lv + "r"][sl]))
            return jnp.minimum(remaining, promoted)

        # ---- MESI directory (dense columns over the frozen block table)
        def dir_evict_at(slot, pred, rr):
            m = st["dirm"][slot]
            o = st["diro"][slot]
            m2 = m & ~(i64(1) << rr)
            o2 = jnp.where(o == rr, i64(-1), o)
            o2 = jnp.where(m2 == 0, i64(-1), o2)
            st["dirm"] = st["dirm"].at[slot].set(jnp.where(pred, m2, m))
            st["diro"] = st["diro"].at[slot].set(jnp.where(pred, o2, o))

        # ---- fills ------------------------------------------------------
        def fill_shared(pred, blk, ten, reu, now, is_w):
            if not S.has_l3:
                return
            if S.ta3:
                byp = ((reu == 0) & ~is_w
                       & util_lt(st["l3_utn"][0, ten], st["l3_utd"][0, ten],
                                 cfg["ta_bypass"]))
            else:
                byp = jnp.bool_(False)
            ins = pred & ~byp
            s3 = blk & (S3 - 1)
            _, vd, va = c_insert("l3", ins, s3, s3, blk >> s3b, blk, ten,
                                 reu, now, jnp.bool_(False),
                                 jnp.bool_(False), i64(0))
            wb(vd, now, va)

        def fill_private(pred, rr, blk, ten, reu, now, is_w):
            s2 = blk & (S2 - 1)
            v2, vd2, va2 = c_insert("l2", pred, rr * S2 + s2, s2,
                                    blk >> s2b, blk, ten, reu, now, is_w,
                                    jnp.bool_(False), i64(0))
            if S.mesi:
                # victim leaves the private hierarchy entirely only when
                # it is not also resident in this requester's L1
                vblk = va2 >> 6
                s1v = vblk & (S1 - 1)
                in_l1, _, _ = c_probe("l1", rr * S1 + s1v, vblk >> s1b)
                dslot, dfound, _, _ = probe(consts["blk"], vblk,
                                            consts["tid"])
                dir_evict_at(dslot, v2 & ~in_l1 & dfound, rr)
            wb(vd2, now, va2)
            s1 = blk & (S1 - 1)
            _, vd1, va1 = c_insert("l1", pred, rr * S1 + s1, s1,
                                   blk >> s1b, blk, ten, reu, now, is_w,
                                   jnp.bool_(False), i64(0))
            vblk1 = va1 >> 6
            s2v = vblk1 & (S2 - 1)
            hit2, w2, _ = c_probe("l2", rr * S2 + s2v, vblk1 >> s2b)
            sl2 = (rr * S2 + s2v) * A2 + w2
            mark = vd1 & hit2
            st["l2d"] = st["l2d"].at[sl2].set(
                jnp.where(mark, True, st["l2d"][sl2]))
            wb(vd1 & ~hit2, now, va1)

        # ---- prefetchers ------------------------------------------------
        def do_prefetch(pred, rr, tgt, ten, reu, now, is_stride):
            # is_stride is a Python bool: stride and ML candidates are
            # issued from separate (static) call sites
            blk = tgt >> 6
            s2 = blk & (S2 - 1)
            in2, _, _ = c_probe("l2", rr * S2 + s2, blk >> s2b)
            act = pred & ~in2
            if S.has_l3:
                s3 = blk & (S3 - 1)
                in3, _, _ = c_probe("l3", s3, blk >> s3b)
                if is_stride:
                    # shared-level hit: cheap promote to L2
                    cp = act & in3
                    _, vd, va = c_insert(
                        "l2", cp, rr * S2 + s2, s2, blk >> s2b, blk, ten,
                        reu, now, jnp.bool_(False), jnp.bool_(True),
                        fb.add(now, cfg["hl3"]))
                    wb(vd, now, va)
                act = act & ~in3
            # throttle on the target channel's speculative backlog
            if S.hybrid:
                page = tgt >> 12
                pslot, pfound, pcan, povf = probe(st["pgk"], page)
                flag(act & (povf | (~pfound & ~pcan)), _F_PAGE)
                st["pgk"] = st["pgk"].at[pslot].set(
                    jnp.where(act & ~pfound, page, st["pgk"][pslot]))
                use_h = st["pgl"][pslot] == 1
                sb_ = jnp.where(use_h, st["hs"], st["ds"])
                bb_ = jnp.where(use_h, st["hb"], st["db"])
            else:
                pslot = None
                sb_, bb_ = st["ds"], st["db"]
            # spec_busy - busy > throttle (a negative backlog never is)
            drop = act & (fb.sub_clamp(sb_, bb_) > B(S.pf_throttle))
            st["pfd"] = st["pfd"] + jnp.where(drop, 1, 0)
            act = act & ~drop
            done, _ = mem_access(act, now, tgt, True, pslot)
            if (not is_stride) and S.has_l3:
                s3 = blk & (S3 - 1)
                _, vd, va = c_insert("l3", act, s3, s3, blk >> s3b, blk,
                                     ten, reu, now, jnp.bool_(False),
                                     jnp.bool_(True), done)
            else:
                _, vd, va = c_insert("l2", act, rr * S2 + s2, s2,
                                     blk >> s2b, blk, ten, reu, now,
                                     jnp.bool_(False), jnp.bool_(True),
                                     done)
            wb(vd, now, va)

        def stride_observe(pred, rr, pc, a):
            blk = a >> 6
            popped, val = ld_pop("sp", rr, blk, pred)
            src = jnp.where(popped, val[0], 0)
            st["sau"] = st["sau"].at[rr, src].add(jnp.where(popped, 1, 0))
            pres = st["stp"][rr, pc]
            create = pred & ~pres
            upd = pred & pres
            old_last = st["sta"][rr, pc]
            old_st = st["sts"][rr, pc]
            old_cf = st["stc"][rr, pc]
            strd = a - old_last
            same = upd & (strd != 0) & (strd == old_st)
            ncf = jnp.where(same, jnp.minimum(old_cf + 1, 7),
                            jnp.where(upd, 0, old_cf))
            nst = jnp.where(same, old_st, jnp.where(upd, strd, old_st))
            st["stp"] = st["stp"].at[rr, pc].set(
                jnp.where(create, True, pres))
            st["sta"] = st["sta"].at[rr, pc].set(
                jnp.where(pred, a, old_last))
            st["sts"] = st["sts"].at[rr, pc].set(
                jnp.where(create, 0, nst))
            st["stc"] = st["stc"].at[rr, pc].set(
                jnp.where(create, 0, ncf))
            issue = upd & (ncf >= cfg["st_conf"]) & (nst != 0)
            iss = st["sai"][rr, pc]
            used = st["sau"][rr, pc]
            # the C kernel's used / iss < 0.4 in doubles, exactly: with
            # iss < 2**53 the quotient rounds below 0.4 iff it is below 2/5
            issue = issue & ~((iss >= 32) & (5 * used < 2 * iss))
            def put_one(k):
                st["sai"] = st["sai"].at[rr, pc].add(
                    jnp.where(issue, 1, 0))
                ev = issue & (ld_len("sp", rr) > 4096)
                ld_evict("sp", rr, ev)
                ld_put("sp", rr, (a + nst * (k + 1)) >> 6, [pc], issue)
            rolled(S.st_deg, put_one)
            st["sti"] = st["sti"].at[rr].add(
                jnp.where(issue, S.st_deg, 0))
            # the k-th candidate (k = 1..st_deg) targets a + nst * k
            return issue, nst

        def ml_train(pred, rr, ff1, ff2, ff3, useful):
            # weights in units of 1/8: every value the C kernel's doubles
            # take (steps of 0.5 and 0.125, clipped to +-8) is exact
            lr = 4 if useful else -4
            for w, f in (("wpc", ff1), ("wd1", ff2), ("wd2", ff3)):
                v = jnp.clip(st[w][rr, f] + lr, -64, 64)
                st[w] = st[w].at[rr, f].set(
                    jnp.where(pred, v, st[w][rr, f]))
            vb = jnp.clip(st["wbs"][rr] + lr // 4, -64, 64)
            st["wbs"] = st["wbs"].at[rr].set(
                jnp.where(pred, vb, st["wbs"][rr]))
            st["mlt"] = st["mlt"].at[rr].add(jnp.where(pred, 1, 0))

        def mk_probe(rr, k1, k2, k3):
            """Probe the per-requester markov table for (k1,k2,k3).
            mk1 == -1 marks a free slot (k1 is a pc id, always >= 0)."""
            cap = st["mk1"].shape[1]
            h = (_h64j(k1) ^ (_h64j(k2) << jnp.uint64(1))
                 ^ (_h64j(k3) << jnp.uint64(2)))
            home = (h & jnp.uint64(cap - 1)).astype(i64)
            idx = (home + jnp.arange(_PROBE, dtype=i64)) & (cap - 1)
            a1_ = st["mk1"][rr, idx]
            match = ((a1_ == k1) & (st["mk2"][rr, idx] == k2)
                     & (st["mk3"][rr, idx] == k3))
            empty = a1_ == -1
            stop = match | empty
            any_stop = jnp.any(stop)
            first = jnp.argmax(stop)
            slot = idx[first]
            found = any_stop & match[first]
            can_ins = any_stop & empty[first]
            return slot, found, can_ins, ~any_stop

        def ml_observe(pred, rr, pc, ff1, a):
            blkm = a >> 6
            popped, pv = ld_pop("mp", rr, blkm, pred)
            ml_train(popped, rr,
                     jnp.where(popped, pv[0], 0),
                     jnp.where(popped, pv[1], 0),
                     jnp.where(popped, pv[2], 0), True)
            hl = st["mhl"][rr, pc]
            hb = st["mhb"][rr, pc]
            ar9 = jnp.arange(9)
            b2 = pred & (hl >= 2)
            hi = jnp.maximum(hl - 1, 0)
            d_new = blkm - hb[hi]
            key2 = jnp.where(hl >= 3,
                             hb[jnp.maximum(hi - 1, 0)]
                             - hb[jnp.maximum(hi - 2, 0)], 0)
            key3 = hb[hi] - hb[jnp.maximum(hi - 1, 0)]
            # markov transition update: entry (pc, key2, key3) += d_new
            es, ef, eci, eovf = mk_probe(rr, pc, key2, key3)
            flag(b2 & (eovf | (~ef & ~eci)), _F_MK)
            enew = b2 & ~ef
            for col, val in (("mk1", pc), ("mk2", key2), ("mk3", key3)):
                st[col] = st[col].at[rr, es].set(
                    jnp.where(enew, val, st[col][rr, es]))
            dr = st["mkd"][rr, es]
            co = st["mko"][rr, es]
            cnt = st["mkc"][rr, es]
            mfound = (ar9 < cnt) & (dr == d_new.astype(jnp.int32))
            fi_any = jnp.any(mfound)
            fi = jnp.argmax(mfound)
            app_i = jnp.minimum(cnt, 8)
            co2 = jnp.where(b2 & fi_any & (ar9 == fi), co + 1, co)
            dr2 = jnp.where(b2 & ~fi_any & (ar9 == app_i),
                            d_new.astype(jnp.int32), dr)
            co2 = jnp.where(b2 & ~fi_any & (ar9 == app_i),
                            jnp.int32(1), co2)
            cnt2 = cnt + jnp.where(b2 & ~fi_any, 1, 0)
            ov = b2 & (cnt2 > 8)
            cm = jnp.where(ar9 < cnt2, co2, jnp.int32(1 << 30))
            mi = jnp.argmin(cm)
            gi = jnp.minimum(ar9 + 1, 8)
            shift = ov & (ar9 >= mi)
            dr3 = jnp.where(shift, dr2[gi], dr2)
            co3 = jnp.where(shift, co2[gi], co2)
            cnt3 = cnt2 - jnp.where(ov, 1, 0)
            st["mkd"] = st["mkd"].at[rr, es].set(dr3)
            st["mko"] = st["mko"].at[rr, es].set(co3)
            st["mkc"] = st["mkc"].at[rr, es].set(
                jnp.where(b2, cnt3, cnt))
            # candidate lookup (post-update): entry (pc, key3, d_new)
            cs, cf, _, covf = mk_probe(rr, pc, key3, d_new)
            flag(b2 & covf, _F_MK)
            ccnt = jnp.where(cf, st["mkc"][rr, cs], 0)
            bc = b2 & cf & (ccnt > 0)
            cco = st["mko"][rr, cs]
            bm_ = jnp.where(ar9 < ccnt, cco, jnp.int32(-1))
            bi = jnp.argmax(bm_)
            best = st["mkd"][rr, cs][bi].astype(i64)
            bb = bc & (best != 0)
            f2 = pmod(key3, S.ml_tsize)
            f3 = pmod(d_new, S.ml_tsize)
            score = (st["wpc"][rr, ff1] + st["wd1"][rr, f2]
                     + st["wd2"][rr, f3] + st["wbs"][rr])
            # cfg["ml_thresh"] is ceil(8 * threshold): score/8 >= t exactly
            emit = bb & (score >= cfg["ml_thresh"])
            st["mli"] = st["mli"].at[rr].add(jnp.where(emit, 1, 0))
            ev = bb & (ld_len("mp", rr) > 2048)
            evd, _, evv = ld_evict("mp", rr, ev)
            ml_train(evd, rr,
                     jnp.where(evd, evv[0], 0),
                     jnp.where(evd, evv[1], 0),
                     jnp.where(evd, evv[2], 0), False)
            ld_put("mp", rr, blkm + best, [ff1, f2, f3], bb)
            # history append + trim
            st["mhb"] = st["mhb"].at[rr, pc, jnp.minimum(hl, 8)].set(
                jnp.where(pred, blkm, st["mhb"][rr, pc,
                                               jnp.minimum(hl, 8)]))
            hl2_ = hl + 1
            trim = pred & (hl2_ > S.ml_hist)
            row = st["mhb"][rr, pc]
            sh = row[jnp.minimum(ar9 + 1, 8)]
            st["mhb"] = st["mhb"].at[rr, pc].set(
                jnp.where(trim, sh, row))
            st["mhl"] = st["mhl"].at[rr, pc].set(
                jnp.where(pred, jnp.where(trim, hl2_ - 1, hl2_), hl))
            return emit, (blkm + best) * 64

        # ================================================================
        # the access itself
        # ================================================================
        act0 = x["valid"]
        rr = x["r"]
        now = st["time"][rr]
        w = x["w"]
        a = x["a"]
        ten = x["ten"]
        reu = x["reu"]
        blk = a >> 6
        t1 = blk >> s1b
        s1 = blk & (S1 - 1)
        si1 = rr * S1 + s1
        lat = cfg["hl1"]

        # ---- L1 ----
        hit1, w1, _ = c_probe("l1", si1, t1)
        h1p = act0 & hit1
        sl1 = si1 * A1 + w1
        st["l1h"] = st["l1h"].at[rr].add(jnp.where(h1p, 1, 0))
        if S.ta1:
            ta_hit("l1", rr, h1p, st["l1n"][sl1])
        pu1 = h1p & st["l1p"][sl1]
        st["l1pu"] = st["l1pu"].at[rr].add(jnp.where(pu1, 1, 0))
        st["l1p"] = st["l1p"].at[sl1].set(
            jnp.where(pu1, False, st["l1p"][sl1]))
        st["l1l"] = st["l1l"].at[sl1].set(
            jnp.where(h1p, now, st["l1l"][sl1]))
        st["l1d"] = st["l1d"].at[sl1].set(
            jnp.where(h1p & w, True, st["l1d"][sl1]))
        pw1 = h1p & (st["l1r"][sl1] > now)
        lat = jnp.where(pw1, fb.add(lat, promote_wait(
            "l1", pw1, sl1, x["pg_slot"], now)), lat)
        miss1 = act0 & ~hit1
        st["l1m"] = st["l1m"].at[rr].add(jnp.where(miss1, 1, 0))

        # ---- prefetcher observation (on L1 miss) ----
        if S.pf_on:
            issue, nst = stride_observe(miss1, rr, x["pc"], a)
            if S.ml_on:
                emit_ml, tgt_ml = ml_observe(miss1, rr, x["pc"],
                                             x["f1"], a)
        lat = jnp.where(miss1, fb.add(lat, cfg["hl2"]), lat)

        # ---- L2 ----
        s2 = blk & (S2 - 1)
        t2 = blk >> s2b
        si2 = rr * S2 + s2
        hit2, w2, _ = c_probe("l2", si2, t2)
        h2p = miss1 & hit2
        sl2 = si2 * A2 + w2
        st["l2h"] = st["l2h"].at[rr].add(jnp.where(h2p, 1, 0))
        if S.ta2:
            ta_hit("l2", rr, h2p, st["l2n"][sl2])
        pu2 = h2p & st["l2p"][sl2]
        st["l2pu"] = st["l2pu"].at[rr].add(jnp.where(pu2, 1, 0))
        st["l2p"] = st["l2p"].at[sl2].set(
            jnp.where(pu2, False, st["l2p"][sl2]))
        st["l2l"] = st["l2l"].at[sl2].set(
            jnp.where(h2p, now, st["l2l"][sl2]))
        st["l2d"] = st["l2d"].at[sl2].set(
            jnp.where(h2p & w, True, st["l2d"][sl2]))
        pw2 = h2p & (st["l2r"][sl2] > now)
        lat = jnp.where(pw2, fb.add(lat, promote_wait(
            "l2", pw2, sl2, x["pg_slot"], now)), lat)
        # L2 hit copies into L1 (victim writeback dropped, C semantics)
        c_insert("l1", h2p, si1, s1, t1, blk, ten, reu, now, w,
                 jnp.bool_(False), i64(0))
        miss2 = miss1 & ~hit2
        st["l2m"] = st["l2m"].at[rr].add(jnp.where(miss2, 1, 0))

        # ---- prefetch issue (on L2 miss) ----
        if S.pf_on:
            rolled(S.st_deg, lambda k: do_prefetch(
                miss2 & issue, rr, a + nst * (k + 1), ten, reu, now, True))
            if S.ml_on:
                do_prefetch(miss2 & emit_ml, rr, tgt_ml, ten, reu, now,
                            False)

        # ---- coherence (leaving the private domain) ----
        served = jnp.bool_(False)
        if S.mesi:
            dslot = x["blk_slot"]
            bit = i64(1) << rr
            m0 = st["dirm"][dslot]
            o0 = st["diro"][dslot]
            bw_ = miss2 & w
            br_ = miss2 & ~w
            others = m0 & ~bit
            ninv = popcount(others)
            st["dinv"] = st["dinv"] + jnp.where(bw_, ninv, 0)
            st["dupg"] = st["dupg"] + jnp.where(
                bw_ & ((m0 & bit) != 0) & (o0 != rr), 1, 0)
            prov = br_ & (o0 >= 0) & (o0 != rr)
            st["dc2c"] = st["dc2c"] + jnp.where(prov, 1, 0)
            m_r = m0 | bit
            o_r = jnp.where(prov, i64(-1), o0)
            o_r = jnp.where((m_r == bit) & ~prov, rr, o_r)
            st["dirm"] = st["dirm"].at[dslot].set(
                jnp.where(bw_, bit, jnp.where(br_, m_r, m0)))
            st["diro"] = st["diro"].at[dslot].set(
                jnp.where(bw_, rr, jnp.where(br_, o_r, o0)))
            # invalidate other sharers' private lines (the paired
            # dir_evict calls are no-ops: mask was just set to only-us)
            inv_act = bw_ & (ninv > 0)
            r2 = jnp.arange(R)
            iact = inv_act & (r2 != rr)
            idx1v = (r2 * S1 + s1)[:, None] * A1 + jnp.arange(A1)[None, :]
            m1v = (st["l1v"][idx1v] & (st["l1t"][idx1v] == t1)
                   & iact[:, None])
            st["l1v"] = st["l1v"].at[idx1v].set(
                jnp.where(m1v, False, st["l1v"][idx1v]))
            idx2v = (r2 * S2 + s2)[:, None] * A2 + jnp.arange(A2)[None, :]
            m2v = (st["l2v"][idx2v] & (st["l2t"][idx2v] == t2)
                   & iact[:, None])
            st["l2v"] = st["l2v"].at[idx2v].set(
                jnp.where(m2v, False, st["l2v"][idx2v]))
            lat = jnp.where(inv_act, fb.add(lat, B(S.inv_lat)), lat)
            served = prov

        cont3 = miss2 & ~served
        l3hit = jnp.bool_(False)
        if S.has_l3:
            if S.mesi:
                lat = jnp.where(served, fb.add(lat, B(S.c2c_lat)), lat)
            lat = jnp.where(cont3, fb.add(lat, cfg["hl3"]), lat)
            s3 = blk & (S3 - 1)
            hit3, w3, _ = c_probe("l3", s3, blk >> s3b)
            h3p = cont3 & hit3
            sl3 = s3 * A3 + w3
            st["l3h"] = st["l3h"] + jnp.where(h3p, 1, 0)
            if S.ta3:
                ta_hit("l3", 0, h3p, st["l3n"][sl3])
            pu3 = h3p & st["l3p"][sl3]
            st["l3pu"] = st["l3pu"] + jnp.where(pu3, 1, 0)
            st["l3p"] = st["l3p"].at[sl3].set(
                jnp.where(pu3, False, st["l3p"][sl3]))
            st["l3l"] = st["l3l"].at[sl3].set(
                jnp.where(h3p, now, st["l3l"][sl3]))
            st["l3d"] = st["l3d"].at[sl3].set(
                jnp.where(h3p & w, True, st["l3d"][sl3]))
            st["l3m"] = st["l3m"] + jnp.where(cont3 & ~hit3, 1, 0)
            l3hit = h3p

        bm = cont3 & ~l3hit

        # ---- demand memory access (merged: miss path + c2c w/o L3) ----
        dem = bm if S.has_l3 else (bm | served)
        _, svc = mem_access(dem, fb.add(now, lat), a, False, x["pg_slot"])
        lat = jnp.where(dem, fb.add(lat, svc), lat)
        fs_pred = (bm | served) if S.has_l3 else bm
        fill_shared(fs_pred, blk, ten, reu, now, bm & w)
        fill_private(bm | served | l3hit, rr, blk, ten, reu, now, w)

        # ---- retire ----
        hitdone = h1p | h2p | served | l3hit
        active = hitdone | bm
        st["lat"] = jnp.where(active, fb.add(st["lat"], lat), st["lat"])
        st["nacc"] = st["nacc"] + jnp.where(active, 1, 0)
        d_ = jnp.where(rr >= NC, fb.div_const(lat, S.accel_mlp),
                       fb.div_const(lat, S.core_mlp))
        slow = fb.add(now, jnp.maximum(d_, TWO))
        fast = hitdone & (lat <= fb.add(cfg["hl1"], TWELVE))
        newt = jnp.where(fast, fb.add(now, ONE), slow)
        st["time"] = st["time"].at[rr].set(
            jnp.where(active, newt, st["time"][rr]))
        return st

    return step


# ---------------------------------------------------------------------------
# scan drivers + counter export (oi[98]/od[10], the C kernel's layout)
# ---------------------------------------------------------------------------
def _export_arrays(S: StaticConfig, st: Dict):
    R = S.n_req
    z = jnp.int64(0)
    oi = jnp.zeros(98, jnp.int64)
    oi = oi.at[0].set(st["nacc"]).at[1].set(st["wbl"])
    oi = oi.at[2].set(st["pfd"])
    if S.mesi:
        oi = oi.at[3].set(st["dinv"]).at[4].set(st["dc2c"])
        oi = oi.at[5].set(st["dupg"])
    oi = oi.at[6].set(st["mig"]).at[7].set(st["migb"])
    oi = oi.at[8].set(st["dby"]).at[9].set(st["drh"])
    oi = oi.at[10].set(st["dac"])
    if S.hybrid:
        oi = oi.at[11].set(st["hby"]).at[12].set(st["hrh"])
        oi = oi.at[13].set(st["hac"])
    oi = oi.at[14].set(st["l1_ev"]).at[15].set(st["l1_dev"])
    oi = oi.at[16].set(st["l1_pf"])
    oi = oi.at[17].set(st["l2_ev"]).at[18].set(st["l2_dev"])
    oi = oi.at[19].set(st["l2_pf"])
    if S.has_l3:
        oi = oi.at[20].set(st["l3_ev"]).at[21].set(st["l3_dev"])
        oi = oi.at[22].set(st["l3_pf"])
        oi = oi.at[23].set(st["l3h"]).at[24].set(st["l3m"])
        oi = oi.at[25].set(st["l3pu"])
    oi = oi.at[26:26 + R].set(st["l1h"])
    oi = oi.at[34:34 + R].set(st["l1m"])
    oi = oi.at[42:42 + R].set(st["l1pu"])
    oi = oi.at[50:50 + R].set(st["l2h"])
    oi = oi.at[58:58 + R].set(st["l2m"])
    oi = oi.at[66:66 + R].set(st["l2pu"])
    if S.pf_on:
        oi = oi.at[74:74 + R].set(st["sti"])
        if S.ml_on:
            oi = oi.at[82:82 + R].set(st["mli"])
            oi = oi.at[90:90 + R].set(st["mlt"])
    od = jnp.zeros(10, jnp.int64)          # bit patterns of the doubles
    od = od.at[0:R].set(st["time"])
    od = od.at[8].set(st["lat"]).at[9].set(st["migs"])
    return oi, od, st["flags"]


#: scan steps per device call.  A longer trace runs as a sequence of
#: calls that carry the state on the device, so the compiled program is
#: independent of the trace length.
CHUNK = 1 << 16


def _make_run(static: StaticConfig, batched: bool):
    """``(scan, export)``: ``scan`` advances a state over one chunk of
    trace steps, ``export`` reduces a final state to the C kernel's
    counter vectors.  Batched, every lane carries its own state.

    The chunk holds the steps of T traces (``xs`` leaves ``[C, T]``, the
    frozen block tables ``[T, blk]``); lane ``tid`` runs trace ``tid``,
    so one program scans every trace of a campaign side by side."""
    step = _make_step(static)

    def scan(consts, cfg, st, xs, tid):
        lane_consts = {"blk": consts["blk"], "tid": tid}

        def body(s, x):
            return step(lane_consts, cfg, s,
                        {k: v[tid] for k, v in x.items()}), None
        return lax.scan(body, st, xs)[0]

    def export(st):
        return _export_arrays(static, st)

    if batched:
        # cfg rows, states and trace indices vary per lane; the block
        # tables and the trace chunk are shared by all lanes
        scan = jax.vmap(scan, in_axes=(None, 0, 0, None, 0))
        export = jax.vmap(export)
    return jax.jit(scan, donate_argnums=2), jax.jit(export)


#: compiled ``(scan, export)`` by (StaticConfig, batched, argument shapes)
_COMPILED: Dict[tuple, tuple] = {}

#: one record per executed shape bucket, oldest first: the bucket's first
#: config name, its lanes, its distinct traces, the scan steps, the
#: simulated accesses (summed over lanes), and the seconds spent
#: compiling and scanning (device work ended by ``block_until_ready``)
SCAN_LOG: List[Dict] = []


def _x64():
    return jax.enable_x64(True)


def _nten(trace: Dict) -> int:
    tensor = np.asarray(trace["tensor"])
    return int(tensor.max()) + 1 if len(tensor) else 1


def _lower_cfg(arrays: Dict) -> Dict:
    """The device form of the lane knobs (scalars or lane-stacked).  No
    double goes to the device as a double (the chip rounds them, see
    f64bits): latencies and costs become bit patterns, rank knobs order
    keys, utility thresholds the constants of ``f64bits.ratio_lt``, and
    the perceptron threshold ceil(8 t) for scores in units of 1/8."""
    out = {k: np.asarray(arrays[k], np.int64) for k in _CFG_I}
    f = {k: np.asarray(arrays[k], np.float64) for k in _CFG_F}
    for k in ("hl1", "hl2", "hl3", "migcost"):
        out[k] = f[k].view(np.int64)
    for k in ("ta_pref", "ta_stream"):
        b = f[k].view(np.int64)
        out[k] = np.where(b < 0, b ^ np.int64((1 << 63) - 1), b)
    for k in ("ta_low", "ta_high", "ta_bypass"):
        try:
            rows = [fb.ratio_threshold(t) for t in f[k].ravel().tolist()]
        except ValueError as e:
            raise JaxEngineUnsupported(f"{k}: {e}") from None
        out[k] = {c: np.asarray([r[c] for r in rows],
                                np.int64).reshape(f[k].shape)
                  for c in rows[0]}
        out[k]["one"] = (1.0 < f[k]).astype(np.int64)
        out[k]["four"] = (4.0 < f[k]).astype(np.int64)
    out["ml_thresh"] = np.clip(np.ceil(8.0 * f["ml_thresh"]),
                               -1 << 20, 1 << 20).astype(np.int64)
    assert set(out) == set(_CFG_I) | set(_CFG_F)
    return jax.tree.map(jnp.asarray, out)


def _cfg_scalars(cfg: Dict) -> Dict:
    return _lower_cfg({k: cfg[k] for k in list(_CFG_I) + list(_CFG_F)})


def _cfg_stack(cfgs: List[Dict]) -> Dict:
    """Stack lane dicts into the ConfigArrays pytree, padded to a
    power-of-two lane count (see ``params.stack_lanes``) so nearby
    batch sizes hit one compiled program."""
    arrays, _ = params_mod.stack_lanes(cfgs)
    return _lower_cfg(arrays)


def _check_flags(flags: int) -> None:
    f = int(flags)
    if f:
        hit = [name for bit, name in _FLAG_NAMES.items() if f & bit]
        raise JaxEngineOverflow(
            "fixed-capacity table overflow in jax engine: "
            + ", ".join(hit))


def _signature(tree) -> tuple:
    return tuple((jax.tree_util.keystr(path), a.shape, str(a.dtype))
                 for path, a in jax.tree_util.tree_leaves_with_path(tree))


def _execute(static: StaticConfig, preps: List[PreparedTrace],
             cfg: Dict, tids: Optional[np.ndarray], label: str,
             lanes: int = 1):
    """Compile once per argument shape, then scan the prepared traces
    chunk by chunk; returns host ``(oi, od, flags)``.  ``tids`` gives
    each stacked lane's index into ``preps``, the first ``lanes`` of
    them real (the rest pad the lane axis); None runs the unbatched
    program over ``preps[0]``."""
    n = max(p.n for p in preps)
    C = min(CHUNK, n) if n else 1
    m = -(-n // C) * C
    # [T, ...] per trace, then the trace-major columns [m, T]
    consts = {"blk": jnp.asarray(np.stack([p.blk_tab for p in preps]))}
    cols = [p.padded(m) for p in preps]
    xs = {k: np.stack([c[k] for c in cols], axis=1) for k in cols[0]}
    inits = [init_state(static, p) for p in preps]
    if tids is None:
        st = {k: jnp.asarray(v) for k, v in inits[0].items()}
        tid = jnp.int32(0)
    else:
        tid = jnp.asarray(tids, jnp.int32)
        st = {k: jnp.stack([jnp.asarray(i[k]) for i in inits])[tid]
              for k in inits[0]}
    chunks = [{k: v[i:i + C] for k, v in xs.items()}
              for i in range(0, m, C)]
    key = (static, tids is not None,
           _signature((consts, cfg, st, chunks[0], tid)))
    t0 = time.perf_counter()
    exe = _COMPILED.get(key)
    if exe is None:
        scan, export = _make_run(static, tids is not None)
        exe = (scan.lower(consts, cfg, st, chunks[0], tid).compile(),
               export.lower(st).compile())
        _COMPILED[key] = exe
    t1 = time.perf_counter()
    scan, export = exe
    for x in chunks:
        st = scan(consts, cfg, st, {k: jnp.asarray(v) for k, v in x.items()},
                  tid)
    out = jax.block_until_ready(export(st))
    t2 = time.perf_counter()
    real = [0] if tids is None else tids[:lanes]
    SCAN_LOG.append({"bucket": label, "lanes": len(real),
                     "traces": len(preps), "steps": m,
                     "accesses": sum(preps[t].n for t in real),
                     "compile_s": t1 - t0, "scan_s": t2 - t1})
    return tuple(np.asarray(a) for a in out)


def run_single(sp, trace: Dict,
               caps: Optional[Caps] = None) -> Tuple[np.ndarray, np.ndarray]:
    """Run one config through the jax engine; returns (oi, od) in the C
    kernel's export layout (feed to native.deposit_counters)."""
    caps = Caps.cover([caps or Caps.of(trace), Caps.of(trace)])
    with _x64():
        static, cfg = split_config(sp, caps.nten)
        prep = prepare_trace(static, trace, caps)
        oi, od, fl = _execute(static, [prep], _cfg_scalars(cfg), None,
                              sp.name)
    _check_flags(fl)
    return oi, od.view(np.float64)


def run_batch(sps: List, traces, caps: Optional[Caps] = None,
              ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Run N configs; ``traces`` is one trace for all of them or a list
    with one trace per config.  Lanes sharing a StaticConfig execute as
    one vmapped device program (a "shape bucket") that scans all their
    traces side by side, each lane its own.  Results come back in input
    order; per-lane overflow raises.

    Every trace is laid out at the common extents of all of them and of
    ``caps`` (see :class:`Caps`): pass a campaign's envelope as ``caps``
    and each bucket compiles once for the whole campaign."""
    if isinstance(traces, dict):
        traces = [traces] * len(sps)
    distinct = list({id(t): t for t in traces}.values())
    caps = Caps.cover([Caps.of(t) for t in distinct]
                      + ([caps] if caps else []))
    results: List = [None] * len(sps)
    with _x64():
        groups: Dict[StaticConfig, List[tuple]] = {}
        for i, (sp, tr) in enumerate(zip(sps, traces)):
            static, cfg = split_config(sp, caps.nten)
            groups.setdefault(static, []).append((i, sp.name, cfg, tr))
        for static, lanes in groups.items():
            trs = list({id(t): t for *_, t in lanes}.values())
            tix = {id(t): j for j, t in enumerate(trs)}
            preps = [prepare_trace(static, t, caps) for t in trs]
            cfgj = _cfg_stack([c for _, _, c, _ in lanes])
            # lanes padded to a power of two re-run lane 0
            # (params.stack_lanes)
            tids = [tix[id(t)] for *_, t in lanes]
            tids += tids[:1] * (len(cfgj[_CFG_I[0]]) - len(tids))
            oi, od, fl = _execute(static, preps, cfgj,
                                  np.asarray(tids), lanes[0][1],
                                  len(lanes))
            for j, (i, *_) in enumerate(lanes):
                _check_flags(fl[j])
                results[i] = (oi[j], od[j].view(np.float64))
    return results


# ---------------------------------------------------------------------------
# HierarchySim-compatible front
# ---------------------------------------------------------------------------
from repro.core.engine_soa import SoAHierarchySim  # noqa: E402


class JaxHierarchySim(SoAHierarchySim):
    """SoA-compatible sim whose run() executes on the jax engine."""

    def run(self, trace: Dict):
        from repro.core.engine_soa import _SimView
        from repro.core.simulator import compute_metrics
        oi, od = run_single(self.sp, trace)
        _native.deposit_counters(self, oi, od)
        return compute_metrics(_SimView(self, *self._native_counts),
                               trace)


def metrics_from_outputs(sp, trace: Dict, oi: np.ndarray, od: np.ndarray):
    """Metrics for one lane of a ``run_batch`` result — the same
    deposit-and-derive path ``JaxHierarchySim.run`` uses."""
    from repro.core.engine_soa import _SimView
    from repro.core.simulator import compute_metrics
    sim = SoAHierarchySim(sp)
    _native.deposit_counters(sim, oi, od)
    return compute_metrics(_SimView(sim, *sim._native_counts), trace)
