"""The least memory traffic the modelled work of a campaign needs.

Counted from the configuration's geometry and the counters the run
exported, never from the compiled program, so that a rewrite of the scan
cannot change what it is measured against.  Per scan step and trace the
device reads one trace record; per probe of a cache set it reads the
set's tags and writes one.  Everything else the step does (replacement
state, directory, prefetcher and tensor-policy tables, channel timing)
is left out, so the count is a lower bound and a share of the roofline
built on it cannot pass 100 %.
"""

from __future__ import annotations

from typing import Mapping, Sequence

import numpy as np

#: bytes of one trace record as the device reads it: eight 64-bit
#: columns (requester, address, tensor, reuse class, PC id, perceptron
#: feature, block slot, page slot) and two one-byte flags (write, valid)
RECORD_BYTES = 8 * 8 + 2
#: bytes of one tag
TAG_BYTES = 8


def probes(oi: np.ndarray, n_req: int) -> dict:
    """Demand probes per level from one lane's ``oi`` export: every
    access probes its L1, every L1 miss its L2, every L2 miss that
    reaches the shared level the L3 (hits plus misses at each)."""
    return {"l1": int(oi[26:26 + n_req].sum() + oi[34:34 + n_req].sum()),
            "l2": int(oi[50:50 + n_req].sum() + oi[58:58 + n_req].sum()),
            "l3": int(oi[23] + oi[24])}


def campaign_bytes(system: Mapping, steps: int, traces: int,
                   lane_ois: Sequence[np.ndarray]) -> int:
    """Bytes a campaign of ``steps`` scan steps over ``traces`` traces
    must move, for the real lanes whose exports are ``lane_ois``."""
    n_req = system["n_cores"] + (1 if system.get("accel_port", True) else 0)
    total = steps * traces * RECORD_BYTES
    for oi in lane_ois:
        for level, n in probes(oi, n_req).items():
            if system.get(level) is not None:
                total += n * (system[level]["assoc"] + 1) * TAG_BYTES
    return total
