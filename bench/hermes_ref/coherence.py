"""MESI coherence directory for the shared-L3 configuration.

The paper's shared L3 uses "fine-grained coherence protocols" (MESI per
§IV Simulation Configuration) between the 4 CPU cores and the Gemmini
accelerator port.  We model a directory colocated with the shared level:

* per-line sharer bitmask + owner
* read miss while another requestor holds M  → cache-to-cache transfer
  (writeback to L3, both end S)                — ``c2c_transfers``
* write (upgrade or write-miss) → invalidate all other sharers
                                               — ``invalidations``
* without a shared L3 (baseline), coherence degrades to resolving through
  main memory: same events, but the penalty charged by the simulator is a
  DRAM round-trip instead of an L3 hop (this is why the shared L3 row
  improves latency in Table I).

The directory tracks *private-cache* (L1+L2) presence; L3 itself is shared
so it needs no sharer tracking.
"""

from __future__ import annotations

from typing import Dict, List, Optional


class MESIDirectory:
    def __init__(self, n_requestors: int):
        self.n = n_requestors
        # line_block -> (sharer_mask, owner or -1 when unowned/shared)
        self.state: Dict[int, List[int]] = {}
        self.invalidations = 0
        self.c2c_transfers = 0
        self.upgrades = 0

    def _entry(self, block: int) -> List[int]:
        e = self.state.get(block)
        if e is None:
            e = [0, -1]
            self.state[block] = e
        return e

    def on_read(self, block: int, requestor: int) -> Optional[int]:
        """Read miss in requestor's private caches.

        Returns the previous owner's id if a cache-to-cache transfer is
        required (owner held the line M/E), else None.
        """
        e = self._entry(block)
        mask, owner = e
        provider = None
        if owner >= 0 and owner != requestor:
            # owner had M/E: intervention — owner downgrades to S
            provider = owner
            self.c2c_transfers += 1
            e[1] = -1
        e[0] = mask | (1 << requestor)
        if e[0] == (1 << requestor) and provider is None:
            e[1] = requestor  # sole sharer → E
        return provider

    def on_write(self, block: int, requestor: int) -> int:
        """Write by requestor: invalidate other sharers.

        Returns the number of invalidated remote copies (coherence traffic
        the simulator turns into latency + energy).
        """
        e = self._entry(block)
        mask, owner = e
        others = mask & ~(1 << requestor)
        n_inv = bin(others).count("1")
        if n_inv:
            self.invalidations += n_inv
        if mask & (1 << requestor) and owner != requestor:
            self.upgrades += 1
        e[0] = 1 << requestor
        e[1] = requestor
        return n_inv

    def on_evict(self, block: int, requestor: int) -> None:
        e = self.state.get(block)
        if e is None:
            return
        e[0] &= ~(1 << requestor)
        if e[1] == requestor:
            e[1] = -1
        if e[0] == 0:
            del self.state[block]

    def sharers(self, block: int) -> int:
        e = self.state.get(block)
        return bin(e[0]).count("1") if e else 0
