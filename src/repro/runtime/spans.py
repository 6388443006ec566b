"""Host spans of the simulator's phases.

``with span("prepare_trace") as s: ...`` does two things:

* it opens ``jax.profiler.TraceAnnotation("hermes.prepare_trace")``, so
  that a profiler trace shows the span on its host plane, on the same
  clock as the device's work;
* on leaving, it appends a :class:`Span` to :data:`SPANS`, a bounded
  in-memory store that is always on.  Its times are
  ``time.perf_counter()`` seconds, so they compare directly with other
  host timings of the same process.

After the block, ``s.seconds`` is the span's duration.  With no
profiler running a span costs two clock reads and one append.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Deque, NamedTuple, Optional

from jax.profiler import TraceAnnotation

PREFIX = "hermes."


class Span(NamedTuple):
    index: int               # order of opening, over the process
    name: str                # without PREFIX
    t0: float                # perf_counter seconds
    t1: float
    parent: Optional[int]    # index of the enclosing open span


#: closed spans, oldest first; the oldest fall out past ``maxlen``
SPANS: Deque[Span] = collections.deque(maxlen=65536)

_next_index = itertools.count()
_open = threading.local()        # per thread: indices of the open spans


class span:
    """One span named ``PREFIX + name``, nested in the thread's open one."""

    def __init__(self, name: str):
        self.name = name
        self.t0 = self.t1 = 0.0

    def __enter__(self) -> "span":
        self._stack = _open.__dict__.setdefault("stack", [])
        self.index = next(_next_index)
        self.parent = self._stack[-1] if self._stack else None
        self._stack.append(self.index)
        self._annotation = TraceAnnotation(PREFIX + self.name)
        self._annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()
        self._annotation.__exit__(*exc)
        self._stack.pop()
        SPANS.append(Span(self.index, self.name, self.t0, self.t1,
                          self.parent))

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0
