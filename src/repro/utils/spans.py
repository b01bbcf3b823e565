"""Host spans of the program, on the profiler's trace and in memory.

``with span("fed.segment", bucket=64) as attrs: ...`` opens a
``jax.profiler.TraceAnnotation`` (so a profiler trace holds the span, on the
device trace's clock, and its idle gaps can be labelled by it) and, when the
block ends, appends one :class:`Record` to a bounded in-memory ring, timed
with ``time.perf_counter()``.  Counters are attributes of the span at the
boundary where the work happens: the block may add to the ``attrs`` dict it
is handed (``attrs["h2d_bytes"] = n``), and those land in the record (the
trace event carries the attributes given when the span opened).

The parent of a span is the innermost span still open on the same thread; a
span opened with none open is a root and starts a new ``run_id``, which
every span under it shares.  Always on: there is nothing to enable, and with
no profiler session a span costs a ``TraceAnnotation`` and two clock reads.
Read the ring with :func:`records`.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import NamedTuple

import jax

RING_SIZE = 1 << 16


class Record(NamedTuple):
    name: str
    t0: float             # time.perf_counter() when the span opened
    t1: float             # ... and when it closed
    span_id: int
    parent_id: int | None
    run_id: int           # span_id of the root span this one runs under
    attrs: dict


_ring: collections.deque = collections.deque(maxlen=RING_SIZE)
_ids = itertools.count(1)
_open = threading.local()


@contextlib.contextmanager
def span(name: str, **attrs):
    """Record the block as span ``name``; yields its ``attrs`` dict."""
    stack = _open.__dict__.setdefault("stack", [])
    span_id = next(_ids)
    parent_id, run_id = stack[-1] if stack else (None, span_id)
    stack.append((span_id, run_id))
    try:
        with jax.profiler.TraceAnnotation(name, **attrs):
            t0 = time.perf_counter()
            try:
                yield attrs
            finally:
                _ring.append(Record(name, t0, time.perf_counter(), span_id,
                                    parent_id, run_id, attrs))
    finally:
        stack.pop()


def records() -> list[Record]:
    """A copy of the ring, oldest record first (at most ``RING_SIZE``)."""
    return list(_ring)
