"""Host spans at the flow's layer boundaries, kept in a bounded ring.

``span(name, **attrs)`` times one stretch of host work (test data, the DFG
oracle, plane building, one simulator launch, the comparison, mapping) and
does two things with it:

  * it opens a ``jax.profiler.TraceAnnotation`` of the same name, so the
    span lies on the profiler's clock beside the device events and an idle
    gap in a trace can be put down to what the host was doing;
  * when it closes, it appends one record to an in-memory ring of at most
    ``RING_MAX`` records (the oldest fall out):

        {"name", "t0_ns", "t1_ns", "id", "parent", "root", "attrs"}

    with times on ``time.perf_counter_ns()``, ``parent`` the id of the
    enclosing span of this thread (None at the top) and ``root`` the id of
    the outermost one, so every span of one ``verify_batch`` call shares a
    root.  The context manager yields the ``attrs`` dict: a span may add to
    it before it closes (the simulator's launch counters are set that way).

``spans()`` returns a copy of the ring.  Spans sit only at layer
boundaries, never inside a traced body, a scan or a per-seed loop, so they
cost a few microseconds each and are always on.  Names start with
``morpher.``.

No JAX import here: compile workers open no spans and must never load JAX,
and a profiler session can only be running where JAX already is.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import sys
import threading
import time
from typing import Dict, Iterator, List

RING_MAX = 65536

_ring: "collections.deque[dict]" = collections.deque(maxlen=RING_MAX)
_ids = itertools.count(1)
_local = threading.local()


def _annotation(name: str, attrs: Dict):
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name, **attrs)


@contextlib.contextmanager
def span(name: str, **attrs) -> Iterator[Dict]:
    """Time the enclosed host work as one span; yields its ``attrs``."""
    stack: List[dict] = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    sid = next(_ids)
    parent = stack[-1] if stack else None
    rec = {"name": name, "t0_ns": 0, "t1_ns": 0, "id": sid,
           "parent": parent["id"] if parent else None,
           "root": parent["root"] if parent else sid, "attrs": attrs}
    stack.append(rec)
    try:
        with _annotation(name, attrs):
            rec["t0_ns"] = time.perf_counter_ns()
            try:
                yield attrs
            finally:
                rec["t1_ns"] = time.perf_counter_ns()
    finally:
        stack.pop()
        _ring.append(rec)


def spans() -> List[dict]:
    """The records in the ring, oldest first (a copy of the ring)."""
    return list(_ring)
