"""Host spans of the grid path (DESIGN.md §18).

``span(name, **args)`` opens a ``jax.profiler.TraceAnnotation``, so under
a running profiler the span lands on the same timeline as the device
ops; without one it costs the annotation's enter and exit and nothing
else. The grid path names its host phases with it:

* ``fabric.build_case`` — topology, allocation, flows and geometry;
* ``fabric.grid_params`` — the per-lane SimParams stack;
* ``fabric.dispatch`` — the engine call (asynchronous: the enqueue, and
  the compile on a cache miss);
* ``fabric.shard`` — one per device of a per-device dispatch
  (``device=<id>``): that shard's transfers and launch;
* ``fabric.marshal`` — reading the outputs back into results (it waits
  for the device).

A caller may install a recorder (``with recording() as rec``) to keep
``(name, start_ns, end_ns)`` of every span on the
``time.perf_counter_ns`` clock, the latest ``_LIMIT`` of them. Nothing
is recorded unless a recorder is installed.
"""
from __future__ import annotations

import collections
import contextlib
import time
from typing import Deque, Optional, Tuple

import jax

BUILD_CASE = "fabric.build_case"
GRID_PARAMS = "fabric.grid_params"
DISPATCH = "fabric.dispatch"
SHARD = "fabric.shard"
MARSHAL = "fabric.marshal"

# spans a recorder keeps: a window's questions hold four to eight each
_LIMIT = 4096

Recorded = Deque[Tuple[str, int, int]]
_recorder: Optional[Recorded] = None


@contextlib.contextmanager
def recording():
    """Keep the latest ``_LIMIT`` spans closed inside the block as
    ``(name, start_ns, end_ns)``; the recorder installed before (if any)
    comes back after it."""
    global _recorder
    before, _recorder = _recorder, collections.deque(maxlen=_LIMIT)
    try:
        yield _recorder
    finally:
        _recorder = before


@contextlib.contextmanager
def span(name: str, **args):
    rec = _recorder
    start = time.perf_counter_ns() if rec is not None else 0
    try:
        with jax.profiler.TraceAnnotation(name, **args):
            yield
    finally:
        if rec is not None:
            rec.append((name, start, time.perf_counter_ns()))
