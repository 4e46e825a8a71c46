"""Spans of the served path, and the interpreter's garbage collections, on
the profiler's clock.

`span(name, **meta)` marks one phase as a `jax.profiler.TraceAnnotation`
named `repro.<name>`: while a profiler records, it lands in the same trace
as the device's operations, on their clock, on the thread that ran it;
otherwise it records nothing and costs the object's creation.  Spans mark
phases (a micro-batch, a backend call, a bucket-step chunk), never single
requests or rows.

`trace_gc()` marks every garbage collection as a `repro.gc` span on the
collecting thread and counts collections and their pause seconds in
`GC`.  Entry points turn it on (as they turn on the compile cache); an
import does not.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import jax

PREFIX = "repro."


def span(name: str, **meta):
    """A `repro.<name>` trace span (a context manager); `meta` rides along
    as the event's metadata."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **meta)


@dataclasses.dataclass
class GcStats:
    """Garbage collections seen since `trace_gc()`, and their pauses."""
    collections: int = 0
    pause_s: float = 0.0


GC = GcStats()
_open: list = []          # the collection in progress: [(span, start)]


def _on_gc(phase: str, info: dict) -> None:
    # collections never overlap (one runs at a time, under the GIL), so one
    # slot holds the open span between the "start" and "stop" callbacks
    if phase == "start":
        sp = span("gc", generation=info["generation"])
        sp.__enter__()
        _open.append((sp, time.perf_counter()))
    elif _open:
        sp, t0 = _open.pop()
        sp.__exit__(None, None, None)
        GC.collections += 1
        GC.pause_s += time.perf_counter() - t0


def trace_gc() -> GcStats:
    """Install the collection hook (once, however often called); returns
    the counters."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    return GC
