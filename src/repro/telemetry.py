"""The program's own spans and counters, kept in memory.

``span(name)`` times a block on ``time.perf_counter`` and records
``Span(id, parent, name, t0, t1, counts)`` when it ends.  A span's parent
is the innermost span open on the same thread, or the id given as
``parent`` (work handed to another thread names the span that handed it
over).  Each span also opens a ``jax.profiler.TraceAnnotation`` of its
name, so a profiler trace holds it on the device's clock.

``count(name, n)`` adds ``n`` to a counter of the innermost span open on
the calling thread.  While the recorder is on, JAX's compile and
compilation-cache events are counted the same way: the seconds of
``COMPILE_SECONDS`` (tracing, lowering, and compiling or loading from the
cache, which JAX times inside ``backend_compile_duration``) and the counts
of ``COMPILE_EVENTS``, so a span says which of its calls compiled, and for
how long.

The recorder is on while a profiler trace is being recorded, so the records
hold what the trace's annotations show.  Otherwise ``span`` returns one
shared no-op context: no annotation, no record.  ``reset()`` drops the
records, ``records()`` returns them.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from typing import NamedTuple, Optional

import jax

COMPILE_SECONDS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
COMPILE_EVENTS = (
    "/jax/compilation_cache/cache_hits",
    "/jax/compilation_cache/cache_misses",
)


class Span(NamedTuple):
    id: int
    parent: Optional[int]
    name: str
    t0: float
    t1: float
    counts: dict


_NOOP = contextlib.nullcontext()
_listening = False
_lock = threading.Lock()
_records: list = []          # list.append is atomic: spans end on any thread
_ids = itertools.count(1)
_local = threading.local()


def _stack() -> list:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


class _Open:
    """A span while it is open; entering it gives its id."""

    __slots__ = ("id", "parent", "name", "t0", "counts", "_ann")

    def __init__(self, name: str, parent: Optional[int]):
        self.id, self.parent, self.name, self.counts = next(_ids), parent, name, {}

    def __enter__(self) -> int:
        stack = _stack()
        if self.parent is None and stack:
            self.parent = stack[-1].id
        stack.append(self)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self.id

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        _stack().pop()
        _records.append(Span(self.id, self.parent, self.name, self.t0, t1, self.counts))
        return False


def _listen():
    """Register the compile listeners, once per process."""
    global _listening
    with _lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            jax.monitoring.register_event_listener(_on_event)
            _listening = True


def _on_duration(event: str, secs: float, **_):
    if event in COMPILE_SECONDS:
        count(event, secs)


def _on_event(event: str, **_):
    if event in COMPILE_EVENTS:
        count(event)


def span(name: str, parent: Optional[int] = None):
    """A context manager that records the block as a span named ``name``;
    ``with span(...) as sid`` gives its id (None while the recorder is off)."""
    if not jax.profiler.TraceAnnotation.is_enabled():
        return _NOOP
    if not _listening:
        _listen()
    return _Open(name, parent)


def count(name: str, n=1):
    """Add ``n`` to counter ``name`` of the innermost open span of this
    thread; with none open, nothing is kept."""
    stack = getattr(_local, "stack", None)
    if stack:
        counts = stack[-1].counts
        counts[name] = counts.get(name, 0) + n


def reset():
    _records.clear()


def records() -> list:
    return list(_records)
