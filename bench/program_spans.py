"""The program's own spans (``repro.telemetry``), read for the benchmark.

The program records a span tree (``Span(id, parent, name, t0, t1,
counts)``, ``time.perf_counter`` seconds) while a profiler trace records,
which in a ``--trace 1`` run is the window's first revocation alone: the
readings are that revocation's, one sample a run, not means over the
window.  A checkout whose program has no recorder gives no records, and
every reading here is then None.

- ``mean_per``: seconds of some spans per span of another name (one a
  revocation) over the revocations the recorder saw;
- ``counts_per``: the counters of some names under such a span, per span;
  ``compile_seconds_per`` and ``cache_lookups_per`` count JAX's compile
  events as the program names them (``telemetry.COMPILE_SECONDS``,
  ``COMPILE_EVENTS``).

The ``*.resume`` readers of the checkpoint and trial-runtime layers use them.
"""

from __future__ import annotations


def _telemetry():
    try:
        from repro import telemetry
    except ImportError:
        return None
    return telemetry


def program_records(run):
    """The program's span records: ``run["program"]`` where the run passes
    them, else what the recorder of this process holds; None where the
    program has no recorder."""
    if "program" in run:
        return run["program"]
    telemetry = _telemetry()
    return telemetry.records() if telemetry else None


def _in_window(run) -> list:
    recs = program_records(run) or []
    return [r for r in recs if r.t0 >= run["window"][0]]


def mean_per(run, names, per: str):
    """Seconds of the window's spans named in ``names``, per span named
    ``per`` there; None where the window has no ``per`` span."""
    recs = _in_window(run)
    n = sum(r.name == per for r in recs)
    if not n:
        return None
    return sum(r.t1 - r.t0 for r in recs if r.name in names) / n


def _under(recs, root: str) -> list:
    """Spans that are, or descend from, a span named ``root``."""
    by_id = {r.id: r for r in recs}
    inside = {}

    def has_root(r):
        if r.id not in inside:
            parent = by_id.get(r.parent)
            inside[r.id] = r.name == root or (parent is not None and has_root(parent))
        return inside[r.id]

    return [r for r in recs if has_root(r)]


def counts_per(run, keys, root: str):
    """The counters ``keys`` of the window's spans named ``root`` and of
    their descendants, per such span; None where there is none."""
    recs = _in_window(run)
    n = sum(r.name == root for r in recs)
    if not n:
        return None
    return sum(r.counts.get(k, 0) for r in _under(recs, root) for k in keys) / n


def compile_seconds_per(run, root: str):
    """Trace, lowering, and compile or cache-load seconds under each ``root``."""
    telemetry = _telemetry()
    return counts_per(run, telemetry.COMPILE_SECONDS, root) if telemetry else None


def cache_lookups_per(run, root: str):
    """Compilation-cache hits plus misses under each ``root``."""
    telemetry = _telemetry()
    return counts_per(run, telemetry.COMPILE_EVENTS, root) if telemetry else None
