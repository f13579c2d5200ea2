"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

What a TPU trace holds, as read from one recorded on a v5e:

- one plane per chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` has one
  event per operation the chip ran and whose line ``XLA Modules`` has one
  event per compiled program run (named ``jit_<function>(<fingerprint>)``);
- a host plane ``/host:CPU`` whose lines hold the benchmark's own spans
  (``jax.profiler.TraceAnnotation``), among them ``window``, which marks
  the traced part of the measured window.

Device and host events share one clock (nanoseconds from the trace's start).
Everything is clipped to the ``window`` span:

- ``busy_s``: the union of the op intervals, averaged over the chips;
- ``window_s``: the window's length;
- ``modules``: per program (fingerprint dropped), its run count and
  seconds on the device;
- ``device_ops``: the ten operations (by HLO instruction name) that took the
  most device time, summed over the chips (an op whose event encloses the
  next op's, such as a loop's, is left out: its body's ops are counted);
- ``idle_gaps``: the device's idle time inside the window, summed by the
  benchmark span the host was in (``outside spans`` when none),
  ten at most, longest first.
"""

from __future__ import annotations

import bisect
import pathlib
import re
from collections import defaultdict

WINDOW = "window"
_FINGERPRINT = re.compile(r"\(\d+\)$")


def find_xplane(directory) -> pathlib.Path:
    files = sorted(pathlib.Path(directory).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return files[-1]


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _leaves(ops):
    """Ops that enclose no later op: a loop's own event spans its body's."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or not (nxt[0] < o[1] and nxt[1] <= o[1])]


def op_name(hlo_text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def reduce_trace(path, span_names=()) -> dict:
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    devices, spans, window = [], [], None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                           for e in line.events]
                elif line.name == "XLA Modules":
                    modules = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                               for e in line.events]
            devices.append((ops, modules))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name in span_names:
                        spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    if window is None:
        raise ValueError(f"trace {path} has no '{WINDOW}' span")
    if not devices:
        raise ValueError(f"trace {path} has no TPU device plane")
    lo, hi = window
    busy, op_time = [], defaultdict(float)
    modules = defaultdict(lambda: [0, 0.0])
    idle = defaultdict(float)
    spans.sort()
    starts = [s for s, _, _ in spans]
    for ops, mods in devices:
        merged = _union(_clip([(s, e) for s, e, _ in ops], lo, hi))
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        for s, e, name in _leaves(ops):
            if e > lo and s < hi:
                op_time[op_name(name)] += (min(e, hi) - max(s, lo)) * 1e-9
        for s, e, name in mods:
            if s >= lo and e <= hi:
                m = modules[_FINGERPRINT.sub("", name)]
                m[0] += 1
                m[1] += (e - s) * 1e-9
        gaps, t = [], lo
        for s, e in merged:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < hi:
            gaps.append((t, hi))
        for s, e in gaps:
            idle[_span_at(spans, starts, (s + e) / 2)] += (e - s) * 1e-9 / len(devices)
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": (hi - lo) * 1e-9,
        "chips": len(devices),
        "modules": {k: {"count": c, "seconds": s} for k, (c, s) in modules.items()},
        "device_ops": [[k, v] for k, v in top],
        "idle_gaps": [[k, v] for k, v in sorted(idle.items(), key=lambda kv: -kv[1])[:10]],
    }


def _span_at(spans, starts, t):
    """Name of the span covering time t (the benchmark's spans follow one
    another and do not nest)."""
    i = bisect.bisect_right(starts, t) - 1
    return spans[i][2] if i >= 0 and spans[i][1] >= t else "outside spans"
