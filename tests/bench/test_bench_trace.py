"""The trace reduction, checked on a trace recorded on a TPU v5e.

``bench/testdata/tiny_revoked.xplane.pb`` is a traced run of the tiny
revoked cell (``bench_testlib``) on one v5e chip: one revocation cycle
(snapshot, new trainer, restore, first step) and a train step inside the
``window`` span.  It was cut from 3.6 MB to what the reducer reads: the TPU
plane's ``XLA Ops`` and ``XLA Modules`` lines (each op's HLO text cut to its
instruction name), the host's ``python`` line with the benchmark's spans,
and the trace's environment; the reduction of the cut file equals that of
the whole one.  The checks recompute what the reducer reports by other
means: busy time by a sweep over sorted op edges, the train-step program's
runs against the ``train_step`` spans, and idle time against the window.
"""

import pytest

from bench_testlib import ROOT

from bench.trace_reduce import _leaves, _span_at, _union, op_name, reduce_trace

TRACE = ROOT / "bench" / "testdata" / "tiny_revoked.xplane.pb"
SPANS = {"train_step", "snapshot", "trainer_init", "restore"}


@pytest.fixture(scope="module")
def raw():
    import jax

    data = jax.profiler.ProfileData.from_file(str(TRACE))
    out = {"ops": [], "modules": [], "spans": [], "window": None}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key:
                    out[key] += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                                 for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == "window":
                        out["window"] = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name in SPANS:
                        out["spans"].append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    return out


@pytest.fixture(scope="module")
def reduced():
    return reduce_trace(TRACE, SPANS)


def test_trace_is_small_and_from_the_chip(raw):
    assert TRACE.stat().st_size < 600_000
    assert raw["ops"] and raw["modules"] and raw["window"]
    assert {n for _, _, n in raw["spans"]} == SPANS


def test_busy_matches_an_edge_sweep(raw, reduced):
    lo, hi = raw["window"]
    edges = sorted([(max(s, lo), 1) for s, e, _ in raw["ops"] if e > lo and s < hi]
                   + [(min(e, hi), -1) for s, e, _ in raw["ops"] if e > lo and s < hi],
                   key=lambda x: (x[0], -x[1]))
    busy, depth, since = 0, 0, None
    for t, d in edges:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0:
            busy += t - since
    assert reduced["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-12)
    assert reduced["window_s"] == pytest.approx((hi - lo) * 1e-9, rel=1e-12)
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    assert reduced["chips"] == 1


def test_idle_time_adds_up_and_is_named_by_spans(reduced):
    idle = sum(v for _, v in reduced["idle_gaps"])
    assert idle == pytest.approx(reduced["window_s"] - reduced["busy_s"], rel=1e-9)
    assert {k for k, _ in reduced["idle_gaps"]} <= SPANS | {"outside spans"}
    values = [v for _, v in reduced["idle_gaps"]]
    assert values == sorted(values, reverse=True)


def test_train_step_program_runs_once_a_step(raw, reduced):
    lo, hi = raw["window"]
    steps = [s for s in raw["spans"] if s[2] == "train_step" and s[0] >= lo and s[1] <= hi]
    runs = reduced["modules"]["jit_train_step"]
    assert runs["count"] == len(steps) >= 1
    assert 0 < runs["seconds"] < sum(e - s for s, e, _ in steps) * 1e-9


def test_top_device_ops(reduced):
    ops = reduced["device_ops"]
    assert 1 <= len(ops) <= 10
    assert [v for _, v in ops] == sorted((v for _, v in ops), reverse=True)
    assert sum(v for _, v in ops) <= reduced["busy_s"] * (1 + 1e-9)


def test_helpers():
    assert _union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    loop = (0, 10, "%while.1 = (f32[]) while(...)")
    body = [(1, 4, "%fusion.2 = f32[] fusion(...)"), (5, 9, "%fusion.3 = f32[] fusion(...)")]
    assert _leaves([loop] + body) == body
    assert op_name(body[0][2]) == "fusion.2"
    spans = [(0, 10, "train_step"), (20, 30, "snapshot")]
    starts = [s for s, _, _ in spans]
    assert [_span_at(spans, starts, t) for t in (5, 15, 25, 35)] == [
        "train_step", "outside spans", "snapshot", "outside spans"]
