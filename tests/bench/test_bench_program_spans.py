"""The readers of the program's own spans (``bench/program_spans.py`` and the
``*.resume`` metrics that use it), on hand-built records and on a tiny
resume cell driven on the CPU under a profiler trace, as a ``--trace 1``
run records it."""

import sys
import time
from types import SimpleNamespace

import jax
import pytest

import bench_testlib as tl
from bench_testlib import ROOT

from bench.harness import Bench

NEW_METRICS = ["snapshot_to_host_s.resume", "snapshot_write_s.resume",
               "restore_read_s.resume", "restore_to_device_s.resume",
               "trainer_init_state_s.resume", "trainer_compile_s.resume",
               "restore_host_copy_gb.resume", "trainer_cache_lookups.resume"]
UNITS = {"restore_host_copy_gb.resume": "GB", "trainer_cache_lookups.resume": "count"}


def revocation(t, k, first_id):
    """One revocation's program spans from ``t``, each base duration times
    ``k``: the benchmark spans ``(name, t0, t1)`` and the program's."""
    from repro.telemetry import Span

    recs, ids = [], iter(range(first_id, first_id + 100))

    def span(name, parent, t0, dur, counts=()):
        r = Span(next(ids), parent, name, t0, t0 + dur * k, {c: v * k for c, v in counts})
        recs.append(r)
        return r

    save = span("ckpt.save", None, t, 2.0)
    span("ckpt.save.to_host", save.id, t, 1.4)
    for i in range(2):
        span("ckpt.save.serialize", save.id, t + (1.4 + 0.15 * i) * k, 0.1)
        span("ckpt.save.put", save.id, t + (1.5 + 0.15 * i) * k, 0.05)
    span("ckpt.save.manifest", save.id, t + 1.8 * k, 0.1)
    t_init = t + 2.0 * k
    init = span("trainer.init", None, t_init, 1.5)
    span("trainer.build", init.id, t_init, 0.1)
    span("trainer.init_state", init.id, t_init + 0.1 * k, 1.4, [
        ("/jax/core/compile/jaxpr_trace_duration", 0.3),
        ("/jax/core/compile/backend_compile_duration", 0.25),
        ("/jax/compilation_cache/cache_retrieval_time_sec", 0.2),   # not compile time
        ("/jax/compilation_cache/cache_hits", 1)])
    t_r = t_init + 1.5 * k
    tr = span("trainer.restore", None, t_r, 2.0)
    cr = span("ckpt.restore", tr.id, t_r + 0.1 * k, 1.8)
    span("ckpt.restore.manifest", cr.id, t_r + 0.1 * k, 0.1)
    span("ckpt.restore.get", cr.id, t_r + 0.2 * k, 0.1)
    span("ckpt.restore.decode", cr.id, t_r + 0.3 * k, 0.4, [("ckpt.host_copy_bytes", 5e8)])
    span("ckpt.restore.to_device", cr.id, t_r + 0.7 * k, 1.2)
    bench = [("snapshot", t, t + 2.0 * k), ("trainer_init", t_init, t_init + 1.5 * k),
             ("restore", t_r, t_r + 2.1 * k)]
    return bench, recs


def hand_built_run():
    """A set-up revocation before the window and two in it, at 1x and 3x the
    base durations: each reading is twice its base."""
    spans, recs = [], []
    for t, k, first in ((1.0, 1, 1), (20.0, 1, 101), (40.0, 3, 201)):
        b, r = revocation(t, k, first)
        spans += b
        recs += r
    return {"spans": SimpleNamespace(items=spans), "window": (10.0, 80.0), "program": recs}


@pytest.mark.parametrize("metric,base", [
    ("snapshot_to_host_s.resume", 1.4),
    ("snapshot_write_s.resume", 2 * 0.1 + 2 * 0.05 + 0.1),
    ("restore_read_s.resume", 0.1 + 0.1 + 0.4),
    ("restore_to_device_s.resume", 1.2),
    ("trainer_init_state_s.resume", 1.4),
    ("trainer_compile_s.resume", 0.3 + 0.25),
    ("restore_host_copy_gb.resume", 0.5),
    ("trainer_cache_lookups.resume", 1),
])
def test_reader_on_hand_built_records(metric, base):
    value = Bench(ROOT).reader(metric)(hand_built_run())
    assert value == pytest.approx(2 * base, rel=1e-9)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_reader_finds_nothing_where_the_program_records_nothing(metric, monkeypatch):
    read = Bench(ROOT).reader(metric)
    run = hand_built_run()
    assert read(dict(run, program=[])) is None
    assert read(dict(run, window=(90.0, 100.0))) is None      # nothing in the window
    del run["program"]
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)  # a program without one
    assert read(run) is None


def test_new_metrics_are_entries_of_the_resume_cell():
    bench = Bench(ROOT)
    entries = {m["name"]: m for m in bench.per_layer("trial.mamba2.resume")}
    for name in NEW_METRICS:
        m = entries[name]
        assert (m["unit"], m["better"], m["moves"]) == (
            UNITS.get(name, "s"), "lower", "revocation_stall_s")
        assert m["layer"] in ("checkpoint", "trial runtime")
        assert m["workloads"] == ["trial.mamba2.resume"]


def test_tiny_resume_cell_splits_the_stall(tmp_path):
    from repro import telemetry

    from bench.run import ResumeLoop

    bench = Bench(tl.tiny_root(tmp_path))
    loop = ResumeLoop(bench, "tiny.mamba2.resume", 2**31 + 11)
    telemetry.reset()
    try:
        with jax.profiler.trace(str(tmp_path / "trace")):
            t0 = time.perf_counter()
            loop.unit()
            loop.unit()
            t1 = time.perf_counter()
        run = {"spans": loop.trial.spans, "window": (t0, t1)}
        values = {m: bench.reader(m)(run) for m in NEW_METRICS}
        loop.finish(t0, t1)
    finally:
        telemetry.reset()
    assert all(v is not None and v >= 0 for v in values.values()), values
    assert values["snapshot_to_host_s.resume"] > 0 and values["restore_to_device_s.resume"] > 0
    snapshot = sum(loop.trial.spans.durations("snapshot", since=t0)) / 2
    restore = sum(loop.trial.spans.durations("restore", since=t0)) / 2
    init = sum(loop.trial.spans.durations("trainer_init", since=t0)) / 2
    assert values["snapshot_to_host_s.resume"] + values["snapshot_write_s.resume"] < snapshot
    assert values["restore_read_s.resume"] + values["restore_to_device_s.resume"] < restore
    assert values["trainer_init_state_s.resume"] < init
    # every leaf's bytes are copied once more by the decode
    assert values["restore_host_copy_gb.resume"] * 1e9 == pytest.approx(loop.state_bytes)
