"""The program's recorder (``repro.telemetry``): off, it records nothing and
opens no annotation; on (while a profiler trace records), spans nest by
thread or by a given parent, counts land on the innermost span, compile
events on the span that compiled, and the checkpoint path and the trainer
build record their named spans."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.checkpoint import CheckpointManager, LocalObjectStore
from repro.checkpoint.checkpointer import tree_bytes

# the benchmark's own spans (bench/trial.py, bench/run.py): a program span of
# one of these names would be read by the benchmark's trace reduction
BENCHMARK_SPANS = {"snapshot", "trainer_init", "restore", "train_step", "window"}


@pytest.fixture
def recorder(tmp_path):
    telemetry.reset()
    with jax.profiler.trace(str(tmp_path / "trace")):
        yield telemetry
    telemetry.reset()


def by_name(recs, name):
    return [r for r in recs if r.name == name]


def test_off_records_nothing_and_opens_no_annotation(monkeypatch):
    opened = []

    class Annotation:
        def __init__(self, name):
            opened.append(name)

        @staticmethod
        def is_enabled():
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    telemetry.reset()
    assert telemetry.span("a") is telemetry.span("b")
    with telemetry.span("a") as sid:
        telemetry.count("n", 3)
        with telemetry.span("b"):
            pass
    assert sid is None
    assert telemetry.records() == [] and opened == []


def test_on_each_span_opens_an_annotation(recorder, monkeypatch):
    opened = []
    real = jax.profiler.TraceAnnotation

    class Annotation(real):
        def __init__(self, name):
            opened.append(name)
            super().__init__(name)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    with telemetry.span("a"):
        with telemetry.span("a.b"):
            pass
    assert opened == ["a", "a.b"]
    assert [r.name for r in telemetry.records()] == ["a.b", "a"]


def test_a_profiler_trace_turns_the_recorder_on(tmp_path):
    telemetry.reset()
    with jax.profiler.trace(str(tmp_path)):
        with telemetry.span("traced"):
            pass
    with telemetry.span("after"):
        pass
    assert [r.name for r in telemetry.records()] == ["traced"]
    telemetry.reset()


def test_parents_nest_by_thread_and_by_given_parent(recorder):
    def worker(parent):
        with telemetry.span("w.given", parent=parent):
            with telemetry.span("w.child"):
                pass
        with telemetry.span("w.free"):
            pass

    with telemetry.span("outer") as outer:
        with telemetry.span("inner") as inner:
            with telemetry.span("leaf"):
                pass
        t = threading.Thread(target=worker, args=(outer,))
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    recs = {r.name: r for r in telemetry.records()}
    seen = {n: r.parent for n, r in recs.items()}
    assert seen == {"outer": None, "inner": outer, "leaf": inner,
                    "w.given": outer, "w.child": recs["w.given"].id, "w.free": None}
    assert all(r.t0 <= r.t1 for r in recs.values())
    assert recs["outer"].t0 <= recs["inner"].t0 <= recs["leaf"].t1 <= recs["outer"].t1
    assert len({r.id for r in recs.values()}) == len(recs)


def test_counts_land_on_the_innermost_span(recorder):
    telemetry.count("lost", 1)   # no span open: not kept
    with telemetry.span("outer"):
        telemetry.count("n", 2)
        with telemetry.span("inner"):
            telemetry.count("n", 5)
            telemetry.count("n", 1)
            telemetry.count("bytes", 10)
        telemetry.count("n", 1)
    recs = {r.name: r for r in telemetry.records()}
    assert recs["inner"].counts == {"n": 6, "bytes": 10}
    assert recs["outer"].counts == {"n": 3}


def test_a_fresh_jit_counts_its_compile_seconds(recorder):
    with telemetry.span("outer"):
        with telemetry.span("compiles"):
            jax.jit(lambda x: x * 3.25 + 1.0)(jnp.ones(7)).block_until_ready()
    recs = {r.name: r for r in telemetry.records()}
    counts = recs["compiles"].counts
    for event in ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration"):
        assert counts[event] > 0
    assert set(counts) <= set(telemetry.COMPILE_SECONDS + telemetry.COMPILE_EVENTS)
    assert recs["outer"].counts == {}
    assert sum(counts[e] for e in telemetry.COMPILE_SECONDS if e in counts) <= (
        recs["compiles"].t1 - recs["compiles"].t0)


def small_tree():
    return {"w": jnp.arange(24, dtype=jnp.float32).reshape(4, 6),
            "b": jnp.ones((5,), jnp.bfloat16),
            "opt": {"step": jnp.zeros((), jnp.int32), "m": jnp.full((3, 2), 0.5)}}


SAVE_PER_LEAF = ("ckpt.save.serialize", "ckpt.save.put")
RESTORE_PER_LEAF = ("ckpt.restore.get", "ckpt.restore.decode", "ckpt.restore.to_device")


@pytest.mark.parametrize("blocking", [True, False])
def test_checkpoint_save_and_restore_spans(recorder, tmp_path, blocking):
    tree = small_tree()
    n = len(jax.tree.leaves(tree))
    mgr = CheckpointManager(LocalObjectStore(str(tmp_path / "s")), "t", keep_n=1)
    mgr.save(3, tree, blocking=blocking)
    mgr.wait()
    out, step = mgr.restore(jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                                         tree))
    assert step == 3
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    recs = telemetry.records()
    (save,), (restore,) = by_name(recs, "ckpt.save"), by_name(recs, "ckpt.restore")
    for name in ("ckpt.save.to_host", "ckpt.save.manifest"):
        assert [r.parent for r in by_name(recs, name)] == [save.id]
    for name in SAVE_PER_LEAF:
        assert [r.parent for r in by_name(recs, name)] == [save.id] * n
    assert [r.parent for r in by_name(recs, "ckpt.restore.manifest")] == [restore.id]
    for name in RESTORE_PER_LEAF:
        assert [r.parent for r in by_name(recs, name)] == [restore.id] * n

    def total(key):
        return sum(r.counts.get(key, 0) for r in recs)

    # every saved dtype is the template's: each leaf decodes in place
    assert [r.counts.get("ckpt.host_copy_bytes") for r in by_name(recs, "ckpt.restore.decode")
            ] == [None] * n
    assert total("ckpt.host_copy_bytes") == 0
    assert not hasattr(mgr, "save_seconds") and not hasattr(mgr, "saves")

    # a template of another dtype for one leaf: that leaf's conversion copy,
    # its converted bytes, on its own decode span, and no other
    telemetry.reset()
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    like["w"] = jax.ShapeDtypeStruct(tree["w"].shape, jnp.bfloat16)
    mgr.restore(like)
    recs = telemetry.records()
    assert [r.counts.get("ckpt.host_copy_bytes") for r in by_name(recs, "ckpt.restore.decode")
            ] == [tree_bytes({"w": like["w"]}) if leaf is like["w"] else None
                  for leaf in jax.tree.leaves(like)]
    assert total("ckpt.host_copy_bytes") == tree_bytes({"w": like["w"]})


def test_trainer_spans_are_the_programs_own(recorder, tmp_path):
    from repro.configs.base import get_config
    from repro.launch.train import Trainer

    cfg = get_config("mamba2-130m", reduced=True)
    store = LocalObjectStore(str(tmp_path / "s"))
    tr = Trainer(cfg, batch=1, seq=16, seed=0, ckpt=CheckpointManager(store, "tr"))
    tr.save(blocking=True)
    tr2 = Trainer(cfg, batch=1, seq=16, seed=0, ckpt=CheckpointManager(store, "tr"))
    assert tr2.restore() == 0
    recs = telemetry.records()
    names = {r.name for r in recs}
    assert names == {"trainer.init", "trainer.build", "trainer.init_state", "trainer.restore",
                     "ckpt.save", "ckpt.save.to_host", "ckpt.save.serialize", "ckpt.save.put",
                     "ckpt.save.manifest", "ckpt.restore", "ckpt.restore.manifest",
                     "ckpt.restore.get", "ckpt.restore.decode", "ckpt.restore.to_device"}
    assert not names & BENCHMARK_SPANS
    inits = by_name(recs, "trainer.init")
    assert len(inits) == 2
    # construction builds no state: each trainer.init holds trainer.build alone
    for init in inits:
        assert [r.name for r in recs if r.parent == init.id] == ["trainer.build"]
    assert sorted(r.parent for r in by_name(recs, "trainer.build")) == sorted(
        r.id for r in inits)
    (restore,) = by_name(recs, "trainer.restore")
    assert by_name(recs, "ckpt.restore")[0].parent == restore.id
    # the first trainer builds its random state when save first reads it, and
    # that build traces and compiles the random init; the counts land there.
    # The resumed trainer builds none.
    (first,) = by_name(recs, "trainer.init_state")
    (save,) = by_name(recs, "ckpt.save")
    assert first.parent is None and first.t1 <= save.t0
    assert first.counts.get("/jax/core/compile/jaxpr_trace_duration", 0) > 0
