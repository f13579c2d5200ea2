"""Checkpointing: atomicity, async, retention, elastic restore, and the
2-minute-notice deadline model (paper §IV-F)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (CheckpointManager, LocalObjectStore,
                              ThrottledStore, latest_step, restore_pytree,
                              save_pytree)
from repro.checkpoint.checkpointer import MANIFEST, steps, tree_bytes


@pytest.fixture
def store(tmp_path):
    return LocalObjectStore(str(tmp_path / "s3"))


def tree():
    return {"a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": {"c": jnp.ones((2, 2), jnp.bfloat16)}}


def test_roundtrip(store):
    t = tree()
    save_pytree(store, "ckpt", 10, t)
    out, step, extra = restore_pytree(store, "ckpt", t)
    assert step == 10 and extra == {}
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(t["a"]))
    np.testing.assert_array_equal(np.asarray(out["b"]["c"], np.float32),
                                  np.asarray(t["b"]["c"], np.float32))


def test_atomicity_missing_manifest_ignored(store):
    t = tree()
    save_pytree(store, "ckpt", 10, t)
    save_pytree(store, "ckpt", 20, t)
    store.delete(f"ckpt/step_{20:08d}/{MANIFEST}")  # simulate torn write
    assert latest_step(store, "ckpt") == 10


def test_async_save(store):
    t = tree()
    h = save_pytree(store, "ckpt", 5, t, blocking=False)
    h.wait()
    assert latest_step(store, "ckpt") == 5


def test_manager_retention(store):
    mgr = CheckpointManager(store, "run1", save_interval_steps=10, keep_n=2)
    t = tree()
    for s in (10, 20, 30, 40):
        mgr.save(s, t, blocking=True)
    mgr.wait()
    assert steps(store, "run1") == [30, 40]


def test_deadline_model(tmp_path):
    inner = LocalObjectStore(str(tmp_path / "s3b"))
    slow = ThrottledStore(inner, bandwidth_bps=1e6, latency_s=0.0, simulate=True)
    mgr = CheckpointManager(slow, "run", keep_n=1)
    small = {"a": jnp.zeros((10,), jnp.float32)}
    big = {"a": jnp.zeros((200_000_000 // 4,), jnp.float32)}  # 200 MB @ 1MB/s
    assert mgr.fits_deadline(small, deadline_s=120.0)
    assert not mgr.fits_deadline(big, deadline_s=120.0)
    assert tree_bytes(big) == 200_000_000


def test_elastic_restore_resharding_hook(store):
    """A pytree of shardings of the template's structure places each leaf:
    the device placement hook."""
    t = tree()
    save_pytree(store, "ckpt", 1, t)
    sh = jax.sharding.SingleDeviceSharding(jax.devices()[1])
    out, _, _ = restore_pytree(store, "ckpt", t,
                               shardings=jax.tree.map(lambda _: sh, t))
    assert [leaf.sharding for leaf in jax.tree.leaves(out)] == [sh, sh]


def test_restore_places_each_leaf_by_position(store):
    """Leaf ``i`` of the shardings pytree places leaf ``i`` of the state, with
    more leaves than devices so the placement wraps round them."""
    devs = jax.devices()
    assert len(devs) == 8
    t = {f"l{i:02d}": jnp.arange(i + 1, dtype=jnp.float32) * (i + 0.5) for i in range(11)}
    t["l03"] = t["l03"].astype(jnp.bfloat16)
    t["l07"] = jnp.arange(3, dtype=jnp.int32) - 2
    save_pytree(store, "ckpt", 6, t, extra_meta={"note": "pos"})
    leaves, treedef = jax.tree.flatten(t)
    shardings = jax.tree.unflatten(treedef, [
        jax.sharding.SingleDeviceSharding(devs[i % 8]) for i in range(len(leaves))])
    out, step, extra = restore_pytree(store, "ckpt", t, shardings=shardings)
    assert step == 6 and extra == {"note": "pos"}
    got = jax.tree.leaves(out)
    assert len(got) == len(leaves)
    for i, (a, b) in enumerate(zip(leaves, got)):
        assert b.sharding == jax.sharding.SingleDeviceSharding(devs[i % 8])
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(_bits(a), _bits(b))


def test_manager_restore_specific_step(store):
    """The re-deploy path restores the step that actually fit the notice
    deadline, not necessarily the newest checkpoint."""
    mgr = CheckpointManager(store, "run2", save_interval_steps=10, keep_n=3)
    for s in (10, 20, 30):
        t = {"a": jnp.full((4,), float(s), jnp.float32)}
        mgr.save(s, t, blocking=True, extra_meta={"saved_at": s})
    like = {"a": jnp.zeros((4,), jnp.float32)}
    out, got = mgr.restore(like, step=20)
    assert got == 20 and mgr.restored_extra == {"saved_at": 20}
    np.testing.assert_array_equal(np.asarray(out["a"]), np.full((4,), 20.0))
    out, got = mgr.restore(like)              # step=None -> latest
    assert got == 30 and mgr.restored_extra == {"saved_at": 30}


def test_snapshot_restore_cross_mesh_optimizer_state(tmp_path):
    """Full training state (params + AdamW moments) round-trips bit-identical
    through save/restore onto a *different* device than the writer's — the
    elastic re-shard path of a revoked trial re-deployed on another slice."""
    from repro.configs.base import get_config
    from repro.launch.train import Trainer, state_template

    cfg = get_config("qwen1.5-0.5b", reduced=True)
    store = LocalObjectStore(str(tmp_path / "s3m"))
    mgr = CheckpointManager(store, "trialX", save_interval_steps=10 ** 9)
    tr = Trainer(cfg, batch=2, seq=16, seed=0, ckpt=mgr, val_every=5)
    tr.run_steps(7)
    tr.save(blocking=True)
    want = jax.tree.map(np.asarray, tr.state)

    dev = jax.devices()[1]
    tr2 = Trainer(cfg, batch=2, seq=16, seed=0,
                  ckpt=CheckpointManager(store, "trialX", 10 ** 9), val_every=5)
    sh = jax.sharding.SingleDeviceSharding(dev)
    step = tr2.restore(shardings=jax.tree.map(
        lambda _: sh, state_template(tr2.model, tr2.optimizer)))
    assert step == 7
    got = jax.tree.leaves(tr2.state)
    assert all(leaf.devices() == {dev} for leaf in got)
    for a, b in zip(jax.tree.leaves(want), got):
        np.testing.assert_array_equal(a, np.asarray(b))
    # the metric stream reloaded from the manifest continues the original
    assert tr2.metrics_steps == tr.metrics_steps
    assert tr2.metrics_vals == tr.metrics_vals


def test_trainer_checkpoint_restart_bitwise(tmp_path):
    """Revocation-restart determinism: restore + replay == uninterrupted."""
    from repro.configs.base import get_config
    from repro.launch.train import Trainer

    cfg = get_config("qwen1.5-0.5b", reduced=True)
    store = LocalObjectStore(str(tmp_path / "s3c"))
    mgr = CheckpointManager(store, "trial0", save_interval_steps=10, keep_n=2)
    tr1 = Trainer(cfg, batch=2, seq=16, seed=0, ckpt=mgr, val_every=5)
    tr1.run_steps(10)  # saves at 10
    mgr.wait()
    tr1.run_steps(5)   # no save (interval 10)
    loss_direct = tr1.metrics_vals[-1]

    tr2 = Trainer(cfg, batch=2, seq=16, seed=0,
                  ckpt=CheckpointManager(store, "trial0", 10, 2), val_every=5)
    step = tr2.restore()
    assert step == 10
    tr2.run_steps(5)
    assert tr2.metrics_vals[-1] == pytest.approx(loss_direct, rel=1e-5)


# ------------------------------------------------------ the lazy trainer state


def _mamba2_trainer(store, seed):
    from repro.configs.base import get_config
    from repro.launch.train import Trainer

    return Trainer(get_config("mamba2-130m", reduced=True), batch=1, seq=16, seed=seed,
                   ckpt=CheckpointManager(store, "lazy", save_interval_steps=10 ** 9))


def _recorded(tmp_path, fn):
    """``fn()``'s result and the program spans recorded while it ran."""
    from repro import telemetry

    telemetry.reset()
    with jax.profiler.trace(str(tmp_path / "trace")):
        out = fn()
    recs = telemetry.records()
    telemetry.reset()
    return out, recs


@pytest.mark.parametrize("built_first", [False, True], ids=["unbuilt", "built"])
@pytest.mark.parametrize("elastic", [False, True], ids=["local", "shardings"])
def test_restored_trainer_gives_back_the_saved_bits(tmp_path, built_first, elastic):
    """A trainer restored before its state is read never builds the random
    state, with or without a re-shard; one whose state was read first builds
    it once.  Both hold the saved bits, which differ from their own random
    init (another seed)."""
    store = LocalObjectStore(str(tmp_path / "s"))
    saver = _mamba2_trainer(store, seed=1)
    saver.step = 5
    saver.save(blocking=True)
    want = jax.device_get(saver.state)
    dev = jax.devices()[1]

    def resume():
        from repro.launch.train import state_template

        tr = _mamba2_trainer(store, seed=0)
        if built_first:
            jax.block_until_ready(tr.state)
        return tr, tr.restore(shardings=_shardings(
            elastic, state_template(tr.model, tr.optimizer)))

    (tr, step), recs = _recorded(tmp_path, resume)
    assert step == 5 and tr.step == 5
    got = jax.tree.leaves(tr.state)
    assert len(got) == len(jax.tree.leaves(want))
    for a, b in zip(jax.tree.leaves(want), got):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, np.asarray(b))
    if elastic:
        assert all(leaf.devices() == {dev} for leaf in got)
    (init,) = [r for r in recs if r.name == "trainer.init"]
    (restore,) = [r for r in recs if r.name == "trainer.restore"]
    assert [r.name for r in recs if r.parent == init.id] == ["trainer.build"]
    init_states = [r for r in recs if r.name == "trainer.init_state"]
    assert len(init_states) == int(built_first)
    assert all(r.t1 <= restore.t0 for r in init_states)


@pytest.mark.parametrize("first_read", ["plain", "eval_shape"])
def test_unrestored_trainer_holds_the_random_init(tmp_path, first_read):
    """Read without a restore, a trainer holds ``init_state``'s bits; a first
    read inside a trace still keeps concrete arrays, not tracers."""
    from repro.launch.train import init_state

    tr = _mamba2_trainer(LocalObjectStore(str(tmp_path / "s")), seed=3)
    if first_read == "eval_shape":
        shapes = jax.eval_shape(lambda: tr.state)
        assert all(isinstance(s, jax.ShapeDtypeStruct) for s in jax.tree.leaves(shapes))
    held = tr.state
    assert all(isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer)
               for x in jax.tree.leaves(held))
    want = init_state(tr.model, tr.optimizer, 3)
    assert jax.tree.structure(held) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(held)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert tr.state is held


# ------------------------------------------------------- decoding in place


def _mixed_tree():
    return {"w": jnp.arange(24, dtype=jnp.float32).reshape(4, 6) / 7,
            "b": (jnp.arange(5, dtype=jnp.float32) / 3).astype(jnp.bfloat16),
            "step": jnp.array(41, jnp.int32),
            "ids": jnp.arange(6, dtype=jnp.int32).reshape(2, 3) - 2}


def _bits(x):
    return np.asarray(x).reshape(-1).view(np.uint8)


def _shardings(elastic, like):
    """None, or every leaf of ``like`` onto the second device."""
    if not elastic:
        return None
    sh = jax.sharding.SingleDeviceSharding(jax.devices()[1])
    return jax.tree.map(lambda _: sh, like)


def _decode_copies(recs):
    return [r.counts.get("ckpt.host_copy_bytes")
            for r in recs if r.name == "ckpt.restore.decode"]


@pytest.mark.parametrize("elastic", [False, True], ids=["asarray", "device_put"])
def test_matching_dtypes_decode_in_place(store, tmp_path, elastic):
    """Float, bfloat16 and int leaves restored into templates of their saved
    dtypes come back bit for bit, and no decode copies a byte."""
    t = _mixed_tree()
    save_pytree(store, "ckpt", 4, t)
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    (out, step, _), recs = _recorded(
        tmp_path, lambda: restore_pytree(store, "ckpt", like, shardings=_shardings(elastic, like)))
    assert step == 4
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(out)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(_bits(a), _bits(b))
    if elastic:
        assert all(leaf.devices() == {jax.devices()[1]} for leaf in jax.tree.leaves(out))
    assert _decode_copies(recs) == [None] * len(jax.tree.leaves(t))


@pytest.mark.parametrize("elastic", [False, True], ids=["asarray", "device_put"])
def test_other_dtype_template_converts_and_counts(store, tmp_path, elastic):
    """A leaf restored into a template of another dtype is numpy's ``astype``
    of the saved one, and its decode counts exactly the converted bytes; the
    leaves of matching dtype in the same tree count none."""
    t = _mixed_tree()
    save_pytree(store, "ckpt", 2, t)
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    like["w"] = jax.ShapeDtypeStruct(t["w"].shape, jnp.bfloat16)
    like["b"] = jax.ShapeDtypeStruct(t["b"].shape, jnp.float32)
    (out, _, _), recs = _recorded(
        tmp_path, lambda: restore_pytree(store, "ckpt", like, shardings=_shardings(elastic, like)))
    want = {k: np.asarray(t[k]).astype(like[k].dtype) for k in t}
    for k in t:
        assert out[k].dtype == like[k].dtype
        np.testing.assert_array_equal(_bits(out[k]), _bits(want[k]))
    leaves_want = jax.tree.leaves(want)   # the template's flattening order
    converted = [k in ("w", "b") for k in sorted(t)]
    assert _decode_copies(recs) == [leaf.nbytes if c else None
                                    for leaf, c in zip(leaves_want, converted)]


class _AlignedStore:
    """A host-memory store whose leaves are read-only, 64-byte aligned
    buffers: the alignment at which the CPU backend adopts a host array
    without a copy, so the restored state may alias the stored snapshot."""

    def __init__(self):
        self.blobs = {}

    def put(self, key, data):
        if key.endswith(".npy"):
            raw = np.empty(len(data) + 64, np.uint8)
            off = -raw.ctypes.data % 64
            buf = raw[off:off + len(data)]
            buf[:] = np.frombuffer(data, np.uint8)
            buf.flags.writeable = False
            data = memoryview(buf)
        self.blobs[key] = data

    def get(self, key):
        return self.blobs[key]

    def delete(self, key):
        self.blobs.pop(key, None)

    def list(self, prefix=""):
        return sorted(k for k in self.blobs if k.startswith(prefix))


@pytest.mark.parametrize("kind", ["local", "aligned"])
def test_donated_step_leaves_the_stored_snapshot_intact(tmp_path, kind):
    """A restored state may be a view of the store's bytes on the host; the
    train step donates its state, and that must never write into a stored
    snapshot.  After one donated step, a second restore of the same step
    gives the first one's bits, and the stored leaves are unchanged."""
    store = LocalObjectStore(str(tmp_path / "s")) if kind == "local" else _AlignedStore()
    saver = _mamba2_trainer(store, seed=1)
    saver.step = 3
    saver.save(blocking=True)
    leaf_keys = [k for k in store.list("lazy/") if k.endswith(".npy")]
    stored = {k: bytes(store.get(k)) for k in leaf_keys}

    tr = _mamba2_trainer(store, seed=0)
    assert tr.restore() == 3
    first = [np.array(x) for x in jax.tree.leaves(tr.state)]
    tr.run_steps(1)
    assert tr.step == 4
    jax.block_until_ready(tr.state)

    again = _mamba2_trainer(store, seed=0)
    assert again.restore(step=3) == 3
    second = jax.tree.leaves(again.state)
    assert len(second) == len(first)
    for a, b in zip(first, second):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(_bits(a), _bits(b))
    assert {k: bytes(store.get(k)) for k in leaf_keys} == stored


class _ManifestCountingStore(LocalObjectStore):
    """A local store that counts the gets of checkpoint manifests."""

    manifest_gets = 0

    def get(self, key):
        if key.endswith(MANIFEST):
            self.manifest_gets += 1
        return super().get(key)


def test_trainer_restore_reads_the_manifest_once(tmp_path):
    """A trainer's restore gets the checkpoint's manifest once, and takes its
    metric stream from that one read."""
    store = _ManifestCountingStore(str(tmp_path / "s"))
    saver = _mamba2_trainer(store, seed=1)
    saver.step = 20
    saver.metrics_steps, saver.metrics_vals = [10, 20], [2.5, 2.25]
    saver.save(blocking=True)
    assert store.manifest_gets == 0
    tr = _mamba2_trainer(store, seed=0)
    assert tr.restore() == 20
    assert store.manifest_gets == 1
    assert (tr.metrics_steps, tr.metrics_vals) == ([10, 20], [2.5, 2.25])
    assert tr.restore(step=20) == 20
    assert store.manifest_gets == 2
