"""Checkpointing: async, atomic, elastic-restore.

Layout (Orbax-flavored, one object per leaf so multi-host writers shard
naturally):

    <prefix>/step_<N>/leaf_<i>.npy      # one array per pytree leaf
    <prefix>/step_<N>/MANIFEST.json     # written LAST -> atomicity marker

A checkpoint is valid iff its manifest exists (readers ignore torn writes).
The layout is this module's alone: ``restore_pytree`` returns the tree, its
step and the manifest's ``extra`` dict, so no caller reads a manifest.  Given
a pytree of shardings it places each leaf onto a *different* mesh than the
writer's — the elastic path used when a revoked trial is re-deployed on
another slice type (SpotTune Algorithm 1 lines 24-26).

The 2-minute-revocation-notice budget: ``CheckpointManager.fits_deadline``
predicts the transfer time from the store's bandwidth model, reproducing the
paper's "max model size = speed x 120 s" bound (§IV-F).
"""

from __future__ import annotations

import json
import threading
from typing import Optional

import jax
import ml_dtypes  # noqa: F401  (registers bfloat16 with numpy)
import numpy as np

from repro import telemetry

MANIFEST = "MANIFEST.json"


def _leaf_paths(tree):
    paths = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        paths.append((jax.tree_util.keystr(path), leaf))
    return paths


def tree_bytes(tree) -> int:
    return sum(int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
               for l in jax.tree.leaves(tree))


def _to_host(leaves):
    """Each leaf as a host array, in order, each wait in its own
    ``ckpt.save.to_host`` span (closed before the leaf is handed on)."""
    for leaf in leaves:
        with telemetry.span("ckpt.save.to_host"):
            arr = np.asarray(leaf)
        yield arr


def save_pytree(store, prefix: str, step: int, tree, blocking: bool = True,
                extra_meta: Optional[dict] = None):
    """Serialize a pytree.  Returns a handle with .wait() (async support).

    Every device leaf's copy to the host is started before the first is
    waited on.  A blocking save then serialises leaf ``i`` while the later
    leaves are still in flight; a non-blocking one brings every leaf to the
    host on the caller's thread before its writer starts, so the caller may
    donate the state as soon as it returns.

    Spans (``repro.telemetry``): ``ckpt.save`` around the call, with per
    leaf ``ckpt.save.to_host`` (the wait for that leaf's copy; on the
    caller's thread), ``ckpt.save.serialize`` and ``ckpt.save.put``, then
    ``ckpt.save.manifest`` (on the writer thread when not blocking, still
    children of ``ckpt.save``); counter ``ckpt.save.prefetch_bytes`` on
    ``ckpt.save`` (the bytes of the leaves whose copy was started before
    the first wait: 0 for a tree of host arrays)."""
    with telemetry.span("ckpt.save") as save_id:
        leaves, treedef = jax.tree.flatten(tree)
        leaves = [l if isinstance(l, jax.Array) else np.asarray(l) for l in leaves]
        jax.copy_to_host_async(leaves)
        telemetry.count("ckpt.save.prefetch_bytes",
                        sum(l.nbytes for l in leaves if isinstance(l, jax.Array)))
        meta = {
            "treedef": str(treedef),
            "n_leaves": len(leaves),
            "step": step,
            "shapes": [list(l.shape) for l in leaves],
            "dtypes": [str(l.dtype) for l in leaves],
            "keys": [k for k, _ in _leaf_paths(tree)],
            "extra": extra_meta or {},
        }
        host_leaves = _to_host(leaves)
        if not blocking:
            host_leaves = list(host_leaves)   # device->host before the thread

        def write():
            base = f"{prefix}/step_{step:08d}"
            for i, arr in enumerate(host_leaves):
                # raw buffers (not np.save): numpy can't serialize ml_dtypes
                # (bfloat16); shape/dtype live in the manifest
                with telemetry.span("ckpt.save.serialize", parent=save_id):
                    data = arr.tobytes()
                with telemetry.span("ckpt.save.put", parent=save_id):
                    store.put(f"{base}/leaf_{i:05d}.npy", data)
            with telemetry.span("ckpt.save.manifest", parent=save_id):
                store.put(f"{base}/{MANIFEST}", json.dumps(meta).encode())

        if blocking:
            write()
            return _DoneHandle()
        t = threading.Thread(target=write, daemon=True)
        t.start()
        return _ThreadHandle(t)


class _DoneHandle:
    def wait(self):
        return None

    def done(self) -> bool:
        return True


class _ThreadHandle:
    def __init__(self, t):
        self._t = t

    def wait(self):
        self._t.join()

    def done(self) -> bool:
        return not self._t.is_alive()


def steps(store, prefix: str):
    """All *valid* (manifest-present) checkpoint steps, ascending."""
    out = []
    for key in store.list(prefix + "/"):
        if key.endswith(MANIFEST):
            stepdir = key.split("/")[-2]
            out.append(int(stepdir.split("_")[1]))
    return sorted(set(out))


def latest_step(store, prefix: str) -> Optional[int]:
    s = steps(store, prefix)
    return s[-1] if s else None


def restore_pytree(store, prefix: str, like, step: Optional[int] = None,
                   shardings=None):
    """Restore into the structure of ``like`` (a pytree of arrays or
    ShapeDtypeStructs).  Returns ``(tree, step, extra)``, ``extra`` being
    the manifest's ``extra`` (the save's ``extra_meta``; ``{}`` when none).

    With ``shardings=None`` each leaf goes to the default device
    (``jnp.asarray``); otherwise ``shardings`` is a pytree of ``like``'s
    structure and each leaf is ``device_put`` onto its own sharding, the
    elastic re-shard onto a new mesh.

    A leaf saved in its template's dtype decodes in place: the host array
    is a read-only view of the store's bytes, and only a leaf whose dtype
    differs is converted into a copy.

    Spans (``repro.telemetry``): ``ckpt.restore`` around the call, with
    ``ckpt.restore.manifest`` (finding and reading it), then per leaf
    ``ckpt.restore.get``, ``ckpt.restore.decode`` and
    ``ckpt.restore.to_device``; counter ``ckpt.host_copy_bytes`` (bytes
    copied on the host beyond those read: those of the dtype conversions,
    0 when every leaf decodes in place)."""
    with telemetry.span("ckpt.restore"):
        with telemetry.span("ckpt.restore.manifest"):
            if step is None:
                step = latest_step(store, prefix)
                if step is None:
                    raise FileNotFoundError(f"no checkpoint under {prefix}")
            base = f"{prefix}/step_{step:08d}"
            meta = json.loads(store.get(f"{base}/{MANIFEST}").decode())
        leaves_like, treedef = jax.tree.flatten(like)
        assert meta["n_leaves"] == len(leaves_like), (
            f"checkpoint has {meta['n_leaves']} leaves, template has {len(leaves_like)}")
        leaves_sh = None if shardings is None else treedef.flatten_up_to(shardings)
        out = []
        for i, tmpl in enumerate(leaves_like):
            with telemetry.span("ckpt.restore.get"):
                data = store.get(f"{base}/leaf_{i:05d}.npy")
            with telemetry.span("ckpt.restore.decode"):
                arr = np.frombuffer(data, dtype=np.dtype(meta["dtypes"][i])
                                    ).reshape(meta["shapes"][i])
                assert list(arr.shape) == list(tmpl.shape), (i, arr.shape, tmpl.shape)
                host = arr.astype(tmpl.dtype, copy=False)
                if not np.may_share_memory(host, arr):
                    telemetry.count("ckpt.host_copy_bytes", host.nbytes)
            with telemetry.span("ckpt.restore.to_device"):
                if leaves_sh is not None:
                    out.append(jax.device_put(host, leaves_sh[i]))
                else:
                    out.append(jax.numpy.asarray(host))
    return jax.tree.unflatten(treedef, out), step, meta.get("extra", {})


class CheckpointManager:
    """Interval + on-demand checkpointing with retention and deadline checks."""

    def __init__(self, store, prefix: str, save_interval_steps: int = 100,
                 keep_n: int = 3):
        self.store = store
        self.prefix = prefix
        self.save_interval_steps = save_interval_steps
        self.keep_n = keep_n
        self._pending = None
        self.restored_extra: dict = {}  # the manifest's ``extra`` of the last restore

    def should_save(self, step: int) -> bool:
        return step > 0 and step % self.save_interval_steps == 0

    def save(self, step: int, tree, blocking: bool = False, extra_meta=None):
        if self._pending is not None:
            self._pending.wait()  # never two in flight
        h = save_pytree(self.store, self.prefix, step, tree,
                        blocking=blocking, extra_meta=extra_meta)
        self._pending = h
        self._gc()
        return h

    def wait(self):
        if self._pending is not None:
            self._pending.wait()
            self._pending = None

    def fits_deadline(self, tree, deadline_s: float = 120.0) -> bool:
        """Can this pytree reach the store before the revocation deadline?"""
        if hasattr(self.store, "transfer_time"):
            return self.store.transfer_time(tree_bytes(tree)) <= deadline_s
        return True

    def restore(self, like, step: Optional[int] = None, shardings=None):
        """``restore_pytree`` of a specific checkpoint step (None = latest) —
        the re-deploy path when a revoked trial must resume from the snapshot
        that actually fit the notice deadline, not the newest one.  Returns
        ``(tree, step)`` and keeps the manifest's ``extra`` in
        ``restored_extra``: the benchmark's fault tests wrap this method and
        unpack two values."""
        tree, step, self.restored_extra = restore_pytree(
            self.store, self.prefix, like, step=step, shardings=shardings)
        return tree, step

    def _gc(self):
        all_steps = steps(self.store, self.prefix)
        for s in all_steps[: -self.keep_n] if self.keep_n else []:
            base = f"{self.prefix}/step_{s:08d}"
            # delete manifest first so the checkpoint is atomically invalidated
            self.store.delete(f"{base}/{MANIFEST}")
            for key in list(self.store.list(base + "/")):
                self.store.delete(key)
