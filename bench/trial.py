"""One trial of the system under test, driven as SpotTune drives it.

The trial's runtime is the program's own ``repro.launch.train.Trainer``
(with ``repro.checkpoint.CheckpointManager`` for the revoked traffic): the
benchmark builds it, hands it the weights and the token feed it made from
the seed, and calls ``run_steps(1)`` step after step.  A revocation notice
is what a SpotTune trial gets when its spot instance is taken: the trainer
saves (``Trainer.save(blocking=True)``), is deleted with its instance, and
a new ``Trainer`` built with the same arguments restores and trains on.

Set-up drives the trial through its first ``CHECKED_STEPS`` steps through
that same call and feed, and reads what the reference has to match: each
step's loss, the first gradient from AdamW's first moment after one step,
and the float32 weights' change after the steps.  In the revoked traffic a
revocation comes after the first step, so the restored state and the
training that continues from it are inside the comparison.

The resume traffic runs no train step: its trial holds a whole state made
from the seed in the runtime's own layout (``seed_whole_state``), and each
revocation (``resume``) ends when the restored state is on the device.  What
it produces is that state, read back against the seed's bits.
"""

from __future__ import annotations

import contextlib
import gc
import math
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import leaf_norms, seed_key

CHECKED_STEPS = 3


class Spans:
    """Host-clock spans around the benchmark's calls into the program; with
    the profiler on, each is also a span of its trace."""

    def __init__(self):
        self.items: list = []

    @contextlib.contextmanager
    def __call__(self, name: str, step: Optional[int] = None):
        ann = (jax.profiler.StepTraceAnnotation(name, step_num=step)
               if step is not None else jax.profiler.TraceAnnotation(name))
        t0 = time.perf_counter()
        with ann:
            yield
        self.items.append((name, t0, time.perf_counter()))

    def durations(self, name: str, since: float = -math.inf) -> list:
        return [t1 - t0 for n, t0, t1 in self.items if n == name and t0 >= since]


class MemoryStore:
    """The object store a snapshot goes to, held in host memory.

    A revoked trial's snapshot leaves its instance for a remote object store;
    this stand-in keeps the bytes in the benchmark's process, so a run
    measures the program's own path (device to host, serialisation, the
    store protocol, and back) and writes nothing to the machine's disk.
    """

    def __init__(self):
        self._blobs: dict = {}

    def put(self, key: str, data: bytes) -> None:
        self._blobs[key] = bytes(data)

    def get(self, key: str) -> bytes:
        return self._blobs[key]

    def delete(self, key: str) -> None:
        self._blobs.pop(key, None)

    def list(self, prefix: str = ""):
        return sorted(k for k in self._blobs if k.startswith(prefix))


def _checksum_leaf(x):
    """Position-weighted sum of a leaf's bits, mod 2**32: equal states give
    equal sums; a changed, dropped or moved value almost never does."""
    width = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
    bits = jax.lax.bitcast_convert_type(x, width).astype(jnp.uint32).reshape(-1)
    weight = (jnp.arange(bits.size, dtype=jnp.uint32) % jnp.uint32(65521)) + 1
    return jnp.sum(bits * weight, dtype=jnp.uint32)


@jax.jit
def state_checksums(state):
    return jnp.stack([_checksum_leaf(x) for x in jax.tree.leaves(state)])


def host_checksums(tree) -> np.ndarray:
    """``state_checksums`` of a host copy, computed in numpy."""
    out = []
    for x in jax.tree.leaves(tree):
        x = np.ascontiguousarray(x)
        width = {1: np.uint8, 2: np.uint16, 4: np.uint32}[x.dtype.itemsize]
        bits = x.view(width).reshape(-1).astype(np.uint32)
        weight = (np.arange(bits.size, dtype=np.uint32) % np.uint32(65521)) + np.uint32(1)
        out.append(np.sum(bits * weight, dtype=np.uint32))
    return np.asarray(out, np.uint32)


def _same_bits(a, b) -> bool:
    a, b = np.ascontiguousarray(a), np.ascontiguousarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and a.tobytes() == b.tobytes()


@jax.jit
def _change_norm(master, initial):
    return jnp.sqrt(jnp.sum(jnp.square(master - initial.astype(jnp.float32))))


def _seeded_leaf(key, like):
    """One leaf of a trial's state, from the seed: normal(0, 0.02) in the
    leaf's own float type, or an integer counter in [1, 2**20)."""
    if jnp.issubdtype(like.dtype, jnp.floating):
        return (0.02 * jax.random.normal(key, like.shape, jnp.float32)).astype(like.dtype)
    return jax.random.randint(key, like.shape, 1, 2 ** 20, like.dtype)


def seeded_state(like, key):
    """Every leaf of a state shaped as ``like`` from ``key``: float32 master
    weights and moments full of float32 bits, as a trained state's are."""
    leaves, tree = jax.tree.flatten(like)
    return tree.unflatten([_seeded_leaf(jax.random.fold_in(key, i), x)
                           for i, x in enumerate(leaves)])


class Trial:
    """The trial runtime under test, with the benchmark's weights and feed.

    With ``ref`` the trainer trains from the reference's initial weights;
    with ``ref=None`` it holds the state that ``seed_whole_state`` makes."""

    def __init__(self, cfg, conf: dict, ref, feed, seed: int, store=None):
        self.cfg, self.conf, self.ref, self.feed, self.seed = cfg, conf, ref, feed, seed
        self.store = store
        self.spans = Spans()
        self.trainer = self._new_trainer()
        if ref is not None:
            self._set_weights(jax.jit(lambda key: ref.init_params(conf, key)), seed_key(seed))
        else:
            self.seed_whole_state()
        self.losses: list = []
        self.resumes: list = []          # (notice, resumed) host times
        self.checksums: list = []        # (saved, restored) per revocation
        self.restored_steps: list = []   # (saved, restored) step counters

    # ------------------------------------------------------------ runtime
    def _new_trainer(self):
        from repro.checkpoint import CheckpointManager
        from repro.launch.train import Trainer

        ckpt = (CheckpointManager(self.store, "trial", save_interval_steps=2 ** 62,
                                  keep_n=1) if self.store is not None else None)
        tr = Trainer(self.cfg, batch=self.conf["batch"], seq=self.conf["seq"],
                     lr=self.conf["optimizer"]["lr"], seed=self.seed, ckpt=ckpt,
                     val_every=1)
        if self.feed is not None:
            tr.data = self.feed
        return tr

    def seed_whole_state(self):
        """Replace the trainer's random state by one made from the seed in
        one call on the device (after that state is freed), and keep a host
        copy of its bits: what every restore has to give back."""
        tr = self.trainer
        like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tr.state)
        tr.state = None
        tr.state = jax.jit(lambda key: seeded_state(like, key))(seed_key(self.seed))
        tr.step = int(self.seed % 100_000) + 1
        self.initial = jax.device_get(tr.state)

    def _set_weights(self, make, key):
        """Replace the trainer's own random state by the benchmark's weights
        (made on the device in one call, after that state is freed) and a
        fresh AdamW state for them."""
        tr = self.trainer
        want = jax.tree.map(lambda x: (x.shape, jnp.dtype(x.dtype)), tr.state["params"])
        tr.state = None
        params = make(key)
        got = jax.tree.map(lambda x: (x.shape, jnp.dtype(x.dtype)), params)
        if want != got:
            raise ValueError("the reference's parameter layout differs from the "
                             f"trial runtime's:\n{got}\nvs\n{want}")
        # the weights' change is read against these very bits: made again
        # inside another compiled program on the TPU, most bfloat16 weights
        # came out an ulp away, which outweighs three AdamW steps (PERF.md)
        self.initial = jax.device_get(params)
        tr.state = {"params": params, "opt": tr.optimizer.init(params)}

    def step(self):
        """One ``run_steps(1)``; returns its loss."""
        tr = self.trainer
        with self.spans("train_step", step=tr.step):
            points = tr.run_steps(1)
        loss = points[-1][1]
        self.losses.append(loss)
        return loss

    def _snapshot_and_restore(self):
        """Notice, snapshot, instance gone, new trainer, restore; returns
        the notice's host time."""
        notice = time.perf_counter()
        with self.spans("snapshot"):
            self.trainer.save(blocking=True)
        saved, saved_step = state_checksums(self.trainer.state), self.trainer.step
        self.trainer = None
        gc.collect()
        with self.spans("trainer_init"):
            self.trainer = self._new_trainer()
        with self.spans("restore"):
            self.trainer.restore()
            jax.block_until_ready(self.trainer.state)
        self.checksums.append((saved, state_checksums(self.trainer.state)))
        self.restored_steps.append((saved_step, self.trainer.step))
        return notice

    def revoke(self):
        """Notice, snapshot, instance gone, new trainer, restore, first step."""
        notice = self._snapshot_and_restore()
        self.step()
        self.resumes.append((notice, time.perf_counter()))

    def resume(self):
        """A revocation with no training after it: it ends with the restored
        state on the device.  Each notice comes at a step of its own, as in a
        trial that trains between notices, so each snapshot is a new
        checkpoint and the store drops the one before it."""
        self.trainer.step += 1
        notice = self._snapshot_and_restore()
        self.resumes.append((notice, time.perf_counter()))

    # ----------------------------------------------------------- readings
    def checked_steps(self, revoke_after_first: bool) -> dict:
        """The first steps, with the readings the reference must match."""
        b1 = self.conf["optimizer"]["b1"]
        self.step()
        grad = jax.jit(lambda m: leaf_norms(m) / (1 - b1))(self.trainer.state["opt"]["m"])
        if revoke_after_first:
            self.revoke()
        while len(self.losses) < CHECKED_STEPS:
            self.step()
        master = jax.tree.leaves(self.trainer.state["opt"]["master"])
        change = [float(_change_norm(m, x))   # a leaf at a time: no second copy
                  for m, x in zip(master, jax.tree.leaves(self.initial))]
        self.initial = None
        return {"losses": list(self.losses), "grad_norms": np.asarray(grad),
                "change_norms": np.asarray(change)}

    def restore_mismatches(self) -> int:
        """Leaves whose restored bits differ from the saved ones, summed
        over every revocation."""
        return int(sum(int(np.sum(np.asarray(a) != np.asarray(b)))
                       for a, b in self.checksums))

    def resume_mismatches(self) -> dict:
        """The resume traffic's answers against the seed's state, over every
        revocation of the run: the leaves of each restore whose checksum
        differs from the seed's (summed in numpy over the host copy, apart
        from the device), the leaves of the last restore whose bits differ,
        read back whole, and the restores whose step counter is not the one
        saved."""
        want = host_checksums(self.initial)
        sums = sum(int(np.sum(np.asarray(r) != want)) for _, r in self.checksums)
        last = jax.device_get(self.trainer.state)
        bits = sum(int(not _same_bits(a, b)) for a, b in
                   zip(jax.tree.leaves(last), jax.tree.leaves(self.initial)))
        steps = sum(int(a != b) for a, b in self.restored_steps)
        return {"checksums": sums, "last_state": bits, "steps": steps}

    def close(self):
        self.trainer = None
        gc.collect()
