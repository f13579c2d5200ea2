"""A run with the timed path broken underneath reads ``correct`` false.

Each test skips the look for a chip and drives the rest of a run
(``run.run_cell``) on the CPU at tiny sizes, with one fault planted in the
program where the fault would be produced:

- a train step that returns its state unchanged;
- a train step that takes the mean over half of its batch;
- a restore that keeps the resumed trainer's fresh state (revoked and
  resume traffic);
- a restore that gives back half of the leaves and zeros in place of the
  others, and one that alters one value of the state it restores
  (resume traffic, whose answer is the restored state).

One chip has no exchange between chips to leave out, and a training trial
produces no token or answer of its own (its batches are the benchmark's).  The program's own
cross-entropy has a wrong gradient (PERF.md, Open questions), so every test
here runs on the corrected loss of ``bench_testlib``: the sound run then
reads true, and what turns it false is the planted fault alone.  The tiny
sizes' limits are their own, read from the corrected program on six seeds
(largest: loss_gap 0.0031, grad_gap 0.0057, update_gap 0.0159) and from the
half-batch fault (smallest: 0.0069, 0.026, 0.065).
"""

import jax
import pytest

import bench_testlib as tl

from bench.harness import Bench
from bench.run import run_cell

LIMITS = {"loss_gap": 0.006, "grad_gap": 0.015, "update_gap": 0.04, "restore_mismatch": 0}
SEED = 2**31 + 5


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """The tiny root, with a compile cache of its own for this module's runs
    (each run builds its trainer and reference afresh, as a process would)."""
    from jax.experimental.compilation_cache import compilation_cache

    cache = tmp_path_factory.mktemp("jax_cache")
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compilation_cache.reset_cache()
    yield Bench(tl.tiny_root(tmp_path_factory.mktemp("tiny"), LIMITS))
    jax.config.update("jax_compilation_cache_dir", before[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before[1])
    compilation_cache.reset_cache()


@pytest.fixture(autouse=True)
def sound_loss(monkeypatch):
    from repro.models import model

    monkeypatch.setattr(model.Model, "loss", tl.corrected_loss)


def run(bench, cell):
    result, _ = run_cell(bench, cell, SEED, 0.2, False, tl.V5E)
    return result


def _broken_step(monkeypatch, wrap):
    from repro.launch import train

    make = train.make_train_step
    monkeypatch.setattr(train, "make_train_step",
                        lambda *a, **k: wrap(make(*a, **k)))


@pytest.mark.parametrize("cell", ["tiny.qwen.steady", "tiny.mamba2.revoked",
                                  "tiny.mamba2.resume"])
def test_sound_run_is_correct(bench, cell):
    result = run(bench, cell)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0


@pytest.mark.parametrize("cell", ["tiny.qwen.steady", "tiny.mamba2.revoked"])
def test_state_left_unchanged(bench, cell, monkeypatch):
    def wrap(step):
        def unchanged(state, batch):
            return state, step(state, batch)[1]
        return unchanged

    _broken_step(monkeypatch, wrap)
    result = run(bench, cell)
    assert not result["correct"]
    assert result["checks"]["update_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("cell", ["tiny.qwen.steady", "tiny.mamba2.revoked"])
def test_half_the_batch(bench, cell, monkeypatch):
    def wrap(step):
        def half(state, batch):
            rows = batch["tokens"].shape[0] // 2
            return step(state, {k: v[:rows] for k, v in batch.items()})
        return half

    _broken_step(monkeypatch, wrap)
    assert not run(bench, cell)["correct"]


@pytest.mark.parametrize("cell", ["tiny.mamba2.revoked", "tiny.mamba2.resume"])
def test_restore_keeps_the_fresh_state(bench, cell, monkeypatch):
    from repro.launch import train

    monkeypatch.setattr(train.Trainer, "restore", lambda self, *a, **k: self.step)
    result = run(bench, cell)
    assert not result["correct"]
    assert result["checks"]["restore_mismatch"]["value"] > 0


def _broken_restore(monkeypatch, alter):
    """``CheckpointManager.restore`` whose restored leaves pass through
    ``alter(leaves, fresh)``, ``fresh`` being the leaves it would replace."""
    from repro.checkpoint import checkpointer

    own = checkpointer.CheckpointManager.restore

    def restore(self, like, *a, **k):
        state, step = own(self, like, *a, **k)
        leaves, tree = jax.tree.flatten(state)
        fresh = [jax.numpy.zeros(x.shape, x.dtype) for x in leaves]
        return tree.unflatten(alter(leaves, fresh)), step

    monkeypatch.setattr(checkpointer.CheckpointManager, "restore", restore)


def test_restore_leaves_out_half_the_leaves(bench, monkeypatch):
    _broken_restore(monkeypatch, lambda got, fresh: [g if i % 2 else f for i, (g, f)
                                                     in enumerate(zip(got, fresh))])
    result = run(bench, "tiny.mamba2.resume")
    assert not result["correct"]
    assert result["checks"]["restore_mismatch"]["value"] > 0


def test_restore_alters_one_value(bench, monkeypatch):
    def alter(got, fresh):
        x = got[-1]
        return got[:-1] + [x.reshape(-1).at[0].add(1).reshape(x.shape)]

    _broken_restore(monkeypatch, alter)
    result = run(bench, "tiny.mamba2.resume")
    assert not result["correct"]
    assert result["checks"]["restore_mismatch"]["value"] > 0
