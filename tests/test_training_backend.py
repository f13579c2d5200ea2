"""Real-training backend: protocol conformance, HP binding, checkpoint
lifecycle (deadline gate, cross-mesh restore, stream continuation), donor
inheritance (PBT exploit / TrimTuner warm start), the registry JSON
contract, and the full SpotTune loop on real trials."""

import json
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.backends import BACKENDS, TrialBackend, make_backend
from repro.backends.training import (TRAINING_BINDINGS, TRAINING_WORKLOADS,
                                     TrainingBinding, TrainingTrialBackend)
from repro.checkpoint import CheckpointManager
from repro.core.market import DEFAULT_POOL
from repro.core.trial import SimTrialBackend, TrialSpec
from repro.launch.train import Trainer
from repro.sweep.runner import SweepRunner
from repro.sweep.spec import ScenarioSpec
from repro.tuner.policies.pbt import PBTScheduler, PBTSearcher


@pytest.fixture(scope="module")
def qwen():
    """Shared backend + workload: trials/compiles amortize across tests."""
    w = TRAINING_WORKLOADS["qwen1.5-0.5b"]
    return TrainingTrialBackend(), w


def _leaves_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------- protocol


def test_protocol_conformance(qwen):
    be, w = qwen
    assert isinstance(be, TrialBackend)
    assert isinstance(SimTrialBackend(list(DEFAULT_POOL)), TrialBackend)
    # the sim keeps the base no-op snapshot/restore (curves carry no state);
    # the training backend overrides both — the engine's capability gate
    assert type(be).snapshot is not TrialBackend.snapshot
    assert type(be).restore is not TrialBackend.restore
    assert SimTrialBackend.snapshot is TrialBackend.snapshot
    assert SimTrialBackend.restore is TrialBackend.restore
    # default snapshot echoes the request — sim rollback accounting intact
    sim = SimTrialBackend(list(DEFAULT_POOL))
    t = TrialSpec(w, w.hp_grid()[0], 0)
    assert sim.snapshot(t, 123.0) == 123.0


def test_backend_registry_and_factory():
    assert set(BACKENDS) == {"sim", "training"}
    assert BACKENDS["sim"]["default"] and not BACKENDS["training"]["default"]
    assert isinstance(make_backend("sim"), SimTrialBackend)
    with pytest.raises(ValueError, match="unknown backend"):
        make_backend("bogus")


def test_binding_maps_hps():
    b = TrainingBinding(arch="qwen1.5-0.5b")
    kw = b.trainer_kwargs({"lr": 1e-3, "dr": 0.5, "ds": 16, "bs": 2},
                          val_every=4)
    assert kw["lr"] == 1e-3 and kw["batch"] == 2 and kw["val_every"] == 4
    assert callable(kw["lr_schedule"])          # decay declared -> schedule
    # dr >= 1.0 means constant LR: no schedule object
    kw2 = b.trainer_kwargs({"lr": 3e-3, "dr": 1.0, "ds": 16}, val_every=4)
    assert kw2["lr_schedule"] is None and kw2["batch"] == b.batch


def test_roofline_step_times(qwen):
    be, w = qwen
    t = TrialSpec(w, w.hp_grid()[0], 0)
    ref = next(i for i in DEFAULT_POOL if i.chips == be.ref_chips)
    assert be.base_step_time(t, ref) == pytest.approx(w.s0)
    # fewer chips -> slower; the jittered observations reuse the shared
    # tick stream bit-exactly (inherited protocol default)
    one = next(i for i in DEFAULT_POOL if i.chips == 1)
    assert be.base_step_time(t, one) > w.s0
    ticks = be.noisy_step_times(t, ref, 3, 5, 10.0)
    singles = [be.step_time(t, ref, noisy_t=k * 10.0) for k in (3, 4, 5)]
    assert list(ticks) == singles


# ------------------------------------------------------------ metric stream


def test_real_curve_matches_uninterrupted_trainer(qwen):
    be, w = qwen
    t = TrialSpec(w, w.hp_grid()[0], 0)
    stream = be.metric_range(t, 1, 4)                 # steps 4..16
    binding = be._binding(t)
    tr = Trainer(**binding.trainer_kwargs(t.hp, w.val_every))
    tr.run_steps(16)
    assert stream == tr.metrics_vals[:4]
    assert be.metric_at(t, w.val_every - 1) is None   # before first point
    # past-the-end queries clamp to the last point, like the sim
    assert be.metric_at(t, w.max_trial_steps * 10) == be.true_final(t)


def test_metric_stream_is_decreasing_on_average(qwen):
    be, w = qwen
    t = TrialSpec(w, w.hp_grid()[0], 0)
    vals = be.metric_range(t, 1, w.max_trial_steps // w.val_every)
    assert vals[-1] < vals[0]                         # it actually learns


@pytest.mark.parametrize("data_seed", [0, 1, 2])
def test_mamba2_multi_seed_losses_finite(data_seed):
    """Regression: the reduced mamba2 preset used to NaN within a handful
    of steps on data seed 0 (masked SSD decay overflowing exp in the
    backward pass — see repro.models.ssd), which was papered over by
    pinning the binding to seed 1.  The op is fixed and the pin removed;
    training must stay finite on every data seed."""
    from repro.configs.base import get_config
    from repro.data.pipeline import SyntheticLMDataset
    from repro.launch.train import init_state, make_train_step
    from repro.models.context import null_ctx
    from repro.models.model import Model
    from repro.optim.optimizers import adamw

    cfg = get_config("mamba2-130m", reduced=True)
    model = Model(cfg)
    opt = adamw(3e-3, keep_master=(cfg.opt_precision == "fp32"))
    state = init_state(model, opt, 0)
    ds = SyntheticLMDataset(cfg, 4, 32, seed=data_seed)
    step = jax.jit(make_train_step(model, opt, null_ctx(attn_chunk=32,
                                                        remat="none")))
    for i in range(12):                 # seed 0 used to explode at step 5
        state, metrics = step(state, ds.get_batch(i))
        assert np.isfinite(float(metrics["loss"])), \
            f"non-finite loss at step {i} (data seed {data_seed})"
    assert all(np.isfinite(np.asarray(x, np.float32)).all()
               for x in jax.tree.leaves(state["params"]))


def test_mamba2_binding_uses_default_data_seed():
    """The seed-1 workaround must stay gone now that the op is fixed."""
    assert TRAINING_BINDINGS[TRAINING_WORKLOADS["mamba2-130m"].name].seed == 0


# ------------------------------------------------------- checkpoint lifecycle


def test_snapshot_restore_cross_mesh_bit_identical(qwen):
    _, w = qwen
    dev = jax.devices()[1]
    be = TrainingTrialBackend(
        sharding_fn=lambda tmpl: jax.sharding.SingleDeviceSharding(dev))
    t = TrialSpec(w, w.hp_grid()[0], 0)
    assert be.snapshot(t, 8, deadline_s=120.0) == 8.0
    be.restore(t, 8)
    key, step, restored = be.last_restore
    assert (key, step) == (t.key, 8)
    run = be._run(t)
    # bit-identical full state — params AND optimizer moments — after the
    # elastic re-shard onto a different device than the writer's
    assert _leaves_equal(restored, be._host_state(run, 8))
    like = jax.tree.map(jax.numpy.asarray, run.state0)
    from repro.checkpoint.checkpointer import restore_pytree
    tree, got = restore_pytree(
        be.store, run.prefix, like, step=8,
        sharding_fn=lambda tmpl: jax.sharding.SingleDeviceSharding(dev))
    assert got == 8
    assert all(leaf.devices() == {dev} for leaf in jax.tree.leaves(tree))


def test_restored_stream_continues_exactly(qwen):
    be, w = qwen
    t = TrialSpec(w, w.hp_grid()[0], 0)
    be.snapshot(t, 8, deadline_s=120.0)
    run = be._run(t)
    binding = be._binding(t)
    mgr = CheckpointManager(be.store, run.prefix, save_interval_steps=10 ** 9,
                            keep_n=0)
    tr = Trainer(**binding.trainer_kwargs(t.hp, w.val_every), ckpt=mgr)
    assert tr.restore(step=8) == 8
    # manifest metadata rebuilt the stream up to the snapshot...
    assert tr.metrics_vals == be.metric_range(t, 1, 2)
    tr.run_steps(8)
    # ...and the continuation reproduces the uninterrupted stream exactly
    assert tr.metrics_vals == pytest.approx(be.metric_range(t, 1, 4),
                                            rel=1e-6)


def test_fits_deadline_gates_snapshot(qwen):
    _, w = qwen
    be = TrainingTrialBackend(bandwidth_bps=1e3)      # ~1 KB/s store
    t = TrialSpec(w, w.hp_grid()[0], 0)
    # the 120 s notice budget cannot move megabytes at 1 KB/s: no snapshot,
    # nothing durable -> the engine rolls the trial back to step 0
    assert be.snapshot(t, 8, deadline_s=120.0) == 0.0
    assert be.snapshot_skips == 1 and be.snapshots == 0
    # an earlier durable snapshot (taken under a feasible deadline) pins
    # later gated attempts to the old step instead of 0
    assert be.snapshot(t, 8, deadline_s=1e9) == 8.0
    assert be.snapshot(t, 16, deadline_s=120.0) == 8.0
    assert be.snapshot_skips == 2 and be.snapshots == 1


def test_engine_notice_budget_honored(qwen):
    """The engine passes cfg.notice_s as the snapshot deadline; with the
    default store the reduced config fits the 120 s window."""
    be, w = qwen
    t = TrialSpec(w, w.hp_grid()[0], 0)
    assert be.store.transfer_time(int(w.model_bytes)) < 120.0
    assert be.checkpoint_time(t, 999.0) == pytest.approx(
        be.store.transfer_time(int(w.model_bytes)))   # engine knob ignored


# --------------------------------------------------------- donor inheritance


def test_inherited_trial_starts_from_donor_state(qwen):
    be, w = qwen
    donor = TrialSpec(w, w.hp_grid()[0], 0)
    be.metric_at(donor, 8)                            # materialize donor run
    child = TrialSpec(w, w.hp_grid()[3], 3, inherit=(donor.key, 8))
    run = be._run(child)
    donor_state = be._host_state(be._run(donor), 8)
    assert _leaves_equal(run.state0, donor_state)     # params + opt moments
    # a non-inherited trial of the same config starts from a fresh init
    fresh = be._run(TrialSpec(w, w.hp_grid()[3], 3))
    assert not _leaves_equal(fresh.state0, donor_state)


def test_pbt_exploit_resumes_from_donor_checkpoint(qwen):
    be, w = qwen
    sched = PBTScheduler(population=4, seed=0)
    searcher = PBTSearcher(w, population=4, resample_prob=0.0, seed=0)
    searcher.bind_scheduler(sched)
    members = [searcher.suggest() for _ in range(4)]
    for m in members:
        sched.on_trial_added(m)
    # milestone results: member 0 best, member 3 worst
    m0 = sched.milestones[0]
    for rank, m in enumerate(members):
        sched._results[0][m.key] = 1.0 + rank
        sched._ms_idx[m.key] = 1
    donors = sched.exploit_donors()
    assert donors[0][0] == members[0].key and donors[0][2] == m0
    assert len(donors) == 3                           # bottom quartile cut
    repl = searcher.suggest()
    assert repl is not None and repl.inherit is not None
    dkey, dstep = repl.inherit
    assert dstep == m0 and dkey in {m.key for m in members[:3]}
    # the replacement's real run opens from the donor's checkpointed state
    donor_spec = next(m for m in members if m.key == dkey)
    be.metric_at(donor_spec, dstep)
    run = be._run(repl)
    assert _leaves_equal(run.state0,
                         be._host_state(be._run(donor_spec), dstep))


@pytest.mark.parametrize("path", ["inherit", "replayer"])
def test_assigned_trainer_never_builds_its_random_state(qwen, tmp_path, path):
    """The inherit path and the replayer assign a state to a new Trainer
    before anything reads it, so neither builds the random init."""
    from repro import telemetry

    be, w = qwen
    donor = TrialSpec(w, w.hp_grid()[0], 0)
    be.metric_at(donor, 8)                            # materialize donor run
    run = be._run(donor)
    child = TrialSpec(w, w.hp_grid()[1], 1, inherit=(donor.key, 8))
    if path == "inherit":
        be._runs.pop((child.key, child.inherit), None)  # the next _run builds it
        want = be._host_state(be._by_key[donor.key], 8)  # the donor it inherits from
    else:
        run.replayer = None                           # the next replay builds one
    telemetry.reset()
    with jax.profiler.trace(str(tmp_path / "trace")):
        if path == "inherit":
            got = be._run(child).state0
        else:
            got = be._host_state(run, 3)
    recs = telemetry.records()
    telemetry.reset()
    names = [r.name for r in recs]
    assert "trainer.init" in names and "trainer.init_state" not in names
    if path == "replayer":
        assert run.replayer is not None and run.replayer.step == 3
        want = be._host_state(run, 3)
    assert _leaves_equal(got, want)


def test_trimtuner_warm_start_declares_inherit():
    from repro.tuner.policies.trimtuner import TrimTunerSearcher

    w = TRAINING_WORKLOADS["qwen1.5-0.5b"]
    s = TrimTunerSearcher(w, initial=4, batch=2, seed=0)
    boot = [s.suggest() for _ in range(4)]
    assert all(b.inherit is None for b in boot)       # bootstrap: fresh

    class _View:
        def __init__(self, spec, metric, steps):
            self.spec = spec
            self.metrics_vals = [metric]
            self.steps = steps
            self.billed_cost = 1.0

    for j, b in enumerate(boot):
        s.on_trial_finished(_View(b, 5.0 + j, 21))
    donor_hp = boot[0].hp
    near = next(i for i, hp in enumerate(s.grid)
                if sum(hp[k] != donor_hp[k] for k in hp) == 1)
    far = next(i for i, hp in enumerate(s.grid)
               if sum(hp[k] != donor_hp[k] for k in hp) > 1)
    # one-dim-away candidates inherit the best donor at its observed
    # progress snapped down to the metric grid; distant ones start fresh
    assert s._warm_start(near) == (boot[0].key, 20)
    assert s._warm_start(far) is None
    assert s.suggest() is not None                    # refinement wave runs


# -------------------------------------------------- registry + spec contract


def test_registry_describe_json():
    from repro.tuner.registry import describe_json
    info = describe_json()
    assert set(info["backends"]) == {"sim", "training"}
    assert info["backends"]["training"]["spaces"] == ["grid"]
    assert "qwen1.5-0.5b" in info["backends"]["training"]["workloads"]
    assert info["searchers"]["pbt"]["supports_continuous"]
    assert not info["searchers"]["trimtuner"]["supports_continuous"]
    assert info["policy_defaults"]["pbt"]["searcher"] == "pbt"


def test_registry_json_cli():
    import os
    import pathlib
    env = dict(os.environ)
    env["PYTHONPATH"] = str(
        pathlib.Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.tuner.registry", "--json"],
        capture_output=True, text=True, check=True, env=env)
    info = json.loads(out.stdout)
    assert "backends" in info and "schedulers" in info


def test_spec_validation_rejects_bad_combos():
    ok = ScenarioSpec(workload="qwen1.5-0.5b", market_seed=0,
                      backend="training")
    ok.validate()
    with pytest.raises(ValueError, match="unknown backend"):
        ScenarioSpec(workload="LoR", market_seed=0,
                     backend="bogus").validate()
    with pytest.raises(ValueError, match="ground-truths spaces"):
        ScenarioSpec(workload="qwen1.5-0.5b", market_seed=0,
                     backend="training", space="continuous").validate()
    with pytest.raises(ValueError, match="binds workloads"):
        ScenarioSpec(workload="LoR", market_seed=0,
                     backend="training").validate()
    with pytest.raises(ValueError, match="unknown searcher"):
        ScenarioSpec(workload="LoR", market_seed=0,
                     searcher="bogus").validate()
    with pytest.raises(ValueError, match="finite spaces only"):
        ScenarioSpec(workload="LoR", market_seed=0, space="continuous",
                     searcher="grid").validate()
    # workload_obj mirrors the arch-name handling (train- prefix optional)
    assert (ScenarioSpec(workload="train-qwen1.5-0.5b", market_seed=0,
                         backend="training").workload_obj()
            is ok.workload_obj())
    with pytest.raises(ValueError, match="no training binding"):
        ScenarioSpec(workload="LoR", market_seed=0,
                     backend="training").workload_obj()


# ------------------------------------------------------------- full loop


def test_training_scenario_full_spottune_loop():
    """Acceptance: a backend="training" sweep runs the whole SpotTune loop —
    θ provisioning, real revocation checkpoint/restore through
    repro.checkpoint, EarlyCurve fit on the real loss stream — alongside a
    sim replica sharing the same runner."""
    sim = ScenarioSpec(workload="LoR", market_seed=0, days=2.0)
    train = ScenarioSpec(workload="qwen1.5-0.5b", market_seed=0,
                         backend="training", days=2.0)
    runner = SweepRunner()
    tuners = runner.prepare([sim, train])
    assert isinstance(tuners[0].engine.backend, SimTrialBackend)
    be = tuners[1].engine.backend
    assert isinstance(be, TrainingTrialBackend)
    res_sim = tuners[0].run()
    res = tuners[1].run()
    assert res_sim.steps_total > 0
    # full loop ran: trials moved, re-deploys happened, real checkpoints
    # were written and re-read through repro.checkpoint
    assert res.steps_total > 0 and res.redeployments > 0
    assert be.snapshots > 0 and be.restores > 0
    assert be.store.inner.bytes_written > 0
    # >= 1 forced revocation: the market refunds first-hour revocations only
    assert res.refunded > 0
    # EarlyCurve fitted the real loss stream into a full ranking
    grid = tuners[1].engine.views()
    assert len(res.predicted_rank) == len(list(grid)) == 8
    assert res.predicted_rank[0].startswith("train-qwen1.5-0.5b/")
