"""Event-driven fast path == legacy exact-tick path, across seeds/policies.

The equivalence contract (see repro.tuner.equivalence): billed and refunded
dollars, per-allocation billing records, trial finish times, per-trial metric
histories, and the full event log must match between
``EngineConfig(exact_ticks=False)`` (the boundary-jumping default) and
``exact_ticks=True`` (the verbatim Algorithm 1 SLEEP loop).  Step counters
are compared to a tight relative tolerance (fused vs per-tick summation).

Fixed-seed parametrizations always run; the hypothesis property widens the
seed space when the library is installed (tests/_hypothesis_compat.py lets it
degrade to a clean skip otherwise).
"""

import pytest
from _hypothesis_compat import given, settings, st

from repro.core.revpred import OracleRevPred
from repro.core.trial import WORKLOADS, continuous_variant
from repro.tuner import (AdaptiveSpotTuneScheduler, ASHAScheduler,
                         HyperbandScheduler, PBTScheduler, PBTSearcher,
                         TrimTunerGPSearcher, TrimTunerSearcher)
from repro.tuner.equivalence import compare_runs

LOR = WORKLOADS[0]


def _hyperband_kw():
    return dict(
        scheduler_factory=lambda: HyperbandScheduler(eta=2, num_brackets=3,
                                                     seed=0))


def _pbt_kw():
    return dict(
        scheduler_factory=lambda: PBTScheduler(population=8, seed=0),
        searcher_factory=lambda w: PBTSearcher(w, population=8, seed=0),
        initial_trials=8)


@pytest.mark.parametrize("market_seed", [1, 3, 7, 11, 23])
def test_fast_equals_exact_across_market_seeds(market_seed):
    diffs = compare_runs(LOR, market_seed=market_seed, days=8.0)
    assert not diffs, "\n".join(diffs)


@pytest.mark.parametrize("workload", WORKLOADS[1:4], ids=lambda w: w.name)
def test_fast_equals_exact_across_workloads(workload):
    diffs = compare_runs(workload, days=8.0, n_trials=8)
    assert not diffs, "\n".join(diffs)


def test_fast_equals_exact_with_oracle_revpred():
    """Oracle p(revoke) drives the engine into the refund-chasing regime —
    many revocations, rollbacks, and requeues to replay."""
    diffs = compare_runs(LOR, market_seed=3, days=8.0,
                         revpred_factory=lambda m: OracleRevPred(m))
    assert not diffs, "\n".join(diffs)


def test_fast_equals_exact_theta_one():
    """theta=1: no phase-2 promotions — pure run-to-completion engine."""
    diffs = compare_runs(LOR, theta=1.0, days=8.0, n_trials=6)
    assert not diffs, "\n".join(diffs)


def test_fast_equals_exact_asha_pause_promote():
    """ASHA exercises PAUSE decisions, async promotions, and idle resumes."""
    diffs = compare_runs(LOR, days=8.0,
                         scheduler_factory=lambda: ASHAScheduler(eta=2))
    assert not diffs, "\n".join(diffs)


@pytest.mark.parametrize("market_seed", [1, 3, 7, 11, 23])
def test_fast_equals_exact_hyperband_across_market_seeds(market_seed):
    """Hyperband routes events through per-bracket ASHA ladders; the
    fast path's rung previews must stay equivalent under every bracket."""
    diffs = compare_runs(LOR, market_seed=market_seed, days=8.0,
                         **_hyperband_kw())
    assert not diffs, "\n".join(diffs)


@pytest.mark.parametrize("workload", WORKLOADS[1:4], ids=lambda w: w.name)
def test_fast_equals_exact_hyperband_across_workloads(workload):
    diffs = compare_runs(workload, days=8.0, n_trials=8, **_hyperband_kw())
    assert not diffs, "\n".join(diffs)


@pytest.mark.parametrize("market_seed", [1, 3, 7, 11, 23])
def test_fast_equals_exact_pbt_across_market_seeds(market_seed):
    """PBT adds milestone PAUSEs, promotions of parked members, and
    idle-path exploit/explore replacements on top of the engine."""
    diffs = compare_runs(LOR, market_seed=market_seed, days=8.0, **_pbt_kw())
    assert not diffs, "\n".join(diffs)


@pytest.mark.parametrize("workload", WORKLOADS[1:4], ids=lambda w: w.name)
def test_fast_equals_exact_pbt_across_workloads(workload):
    diffs = compare_runs(workload, days=8.0, **_pbt_kw())
    assert not diffs, "\n".join(diffs)


def test_fast_equals_exact_trimtuner_bo():
    """Cost-aware BO feeds on per-trial billed cost; both paths must hand
    the searcher identical feedback and replay identical suggestions."""
    diffs = compare_runs(
        LOR, days=8.0,
        scheduler_factory=lambda: AdaptiveSpotTuneScheduler(theta=0.7,
                                                            mcnt=3, seed=0),
        searcher_factory=lambda w: TrimTunerSearcher(w, seed=0),
        initial_trials=6)
    assert not diffs, "\n".join(diffs)


def test_fast_equals_exact_trimtuner_gp_continuous_space():
    """The GP searcher proposes grid-free configs off the continuous
    variant (config-hash trial identity, interpolated ground truth); both
    engine paths must feed it identical cost/metric feedback and replay
    identical suggestion streams."""
    diffs = compare_runs(
        continuous_variant(LOR), days=8.0,
        scheduler_factory=lambda: AdaptiveSpotTuneScheduler(theta=0.7,
                                                            mcnt=3, seed=0),
        searcher_factory=lambda w: TrimTunerGPSearcher(w, seed=0),
        initial_trials=6)
    assert not diffs, "\n".join(diffs)


@pytest.mark.parametrize("market_seed", [3, 11])
def test_fast_equals_exact_hyperband_adaptive_brackets(market_seed):
    """Survival-reweighted bracket sampling admits trials in idle-time
    waves, folding rung state into later trial->bracket assignments; fast
    and exact paths must observe identical survival rates at each wave and
    assign identically."""
    diffs = compare_runs(
        LOR, market_seed=market_seed, days=8.0, initial_trials=6,
        scheduler_factory=lambda: HyperbandScheduler(
            eta=2, num_brackets=3, adaptive_brackets=True, seed=0))
    assert not diffs, "\n".join(diffs)


def test_fast_equals_exact_straggler_mode():
    """Straggler mitigation compares the perf matrix each tick; the fast
    path predicts the comparison's crossing tick by replaying the EWMA fold
    ahead (engine._straggler_boundary) instead of single-tick stepping, and
    must stay equivalent."""
    diffs = compare_runs(LOR, days=8.0, n_trials=4, theta=0.5,
                         straggler_factor=1.5)
    assert not diffs, "\n".join(diffs)


@pytest.mark.parametrize("factor", [1.05, 1.2, 3.0])
@pytest.mark.parametrize("market_seed", [3, 9])
def test_fast_equals_exact_straggler_boundary_sweep(factor, market_seed):
    """The straggler fast path across trigger-happy (1.05) through rare
    (3.0) factors, full grid, including the oracle refund-chasing regime."""
    diffs = compare_runs(LOR, days=8.0, n_trials=6, market_seed=market_seed,
                         straggler_factor=factor,
                         revpred_factory=lambda m: OracleRevPred(m))
    assert not diffs, "\n".join(diffs)


def test_straggler_fast_path_actually_jumps(monkeypatch):
    """Regression for the old single-tick fallback: in straggler mode the
    event-driven engine must visit far fewer ticks than the exact loop
    (it used to visit every one of them)."""
    from repro.tuner import engine as engine_mod
    from repro.tuner.equivalence import run_one

    calls = {"fast": 0, "exact": 0}
    orig = engine_mod.ExecutionEngine._tick

    def counting(self, runnable, exact):
        calls["exact" if exact else "fast"] += 1
        return orig(self, runnable, exact)

    monkeypatch.setattr(engine_mod.ExecutionEngine, "_tick", counting)
    fast_eng, _ = run_one(LOR, exact_ticks=False, days=8.0, n_trials=4,
                          theta=0.5, straggler_factor=1.5)
    exact_eng, _ = run_one(LOR, exact_ticks=True, days=8.0, n_trials=4,
                           theta=0.5, straggler_factor=1.5)
    assert fast_eng.t == exact_eng.t
    assert calls["fast"] < calls["exact"] / 5


@given(st.integers(0, 10_000), st.integers(0, 3))
@settings(max_examples=10, deadline=None)
def test_fast_equals_exact_property(market_seed, engine_seed):
    diffs = compare_runs(LOR, market_seed=market_seed, seed=engine_seed,
                         days=6.0, n_trials=6)
    assert not diffs, "\n".join(diffs)


# ------------------------------------------------- SoA sweep vs per-replica
# The structure-of-arrays stepper (repro.sweep.soa) must be bit-exact
# against the per-replica generator path — billing, refunds, metric
# histories, redeployments, and the full event log (compare_sweep_modes
# diffs every replica pairwise with compare_engines' contract).

SWEEP_POLICIES = ("spottune", "asha", "hyperband", "pbt", "adaptive")
SWEEP_SEEDS = (1, 3, 7, 11, 23)


@pytest.mark.parametrize("policy", SWEEP_POLICIES)
def test_soa_equals_per_replica_policy_grid(policy):
    """Per policy, a 4-workload x 5-market-seed grid (20 replicas) through
    the SoA stepper and the generator round-robin path — together the five
    parametrizations cover the full 5x4x5 policy/workload/seed cube."""
    from repro.sweep import scenario_grid
    from repro.tuner.equivalence import compare_sweep_modes

    names = [w.name for w in WORKLOADS[:4]]
    specs = scenario_grid(names, SWEEP_SEEDS, revpred="oracle", theta=0.7,
                          days=8.0, scheduler=policy)
    diffs = compare_sweep_modes(specs)
    assert not diffs, "\n".join(diffs[:12])


# ------------------------------------------------------ Δt deploy batching

@pytest.mark.parametrize("window", [60.0, 600.0])
def test_soa_equals_per_replica_deploy_window(window):
    """Δt > 0 gates deploys into shared flush ticks — a different event
    schedule, but one the SoA stepper must still replay bit-exactly."""
    from repro.sweep import scenario_grid
    from repro.tuner.equivalence import compare_sweep_modes

    specs = scenario_grid(["LoR", "SVM"], [3, 11], revpred="oracle",
                          theta=0.7, days=8.0, deploy_window_s=window)
    diffs = compare_sweep_modes(specs)
    assert not diffs, "\n".join(diffs[:12])


def test_deploy_window_zero_matches_legacy():
    """Δt = 0 must be invariant: a grid with the window set to zero
    explicitly produces the byte-identical outcome of the same grid with
    the field left at its default (the pre-window engine behavior)."""
    from repro.sweep import SweepRunner, clear_shared_caches, scenario_grid

    base = scenario_grid(["LoR", "SVM"], [3, 11], revpred="oracle",
                         theta=0.7, days=8.0)
    gated = scenario_grid(["LoR", "SVM"], [3, 11], revpred="oracle",
                          theta=0.7, days=8.0, deploy_window_s=0.0)
    clear_shared_caches()
    res_a = SweepRunner().run(base)
    clear_shared_caches()
    res_b = SweepRunner().run(gated)
    for ra, rb in zip(res_a.replicas, res_b.replicas):
        assert ra.result == rb.result
        assert ra.metrics == rb.metrics


@pytest.mark.parametrize("window", [60.0, 600.0])
def test_fast_equals_exact_deploy_window(window):
    """Engine-level Δt: the boundary-jumping path must arm/flush the same
    deploy-window ticks the exact SLEEP loop visits."""
    diffs = compare_runs(LOR, days=8.0, n_trials=6, deploy_window_s=window,
                         revpred_factory=lambda m: OracleRevPred(m))
    assert not diffs, "\n".join(diffs)
