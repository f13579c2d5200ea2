"""Quickstart: SpotTune end-to-end in simulation, in under a minute on CPU.

Runs the paper's full loop on one workload (16 HP settings) through the
pluggable tuner API:
  synthetic spot market -> cost-aware provisioning (Eq. 2) -> policy-free
  execution engine with revocation/checkpoint/refund -> SpotTuneScheduler
  (EarlyCurve early shutdown at theta=0.7, top-3 continuation) -> comparison
  against the two single-spot baselines -> the same engine re-run under an
  ASHA scheduler to show the policy is swappable.

    PYTHONPATH=src python examples/quickstart.py
"""

from repro.core.market import SpotMarket
from repro.core.orchestrator import run_single_spot_baseline
from repro.core.revpred import OracleRevPred
from repro.core.trial import WORKLOADS, SimTrialBackend, make_trials
from repro.launch.compile_cache import enable_compile_cache
from repro.tuner import (ASHAScheduler, GridSearcher, SpotTuneScheduler,
                         Tuner, build_engine)


def fresh_engine(seed_market: int = 3, seed: int = 0):
    market = SpotMarket(days=12, seed=seed_market)
    backend = SimTrialBackend(market.pool)
    return build_engine(market, backend, OracleRevPred(market), seed=seed)


def main():
    enable_compile_cache()
    workload = WORKLOADS[0]  # LoR benchmark (Table II analogue)
    print(f"workload={workload.name}: {len(workload.hp_grid())} HP settings, "
          f"max_trial_steps={workload.max_trial_steps}")

    engine = fresh_engine()
    tuner = Tuner(engine, SpotTuneScheduler(theta=0.7, mcnt=3),
                  GridSearcher(workload))
    res = tuner.run()
    print(f"\nSpotTune(theta=0.7):")
    print(f"  cost=${res.cost:.2f}  (+${res.refunded:.2f} refunded back)")
    print(f"  JCT={res.jct / 3600:.2f} h")
    print(f"  free steps (refunded allocations): {res.free_frac:.1%}")
    print(f"  checkpoint+restore overhead: {res.ckpt_frac:.1%} of JCT")
    print(f"  predicted best: {res.predicted_rank[0]}  true best: {res.true_rank[0]}")
    print(f"  top-3 contains true best: {res.top3_contains_best}")

    backend = engine.backend
    for label, pick in (("cheapest", min(engine.market.pool, key=lambda i: i.od_price)),
                        ("fastest", max(engine.market.pool, key=lambda i: i.chips))):
        m = SpotMarket(days=12, seed=3)
        r = run_single_spot_baseline(m, backend, make_trials(workload), pick)
        print(f"\nSingle-Spot ({label}, {pick.name}): cost=${r.cost:.2f} "
              f"JCT={r.jct / 3600:.2f} h  "
              f"PCR ratio vs SpotTune: {r.pcr() / res.pcr():.2f}x")

    # same engine mechanics, different policy: asynchronous successive halving
    asha = Tuner(fresh_engine(), ASHAScheduler(eta=2),
                 GridSearcher(workload)).run()
    print(f"\nASHA(eta=2) on the same engine: cost=${asha.cost:.2f} "
          f"JCT={asha.jct / 3600:.2f} h  best={asha.predicted_rank[0]}  "
          f"(grid ran {len([s for s in asha.per_trial_steps.values() if s >= workload.max_trial_steps])} "
          f"trials to full budget)")


if __name__ == "__main__":
    main()
