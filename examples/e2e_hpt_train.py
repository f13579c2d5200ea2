"""End-to-end driver: REAL JAX training under the SpotTune loop.

Hyper-parameter-tunes a reduced seed config (qwen1.5-0.5b by default) with
ACTUAL train steps on this machine, through the same engine/policy stack
the simulation uses — ``ScenarioSpec(backend="training")`` swaps the
synthetic ``SimTrialBackend`` for ``repro.backends.training``:

  * each trial is a ``repro.launch.train.Trainer`` (real forward/backward);
    ``SearchSpace`` configs bind to real knobs via ``TrainingBinding``
    (lr -> AdamW peak LR, dr/ds -> exponential decay, bs -> batch);
  * the simulated spot market supplies instance choices, revocations with
    the 2-minute notice, first-hour refunds, and billing; per-instance step
    time comes from the HLO/roofline cost model of the compiled train step;
  * on revocation the engine checkpoints through ``repro.checkpoint`` into
    a bandwidth-modelled object store (gated by ``fits_deadline``) and the
    next deploy restores the real optimizer state (elastic restart — the
    paper's core mechanism);
  * the search policy is any registered scheduler; the default is the
    paper's ``SpotTuneScheduler`` with EarlyCurve final-loss prediction
    fitted on the real validation-loss stream.

    PYTHONPATH=src python examples/e2e_hpt_train.py                 # ~1 min
    PYTHONPATH=src python examples/e2e_hpt_train.py --arch mamba2-130m
    PYTHONPATH=src python examples/e2e_hpt_train.py --scheduler pbt
"""

import argparse
import time

from repro.backends.training import TRAINING_ARCHS
from repro.launch.compile_cache import enable_compile_cache
from repro.sweep.runner import SweepRunner
from repro.sweep.spec import ScenarioSpec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=TRAINING_ARCHS)
    ap.add_argument("--scheduler", default="spottune")
    ap.add_argument("--theta", type=float, default=0.7)
    ap.add_argument("--market-seed", type=int, default=0)
    ap.add_argument("--days", type=float, default=2.0)
    args = ap.parse_args()
    enable_compile_cache()

    spec = ScenarioSpec(workload=args.arch, market_seed=args.market_seed,
                        scheduler=args.scheduler, theta=args.theta,
                        backend="training", days=args.days)
    spec.validate()
    print(f"arch={args.arch} scheduler={args.scheduler} theta={args.theta} "
          f"market_seed={args.market_seed}")

    t0 = time.time()
    tuner = SweepRunner().prepare([spec])[0]
    backend = tuner.engine.backend
    res = tuner.run()
    wall = time.time() - t0

    print(f"\nbest (EarlyCurve-predicted): {res.predicted_rank[0]}  "
          f"true best: {res.true_rank[0]}  top-1 correct: {res.top1_correct}")
    print(f"virtual cost=${res.cost:.2f} (refunded ${res.refunded:.2f}), "
          f"JCT={res.jct/3600:.1f} h, redeployments={res.redeployments}")
    print(f"real checkpoints: {backend.snapshots} snapshots, "
          f"{backend.restores} restores "
          f"({backend.store.inner.bytes_written/1e6:.1f} MB written, "
          f"simulated transfer {backend.store.simulated_time:.1f}s)")
    for v in sorted(tuner.engine.views(), key=lambda v: v.key):
        host = backend.host_step_time(v.spec)
        last = v.metrics_vals[-1] if v.metrics_vals else float("nan")
        print(f"  {v.key}: steps={v.steps:.0f} loss={last:.4f} "
              f"host {host*1e3:.0f} ms/step")
    print(f"wall time {wall:.1f}s")


if __name__ == "__main__":
    main()
