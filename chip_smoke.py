"""Smoke run of the tuner's device path on one TPU chip.

    python chip_smoke.py

One process drives the system's main entry points on the chip, phase by
phase, and checks each phase's results:

1. device        the platform is a TPU, else exit non-zero (never the CPU);
2. revpred       RevPred and Tributary predictors trained on a seeded spot
                 market at the sweep's own sizes (gradients through the
                 Pallas LSTM kernel), then every probability of the pool
                 forward compared with a float32 jnp reference on the CPU;
3. study         two tenants' SpotTune studies with trained RevPred through
                 ``TuningService``, billing conservation per replica, and a
                 second identical submission that must reproduce the first;
4. train_full    qwen1.5-0.5b at its published widths through ``Trainer``:
                 finite, falling losses, a checkpoint restored into a fresh
                 trainer that continues bit for bit;
5. train_trials  the training-backend tuning scenario through
                 ``SweepRunner`` with real snapshots and restores.

Any failure prints the phase and the error and exits non-zero.  The last
line of a passing run is one JSON object naming the device.
"""

from __future__ import annotations

import gc
import json
import math
import pathlib
import sys
import tempfile
import time
import traceback

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

# RevPred/Tributary probabilities, chip vs the CPU float32 reference.  At
# default precision the chip runs float32 matmuls as bfloat16 MXU passes
# (8-bit mantissa, ~2e-3 relative per operand); through 3 LSTM layers x
# 59-60 steps and Eq. 3's odds rescaling that should move a probability by
# well under 1e-2, while a wrong gate order or weight layout moves it by
# more than 0.1.
PROB_TOL = 2e-2
# full-width step: the largest batch x seq whose compiled step the v5e
# compiler puts under ~14 GB with >= 1024 tokens (3x512: 13.96 GB;
# 1x1536: 14.90 GB; 2x1024: 15.72 GB)
FULL_BATCH, FULL_SEQ = 3, 512


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# ------------------------------------------------------------------ device
def phase_device():
    import jax

    devs = jax.devices()
    log("device", f"jax.devices() = {devs}")
    if devs[0].platform != "tpu":
        raise RuntimeError(
            f"no TPU: JAX found platform {devs[0].platform!r}; this smoke "
            "run measures the chip and does not fall back to the CPU")
    return devs[0]


# ----------------------------------------------------------------- revpred
def _ref_lstm_stack(layers, seq):
    """Plain float32 jnp LSTM stack: (B, T, I) -> top-layer final h."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.ref import lstm_cell_ref

    h = None
    for lp in layers:
        zeros = jnp.zeros((seq.shape[0], lp["w_hh"].shape[0]), jnp.float32)

        def step(carry, x_t, lp=lp):
            carry = lstm_cell_ref(x_t, *carry, lp["w_ih"], lp["w_hh"], lp["b"])
            return carry, carry[0]

        (h, _), hs = jax.lax.scan(step, (zeros, zeros), seq.transpose(1, 0, 2))
        seq = hs.transpose(1, 0, 2)
    return h


def ref_logits(kind: str, params, hist, present):
    """Reference forward of RevPred (split input) or Tributary (all through
    the LSTM), written from the paper's description, not the model code."""
    import jax
    import jax.numpy as jnp

    if kind == "revpred":
        pe = present
        for k in ("fc1", "fc2", "fc3"):
            pe = jax.nn.relu(pe @ params[k]["w"] + params[k]["b"])
        z = jnp.concatenate([_ref_lstm_stack(params["lstm"], hist), pe], -1)
    else:
        pad = jnp.zeros(hist.shape[:2] + (1,), jnp.float32)
        seq = jnp.concatenate(
            [jnp.concatenate([hist, pad], -1), present[:, None]], 1)
        z = _ref_lstm_stack(params["lstm"], seq)
    return (z @ params["head"]["w"] + params["head"]["b"])[:, 0]


def _eq3(p: float, pos_frac: float) -> float:
    """Paper Eq. 3 odds de-skew: P/(1-P) = P^ phi- / ((1-P^) phi+)."""
    odds = (p * max(1.0 - pos_frac, 1e-6)) / max(
        (1.0 - p) * max(pos_frac, 1e-6), 1e-9)
    return odds / (1.0 + odds)


def _sample(feats, minute: int, max_price: float, od_price: float):
    """The (hist, present) inputs for one market at one minute."""
    import numpy as np

    hist = feats[minute - 59:minute]
    present = np.append(feats[minute], max_price / od_price)
    return hist[None], present[None].astype(np.float32)


def phase_revpred(days: float = 12.0, train_minutes: int = 2880,
                  epochs: int = 4, stride: int = 5, seed: int = 7):
    import jax
    import numpy as np

    from repro.core import revpred as rp
    from repro.core.market import MINUTE, DEFAULT_POOL, SpotMarket

    cpu = jax.devices("cpu")[0]
    market = SpotMarket(pool=list(DEFAULT_POOL), days=days, seed=seed)
    minute = train_minutes + 240
    mps = [float(market.traces[i.name][minute]) * 1.02 for i in market.pool]
    sig = lambda z: 1.0 / (1.0 + np.exp(-np.asarray(z, np.float64)))
    for kind in ("revpred", "tributary"):
        t0 = time.perf_counter()
        pred = rp.RevPred.train(market, train_minutes, kind=kind,
                                epochs=epochs, seed=0, stride=stride)
        train_s = time.perf_counter() - t0
        got = np.asarray(pred.predict_pool(market.pool, minute * MINUTE, mps))
        check(got.shape == (len(market.pool),) and np.isfinite(got).all(),
              f"{kind}: pool forward gave {got}")
        ref_fn = jax.jit(lambda p, h, x, kind=kind: ref_logits(kind, p, h, x))

        def on_cpu(tp, hist, present):
            with jax.default_device(cpu):
                return np.asarray(ref_fn(*jax.device_put(
                    (tp.params, hist, present), cpu)))

        want = []
        for inst, mp in zip(market.pool, mps):
            tp = pred.predictors[inst.name]
            feats = rp.trace_features(market.traces[inst.name], inst.od_price)
            p = float(sig(on_cpu(tp, *_sample(feats, minute, mp,
                                               inst.od_price)))[0])
            want.append(_eq3(p, tp.pos_frac) if tp.use_eq3 else p)
        dp = float(np.max(np.abs(got - np.asarray(want))))
        # one market's held-out day through the batched (non-vmapped)
        # forward, at a batch the kernel pads to its block
        inst = market.pool[0]
        tp = pred.predictors[inst.name]
        held = rp.build_dataset(market.traces[inst.name], inst.od_price,
                                train_minutes, train_minutes + 1440,
                                "random", np.random.default_rng(1), stride)
        lg_chip = np.asarray(jax.jit(tp.logit_fn)(
            tp.params, held["hist"], held["present"]))
        lg_ref = on_cpu(tp, held["hist"], held["present"])
        dp_batch = float(np.max(np.abs(sig(lg_chip) - sig(lg_ref))))
        dlogit = float(np.max(np.abs(lg_chip - lg_ref)))
        log("revpred", f"{kind}: trained {len(market.pool)} markets in "
            f"{train_s:.2f}s; pool probabilities {got.tolist()}")
        log("revpred", f"{kind}: max |p_chip - p_ref| pool={dp:.3e} "
            f"batch[{len(lg_chip)}]={dp_batch:.3e} (tol {PROB_TOL:g}); "
            f"max |logit_chip - logit_ref| batch={dlogit:.3e}")
        check(dp <= PROB_TOL and dp_batch <= PROB_TOL,
              f"{kind}: chip and CPU reference disagree beyond {PROB_TOL}")


# ------------------------------------------------------------------- study
def _study_specs(seed: int, days: float, n_trials):
    from repro.sweep.spec import ScenarioSpec

    return tuple(ScenarioSpec(workload=w, market_seed=seed,
                              scheduler="spottune", revpred="revpred",
                              days=days, n_trials=n_trials)
                 for w in ("LoR", "SVM"))


def _conserves(tuner) -> bool:
    """Event-order billing fold == the market's totals, exactly."""
    billed = refunded = 0.0
    for ev in tuner.engine.events:
        if ev[1] == "release":
            rec = ev[-1]
            billed += rec["cost"] - rec["refund"]
            refunded += rec["refund"]
    m = tuner.engine.market
    return billed == m.billed and refunded == m.refunded


def _run_service(days: float, n_trials, train_minutes: int):
    from repro.service import StudySpec, StudyStatus, TuningService
    from repro.tuner.engine import Status

    svc = TuningService(train_minutes=train_minutes)
    ids = [svc.submit(StudySpec(tenant=tenant,
                                specs=_study_specs(seed, days, n_trials)))
           for tenant, seed in (("tenant-a", 3), ("tenant-b", 11))]
    svc.run_until_complete(max_pumps=1_000_000)
    out = []
    for sid in ids:
        rec = svc.registry.get(sid)
        check(rec.status is StudyStatus.DONE, f"{sid}: status {rec.status}")
        for i, tuner in enumerate(rec.tuners):
            res = tuner.result
            check(res is not None and math.isfinite(res.cost),
                  f"{sid}[{i}]: cost {res and res.cost}")
            check(all(v.status is Status.FINISHED
                      for v in tuner.engine.views()),
                  f"{sid}[{i}]: a trial is not terminal")
            check(_conserves(tuner), f"{sid}[{i}]: billing does not conserve")
            out.append((sid, i, res.cost, res.refunded, res.jct,
                        tuple(res.predicted_rank), tuple(res.true_rank),
                        tuner.engine.market.billed,
                        repr(tuner.engine.events)))
    return out


def phase_study(days: float = 12.0, n_trials=None, train_minutes: int = 2880):
    t0 = time.perf_counter()
    first = _run_service(days, n_trials, train_minutes)
    wall = time.perf_counter() - t0
    for sid, i, cost, refunded, jct, pred, true, _, _ in first:
        log("study", f"{sid}[{i}] cost=${cost:.4f} refunded=${refunded:.4f} "
            f"jct={jct / 3600:.2f}h best={pred[0]} true_best={true[0]}")
    second = _run_service(days, n_trials, train_minutes)
    check(second == first, "a second identical submission diverged")
    log("study", f"{len(first)} replicas DONE, billing conserved, second "
        f"submission identical (first run {wall:.1f}s)")


# -------------------------------------------------------------- train_full
def phase_train_full(cfg=None, batch: int = FULL_BATCH, seq: int = FULL_SEQ,
                     steps: int = 6, extra: int = 2, lr: float = 3e-4):
    import jax
    import numpy as np

    from repro.checkpoint import CheckpointManager
    from repro.checkpoint.object_store import LocalObjectStore
    from repro.configs.base import get_config
    from repro.launch.train import Trainer

    cfg = cfg or get_config("qwen1.5-0.5b")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as root:
        store = LocalObjectStore(root)
        mk = lambda: Trainer(cfg, batch=batch, seq=seq, lr=lr, seed=0,
                             ckpt=CheckpointManager(store, "full", 10 ** 9),
                             val_every=1)
        tr = mk()
        n_params = sum(int(np.prod(p.shape))
                       for p in jax.tree.leaves(tr.state["params"]))
        tr.run_steps(steps)
        losses = list(tr.metrics_vals)
        # the synthetic tokens are uniform, so batch-to-batch loss noise
        # (about +-0.03 nats at 1536 tokens) hides six steps of progress:
        # the fall is checked on one fixed batch, the first step's
        batch_loss = jax.jit(lambda p, b: tr.model.loss(p, b, tr.ctx)[0])
        first_batch_after = float(batch_loss(tr.state["params"],
                                             tr.data.get_batch(0)))
        t0 = time.perf_counter()
        tr.save(blocking=True)
        save_s = time.perf_counter() - t0
        tr.run_steps(extra)
        direct = tr.metrics_vals[steps:]
        secs = list(tr.step_seconds)
        del tr
        gc.collect()

        tr2 = mk()
        t0 = time.perf_counter()
        got_step = tr2.restore()
        restore_s = time.perf_counter() - t0
        tr2.run_steps(extra)
        resumed = tr2.metrics_vals[steps:]
        secs2 = list(tr2.step_seconds)

    ln_v = math.log(cfg.vocab_size)
    log("train_full", f"{cfg.name}: {n_params} params, batch x seq = "
        f"{batch} x {seq}, losses {losses} + {direct}; first batch's loss "
        f"after {steps} steps {first_batch_after}")
    steady = secs[1:steps]
    mean_s = sum(steady) / len(steady)
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    log("train_full", f"first step (compile + run) {secs[0]:.2f}s, restored "
        f"trainer's first step {secs2[0]:.2f}s; mean step {mean_s * 1e3:.2f} "
        f"ms over steps 2-{steps} (host clock to float(loss)); "
        f"peak_bytes_in_use {peak}; save {save_s:.2f}s, restore "
        f"{restore_s:.2f}s")
    check(all(math.isfinite(x) for x in losses + direct + resumed),
          "non-finite loss")
    check(abs(losses[0] - ln_v) < 1.0,
          f"first loss {losses[0]} is not near ln(V) = {ln_v:.4f}")
    check(first_batch_after < losses[0],
          f"first batch's loss did not fall: {losses[0]} -> "
          f"{first_batch_after}")
    check(got_step == steps, f"restored step {got_step} != {steps}")
    check(resumed == direct,
          f"restored trainer diverged: {resumed} vs uninterrupted {direct}")
    log("train_full", f"restore at step {steps} continued bit for bit: "
        f"{resumed}")


# ------------------------------------------------------------ train_trials
def phase_train_trials(days: float = 2.0):
    from repro.sweep.runner import SweepRunner
    from repro.sweep.spec import ScenarioSpec

    spec = ScenarioSpec(workload="qwen1.5-0.5b", market_seed=0,
                        scheduler="spottune", theta=0.7, backend="training",
                        days=days)
    tuner = SweepRunner().prepare([spec])[0]
    backend = tuner.engine.backend
    res = tuner.run()
    log("train_trials", f"best (EarlyCurve) {res.predicted_rank[0]} true "
        f"best {res.true_rank[0]} top-1 correct {res.top1_correct}; "
        f"cost=${res.cost:.2f} refunded=${res.refunded:.2f}; "
        f"{backend.snapshots} snapshots, {backend.restores} restores")
    check(bool(res.predicted_rank) and bool(res.true_rank)
          and isinstance(res.top1_correct, bool), "top-1 not reported")
    check(backend.snapshots > 0 and backend.restores > 0,
          "no real snapshot/restore happened")


PHASES = (("revpred", phase_revpred), ("study", phase_study),
          ("train_full", phase_train_full),
          ("train_trials", phase_train_trials))


def main() -> int:
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    try:
        dev = phase_device()
    except Exception as e:
        log("device", f"FAILED: {type(e).__name__}: {e}")
        return 1
    log("device", f"compile cache {cache}")
    for name, fn in PHASES:
        t0 = time.perf_counter()
        try:
            fn()
        except Exception as e:
            traceback.print_exc()
            log(name, f"FAILED: {type(e).__name__}: {e}")
            return 1
        log(name, f"ok in {time.perf_counter() - t0:.1f}s")
    import jax

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
