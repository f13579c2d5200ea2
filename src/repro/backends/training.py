"""TrainingTrialBackend: trials are actual jitted JAX training runs.

Where ``SimTrialBackend`` answers the engine's queries from synthetic
anchor-lattice curves, this backend answers them from real training: each
trial is a ``launch.train.Trainer`` over a small seed config
(``qwen1_5_0_5b`` / ``mamba2_130m`` / ``whisper_base``, reduced preset), so

  metric stream   real validation losses from the jitted train step — the
                  curve is still a *pure function of the trial*: the data
                  pipeline is deterministic in ``(seed, step)`` and restores
                  are bitwise, so a revoked trial that rolls back re-traces
                  the same loss values.  The backend therefore materializes
                  each trial's curve lazily with a cursor Trainer and serves
                  engine queries from it; revocation only truncates the
                  engine-side view.
  snapshot/restore  real ``CheckpointManager`` saves of the full training
                  state (params + AdamW moments) into a bandwidth-modelled
                  object store, gated by ``fits_deadline`` against the
                  revocation-notice budget; ``restore`` re-reads the pytree
                  through ``restore_pytree`` (elastic re-shard hook).
  step timing     per-instance seconds/step from the HLO cost model of the
                  compiled train step fed through the v5e roofline
                  (compute/HBM bound + ring all-reduce term), scaled so the
                  reference slice matches the workload's declared ``s0`` —
                  replacing the sim's hand-written table.
  HP binding      ``TrainingBinding`` declares how SearchSpace configs map
                  onto real knobs: ``lr`` -> AdamW peak LR, ``dr``/``ds`` ->
                  ``exponential_decay_schedule``, ``bs`` -> batch size.

Donor inheritance (``TrialSpec.inherit = (donor_key, donor_step)``): the
new trial's initial params *and optimizer moments* are the donor's training
state at the declared step (replayed from the donor's real snapshots where
available) — this is what makes PBT exploit and TrimTuner warm starts real
weight inheritance instead of a fresh init.

Everything here is lazily imported (``repro.backends.make_backend``): sim
paths never pay for jax.
"""

from __future__ import annotations

import dataclasses
import tempfile
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.backends.base import TrialBackend
from repro.checkpoint import CheckpointManager
from repro.checkpoint.checkpointer import restore_pytree, tree_bytes
from repro.checkpoint.object_store import LocalObjectStore, ThrottledStore
from repro.configs.base import get_config
from repro.core.market import DEFAULT_POOL, InstanceType, stable_hash
from repro.core.trial import TrialSpec, Workload
from repro.data.pipeline import SyntheticLMDataset
from repro.launch.hlo_cost import module_cost
from repro.launch.roofline import HBM_BW, LINK_BW, PEAK_FLOPS
from repro.launch.train import Trainer, init_state, make_train_step
from repro.models.context import null_ctx
from repro.models.model import Model
from repro.optim import adamw
from repro.optim.schedules import exponential_decay_schedule


# ---------------------------------------------------------------------------
# HP binding: SearchSpace config -> real Trainer knobs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TrainingBinding:
    """Declared mapping from a workload's HP dims onto real training knobs.

    ``lr`` is the AdamW peak learning rate; ``dr < 1.0`` with ``ds`` turns
    on the staircase exponential-decay schedule (the multi-stage curves
    EarlyCurve's staged model targets); ``bs`` overrides the batch size.
    Unmapped dims are ignored, so the same binding serves grid variants.
    """

    arch: str
    reduced: bool = True
    batch: int = 4
    seq: int = 32
    seed: int = 0

    def trainer_kwargs(self, hp: dict, val_every: int) -> dict:
        lr = float(hp.get("lr", 3e-3))
        dr = float(hp.get("dr", 1.0))
        ds = hp.get("ds")
        sched = None
        if dr < 1.0 and ds:
            sched = exponential_decay_schedule(lr, dr, int(ds))
        return dict(cfg=get_config(self.arch, reduced=self.reduced),
                    batch=int(hp.get("bs", self.batch)), seq=self.seq,
                    lr=lr, lr_schedule=sched, seed=self.seed,
                    val_every=val_every)


def _state_template(arch: str, reduced: bool = True, seed: int = 0):
    """Abstract (shape/dtype) full-training-state pytree — no compute."""
    cfg = get_config(arch, reduced=reduced)
    model = Model(cfg)
    optimizer = adamw(3e-3, keep_master=(cfg.opt_precision == "fp32"))
    return jax.eval_shape(lambda: init_state(model, optimizer, seed))


def training_workload(arch: str, max_steps: int = 48, val_every: int = 4,
                      s0: float = 150.0, batch: int = 4, seq: int = 32,
                      ) -> Workload:
    """A Workload whose ground truth is real training of ``arch``.

    ``s0`` is *virtual* seconds/step on the reference slice — the market
    clock the tuner simulates, decoupled from host wall time so trials span
    hour-granularity billing windows and revocations like the paper's.
    ``model_bytes`` is measured from the abstract state pytree (params +
    AdamW moments + fp32 master copies), not a table entry.
    """
    bytes_ = float(tree_bytes(_state_template(arch)))
    hp_space = (("lr", (3e-3, 1e-3)), ("dr", (1.0, 0.5)),
                ("bs", (batch, max(1, batch // 2))), ("ds", (max_steps // 3,)))
    return Workload(f"train-{arch}", hp_space, max_trial_steps=max_steps,
                    val_every=val_every, s0=s0, scale_exp=0.6,
                    model_bytes=bytes_, seed=stable_hash(arch) & 0xFFFF)


#: arch id -> Workload / TrainingBinding for the three seed configs.
TRAINING_ARCHS = ("qwen1.5-0.5b", "mamba2-130m", "whisper-base")
TRAINING_WORKLOADS: Dict[str, Workload] = {
    a: training_workload(a) for a in TRAINING_ARCHS}
# every arch trains on data seed 0.  mamba2 used to be pinned to seed 1: the
# SSD mixer's masked intra-chunk exp overflowed in the *backward* pass once
# dt·|A| grew past fp32 exp range (inf·0 = NaN cotangent), which seed 0 hit
# within a handful of steps.  Fixed at the op (repro.models.ssd masks the
# log-decays before exponentiating); tests/test_training_backend.py pins
# multi-seed finite losses so the workaround cannot silently return.
TRAINING_BINDINGS: Dict[str, TrainingBinding] = {
    TRAINING_WORKLOADS[a].name: TrainingBinding(arch=a, seed=0)
    for a in TRAINING_ARCHS}


# roofline cost of one train step, cached per (arch, reduced, bs, seq):
# (flops, hbm_bytes, grad_bytes) from the single-device-compiled HLO
_COST_CACHE: Dict[tuple, tuple] = {}


def _step_cost(binding: TrainingBinding, bs: int) -> tuple:
    key = (binding.arch, binding.reduced, bs, binding.seq)
    hit = _COST_CACHE.get(key)
    if hit is not None:
        return hit
    cfg = get_config(binding.arch, reduced=binding.reduced)
    model = Model(cfg)
    optimizer = adamw(3e-3, keep_master=(cfg.opt_precision == "fp32"))
    ctx = null_ctx(attn_chunk=min(512, binding.seq), remat="none")
    # module_cost reads the CPU backend's HLO (a TPU compile hides its dots
    # in fusions it does not count): compile for the CPU wherever this runs,
    # so the simulated instance's step time does not depend on the host
    cpu = jax.sharding.SingleDeviceSharding(jax.devices("cpu")[0])
    on_cpu = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=cpu), t)
    state_shapes = on_cpu(jax.eval_shape(
        lambda: init_state(model, optimizer, binding.seed)))
    batch_shapes = on_cpu(SyntheticLMDataset(
        cfg, bs, binding.seq, seed=binding.seed).get_batch(0))
    step = make_train_step(model, optimizer, ctx)
    text = jax.jit(step).lower(state_shapes, batch_shapes).compile().as_text()
    cost = module_cost(text, 1)
    grad_bytes = float(tree_bytes(state_shapes["params"]))
    out = (float(cost.flops), float(cost.bytes), grad_bytes)
    _COST_CACHE[key] = out
    return out


def _roofline_seconds(flops: float, hbm: float, grad_bytes: float,
                      chips: int) -> float:
    """Per-step seconds on a ``chips``-chip data-parallel slice: the larger
    of the compute and HBM roofs, plus the ring all-reduce gradient term
    (2 (n-1)/n x bytes over the per-chip link)."""
    comp = max(flops / (chips * PEAK_FLOPS), hbm / (chips * HBM_BW))
    comm = 2.0 * grad_bytes * (chips - 1) / (chips * LINK_BW) if chips > 1 else 0.0
    return comp + comm


# ---------------------------------------------------------------------------
# per-trial run state
# ---------------------------------------------------------------------------


class _Run:
    """One trial's materialization: cursor Trainer (curve ground truth),
    host copy of the initial state (fresh init or inherited donor state),
    real snapshots saved so far, a persistent replayer used to
    re-materialize states at past steps, and a bounded cache of host-state
    copies at val boundaries so replays start near the requested step."""

    __slots__ = ("trial", "kwargs", "prefix", "trainer", "mgr", "state0",
                 "saved", "replayer", "hostcache")

    def __init__(self, trial, kwargs, prefix, trainer, mgr, state0):
        self.trial = trial
        self.kwargs = kwargs
        self.prefix = prefix
        self.trainer = trainer
        self.mgr = mgr
        self.state0 = state0            # host pytree (donation-safe)
        self.saved: set = set()
        self.replayer: Optional[Trainer] = None
        self.hostcache: Dict[int, object] = {}   # boundary step -> host state


def _to_host(state):
    # independent host copies: the train step donates its input buffers, so
    # any state we keep across run_steps must not alias device memory
    return jax.tree.map(lambda x: np.array(x), state)


def _to_device(state):
    return jax.tree.map(jax.numpy.asarray, state)


#: memory bound on per-run opportunistic host copies: val boundaries are
#: strided so at most this many states are kept (a few MB each for the
#: reduced seed configs)
_HOSTCACHE_MAX = 8


def _hostcache_stride(w: Workload) -> int:
    n = max(1, w.max_trial_steps // w.val_every)
    return max(1, -(-n // _HOSTCACHE_MAX))


class TrainingTrialBackend(TrialBackend):
    """Real-training ground truth behind the ``TrialBackend`` protocol."""

    def __init__(self, pool: Optional[List[InstanceType]] = None,
                 root: Optional[str] = None,
                 bandwidth_bps: float = 134.22e6, latency_s: float = 0.05,
                 ref_chips: int = 8,
                 bindings: Optional[Dict[str, TrainingBinding]] = None,
                 sharding_fn=None):
        self.pool = list(pool or DEFAULT_POOL)
        self.ref_chips = ref_chips
        root = root or tempfile.mkdtemp(prefix="spottune-training-")
        self.store = ThrottledStore(LocalObjectStore(root),
                                    bandwidth_bps=bandwidth_bps,
                                    latency_s=latency_s, simulate=True)
        self.bindings = dict(TRAINING_BINDINGS)
        if bindings:
            self.bindings.update(bindings)
        self.sharding_fn = sharding_fn
        self._runs: Dict[tuple, _Run] = {}      # (trial.key, inherit) -> run
        self._by_key: Dict[str, _Run] = {}      # trial.key -> latest run
        # observability for tests/benchmarks
        self.snapshots = 0
        self.restores = 0
        self.snapshot_skips = 0
        self.last_restore: Optional[tuple] = None   # (key, step, host state)

    # ------------------------------------------------------------ run setup
    def _binding(self, trial: TrialSpec) -> TrainingBinding:
        b = self.bindings.get(trial.workload.name)
        if b is None:
            raise KeyError(
                f"no TrainingBinding for workload {trial.workload.name!r} "
                f"(bound: {sorted(self.bindings)})")
        return b

    def _run(self, trial: TrialSpec) -> _Run:
        rkey = (trial.key, trial.inherit)
        run = self._runs.get(rkey)
        if run is not None:
            return run
        binding = self._binding(trial)
        kwargs = binding.trainer_kwargs(trial.hp, trial.workload.val_every)
        suffix = ""
        state0 = None
        if trial.inherit is not None:
            donor_key, donor_step = trial.inherit
            donor = self._by_key.get(donor_key)
            if donor is None:
                raise KeyError(
                    f"inherit donor {donor_key!r} has no materialized run")
            state0 = self._host_state(donor, int(donor_step))
            suffix = f"__inh{stable_hash(str(trial.inherit)) & 0xFFFFFF:06x}"
        prefix = trial.key.replace("/", "_") + suffix
        mgr = CheckpointManager(self.store, prefix,
                                save_interval_steps=10 ** 9, keep_n=0)
        trainer = Trainer(**kwargs)
        if state0 is None:
            state0 = _to_host(trainer.state)
        else:
            trainer.state = _to_device(state0)
        run = _Run(trial, kwargs, prefix, trainer, mgr, state0)
        self._runs[rkey] = run
        self._by_key[trial.key] = run
        return run

    def _ensure(self, run: _Run, step: int) -> None:
        w = run.trial.workload
        target = min(int(step), w.max_trial_steps)
        tr = run.trainer
        if tr.step >= target:
            return
        # advance in val_every chunks, keeping host copies at strided
        # boundaries: engine snapshots land mid-curve after the cursor has
        # run ahead (metric previews drive it to the horizon), and a cached
        # boundary lets the replayer start steps — not epochs — away
        ve = w.val_every
        stride = _hostcache_stride(w)
        while tr.step < target:
            nxt = min(target, (tr.step // ve + 1) * ve)
            tr.run_steps(nxt - tr.step)
            k, rem = divmod(tr.step, ve)
            if rem == 0 and k % stride == 0 and tr.step not in run.hostcache:
                run.hostcache[tr.step] = _to_host(tr.state)

    def _host_state(self, run: _Run, step: int):
        """Full training state at ``step`` as a host pytree.

        Exact-match reads come straight off the cursor or the boundary
        cache; anything else is replayed on the run's persistent replayer
        (one jit compile per run, ever) seeded from the nearest available
        source <= step — cached boundary copy, real snapshot, or the
        replayer's own position — legitimate because training is bitwise
        deterministic in (state, step) on a fixed host platform."""
        if step <= 0:
            return run.state0
        if run.trainer.step == step:
            return _to_host(run.trainer.state)
        hit = run.hostcache.get(step)
        if hit is not None:
            return hit
        rp = run.replayer
        if rp is None:
            rp = run.replayer = Trainer(**run.kwargs)
            rp.state = _to_device(run.state0)
        cached = max((s for s in run.hostcache if s <= step), default=0)
        snap = max((s for s in run.saved if s <= step), default=0)
        if cached <= rp.step <= step and snap <= rp.step:
            pass                        # replayer already closest: run on
        elif cached >= snap:
            rp.state = _to_device(run.hostcache[cached] if cached
                                  else run.state0)
            rp.step = cached
        else:
            rp.state, got = restore_pytree(self.store, run.prefix,
                                           rp.state, step=snap)
            rp.step = got
        if rp.step < step:
            rp.run_steps(step - rp.step)
        return _to_host(rp.state)

    # ----------------------------------------------------------- step times
    def base_step_time(self, trial: TrialSpec, inst: InstanceType) -> float:
        binding = self._binding(trial)
        bs = int(trial.hp.get("bs", binding.batch))
        flops, hbm, grad_bytes = _step_cost(binding, bs)
        w = trial.workload
        t = _roofline_seconds(flops, hbm, grad_bytes, inst.chips)
        t_ref = _roofline_seconds(flops, hbm, grad_bytes, self.ref_chips)
        return w.s0 * t / t_ref

    def host_step_time(self, trial: TrialSpec) -> float:
        """Measured mean wall seconds/step of the trial's cursor on this
        host (compile steps dropped) — reporting only; the virtual clock
        the engine bills against stays the deterministic roofline model."""
        run = self._runs.get((trial.key, trial.inherit))
        return run.trainer.mean_step_time() if run is not None else 0.0

    # --------------------------------------------------------- metric stream
    def metric_at(self, trial: TrialSpec, step: int) -> Optional[float]:
        w = trial.workload
        if step < w.val_every:
            return None
        run = self._run(trial)
        n = w.max_trial_steps // w.val_every
        k = min(step // w.val_every, n)
        self._ensure(run, k * w.val_every)
        lst = run.trainer.metrics_vals
        return lst[min(k, len(lst)) - 1]

    def metric_range(self, trial: TrialSpec, lo: int, hi: int) -> list:
        w = trial.workload
        run = self._run(trial)
        n = w.max_trial_steps // w.val_every
        self._ensure(run, min(hi, n) * w.val_every)
        lst = run.trainer.metrics_vals
        m = len(lst)
        if hi <= m:
            return lst[lo - 1:hi]
        return [lst[min(k, m) - 1] for k in range(lo, hi + 1)]

    def true_final(self, trial: TrialSpec) -> float:
        run = self._run(trial)
        self._ensure(run, trial.workload.max_trial_steps)
        return float(run.trainer.metrics_vals[-1])

    # ------------------------------------------------- checkpoint accounting
    def checkpoint_time(self, trial: TrialSpec, bandwidth_bps: float) -> float:
        # the store's transfer model prices the measured state size; the
        # engine's bandwidth knob is ignored — the store IS the bandwidth
        return self.store.transfer_time(int(self.model_bytes(trial)))

    # ------------------------------------------------------ snapshot/restore
    def snapshot(self, trial: TrialSpec, steps: float,
                 deadline_s: float = 120.0) -> float:
        step = min(int(steps), trial.workload.max_trial_steps)
        if step <= 0:
            return 0.0
        run = self._run(trial)
        if step in run.saved:
            return float(step)
        if not run.mgr.fits_deadline(run.state0, deadline_s):
            # paper §IV-F: model too big for the notice window — the trial
            # stays durable only at its last completed snapshot
            self.snapshot_skips += 1
            durable = [s for s in run.saved if s <= step]
            return float(max(durable)) if durable else 0.0
        self._ensure(run, step)
        state = self._host_state(run, step)
        meta = {"metrics_steps": [s for s in run.trainer.metrics_steps
                                  if s <= step],
                "metrics_vals": [v for s, v in zip(run.trainer.metrics_steps,
                                                   run.trainer.metrics_vals)
                                 if s <= step]}
        run.mgr.save(step, state, blocking=True, extra_meta=meta)
        run.saved.add(step)
        self.snapshots += 1
        return float(step)

    def restore(self, trial: TrialSpec, steps: float) -> None:
        step = int(steps)
        run = self._run(trial)
        snaps = sorted(s for s in run.saved if s <= step)
        if not snaps:
            return None             # fresh start — nothing durable to read
        # the host copy's shapes and dtypes: no device copy to read them from
        like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            run.state0)
        state, got = restore_pytree(self.store, run.prefix, like,
                                    step=snaps[-1],
                                    sharding_fn=self.sharding_fn)
        self.restores += 1
        self.last_restore = (trial.key, got, _to_host(state))
        return None
