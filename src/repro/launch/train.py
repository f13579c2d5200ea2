"""Training driver: train_step builder (shared by dry-run and real runs) and
a CPU-runnable Trainer used by the HPT examples and
``repro.backends.training.TrainingTrialBackend``.

A Trainer builds its random state on first read of ``Trainer.state``; a
trainer that is restored, or assigned a state, before that never builds it.

The train step is one pjit'd program: loss (vocab-sharded xent + MoE aux) →
grads → clip → AdamW update.  Fault tolerance comes from the checkpoint
manager (atomic manifests) + the deterministic data pipeline: restore(step)
replays the exact stream.
"""

from __future__ import annotations

import time
from typing import Callable, Optional

import jax
import numpy as np

from repro import telemetry
from repro.checkpoint import CheckpointManager
from repro.data.pipeline import SyntheticLMDataset, prefetch
from repro.models.context import ModelCtx, null_ctx
from repro.models.model import Model
from repro.optim import adamw
from repro.optim.optimizers import Optimizer


def make_train_step(model: Model, optimizer: Optimizer, ctx: ModelCtx) -> Callable:
    def train_step(state, batch):
        def loss_fn(p):
            return model.loss(p, batch, ctx)

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state["params"])
        new_params, new_opt, opt_metrics = optimizer.update(
            grads, state["opt"], state["params"])
        return ({"params": new_params, "opt": new_opt},
                {"loss": loss, **metrics, **opt_metrics})

    return train_step


def init_state(model: Model, optimizer: Optimizer, seed: int = 0):
    params = jax.jit(model.init)(jax.random.key(seed))
    return {"params": params, "opt": optimizer.init(params)}


def trial_optimizer(cfg, lr) -> Optimizer:
    """A trial's AdamW: an fp32 master copy of the params iff the config
    asks for fp32 optimizer precision."""
    return adamw(lr, keep_master=(cfg.opt_precision == "fp32"))


def state_template(model: Model, optimizer: Optimizer, seed: int = 0):
    """The shapes and dtypes of ``init_state``, computing nothing."""
    return jax.eval_shape(lambda: init_state(model, optimizer, seed))


_UNBUILT = object()     # ``Trainer._state`` before anything reads or assigns it


class Trainer:
    """Small real-training loop (CPU-scale configs) with checkpoint/restart.

    Used by examples/ and ``repro.backends.training.TrainingTrialBackend``:
    SpotTune treats one Trainer as one HPT trial; ``run_steps`` advances it
    and returns the validation metrics stream the engine/EarlyCurve consume.

    Construction builds the model, optimizer, dataset and the step's ``jit``
    wrapper, not the state.  The first read of ``state`` builds
    ``init_state(model, optimizer, seed)`` unless a state was assigned or
    restored before it, so a resumed trial never makes the random state its
    restore would replace.
    """

    def __init__(self, cfg, batch: int, seq: int, lr: float = 3e-3,
                 lr_schedule=None, seed: int = 0,
                 ckpt: Optional[CheckpointManager] = None,
                 val_every: int = 10, ctx: Optional[ModelCtx] = None):
        with telemetry.span("trainer.init"):
            with telemetry.span("trainer.build"):
                self.cfg = cfg
                self.model = Model(cfg)
                self.optimizer = trial_optimizer(
                    cfg, lr_schedule if lr_schedule is not None else lr)
                self.ctx = ctx or null_ctx(attn_chunk=min(512, seq), remat="none")
                self.data = SyntheticLMDataset(cfg, batch, seq, seed=seed)
                self.step_fn = jax.jit(
                    make_train_step(self.model, self.optimizer, self.ctx),
                    donate_argnums=(0,))
        self.seed = seed
        self._state = _UNBUILT
        self.step = 0
        self.ckpt = ckpt
        self.val_every = val_every
        self.metrics_steps: list = []
        self.metrics_vals: list = []
        self.step_seconds: list = []

    @property
    def state(self):
        if self._state is _UNBUILT:
            # concrete arrays even when first read inside a trace; unlike
            # ensure_compile_time_eval, this folds no constants into
            # model.init's own trace, so the bits are those of a plain call
            with telemetry.span("trainer.init_state"), jax.core.eval_context():
                self._state = init_state(self.model, self.optimizer, self.seed)
        return self._state

    @state.setter
    def state(self, value):
        self._state = value

    def run_steps(self, n: int):
        """Advance n steps; returns newly recorded (step, val_loss) points."""
        new_points = []
        for _ in range(n):
            batch = self.data.get_batch(self.step)
            t0 = time.perf_counter()
            self.state, m = self.step_fn(self.state, batch)
            loss = float(m["loss"])
            self.step_seconds.append(time.perf_counter() - t0)
            self.step += 1
            if self.step % self.val_every == 0:
                self.metrics_steps.append(self.step)
                self.metrics_vals.append(loss)
                new_points.append((self.step, loss))
            if self.ckpt and self.ckpt.should_save(self.step):
                self.save()
        return new_points

    # ------------------------------------------------------- checkpointing
    def save(self, blocking: bool = True):
        assert self.ckpt is not None
        meta = {"metrics_steps": self.metrics_steps,
                "metrics_vals": self.metrics_vals}
        self.ckpt.save(self.step, self.state, blocking=blocking, extra_meta=meta)

    def restore(self, step=None, shardings=None):
        """Rehydrate from the latest checkpoint (or an explicit ``step``) and
        return its step; ``shardings`` (a pytree of the state's structure)
        places each leaf, as in ``restore_pytree``.  The metric stream
        reloads from the checkpoint's manifest, so the trial continues the
        original stream exactly.  It restores into ``state_template``, so a
        state never built is never built."""
        assert self.ckpt is not None
        with telemetry.span("trainer.restore"):
            like = state_template(self.model, self.optimizer, self.seed)
            # drop a built state before reading: a full-width state and its
            # restored copy do not fit one chip together
            self.state = None
            self.state, step = self.ckpt.restore(like, step=step, shardings=shardings)
            self.step = step
            extra = self.ckpt.restored_extra
            self.metrics_steps = list(extra.get("metrics_steps", []))
            self.metrics_vals = list(extra.get("metrics_vals", []))
        return step

    def mean_step_time(self) -> float:
        xs = self.step_seconds[2:] or self.step_seconds  # drop compile step
        return float(np.mean(xs)) if xs else 0.0
