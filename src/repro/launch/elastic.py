"""Elastic re-deployment: move a training state between meshes/slice types.

This is the substrate under SpotTune's Algorithm-1 re-deployment (lines
38-44): a revoked trial's checkpoint is restored onto whatever slice the
Provisioner picks next, which generally has a different chip count and hence
a different mesh.  Three pieces:

  * ``slice_mesh(chips)`` — the mesh a given v5e slice exposes (model-axis
    capped at the slice's efficient TP width, remainder to data);
  * ``reshard_state(state, policy)`` — device_put every leaf to the sharding
    the target policy assigns it (works from host arrays or differently-
    sharded jax arrays);
  * ``ElasticTrial`` — checkpoint-manager-backed save/restore-to-new-mesh
    wrapper used by the orchestrator's real backend.

Works on any device topology jax exposes (including the 512 fake host
devices of the dry-run and the single CPU device of the tests — meshes are
built from however many devices exist).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import numpy as np

from repro.checkpoint.checkpointer import restore_pytree, save_pytree
from repro.launch.sharding import Policy


def slice_mesh(chips: Optional[int] = None, max_model: int = 16):
    """Mesh for a slice of ``chips`` devices (defaults to all available).

    model axis = largest power-of-two divisor up to ``max_model``; the rest
    is data/FSDP — the layout the production 16x16 pod uses, shrunk."""
    n_avail = len(jax.devices())
    chips = min(chips or n_avail, n_avail)
    model = 1
    while model * 2 <= min(max_model, chips) and chips % (model * 2) == 0:
        model *= 2
    data = chips // model
    devs = np.asarray(jax.devices()[:chips]).reshape(data, model)
    return jax.sharding.Mesh(devs, ("data", "model"))


def state_shardings(cfg, mesh, state_shapes, kind: str = "train",
                    global_batch: Optional[int] = None):
    """NamedShardings for a {params, opt} train state on ``mesh``."""
    policy = Policy(cfg, mesh, kind, global_batch=global_batch)
    param_sh = policy.param_shardings(state_shapes["params"])
    out = {"params": param_sh}
    if "opt" in state_shapes:
        out["opt"] = policy.opt_state_shardings(state_shapes["opt"], param_sh)
    return out


def reshard_state(state, shardings):
    """device_put every leaf onto its target sharding (gather+scatter as
    needed; host numpy arrays upload directly)."""
    return jax.tree.map(lambda x, s: jax.device_put(x, s), state, shardings)


class ElasticTrial:
    """Checkpoint-backed migration: save on slice A, restore sharded on B.

    ``restore_onto`` hands ``restore_pytree`` the target mesh's pytree of
    shardings and returns ``(state, step)``.  The restore never materializes
    more than one leaf unsharded on a single host — each leaf is loaded from
    the store and device_put straight to its target sharding (the multi-host
    generalization reads per-shard byte ranges; the store layout is already
    one object per leaf)."""

    def __init__(self, cfg, store, prefix: str, kind: str = "train"):
        self.cfg = cfg
        self.store = store
        self.prefix = prefix
        self.kind = kind

    def save(self, step: int, state, blocking: bool = True):
        return save_pytree(self.store, self.prefix, step, state,
                           blocking=blocking)

    def restore_onto(self, mesh, state_shapes, step: Optional[int] = None,
                     global_batch: Optional[int] = None):
        shardings = state_shardings(self.cfg, mesh, state_shapes, self.kind,
                                    global_batch)
        state, got_step, _ = restore_pytree(self.store, self.prefix,
                                            state_shapes, step=step,
                                            shardings=shardings)
        return state, got_step
