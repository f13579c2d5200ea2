"""The comparison that decides ``correct``: the trial runtime's first steps
against the plain reference's, from the same seed, weights and batches.

Numbers compared (each against its cell's limit, ``bench/workloads``):

- ``loss_gap``: the largest |loss - reference loss| over the checked steps,
  in nats;
- ``loss1_gap``: the same for the first step alone, where both start from
  the same weights: steady from seed to seed, so ``control.py`` reports it
  beside ``loss_gap`` when a cell's limits are set;
- ``grad_gap``: over the leaves, the largest gap between the norm of the
  first gradient as AdamW received it (its first moment after one step,
  over 1 - b1) and the reference's clipped gradient's norm, over the larger
  of the reference leaf's norm and the median leaf's;
- ``update_gap``: the same for the norm of the float32 weights' change
  after the checked steps, leaving out leaves whose reference gradient is
  under a thousandth of the median leaf's (a key bias under softmax): AdamW
  moves those by their rounding noise alone;
- ``restore_mismatch`` (revoked traffic): leaves whose restored bits differ
  from the saved ones, over every revocation of the run; the limit is 0.

The resume traffic trains nothing; its one number (``compare_restores``):

- ``restore_mismatch``: over every revocation of the run, the leaves of each
  restore whose checksum differs from the one numpy sums over the seed's
  state, plus the leaves of the last restore whose bits differ from the
  seed's when read back whole, plus the restores whose step counter is not
  the one saved; exact, so its limit is 0.
"""

from __future__ import annotations

import math

import numpy as np

NOUGHT = 1e-3


def worst_leaf(got, want, names):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    den = np.maximum(want, np.median(want))
    gap = np.abs(got - want) / den
    i = int(np.nanargmax(gap)) if np.isfinite(gap).any() else 0
    value = float(np.max(gap)) if np.isfinite(gap).all() else math.nan
    return value, names[i]


def compare(prog: dict, ref: dict, restore_mismatch=None):
    """-> ({number: value}, {number: worst leaf or note})."""
    names = ref["names"]
    keep = np.asarray(ref["raw_grad_norms"]) >= NOUGHT * np.median(ref["raw_grad_norms"])
    kept = [n for n, k in zip(names, keep) if k]
    numbers, where = {}, {}
    gaps = [abs(a - b) for a, b in zip(prog["losses"], ref["losses"])]
    numbers["loss_gap"] = max(gaps) if all(map(math.isfinite, gaps)) else math.nan
    where["loss_gap"] = f"step {int(np.argmax(gaps)) + 1}"
    numbers["loss1_gap"], where["loss1_gap"] = gaps[0], "step 1"
    numbers["grad_gap"], where["grad_gap"] = worst_leaf(
        prog["grad_norms"], ref["grad_norms"], names)
    got, want = np.asarray(prog["change_norms"])[keep], np.asarray(ref["change_norms"])[keep]
    numbers["update_gap"], where["update_gap"] = worst_leaf(got, want, kept)
    where["update_gap"] += f" ({len(names) - len(kept)} leaves left out)"
    if restore_mismatch is not None:
        numbers["restore_mismatch"] = restore_mismatch
        where["restore_mismatch"] = "leaves"
    return numbers, where


def judge(numbers: dict, limits: dict) -> bool:
    """Every number the cell has a limit for is within it (a NaN is not)."""
    return all(numbers[k] <= limit for k, limit in limits.items())


def compare_restores(mismatch: dict):
    """-> ({number: value}, {number: note}) from ``Trial.resume_mismatches``."""
    total = mismatch["checksums"] + mismatch["last_state"] + mismatch["steps"]
    note = (f"{mismatch['checksums']} restored leaves by checksum, "
            f"{mismatch['last_state']} of the last restore by bits, "
            f"{mismatch['steps']} step counters")
    return {"restore_mismatch": total}, {"restore_mismatch": note}
