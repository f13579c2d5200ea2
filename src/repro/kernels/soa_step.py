"""SoA sweep inner-step kernels: batched EWMA fold + segmented boundary min.

The SoA stepper's per-round compute is (a) folding every touched row's
deterministic per-tick step-time observations into its perf-matrix EWMA
entry and (b) the segmented min over the per-row next-boundary ticks that
replaces the engines' heaps.  The columnwise masked fold is bit-exact to the sequential
per-observation ``PerfModel.update_many`` replay (same per-row op order,
elementwise float64), and the boundary scan is one ``np.minimum.reduceat``.

``ewma_fold_sorted`` is the stepper's fold and ``ewma_fold_ref`` its test
reference.  Both stay on the host: the bit-exact contract needs float64,
which the TPU's Pallas backend does not compile.
"""

from __future__ import annotations

import numpy as np

_BIG = np.int64(1) << np.int64(60)


# ---------------------------------------------------------------- reference
def ewma_fold_ref(obs: np.ndarray, lens: np.ndarray, m0: np.ndarray,
                  first: np.ndarray, ewma: np.ndarray) -> np.ndarray:
    """Fold ``obs[i, :lens[i]]`` into ``m0[i]`` per row, columnwise.

    Rows with ``first[i]`` start from their first observation instead of
    ``m0`` (the unobserved-prior special case of ``PerfModel.update_many``).
    Per row this replays ``m = (1-a)*m + a*o`` in observation order with the
    identical float64 ops, so the result is bit-exact to the sequential
    fold regardless of how rows are batched."""
    m = np.where(first, 0.0, m0)
    fr = first.copy()
    b = 1.0 - ewma
    for j in range(obs.shape[1]):
        col = obs[:, j]
        valid = j < lens
        m = np.where(valid & fr, col,
                     np.where(valid, b * m + ewma * col, m))
        fr = fr & ~valid
    return m


def ewma_fold_sorted(obs: np.ndarray, lens: np.ndarray, m0: np.ndarray,
                     first: np.ndarray, ewma: np.ndarray) -> np.ndarray:
    """Same fold, O(sum(lens)) instead of O(rows * max(lens)).

    Rows are independent, so sorting them by descending length and folding
    each column over the still-valid *prefix* does the identical per-row
    float64 op sequence with no masking — bit-exact to ``ewma_fold_ref``
    while skipping the padded tail entirely (the tick windows are heavily
    skewed: most rows see a handful of observations, a few see hundreds)."""
    order = np.argsort(-lens, kind="stable")
    ln = lens[order]
    ob = obs[order]
    a = ewma[order]
    b = 1.0 - a
    fr = first[order]
    m = np.where(fr, 0.0, m0[order])
    neg = -ln                         # ascending, for prefix-count searches
    n = int(np.searchsorted(neg, 0, side="left"))      # rows with >=1 obs
    if n:
        col = ob[:n, 0]
        m[:n] = np.where(fr[:n], col, b[:n] * m[:n] + a[:n] * col)
    for j in range(1, obs.shape[1]):
        n = int(np.searchsorted(neg, -j, side="left"))  # rows with len > j
        if not n:
            break
        m[:n] = b[:n] * m[:n] + a[:n] * ob[:n, j]
    out = np.empty_like(m)
    out[order] = m
    return out


def segmented_min_ref(next_k: np.ndarray, starts: np.ndarray) -> np.ndarray:
    """Per-segment min of ``next_k`` over contiguous ``starts`` segments —
    the "next boundary" scan (``_BIG`` rows are the not-running padding)."""
    return np.minimum.reduceat(next_k, starts)
