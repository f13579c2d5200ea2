"""Training traffic: token batches drawn from the seed.

One general generator for every traffic mix: a batch is ``batch`` rows of
``seq`` next-token pairs, tokens drawn from a Zipf law of exponent
``zipf_s`` over the configuration's vocabulary (rank r has weight
1 / (r + 1) ** s), so a few tokens are common and most are rare, as in text.
Step n's batch depends only on (seed, n): a trial resumed at step n sees the
batch it would have seen.  The trial runtime receives only the arrays.
"""

from __future__ import annotations

import numpy as np


class Feed:
    def __init__(self, vocab: int, batch: int, seq: int, seed: int, zipf_s: float):
        weights = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** zipf_s
        self.cdf = np.cumsum(weights) / weights.sum()
        self.vocab, self.batch, self.seq, self.seed = vocab, batch, seq, seed

    def host_batch(self, step: int) -> dict:
        rng = np.random.default_rng([self.seed, step])
        toks = np.searchsorted(self.cdf, rng.random((self.batch, self.seq + 1)))
        toks = np.minimum(toks, self.vocab - 1).astype(np.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    def get_batch(self, step: int) -> dict:
        import jax.numpy as jnp

        return {k: jnp.asarray(v) for k, v in self.host_batch(step).items()}
