"""Pallas kernel validation: shape/dtype sweeps, interpret mode vs the
pure-jnp oracle (ref.py), per the deliverable-(c) contract."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.lstm_cell import lstm_cell_pallas
from repro.kernels.ssd_scan import ssd_chunk_pallas
from repro.kernels import ops


@pytest.mark.parametrize("B,I,H,bb,bh", [
    (4, 6, 32, 4, 16),
    (8, 7, 64, 4, 32),
    (2, 13, 16, 2, 16),
    (6, 6, 32, 4, 32),          # B not a multiple of the block: padded
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lstm_cell_sweep(B, I, H, bb, bh, dtype, rng):
    x = jnp.asarray(rng.standard_normal((B, I)), dtype)
    h = jnp.asarray(rng.standard_normal((B, H)), dtype)
    c = jnp.asarray(rng.standard_normal((B, H)), dtype)
    wih = jnp.asarray(rng.standard_normal((I, 4 * H)) * 0.3, dtype)
    whh = jnp.asarray(rng.standard_normal((H, 4 * H)) * 0.3, dtype)
    b = jnp.asarray(rng.standard_normal((4 * H,)) * 0.1, dtype)
    h1, c1 = ref.lstm_cell_ref(x, h, c, wih, whh, b)
    h2, c2 = lstm_cell_pallas(x, h, c, wih, whh, b, interpret=True,
                              block_b=bb, block_h=bh)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(h1, np.float32), np.asarray(h2, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(c1, np.float32), np.asarray(c2, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("B,S,H,D,bq,bk", [
    (2, 64, 3, 16, 16, 16),
    (1, 128, 2, 32, 32, 16),
    (2, 48, 1, 8, 16, 16),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, H, D, bq, bk, causal, dtype, rng):
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype)
    o1 = ref.flash_attention_ref(q, k, v, causal)
    o2 = flash_attention_pallas(q, k, v, causal=causal, block_q=bq,
                                block_k=bk, interpret=True)
    tol = 3e-5 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("B,Q,H,P,N", [
    (2, 32, 3, 8, 4),
    (1, 64, 2, 16, 8),
    (3, 16, 1, 4, 4),
])
def test_ssd_chunk_sweep(B, Q, H, P, N, rng):
    x = jnp.asarray(rng.standard_normal((B, Q, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (B, Q, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2.0, (H,)), jnp.float32)
    Bi = jnp.asarray(rng.standard_normal((B, Q, H, N)), jnp.float32)
    Ci = jnp.asarray(rng.standard_normal((B, Q, H, N)), jnp.float32)
    st = jnp.asarray(rng.standard_normal((B, H, P, N)), jnp.float32)
    y1, s1 = ref.ssd_chunk_ref(x, dt, A, Bi, Ci, st)
    y2, s2 = ssd_chunk_pallas(x, dt, A, Bi, Ci, st, interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-4, atol=1e-4)


def test_ops_dispatch_cpu_uses_ref(rng):
    """On the CPU backend the dispatcher must route to the jnp oracle."""
    x = jnp.asarray(rng.standard_normal((2, 6)), jnp.float32)
    h = jnp.asarray(rng.standard_normal((2, 8)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((2, 8)), jnp.float32)
    wih = jnp.asarray(rng.standard_normal((6, 32)), jnp.float32)
    whh = jnp.asarray(rng.standard_normal((8, 32)), jnp.float32)
    b = jnp.zeros((32,), jnp.float32)
    h1, c1 = ops.lstm_cell(x, h, c, wih, whh, b)
    h2, c2 = ref.lstm_cell_ref(x, h, c, wih, whh, b)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-6)
    # force=interpret exercises the Pallas body on CPU
    h3, c3 = ops.lstm_cell(x, h, c, wih, whh, b, force="interpret")
    np.testing.assert_allclose(np.asarray(h3), np.asarray(h2), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("B", [1, 130])
def test_lstm_cell_kernel_grad_matches_ref(B, rng):
    """jax.grad through the kernel path (custom VJP) == grad through the
    oracle, over two chained steps so the kernel's forward feeds the second
    step's backward."""
    I, H = 6, 32
    f32 = lambda *shape, s=1.0: jnp.asarray(rng.standard_normal(shape) * s,
                                            jnp.float32)
    xs = f32(2, B, I)
    h0, c0 = f32(B, H), f32(B, H)
    w = (f32(I, 4 * H, s=0.3), f32(H, 4 * H, s=0.3), f32(4 * H, s=0.1))
    proj = f32(B, H)

    def loss(w, h, c, cell):
        for t in range(2):
            h, c = cell(xs[t], h, c, *w)
        return jnp.sum(h * proj) + jnp.sum(jnp.tanh(c))

    kern = lambda *a: ops.lstm_cell(*a, force="interpret")
    g_kern = jax.grad(loss, argnums=(0, 1, 2))(w, h0, c0, kern)
    g_ref = jax.grad(loss, argnums=(0, 1, 2))(w, h0, c0, ref.lstm_cell_ref)
    for a, b in zip(jax.tree.leaves(g_kern), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_revpred_train_step_through_interpret_kernel(monkeypatch, rng):
    """One ``train_model`` step of ``revpred_logits`` with every LSTM cell
    on the kernel path (interpret mode) lands on the same parameters as the
    jnp-oracle step."""
    import functools

    from repro.core import revpred as rp

    n = 8
    data = {"hist": rng.uniform(0, 1, (n, rp.HISTORY, rp.N_FEAT)).astype(np.float32),
            "present": rng.uniform(0, 1, (n, rp.N_FEAT + 1)).astype(np.float32),
            "label": (np.arange(n) % 3 == 0).astype(np.float32)}
    init = rp.init_revpred(jax.random.key(0))
    want, _ = rp.train_model(rp.revpred_logits, init, data, epochs=1, bs=n)
    monkeypatch.setattr(ops, "lstm_cell",
                        functools.partial(ops.lstm_cell, force="interpret"))
    got, _ = rp.train_model(rp.revpred_logits, init, data, epochs=1, bs=n)
    moved = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                         want, init)
    assert max(jax.tree.leaves(moved)) > 1e-4      # the step did train
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


# =================================================== SoA inner-step kernels
# Two layers, each bit-exact to the one below (repro.kernels.soa_step):
#
#     ewma_fold_sorted / segmented_min_ref      (numpy, the stepper's path)
#         == ewma_fold_ref                      (columnwise masked fold)
#         == PerfModel.update_many called per row   (production semantics)

import types

from repro.core.provisioner import PerfModel
from repro.kernels.soa_step import (ewma_fold_ref, ewma_fold_sorted,
                                    segmented_min_ref)

_BIG = np.int64(1) << np.int64(60)


def _ragged(nprng, rows, width):
    """Random padded (obs, lens, m0, first, ewma) batch; the padding tail
    carries garbage on purpose — folds must never read past lens."""
    lens = nprng.integers(0, width + 1, rows)
    obs = nprng.uniform(0.5, 12.0, (rows, width))
    m0 = nprng.uniform(0.5, 12.0, rows)
    first = nprng.random(rows) < 0.4
    ewma = np.full(rows, 0.5)
    return obs, lens, m0, first, ewma


def _sequential_update_many(obs, lens, m0, first, ewma):
    """Fold each row through the real PerfModel.update_many — the op
    sequence every kernel must replay."""
    out = np.empty_like(m0)
    inst = types.SimpleNamespace(name="i0")
    trial = types.SimpleNamespace(key="t0")
    for i in range(len(lens)):
        pm = PerfModel(pool=[], ewma=float(ewma[i]))
        if not first[i]:
            pm._m[("i0", "t0")] = float(m0[i])
            pm._observed[("i0", "t0")] = True
        pm.update_many(inst, trial, obs[i, :lens[i]])
        if lens[i] == 0 and first[i]:
            out[i] = 0.0          # kernel convention for never-observed rows
        else:
            out[i] = pm._m.get(("i0", "t0"), float(m0[i]))
    return out


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("rows,width", [(1, 1), (7, 5), (64, 40), (129, 3)])
def test_ewma_fold_ref_matches_sequential_update_many(seed, rows, width):
    nprng = np.random.default_rng(seed)
    batch = _ragged(nprng, rows, width)
    assert np.array_equal(ewma_fold_ref(*batch),
                          _sequential_update_many(*batch))


@pytest.mark.parametrize("seed", range(5))
def test_ewma_fold_sorted_matches_ref(seed):
    nprng = np.random.default_rng(100 + seed)
    rows = int(nprng.integers(1, 200))
    width = int(nprng.integers(1, 60))
    batch = _ragged(nprng, rows, width)
    assert np.array_equal(ewma_fold_sorted(*batch), ewma_fold_ref(*batch))


def test_ewma_fold_sorted_skewed_lengths():
    """The skew the sorted fold exists for: one long row among stubs."""
    nprng = np.random.default_rng(7)
    obs, lens, m0, first, ewma = _ragged(nprng, 50, 400)
    lens[:] = nprng.integers(0, 3, 50)
    lens[17] = 400
    batch = (obs, lens, m0, first, ewma)
    assert np.array_equal(ewma_fold_sorted(*batch), ewma_fold_ref(*batch))


@pytest.mark.parametrize("seed", range(3))
def test_segmented_min_matches_python(seed):
    nprng = np.random.default_rng(300 + seed)
    n_seg = int(nprng.integers(1, 20))
    sizes = nprng.integers(1, 9, n_seg)
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]]).astype(np.int64)
    next_k = nprng.integers(0, 1_000_000, int(sizes.sum())).astype(np.int64)
    next_k[nprng.random(len(next_k)) < 0.3] = _BIG    # not-running padding
    got = segmented_min_ref(next_k, starts)
    bounds = list(starts) + [len(next_k)]
    want = np.array([next_k[a:b].min() for a, b in zip(bounds, bounds[1:])])
    assert np.array_equal(got, want)


# --------------------- batched jitter seeding (the SoA fold's input path)


def test_seed_states_replicate_seedsequence():
    """The vectorized seed-sequence mix must reproduce numpy's
    ``SeedSequence([w_seed, t]).generate_state(4, uint64)`` exactly —
    this is the check `_vec_seed_ok` gates the fast jitter fill on."""
    from repro.core.trial import _seed_states, _vec_seed_ok

    assert _vec_seed_ok()       # current numpy passes the runtime gate
    nprng = np.random.default_rng(11)
    for s in nprng.integers(0, 2**32, 6):
        ts = nprng.integers(0, 2**32, 40).astype(np.int64)
        got = _seed_states(int(s), ts)
        for j in (0, 7, 39):
            want = np.random.SeedSequence(
                [int(s), int(ts[j])]).generate_state(4, np.uint64)
            assert np.array_equal(got[j], want), (s, ts[j])


def test_jitter_entry_batch_fill_equals_scalar_fill():
    import repro.core.trial as trial

    trial._JITTER_CACHE.clear()
    fast = trial._jitter_entry(9, 10.0, 5000)[0].copy()
    trial._JITTER_CACHE.clear()
    orig = trial._vec_seed_ok
    trial._vec_seed_ok = lambda: False      # force the literal per-tick path
    try:
        slow = trial._jitter_entry(9, 10.0, 5000)[0].copy()
    finally:
        trial._vec_seed_ok = orig
        trial._JITTER_CACHE.clear()
    assert np.array_equal(fast, slow)
