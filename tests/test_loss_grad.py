"""The training loss and its gradient against a plain float32 reference.

Every trial's gradient comes from ``Model.loss`` (through
``launch.train.make_train_step``), so each family is checked: the gradient
of ``Model.loss`` against that of a cross-entropy written the plain way
(``log_softmax`` of the logits, the gold entries taken, the masked mean),
on the same backbone and at float32.  ``layers.softmax_xent`` is checked
alone on random logits: its gradient with respect to the logits is
softmax minus the one-hot of the label, which sums to 0 over the
vocabulary at every unmasked position, and its value is that of the
log-sum-exp written with the max shifted out by hand.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ARCH_IDS, get_config
from repro.models import inputs as inputs_lib
from repro.models import layers
from repro.models.context import null_ctx
from repro.models.model import Model

# Model.loss and the reference run the same float32 ops up to the logits and
# differ after them (logsumexp against log_softmax), so only float32 rounding
# in the sums over the vocabulary and the positions may separate their
# gradients: on the CPU they agree to the bit for every family.  A wrong
# gradient misses by the order of the gradient itself (an extra unit on each
# token's argmax logit misses by 0.77-2.8 of a leaf's norm), so 1e-4 leaves
# room on both sides.
GRAD_RTOL = 1e-4
# The loss value: the same sums in another order (on the CPU up to 1.5e-7
# apart), far below the 1e-5 this allows.
LOSS_RTOL = 1e-5


def _reference_loss(model, params, batch, ctx):
    """Same backbone; the head and the cross-entropy written out plainly."""
    cfg = model.cfg
    x, aux = model._backbone(params, batch, ctx)
    x = (layers.layer_norm(x, params["ln_f"], cfg.norm_eps) if cfg.family == "audio"
         else layers.rms_norm(x, params["ln_f"], cfg.norm_eps))
    w = params["embed"]["tok"].T if cfg.tie_embeddings else params["unembed"]
    logits = (x @ w).astype(jnp.float32)
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    logp = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                               jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    return -jnp.sum(mask * logp) / jnp.sum(mask) + aux


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_loss_grad_matches_float32_reference(arch):
    cfg = dataclasses.replace(get_config(arch, reduced=True), dtype="float32")
    model = Model(cfg)
    params = jax.jit(model.init)(jax.random.key(3))
    rng = np.random.default_rng(7)
    batch = inputs_lib.sample_train_batch(rng, cfg, 2, 16)
    labels = np.array(batch["labels"])
    labels[rng.random(labels.shape) < 0.25] = -1     # some positions masked
    batch["labels"] = jnp.asarray(labels)
    ctx = null_ctx(attn_chunk=8, remat="none")

    got, grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss(p, batch, ctx)[0]))(params)
    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p: _reference_loss(model, p, batch, ctx)))(params)

    np.testing.assert_allclose(float(got), float(want), rtol=LOSS_RTOL)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    flat_want = jax.tree.leaves(want_grads)
    for (path, g), r in zip(flat, flat_want):
        g, r = np.asarray(g, np.float64), np.asarray(r, np.float64)
        scale = max(np.linalg.norm(r), 1e-12)
        gap = np.linalg.norm(g - r) / scale
        assert gap <= GRAD_RTOL, f"{jax.tree_util.keystr(path)}: relative gap {gap:.3g}"


def _random_xent_inputs(seed, scale, B=3, S=7, V=50):
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.standard_normal((B, S, V)) * scale, jnp.float32)
    labels = rng.integers(0, V, size=(B, S)).astype(np.int32)
    mask = rng.random((B, S)) < 0.7
    labels[~mask] = -1
    return logits, jnp.asarray(labels), jnp.asarray(mask)


@pytest.mark.parametrize("scale", [1.0, 30.0])
def test_xent_grad_sums_to_zero_per_token(scale):
    logits, labels, mask = _random_xent_inputs(11, scale)
    g = np.asarray(jax.grad(layers.softmax_xent)(logits, labels, mask))
    sums = g.sum(axis=-1)
    n = int(np.sum(mask))
    # Each unmasked row is (softmax - onehot) / n: its sum is 0 up to float32
    # rounding over V entries, far below 1/n (what an extra one-hot would add).
    np.testing.assert_allclose(sums[np.asarray(mask)], 0.0, atol=1e-3 / n)
    # A masked position contributes nothing.
    assert np.all(g[~np.asarray(mask)] == 0.0)


@pytest.mark.parametrize("scale", [1.0, 30.0])
def test_xent_value_matches_max_shifted_form(scale):
    logits, labels, mask = _random_xent_inputs(13, scale)
    got = float(layers.softmax_xent(logits, labels, mask))
    mx = jnp.max(logits, axis=-1)
    lse = jnp.log(jnp.sum(jnp.exp(logits - mx[..., None]), axis=-1)) + mx
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    m = mask.astype(jnp.float32)
    want = float(jnp.sum((lse - gold) * m) / jnp.sum(m))
    np.testing.assert_allclose(got, want, rtol=1e-6)
