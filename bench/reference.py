"""Plain reference training: the configuration's model in float32 at the
highest matmul precision, AdamW as published, three steps from the seed.

Nothing here imports the program.  Each configuration's reference module
(``configs/<name>_ref.py``) gives ``init_params(conf, key)`` and
``loss(params, batch, conf, ein)``; this module drives them and returns the
readings that ``check.py`` compares:

- ``losses``: each step's loss, before that step's update;
- ``grad_norms``: per leaf, the norm of the first gradient after global-norm
  clipping, as the optimizer receives it;
- ``raw_grad_norms``: the same before clipping (the rule that leaves out
  leaves whose gradient is nought to rounding reads these);
- ``change_norms``: per leaf, the norm of the float32 weights' change over
  the steps.

``ein(spec, a, b)`` is every contraction of a reference.  ``mode="f32"``
is the reference; ``mode="fp8"`` quantises both operands of every
contraction to float8 with a per-tensor scale (e4m3 forward, e5m2 for the
cotangent), the precision below the configuration's bfloat16: that is the
control, which the comparison has to refuse.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _quant(x, dtype):
    """Round x to ``dtype`` under a per-tensor absmax scale; back to f32."""
    top = float(jnp.finfo(dtype).max)
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ein_fp8(spec, a, b):
    return _einsum(spec, _quant(a, jnp.float8_e4m3fn), _quant(b, jnp.float8_e4m3fn))


def _ein_fp8_fwd(spec, a, b):
    qa, qb = _quant(a, jnp.float8_e4m3fn), _quant(b, jnp.float8_e4m3fn)
    return _einsum(spec, qa, qb), (qa, qb)


def _ein_fp8_bwd(spec, res, g):
    qa, qb = res
    qg = _quant(g, jnp.float8_e5m2)
    _, vjp = jax.vjp(lambda a, b: _einsum(spec, a, b), qa, qb)
    return vjp(qg)


_ein_fp8.defvjp(_ein_fp8_fwd, _ein_fp8_bwd)


def _ein_bf16(spec, a, b):
    return _einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))


def contraction(mode: str):
    """``f32``: the reference; ``bf16``: operands rounded to the
    configuration's compute type (a sound stand-in for the program);
    ``fp8``: the control."""
    modes = {"f32": _einsum, "bf16": _ein_bf16, "fp8": _ein_fp8}
    if mode not in modes:
        raise ValueError(f"unknown reference precision {mode!r}")
    return modes[mode]


def leaf_norms(tree):
    """Per-leaf float32 norms, in ``jax.tree.leaves`` order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def leaf_names(tree):
    return [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


def seed_key(seed: int):
    """A key for any seed below 2**64 (``jax.random.key`` keeps 32 bits)."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def half(batch):
    """The first half of a batch's rows, or of its positions when it has one
    row: the fault of a step that averages over half of its batch."""
    rows, seq = batch["tokens"].shape
    if rows > 1:
        return {k: v[: rows // 2] for k, v in batch.items()}
    return {k: v[:, : seq // 2] for k, v in batch.items()}


def train_readings(ref, conf: dict, seed: int, batches, mode: str = "f32",
                   half_batch: bool = False) -> dict:
    """Three (``len(batches)``) AdamW steps of the reference from the seed.

    ``mode="bf16"`` also computes each step from the weights rounded to the
    type the configuration serves them in (bfloat16 matrices), as a trial
    runtime with float32 master weights does.  ``half_batch`` plants the
    half-batch fault (``half``) in the reference.
    """
    opt = conf["optimizer"]
    ein = contraction(mode)
    key = seed_key(seed)
    served = [x.dtype for x in jax.tree.leaves(
        jax.eval_shape(lambda k: ref.init_params(conf, k), key))]

    def f32_params(key):
        return jax.tree.map(lambda x: x.astype(jnp.float32),
                            ref.init_params(conf, key))

    def as_served(p):
        leaves, tree = jax.tree.flatten(p)
        return tree.unflatten([x.astype(d).astype(jnp.float32)
                               for x, d in zip(leaves, served)])

    def step(p, m, v, t, batch):
        if half_batch:
            batch = half(batch)
        loss_fn = (lambda p, *a: ref.loss(as_served(p), *a)) if mode == "bf16" else ref.loss
        loss, g = jax.value_and_grad(loss_fn)(p, batch, conf, ein)
        raw = leaf_norms(g)
        gn = jnp.sqrt(jnp.sum(jnp.square(raw)))
        scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gn, 1e-9))
        g = jax.tree.map(lambda x: x * scale, g)
        b1, b2 = opt["b1"], opt["b2"]
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        p = jax.tree.map(
            lambda p, m, v: p - opt["lr"] * ((m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
                                             + opt["weight_decay"] * p), p, m, v)
        return p, m, v, loss, leaf_norms(g), raw

    step = jax.jit(step, donate_argnums=(0, 1, 2))
    make = jax.jit(f32_params)   # called again below: the same program, the same bits
    p = make(key)
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    names = leaf_names(p)
    losses = []
    for t, batch in enumerate(batches, start=1):
        p, m, v, loss, gnorm, raw = step(p, m, v, jnp.float32(t), batch)
        losses.append(float(loss))
        if t == 1:
            grad_norms, raw_norms = np.asarray(gnorm), np.asarray(raw)
    del m, v
    change = jax.jit(lambda p, p0: leaf_norms(jax.tree.map(jnp.subtract, p, p0)))(p, make(key))
    return {"losses": losses, "grad_norms": grad_norms,
            "raw_grad_norms": raw_norms, "change_norms": np.asarray(change),
            "names": names}
