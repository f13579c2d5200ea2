"""Basic neural-net layers in pure JAX (no flax): norms, MLPs, RoPE, embeddings.

Parameters are plain nested dicts of jnp arrays.  Every ``init_*`` returns a
pytree; every ``apply``-style function is pure.  Compute runs in the config
dtype (bf16 by default) with fp32 norm/softmax accumulations.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def dtype_of(cfg):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------


def dense_init(key, in_dim: int, out_dim: int, dtype, scale: float = 1.0):
    """Truncated-normal fan-in init (lecun) as used by most LM stacks."""
    std = scale / np.sqrt(in_dim)
    return (jax.random.truncated_normal(key, -2.0, 2.0, (in_dim, out_dim)) * std).astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype):
    return (jax.random.normal(key, (vocab, dim)) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def init_rmsnorm(dim: int, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype=dtype)}


def rms_norm(x, params, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dt)


def init_layernorm(dim: int, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype=dtype), "bias": jnp.zeros((dim,), dtype=dtype)}


def layer_norm(x, params, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"] + params["bias"]).astype(dt)


# ---------------------------------------------------------------------------
# MLP (SwiGLU or plain GeLU)
# ---------------------------------------------------------------------------


def init_mlp(key, d_model: int, d_ff: int, gated: bool, dtype):
    ks = jax.random.split(key, 3)
    p = {
        "w_up": dense_init(ks[0], d_model, d_ff, dtype),
        "w_down": dense_init(ks[1], d_ff, d_model, dtype),
    }
    if gated:
        p["w_gate"] = dense_init(ks[2], d_model, d_ff, dtype)
    return p


def mlp(x, params, gated: bool):
    up = x @ params["w_up"]
    if gated:
        act = jax.nn.silu(x @ params["w_gate"]) * up
    else:
        act = jax.nn.gelu(up)
    return act @ params["w_down"]


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float):
    """Inverse frequencies for the even half of head_dim."""
    half = head_dim // 2
    return 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) / half))


def apply_rope(x, positions, theta: float):
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq).

    Uses the half-split convention (rotate [a,b] halves), matching llama.
    """
    head_dim = x.shape[-1]
    inv_freq = jnp.asarray(rope_frequencies(head_dim, theta))
    ang = positions[..., None].astype(jnp.float32) * inv_freq  # (..., seq, half)
    cos = jnp.cos(ang)[..., None, :]  # (..., seq, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# embeddings / unembedding
# ---------------------------------------------------------------------------


def init_embed(key, cfg):
    p = {"tok": embed_init(key, cfg.vocab_size, cfg.d_model, dtype_of(cfg))}
    if cfg.use_abs_pos:
        k2 = jax.random.fold_in(key, 1)
        p["pos"] = embed_init(k2, cfg.max_abs_pos, cfg.d_model, dtype_of(cfg))
    return p


def embed_tokens(params, tokens, cfg, positions=None):
    x = jnp.take(params["tok"], tokens, axis=0)
    if cfg.use_abs_pos:
        if positions is None:
            positions = jnp.arange(tokens.shape[-1])
        x = x + jnp.take(params["pos"], positions, axis=0)
    return x


def softmax_xent(logits, labels, mask):
    """Mean cross-entropy over the positions where ``mask`` is set, in float32.

    logits: (B, S, V) (V possibly sharded over 'model'); labels, mask: (B, S);
    labels at masked positions are ignored.  Both reductions over V are
    all-reduces of (B, S) scalars under SPMD.
    """
    logits32 = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits32, axis=-1)
    gold = jnp.take_along_axis(
        logits32, jnp.where(mask, labels, 0)[..., None], axis=-1)[..., 0]
    m = mask.astype(jnp.float32)
    return jnp.sum((lse - gold) * m) / jnp.maximum(jnp.sum(m), 1.0)
