"""Plain reference of Mamba-2 (arXiv:2405.21060) for training, float32.

Written from the paper and its minimal SSD listing (``ssd_minimal_discrete``):
per layer pre-RMSNorm and a Mamba2 mixer, residual; final RMSNorm; LM head
tied to the embedding; mean next-token cross-entropy.  The mixer projects
to z, x, B, C and dt; runs x, B and C through a causal depthwise conv with
bias and SiLU; dt = softplus(dt + dt_bias); A = -exp(A_log); y = SSD(x dt,
A dt, B, C) + D x, computed by chunks of ``chunk_size``; then the gated
RMSNorm rmsnorm(y * silu(z)) and the output projection.  B and C have
``ngroups`` groups shared by the heads of a group: the contraction C B^T is
made once per group, not once per head.

Parameters use the layout the trial runtime keeps (the in-projection held
as its five segments, the conv as its three, per-layer leaves stacked on a
leading layer axis, matrices (in, out)), so the benchmark's weights can be
handed to it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _sizes(conf):
    d = conf["d_model"]
    din = conf["expand"] * d
    return (d, din, din // conf["headdim"], conf["headdim"], conf["d_state"],
            conf["ngroups"], conf["d_conv"], conf["n_layer"], conf["padded_vocab_size"])


def init_params(conf, key):
    d, din, h, p, n, g, k, layers, vocab = _sizes(conf)
    ks = iter(jax.random.split(key, 16))
    bf16, f32 = jnp.bfloat16, jnp.float32

    def normal(shape):
        return (0.02 * jax.random.normal(next(ks), shape, f32)).astype(bf16)

    def uniform(shape, lo, hi, dtype=f32):
        return jax.random.uniform(next(ks), shape, f32, lo, hi).astype(dtype)

    def conv(ch):
        return {"w": uniform((layers, ch, k), -0.5, 0.5, bf16),
                "b": uniform((layers, ch), -0.5, 0.5, bf16)}

    dt = jnp.exp(uniform((layers, h), jnp.log(1e-3), jnp.log(1e-1)))
    mixer = {
        "wz": normal((layers, d, din)), "wx": normal((layers, d, din)),
        "wB": normal((layers, d, g * n)), "wC": normal((layers, d, g * n)),
        "wdt": normal((layers, d, h)),
        "conv_x": conv(din), "conv_B": conv(g * n), "conv_C": conv(g * n),
        "A_log": jnp.log(uniform((layers, h), 1.0, 16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "D_skip": jnp.ones((layers, h), f32),
        "gate_norm": {"scale": jnp.ones((layers, din), f32)},
        "wo": normal((layers, din, d)),
    }
    return {"embed": {"tok": normal((vocab, d))},
            "layers": {"ln": {"scale": jnp.ones((layers, d), f32)}, "mixer": mixer},
            "ln_f": {"scale": jnp.ones((d,), f32)}}


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def causal_conv(x, w, b, ein):
    """x (B, S, C); w (C, K): y[t] = b + sum_i w[:, i] x[t - (K-1) + i],
    a contraction over the K taps like every other."""
    k = w.shape[-1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    taps = jnp.stack([xp[:, i: i + x.shape[1]] for i in range(k)], axis=-1)
    return b + ein("bsck,ck->bsc", taps, w)


def segsum(x):
    """x (..., T) -> (..., T, T): sum of x[j+1..i] below the diagonal, -inf above."""
    t = x.shape[-1]
    xx = jnp.where(jnp.tril(jnp.ones((t, t), bool), -1), x[..., :, None], 0.0)
    out = jnp.cumsum(xx, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), out, -jnp.inf)


def ssd(x, a, b, c, q, ein):
    """Chunked SSD.  x (B, S, H, P) already times dt; a (B, S, H) = A dt;
    b, c (B, S, G, N).  Returns y (B, S, H, P)."""
    bb, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    nc = s // q
    x = x.reshape(bb, nc, q, g, h // g, p)
    a = a.reshape(bb, nc, q, h).transpose(0, 3, 1, 2)          # (B, H, C, L)
    b = b.reshape(bb, nc, q, g, n)
    c = c.reshape(bb, nc, q, g, n)
    cum = jnp.cumsum(a, axis=-1)
    decay = jnp.exp(segsum(a)).reshape(bb, g, h // g, nc, q, q)  # (B,G,E,C,L,S)
    # diagonal blocks: (C B^T per group) * decay, times x
    cb = ein("bclgn,bcsgn->bgcls", c, b)
    scores = cb[:, :, None] * decay                              # (B,G,E,C,L,S)
    y_diag = ein("bgecls,bcsgep->bclgep", scores, x)
    # each chunk's final state
    w_end = jnp.exp(cum[..., -1:] - cum).reshape(bb, g, h // g, nc, q)
    xw = x * w_end.transpose(0, 3, 4, 1, 2)[..., None]
    states = ein("bclgn,bclgep->bcgepn", b, xw)
    # pass the states between chunks
    chunk_a = jnp.pad(cum[..., -1], ((0, 0), (0, 0), (1, 0)))   # (B, H, C+1)
    dchunk = jnp.exp(segsum(chunk_a)).reshape(bb, g, h // g, nc + 1, nc + 1)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    new = ein("bgezc,bcgepn->bzgepn", dchunk, states)[:, :-1]
    # the incoming state's contribution inside each chunk
    w_in = jnp.exp(cum).reshape(bb, g, h // g, nc, q).transpose(0, 3, 4, 1, 2)
    y_off = ein("bclgn,bcgepn->bclgep", c, new) * w_in[..., None]
    return (y_diag + y_off).reshape(bb, s, h, p)


def mixer(u, mp, conf, ein):
    d, din, h, p, n, g, k, _, _ = _sizes(conf)
    bb, s, _ = u.shape
    z = ein("bsd,de->bse", u, mp["wz"])
    x = jax.nn.silu(causal_conv(ein("bsd,de->bse", u, mp["wx"]),
                                mp["conv_x"]["w"], mp["conv_x"]["b"], ein))
    b = jax.nn.silu(causal_conv(ein("bsd,de->bse", u, mp["wB"]),
                                mp["conv_B"]["w"], mp["conv_B"]["b"], ein))
    c = jax.nn.silu(causal_conv(ein("bsd,de->bse", u, mp["wC"]),
                                mp["conv_C"]["w"], mp["conv_C"]["b"], ein))
    dt = jax.nn.softplus(ein("bsd,dh->bsh", u, mp["wdt"]) + mp["dt_bias"])
    a = -jnp.exp(mp["A_log"])
    x4 = x.reshape(bb, s, h, p)
    y = ssd(x4 * dt[..., None], a * dt, b.reshape(bb, s, g, n),
            c.reshape(bb, s, g, n), conf["chunk_size"], ein)
    y = (y + mp["D_skip"][:, None] * x4).reshape(bb, s, din)
    y = rms_norm(y * jax.nn.silu(z), mp["gate_norm"]["scale"], conf["norm_epsilon"])
    return ein("bse,ed->bsd", y, mp["wo"])


def loss(params, batch, conf, ein):
    """Mean next-token cross-entropy; float32 throughout."""
    eps = conf["norm_epsilon"]
    x = params["embed"]["tok"][batch["tokens"]]

    def body(x, lp):
        return x + mixer(rms_norm(x, lp["ln"]["scale"], eps), lp["mixer"], conf, ein), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["layers"])
    h = rms_norm(x, params["ln_f"]["scale"], eps)
    w = params["embed"]["tok"].T

    @jax.checkpoint
    def row(hl):
        hr, lr = hl
        logits = ein("sd,dv->sv", hr, w)
        gold = jnp.take_along_axis(logits, lr[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)

    total = jnp.sum(jax.lax.map(row, (h, batch["labels"])))
    return total / batch["labels"].size
