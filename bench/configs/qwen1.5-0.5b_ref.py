"""Plain reference of Qwen1.5 (the Qwen2 layout) for training, float32.

Written from the published architecture (Qwen2ForCausalLM): token
embedding; per layer pre-RMSNorm, causal multi-head attention with q/k/v
biases and rotary positions (rotate-half), pre-RMSNorm SwiGLU MLP, both
residual; final RMSNorm; untied LM head; mean next-token cross-entropy.

Parameters use the layout the trial runtime keeps (per-layer leaves stacked
on a leading layer axis, matrices stored as (in, out)), so the benchmark's
weights can be handed to it.  Matrices and the embedding are made in
bfloat16, norm scales in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _sizes(conf):
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return (d, h, conf["num_key_value_heads"], conf["head_dim"], conf["intermediate_size"],
            conf["num_hidden_layers"], conf["vocab_size"])


def init_params(conf, key):
    d, h, kv, dh, f, n, vocab = _sizes(conf)
    ks = iter(jax.random.split(key, 16))
    bf16, f32 = jnp.bfloat16, jnp.float32

    def normal(shape):
        return (0.02 * jax.random.normal(next(ks), shape, f32)).astype(bf16)

    attn = {"wq": normal((n, d, h * dh)), "wk": normal((n, d, kv * dh)),
            "wv": normal((n, d, kv * dh)), "wo": normal((n, h * dh, d))}
    if conf["qkv_bias"]:
        attn.update(bq=jnp.zeros((n, h * dh), bf16), bk=jnp.zeros((n, kv * dh), bf16),
                    bv=jnp.zeros((n, kv * dh), bf16))
    params = {
        "embed": {"tok": normal((vocab, d))},
        "layers": {
            "ln1": {"scale": jnp.ones((n, d), f32)},
            "attn": attn,
            "ln2": {"scale": jnp.ones((n, d), f32)},
            "mlp": {"w_up": normal((n, d, f)), "w_gate": normal((n, d, f)),
                    "w_down": normal((n, f, d))},
        },
        "ln_f": {"scale": jnp.ones((d,), f32)},
    }
    if not conf["tie_word_embeddings"]:
        params["unembed"] = normal((d, vocab))
    return params


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x (B, S, H, dh): rotate-half rotary embedding at positions 0..S-1."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def layer(x, lp, conf, ein):
    d, h, kv, dh, f, n, vocab = _sizes(conf)
    eps = conf["rms_norm_eps"]
    B, S, _ = x.shape
    a = lp["attn"]
    hn = rms_norm(x, lp["ln1"]["scale"], eps)
    q = ein("bsd,de->bse", hn, a["wq"])
    k = ein("bsd,de->bse", hn, a["wk"])
    v = ein("bsd,de->bse", hn, a["wv"])
    if conf["qkv_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = rope(q.reshape(B, S, h, dh), conf["rope_theta"])
    k = rope(k.reshape(B, S, kv, dh), conf["rope_theta"])
    v = v.reshape(B, S, kv, dh)
    k, v = jnp.repeat(k, h // kv, axis=2), jnp.repeat(v, h // kv, axis=2)
    s = ein("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(dh))
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = ein("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v).reshape(B, S, h * dh)
    x = x + ein("bse,ed->bsd", o, a["wo"])
    hn = rms_norm(x, lp["ln2"]["scale"], eps)
    mp = lp["mlp"]
    act = jax.nn.silu(ein("bsd,df->bsf", hn, mp["w_gate"])) * ein("bsd,df->bsf", hn, mp["w_up"])
    return x + ein("bsf,fd->bsd", act, mp["w_down"])


def loss(params, batch, conf, ein):
    """Mean next-token cross-entropy; float32 throughout."""
    x = params["embed"]["tok"][batch["tokens"]]
    body = jax.checkpoint(lambda x, lp: (layer(x, lp, conf, ein), None))
    x, _ = jax.lax.scan(body, x, params["layers"])
    h = rms_norm(x, params["ln_f"]["scale"], conf["rms_norm_eps"])
    w = params["embed"]["tok"].T if conf["tie_word_embeddings"] else params["unembed"]

    @jax.checkpoint
    def row(hl):
        hr, lr = hl
        logits = ein("sd,dv->sv", hr, w)
        gold = jnp.take_along_axis(logits, lr[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)

    total = jnp.sum(jax.lax.map(row, (h, batch["labels"])))
    return total / batch["labels"].size
