"""Model: init / train-forward / prefill / decode for all assigned families.

Layers are stacked along a leading L axis and driven by ``lax.scan`` so the
HLO (and compile time) is depth-independent; ``ctx.remat`` wraps the scan body
in ``jax.checkpoint``.  The same code traces abstractly (eval_shape /
lower) for the multi-pod dry-run and concretely for the CPU smoke tests.

Families:
  dense / vlm       pre-norm GQA transformer (vlm: stub patch embeds prepended)
  moe               same skeleton, MoE FFN (+ MLA for deepseek-v2)
  ssm               mamba2 stack
  hybrid            mamba2 stack + one weight-shared attention block every
                    ``attn_every`` layers (zamba2)
  audio             whisper-style enc-dec (stub frame embeddings)
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention as attn_lib
from repro.models import blocks, layers, mla, ssd
from repro.models.context import ModelCtx, null_ctx


def _stacked_init(init_fn, key, n):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def _slice_tree(tree, lo, hi):
    return jax.tree.map(lambda a: a[lo:hi], tree)


class Model:
    def __init__(self, cfg):
        self.cfg = cfg

    # ------------------------------------------------------------------ init
    def init(self, key):
        cfg = self.cfg
        dt = layers.dtype_of(cfg)
        ks = jax.random.split(key, 8)
        p = {"embed": layers.init_embed(ks[0], cfg)}

        if cfg.family in ("dense", "vlm"):
            p["layers"] = _stacked_init(
                lambda k: blocks.init_block(k, cfg, moe_layer=False), ks[1], cfg.n_layers)
        elif cfg.family == "moe":
            n_moe = cfg.n_layers - cfg.first_k_dense
            if cfg.first_k_dense:
                p["dense_layers"] = _stacked_init(
                    lambda k: blocks.init_block(k, cfg, moe_layer=False),
                    ks[2], cfg.first_k_dense)
            p["moe_layers"] = _stacked_init(
                lambda k: blocks.init_block(k, cfg, moe_layer=True), ks[1], n_moe)
        elif cfg.family == "ssm":
            p["layers"] = _stacked_init(
                lambda k: blocks.init_mamba(k, cfg), ks[1], cfg.n_layers)
        elif cfg.family == "hybrid":
            p["mamba_layers"] = _stacked_init(
                lambda k: blocks.init_mamba(k, cfg), ks[1], cfg.n_layers)
            p["shared_block"] = blocks.init_block(ks[2], cfg, moe_layer=False)
        elif cfg.family == "audio":
            p["enc_pos"] = layers.embed_init(ks[3], cfg.enc_seq_len, cfg.d_model, dt)
            p["enc_layers"] = _stacked_init(
                lambda k: blocks.init_enc_block(k, cfg), ks[4], cfg.enc_layers)
            p["ln_enc"] = layers.init_layernorm(cfg.d_model)
            p["dec_layers"] = _stacked_init(
                lambda k: blocks.init_dec_block(k, cfg), ks[1], cfg.n_layers)
        else:
            raise ValueError(cfg.family)

        p["ln_f"] = (layers.init_layernorm(cfg.d_model) if cfg.family == "audio"
                     else layers.init_rmsnorm(cfg.d_model))
        if not cfg.tie_embeddings:
            p["unembed"] = layers.dense_init(ks[5], cfg.d_model, cfg.vocab_size, dt)
        return p

    # ------------------------------------------------------------- embedding
    def _embed_inputs(self, params, batch, ctx):
        """-> (x (B,S,D), positions (S,))."""
        cfg = self.cfg
        tokens = batch["tokens"]
        if cfg.family == "vlm":
            patches = batch["patch_embeds"].astype(layers.dtype_of(cfg))
            te = layers.embed_tokens(params["embed"], tokens, cfg)
            x = jnp.concatenate([patches, te], axis=1)
            S = x.shape[1]
        else:
            x = layers.embed_tokens(params["embed"], tokens, cfg)
            S = x.shape[1]
        positions = jnp.arange(S)
        return ctx.constrain(x, "residual"), positions

    def _unembed(self, params, x, ctx, rule="logits"):
        """Final norm and unembed; ``rule`` names the logits' sharding."""
        cfg = self.cfg
        x = (layers.layer_norm(x, params["ln_f"], cfg.norm_eps)
             if cfg.family == "audio" else layers.rms_norm(x, params["ln_f"], cfg.norm_eps))
        w = (params["embed"]["tok"].T if cfg.tie_embeddings else params["unembed"])
        return ctx.constrain(x @ w, rule)

    def _encode(self, params, batch, ctx):
        """Whisper encoder over stub frame embeddings."""
        cfg = self.cfg
        frames = batch["frames"].astype(layers.dtype_of(cfg))
        Se = frames.shape[1]
        x = frames + params["enc_pos"][None, :Se]
        x = ctx.constrain(x, "residual")
        positions = jnp.arange(Se)

        def body(x, lp):
            return blocks.enc_block_fwd(x, lp, cfg, ctx, positions), None

        body = self._maybe_remat(body, ctx)
        x, _ = jax.lax.scan(body, x, params["enc_layers"])
        return layers.layer_norm(x, params["ln_enc"], cfg.norm_eps)

    @staticmethod
    def _maybe_remat(body, ctx):
        if ctx.remat == "full":
            return jax.checkpoint(body, prevent_cse=False)
        return body

    # ----------------------------------------------------------- train fwd
    def forward(self, params, batch, ctx: Optional[ModelCtx] = None):
        """Full-sequence forward.  Returns (logits, aux_loss)."""
        ctx = ctx or null_ctx()
        x, aux = self._backbone(params, batch, ctx)
        return self._unembed(params, x, ctx), aux

    def _backbone(self, params, batch, ctx: ModelCtx):
        """Layer stack only — pre-final-norm hidden states.  Returns (x, aux)."""
        cfg = self.cfg
        x, positions = self._embed_inputs(params, batch, ctx)
        aux0 = jnp.zeros((), jnp.float32)

        if cfg.family in ("dense", "vlm"):
            def body(carry, lp):
                x, aux = carry
                x, a = blocks.block_fwd(x, lp, cfg, ctx, positions)
                return (x, aux + a), None
            body = self._maybe_remat(body, ctx)
            (x, aux), _ = jax.lax.scan(body, (x, aux0), params["layers"])

        elif cfg.family == "moe":
            def dbody(carry, lp):
                x, aux = carry
                x, a = blocks.block_fwd(x, lp, cfg, ctx, positions)
                return (x, aux + a), None
            dbody = self._maybe_remat(dbody, ctx)
            aux = aux0
            if cfg.first_k_dense:
                (x, aux), _ = jax.lax.scan(dbody, (x, aux), params["dense_layers"])
            (x, aux), _ = jax.lax.scan(dbody, (x, aux), params["moe_layers"])

        elif cfg.family == "ssm":
            def body(x, lp):
                return blocks.mamba_fwd(x, lp, cfg, ctx), None
            body = self._maybe_remat(body, ctx)
            x, _ = jax.lax.scan(body, x, params["layers"])
            aux = aux0

        elif cfg.family == "hybrid":
            def body(x, lp):
                return blocks.mamba_fwd(x, lp, cfg, ctx), None
            body = self._maybe_remat(body, ctx)
            for lo, hi in self._segments():
                x, _ = jax.lax.scan(body, x, _slice_tree(params["mamba_layers"], lo, hi))
                x, _ = blocks.block_fwd(x, params["shared_block"], cfg, ctx, positions)
            aux = aux0

        elif cfg.family == "audio":
            enc_out = self._encode(params, batch, ctx)
            def body(x, lp):
                return blocks.dec_block_fwd(x, lp, cfg, ctx, positions, enc_out), None
            body = self._maybe_remat(body, ctx)
            x, _ = jax.lax.scan(body, x, params["dec_layers"])
            aux = aux0
        else:
            raise ValueError(cfg.family)

        return x, aux

    def loss(self, params, batch, ctx: Optional[ModelCtx] = None):
        """Scalar LM loss (mean xent over labels >= 0) + MoE aux.

        Logits take rule "logits_sp" (sequence sharded over the model axis,
        vocab local): each device reduces its (B/d, S/m, V) block locally and
        the only logits collective is the unembed-weight gather.  A loss
        chunked by a scan would force a full activation gather ((B[data],
        S[model]) merges are inexpressible in SPMD)."""
        ctx = ctx or null_ctx()
        x, aux = self._backbone(params, batch, ctx)
        labels = batch["labels"]
        xe = layers.softmax_xent(self._unembed(params, x, ctx, "logits_sp"),
                                 labels, labels >= 0)
        return xe + aux, {"xent": xe, "aux": aux}

    def _segments(self):
        cfg = self.cfg
        segs, lo = [], 0
        while lo < cfg.n_layers:
            hi = min(lo + cfg.attn_every, cfg.n_layers)
            segs.append((lo, hi))
            lo = hi
        return segs

    @property
    def n_shared_invocations(self):
        return len(self._segments())

    # -------------------------------------------------------------- prefill
    def prefill(self, params, batch, ctx: Optional[ModelCtx] = None,
                cache_len: Optional[int] = None):
        """Process the prompt; return (last-position logits, decode cache).

        ``cache_len``: KV-cache capacity (>= prompt length); sequence-indexed
        cache leaves are right-padded to it so decode has free slots."""
        cfg = self.cfg
        ctx = ctx or null_ctx()
        x, positions = self._embed_inputs(params, batch, ctx)

        if cfg.family in ("dense", "vlm", "moe"):
            def body(x, lp):
                return blocks.block_prefill(x, lp, cfg, ctx, positions)
            caches = []
            if cfg.family == "moe":
                if cfg.first_k_dense:
                    x, c_dense = jax.lax.scan(body, x, params["dense_layers"])
                    caches.append(("dense", c_dense))
                x, c_moe = jax.lax.scan(body, x, params["moe_layers"])
                caches.append(("moe", c_moe))
                cache = dict(caches)
            else:
                x, cache = jax.lax.scan(body, x, params["layers"])

        elif cfg.family == "ssm":
            def body(x, lp):
                return blocks.mamba_prefill(x, lp, cfg, ctx)
            x, cache = jax.lax.scan(body, x, params["layers"])

        elif cfg.family == "hybrid":
            def body(x, lp):
                return blocks.mamba_prefill(x, lp, cfg, ctx)
            m_caches, a_caches = [], []
            for lo, hi in self._segments():
                x, mc = jax.lax.scan(body, x, _slice_tree(params["mamba_layers"], lo, hi))
                m_caches.append(mc)
                x, ac = blocks.block_prefill(x, params["shared_block"], cfg, ctx, positions)
                a_caches.append(ac)
            cache = {
                "mamba": jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *m_caches),
                "attn": jax.tree.map(lambda *xs: jnp.stack(xs, 0), *a_caches),
            }

        elif cfg.family == "audio":
            enc_out = self._encode(params, batch, ctx)
            def body(x, lp):
                return blocks.dec_block_prefill(x, lp, cfg, ctx, positions, enc_out)
            x, cache = jax.lax.scan(body, x, params["dec_layers"])
        else:
            raise ValueError(cfg.family)

        if cache_len is not None:
            cache = _pad_cache_to(cache, cache_len)
        logits = self._unembed(params, x[:, -1:], ctx)
        return logits, cache

    # --------------------------------------------------------------- decode
    def decode_step(self, params, cache, tokens, pos, ctx: Optional[ModelCtx] = None):
        """One token step.  tokens (B,1); pos scalar int32 (insert position).
        Returns (logits (B,1,V), new cache)."""
        cfg = self.cfg
        ctx = ctx or null_ctx()
        B = tokens.shape[0]
        x = layers.embed_tokens(params["embed"], tokens, cfg,
                                positions=jnp.full((1,), pos, jnp.int32)
                                if cfg.use_abs_pos else None)

        if cfg.family in ("dense", "vlm", "moe"):
            def body(x, xs):
                lp, c = xs
                x, c = blocks.block_decode(x, lp, cfg, ctx, c, pos)
                return x, c
            if cfg.family == "moe":
                new_cache = {}
                if cfg.first_k_dense:
                    x, new_cache["dense"] = jax.lax.scan(
                        body, x, (params["dense_layers"], cache["dense"]))
                x, new_cache["moe"] = jax.lax.scan(
                    body, x, (params["moe_layers"], cache["moe"]))
                cache = new_cache
            else:
                x, cache = jax.lax.scan(body, x, (params["layers"], cache))

        elif cfg.family == "ssm":
            def body(x, xs):
                lp, c = xs
                x, c = blocks.mamba_decode(x, lp, cfg, ctx, c)
                return x, c
            x, cache = jax.lax.scan(body, x, (params["layers"], cache))

        elif cfg.family == "hybrid":
            def body(x, xs):
                lp, c = xs
                x, c = blocks.mamba_decode(x, lp, cfg, ctx, c)
                return x, c
            m_new, a_new = [], []
            for i, (lo, hi) in enumerate(self._segments()):
                x, mc = jax.lax.scan(
                    body, x, (_slice_tree(params["mamba_layers"], lo, hi),
                              _slice_tree(cache["mamba"], lo, hi)))
                m_new.append(mc)
                ac = _slice_tree(cache["attn"], i, i + 1)
                ac = jax.tree.map(lambda a: a[0], ac)
                x, ac = blocks.block_decode(x, params["shared_block"], cfg, ctx, ac, pos)
                a_new.append(ac)
            cache = {
                "mamba": jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *m_new),
                "attn": jax.tree.map(lambda *xs: jnp.stack(xs, 0), *a_new),
            }

        elif cfg.family == "audio":
            def body(x, xs):
                lp, c = xs
                x, c = blocks.dec_block_decode(x, lp, cfg, ctx, c, pos)
                return x, c
            x, cache = jax.lax.scan(body, x, (params["dec_layers"], cache))
        else:
            raise ValueError(cfg.family)

        return self._unembed(params, x, ctx), cache


_SEQ_CACHE_KEYS = ("k", "v", "c_kv", "k_rope")  # leaves with a seq axis at dim 2


def _pad_cache_to(cache, cache_len: int):
    """Right-pad sequence-indexed cache leaves (stacked layout (L, B, S, ...))
    to ``cache_len``.  SSM states / conv windows / cross-attn K,V untouched."""
    def rec(node):
        if isinstance(node, dict):
            out = {}
            for key, val in node.items():
                if key in _SEQ_CACHE_KEYS and not isinstance(val, dict):
                    pad = cache_len - val.shape[2]
                    if pad > 0:
                        widths = [(0, 0)] * val.ndim
                        widths[2] = (0, pad)
                        val = jnp.pad(val, widths)
                    out[key] = val
                else:
                    out[key] = rec(val)
            return out
        return node
    return rec(cache)


# ---------------------------------------------------------------------------
# analytic accounting (params / model flops) via eval_shape — zero allocation
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=64)
def _param_shapes(cfg):
    model = Model(cfg)
    return jax.eval_shape(model.init, jax.random.key(0))


def count_params_analytic(cfg, active_only: bool = False) -> int:
    shapes = _param_shapes(cfg)
    total = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
    if active_only and cfg.n_experts > 0:
        routed = 0
        moe_stack = shapes.get("moe_layers", {})
        for name in ("w_gate", "w_up", "w_down"):
            for lf in jax.tree.leaves(
                    jax.tree.map(lambda x: x, _find(moe_stack, name))):
                routed += int(np.prod(lf.shape))
        frac = cfg.experts_per_tok / cfg.n_experts
        total = total - routed + int(routed * frac)
    return total


def _find(tree, name):
    """Collect subtrees under keys == name."""
    out = []
    def rec(t):
        if isinstance(t, dict):
            for k, v in t.items():
                if k == name:
                    out.append(v)
                else:
                    rec(v)
    rec(tree)
    return out


def matmul_param_count(cfg) -> int:
    """Params that participate in per-token matmuls (MoE: active only;
    embedding gather excluded; tied unembed counted once as a matmul)."""
    shapes = _param_shapes(cfg)
    total = count_params_analytic(cfg, active_only=True)
    embed = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes["embed"]))
    total -= embed
    if cfg.tie_embeddings:
        total += cfg.d_model * cfg.vocab_size
    return total


def model_flops(cfg, shape, kind: Optional[str] = None) -> float:
    """MODEL_FLOPS = 6·N_active·D (train) / 2·N_active·D (inference).

    Attention score FLOPs are deliberately excluded (standard 6ND convention);
    the HLO/MODEL ratio in the roofline table surfaces that overhead.
    Whisper adds the encoder term over its frame length.
    """
    kind = kind or shape.kind
    n = matmul_param_count(cfg)
    mult = 6.0 if kind == "train" else 2.0
    toks = shape.tokens
    fl = mult * n * toks
    if cfg.is_encoder_decoder and kind != "decode":
        shapes = _param_shapes(cfg)
        enc_n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes["enc_layers"]))
        fl += mult * enc_n * cfg.enc_seq_len * shape.global_batch
    return fl
