"""The configurations' FLOPs-a-token functions against XLA's own count.

XLA's CPU ``cost_analysis()`` of a forward-only jit of the program's model
on the reduced preset counts every operation the compiled forward runs.
The functions count only what the model needs, so the comparison first adds
back what they leave out on purpose: the masked upper triangle of causal
attention and of each SSD chunk, and SSD's C B^T made once per head rather
than once per group.  The rest of XLA's count is elementwise work (norms,
softmax, rotary, gates, decays) that the functions do not count; at these
sizes it is under a quarter of the total.  One layer and one chunk, because
XLA counts a loop's body once whatever its trip count.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from bench_testlib import ROOT

from bench.harness import load_module
from repro.configs.base import get_config
from repro.models.context import null_ctx
from repro.models.model import Model

CONFIGS = ROOT / "bench" / "configs"
ELEMENTWISE = 0.25
BATCH = 2


def xla_forward_flops(cfg, seq):
    model = Model(cfg)
    params = jax.eval_shape(model.init, jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((BATCH, seq), jnp.int32)
    ctx = null_ctx(attn_chunk=seq, remat="none")
    cost = jax.jit(lambda p, t: model.forward(p, {"tokens": t}, ctx)[0]).lower(
        params, tokens).compile().cost_analysis()
    return (cost[0] if isinstance(cost, list) else cost)["flops"]


def qwen_case(seq):
    cfg = dataclasses.replace(get_config("qwen1.5-0.5b", reduced=True), n_layers=1)
    conf = {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "intermediate_size": cfg.d_ff, "num_hidden_layers": 1,
            "vocab_size": cfg.vocab_size, "seq": seq}
    masked = 2 * 2 * cfg.n_heads * cfg.head_dim * (seq * seq - seq * (seq + 1) / 2)
    return cfg, conf, masked / seq


def mamba_case(seq):
    cfg = dataclasses.replace(get_config("mamba2-130m", reduced=True), n_layers=1,
                              ssm_chunk=seq)
    conf = {"d_model": cfg.d_model, "expand": cfg.ssm_expand, "d_state": cfg.ssm_state,
            "ngroups": cfg.ssm_groups, "chunk_size": seq, "headdim": cfg.ssm_headdim,
            "d_conv": cfg.conv_kernel, "n_layer": 1, "padded_vocab_size": cfg.vocab_size,
            "seq": seq}
    h, n, g, din = cfg.ssm_nheads, cfg.ssm_state, cfg.ssm_groups, cfg.ssm_d_inner
    tri = (seq + 1) / 2
    # full-chunk C B^T per head, and the full chunk's scores times x
    left_out = 2 * (h * n * seq - g * n * tri) + 2 * (din * seq - din * tri)
    return cfg, conf, left_out


@pytest.mark.parametrize("name,case", [("qwen1.5-0.5b", qwen_case),
                                       ("mamba2-130m", mamba_case)])
@pytest.mark.parametrize("seq", [64, 256])
def test_flops_match_xla_forward(name, case, seq):
    flops = load_module(CONFIGS / f"{name}_flops.py")
    cfg, conf, left_out_per_token = case(seq)
    ours = (flops.forward_flops_per_token(conf) + left_out_per_token) * BATCH * seq
    xla = xla_forward_flops(cfg, seq)
    assert ours <= xla <= ours * (1 + ELEMENTWISE), (xla, ours)
    assert flops.train_flops_per_token(conf) == 3 * flops.forward_flops_per_token(conf)


def test_qwen_full_width_count():
    """6 x 463.9M matmul parameters (LM head in, embedding out) plus causal
    attention at seq 512: about 2.86 GFLOP a training token."""
    import json

    conf = json.loads((CONFIGS / "qwen1.5-0.5b.json").read_text())
    flops = load_module(CONFIGS / "qwen1.5-0.5b_flops.py")
    matmul_params = 24 * (4 * 1024 * 1024 + 3 * 1024 * 2816) + 1024 * 151936
    assert matmul_params == 463_863_808
    attention = 6 * 24 * 1024 * (512 + 1)
    assert flops.train_flops_per_token(conf) == pytest.approx(
        6 * matmul_params + attention, rel=1e-12)
    assert flops.train_flops_per_token(conf) == pytest.approx(2.86e9, rel=2e-3)


def test_mamba2_full_width_count():
    """0.77 GFLOP a token in the projections and tied head, the rest in the
    conv and the SSD with one group."""
    import json

    conf = json.loads((CONFIGS / "mamba2-130m.json").read_text())
    flops = load_module(CONFIGS / "mamba2-130m_flops.py")
    matmul_params = 24 * (768 * (2 * 1536 + 2 * 128 + 24) + 1536 * 768) + 768 * 50288
    assert 6 * matmul_params == pytest.approx(0.773e9, rel=1e-3)
    assert flops.train_flops_per_token(conf) == pytest.approx(0.861e9, rel=1e-3)
