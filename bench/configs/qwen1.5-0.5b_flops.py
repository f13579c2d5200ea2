"""Model FLOPs a training token needs in qwen1.5-0.5b, from the shapes.

Counted: every matrix product of the forward pass (q, k, v, o, the SwiGLU
MLP, the LM head) at 2 FLOPs a multiply-add, and causal attention's QK^T
and PV over the lower triangle only (key j <= query i).  The backward pass
needs twice the forward's products.  Not counted: the embedding (a gather),
norms, rotary, softmax, biases and other elementwise work, the masked upper
triangle, and any recomputation.
"""


def forward_flops_per_token(conf: dict) -> float:
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    kv, f = conf["num_key_value_heads"], conf["intermediate_size"]
    dh, seq = conf["head_dim"], conf["seq"]
    proj = d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * f
    # causal: query i meets i+1 keys, (seq+1)/2 on average, in QK^T and PV
    attn = 2 * h * dh * (seq + 1) / 2
    head = d * conf["vocab_size"]
    return 2.0 * (conf["num_hidden_layers"] * (proj + attn) + head)


def train_flops_per_token(conf: dict) -> float:
    return 3.0 * forward_flops_per_token(conf)
