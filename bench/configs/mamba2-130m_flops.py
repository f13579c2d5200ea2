"""Model FLOPs a training token needs in mamba2-130m, from the shapes.

Counted at 2 FLOPs a multiply-add: the in-projection (z, x, B, C, dt), the
out-projection, the tied LM head, the depthwise causal conv, and the
chunked SSD with the published ``ngroups``: C B^T once per group over the
lower triangle of each chunk, the (C B^T * decay) x product over the lower
triangle per head, each chunk's state B^T x, the state passed between
chunks, and the incoming state's C h.  The backward pass needs twice the
forward's products.  Not counted: the embedding gather, norms, gates, the
decay exponentials, the masked upper triangles, a per-head broadcast of B
and C, and any recomputation.
"""


def forward_flops_per_token(conf: dict) -> float:
    d = conf["d_model"]
    din = conf["expand"] * d
    n, g, q = conf["d_state"], conf["ngroups"], conf["chunk_size"]
    heads = din // conf["headdim"]
    proj = d * (2 * din + 2 * g * n + heads) + din * d
    conv = conf["d_conv"] * (din + 2 * g * n)
    # per token of a chunk of q: position i meets i+1 positions, (q+1)/2 on average
    tri = (q + 1) / 2
    ssd = (g * n * tri          # C B^T, per group
           + din * tri          # (C B^T * decay) x, per head
           + din * n            # chunk state B^T x
           + din * n / q        # state passed to the next chunk
           + din * n)           # incoming state: C h
    head = d * conf["padded_vocab_size"]
    return 2.0 * (conf["n_layer"] * (proj + conv + ssd) + head)


def train_flops_per_token(conf: dict) -> float:
    return 3.0 * forward_flops_per_token(conf)
