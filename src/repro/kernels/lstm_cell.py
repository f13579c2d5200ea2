"""Fused LSTM cell Pallas kernel (RevPred's hot spot, paper §III-B).

One kernel fuses the two gate matmuls (x·W_ih + h·W_hh), the bias add, the
four gate nonlinearities and the state update — on GPU this is the cuDNN
fused cell; on TPU we tile the batch and hidden dims so every gate tile
lives in VMEM and both matmuls hit the MXU back-to-back.

Inside the kernel the weights are gate-major, (4, I, H) / (4, H, H) with the
bias as (4, 1, H), so each gate is two plain 2-D dots on one leading-axis
slice (gate order i,f,g,o — matches ref.lstm_cell_ref).  A (I, 4, H) layout
needs a 3-D dot and a (…, 4·bh) -> (…, 4, bh) shape cast that Mosaic
refuses at RevPred's widths.  Callers keep the (I, 4H) parameter layout;
``lstm_cell_pallas`` permutes once per call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, h_ref, c_ref, wih_ref, whh_ref, b_ref, h_out, c_out):
    x = x_ref[...].astype(jnp.float32)          # (bb, I)
    h = h_ref[...].astype(jnp.float32)          # (bb, H)

    def gate(g):                                # -> (bb, bh)
        return (jnp.dot(x, wih_ref[g].astype(jnp.float32))
                + jnp.dot(h, whh_ref[g].astype(jnp.float32))
                + b_ref[g].astype(jnp.float32))

    i = jax.nn.sigmoid(gate(0))
    f = jax.nn.sigmoid(gate(1))
    g = jnp.tanh(gate(2))
    o = jax.nn.sigmoid(gate(3))
    c2 = f * c_ref[...].astype(jnp.float32) + i * g
    h_out[...] = (o * jnp.tanh(c2)).astype(h_out.dtype)
    c_out[...] = c2.astype(c_out.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block_b", "block_h"))
def lstm_cell_pallas(x, h, c, w_ih, w_hh, b, interpret: bool = False,
                     block_b: int = 128, block_h: int = 128):
    """x (B, I); h, c (B, H); w_ih (I, 4H); w_hh (H, 4H); b (4H,).

    Any B: a batch larger than ``block_b`` is zero-padded up to a multiple
    of it and the padding rows are sliced off the result."""
    B, I = x.shape
    H = h.shape[1]
    bb = min(block_b, B)
    bh = min(block_h, H)
    assert H % bh == 0, (H, bh)
    Bp = -(-B // bb) * bb
    if Bp != B:
        pad = ((0, Bp - B), (0, 0))
        x, h, c = jnp.pad(x, pad), jnp.pad(h, pad), jnp.pad(c, pad)
    wih = w_ih.reshape(I, 4, H).transpose(1, 0, 2)
    whh = w_hh.reshape(H, 4, H).transpose(1, 0, 2)
    b3 = b.reshape(4, 1, H)

    out_shape = (jax.ShapeDtypeStruct((Bp, H), h.dtype),
                 jax.ShapeDtypeStruct((Bp, H), c.dtype))
    h2, c2 = pl.pallas_call(
        _kernel,
        grid=(Bp // bb, H // bh),
        in_specs=[
            pl.BlockSpec((bb, I), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, H), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, bh), lambda i, j: (i, j)),
            pl.BlockSpec((4, I, bh), lambda i, j: (0, 0, j)),
            pl.BlockSpec((4, H, bh), lambda i, j: (0, 0, j)),
            pl.BlockSpec((4, 1, bh), lambda i, j: (0, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bb, bh), lambda i, j: (i, j)),
            pl.BlockSpec((bb, bh), lambda i, j: (i, j)),
        ],
        out_shape=out_shape,
        interpret=interpret,
        name="lstm_cell",
    )(x, h, c, wih, whh, b3)
    return h2[:B], c2[:B]
