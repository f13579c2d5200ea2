"""Backend-aware dispatch wrappers around the Pallas kernels.

On TPU the Pallas kernels run natively; on CPU (this container) the pure-jnp
oracle runs instead, with ``interpret=True`` available for kernel validation
(tests execute the Pallas body in the interpreter and compare to ref).
"""

from __future__ import annotations

import functools

import jax

from repro.kernels import ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def lstm_cell(x, h, c, w_ih, w_hh, b, force: str | None = None):
    """Fused LSTM cell.  force: None (auto) | 'ref' | 'pallas' | 'interpret'.

    Differentiable in every mode: the kernel paths take their gradient from
    the jnp reference (``_lstm_cell_kernel``'s custom VJP)."""
    mode = force or ("pallas" if _on_tpu() else "ref")
    if mode == "ref":
        return ref.lstm_cell_ref(x, h, c, w_ih, w_hh, b)
    return _lstm_cell_kernel(x, h, c, w_ih, w_hh, b, mode == "interpret")


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _lstm_cell_kernel(x, h, c, w_ih, w_hh, b, interpret):
    from repro.kernels import lstm_cell as klc

    return klc.lstm_cell_pallas(x, h, c, w_ih, w_hh, b, interpret=interpret)


def _lstm_cell_kernel_fwd(x, h, c, w_ih, w_hh, b, interpret):
    out = _lstm_cell_kernel(x, h, c, w_ih, w_hh, b, interpret)
    return out, (x, h, c, w_ih, w_hh, b)


def _lstm_cell_kernel_bwd(interpret, res, g):
    # pallas_call has no VJP: differentiate the oracle at the saved inputs
    _, vjp = jax.vjp(ref.lstm_cell_ref, *res)
    return vjp(g)


_lstm_cell_kernel.defvjp(_lstm_cell_kernel_fwd, _lstm_cell_kernel_bwd)


def flash_attention(q, k, v, causal: bool = True, force: str | None = None,
                    block_q: int = 128, block_k: int = 128):
    mode = force or ("pallas" if _on_tpu() else "ref")
    if mode == "ref":
        return ref.flash_attention_ref(q, k, v, causal)
    from repro.kernels import flash_attention as kfa

    return kfa.flash_attention_pallas(q, k, v, causal=causal,
                                      block_q=block_q, block_k=block_k,
                                      interpret=(mode == "interpret"))


def ssd_chunk(x, dt, A, B_in, C_in, state, force: str | None = None):
    mode = force or ("pallas" if _on_tpu() else "ref")
    if mode == "ref":
        return ref.ssd_chunk_ref(x, dt, A, B_in, C_in, state)
    from repro.kernels import ssd_scan as kss

    return kss.ssd_chunk_pallas(x, dt, A, B_in, C_in, state,
                                interpret=(mode == "interpret"))
