"""RevPred's device path compiled for a described TPU v5e (no chip needed).

The TPU compiler refuses what interpret mode accepts: unsupported vector
shape casts, unaligned slices, too much VMEM.  These tests compile the LSTM
kernel at RevPred's own widths and the vmapped per-market forward the
provisioner dispatches, so such a refusal shows up here and not on the chip.
The topology is described inside a fixture: only the worker given this
file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import revpred as rp
from repro.core.market import DEFAULT_POOL
from repro.kernels import ops
from repro.kernels.lstm_cell import lstm_cell_pallas


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("B,I,H", [(1, 6, 32), (256, 6, 32), (1, 7, 32),
                                   (256, 32, 32)])
def test_lstm_cell_compiles_for_v5e(one_chip, B, I, H):
    s = lambda *shape: _spec(one_chip, shape)
    compiled = lstm_cell_pallas.lower(
        s(B, I), s(B, H), s(B, H), s(I, 4 * H), s(H, 4 * H), s(4 * H)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_stacked_revpred_forward_compiles_for_v5e(one_chip, monkeypatch):
    """The provisioner's one-dispatch pool forward: per-market params
    stacked over the pool, vmapped, every LSTM cell on the kernel."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    M = len(DEFAULT_POOL)
    params = jax.eval_shape(rp.init_revpred, jax.random.key(0))
    stacked = jax.tree.map(
        lambda l: _spec(one_chip, (M,) + l.shape, l.dtype), params)
    fwd = jax.jit(jax.vmap(rp.revpred_logits, in_axes=(0, 0, 0)))
    compiled = fwd.lower(
        stacked, _spec(one_chip, (M, 1, rp.HISTORY, rp.N_FEAT)),
        _spec(one_chip, (M, 1, rp.N_FEAT + 1))).compile()
    assert "tpu_custom_call" in compiled.as_text()
