"""Jit'd public wrappers around the Pallas kernels.

These are the entry points the model/serving layers use: they accept the
framework-level objects (``BlockSparseKernel``, dense IFMs, ``LIFParams``)
and handle the padding/layout plumbing around the raw kernels.

The kernels compile on a TPU and run in interpret mode on the CPU
(:mod:`repro.kernels.platform`).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.goap import build_shift_buffer
from repro.core.lif import LIFParams
from repro.core.sparse_format import BlockSparseKernel

from .goap_conv import goap_conv_block_sparse
from .lif_update import lif_update_fused
from .wm_fc import wm_fc_matmul

__all__ = ["goap_conv_op", "wm_fc_op", "lif_op"]


def goap_conv_op(
    ifm: jax.Array,            # (IC, WI) binary, pre-padded for the conv
    bs: BlockSparseKernel,
    *,
    block_oi: int = 128,
) -> jax.Array:
    """Sparse conv currents (OC, OI) via the block-sparse GOAP kernel."""
    ic, wi = ifm.shape
    assert ic == bs.ic, (ic, bs.ic)
    oi = wi - bs.kw + 1
    x = build_shift_buffer(ifm, bs.kw).astype(jnp.float32)  # (K, OI)
    # pad K to the blocked reduction size and OI to the lane tile
    pad_k = bs.padded_k - x.shape[0]
    pad_oi = (-oi) % block_oi
    x = jnp.pad(x, ((0, pad_k), (0, pad_oi)))
    out = goap_conv_block_sparse(
        jnp.asarray(bs.blocks, jnp.float32),
        jnp.asarray(bs.block_cols),
        x,
        block_oc=bs.block_oc,
        block_k=bs.block_k,
        block_oi=block_oi,
    )
    return out[: bs.oc, :oi]


def wm_fc_op(
    spikes: jax.Array,   # (B, IN) or (IN,) binary
    weights: jax.Array,  # (IN, OUT) masked weights
) -> jax.Array:
    squeeze = spikes.ndim == 1
    s = spikes[None] if squeeze else spikes
    out = wm_fc_matmul(s, weights)
    return out[0] if squeeze else out


def lif_op(
    currents: jax.Array,  # (T, ...) input currents
    params: LIFParams,
    v0: jax.Array | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused LIF over time for arbitrary neuron shape; returns (spikes, v_fin)."""
    t = currents.shape[0]
    neuron_shape = currents.shape[1:]
    cur = currents.reshape(t, -1)
    n = cur.shape[1]
    full = lambda p: jnp.broadcast_to(p, neuron_shape).reshape(-1)
    v0f = jnp.zeros((n,), cur.dtype) if v0 is None else v0.reshape(-1)
    spikes, v_fin = lif_update_fused(
        cur, v0f, full(params.alpha), full(params.theta), full(params.v_th))
    return spikes.reshape((t,) + neuron_shape), v_fin.reshape(neuron_shape)
