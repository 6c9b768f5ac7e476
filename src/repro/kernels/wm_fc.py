"""Weight-masked FC layer as a tiled Pallas TPU matmul kernel.

Paper §III-B: FC weights carry a 1-bit mask; ``FM = IFM AND WM`` selects
the weights that are actually fetched/accumulated.  On TPU the mask is
folded into the stored weight matrix (zeros stay zero) and the binary spike
activations make every multiply a gate: the kernel is a standard
MXU-aligned tiled matmul whose *lhs is {0,1}* — the fetch-traffic win
(1-bit activations) is modeled by the cost layer, the compute win comes
from the batched formulation (B x IN) @ (IN x OUT) keeping the MXU busy.

Grid: (B-tiles, OUT-tiles, IN-tiles) with the reduction dimension minor so
each output tile accumulates in VMEM across IN-tiles (revisiting pattern).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import resolve_interpret

__all__ = ["wm_fc_matmul"]


def _kernel(s_ref, w_ref, out_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jnp.dot(
        s_ref[...], w_ref[...], precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=out_ref.dtype,
    )


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_in", "block_out", "interpret")
)
def wm_fc_matmul(
    spikes: jax.Array,   # (B, IN) binary {0,1}
    weights: jax.Array,  # (IN, OUT) masked weights (zeros pruned)
    *,
    block_b: int = 8,
    block_in: int = 128,
    block_out: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    b, d_in = spikes.shape
    d_in2, d_out = weights.shape
    assert d_in == d_in2, (spikes.shape, weights.shape)

    pad_b = (-b) % block_b
    pad_in = (-d_in) % block_in
    pad_out = (-d_out) % block_out
    s = jnp.pad(spikes.astype(weights.dtype), ((0, pad_b), (0, pad_in)))
    w = jnp.pad(weights, ((0, pad_in), (0, pad_out)))

    grid = (s.shape[0] // block_b, w.shape[1] // block_out, s.shape[1] // block_in)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_in), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_in, block_out), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_b, block_out), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((s.shape[0], w.shape[1]), weights.dtype),
        interpret=resolve_interpret(interpret),
        name="wm_fc_matmul",
    )(s, w)
    return out[:b, :d_out]
