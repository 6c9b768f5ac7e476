"""GOAP spike convolution as a static block-sparse Pallas TPU kernel.

TPU adaptation of the paper's GOAP dataflow (DESIGN.md §2):

* the 1-D conv is lowered to ``W'(OC, K=IC*KW) @ X'(K, OI)`` where X' is the
  binary shifted-input buffer (each non-zero weight's *enable map* is one
  row-slice of X');
* the non-zero structure of W' is compressed into (block_oc x block_k)
  tiles; only non-empty tiles execute, and each oc-tile row's tile list is
  **padded to a fixed length with explicit no-op tiles** — the direct TPU
  analogue of the paper's precomputed empty/extra iterations: a static
  schedule with zero dynamic control flow, so the grid shape (and therefore
  the pipeline) is compile-time fixed;
* tile k-indices are **scalar-prefetched** so the input-tile DMA for tile
  t+1 can be issued while tile t is in the MXU (compute/fetch overlap —
  the streaming-pipeline property of the paper's architecture);
* the {0,1} IFM tile is the gate: multiplying by a binary operand *is* the
  paper's enable-signal accumulation, executed 8x128-lane parallel.

VMEM budget per grid step: block (BO x BK) + input tile (BK x BOI) + output
tile (BO x BOI), all fp32 — with the default (8, 128, 128) tiling that is
8*128 + 128*128 + 8*128 floats = ~68 KB, far under the ~16 MB VMEM of a
TPU v5e core; BOI can be raised to 512 for wider layers.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret

__all__ = ["goap_conv_block_sparse"]


def _kernel(cols_ref, blocks_ref, x_ref, out_ref):
    """One (oc-tile, oi-tile, tile-slot) grid step: out += block @ x_tile."""
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # padded no-op tiles carry zero data (and point at k-tile 0): they
    # contribute nothing — the static-schedule trick, no conditionals.
    out_ref[...] += jnp.dot(
        blocks_ref[0, 0], x_ref[...], precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=out_ref.dtype,
    )


@functools.partial(
    jax.jit, static_argnames=("block_oc", "block_k", "block_oi", "interpret")
)
def goap_conv_block_sparse(
    blocks: jax.Array,      # (n_oc_tiles, max_tiles, BO, BK) tile data
    block_cols: jax.Array,  # (n_oc_tiles, max_tiles) int32 k-tile indices
    x: jax.Array,           # (K_padded, OI_padded) binary shift buffer
    *,
    block_oc: int,
    block_k: int,
    block_oi: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Returns currents (n_oc_tiles * BO, OI_padded) = block-sparse W' @ X'."""
    n_oc_tiles, max_tiles, bo, bk = blocks.shape
    assert (bo, bk) == (block_oc, block_k), (blocks.shape, block_oc, block_k)
    k_padded, oi_padded = x.shape
    assert k_padded % block_k == 0, (k_padded, block_k)
    assert oi_padded % block_oi == 0, (oi_padded, block_oi)
    n_oi_tiles = oi_padded // block_oi

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_oc_tiles, n_oi_tiles, max_tiles),
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_oc, block_k), lambda r, o, t, cols: (r, t, 0, 0)
            ),
            pl.BlockSpec(
                (block_k, block_oi), lambda r, o, t, cols: (cols[r, t], o)
            ),
        ],
        out_specs=pl.BlockSpec(
            (block_oc, block_oi), lambda r, o, t, cols: (r, o)
        ),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (n_oc_tiles * block_oc, oi_padded), blocks.dtype
        ),
        interpret=resolve_interpret(interpret),
        name="goap_conv_block_sparse",
    )(block_cols, blocks, x)
