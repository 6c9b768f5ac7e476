"""Production mesh definitions (TPU v5e target).

Functions, not module-level constants: importing this module never touches
jax device state, so tests/benches keep their 1-CPU view and only
``dryrun.py`` (which sets ``xla_force_host_platform_device_count=512``
before any jax import) ever builds the full meshes.

Axes:
    single-pod  (16, 16)      -> ("data", "model")       256 chips
    multi-pod   (2, 16, 16)   -> ("pod", "data", "model") 512 chips

``pod`` composes with ``data`` for batch sharding (pure DP across pods —
gradient all-reduce is the only cross-pod collective, matching the
slow-inter-pod/fast-intra-pod DCN/ICI hierarchy).  ``model`` carries
TP/SP/EP (see distributed/sharding.py).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

__all__ = ["make_production_mesh", "make_dev_mesh", "HW"]


# TPU v5e hardware constants used by the roofline analysis (per chip).
HW = {
    "name": "tpu-v5e",
    "peak_flops_bf16": 197e12,     # FLOP/s
    "hbm_bw": 819e9,               # B/s
    "ici_bw": 50e9,                # B/s per link
    "hbm_bytes": 16 * 1024**3,
}


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_dev_mesh(data: int = 2, model: int = 4) -> Mesh:
    """Small mesh for CPU multi-device tests (needs host_device_count)."""
    return jax.make_mesh(
        (data, model), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )
