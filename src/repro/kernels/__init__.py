"""Pallas TPU kernels for the paper's compute hot spots.

Each kernel ships with a pure-jnp oracle in ``ref.py`` and a jit'd
framework-facing wrapper in ``ops.py``.  Kernels compile through Mosaic
on a TPU and run in interpret mode on the CPU, decided in one place
(:mod:`repro.kernels.platform`); dots that carry float weights run at
``Precision.HIGHEST`` so they agree with the float32 reference.
"""

from .goap_conv import goap_conv_block_sparse
from .wm_fc import wm_fc_matmul
from .lif_update import lif_update_fused
from .ops import goap_conv_op, wm_fc_op, lif_op
from . import ref
