"""The paper's inter-layer streaming as SPMD pipeline parallelism.

The SAOCDS accelerator instantiates each SNN layer as its own hardware
stage with activations streamed stage-to-stage (paper §III).  This example
maps that structure onto a JAX device mesh: a 4-stage ``spmd_pipeline``
(conv1 | conv2 | conv3 | FC head) where microbatches of spike frames flow
through ``ppermute`` handoffs on a fixed tick schedule — bubbles included
as explicit no-op slots, the paper's precomputed empty/extra iterations.

Every stage is built from the same shared ``LayerSpec`` graph that the
single-device forward executes (``compile_snn`` -> ``SNNProgram``): the
pipeline partitions the graph (``conv_block(i)`` / ``head_layers()``) and
runs each slice through the dense backend — no layer is re-implemented
here.

Needs >=4 devices, so it re-execs itself with
``xla_force_host_platform_device_count=4`` (CPU).

Run:  PYTHONPATH=src python examples/snn_pipeline.py
"""
import os
import sys

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from repro.api import compile_snn, init_snn
from repro.configs.saocds_amc import CONFIG as SNN_CONFIG
from repro.data.pipeline import sigma_delta_encode_np
from repro.data.radioml import generate_batch
from repro.distributed.pipeline import spmd_pipeline


def main():
    cfg = SNN_CONFIG
    program = compile_snn(cfg)
    params = init_snn(jax.random.PRNGKey(0), cfg)
    mesh = jax.make_mesh((4,), ("stage",), axis_types=(AxisType.Auto,))

    # heterogeneous stages share one fixed-width buffer — the software
    # analogue of the accelerator's fixed inter-layer stream width.
    # buffer: (T, C_max, W_max) with C_max=64, W_max=128
    t, cmax, wmax = cfg.timesteps, 64, cfg.input_width

    def conv_stage(li):
        # the (Conv1dLIF, MaxPool) slice of the shared layer graph
        block = program.conv_block(li)
        conv = block[0]
        w_in = cfg.input_width // (cfg.pool ** li)

        def fn(p, buf):   # buf (T, Cmax, Wmax)
            x = buf[:, : conv.ic, : w_in]
            out = program.run_layers(block, p, x)
            pad_c, pad_w = cmax - out.shape[1], wmax - out.shape[2]
            return jnp.pad(out, ((0, 0), (0, pad_c), (0, pad_w)))

        return fn

    def head_stage(p, buf):
        # FC1 -> FC2 -> readout slice of the same graph
        w_in = cfg.input_width // (cfg.pool ** len(cfg.conv_specs))
        x = buf[:, : cfg.conv_specs[-1][2], : w_in]
        logits = program.run_layers(program.head_layers(), p, x)
        out = jnp.zeros((t, cmax, wmax), jnp.float32)
        return out.at[0, 0, : cfg.n_classes].set(logits)

    stages = [conv_stage(0), conv_stage(1), conv_stage(2), head_stage]

    def stage_fn(stage_params, buf):
        idx = jax.lax.axis_index("stage")
        outs = [f(stage_params, buf) for f in stages]
        return jnp.select([idx == i for i in range(4)], outs)

    # data: 8 microbatches of one sample each
    iq, labels, _ = generate_batch(seed=7, batch=8, snr_db=10.0)
    frames = sigma_delta_encode_np(iq, t).astype(np.float32)  # (8, T, 2, 128)
    mbs = jnp.asarray(np.pad(
        frames, ((0, 0), (0, 0), (0, cmax - 2), (0, 0))))     # fixed buffer

    # every stage holds the FULL param tree here (stage_fn selects); a
    # stacked per-stage tree is the memory-lean option for big models
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (4,) + x.shape), params)

    out = spmd_pipeline(stage_fn, stacked, mbs, mesh, stage_axis="stage")
    pipe_logits = np.asarray(out[:, 0, 0, : cfg.n_classes])

    ref_logits = np.asarray(
        program.apply_batch(params, jnp.asarray(frames), "dense"))
    err = np.abs(pipe_logits - ref_logits).max()
    print(f"4-stage pipeline vs single-device forward: max err {err:.2e}")
    assert err < 1e-3
    print(f"ticks executed: {8 + 4 - 1} (8 microbatches + 3 bubble slots, "
          f"the paper's precomputed schedule)")
    print("predictions:", pipe_logits.argmax(-1), "labels:", labels)


if __name__ == "__main__":
    main()
