"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The TPU compiler ships with jaxlib, so a CPU process can compile for a
chip it does not have.  These tests compile, at the paper config's real
widths, what the chip's compiler must accept: the whole-network
``stream_fused`` kernel at the engine's batch buckets, each per-layer
Pallas kernel, and the engine's serving steps.  Where a Pallas kernel is
expected, the compiled HLO must hold it as a ``tpu_custom_call``.  Nothing
runs, so these say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and a test worker that
describes it keeps the library until it exits.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.api import compile_plan, compile_snn, init_snn
from repro.configs.saocds_amc import CONFIG
from repro.core.sparse_format import block_sparse_from_dense
from repro.kernels import goap_conv_block_sparse, lif_update_fused, \
    wm_fc_matmul
from repro.kernels.stream_fused import fused_stack_of, stream_fused_forward
from repro.models.graph import PALLAS_BLOCK_K, PALLAS_BLOCK_OC
from repro.train.pruning import make_mask_pytree

DENSITY = 0.5
IC0, W0, T = CONFIG.conv_specs[0][1], CONFIG.input_width, CONFIG.timesteps


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this jaxlib
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def paper():
    params = init_snn(jax.random.PRNGKey(0), CONFIG)
    return params, make_mask_pytree(params, DENSITY)


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Kernels reached through the plan compile for the chip, not the
    interpreter this CPU process would otherwise pick."""
    import repro.kernels.platform as platform

    monkeypatch.setattr(platform, "interpret_mode", lambda: False)


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("encode", [False, True])
@pytest.mark.parametrize("batch", [1, 8, 64])
def test_stream_fused_compiles(topo, one_chip, paper, batch, encode):
    params, masks = paper
    plan = compile_plan(compile_snn(CONFIG), params, masks=masks,
                        assignment="pallas_fused")
    stack = fused_stack_of(plan)
    shape = (batch, IC0, W0) if encode else (batch, T, IC0, W0)
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    hlo = _compile_text(lambda f: stream_fused_forward(
        stack, f, encode=encode, interpret=False), x)
    assert "tpu_custom_call" in hlo and "stream_fused" in hlo


def test_stream_fused_compiles_under_highest_matmul_precision(
        topo, one_chip, paper):
    """References run under ``default_matmul_precision("highest")``; the
    kernel's bf16 selection dots must not inherit it (Mosaic refuses an
    fp32 contraction of bf16 operands)."""
    params, masks = paper
    plan = compile_plan(compile_snn(CONFIG), params, masks=masks,
                        assignment="pallas_fused")
    stack = fused_stack_of(plan)
    x = jax.ShapeDtypeStruct((8, T, IC0, W0), jnp.float32, sharding=one_chip)
    with jax.default_matmul_precision("highest"):
        hlo = _compile_text(lambda f: stream_fused_forward(
            stack, f, interpret=False), x)
    assert "tpu_custom_call" in hlo and "stream_fused" in hlo


@pytest.mark.parametrize("layer", range(len(CONFIG.conv_specs)))
def test_goap_conv_kernel_compiles(topo, one_chip, paper, layer):
    params, masks = paper
    width = W0 // CONFIG.pool ** layer          # each conv follows a pool
    w = (np.asarray(params["conv"][layer]["w"])
         * np.asarray(masks["conv"][layer]))
    bs = block_sparse_from_dense(w, block_oc=PALLAS_BLOCK_OC,
                                 block_k=PALLAS_BLOCK_K)
    oi_padded = -(-width // 128) * 128     # lane-tiled output positions
    s = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    hlo = _compile_text(
        lambda b, c, x: goap_conv_block_sparse(
            b, c, x, block_oc=bs.block_oc, block_k=bs.block_k,
            interpret=False),
        s(bs.blocks.shape), s(bs.block_cols.shape, jnp.int32),
        s((bs.padded_k, oi_padded)))
    assert "tpu_custom_call" in hlo and "goap_conv_block_sparse" in hlo


@pytest.mark.parametrize("din,dout", CONFIG.fc_specs)
def test_wm_fc_and_lif_kernels_compile(topo, one_chip, din, dout):
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                           sharding=one_chip)
    hlo = _compile_text(lambda x, w: wm_fc_matmul(x, w, interpret=False),
                        s((T, din)), s((din, dout)))
    assert "tpu_custom_call" in hlo and "wm_fc_matmul" in hlo
    hlo = _compile_text(
        lambda c, v, a, th, vt: lif_update_fused(c, v, a, th, vt,
                                                 interpret=False),
        s((T, dout)), *[s((dout,))] * 4)
    assert "tpu_custom_call" in hlo and "lif_update_fused" in hlo


def _engine(paper, backend, **kw):
    from repro.serve import AsyncAMCServeEngine

    params, masks = paper
    return AsyncAMCServeEngine(params, CONFIG, masks=masks, backend=backend,
                               max_batch=64, warmup=False, quant_bits=8,
                               name=f"chip-compile-{backend}", **kw)


@pytest.mark.parametrize("backend", ["dense", "fixed", "stream"])
def test_serving_step_compiles(topo, one_chip, paper, backend):
    with _engine(paper, backend) as engine:
        assert engine.mesh is None and engine.backend == backend
        step = engine.get_version(engine.active_version).step
        x = jax.ShapeDtypeStruct((64, IC0, W0), jnp.float32,
                                 sharding=one_chip)
        assert step.lower(x).compile().as_text()


def test_pallas_fused_serving_step_compiles_over_four_chips(
        topo, paper, compiled_kernels):
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    with _engine(paper, "pallas_fused", mesh=mesh) as engine:
        assert engine.plan.fused_stack() is not None
        assert all(b % 4 == 0 for b in engine.batcher.buckets)
        step = engine.get_version(engine.active_version).step
        x = jax.ShapeDtypeStruct((64, IC0, W0), jnp.float32,
                                 sharding=NamedSharding(mesh, P("data")))
        compiled = step.lower(x).compile()
        hlo = compiled.as_text()
        assert "tpu_custom_call" in hlo and "stream_fused" in hlo
        # pure data parallelism: no collective between the chips
        for op in ("all-reduce", "all-gather", "all-to-all",
                   "collective-permute"):
            assert op not in hlo, op
