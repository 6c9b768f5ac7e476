"""Parity tests for the multi-layer fused Pallas streaming kernel.

The ``pallas_fused`` backend collapses the whole network into one
``pallas_call`` with every layer's LIF state in VMEM
(:mod:`repro.kernels.stream_fused`).  It must be *invisible* numerically:

* logits match the dense oracle at atol 1e-5 across seeded configs;
* the per-conv gated-accumulation counters match the ``stream``
  backend's Tables I/III counters **exactly** (integer equality — the
  counts·row_sums identity is exact in f32 for integer-valued totals);
* on the paper config the counters hit the same pinned literals as
  ``tests/test_stream_golden.py``;
* the batched kernel path equals per-sample runs, and the fused Σ-Δ
  encode path equals encode-then-forward.

Everything runs in interpret mode on CPU; the compiled-mode test is
skipped unless a real TPU is attached (``tests/test_chip_compile.py``
compiles the kernel for a described v5e without one).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import compile_plan, compile_snn, init_snn
from repro.core.encoder import sigma_delta_encode
from repro.kernels.stream_fused import fused_stack_of, stream_fused_forward
from repro.models.snn import SNNConfig
from repro.train.pruning import make_mask_pytree

SMALL = SNNConfig(conv_specs=((3, 2, 4), (3, 4, 8)), pool=2,
                  fc_specs=((64, 16), (16, 5)), input_width=32,
                  timesteps=4, n_classes=5).validate()
DENSITY = 0.5
# 10 seeded (weights, mask density, input) configurations
SEED_GRID = [(seed, density) for seed in range(5)
             for density in (0.3, 0.6)]


def _setup(cfg, seed, density):
    program = compile_snn(cfg)
    params = init_snn(jax.random.PRNGKey(seed), cfg)
    masks = make_mask_pytree(params, density)
    rng = np.random.default_rng(seed)
    frames = jnp.asarray(
        (rng.random((cfg.timesteps, cfg.conv_specs[0][1],
                     cfg.input_width)) < 0.5).astype(np.float32))
    return program, params, masks, frames


def _fused_plan(program, params, masks):
    return compile_plan(program, params, masks=masks,
                        assignment="pallas_fused")


@pytest.mark.parametrize("seed,density", SEED_GRID)
def test_fused_matches_dense_oracle(seed, density):
    program, params, masks, frames = _setup(SMALL, seed, density)
    want = np.asarray(program.apply(params, frames, "dense", masks=masks))
    plan = _fused_plan(program, params, masks)
    assert fused_stack_of(plan) is not None, "plan did not fuse"
    got, _ = plan.run_streaming(frames)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


@pytest.mark.parametrize("seed", range(3))
def test_fused_counters_equal_stream_backend_exactly(seed):
    program, params, masks, frames = _setup(SMALL, seed, DENSITY)
    _, want = program.apply(params, frames, "stream", masks=masks,
                            return_counters=True)
    plan = _fused_plan(program, params, masks)
    _, got = plan.run_streaming(frames)
    assert set(got) == set(want)
    for name in want:
        for key in ("reps_per_timestep", "compute_iters", "extra_iters",
                    "empty_iters", "accumulations", "timesteps"):
            assert int(np.asarray(got[name][key])) == \
                int(np.asarray(want[name][key])), (
                    f"{name}.{key}: fused kernel counter diverged from "
                    f"the stream backend")


def test_fused_counters_match_golden_paper_config():
    """The in-kernel counters reproduce the pinned Tables I/III literals
    on the full paper config (same setup as tests/test_stream_golden.py)."""
    from test_stream_golden import GOLDEN_LAYERS, _setup as golden_setup

    program, params, masks, frames = golden_setup()
    plan = _fused_plan(program, params, masks)
    assert fused_stack_of(plan) is not None
    logits, counters = plan.run_streaming(frames)
    want = np.asarray(program.apply(params, frames, "dense", masks=masks))
    np.testing.assert_allclose(np.asarray(logits), want, atol=1e-5)
    assert set(counters) == set(GOLDEN_LAYERS)
    for name, golden in GOLDEN_LAYERS.items():
        for key, val in golden.items():
            assert int(np.asarray(counters[name][key])) == val, (
                f"{name}.{key}: fused kernel drifted off the golden "
                f"Tables I/III value")


def test_batched_kernel_equals_per_sample():
    program, params, masks, _ = _setup(SMALL, 0, DENSITY)
    plan = _fused_plan(program, params, masks)
    stack = fused_stack_of(plan)
    rng = np.random.default_rng(7)
    frames_b = jnp.asarray(
        (rng.random((3, SMALL.timesteps, SMALL.conv_specs[0][1],
                     SMALL.input_width)) < 0.5).astype(np.float32))
    logits_b, accs_b = stream_fused_forward(stack, frames_b)
    for i in range(frames_b.shape[0]):
        logits_1, accs_1 = stream_fused_forward(stack, frames_b[i:i + 1])
        np.testing.assert_array_equal(np.asarray(logits_b[i]),
                                      np.asarray(logits_1[0]))
        np.testing.assert_array_equal(np.asarray(accs_b[i]),
                                      np.asarray(accs_1[0]))
    # and the plan's batch entry point (what the engine jits) agrees with
    # the layer-by-layer bound program
    want = np.asarray(plan.bound.batch(frames_b))
    np.testing.assert_allclose(np.asarray(plan.batch(frames_b)), want,
                               atol=1e-5)


def test_fused_sigma_delta_encode_matches_encode_then_forward():
    """encode=True fuses the Σ-Δ modulator into the kernel: feeding the
    normalized analog frame must equal modulating first and streaming the
    resulting spike frames."""
    program, params, masks, _ = _setup(SMALL, 1, DENSITY)
    plan = _fused_plan(program, params, masks)
    stack = fused_stack_of(plan)
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.random((2, SMALL.conv_specs[0][1],
                                SMALL.input_width)).astype(np.float32))
    frames = jnp.moveaxis(sigma_delta_encode(x, SMALL.timesteps), 0, 1)
    want_logits, want_accs = stream_fused_forward(stack, frames)
    got_logits, got_accs = stream_fused_forward(stack, x, encode=True)
    np.testing.assert_array_equal(np.asarray(got_logits),
                                  np.asarray(want_logits))
    np.testing.assert_array_equal(np.asarray(got_accs),
                                  np.asarray(want_accs))


@pytest.fixture
def tpu():
    """Skip unless a TPU is attached (decided when the test runs, not at
    import, so every test worker collects the same tests)."""
    if jax.default_backend() != "tpu":
        pytest.skip("compiled Mosaic kernel needs a real TPU")


def test_fused_compiled_matches_interpret(tpu):
    program, params, masks, frames = _setup(SMALL, 0, DENSITY)
    plan = _fused_plan(program, params, masks)
    stack = fused_stack_of(plan)
    li, ai = stream_fused_forward(stack, frames[None], interpret=True)
    lc, ac = stream_fused_forward(stack, frames[None], interpret=False)
    np.testing.assert_allclose(np.asarray(lc), np.asarray(li), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(ac), np.asarray(ai))
