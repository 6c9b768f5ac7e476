"""Golden-counter regression: the stream backend's Tables I/III numbers.

The ``stream`` backend interprets the precomputed Algorithm-2 schedule and
returns the compute/extra/empty iteration counts the paper reports in
Tables I and III.  Everything here is deterministic — paper config
(``configs/saocds_amc.py``), seeded init, magnitude masks at 50% density,
seeded input frames — so the totals are pinned to literal values: any
change to the COO sort order, the schedule builder, the mask rule, or the
interpreter that shifts these numbers (and hence the paper-table
reproductions) fails loudly instead of drifting silently.

Regenerate after an *intentional* semantic change with:

    PYTHONPATH=src python tests/test_stream_golden.py
"""
import numpy as np

import jax
import jax.numpy as jnp

from repro.api import compile_snn, init_snn, stream_totals
from repro.configs.saocds_amc import CONFIG
from repro.train.pruning import make_mask_pytree

DENSITY = 0.5

# Per-layer static schedule geometry (input-independent: fixed by the
# masked weights alone) and the gated accumulation counts for the seeded
# input below.  nnz at 50%: conv1 11*2*16/2 = 176, conv2 11*16*32/2 =
# 2816, conv3 5*32*64/2 = 5120.  Cut with the default JAX PRNG
# (``jax_threefry_partitionable`` on), which seeds ``init_snn``.
GOLDEN_LAYERS = {
    "conv1": {"reps_per_timestep": 176, "compute_iters": 176,
              "extra_iters": 0, "empty_iters": 0, "accumulations": 89015},
    "conv2": {"reps_per_timestep": 2816, "compute_iters": 2816,
              "extra_iters": 0, "empty_iters": 0, "accumulations": 306721},
    "conv3": {"reps_per_timestep": 5120, "compute_iters": 5120,
              "extra_iters": 0, "empty_iters": 0, "accumulations": 254284},
}
GOLDEN_TOTALS = {"compute_iters": 8112, "extra_iters": 0, "empty_iters": 0,
                 "reps_per_timestep": 8112, "accumulations": 650020}


def _setup():
    program = compile_snn(CONFIG)
    params = init_snn(jax.random.PRNGKey(0), CONFIG)
    masks = make_mask_pytree(params, DENSITY)
    rng = np.random.default_rng(0)
    frames = jnp.asarray(
        (rng.random((CONFIG.timesteps, CONFIG.conv_specs[0][1],
                     CONFIG.input_width)) < 0.5).astype(np.float32))
    return program, params, masks, frames


def _run():
    program, params, masks, frames = _setup()
    _, counters = program.apply(params, frames, "stream", masks=masks,
                                return_counters=True)
    return counters


def _assert_golden(counters):
    assert set(counters) == set(GOLDEN_LAYERS)
    for name, golden in GOLDEN_LAYERS.items():
        got = counters[name]
        assert int(np.asarray(got["timesteps"])) == CONFIG.timesteps
        for key, want in golden.items():
            assert int(np.asarray(got[key])) == want, (
                f"{name}.{key}: got {int(np.asarray(got[key]))}, "
                f"golden {want} — Tables I/III reproduction drifted")
    totals = stream_totals(counters)
    for key, want in GOLDEN_TOTALS.items():
        assert int(np.asarray(totals[key])) == want
    # schedule invariant: every slot is exactly one of the three kinds
    assert (GOLDEN_TOTALS["compute_iters"] + GOLDEN_TOTALS["extra_iters"]
            + GOLDEN_TOTALS["empty_iters"]
            == GOLDEN_TOTALS["reps_per_timestep"])


def test_stream_counters_match_golden_paper_config():
    _assert_golden(_run())


def test_stream_counters_match_golden_through_fused_plan():
    """The fused single-scan executor must reproduce the exact same
    Tables I/III counters as the layer-by-layer path."""
    from repro.api import compile_plan
    from repro.plan import PlanCache

    program, params, masks, frames = _setup()
    plan = compile_plan(program, params, masks=masks, assignment="stream",
                        cache=PlanCache(disk_dir=""))
    _, counters = plan.run_streaming(frames)
    _assert_golden(counters)


def test_stream_counters_match_golden_with_quantized_weights():
    """16-bit weight quantization must not move the Table I schedule.

    The Algorithm-2 schedule is built from the *positions* of surviving
    weights, never their magnitudes; every weight the 50%-density mask
    keeps has |w| at or above the layer median, orders of magnitude above
    the LSQ step, so fake-quant rounds none of them to zero.  If
    quantization ever perturbed nnz — and with it reps/compute/empty —
    the paper-table reproduction would silently depend on weight values.

    Accumulation counts are pinned only for conv1 (its input is the fixed
    seeded frame): downstream layers see quantization-perturbed spike
    trains, so their gated-accumulation totals legitimately shift by the
    activity delta — bounded here to <1% of the float goldens.
    """
    from repro.train.lsq import init_lsq_scales, make_serving_quant_fn

    program, params, masks, frames = _setup()
    quant_fn = make_serving_quant_fn(init_lsq_scales(params, 16), 16)
    _, counters = program.apply(params, frames, "stream", masks=masks,
                                quant_fn=quant_fn, return_counters=True)
    assert set(counters) == set(GOLDEN_LAYERS)
    schedule_keys = ("reps_per_timestep", "compute_iters", "extra_iters",
                     "empty_iters")
    for name, golden in GOLDEN_LAYERS.items():
        got = counters[name]
        for key in schedule_keys:
            assert int(np.asarray(got[key])) == golden[key], (
                f"{name}.{key}: quantization moved the static schedule "
                f"({int(np.asarray(got[key]))} != {golden[key]})")
        drift = abs(int(np.asarray(got["accumulations"]))
                    - golden["accumulations"])
        if name == "conv1":
            assert drift == 0
        else:
            assert drift <= 0.01 * golden["accumulations"], (
                f"{name}: accumulation count drifted {drift} "
                f"(> 1% of {golden['accumulations']})")


if __name__ == "__main__":  # regeneration helper
    for name, c in _run().items():
        print(name, {k: int(np.asarray(v)) for k, v in c.items()})
