"""Fixed-point tier: bit-exact hardware parity, serving, and reporting.

The acceptance bar for the integer backend is *bit-exactness*, not
tolerance: the jitted JAX ``fixed`` backend must produce integer logits
identical to the pure-NumPy golden datapath interpreter
(``repro.fixed.golden``) — across configs, seeds, and both deployment
widths, across jit/eager, and run to run.  On top of parity: the integer
Σ-Δ front end matches its golden twin, float-vs-fixed logit divergence is
bounded, the serving tier binds/classifies/canaries through
``backend="fixed"``, and the robustness harness sweeps it per SNR.

Tiny reduced configs throughout so binds stay cheap; the full paper
config's parity is gated in CI by ``benchmarks/fixed_bench.py``.
"""
import itertools
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import SNNConfig, init_snn
from repro.fixed import (
    FixedQuantFn,
    build_golden,
    calibrate_step,
    fixed_encode_batch,
    fixed_logit_scale,
    fixed_sigma_delta_encode,
    golden_encode_frames,
    quantize_codes,
)
from repro.models.graph import available_backends, compile_snn
from repro.plan import PlanCache, compile_plan
from repro.train.lsq import init_lsq_scales
from repro.train.pruning import make_mask_pytree

CFG_A = SNNConfig(
    conv_specs=((3, 2, 4), (3, 4, 8)),
    pool=2,
    fc_specs=((32, 16), (16, 5)),
    input_width=16,
    timesteps=3,
    n_classes=5,
)
CFG_B = SNNConfig(
    conv_specs=((5, 2, 8),),
    pool=2,
    fc_specs=((64, 10),),
    input_width=16,
    timesteps=4,
    n_classes=10,
    readout="spike_count",
)
CONFIGS = {"two_conv_current": CFG_A, "one_conv_spikecount": CFG_B}


def _iq(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, 2, cfg.input_width)).astype(np.float32)


def _setup(cfg, seed, bits, calibrate=False):
    """(params, masks, quant_fn factory) — fresh quant_fn per bind."""
    params = init_snn(jax.random.PRNGKey(seed), cfg)
    masks = make_mask_pytree(params, 0.5)
    scales = None if calibrate else init_lsq_scales(params, bits)
    return params, masks, (lambda: FixedQuantFn(scales, bits=bits))


# ---------------------------------------------------------------------------
# bit-exact parity: backend vs golden, jit vs eager, run to run
# ---------------------------------------------------------------------------

# 2 configs x 3 seeds x 2 widths = 12 seeded combos (acceptance floor: 10)
PARITY_GRID = list(itertools.product(CONFIGS, (0, 1, 2), (8, 16)))


@pytest.mark.parametrize("cfg_name,seed,bits", PARITY_GRID)
def test_fixed_backend_bit_exact_vs_golden(cfg_name, seed, bits):
    cfg = CONFIGS[cfg_name]
    # odd seeds exercise max-abs calibration (no LSQ state at serve time)
    params, masks, mk_qfn = _setup(cfg, seed, bits, calibrate=seed % 2 == 1)
    plan = compile_plan(compile_snn(cfg), params, masks=masks,
                        quant_fn=mk_qfn(), assignment="fixed",
                        cache=PlanCache(disk_dir=""))
    iq = _iq(cfg, 3, seed=seed)
    enc = fixed_encode_batch(jnp.asarray(iq), cfg.timesteps)

    step = jax.jit(plan.bound.batch)
    got = np.asarray(step(enc))
    assert got.dtype == np.int32

    golden = build_golden(cfg, params, masks=masks, quant_fn=mk_qfn())
    want = np.stack([golden.forward_iq(f) for f in iq])
    assert np.array_equal(got, want), (
        f"{cfg_name}/seed{seed}/q{bits}: jitted fixed backend diverged "
        f"from the golden datapath (max |dint| = "
        f"{np.abs(got.astype(np.int64) - want.astype(np.int64)).max()})")

    # run-to-run determinism and jit-vs-eager identity
    assert np.array_equal(np.asarray(step(enc)), got)
    assert np.array_equal(np.asarray(plan.bound.batch(enc)), got)


def test_layered_and_streaming_paths_match_golden():
    """Both plan executors reproduce the golden ints frame by frame."""
    cfg = CFG_A
    params, masks, mk_qfn = _setup(cfg, 5, 16)
    plan = compile_plan(compile_snn(cfg), params, masks=masks,
                        quant_fn=mk_qfn(), assignment="fixed",
                        cache=PlanCache(disk_dir=""))
    golden = build_golden(cfg, params, masks=masks, quant_fn=mk_qfn())
    for i, f in enumerate(_iq(cfg, 2, seed=5)):
        enc = golden_encode_frames(f, cfg.timesteps)
        want = golden.forward(enc)
        lay, _ = plan.run_layered(jnp.asarray(enc))
        stream, _ = plan.run_streaming(jnp.asarray(enc))
        assert np.array_equal(np.asarray(lay), want), f"frame {i} layered"
        assert np.array_equal(np.asarray(stream), want), f"frame {i} stream"


def test_artifact_cache_hit_stays_bit_exact():
    """A second compile from the shared artifact cache serves identical
    ints — the (codes, step) pair must travel together through the cache."""
    cfg = CFG_A
    params, masks, mk_qfn = _setup(cfg, 9, 8)
    cache = PlanCache(disk_dir="")
    program = compile_snn(cfg)
    enc = fixed_encode_batch(jnp.asarray(_iq(cfg, 2, seed=9)), cfg.timesteps)
    p1 = compile_plan(program, params, masks=masks, quant_fn=mk_qfn(),
                      assignment="fixed", cache=cache)
    p2 = compile_plan(program, params, masks=masks, quant_fn=mk_qfn(),
                      assignment="fixed", cache=cache)
    assert np.array_equal(np.asarray(p1.bound.batch(enc)),
                          np.asarray(p2.bound.batch(enc)))


@pytest.mark.parametrize("bits", [8, 16])
def test_fixed_quant_fn_quantizes_concrete_weights_on_the_host(bits):
    """Concrete weights fake-quantize in NumPy, bit-identical to the codes
    the golden derives: a device division may round a weight that sits on
    a half step the other way.  Traced weights keep the jnp path."""
    rng = np.random.default_rng(bits)
    w = rng.normal(size=(64, 32)).astype(np.float32)
    qfn = FixedQuantFn(None, bits=bits)
    got = qfn(w)
    assert isinstance(got, np.ndarray) and got.dtype == np.float32
    step = calibrate_step(w, bits)
    want = quantize_codes(w, step, bits).astype(np.float32) * np.float32(step)
    assert np.array_equal(got, want)
    scales = init_lsq_scales({"conv": [{"w": w}], "fc": []}, bits)
    traced = jax.jit(FixedQuantFn(scales, bits=bits))(jnp.asarray(w))
    assert np.array_equal(np.asarray(traced),
                          FixedQuantFn(scales, bits=bits)(w))


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_integer_encoder_matches_golden(seed):
    """jnp integer Σ-Δ front end == NumPy golden encoder, bit for bit."""
    rng = np.random.default_rng(seed)
    frame = rng.normal(size=(2, 32)).astype(np.float32)
    got = np.asarray(fixed_sigma_delta_encode(
        jnp.asarray(np.float32(0.5) * (frame / (np.abs(frame).max()
                                                + np.float32(1e-8))
                                       + np.float32(1.0))), 8))
    want = golden_encode_frames(frame, 8)
    assert got.dtype == np.int32 and want.dtype == np.int32
    assert np.array_equal(got, want)
    assert set(np.unique(want)) <= {0, 1}


# ---------------------------------------------------------------------------
# float-vs-fixed divergence
# ---------------------------------------------------------------------------

def test_float_vs_fixed_divergence_bounded():
    """Dequantized fixed logits track the fake-quant float reference.

    Same fake-quant weights on both sides, so the residual is the integer
    datapath's truncation (acc_shift, leak shift, int16 membrane) —
    bounded relative to the logit scale, with argmax agreement on a
    majority of frames (untrained nets put some frames at coin-flip
    margins; bit-exactness is the golden tests' job, not this one's).
    """
    cfg = CFG_A
    params, masks, mk_qfn = _setup(cfg, 2, 16)
    program = compile_snn(cfg)
    cache = PlanCache(disk_dir="")
    iq = _iq(cfg, 16, seed=2)
    fplan = compile_plan(program, params, masks=masks, quant_fn=mk_qfn(),
                         assignment="dense", cache=cache)
    qplan = compile_plan(program, params, masks=masks, quant_fn=mk_qfn(),
                         assignment="fixed", cache=cache)
    ref = np.asarray(fplan.bound.batch(
        jnp.asarray(np.stack([np.asarray(golden_encode_frames(
            f, cfg.timesteps), np.float32) for f in iq]))))
    scale = fixed_logit_scale(params, cfg, masks=masks, quant_fn=mk_qfn())
    fx = np.asarray(qplan.bound.batch(
        fixed_encode_batch(jnp.asarray(iq), cfg.timesteps))
    ).astype(np.float32) * scale
    # the residual is bimodal: near-zero almost everywhere, with isolated
    # O(theta) shifts where integer truncation flips a single mid-network
    # spike — so bound the *distribution*, not the worst element
    diff = np.abs(fx - ref)
    denom = max(1.0, float(np.abs(ref).max()))
    assert float(diff.mean()) / denom < 0.05
    assert float(np.median(diff.max(-1))) / denom < 0.05
    agree = float((fx.argmax(-1) == ref.argmax(-1)).mean())
    assert agree >= 0.6, f"argmax agreement {agree:.2f}"


# ---------------------------------------------------------------------------
# registration / serving tier / robustness harness
# ---------------------------------------------------------------------------

def test_lazy_backend_registration():
    assert "fixed" in available_backends()
    # a fresh interpreter must see it without importing repro.fixed first
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c",
         "from repro.models.graph import available_backends; "
         "print('fixed' in available_backends())"],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "True"


def test_async_serve_fixed_backend_smoke():
    from repro.serve import AsyncAMCServeEngine

    cfg = CFG_A
    params, masks, _ = _setup(cfg, 0, 8)
    scales = init_lsq_scales(params, 8)
    with AsyncAMCServeEngine(params, cfg, masks=masks, backend="fixed",
                             max_batch=4, lsq_scales=scales,
                             quant_bits=8) as engine:
        preds = engine.classify(_iq(cfg, 12))
        assert preds.shape == (12,)
        assert engine.stats.backend == "fixed"


def test_sync_serve_fixed_backend_smoke():
    from repro.serve import AMCServeEngine

    cfg = CFG_A
    params, masks, _ = _setup(cfg, 0, 16)
    engine = AMCServeEngine(params, cfg, masks=masks, backend="fixed",
                            batch_size=4,
                            lsq_scales=init_lsq_scales(params, 16))
    preds = engine.classify(_iq(cfg, 8))
    assert preds.shape == (8,)


def test_fixed_canary_shadowed_by_monitor():
    """A quantized canary rides next to a float production binding and the
    monitor shadow-scores it per SNR bin without touching production."""
    from repro.deploy import CanaryMonitor, MonitorConfig, canary_router
    from repro.serve import AsyncAMCServeEngine

    cfg = CFG_A
    params, masks, _ = _setup(cfg, 0, 16)
    scales = init_lsq_scales(params, 16)
    with AsyncAMCServeEngine(params, cfg, masks=masks, backend="dense",
                             max_batch=4, max_delay_ms=1.0,
                             version_label="prod") as engine:
        engine.bind_version("canary-q16", params, masks, backend="fixed",
                            lsq_scales=scales, quant_bits=16)
        assert engine.get_version("canary-q16").backend == "fixed"
        engine.set_router(canary_router("prod", "canary-q16", 25.0))
        engine.classify(_iq(cfg, 32))
        stats = engine.version_stats()
        assert stats["canary-q16"].batches > 0

        mon = CanaryMonitor(
            engine, baseline="prod", canary="canary-q16",
            config=MonitorConfig(snr_bins=(10.0,), frames_per_bin=8,
                                 window=2, min_rounds=1, promote_after=2,
                                 score="agreement"))
        decision = mon.run(max_rounds=3)
        assert decision in ("promote", "rollback", "pending")
        assert mon.history and all(
            10.0 in h.canary_acc for h in mon.history)
        # identical weights quantized at 16 bits: a promoted fixed canary
        # becomes the active version; any other decision leaves production
        assert engine.active_version == (
            "canary-q16" if decision == "promote" else "prod")
        assert engine.classify(_iq(cfg, 8)).shape == (8,)


def test_registry_quantized_publish_serves_fixed(tmp_path):
    """A quantized publish round-trips through the registry into genuinely
    integer serving: the stored LSQ state binds ``backend="fixed"``."""
    from repro.deploy import ModelRegistry
    from repro.serve import AsyncAMCServeEngine

    cfg = CFG_A
    params, masks, _ = _setup(cfg, 0, 16)
    scales = init_lsq_scales(params, 16)
    reg = ModelRegistry(str(tmp_path / "registry"))
    version = reg.publish("amc", params, cfg, masks=masks,
                          lsq_scales=scales, quant_bits=16,
                          assignment="fixed")
    loaded = reg.load(version.spec)
    assert loaded.version.quant_bits == 16
    with AsyncAMCServeEngine(loaded.params, loaded.cfg, masks=loaded.masks,
                             backend="fixed", max_batch=4,
                             lsq_scales=loaded.lsq_scales,
                             quant_bits=loaded.version.quant_bits) as eng:
        preds = eng.classify(_iq(cfg, 8))
        assert preds.shape == (8,)
        assert eng.stats.backend == "fixed"


def test_robustness_harness_sweeps_fixed_backend():
    from repro.eval import RobustnessConfig, evaluate_robustness

    cfg = CFG_A
    params, masks, mk_qfn = _setup(cfg, 0, 16)
    rcfg = RobustnessConfig(snr_grid=(0.0, 10.0), frames_per_cell=8,
                            backends=("dense", "fixed"), seed=0,
                            include_clean=False,
                            agreement_atol=float("inf"))
    report = evaluate_robustness(params, cfg, rcfg, masks=masks,
                                 quant_fn=mk_qfn(),
                                 scenarios=("static_awgn",))
    per_snr = report["scenarios"]["static_awgn"]["per_snr"]
    for snr in ("+0.0", "+10.0"):
        acc = per_snr[snr]["accuracy"]
        assert set(acc) == {"dense", "fixed"}
        assert 0.0 <= acc["fixed"] <= 1.0
    assert np.isfinite(report["agreement"]["max_abs_logit_diff"])
