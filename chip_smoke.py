#!/usr/bin/env python3
"""Chip smoke: serve the paper model on a TPU through the fused kernel.

Run from the root of a checkout::

    python chip_smoke.py                # phases A and B, one chip
    python chip_smoke.py --chips 4      # phase C only, four chips
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse   # CPU rehearsal

Phase A serves seeded I/Q frames through ``AsyncAMCServeEngine`` with an
explicit ``backend="pallas_fused"`` on the paper config at density 0.5,
checks that the compiled serving step holds the ``stream_fused`` Mosaic
kernel, and holds the served predictions, logits and per-conv
accumulation counters to the float32 ``dense`` reference (at
``jax.default_matmul_precision("highest")``) and to the ``stream``
counter oracle.  Float32 paths that sum in different orders may split on
a membrane within a few ulps of its threshold, so a frame may diverge
only where a float64 run of the network shows such a tie.  Phase B serves the same frames through the integer
``fixed`` backend at 8 bits and holds its logits bit-identical to the
NumPy golden datapath (``repro.fixed.golden``).  Phase C serves them
through the engine sharded over four chips and holds it to the same plan
run on one device.

One process touches JAX once and starts no child.  Without a TPU the
script exits non-zero unless ``--rehearse`` is given, which runs the
kernels in interpret mode with fewer frames.  A failed check raises and
exits non-zero; only a run in which every check passed prints the last
line, one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.launch.compile_cache import place_compile_cache  # noqa: E402

DENSITY = 0.5
SNR_DB = 10.0
FRAMES = 512            # frames served per phase on the chip
REHEARSAL_FRAMES = 16   # interpret mode on the CPU is slow
LOGIT_ATOL = 1e-4       # float logits: kernel vs reference, same spikes
# Float32 paths that sum the same weights in another order (the MXU, XLA's
# dot, the stream backend's schedule) may put a membrane on either side of
# its threshold when it lies within a few ulps of it.  A frame may diverge
# from a reference only if its float64 run has a membrane that close, and
# at most this share of frames may.
TIE_EPS = 1e-5
MAX_TIE_SHARE = 0.01
RESULT_TIMEOUT_S = 600.0


class SmokeFailure(RuntimeError):
    """A check of the smoke failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def _chunks(n: int, size: int):
    for s in range(0, n, size):
        yield slice(s, min(n, s + size))


def float64_margins(params, masks, frames: np.ndarray) -> np.ndarray:
    """Per frame, the least ``|v - v_th|`` over every neuron and timestep.

    A plain float64 run of the paper network (float32 parameters, exact
    sums) on encoded spike frames ``(N, T, IC, W)``, written apart from the
    code under test.
    """
    from repro.configs.saocds_amc import CONFIG

    def lif(p, shape):
        alpha = np.asarray(jax.nn.sigmoid(jnp.asarray(p.alpha_logit)))
        return [np.asarray(a, np.float64).reshape(shape)
                for a in (alpha, p.theta, p.v_th)]

    convs = [(kw, np.asarray(layer["w"], np.float64) * np.asarray(m),
              lif(layer["lif"], (-1, 1)))
             for (kw, _, _), layer, m in zip(CONFIG.conv_specs,
                                             params["conv"], masks["conv"])]
    fcs = [(np.asarray(layer["w"], np.float64) * np.asarray(m),
            lif(layer["lif"], (-1,)))
           for layer, m in zip(params["fc"], masks["fc"])]
    out = np.empty(frames.shape[0])
    for n, frame in enumerate(frames):
        state = [0.0] * (len(convs) + len(fcs))
        margin = np.inf
        for x in frame.astype(np.float64):
            k = 0
            for kw, w, (alpha, theta, v_th) in convs:
                left, width = (kw - 1) // 2, x.shape[1]
                xp = np.pad(x, ((0, 0), (left, kw - 1 - left)))
                cur = sum(w[ci].T @ xp[:, ci:ci + width] for ci in range(kw))
                v = alpha * state[k] + cur
                margin = min(margin, float(np.abs(v - v_th).min()))
                s = (v > v_th).astype(np.float64)
                state[k], k = v - theta * s, k + 1
                pool = CONFIG.pool
                x = s[:, :width // pool * pool].reshape(
                    s.shape[0], width // pool, pool).max(-1)
            x = x.reshape(-1)
            for w, (alpha, theta, v_th) in fcs:
                v = alpha * state[k] + x @ w
                margin = min(margin, float(np.abs(v - v_th).min()))
                x = (v > v_th).astype(np.float64)
                state[k], k = v - theta * x, k + 1
        out[n] = margin
    return out


def _check_divergence(diverged: np.ndarray, margin: np.ndarray,
                      what: str) -> None:
    """Frames that diverge from a reference must be near-threshold ties."""
    idx = np.nonzero(diverged)[0]
    ties = ", ".join(f"frame {i} margin {margin[i]:.3g}" for i in idx)
    log(f"[A] frames diverging from {what}: {idx.size}/{diverged.size}"
        + (f" ({ties})" if ties else ""))
    check(bool(np.all(margin[idx] < TIE_EPS)),
          f"a frame diverged from {what} with no membrane within "
          f"{TIE_EPS} of threshold")
    check(idx.size <= MAX_TIE_SHARE * diverged.size,
          f"more than {MAX_TIE_SHARE:.0%} of frames diverged from {what}")


def _warm(engine, label: str) -> None:
    """Compile every bucket of ``engine`` and print the seconds each took."""
    from repro.configs.saocds_amc import CONFIG

    step = engine.get_version(engine.active_version).step
    shape = (CONFIG.conv_specs[0][1], CONFIG.input_width)
    for b in engine.batcher.buckets:
        t0 = time.perf_counter()
        jax.block_until_ready(step(jnp.zeros((b,) + shape, jnp.float32)))
        log(f"[{label}] compile+first call bucket={b}: "
            f"{time.perf_counter() - t0:.2f} s")


def _check_kernel_in_step(engine, batch: int, rehearse: bool) -> None:
    """The compiled serving step must hold the stream_fused Mosaic kernel."""
    from repro.configs.saocds_amc import CONFIG

    if rehearse:
        log("kernel check: skipped in rehearsal (interpret mode has no "
            "tpu_custom_call)")
        return
    step = engine.get_version(engine.active_version).step
    x = jax.ShapeDtypeStruct(
        (batch, CONFIG.conv_specs[0][1], CONFIG.input_width), jnp.float32)
    hlo = step.lower(x).compile().as_text()
    check("tpu_custom_call" in hlo and "stream_fused" in hlo,
          "compiled serving step holds no tpu_custom_call for stream_fused")
    log(f"kernel check: tpu_custom_call stream_fused present at batch {batch}")


def _serve(engine, iq: np.ndarray) -> np.ndarray:
    """Submit every frame; every future must resolve to a class id."""
    futures = [engine.submit(iq[i]) for i in range(iq.shape[0])]
    preds = np.array([f.result(timeout=RESULT_TIMEOUT_S) for f in futures],
                     dtype=np.int32)
    check(preds.shape == (iq.shape[0],), "not every future resolved")
    return preds


def phase_a(params, masks, iq: np.ndarray, max_batch: int,
            rehearse: bool) -> None:
    """pallas_fused serving vs the dense reference and the stream oracle."""
    from repro.configs.saocds_amc import CONFIG
    from repro.data.pipeline import sigma_delta_encode_batch
    from repro.models.graph import compile_snn
    from repro.obs.metrics import default_registry
    from repro.plan import compile_plan
    from repro.serve import AsyncAMCServeEngine

    n, t_steps = iq.shape[0], CONFIG.timesteps
    engine = AsyncAMCServeEngine(params, CONFIG, masks=masks,
                                 backend="pallas_fused", max_batch=max_batch,
                                 warmup=False, name="smoke-fused")
    try:
        check(engine.backend == "pallas_fused",
              f"engine serves {engine.backend}, not pallas_fused")
        check(engine.mesh is None, "phase A expects one device")
        check(engine.plan is not None
              and engine.plan.fused_stack() is not None,
              "the pallas_fused plan did not fuse into one kernel")
        _warm(engine, "A pallas_fused")
        _check_kernel_in_step(engine, max_batch, rehearse)
        t0 = time.perf_counter()
        preds = _serve(engine, iq)
        log(f"[A] served {n} frames through submit() in "
            f"{time.perf_counter() - t0:.2f} s (smoke time, not a metric)")

        ver = engine.get_version(engine.active_version)
        logits, accs = [], {}
        for sl in _chunks(n, max_batch):
            lg, ac = ver.unpack(np.asarray(ver.step(jnp.asarray(iq[sl]))))
            logits.append(lg)
            for name, v in ac.items():
                accs.setdefault(name, []).append(v)
        logits = np.concatenate(logits)
        accs = {k: np.concatenate(v) for k, v in accs.items()}
        check(np.array_equal(preds, logits.argmax(-1)),
              "served predictions differ from the step's own logits")

        program = compile_snn(CONFIG)
        dense = compile_plan(program, params, masks=masks, assignment="dense")
        stream = compile_plan(program, params, masks=masks,
                              assignment="stream")
        with jax.default_matmul_precision("highest"):
            ref_fn = jax.jit(lambda x: dense.bound.batch(
                sigma_delta_encode_batch(x, t_steps)))
            oracle_fn = jax.jit(lambda x: stream.batch_counters(
                sigma_delta_encode_batch(x, t_steps))[1])
            ref = np.concatenate([np.asarray(ref_fn(jnp.asarray(iq[sl])))
                                  for sl in _chunks(n, max_batch)])
            want = {}
            for sl in _chunks(n, max_batch):
                for name, v in oracle_fn(jnp.asarray(iq[sl])).items():
                    want.setdefault(name, []).append(np.asarray(v))
            want = {k: np.concatenate(v) for k, v in want.items()}

        frames = np.concatenate([
            np.asarray(sigma_delta_encode_batch(jnp.asarray(iq[sl]),
                                                t_steps))
            for sl in _chunks(n, max_batch)])
        margin = float64_margins(params, masks, frames)
        log(f"[A] float64 reference: {int((margin < TIE_EPS).sum())}/{n} "
            f"frames hold a membrane within {TIE_EPS} of threshold")

        n_agree = int((preds == ref.argmax(-1)).sum())
        err = np.abs(logits - ref).max(-1)
        log(f"[A] predictions equal to dense argmax: {n_agree}/{n}; "
            f"max |logit - dense| = {float(err.max())!r} "
            f"(atol {LOGIT_ATOL})")
        _check_divergence((preds != ref.argmax(-1)) | (err > LOGIT_ATOL),
                          margin, "dense")

        check(set(accs) == set(want), "conv layers differ from the oracle")
        reg = default_registry()
        counts_differ = np.zeros(n, bool)
        for name in sorted(want):
            got, oracle = (accs[name].astype(np.int64),
                           want[name].astype(np.int64))
            observed = reg.value("repro_activity_accumulations_total",
                                 engine="smoke-fused", layer=name)
            log(f"[A] {name}: accumulations {int(got.sum())} (kernel) "
                f"{int(observed)} (engine observer) {int(oracle.sum())} "
                f"(stream); frames differing from stream "
                f"{int((got != oracle).sum())}, max diff "
                f"{int(np.abs(got - oracle).max())}")
            check(int(observed) == int(got.sum()),
                  f"{name}: engine observer total differs from the kernel")
            counts_differ |= got != oracle
        _check_divergence(counts_differ, margin, "the stream counters")
    finally:
        engine.close()


def phase_b(params, masks, iq: np.ndarray, max_batch: int) -> None:
    """The integer twin: fixed backend at 8 bits vs the NumPy golden."""
    from repro.configs.saocds_amc import CONFIG
    from repro.fixed import FixedQuantFn, build_golden
    from repro.serve import AsyncAMCServeEngine

    n = iq.shape[0]
    engine = AsyncAMCServeEngine(params, CONFIG, masks=masks,
                                 backend="fixed", quant_bits=8,
                                 max_batch=max_batch, warmup=False,
                                 name="smoke-fixed")
    try:
        check(engine.backend == "fixed",
              f"engine serves {engine.backend}, not fixed")
        _warm(engine, "B fixed")
        t0 = time.perf_counter()
        preds = _serve(engine, iq)
        log(f"[B] served {n} frames through submit() in "
            f"{time.perf_counter() - t0:.2f} s (smoke time, not a metric)")
        step = engine.get_version(engine.active_version).step
        got = np.concatenate([np.asarray(step(jnp.asarray(iq[sl])))
                              for sl in _chunks(n, max_batch)])
        check(got.dtype == np.int32, f"fixed logits are {got.dtype}")
        check(np.array_equal(preds, got.argmax(-1)),
              "served predictions differ from the step's own logits")
    finally:
        engine.close()
    golden = build_golden(CONFIG, params, masks=masks,
                          quant_fn=FixedQuantFn(None, bits=8))
    want = np.stack([golden.forward_iq(f) for f in iq])
    differ = np.nonzero(np.any(got != want, axis=-1))[0]
    n_equal = n - differ.size
    log(f"[B] integer logits bit-identical to golden: {n_equal}/{n}"
        + (f" (differing frames {differ.tolist()})" if differ.size else ""))
    check(n_equal == n, "fixed backend diverged from the golden datapath")


def phase_c(params, masks, iq: np.ndarray, max_batch: int, chips: int,
            rehearse: bool) -> None:
    """The engine over all chips vs the same plan on one device."""
    from repro.configs.saocds_amc import CONFIG
    from repro.data.pipeline import sigma_delta_encode_batch
    from repro.serve import AsyncAMCServeEngine

    n, t_steps = iq.shape[0], CONFIG.timesteps
    engine = AsyncAMCServeEngine(params, CONFIG, masks=masks,
                                 backend="pallas_fused", max_batch=max_batch,
                                 warmup=False, name="smoke-mesh")
    try:
        check(engine.mesh is not None
              and int(engine.mesh.shape["data"]) == chips,
              f"engine did not shard over {chips} devices")
        check(all(b % chips == 0 for b in engine.batcher.buckets),
              f"buckets {engine.batcher.buckets} not aligned to {chips}")
        _warm(engine, f"C pallas_fused x{chips}")
        _check_kernel_in_step(engine, max_batch, rehearse)
        t0 = time.perf_counter()
        preds = _serve(engine, iq)
        log(f"[C] served {n} frames through submit() in "
            f"{time.perf_counter() - t0:.2f} s (smoke time, not a metric)")
        step = engine.get_version(engine.active_version).step
        logits = np.concatenate([np.asarray(step(jnp.asarray(iq[sl])))
                                 for sl in _chunks(n, max_batch)])
        one = jax.devices()[0]
        ref_fn = jax.jit(lambda x: engine.plan.batch(
            sigma_delta_encode_batch(x, t_steps)))
        ref = np.concatenate([
            np.asarray(ref_fn(jax.device_put(iq[sl], one)))
            for sl in _chunks(n, max_batch)])
    finally:
        engine.close()
    n_agree = int((preds == ref.argmax(-1)).sum())
    log(f"[C] predictions equal to the one-device plan: {n_agree}/{n}")
    check(n_agree == n, "sharded predictions differ from one device")
    err = float(np.abs(logits - ref).max())
    log(f"[C] max |logit - one-device| = {err!r} (atol {LOGIT_ATOL})")
    check(err <= LOGIT_ATOL, "sharded logits differ from one device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only phase C, the engine over four chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="run without a TPU: interpret mode, fewer frames")
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    cache_dir = place_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); "
              "pass --rehearse to run on the CPU", file=sys.stderr)
        return 1
    check(len(devices) == args.chips,
          f"--chips {args.chips} but JAX sees {len(devices)} devices")
    log(f"jax {jax.__version__}, platform {dev.platform}, "
        f"device_kind {dev.device_kind!r}, {len(devices)} device(s)")
    log(f"compile cache: {cache_dir}")

    from repro.configs.saocds_amc import CONFIG
    from repro.data.radioml import generate_batch
    from repro.models.snn import init_snn
    from repro.plan import PlanCache, set_default_cache
    from repro.train.pruning import make_mask_pytree

    # a memory-only plan cache: nothing read from or written to $HOME
    set_default_cache(PlanCache(disk_dir=""))
    n = REHEARSAL_FRAMES if args.rehearse else FRAMES
    max_batch = 8 if args.rehearse else 64
    params = init_snn(jax.random.PRNGKey(0), CONFIG)
    masks = make_mask_pytree(params, DENSITY)
    iq, _, _ = generate_batch(0, n, snr_db=SNR_DB,
                              frame_len=CONFIG.input_width)
    iq = np.asarray(iq, np.float32)
    log(f"paper config at density {DENSITY}: {n} frames, "
        f"max batch {max_batch}")

    if args.chips == 1:
        phase_a(params, masks, iq, max_batch, args.rehearse)
        log("phase A (pallas_fused vs dense and stream): pass")
        phase_b(params, masks, iq, max_batch)
        log("phase B (fixed int8 vs golden): pass")
    else:
        phase_c(params, masks, iq, max_batch, args.chips, args.rehearse)
        log(f"phase C (pallas_fused over {args.chips} chips vs one): pass")
    log(f"smoke wall time {time.perf_counter() - t_start:.1f} s "
        "(smoke time, not a metric)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
