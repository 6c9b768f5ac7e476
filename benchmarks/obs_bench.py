"""Observability overhead benchmark: traced vs untraced serving.

The observability layer's contract is "on by default, invisible in the
numbers": full request tracing (sample_every=1) plus the metrics-registry
mirrors must cost <5% of both throughput and p99 latency on the async
serving path.  This bench measures exactly that and records the verdict
to ``BENCH_obs.json``:

* **untraced** — tracing disabled (the hot path pays one module-global
  read per request), metrics registry still live (it always is);
* **traced** — ``enable_tracing(sample_every=1)``: every request carries
  a full span timeline through submit -> enqueue -> dequeue ->
  batch-form -> jit-step -> complete.

Each attempt runs the same frame pile through a fresh ``ServeStats``
window in both modes and compares; the gate passes if ANY attempt lands
under the overhead bar on both axes (scheduler noise on shared CI boxes
produces occasional outlier attempts — requiring every attempt to pass
gates on the machine, not the code).

Also recorded: spans/sec the tracer absorbed, and an **activity-gauge
sanity block** — the live per-batch gauges replayed over the pinned
``tests/test_stream_golden.py`` input must reproduce the paper's
Tables I/III totals bit-exactly.

Run:  PYTHONPATH=src python benchmarks/obs_bench.py [--smoke] [--out p]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

import jax

from repro.api import init_snn
from repro.configs.saocds_amc import CONFIG as CFG
from repro.obs import (
    AlertManager,
    BurnRateEngine,
    BurnRateWatcher,
    MetricsRegistry,
    SeriesWatcher,
    TimeSeriesRecorder,
    default_serve_slos,
    disable_tracing,
    enable_tracing,
    scaled_windows,
    set_default_registry,
    to_perfetto,
    validate_perfetto,
)
from repro.serve import AsyncAMCServeEngine
from repro.serve.engine import ServeStats
from repro.train.pruning import make_mask_pytree

NAME = "obs_bench"

DENSITY = 0.5
MAX_BATCH = 64
MAX_DELAY_MS = 2.0
OVERHEAD_BAR = 0.05      # <5% on throughput AND p99
P99_SLACK_MS = 0.25      # absolute floor: sub-ms p99s jitter more than 5%

#: Pinned Tables I/III golden totals for the paper config at 50% density
#: (the literals asserted by tests/test_stream_golden.py; duplicated here
#: so the bench artifact is self-contained).
GOLDEN_ACCUMULATIONS = {"conv1": 89015, "conv2": 306721, "conv3": 254284}
GOLDEN_TOTAL = 650020


def _synthetic_frames(n: int) -> np.ndarray:
    rng = np.random.default_rng(0)
    iq = rng.normal(size=(n, 2, CFG.input_width)).astype(np.float32)
    return iq / np.sqrt(np.mean(iq**2, axis=(-2, -1), keepdims=True))


def _one_pass(engine, iq: np.ndarray) -> dict:
    """Serve the pile through a fresh stats window; return its summary.

    Throughput is wall-clock around *this* pass: the engine-maintained
    ``stats.wall_s`` window opens at the engine's first-ever enqueue, so
    on a reused engine it spans every earlier pass and would make each
    successive measurement look mechanically slower.
    """
    engine.stats = ServeStats(backend=engine.backend)
    t0 = time.perf_counter()
    engine.classify(iq)
    wall = time.perf_counter() - t0
    s = engine.stats.summary()
    s["throughput_fps"] = iq.shape[0] / max(wall, 1e-9)
    return s


def measure_overhead(n_frames: int, attempts: int = 3) -> dict:
    """Traced vs untraced passes over one warm engine; per-attempt pairs.

    The traced side now carries the *whole* analysis plane live — full
    tracing plus a :class:`TimeSeriesRecorder` sweeping the registry and
    a burn-rate + drift evaluation on every sweep — so the <5% bar gates
    recorder and SLO-evaluation overhead too, not just span appends.

    Each attempt installs a **fresh per-pass** :class:`TraceLog` sized to
    the pass (``capacity >= n_frames``) and validates its dump before
    the next pass begins: an earlier pass's ring can never evict this
    pass's traces (the regression tests/test_obs_analysis.py pins).
    """
    import threading

    params = init_snn(jax.random.PRNGKey(0), CFG)
    masks = make_mask_pytree(params, DENSITY)
    iq = _synthetic_frames(n_frames)

    engine = AsyncAMCServeEngine(
        params, CFG, masks=masks, backend="dense", max_batch=MAX_BATCH,
        max_delay_ms=MAX_DELAY_MS, workers=1, count_activity=False,
        activity_gauges=False, name="obs-bench")
    engine.classify(iq[:MAX_BATCH])      # warm the serving path
    pairs = []
    spans_per_s = 0.0
    perfetto = {"n_events": 0, "problems": ["no traced pass ran"]}
    try:
        for _ in range(max(1, attempts)):
            disable_tracing()
            untraced = _one_pass(engine, iq)
            # fresh per-pass ring, never smaller than the pass itself
            log = enable_tracing(sample_every=1,
                                 capacity=max(4096, n_frames))
            # live analysis plane riding on the traced pass (0.1s:
            # 5x denser than the serve driver's 0.5s default — a GIL
            # headroom test, not just a liveness check)
            recorder = TimeSeriesRecorder(interval_s=0.1, capacity=4096)
            burn = BurnRateEngine(recorder, default_serve_slos(),
                                  windows=scaled_windows(1.0 / 600.0))
            manager = AlertManager()
            watchers = [SeriesWatcher(recorder, manager),
                        BurnRateWatcher(burn, manager)]
            stop = threading.Event()

            def analysis_loop(rec=recorder, ws=watchers, ev=stop):
                while not ev.wait(rec.interval_s):
                    rec.sample()
                    for w in ws:
                        w.step()

            analysis = threading.Thread(target=analysis_loop, daemon=True)
            analysis.start()
            t0 = time.perf_counter()
            traced = _one_pass(engine, iq)
            traced_wall = time.perf_counter() - t0
            stop.set()
            analysis.join(timeout=5.0)
            # per-pass dump validation *before* the next pass can touch
            # any tracer state: every frame of this pass must be present
            dump = log.dump()
            traced["dump_completed"] = dump["n_completed"]
            traced["dump_complete"] = bool(
                dump["n_completed"] == n_frames
                and len(dump["traces"]) == n_frames)
            doc = to_perfetto(dump)
            perfetto = {"n_events": len(doc["traceEvents"]),
                        "problems": validate_perfetto(doc)}
            traced["analysis_sweeps"] = recorder.n_sweeps
            n_events = sum(len(tr.events) for tr in log.completed())
            spans_per_s = max(spans_per_s, n_events / max(traced_wall, 1e-9))
            tput_over = (untraced["throughput_fps"] /
                         max(traced["throughput_fps"], 1e-9)) - 1.0
            p99_over_ms = traced["p99_ms"] - untraced["p99_ms"]
            p99_ok = (traced["p99_ms"] <= untraced["p99_ms"]
                      * (1.0 + OVERHEAD_BAR) + P99_SLACK_MS)
            pairs.append({
                "untraced": untraced,
                "traced": traced,
                "throughput_overhead": tput_over,
                "p99_delta_ms": p99_over_ms,
                "pass": bool(tput_over < OVERHEAD_BAR and p99_ok
                             and traced["dump_complete"]),
            })
    finally:
        disable_tracing()
        engine.close()
    return {
        "attempts": pairs,
        "spans_per_s": spans_per_s,
        "best_throughput_overhead":
            min(p["throughput_overhead"] for p in pairs),
        "dumps_complete": all(p["traced"]["dump_complete"] for p in pairs),
        "perfetto": perfetto,
        "pass": any(p["pass"] for p in pairs),
    }


def activity_sanity() -> dict:
    """Replay the golden stream input through the live activity gauges.

    Same recipe as ``tests/test_stream_golden.py``: paper config, seed-0
    init, 50% masks, seed-0 binary frames.  The per-batch gauges must
    land on the pinned Tables I/III accumulation literals *exactly* —
    fp32 counters are integral below 2**24.
    """
    import jax.numpy as jnp

    from repro.api import compile_plan, compile_snn
    from repro.obs import ActivityObserver
    from repro.plan import PlanCache

    program = compile_snn(CFG)
    params = init_snn(jax.random.PRNGKey(0), CFG)
    masks = make_mask_pytree(params, DENSITY)
    plan = compile_plan(program, params, masks=masks, assignment="stream",
                        cache=PlanCache(disk_dir=""))
    rng = np.random.default_rng(0)
    frames = jnp.asarray(
        (rng.random((1, CFG.timesteps, CFG.conv_specs[0][1],
                     CFG.input_width)) < 0.5).astype(np.float32))
    _, accs = plan.batch_counters(frames)
    reg = MetricsRegistry()
    obs = ActivityObserver(plan, registry=reg, engine="sanity")
    obs.observe({k: np.asarray(v) for k, v in accs.items()}, n_real=1)
    got = {name: int(reg.value("repro_activity_accumulations_total",
                               engine="sanity", layer=name))
           for name in GOLDEN_ACCUMULATIONS}
    return {
        "golden": GOLDEN_ACCUMULATIONS,
        "observed": got,
        "total": sum(got.values()),
        "golden_total": GOLDEN_TOTAL,
        "exact": bool(got == GOLDEN_ACCUMULATIONS
                      and sum(got.values()) == GOLDEN_TOTAL),
    }


def alert_pipeline(n_baseline: int = 24, n_shift: int = 24,
                   n_revert: int = 64) -> dict:
    """Injected-drift scenario: density shift -> drift alert -> revert.

    The full detection pipeline on a fake clock: live Tables I/III
    activity gauges (``ActivityObserver`` over the streaming plan's
    in-graph counters) -> ``TimeSeriesRecorder`` -> EWMA drift detectors
    -> ``AlertManager``.  Phase 1 feeds frames at the paper's 50% input
    density (baseline learned, nothing may fire); phase 2 swaps the
    scenario to 15% density and counts samples until ``sparsity_drift``
    fires; phase 3 reverts and counts samples until it resolves.  The
    verdict (fired within the budget AND resolved after revert AND no
    baseline false positive) is part of the bench gate.
    """
    import jax.numpy as jnp

    from repro.api import compile_plan, compile_snn
    from repro.obs import ActivityObserver
    from repro.plan import PlanCache

    program = compile_snn(CFG)
    params = init_snn(jax.random.PRNGKey(0), CFG)
    masks = make_mask_pytree(params, DENSITY)
    plan = compile_plan(program, params, masks=masks, assignment="stream",
                        cache=PlanCache(disk_dir=""))
    rng = np.random.default_rng(0)

    t = {"now": 0.0}
    reg = MetricsRegistry()
    obs = ActivityObserver(plan, registry=reg, engine="drift")
    recorder = TimeSeriesRecorder(reg, interval_s=1.0, capacity=4096,
                                  clock=lambda: t["now"])
    manager = AlertManager(registry=reg, clock=lambda: t["now"])
    watcher = SeriesWatcher(recorder, manager)

    def feed(density: float) -> None:
        frames = jnp.asarray(
            (rng.random((1, CFG.timesteps, CFG.conv_specs[0][1],
                         CFG.input_width)) < density).astype(np.float32))
        _, accs = plan.batch_counters(frames)
        obs.observe({k: np.asarray(v) for k, v in accs.items()}, n_real=1)
        t["now"] += 1.0
        recorder.sample()
        watcher.step()

    def drift_firing() -> bool:
        return any(a.name == "sparsity_drift" for a in manager.firing())

    for _ in range(n_baseline):
        feed(DENSITY)
    baseline_clean = not manager.firing()

    fired_after = None
    for i in range(n_shift):
        feed(0.15)
        if fired_after is None and drift_firing():
            fired_after = i + 1

    resolved_after = None
    for i in range(n_revert):
        feed(DENSITY)
        if resolved_after is None and not drift_firing():
            resolved_after = i + 1

    gauge = reg.value("repro_alerts_firing", alert="sparsity_drift")
    return {
        "n_baseline": n_baseline,
        "n_shift": n_shift,
        "n_revert": n_revert,
        "baseline_clean": bool(baseline_clean),
        "fired_after_samples": fired_after,
        "resolved_after_samples": resolved_after,
        "firing_gauge_after_revert": float(gauge),
        "transitions": len(manager.history),
        "pass": bool(baseline_clean and fired_after is not None
                     and resolved_after is not None and gauge == 0.0),
    }


def run(n_frames: int = 4096, attempts: int = 3) -> dict:
    # isolate the bench from whatever the process registry accumulated
    prev = set_default_registry(MetricsRegistry())
    try:
        overhead = measure_overhead(n_frames, attempts=attempts)
        sanity = activity_sanity()
        drift = alert_pipeline()
    finally:
        set_default_registry(prev)
    return {
        "n_frames": n_frames,
        "density": DENSITY,
        "jax_backend": jax.default_backend(),
        "overhead_bar": OVERHEAD_BAR,
        "overhead": overhead,
        "activity_sanity": sanity,
        "alert_pipeline": drift,
        "pass": bool(overhead["pass"] and sanity["exact"]
                     and drift["pass"]
                     and not overhead["perfetto"]["problems"]),
    }


def check(res: dict) -> list:
    """Regression-gate hook for benchmarks/run.py: list of failures."""
    fails = []
    if not res["overhead"]["pass"]:
        best = res["overhead"]["best_throughput_overhead"]
        fails.append(f"tracing overhead above {OVERHEAD_BAR:.0%} on every "
                     f"attempt (best throughput overhead {best:.1%})")
    if not res["activity_sanity"]["exact"]:
        fails.append(f"activity gauges diverged from Tables I/III goldens: "
                     f"{res['activity_sanity']['observed']}")
    if not res["overhead"].get("dumps_complete", True):
        fails.append("per-pass trace dump incomplete: an earlier pass's "
                     "ring evicted traces before validation")
    perfetto = res["overhead"].get("perfetto", {})
    if perfetto.get("problems"):
        fails.append(f"perfetto export schema-invalid: "
                     f"{perfetto['problems'][:3]}")
    drift = res.get("alert_pipeline", {})
    if drift and not drift.get("pass"):
        fails.append(
            f"alert pipeline: baseline_clean={drift.get('baseline_clean')} "
            f"fired_after={drift.get('fired_after_samples')} "
            f"resolved_after={drift.get('resolved_after_samples')}")
    return fails


def format_table(res: dict) -> str:
    o = res["overhead"]
    lines = [f"Obs bench: {res['n_frames']} frames, "
             f"{res['jax_backend']} backend, bar {res['overhead_bar']:.0%}"]
    for i, p in enumerate(o["attempts"]):
        lines.append(
            f"  attempt {i}: untraced {p['untraced']['throughput_fps']:8.1f} "
            f"frames/s  traced {p['traced']['throughput_fps']:8.1f}  "
            f"overhead {p['throughput_overhead']:+6.1%}  "
            f"p99 delta {p['p99_delta_ms']:+6.2f}ms  "
            f"{'PASS' if p['pass'] else 'fail'}")
    lines.append(f"  spans/sec absorbed: {o['spans_per_s']:.0f}")
    p = o.get("perfetto", {})
    lines.append(f"  perfetto export: {p.get('n_events', 0)} events, "
                 f"{'VALID' if not p.get('problems') else p['problems'][:2]}"
                 f"  per-pass dumps "
                 f"{'complete' if o.get('dumps_complete') else 'EVICTED'}")
    s = res["activity_sanity"]
    lines.append(f"  activity gauges vs Tables I/III: "
                 f"{'EXACT' if s['exact'] else 'DIVERGED'} "
                 f"(total {s['total']} vs golden {s['golden_total']})")
    d = res.get("alert_pipeline", {})
    if d:
        lines.append(
            f"  alert pipeline: drift fired after "
            f"{d['fired_after_samples']} shifted samples, resolved after "
            f"{d['resolved_after_samples']} reverted samples "
            f"({'PASS' if d['pass'] else 'fail'})")
    lines.append(f"  verdict: {'PASS' if res['pass'] else 'FAIL'}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced frame count for CI smoke runs")
    ap.add_argument("--frames", type=int, default=None)
    ap.add_argument("--attempts", type=int, default=3)
    ap.add_argument("--out", default="BENCH_obs.json")
    args = ap.parse_args(argv)

    n = args.frames if args.frames else (256 if args.smoke else 4096)
    res = run(n_frames=n, attempts=args.attempts)
    print(format_table(res))
    out = pathlib.Path(args.out)
    out.write_text(json.dumps(res, indent=1, default=str))
    print(f"wrote {out}")
    if not args.smoke and not res["pass"]:
        print("FAIL: observability overhead / sanity gate")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
