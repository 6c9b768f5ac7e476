"""Benchmark aggregator: ``PYTHONPATH=src python -m benchmarks.run``.

Runs one module per paper table/figure plus the kernel microbench and the
roofline report, prints each, and writes JSON records to
``experiments/bench/``.  ``--quick`` skips the training-based accuracy
sweep (several CPU-minutes); ``--only <name>`` runs one module.

``--check-regression`` is the perf gate: it reruns ``fusion_bench`` at
the committed batch size and exit-fails if any backend's
``fused_speedup`` or layered fps dropped more than ``--tolerance``
(default 20%) below the committed ``BENCH_fusion.json``.  It then runs
the observability gate (``obs_bench``): tracing overhead must stay under
its absolute bar and the live activity gauges must reproduce the
Tables I/III goldens exactly.  CI runs it on every push so a change that
silently slows the fused streaming path (or de-fuses it, or makes
tracing expensive) turns the build red.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time
import traceback

OUT = pathlib.Path("experiments/bench")
HISTORY = pathlib.Path("BENCH_history.jsonl")

#: Per-bench headline extractors for the cumulative history log. Each
#: maps a module's ``run()`` result to the few scalars whose trajectory
#: matters; benches without an entry fall back to top-level scalars.
_HEADLINES = {
    "fusion_bench": lambda r: {
        f"{row['backend']}_{m}": row[m]
        for row in r.get("execution", ())
        for m in ("layered_fps", "fused_speedup")},
    "obs_bench": lambda r: {
        "best_throughput_overhead":
            r["overhead"]["best_throughput_overhead"],
        "spans_per_s": r["overhead"]["spans_per_s"],
        "drift_fired_after":
            r.get("alert_pipeline", {}).get("fired_after_samples"),
        "pass": r["pass"]},
    "serve_bench": lambda r: {
        "async_fps": r.get("async", {}).get("throughput_fps"),
        "speedup": r.get("speedup")},
}


def _headline(name: str, res: dict) -> dict:
    extract = _HEADLINES.get(name)
    if extract is not None:
        try:
            return extract(res)
        except (KeyError, TypeError):
            pass  # artifact shape changed: fall through to the generic cut
    return {k: v for k, v in res.items()
            if isinstance(v, (int, float, bool, str)) and k != "name"}


def append_history(name: str, res: dict, sha: str = "",
                   path: pathlib.Path = HISTORY) -> dict:
    """Append one bench invocation's headline to the cumulative log.

    One JSON line per run — the per-bench artifacts are overwritten each
    run, this file is only ever appended, so the perf *trajectory* stays
    reconstructable.  ``sha`` is stamped by the caller (``--sha`` or the
    ``GIT_SHA`` env var): no in-process timestamping or git calls.
    """
    record = {"bench": name, "sha": sha, "metrics": _headline(name, res)}
    with open(path, "a") as f:
        f.write(json.dumps(record, default=str) + "\n")
    return record


def _modules(quick: bool):
    from . import (
        accuracy_sweep,
        deploy_bench,
        fixed_bench,
        fleet_bench,
        fusion_bench,
        kernel_bench,
        obs_bench,
        robustness_bench,
        roofline,
        serve_bench,
        table1_goap_vs_sw,
        table2_coo_overhead,
        table3_accum_ratio,
        table45_perf_model,
    )

    mods = [table1_goap_vs_sw, table2_coo_overhead, table3_accum_ratio,
            table45_perf_model, kernel_bench, fusion_bench, roofline]
    if not quick:
        # several CPU-minutes each: training sweep, full 4096-frame serve
        # run, the hot-swap-under-load deployment bench, the
        # scenario-robustness sweep across all four backends, the
        # float-vs-fixed fidelity sweep of the integer tier, the
        # open-loop fleet load/autoscaling harness, and the observability
        # overhead gate
        mods.extend([accuracy_sweep, serve_bench, deploy_bench,
                     robustness_bench, fixed_bench, fleet_bench, obs_bench])
    return mods


def _gate_failures(base: dict, best: dict, tolerance: float):
    """Compare best-observed fresh metrics against the committed floors.

    ``fused_speedup`` is a within-run ratio, compared directly.
    ``layered_fps`` is absolute throughput, so its floor is rescaled by
    the dense backend's fresh/committed layered-fps ratio — dense is the
    machine-speed proxy, making the gate meaningful on hosts (CI runners)
    slower or faster than the one that committed the baseline.
    """
    base_rows = {r["backend"]: r for r in base["execution"]}
    dense_base = base_rows.get("dense", {}).get("layered_fps")
    dense_fresh = best.get("dense", {}).get("layered_fps")
    calib = (float(dense_fresh) / float(dense_base)
             if dense_base and dense_fresh else 1.0)
    failures, lines = [], [f"  machine-speed calibration (dense layered): "
                           f"x{calib:.2f}"]
    for br in base["execution"]:
        name = br["backend"]
        fr = best.get(name)
        if fr is None:
            failures.append(f"{name}: backend missing from fresh run")
            continue
        for metric in ("fused_speedup", "layered_fps"):
            scale = calib if metric == "layered_fps" else 1.0
            floor = float(br[metric]) * scale * (1.0 - tolerance)
            got = float(fr[metric])
            verdict = "ok" if got >= floor else "REGRESSED"
            lines.append(f"  {name:12s} {metric:13s} committed "
                         f"{float(br[metric]):10.2f}  best fresh "
                         f"{got:10.2f}  floor {floor:10.2f}  {verdict}")
            if got < floor:
                failures.append(
                    f"{name}.{metric}: {got:.2f} < floor {floor:.2f} "
                    f"(committed {float(br[metric]):.2f}, "
                    f"tolerance {tolerance:.0%})")
    return failures, lines


def check_regression(baseline: pathlib.Path, tolerance: float,
                     reps: int = 3, attempts: int = 3) -> int:
    """Rerun fusion_bench at the committed batch; fail on >tolerance drops.

    Gated metrics, per backend row present in the committed artifact:
    ``fused_speedup`` (within-run ratio — catches de-fusing) and
    ``layered_fps`` (throughput, machine-calibrated — catches backend
    slowdowns).  Wall-clock benchmarks on shared hosts are noisy, so the
    gate keeps the best value per metric over up to ``attempts`` fresh
    runs and only fails if a floor is still unmet after the last.
    """
    from . import fusion_bench, obs_bench

    base = json.loads(baseline.read_text())
    print(f"perf gate: baseline {baseline} "
          f"(batch {base['batch']}, {base['jax_backend']})")
    best: dict = {}
    failures, lines = ["no fresh run"], []
    for attempt in range(attempts):
        fresh = fusion_bench.run(batch=int(base["batch"]), reps=reps)
        print(f"-- attempt {attempt + 1}/{attempts}")
        print(fusion_bench.format_table(fresh))
        for r in fresh["execution"]:
            slot = best.setdefault(r["backend"], dict(r))
            for metric in ("fused_speedup", "layered_fps"):
                slot[metric] = max(float(slot[metric]), float(r[metric]))
        failures, lines = _gate_failures(base, best, tolerance)
        if not failures:
            break
    print("\n".join(lines))
    if failures:
        print("perf gate FAILED:")
        for f in failures:
            print(f"  - {f}")
        return 1
    print(f"perf gate OK ({len(base['execution'])} backends, "
          f"tolerance {tolerance:.0%})")

    # Observability gate: tracing overhead and activity-gauge fidelity are
    # within-run comparisons, so no committed baseline (and no machine
    # calibration) is needed — the bar is absolute.
    print("\nobs gate: traced-vs-untraced overhead + activity gauges")
    obs_res = obs_bench.run(n_frames=512, attempts=attempts)
    print(obs_bench.format_table(obs_res))
    obs_failures = obs_bench.check(obs_res)
    if obs_failures:
        print("obs gate FAILED:")
        for f in obs_failures:
            print(f"  - {f}")
        return 1
    print("obs gate OK")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--check-regression", action="store_true",
                    help="perf gate: rerun fusion_bench and compare "
                         "against the committed baseline")
    ap.add_argument("--baseline", default="BENCH_fusion.json",
                    help="committed artifact the perf gate diffs against")
    ap.add_argument("--tolerance", type=float, default=0.2,
                    help="allowed fractional drop per gated metric")
    ap.add_argument("--sha", default=None,
                    help="git SHA to stamp into BENCH_history.jsonl "
                         "(default: the GIT_SHA env var)")
    ap.add_argument("--no-history", action="store_true",
                    help="skip the BENCH_history.jsonl append")
    args = ap.parse_args(argv)
    sha = args.sha if args.sha is not None else os.environ.get("GIT_SHA", "")
    from repro.launch.compile_cache import place_compile_cache

    place_compile_cache()

    if args.check_regression:
        return check_regression(pathlib.Path(args.baseline), args.tolerance)

    OUT.mkdir(parents=True, exist_ok=True)
    failures = 0
    for mod in _modules(args.quick):
        if args.only and mod.NAME != args.only:
            continue
        print(f"\n=== {mod.NAME} " + "=" * max(0, 60 - len(mod.NAME)))
        t0 = time.perf_counter()
        try:
            res = mod.run()
            print(mod.format_table(res))
            (OUT / f"{mod.NAME}.json").write_text(
                json.dumps(res, indent=1, default=str))
            if not args.no_history:
                append_history(mod.NAME, res, sha=sha)
            print(f"[{mod.NAME}: {time.perf_counter() - t0:.1f}s]")
        except Exception:
            failures += 1
            print(f"[{mod.NAME}: FAILED]\n{traceback.format_exc()}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
