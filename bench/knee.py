#!/usr/bin/env python3
"""Find the knee of an open-loop cell: sweep offered rates on one engine.

    python3 bench/knee.py --workload f32-d50.poisson --seed 7 \\
        --rates 4000,6000,8000 --seconds 8

Builds the cell's engine once and offers each rate (the cell's traffic
with ``rate_fps`` replaced) for ``--seconds``.  For each rate it prints
the completions per second, the median and 99th percentile latency from
the due time, and the median latency of the window's first and last
fifths: a backlog that grows through the window shows as a last fifth
far above the first.  The knee is the highest rate whose completions keep
pace with the offer and whose backlog does not grow.  A tool for setting
a cell's rate, not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
import types

import numpy as np

import run as bench_run
import loadgen
import measure


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)

    spec = bench_run.load_json(bench_run.ROOT / "BENCHMARK.json")
    cell = bench_run.entry(spec["workloads"], args.workload, "workload")
    cfg = bench_run.load_json(bench_run.BENCH / "configs"
                              / f"{cell['config']}.json")
    traffic = bench_run.load_json(bench_run.BENCH / "traffic"
                                  / f"{cell['traffic']}.json")
    import jax

    jax.config.update("jax_compilation_cache_dir", str(bench_run.CACHE_DIR))
    devices = bench_run.devices_or_refuse(int(cell["chips"]), False)
    sys.path.insert(0, str(bench_run.ROOT / "src"))
    pool, labels = bench_run.make_pool(args.seed, cfg, traffic)
    weights = bench_run.make_weights(args.seed, cfg, pool, labels)
    engine = bench_run.build_engine(cfg, weights, int(cell["chips"]))
    try:
        for rate in [float(r) for r in args.rates.split(",")]:
            offered = dict(traffic, rate_fps=rate)
            log, t0, t1 = loadgen.run(lambda k: engine.submit(pool[k]),
                                      pool.shape[0], offered, args.seconds,
                                      args.seed)
            run = types.SimpleNamespace(log=log, t0=t0, t1=t1)
            lat = measure.latencies_s(run)
            fifth = max(1, lat.size // 5)
            done = log.done[:log.n]
            completed = np.count_nonzero(log.ok[:log.n] & (done <= t1))
            print(json.dumps({
                "rate_fps": rate, "sent": int(log.n),
                "completed_per_s": completed / args.seconds,
                "p50_ms": measure.percentile(lat, 50) * 1e3,
                "p99_ms": measure.percentile(lat, 99) * 1e3,
                "first_fifth_p50_ms": float(np.median(lat[:fifth])) * 1e3,
                "last_fifth_p50_ms": float(np.median(lat[-fifth:])) * 1e3,
                "device": devices[0].device_kind}), flush=True)
    finally:
        engine.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
