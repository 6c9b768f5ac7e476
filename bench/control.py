#!/usr/bin/env python3
"""The control of ``correct``: the reference, one precision down, must fail.

    python3 bench/control.py --workload f32-d50.saturate --seeds 1,2,3

For each seed it makes the cell's weights and frame pool and puts the
plain reference, computed one precision below the configuration's, in the
program's place: every sampled frame answered once by it.  The answers go
through the same comparison as a run's (``bench/check.py``) and must come
out not correct.

* Float32 at ``Precision.HIGHEST``: the control is three-pass bfloat16
  (``Precision.HIGH``).  ``tpu-high`` runs the network in float32 with
  ``jax.numpy`` dots at that precision on the default device (a TPU);
  ``emulated-high`` runs it in NumPy float32 with each weight cut to what
  a three-pass product keeps against a spike (head plus tail in bfloat16),
  which is the same arithmetic and runs anywhere.
* Integer at 8 bits: the control is the integer twin at 4 bits.

Prints one JSON line per seed and mode.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
from functools import partial

import numpy as np

import check
import reference
import run as bench_run


def control_answers(cfg: dict, iq: np.ndarray, weights: dict,
                    mode: str) -> np.ndarray:
    """One class per frame from the reference at the control's precision."""
    net = cfg["network"]
    if mode == "int4":
        return reference.integer_reference(iq, weights, net, 4).argmax(axis=1)
    if mode == "emulated-high":
        logits, _ = reference.float_reference(
            iq, weights, net, weight_map=reference.bf16_3pass_weights,
            dot=np.matmul, dtype=np.float32)
        return logits.argmax(axis=1)
    if mode == "tpu-high":
        import jax
        import jax.numpy as jnp

        dot = partial(jnp.matmul, precision=jax.lax.Precision.HIGH)
        logits, _ = reference.float_reference(iq, weights, net, dot=dot,
                                              xp=jnp, dtype=np.float32)
        return logits.argmax(axis=1)
    raise ValueError(f"unknown control mode {mode!r}")


def modes_for(cfg: dict) -> list:
    return ["int4"] if cfg["check"]["reference"] == "integer" \
        else ["emulated-high", "tpu-high"]


def control_reading(cfg: dict, seed: int, pool: np.ndarray, weights: dict,
                    mode: str) -> dict:
    """The comparison's numbers with the control in the program's place."""
    sample = check.sample_frames(seed, pool.shape[0],
                                 int(cfg["check"]["sample_frames"]))
    answers = control_answers(cfg, pool[sample], weights, mode)
    numbers, info = check.compare(cfg, seed, pool, weights, sample, answers,
                                  missing=0)
    return {"mode": mode, "seed": seed, "correct": check.passed(numbers),
            "check": numbers, **info}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--modes", default=None)
    args = ap.parse_args(argv)
    spec = bench_run.load_json(bench_run.ROOT / "BENCHMARK.json")
    cell = bench_run.entry(spec["workloads"], args.workload, "workload")
    cfg = bench_run.load_json(bench_run.BENCH / "configs"
                              / f"{cell['config']}.json")
    traffic = bench_run.load_json(bench_run.BENCH / "traffic"
                                  / f"{cell['traffic']}.json")
    import weights as weights_mod

    modes = args.modes.split(",") if args.modes else modes_for(cfg)
    for seed in [int(s) for s in args.seeds.split(",")]:
        pool, labels = bench_run.make_pool(seed, cfg, traffic)
        w = weights_mod.to_host(bench_run.make_weights(seed, cfg, pool,
                                                       labels))
        for mode in modes:
            print(json.dumps(control_reading(cfg, seed, pool, w, mode)),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
