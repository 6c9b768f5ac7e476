"""The control of ``correct`` fails, and a sound float32 run passes.

At the cells' own sizes (the paper network, the 4096-frame pool), on the
CPU: the float reference with its weights cut to what a three-pass
bfloat16 product keeps (``Precision.HIGH``) must come out not correct, as
must the integer twin at 4 bits; the float network in float32 at full
weight precision, summed in another order than the float64 reference,
must come out correct.
"""

import numpy as np
import pytest

import check
import control
import reference
import run as bench_run

SEEDS = [11, 12]


def _cell(name):
    spec = bench_run.load_json(bench_run.ROOT / "BENCHMARK.json")
    cell = bench_run.entry(spec["workloads"], name, "workload")
    cfg = bench_run.load_json(bench_run.BENCH / "configs"
                              / f"{cell['config']}.json")
    traffic = bench_run.load_json(bench_run.BENCH / "traffic"
                                  / f"{cell['traffic']}.json")
    return cfg, traffic


def _setup(name, seed):
    import weights as weights_mod

    cfg, traffic = _cell(name)
    pool, labels = bench_run.make_pool(seed, cfg, traffic)
    w = weights_mod.to_host(bench_run.make_weights(seed, cfg, pool, labels))
    return cfg, pool, w


@pytest.mark.parametrize("seed", SEEDS)
def test_float_control_is_not_correct_and_float32_is(seed):
    cfg, pool, w = _setup("f32-d50.saturate", seed)
    reading = control.control_reading(cfg, seed, pool, w, "emulated-high")
    assert not reading["correct"], reading

    sample = check.sample_frames(seed, pool.shape[0],
                                 int(cfg["check"]["sample_frames"]))
    logits, _ = reference.float_reference(pool[sample], w, cfg["network"],
                                          dot=np.matmul, dtype=np.float32)
    numbers, _ = check.compare(cfg, seed, pool, w, sample,
                               logits.argmax(axis=1), missing=0)
    assert check.passed(numbers), numbers


@pytest.mark.parametrize("seed", SEEDS)
def test_integer_control_is_not_correct(seed):
    cfg, pool, w = _setup("int8-d50.saturate", seed)
    reading = control.control_reading(cfg, seed, pool, w, "int4")
    assert not reading["correct"], reading
