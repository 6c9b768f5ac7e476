"""Profiler spans: the serving worker's phases and the collector.

The engine's worker writes one span per phase of each batch on the JAX
profiler's clock (``repro.obs.trace.span``); the process telemetry adds a
``host.gc`` span per garbage collection and the collection counters.
Each test captures a profile on the CPU and reads it back with
``jax.profiler.ProfileData``, as the benchmark reads a chip's.
"""
import gc
import glob
import os
import time

import numpy as np
import pytest

import jax

from repro.api import SNNConfig, init_snn
from repro.obs import (
    MetricsRegistry,
    default_registry,
    install_process_telemetry,
    set_default_registry,
)
from repro.obs import trace as obs_trace
from repro.serve import AsyncAMCServeEngine
from repro.train.pruning import make_mask_pytree

CFG = SNNConfig(
    conv_specs=((3, 2, 4), (3, 4, 8)),
    pool=2,
    fc_specs=((32, 16), (16, 5)),
    input_width=16,
    timesteps=3,
    n_classes=5,
)
PHASES = ["engine.gather", "engine.put", "engine.dispatch", "engine.fetch",
          "engine.resolve"]


@pytest.fixture(autouse=True)
def fresh_registry():
    prev = set_default_registry(MetricsRegistry())
    try:
        yield
    finally:
        set_default_registry(prev)


@pytest.fixture(scope="module")
def weights():
    params = init_snn(jax.random.PRNGKey(0), CFG)
    return params, make_mask_pytree(params, 0.5)


def _iq(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, 2, CFG.input_width)).astype(np.float32)


def _profile(tmp_path, fn):
    """Run ``fn`` under the profiler; its host events, one list per thread:
    ``(name, start_ns, end_ns, {stat: value})`` in start order."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    threads = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            threads.append(sorted(
                ((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                  dict(ev.stats)) for ev in line.events),
                key=lambda e: e[1]))
    return threads


def _batches(thread):
    """A worker thread's engine spans cut into batches: the
    ``engine.gather`` that returned each batch, through its
    ``engine.resolve``."""
    engine = [e for e in thread if e[0].startswith("engine.")]
    out = []
    for i, e in enumerate(engine):
        if e[0] == "engine.put" and i > 0:
            end = next(j for j in range(i, len(engine))
                       if engine[j][0] == "engine.resolve")
            out.append(engine[i - 1:end + 1])
    return out


@pytest.mark.parametrize("backend, counters", [("dense", False),
                                               ("stream", True)])
def test_worker_phases_tile_each_batch_in_order(tmp_path, weights,
                                                backend, counters):
    params, masks = weights
    eng = AsyncAMCServeEngine(params, CFG, masks=masks, backend=backend,
                              buckets=[4], max_delay_ms=5)
    try:
        def serve():
            # the worker's gather in progress predates the profile: let
            # it time out (0.1 s) so each batch's gather is recorded
            time.sleep(0.25)
            eng.classify(_iq(12), timeout=60)

        threads = _profile(tmp_path, serve)
    finally:
        eng.close()
    worker = [t for t in threads
              if any(e[0] == "engine.put" for e in t)]
    assert len(worker) == 1, "every batch is served on the worker thread"
    thread = worker[0]
    batches = _batches(thread)
    assert len(batches) == 3
    want = PHASES[:4] + ["engine.counters"] * counters + PHASES[4:]
    for spans in batches:
        assert [e[0] for e in spans] == want
        for a, b in zip(spans, spans[1:]):
            assert a[2] <= b[1], f"{a[0]} overlaps {b[0]}"
        gather, dispatch = spans[0], spans[2]
        assert dispatch[3] == {"bucket": 4, "n_real": 4, "backend": backend}
        # the bucket fill is nested in the gather that returned the batch
        form = [e for e in thread if e[0] == "batcher.form"
                and gather[1] <= e[1] and e[2] <= gather[2]]
        assert len(form) == 1 and form[0][3] == {"n_real": 4}


def test_gc_collection_is_a_span_and_counted(tmp_path):
    install_process_telemetry()
    reg = default_registry()
    n0 = reg.value("repro_gc_collections_total", generation="2")
    s0 = reg.value("repro_gc_pause_seconds_total", generation="2")
    threads = _profile(tmp_path, gc.collect)
    spans = [e for t in threads for e in t if e[0] == "host.gc"]
    assert any(e[3] == {"generation": 2} and e[2] > e[1] for e in spans)
    assert reg.value("repro_gc_collections_total", generation="2") >= n0 + 1
    assert reg.value("repro_gc_pause_seconds_total", generation="2") > s0


def test_install_is_idempotent_and_follows_the_default_registry():
    install_process_telemetry()
    install_process_telemetry()
    assert gc.callbacks.count(obs_trace._on_gc) == 1
    n = default_registry().value("repro_gc_collections_total",
                                 generation="0")
    fresh = MetricsRegistry()
    prev = set_default_registry(fresh)
    try:
        install_process_telemetry()
        gc.collect(0)
        # a fresh registry exposes the same process-wide totals
        assert fresh.value("repro_gc_collections_total",
                           generation="0") >= n + 1
        assert "repro_gc_pause_seconds_total" in fresh.to_prometheus()
    finally:
        set_default_registry(prev)


def test_pulled_counter_reads_its_function():
    reg = MetricsRegistry()
    box = [3]
    fam = reg.counter("repro_test_pulled_total", "a pulled counter",
                      ("kind",))
    fam.pull(lambda: box[0], kind="a")
    assert reg.value("repro_test_pulled_total", kind="a") == 3
    box[0] = 5
    assert 'repro_test_pulled_total{kind="a"} 5' in reg.to_prometheus()
    assert MetricsRegistry.merged([reg, reg]).value(
        "repro_test_pulled_total", kind="a") == 10
    with pytest.raises(ValueError):
        reg.gauge("repro_test_gauge").pull(lambda: 1)

