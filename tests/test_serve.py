"""Serving tier: micro-batcher flush policy, tail padding, autotuner
fallback, percentile math, activity counting.

Tiny reduced config throughout so binds/compiles stay cheap; timing
assertions use generous margins (CI containers jitter).
"""
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import SNNConfig, compile_snn, init_snn, register_backend
from repro.distributed.sharding import serve_mesh
from repro.serve import (
    AMCServeEngine,
    AsyncAMCServeEngine,
    DeadlineExceeded,
    EngineClosed,
    MicroBatcher,
    QueueFull,
    ServeStats,
    autotune_backend,
)
from repro.serve.batcher import bucket_for, make_buckets
from repro.train.pruning import make_mask_pytree

CFG = SNNConfig(
    conv_specs=((3, 2, 4), (3, 4, 8)),
    pool=2,
    fc_specs=((32, 16), (16, 5)),
    input_width=16,
    timesteps=3,
    n_classes=5,
)
FRAME_SHAPE = (2, CFG.input_width)


@pytest.fixture(scope="module")
def setup():
    params = init_snn(jax.random.PRNGKey(0), CFG)
    masks = make_mask_pytree(params, 0.5)
    return compile_snn(CFG), params, masks


def _iq(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n,) + FRAME_SHAPE).astype(np.float32)


# ---------------------------------------------------------------------------
# micro-batcher
# ---------------------------------------------------------------------------

def test_bucket_ladder():
    assert make_buckets(64) == (1, 2, 4, 8, 16, 32, 64)
    assert make_buckets(48, align=4) == (4, 8, 16, 32, 48)
    assert make_buckets(5, align=2) == (2, 4)  # cap rounded DOWN to align
    assert make_buckets(1, align=2) == (2,)    # ... but never below align
    assert bucket_for(3, (1, 2, 4, 8)) == 4
    assert bucket_for(100, (1, 2, 4, 8)) == 8


def test_batcher_flushes_on_size():
    mb = MicroBatcher(FRAME_SHAPE, max_batch=4, max_delay_ms=60_000)
    for i in range(4):
        mb.submit(_iq(1)[0])
    t0 = time.perf_counter()
    batch = mb.get_batch(timeout=1.0)
    # full bucket ships immediately — nowhere near the 60 s delay cap
    assert time.perf_counter() - t0 < 5.0
    assert batch is not None and batch.n_real == 4 and batch.bucket == 4
    assert batch.n_padded == 0
    mb.close()


def test_batcher_flushes_on_timeout_and_pads_to_bucket():
    mb = MicroBatcher(FRAME_SHAPE, max_batch=64, max_delay_ms=50)
    frames = _iq(3)
    for i in range(3):
        mb.submit(frames[i])
    t0 = time.perf_counter()
    batch = mb.get_batch(timeout=1.0)
    elapsed = time.perf_counter() - t0
    assert batch is not None and batch.n_real == 3
    assert elapsed >= 0.04  # waited for the delay budget before flushing
    assert batch.bucket == 4 and batch.n_padded == 1  # smallest covering bucket
    assert batch.frames.shape == (4,) + FRAME_SHAPE
    np.testing.assert_array_equal(batch.frames[:3], frames)
    np.testing.assert_array_equal(batch.frames[3], np.zeros(FRAME_SHAPE))
    mb.close()


def test_batcher_rejects_bad_shapes_and_close_wakes_consumers():
    mb = MicroBatcher(FRAME_SHAPE, max_batch=4, max_delay_ms=10)
    with pytest.raises(ValueError, match="expected frame of shape"):
        mb.submit(np.zeros((3, 7), np.float32))
    mb.close()
    assert mb.get_batch(timeout=1.0) is None  # sentinel wakes the consumer
    # the dedicated type (an EngineClosed IS-A RuntimeError) lets the
    # fleet router skip a retiring replica without masking real faults
    with pytest.raises(EngineClosed, match="closed"):
        mb.submit(np.zeros(FRAME_SHAPE, np.float32))


def test_drain_barrier_waits_for_priority_reordered_backlog():
    """Weighted dequeue hands realtime ahead of bulk; the barrier must
    still hold until the *lower-seq* bulk request is handed — a max-seq
    watermark would release it early and let hot_swap/scale_down close
    an engine over a still-queued request."""
    mb = MicroBatcher(FRAME_SHAPE, max_batch=1, max_delay_ms=1)
    frames = _iq(2)
    bulk = mb.submit(frames[0], priority="bulk")          # seq 0
    mb.submit(frames[1], priority="realtime")             # seq 1
    batch = mb.get_batch(timeout=1.0)                     # WRR: realtime first
    assert [r.priority for r in batch.requests] == ["realtime"]
    # seq 1 handed, seq 0 still queued: the barrier must NOT release
    assert not mb.drain_barrier(timeout=0.05)
    batch = mb.get_batch(timeout=1.0)
    assert [r.priority for r in batch.requests] == ["bulk"]
    assert mb.drain_barrier(timeout=1.0)
    bulk.cancel()
    mb.close()


def test_expired_requests_fail_even_while_consumer_keeps_blocking():
    """A round that pops only expired requests must fail their futures
    when the round ends — not hold them until get_batch returns (which,
    with no further traffic and timeout=None, is never)."""
    mb = MicroBatcher(FRAME_SHAPE, max_batch=4, max_delay_ms=1)
    fut = mb.submit(_iq(1)[0], deadline=mb.now() - 1.0)   # already expired
    consumer = threading.Thread(target=mb.get_batch,
                                kwargs={"timeout": None}, daemon=True)
    consumer.start()
    with pytest.raises(DeadlineExceeded):
        fut.result(timeout=5.0)       # resolves while get_batch still blocks
    assert consumer.is_alive()        # no live request ever arrived
    assert mb.n_expired == 1
    mb.close()
    consumer.join(timeout=5.0)
    assert not consumer.is_alive()


def _run_trickle(n_consumers: int, n_frames: int = 200):
    """``n_consumers`` threads drain one batcher fed a frame every ~1 ms;
    returns the batches they formed."""
    from repro.obs.trace import RequestTrace, TraceLog

    mb = MicroBatcher(FRAME_SHAPE, max_batch=64, max_delay_ms=20)
    log = TraceLog(capacity=4 * n_frames)
    batches, lock = [], threading.Lock()

    def consume():
        while True:
            batch = mb.get_batch(timeout=0.05)
            if batch is None:
                if mb.closed:
                    return
                continue
            with lock:
                batches.append(batch)
            for r in batch.requests:
                r.future.set_result(0)

    threads = [threading.Thread(target=consume, daemon=True)
               for _ in range(n_consumers)]
    for th in threads:
        th.start()
    frame = _iq(1)[0]
    for i in range(n_frames):
        mb.submit(frame, trace=RequestTrace(i, log))
        time.sleep(0.001)
    assert mb.drain_barrier(timeout=5.0)
    mb.close()
    for th in threads:
        th.join(timeout=5.0)
        assert not th.is_alive()
    assert sum(b.n_real for b in batches) == n_frames
    return batches


def test_two_consumers_form_one_batch_at_a_time():
    """Under a trickle two consumers take turns: a forming window (first
    request dequeued -> batch formed) opens only after the previous one
    closed, so arrivals are never split into two half-full batches."""
    def window(batch):
        events = [ev for r in batch.requests for ev in r.trace.events]
        return (min(ev.t for ev in events if ev.name == "dequeue"),
                max(ev.t for ev in events if ev.name == "batch-form"))

    one = _run_trickle(1)
    two = _run_trickle(2)
    windows = sorted(window(b) for b in two)
    for (_, prev_end), (start, _) in zip(windows, windows[1:]):
        assert start >= prev_end
    mean = {k: np.mean([b.n_real for b in batches])
            for k, batches in (("one", one), ("two", two))}
    assert mean["two"] >= 0.75 * mean["one"], mean


# ---------------------------------------------------------------------------
# tail padding: exactly N predictions, no padded-frame leakage into stats
# ---------------------------------------------------------------------------

def test_sync_engine_tail_padding(setup):
    _, params, masks = setup
    engine = AMCServeEngine(params, CFG, masks=masks, batch_size=4,
                            backend="dense")
    iq = _iq(11)
    preds = engine.classify(iq)
    st = engine.stats
    assert preds.shape == (11,)
    assert st.requests == 11 and st.batches == 3
    assert st.padded_frames == 1
    assert len(st.latencies_s) == 11  # one latency per real request only
    assert st.backend_batch_counts() == {"dense": 3}


def test_async_engine_tail_padding_matches_reference(setup):
    program, params, masks = setup
    iq = _iq(11)
    # reference: dense program over the exact same (padded-free) frames
    from repro.data.pipeline import sigma_delta_encode_np

    frames = jnp.asarray(sigma_delta_encode_np(iq, CFG.timesteps))
    ref = np.asarray(program.apply_batch(params, frames, "dense",
                                         masks=masks)).argmax(-1)
    with AsyncAMCServeEngine(params, CFG, masks=masks, backend="dense",
                             max_batch=8, max_delay_ms=5.0,
                             warmup=False) as engine:
        preds = engine.classify(iq)
        st = engine.stats
    assert preds.shape == (11,)
    np.testing.assert_array_equal(preds, ref)  # padding never leaks into preds
    assert st.requests == 11
    assert len(st.latencies_s) == 11  # padded tail rows get no latency entry
    assert len(st.queue_depths) == st.batches
    assert all(b == "dense" for b in st.batch_backends)
    # worker-maintained serving window: throughput is real even though no
    # caller ever passed through a timed classify() section
    assert st.wall_s > 0 and st.throughput_fps() > 0


def test_submit_future_path_reports_throughput(setup):
    _, params, masks = setup
    with AsyncAMCServeEngine(params, CFG, masks=masks, backend="dense",
                             max_batch=4, max_delay_ms=5.0,
                             warmup=False) as engine:
        futs = [engine.submit(f) for f in _iq(9, seed=11)]
        preds = [f.result(timeout=30.0) for f in futs]
        st = engine.stats
    assert len(preds) == 9 and all(isinstance(p, int) for p in preds)
    assert st.requests == 9
    assert st.wall_s > 0 and st.throughput_fps() > 0


def test_batcher_rejects_conflicting_max_batch_and_buckets():
    with pytest.raises(ValueError, match="conflicts with explicit buckets"):
        MicroBatcher(FRAME_SHAPE, max_batch=64, buckets=(2, 4))
    mb = MicroBatcher(FRAME_SHAPE, buckets=(2, 4))  # buckets authoritative
    assert mb.max_batch == 4
    mb.close()


def test_close_never_leaves_a_future_pending(setup):
    _, params, masks = setup
    engine = AsyncAMCServeEngine(params, CFG, masks=masks, backend="dense",
                                 max_batch=2, max_delay_ms=1.0, warmup=False)
    futures = [engine.submit(f) for f in _iq(16, seed=9)]
    engine.close()  # immediately: some batches served, the rest drained
    served = drained = 0
    for fut in futures:
        assert fut.done() or True  # must resolve promptly either way
        try:
            pred = fut.result(timeout=10.0)
            assert isinstance(pred, int)
            served += 1
        except RuntimeError as e:
            assert "closed" in str(e)
            drained += 1
    assert served + drained == 16  # nobody hangs
    with pytest.raises(RuntimeError, match="closed"):
        engine.submit(_iq(1)[0])


def test_async_engine_counts_activity_like_sync(setup):
    _, params, masks = setup
    iq = _iq(6, seed=3)
    sync = AMCServeEngine(params, CFG, masks=masks, batch_size=8,
                          count_activity=True, backend="dense")
    sync.classify(iq)
    with AsyncAMCServeEngine(params, CFG, masks=masks, backend="dense",
                             max_batch=8, max_delay_ms=5.0, warmup=False,
                             count_activity=True) as engine:
        engine.classify(iq)
        st = engine.stats
    # identical activity despite different batching/padding: padded tail
    # rows are stripped before the counting hooks run
    assert st.accumulations == sync.stats.accumulations
    assert st.fetched_bits == sync.stats.fetched_bits
    assert st.accumulations > 0 and st.fetched_bits > 0


def test_sync_engine_count_path_unit(setup):
    """The counting path (old ``_count``) alone, on a 1-frame batch."""
    _, params, masks = setup
    engine = AMCServeEngine(params, CFG, masks=masks, batch_size=2,
                            count_activity=True, backend="goap")
    engine.classify(_iq(1, seed=7))
    st = engine.stats
    assert st.requests == 1 and st.batches == 1
    assert st.accumulations > 0
    assert st.fetched_bits > st.accumulations  # >=1 bit fetched per accum


def test_cancelled_future_does_not_poison_its_batch(setup):
    _, params, masks = setup
    with AsyncAMCServeEngine(params, CFG, masks=masks, backend="dense",
                             max_batch=64, max_delay_ms=200.0,
                             warmup=False) as engine:
        # both requests land in the same (timeout-flushed) micro-batch
        fut_a = engine.submit(_iq(1, seed=21)[0])
        fut_b = engine.submit(_iq(1, seed=22)[0])
        cancelled = fut_a.cancel()
        pred_b = fut_b.result(timeout=30.0)  # must still resolve normally
    assert isinstance(pred_b, int)
    if cancelled:  # cancel() raced the worker; when it won, a is cancelled
        assert fut_a.cancelled()
    else:
        assert isinstance(fut_a.result(timeout=30.0), int)


def test_traceable_encoder_matches_numpy_encoder():
    from repro.data.pipeline import (
        sigma_delta_encode_batch,
        sigma_delta_encode_np,
    )

    iq = _iq(5, seed=13)
    for osr in (1, 3, 8):
        np.testing.assert_array_equal(
            np.asarray(sigma_delta_encode_batch(jnp.asarray(iq), osr)),
            sigma_delta_encode_np(iq, osr))


# ---------------------------------------------------------------------------
# autotuner
# ---------------------------------------------------------------------------

def test_autotuner_picks_a_winner(setup):
    program, params, masks = setup
    report = autotune_backend(program, params, (4, CFG.timesteps, 2, CFG.input_width),
                              masks=masks, candidates=("dense", "goap"),
                              reps=1)
    assert report.choice in ("dense", "goap")
    assert set(report.timings_ms) == {"dense", "goap"}
    assert not report.errors and not report.fell_back


def test_autotuner_falls_back_to_goap_when_backend_raises(setup):
    program, params, masks = setup
    from repro.models import graph

    def _boom(spec, layer_params, *, cfg, mask=None, quant_fn=None):
        raise RuntimeError("no such accelerator")

    snapshot = dict(graph._REGISTRY)
    try:
        register_backend("boom", "conv_lif", _boom)
        register_backend("boom", "fc_lif", _boom)
        report = autotune_backend(program, params, (4, CFG.timesteps, 2, CFG.input_width),
                                  masks=masks, candidates=("boom",))
        assert report.choice == "goap" and report.fell_back
        assert "boom" in report.errors
        assert "RuntimeError" in report.errors["boom"]
        # a raising candidate is excluded, not fatal, when others survive
        report = autotune_backend(program, params, (4, CFG.timesteps, 2, CFG.input_width),
                                  masks=masks, candidates=("boom", "dense"),
                                  reps=1)
        assert report.choice == "dense" and not report.fell_back
        assert "boom" in report.errors
    finally:
        graph._REGISTRY.clear()
        graph._REGISTRY.update(snapshot)


def test_async_engine_auto_backend(setup):
    _, params, masks = setup
    with AsyncAMCServeEngine(params, CFG, masks=masks, backend="auto",
                             candidates=("dense", "goap"), max_batch=4,
                             max_delay_ms=5.0, warmup=False) as engine:
        assert engine.autotune is not None
        assert engine.backend == engine.autotune.choice
        preds = engine.classify(_iq(5))
    assert preds.shape == (5,)


# ---------------------------------------------------------------------------
# sharded path (1-device mesh: same code path as a pod, minus the fan-out)
# ---------------------------------------------------------------------------

def test_async_engine_shard_map_path(setup):
    program, params, masks = setup
    iq = _iq(6, seed=5)
    from repro.data.pipeline import sigma_delta_encode_np

    frames = jnp.asarray(sigma_delta_encode_np(iq, CFG.timesteps))
    ref = np.asarray(program.apply_batch(params, frames, "dense",
                                         masks=masks)).argmax(-1)
    mesh = serve_mesh(1)
    with AsyncAMCServeEngine(params, CFG, masks=masks, backend="dense",
                             mesh=mesh, max_batch=4, max_delay_ms=5.0,
                             warmup=False) as engine:
        assert all(b % 1 == 0 for b in engine.batcher.buckets)
        preds = engine.classify(iq)
    np.testing.assert_array_equal(preds, ref)


# ---------------------------------------------------------------------------
# ServeStats percentile math vs numpy
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 2, 5, 100, 997])
def test_percentiles_match_numpy(n):
    rng = np.random.default_rng(n)
    lat = rng.exponential(scale=0.01, size=n).tolist()
    st = ServeStats(latencies_s=list(lat))
    for q in (50.0, 95.0, 99.0, 0.0, 100.0, 37.3):
        np.testing.assert_allclose(
            st.latency_percentile(q), np.percentile(lat, q), rtol=1e-12)
    np.testing.assert_allclose(st.p50_ms, np.percentile(lat, 50) * 1e3)
    np.testing.assert_allclose(st.p95_ms, np.percentile(lat, 95) * 1e3)
    np.testing.assert_allclose(st.p99_ms, np.percentile(lat, 99) * 1e3)


def test_percentiles_empty_stats():
    st = ServeStats()
    assert st.p50_ms == 0.0 and st.p99_ms == 0.0
    assert st.throughput_fps() == 0.0
    assert st.mean_queue_depth() == 0.0


def test_stats_histories_are_bounded_but_totals_exact():
    st = ServeStats()
    cap = ServeStats.MAX_SAMPLES
    st.record_latencies([0.001] * (cap + 100))
    assert len(st.latencies_s) == cap
    for i in range(cap + 50):
        st.record_batch("dense", queue_depth=i)
    st.record_batch("goap", queue_depth=0)
    assert len(st.queue_depths) <= cap and len(st.batch_backends) <= cap
    # exact totals survive the history trimming
    assert st.backend_batch_counts() == {"dense": cap + 50, "goap": 1}
    assert st.batches == cap + 51


def test_stats_summary_roundtrips_to_json():
    import json

    st = ServeStats(requests=3, batches=1, backend="dense",
                    batch_backends=["dense"], latencies_s=[0.1, 0.2, 0.3],
                    queue_depths=[2], wall_s=0.5)
    d = json.loads(json.dumps(st.summary()))
    assert d["requests"] == 3
    assert d["backend_batch_counts"] == {"dense": 1}
    assert d["throughput_fps"] == pytest.approx(6.0)


# ---------------------------------------------------------------------------
# ServeStats edge cases: empty/singleton histories, zero elapsed time
# ---------------------------------------------------------------------------

def test_stats_empty_histories_stay_finite():
    import json

    st = ServeStats()
    assert st.latency_percentile(99.0) == 0.0
    assert st.p50_ms == st.p95_ms == st.p99_ms == 0.0
    assert st.mean_queue_depth() == 0.0
    assert st.throughput_fps() == 0.0
    assert st.throughput_samples_per_s() == 0.0
    d = json.loads(json.dumps(st.summary()))
    for key, val in d.items():
        if isinstance(val, (int, float)):
            assert np.isfinite(val), f"{key} not finite on empty stats"


def test_stats_singleton_latency_percentiles():
    st = ServeStats()
    st.record_latencies([0.004])
    # one sample: every percentile is that sample, no interpolation NaNs
    for q in (0.0, 50.0, 99.0, 100.0):
        assert st.latency_percentile(q) == pytest.approx(0.004)
    assert st.p50_ms == st.p99_ms == pytest.approx(4.0)


def test_stats_zero_elapsed_throughput_is_zero():
    # requests recorded but no wall time yet (first batch still in
    # flight): throughput must report 0.0, never divide by zero
    st = ServeStats(requests=10, wall_s=0.0)
    assert st.throughput_fps() == 0.0
    assert st.throughput_samples_per_s() == 0.0


# ---------------------------------------------------------------------------
# classify() abandonment: timeouts must not leak futures into the batcher
# ---------------------------------------------------------------------------

def test_classify_timeout_cancels_queued_futures(setup):
    _, params, masks = setup
    # max_delay far beyond the classify timeout and a 64-wide bucket:
    # the 4 submitted frames just sit queued, so the timeout must fire
    # with every future still pending
    eng = AsyncAMCServeEngine(params, CFG, masks=masks, backend="dense",
                              max_delay_ms=60_000.0, warmup=False)
    captured = []
    orig_submit = eng.submit

    def recording_submit(iq, **kw):
        fut = orig_submit(iq, **kw)
        captured.append(fut)
        return fut

    eng.submit = recording_submit
    try:
        # on 3.10 concurrent.futures.TimeoutError is not yet the builtin
        import concurrent.futures

        with pytest.raises((TimeoutError, concurrent.futures.TimeoutError)):
            eng.classify(_iq(4), timeout=0.2)
        # regression: classify used to return leaving its requests queued
        # forever; now every outstanding future is cancelled, and the
        # dequeue path drops cancelled requests without a batch slot
        assert len(captured) == 4
        assert all(f.done() for f in captured)
        assert all(f.cancelled() for f in captured)
    finally:
        eng.close()
    assert eng.stats.requests == 0


# ---------------------------------------------------------------------------
# two serving threads: two batches in flight
# ---------------------------------------------------------------------------

def _stub_step(pred, entered=None, hold=None, sleep_s=0.0):
    """A serving step answering class ``pred`` for every row; it signals
    ``entered``, then waits for ``hold`` or sleeps, as a device would."""
    def step(x):
        if entered is not None:
            entered.release()
        if hold is not None:
            assert hold.wait(timeout=30.0)
        time.sleep(sleep_s)
        out = np.zeros((x.shape[0], CFG.n_classes), np.float32)
        out[:, pred] = 1.0
        return out
    return step


def test_two_workers_answer_as_one(setup):
    _, params, masks = setup
    iq = _iq(300, seed=5)
    preds = {}
    for workers in (1, 2):
        with AsyncAMCServeEngine(params, CFG, masks=masks, backend="dense",
                                 max_batch=8, max_delay_ms=1.0,
                                 warmup=False, workers=workers,
                                 name=f"depth-{workers}") as engine:
            assert engine.n_workers == workers
            preds[workers] = engine.classify(iq)
            assert engine.stats.requests == 300
    np.testing.assert_array_equal(preds[2], preds[1])


def test_close_with_both_workers_mid_batch_leaves_no_future_pending(setup):
    _, params, masks = setup
    engine = AsyncAMCServeEngine(params, CFG, masks=masks, backend="dense",
                                 max_batch=4, max_delay_ms=1000.0,
                                 warmup=False, workers=2)
    entered, hold = threading.Semaphore(0), threading.Event()
    engine.get_version("default").step = _stub_step(3, entered, hold)
    futures = [engine.submit(f) for f in _iq(24, seed=9)]
    try:
        for _ in range(2):              # both threads inside a step
            assert entered.acquire(timeout=30.0)
        closer = threading.Thread(target=engine.close)
        closer.start()
        closer.join(timeout=0.2)
        assert closer.is_alive()        # waits for the batches in flight
    finally:
        hold.set()
    closer.join(timeout=30.0)
    assert not closer.is_alive()
    assert all(f.done() for f in futures)
    served = [f.result() for f in futures if f.exception() is None]
    assert served == [3] * 8            # the two in-flight batches
    for f in futures:
        if f.exception() is not None:
            assert "closed" in str(f.exception())


def test_hot_swap_with_two_in_flight_pins_each_batch(setup):
    """A swap while one thread's batch is on the device: that batch
    completes on the version that started it, and the other thread
    serves the next batch on the new primary meanwhile."""
    _, params, masks = setup
    engine = AsyncAMCServeEngine(params, CFG, masks=masks, backend="dense",
                                 max_batch=4, max_delay_ms=1000.0,
                                 warmup=False, workers=2)
    entered, hold = threading.Semaphore(0), threading.Event()
    try:
        engine.get_version("default").step = _stub_step(0, entered, hold)
        engine.bind_version("canary", params, masks=masks, warmup=False)
        engine.get_version("canary").step = _stub_step(1)
        first = [engine.submit(f) for f in _iq(4, seed=1)]
        assert entered.acquire(timeout=30.0)
        assert engine.swap_to("canary") == "default"
        second = [engine.submit(f) for f in _iq(4, seed=2)]
        assert [f.result(timeout=30.0) for f in second] == [1] * 4
        assert not any(f.done() for f in first)
        hold.set()
        assert [f.result(timeout=30.0) for f in first] == [0] * 4
        stats = engine.version_stats()
        assert stats["default"].requests == 4
        assert stats["canary"].requests == 4
    finally:
        hold.set()
        engine.close()


def test_overlapped_batches_are_counted(setup):
    from repro.obs.metrics import default_registry

    _, params, masks = setup
    seen = {}
    for workers in (1, 2):
        name = f"overlap-{workers}"
        with AsyncAMCServeEngine(params, CFG, masks=masks, backend="dense",
                                 max_batch=4, max_delay_ms=1000.0,
                                 warmup=False, workers=workers,
                                 name=name) as engine:
            engine.get_version("default").step = _stub_step(
                2, sleep_s=0.03)
            assert list(engine.classify(_iq(32, seed=3))) == [2] * 32
            batches = engine.stats.batches
        seen[workers] = (default_registry().value(
            "repro_serve_overlapped_batches_total", engine=name), batches)
    assert seen[1] == (0.0, 8)
    overlapped, batches = seen[2]
    assert batches == 8 and batches // 2 <= overlapped < batches


def test_more_workers_than_cores_lose_no_update(setup):
    """Serving threads outnumbering the cores, switching every 10 µs: the
    shared counts (engine stats, registry, in-flight) lose no update and
    every future resolves."""
    import os
    import sys

    from repro.obs.metrics import default_registry

    _, params, masks = setup
    workers = min(os.cpu_count() or 1, 30) + 2
    n = 600
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with AsyncAMCServeEngine(params, CFG, masks=masks, backend="dense",
                                 max_batch=4, max_delay_ms=1.0,
                                 warmup=False, workers=workers,
                                 name="stress") as engine:
            engine.get_version("default").step = _stub_step(
                1, sleep_s=0.001)
            futures = [engine.submit(f) for f in _iq(n, seed=11)]
            assert [f.result(timeout=60) for f in futures] == [1] * n
            stats = engine.stats
            assert stats.requests == n
            assert engine._in_flight == 0
        reg = default_registry()
        batches = reg.value("repro_serve_batches_total", engine="stress",
                            backend="dense")
        assert batches == stats.batches >= n // 4
        assert reg.value("repro_serve_requests_total", engine="stress") == n
        assert reg.value("repro_serve_overlapped_batches_total",
                         engine="stress") <= batches
    finally:
        sys.setswitchinterval(interval)


def test_two_workers_stay_busy_at_saturation(setup):
    """Windowed utilization as the fleet autoscaler reads it (busy seconds
    over elapsed × threads) stays above its default scale-up threshold
    when two serving threads drain a full queue: the forming turn does not
    make a saturated replica look idle."""
    import inspect

    from repro.fleet.autoscaler import Autoscaler

    high = inspect.signature(Autoscaler).parameters["high_utilization"].default
    _, params, masks = setup
    with AsyncAMCServeEngine(params, CFG, masks=masks, backend="dense",
                             max_batch=4, max_delay_ms=1000.0,
                             warmup=False, workers=2,
                             name="saturated-2") as engine:
        engine.get_version("default").step = _stub_step(2, sleep_s=0.02)
        futures = [engine.submit(f) for f in _iq(240, seed=7)]
        futures[15].result(timeout=60)   # both threads serving a full queue
        t0, busy0 = time.perf_counter(), engine.busy_s
        futures[-16].result(timeout=60)  # the queue still holds batches
        t1, busy1 = time.perf_counter(), engine.busy_s
        assert [f.result(timeout=60) for f in futures] == [2] * 240
    assert (busy1 - busy0) / ((t1 - t0) * 2) > high


def test_default_depth_is_one_on_cpu_two_on_an_accelerator(setup):
    from types import SimpleNamespace

    from repro.serve.engine import default_workers

    _, params, masks = setup
    assert default_workers(jax.devices()) == 1
    assert default_workers([SimpleNamespace(platform="tpu")]) == 2
    with AsyncAMCServeEngine(params, CFG, masks=masks, backend="dense",
                             warmup=False) as engine:
        assert engine.n_workers == 1


# ---------------------------------------------------------------------------
# micro-batcher concurrency stress (slow: excluded from default tier-1)
# ---------------------------------------------------------------------------

def _stress_round(seed: int) -> None:
    """Producers, consumers, and a chaos thread hammer one batcher.

    The invariant under test: every submitted future resolves exactly
    once (result, error, cancel — any is fine; zero or double is a bug),
    no matter how submits race expiry, cancellation, drain barriers, and
    close. Done-callbacks fire once per future by contract, so counting
    them counts resolutions.
    """
    rng = np.random.default_rng(seed)
    mb = MicroBatcher(FRAME_SHAPE, max_batch=4,
                      max_delay_ms=float(rng.choice([0.2, 1.0, 5.0])),
                      max_queue=32,
                      pace_ms=float(rng.choice([0.0, 0.5])))
    errors, resolved, futures = [], [], []
    lock = threading.Lock()
    frame = _iq(1)[0]

    def producer(t):
        prng = np.random.default_rng(seed * 100 + t)
        for _ in range(30):
            try:
                fut = mb.submit(
                    frame,
                    priority="bulk" if prng.random() < 0.4 else "realtime",
                    deadline=(mb.now() + 1e-4 if prng.random() < 0.2
                              else None))
            except QueueFull:
                continue
            except RuntimeError:
                return          # racing close(): valid terminal state
            fut.add_done_callback(lambda f: resolved.append(1))
            with lock:
                futures.append(fut)
            if prng.random() < 0.1:
                fut.cancel()
            if prng.random() < 0.3:
                time.sleep(prng.random() * 1e-3)

    def consumer():
        try:
            while True:
                batch = mb.get_batch(timeout=0.02)
                if batch is None:
                    if mb.closed:
                        return
                    continue
                for req in batch.requests:
                    try:
                        req.future.set_result(0)
                    except Exception:   # lost a cancel race: fine
                        pass
        except Exception as exc:  # noqa: BLE001 — fail the test, not the thread
            errors.append(exc)

    def chaos():
        for _ in range(10):
            mb.qsize()
            mb.qsizes()
            mb.drain_barrier(timeout=0.005)

    threads = ([threading.Thread(target=producer, args=(t,))
                for t in range(3)]
               + [threading.Thread(target=consumer) for _ in range(2)]
               + [threading.Thread(target=chaos)])
    for th in threads[:3] + threads[5:]:
        th.start()
    for th in threads[3:5]:
        th.start()
    for th in threads[:3] + threads[5:]:
        th.join(timeout=30.0)
    mb.drain_barrier(timeout=5.0)
    mb.close()
    for th in threads[3:5]:
        th.join(timeout=30.0)
    # anything still queued at close is failed, exactly as the engine does
    err = RuntimeError("closed")
    for req in mb.drain():
        if not req.future.done():
            try:
                req.future.set_exception(err)
            except Exception:
                pass
    assert not errors, errors
    deadline = time.perf_counter() + 5.0
    while len(resolved) < len(futures) and time.perf_counter() < deadline:
        time.sleep(0.005)
    assert all(f.done() for f in futures), "unresolved futures leaked"
    assert len(resolved) == len(futures), (
        f"{len(futures)} futures but {len(resolved)} resolutions")


@pytest.mark.slow
def test_batcher_concurrency_stress_50_seeds():
    for seed in range(50):
        _stress_round(seed)
