"""The load generator times an open loop from the due time (bench/loadgen.py)."""
import concurrent.futures
import threading
import time
import types

import numpy as np
import pytest

import loadgen
import measure
from metrics_loader import read_metric

RATE = 200.0
SECONDS = 1.0
STALL_S = 0.15


class FakeEngine:
    """Answers every frame with its index after 1 ms; one submit stalls."""

    def __init__(self, stall_at=None):
        self.stall_at = stall_at
        self.n = 0
        self.pool = concurrent.futures.ThreadPoolExecutor(2)

    def submit(self, k):
        self.n += 1
        if self.n == self.stall_at:
            time.sleep(STALL_S)      # the client is held up in submit()
        return self.pool.submit(lambda: (time.sleep(0.001), int(k))[1])

    def close(self):
        self.pool.shutdown(wait=True)


def _run(stall_at):
    engine = FakeEngine(stall_at)
    try:
        log, t0, t1 = loadgen.run(engine.submit, 64,
                                  {"loop": "open", "rate_fps": RATE},
                                  SECONDS, seed=2 ** 31 + 5)
    finally:
        engine.close()
    return types.SimpleNamespace(log=log, t0=t0, t1=t1)


def test_open_loop_sends_every_request_due_and_all_resolve():
    run = _run(None)
    n = run.log.n
    assert abs(n - RATE * SECONDS) < 5 * np.sqrt(RATE * SECONDS)
    assert run.log.ok[:n].all()
    assert np.array_equal(run.log.answer[:n], run.log.frame[:n])
    assert read_metric("loadgen_late_p99_ms", run) < STALL_S * 1e3 / 3


def test_a_stall_shows_in_latency_and_in_lateness():
    calm, stalled = _run(None), _run(stall_at=50)
    late = read_metric("loadgen_late_p99_ms", stalled)
    assert late > STALL_S * 1e3 / 2 > read_metric("loadgen_late_p99_ms", calm)
    assert read_metric("latency_p90_ms", stalled) > STALL_S * 1e3 / 2
    # timed from the send instead of the due time, the stall would hide
    log = stalled.log
    idx = measure.due_in_window(stalled)
    from_send = np.percentile(log.done[idx] - log.sent[idx], 99) * 1e3
    assert from_send < STALL_S * 1e3 / 3


def test_arrivals_are_seeded_and_bursts_keep_the_mean():
    traffic = {"loop": "open", "rate_fps": 1000.0,
               "burst": {"period_ms": 500, "on_ms": 50, "factor": 4}}
    a = loadgen.arrivals(traffic, 20.0, np.random.default_rng(1))
    b = loadgen.arrivals(traffic, 20.0, np.random.default_rng(1))
    assert np.array_equal(a, b)
    assert a.size == pytest.approx(20_000, rel=0.03)
    on = (a % 0.5) < 0.05
    assert on.sum() / 0.05 / 40 == pytest.approx(4000, rel=0.1)


def test_closed_loop_keeps_its_outstanding_requests():
    engine = FakeEngine()
    seen = []
    lock = threading.Lock()

    def submit(k):
        fut = engine.submit(k)
        with lock:
            seen.append(sum(1 for f in pending if not f.done()))
            pending.append(fut)
        return fut

    pending = []
    try:
        log, t0, t1 = loadgen.run(submit, 64,
                                  {"loop": "closed", "outstanding": 4},
                                  0.3, seed=1)
    finally:
        engine.close()
    assert log.n > 20 and log.ok[:log.n].all()
    assert max(seen) <= 4
