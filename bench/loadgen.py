"""The load generator: one general client driven by a traffic file.

A traffic file (``bench/traffic/<name>.json``) names a loop and its
parameters; nothing about a mix lives in code.

* ``"loop": "closed"`` — one client thread keeps ``outstanding`` requests
  in flight: it submits whenever a slot is free, and a request's slot is
  freed when its future resolves.
* ``"loop": "open"`` — arrivals on a schedule drawn from the seed before
  the window opens, sent whether or not earlier requests have finished.
  ``rate_fps`` is the mean rate; an optional ``burst`` block
  (``period_ms``, ``on_ms``, ``factor``) multiplies the rate by ``factor``
  during the first ``on_ms`` of every period and lowers it in the rest so
  the mean stays ``rate_fps``.  Each request is timed from the moment it
  was **due**, so a stall of the client or of the system counts against
  every request it delays, and the client's own lateness (send minus due)
  is recorded beside it.

Frames are taken from the pool in an order drawn from the seed.  The
client talks to the system only through ``submit(frame) -> future``.
"""
from __future__ import annotations

import math
import threading
import time
from typing import Callable, Optional

import numpy as np

CLOCK = time.perf_counter
MAX_RATE_FPS = 100_000   # a closed loop's log holds this many per second


class Log:
    """What the client saw, one row per request, on the ``CLOCK``'s seconds."""

    def __init__(self, capacity: int):
        self.frame = np.full(capacity, -1, np.int64)
        self.due = np.full(capacity, np.nan)
        self.sent = np.full(capacity, np.nan)
        self.done = np.full(capacity, np.nan)
        self.answer = np.full(capacity, -1, np.int64)
        self.ok = np.zeros(capacity, bool)
        self.n = 0                     # requests sent (or refused) so far
        self.lock = threading.Lock()
        self.all_done = threading.Condition(self.lock)
        self.n_done = 0
        self.overflow = False          # a closed loop outran MAX_RATE_FPS

    def callback(self, i: int, release: Optional[Callable] = None):
        def done(fut):
            t = CLOCK()
            try:
                ans = fut.result()
                ok = True
            except Exception:  # noqa: BLE001 - any failure is a missed request
                ans, ok = -1, False
            self.done[i] = t
            self.answer[i] = ans
            self.ok[i] = ok
            with self.lock:
                self.n_done += 1
                self.all_done.notify_all()
            if release is not None:
                release()
        return done

    def wait(self, timeout: float) -> bool:
        """Wait until every request sent has resolved; False on timeout."""
        end = CLOCK() + timeout
        with self.lock:
            while self.n_done < self.n:
                left = end - CLOCK()
                if left <= 0:
                    return False
                self.all_done.wait(left)
        return True


def arrivals(traffic: dict, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """Due times in ``[0, seconds)`` of an open loop (seconds from the start)."""
    rate = float(traffic["rate_fps"])
    burst = traffic.get("burst")
    if burst is None:
        n = int(rate * seconds * 1.2 + 10 * math.sqrt(rate * seconds) + 10)
        t = np.cumsum(rng.exponential(1.0 / rate, n))
        return t[t < seconds]
    period = burst["period_ms"] / 1e3
    on = burst["on_ms"] / 1e3
    high = rate * burst["factor"]
    low = (rate * period - high * on) / (period - on)
    if low < 0:
        raise ValueError("burst factor too large for its mean rate")
    n = int(high * seconds * 1.2 + 10 * math.sqrt(high * seconds) + 10)
    t = np.cumsum(rng.exponential(1.0 / high, n))
    t = t[t < seconds]
    keep = rng.random(t.size) < np.where((t % period) < on, 1.0, low / high)
    return t[keep]


def _closed(submit, order, log: Log, traffic: dict, stop: threading.Event,
            span) -> None:
    slots = threading.Semaphore(int(traffic["outstanding"]))
    capacity = log.frame.size
    for i in range(capacity):
        while not slots.acquire(timeout=0.05):
            if stop.is_set():
                return
        if stop.is_set():
            return
        k = order[i % order.size]
        log.frame[i] = k
        log.due[i] = log.sent[i] = CLOCK()
        with log.lock:
            log.n = i + 1
        try:
            with span("loadgen.submit"):
                fut = submit(k)
        except Exception:  # noqa: BLE001 - a refused request is a failure
            log.callback(i, slots.release)(_Refused())
        else:
            fut.add_done_callback(log.callback(i, slots.release))
    log.overflow = True


def _open(submit, order, log: Log, due: np.ndarray, t0: float,
          span) -> None:
    # every request due in the window is sent, however late the client
    # runs: its latency is counted from when it was due
    for i in range(due.size):
        target = t0 + due[i]
        now = CLOCK()
        if now < target:
            with span("loadgen.sleep"):
                while now < target:
                    time.sleep(min(0.002, target - now))
                    now = CLOCK()
        k = order[i % order.size]
        log.frame[i] = k
        log.due[i] = target
        log.sent[i] = CLOCK()
        with log.lock:
            log.n = i + 1
        try:
            with span("loadgen.submit"):
                fut = submit(k)
        except Exception:  # noqa: BLE001
            log.callback(i)(_Refused())
        else:
            fut.add_done_callback(log.callback(i))


class _Refused:
    """A future-like stand-in for a submit that raised."""

    def result(self):
        raise RuntimeError("submit refused")


def run(submit: Callable[[int], object], pool_size: int, traffic: dict,
        seconds: float, seed: int, span=None, on_start: Callable = None,
        on_stop: Callable = None, drain_s: float = 60.0) -> tuple:
    """Drive ``submit(frame_index)`` for ``seconds``; returns (log, t0, t1).

    ``t0``/``t1`` bound the window on the ``CLOCK``.  A closed loop sends
    nothing after ``t1``; an open loop sends every request due before it.
    The call then waits up to ``drain_s`` for every sent request to
    resolve.  ``on_start(t0)`` runs as the window opens and ``on_stop(t1)``
    as it closes.
    """
    import contextlib

    span = span or (lambda name: contextlib.nullcontext())
    rng = np.random.default_rng([int(seed), 0x10AD])
    order = rng.permutation(pool_size)
    loop = traffic["loop"]
    if loop == "open":
        due = arrivals(traffic, seconds, rng)
        capacity = due.size
    elif loop == "closed":
        due = None
        capacity = int(MAX_RATE_FPS * seconds) + int(traffic["outstanding"])
    else:
        raise ValueError(f"unknown loop {loop!r}")
    log = Log(capacity)
    stop = threading.Event()
    t0 = CLOCK()
    if loop == "open":
        client = threading.Thread(target=_open, name="loadgen",
                                  args=(submit, order, log, due, t0, span))
    else:
        client = threading.Thread(target=_closed, name="loadgen",
                                  args=(submit, order, log, traffic, stop,
                                        span))
    if on_start is not None:
        on_start(t0)
    client.start()
    t1 = t0 + seconds
    try:
        time.sleep(max(0.0, t1 - CLOCK()))
        if on_stop is not None:
            on_stop(t1)
    finally:
        stop.set()
        client.join()
    log.wait(drain_s)
    return log, t0, t1
