"""Small helpers shared by the metric readers in ``bench/metrics/``."""
from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float:
    """The ``q``-th percentile as an observed value (no interpolation)."""
    values = np.asarray(values, np.float64)
    return float(np.percentile(values, q, method="inverted_cdf"))


def due_in_window(run) -> np.ndarray:
    """Indices of the requests due inside the window."""
    log = run.log
    due = log.due[:log.n]
    return np.nonzero((due >= run.t0) & (due < run.t1))[0]


def latencies_s(run) -> np.ndarray:
    """Due-to-resolution seconds of every request due in the window.

    A request that failed or never resolved counts as infinitely late.
    """
    log = run.log
    idx = due_in_window(run)
    lat = log.done[idx] - log.due[idx]
    return np.where(log.ok[idx] & np.isfinite(lat), lat, np.inf)


def device_time(run, name: str, kind: str = "op_s"):
    """Summed device seconds of one stable op or module name, or None."""
    if run.trace is None:
        return None
    seconds = run.trace[kind].get(name, 0.0)
    return seconds if seconds > 0 else None


def rows_and_calls(run):
    """Rows the serving step computed (real and padded) and its calls."""
    c = run.counters
    return c["requests"] + c["padded"], c["batches"] * run.chips
