"""The stream_fused kernel's least time over its summed device time (%).

Least time: the larger of the rows' work over the peak and the calls'
bytes over HBM bandwidth (``bench/cost.py``); rows and calls are the
engine's counters over the window, kernel time the trace's.
"""
import cost
import measure


def read(run):
    seconds = measure.device_time(run, "stream_fused")
    rows, calls = measure.rows_and_calls(run)
    if seconds is None or not rows:
        return None
    least, _ = cost.least_time_s(rows, calls, run.work_per_frame,
                                 run.weight_bytes, run.frame_bytes,
                                 run.peak["ops"], run.peak["bytes_per_s"])
    return 100.0 * least / seconds
