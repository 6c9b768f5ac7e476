"""The integer serving step's least time over its device time (%).

The same work count as the float kernel, over the int8 peak; the step's
device time is the trace's jit_step program time.
"""
import cost
import measure


def read(run):
    seconds = measure.device_time(run, "jit_step", "module_s")
    rows, calls = measure.rows_and_calls(run)
    if seconds is None or not rows:
        return None
    least, _ = cost.least_time_s(rows, calls, run.work_per_frame,
                                 run.weight_bytes, run.frame_bytes,
                                 run.peak["ops"], run.peak["bytes_per_s"])
    return 100.0 * least / seconds
