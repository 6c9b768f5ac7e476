"""Frames whose futures resolved inside the window, per window second."""
import numpy as np


def read(run):
    done = run.log.done[:run.log.n]
    ok = run.log.ok[:run.log.n]
    n = np.count_nonzero(ok & (done >= run.t0) & (done <= run.t1))
    return n / (run.t1 - run.t0)
