"""Frames per second times work per frame over chips times the peak (%).

The work is counted over non-zero weights (``bench/cost.py``); the peak is
the one the configuration names (bf16 for the float configuration, int8
for the integer twin).
"""
import numpy as np


def read(run):
    done = run.log.done[:run.log.n]
    ok = run.log.ok[:run.log.n]
    fps = np.count_nonzero(ok & (done >= run.t0) & (done <= run.t1)) \
        / (run.t1 - run.t0)
    return 100.0 * fps * run.work_per_frame / (run.chips * run.peak["ops"])
