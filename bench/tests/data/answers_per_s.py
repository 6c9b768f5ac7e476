"""A metric added by a test: answered requests per window second."""
import numpy as np


def read(run):
    return np.count_nonzero(run.log.ok[:run.log.n]) / (run.t1 - run.t0)
