"""Per batch, the ``engine.gather`` that ended with it (ms), median.

The worker in the batcher, from the end of the previous batch until the
next is formed (``bench/spans.py``).
"""
import spans

spans.install()


def read(run):
    return spans.median_ms(run, "gather_s")
