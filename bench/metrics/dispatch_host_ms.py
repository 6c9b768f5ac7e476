"""Per batch, start of ``engine.put`` to end of ``engine.dispatch`` (ms), median.

The host putting the frames on the device and dispatching the step, up
to its asynchronous return (``bench/spans.py``).
"""
import spans

spans.install()


def read(run):
    return spans.median_ms(run, "dispatch_host_s")
