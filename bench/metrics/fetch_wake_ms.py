"""Per batch, the device's result ready to the worker holding it (ms), median.

End of ``engine.fetch`` minus the later of its start and the latest end,
over chips, of the batch's ``jit_step`` (``bench/spans.py``).
"""
import spans

spans.install()


def read(run):
    return spans.median_ms(run, "fetch_wake_s")
