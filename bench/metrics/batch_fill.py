"""Real rows over bucket rows of the batches served in the window (%)."""


def read(run):
    c = run.counters
    rows = c["requests"] + c["padded"]
    return 100.0 * c["requests"] / rows if rows else None
