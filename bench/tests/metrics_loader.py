"""Read one metric through its reader file, as the harness does."""
import run as bench_run


def read_metric(name, run):
    return bench_run.load_reader(name)(run)
