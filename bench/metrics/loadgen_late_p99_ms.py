"""99th percentile of how late the load generator sent (sent minus due)."""
import measure


def read(run):
    idx = measure.due_in_window(run)
    if not idx.size:
        return None
    late = run.log.sent[idx] - run.log.due[idx]
    return measure.percentile(late, 99) * 1e3
