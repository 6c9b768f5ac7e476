"""99th percentile wait in the batcher: engine request traces, enqueue to batch-form."""
import measure


def read(run):
    waits = []
    for events in run.request_traces:
        ev = dict(events)
        if "enqueue" in ev and "batch-form" in ev \
                and run.t0 <= ev["enqueue"] < run.t1:
            waits.append(ev["batch-form"] - ev["enqueue"])
    return measure.percentile(waits, 99) * 1e3 if waits else None
