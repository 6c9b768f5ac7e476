"""Median due-to-resolution latency of the requests due in the window."""
import measure


def read(run):
    lat = measure.latencies_s(run)
    return measure.percentile(lat, 50) * 1e3 if lat.size else None
