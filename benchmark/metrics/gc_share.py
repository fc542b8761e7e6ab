"""Share of the traced window the serving process spent in garbage
collection: 100 x the window's process_gc_pause_seconds sum over the
trace's window_s (obs/tracer.py GcPauses)."""

from benchmark.series import dist


def read(record):
    tr = record.get("trace")
    n, s = dist(record, "process_gc_pause_seconds")
    if not tr or tr["window_s"] <= 0 or not n:
        return None
    return 100.0 * s / tr["window_s"]
