"""Seconds per sweep the sync controller spent applying the churn's
watch events to the synced inventory: the window's
sync_duration_seconds sum (control/controllers.py, `gk.sync.apply`)."""

from benchmark.series import dist


def read(record):
    sweeps = record["work"].get("sweeps")
    n, s = dist(record, "sync_duration_seconds")
    return s / sweeps if sweeps and n else None
