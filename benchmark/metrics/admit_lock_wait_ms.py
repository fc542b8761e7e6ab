"""Time a review batch spent blocked on the driver's serving mutex, per
batch that takes it (fused and interpreter routes),
driver_phase_seconds{phase=lock_wait,path=webhook}
(constraint/tpudriver.py)."""

from benchmark.series import dist


def read(record):
    n, s = dist(record, "driver_phase_seconds", phase="lock_wait", path="webhook")
    return 1000.0 * s / n if n else None
