"""The audit corpus's re-encode per sweep after the synced inventory
changed: driver_phase_seconds{phase=corpus_encode,path=audit}
(constraint/tpudriver.py, `gk.driver.corpus_encode`). A program
without the stage reads nothing."""

from benchmark.series import dist


def read(record):
    sweeps = record["work"].get("sweeps")
    n, s = dist(record, "driver_phase_seconds", phase="corpus_encode", path="audit")
    return s / sweeps if sweeps and n else None
