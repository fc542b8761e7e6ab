"""The inventory joins' screen work per sweep: duplicate-key row bits,
their value counts and the prune key indexes,
driver_phase_seconds{phase=join_screen,path=audit}
(constraint/tpudriver.py, `gk.driver.join_screen`). A program without
the stage reads nothing."""

from benchmark.series import dist


def read(record):
    sweeps = record["work"].get("sweeps")
    n, s = dist(record, "driver_phase_seconds", phase="join_screen", path="audit")
    return s / sweeps if sweeps and n else None
