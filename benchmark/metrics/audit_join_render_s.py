"""The inventory joins' flagged pairs rendered on the interpreter
against their prune candidates, per sweep:
driver_phase_seconds{phase=render_pruned,path=audit}
(constraint/tpudriver.py, `gk.driver.render_pruned`). A program
without the stage reads nothing."""

from benchmark.series import dist


def read(record):
    sweeps = record["work"].get("sweeps")
    n, s = dist(record, "driver_phase_seconds", phase="render_pruned", path="audit")
    return s / sweeps if sweeps and n else None
