"""The constraint client's result assembly per sweep: the driver's
flattening of its per-review results and handle_violation over every
violation, client_phase_seconds{phase=assemble,path=audit}
(constraint/client.py, constraint/tpudriver.py)."""

from benchmark.series import dist


def read(record):
    sweeps = record["work"].get("sweeps")
    n, s = dist(record, "client_phase_seconds", phase="assemble", path="audit")
    return s / sweeps if sweeps and n else None
