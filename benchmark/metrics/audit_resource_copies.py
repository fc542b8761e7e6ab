"""Violating resources the constraint client materialized per sweep:
client_resource_copies_total{path=audit} over the window's sweeps, one
JSON round-trip copy per violating object per call
(constraint/client.py). A program without the counter reads nothing."""

from benchmark.series import counter, parse


def read(record):
    sweeps = record["work"].get("sweeps")
    seen = any(parse(k)[0] == "client_resource_copies_total" for k in record["counters"])
    if not sweeps or not seen:
        return None
    return counter(record, "client_resource_copies_total", path="audit") / sweeps
