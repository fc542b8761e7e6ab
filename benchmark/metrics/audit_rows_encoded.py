"""Rows of the audit corpus encoded per sweep:
driver_rows_encoded_total{path=audit} (constraint/tpudriver.py), the
whole synced inventory at each rebuild. A program without the counter
reads nothing."""

from benchmark.series import counter, parse


def read(record):
    sweeps = record["work"].get("sweeps")
    seen = any(parse(k)[0] == "driver_rows_encoded_total" for k in record["counters"])
    if not sweeps or not seen:
        return None
    return counter(record, "driver_rows_encoded_total", path="audit") / sweeps
