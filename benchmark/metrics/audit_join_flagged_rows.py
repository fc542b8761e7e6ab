"""Pairs the inventory joins' screens sent to the interpreter render
per sweep, over every join template:
driver_join_rows_total{route=flagged} (constraint/tpudriver.py). A
program without the counter reads nothing."""

from benchmark.series import counter, parse


def read(record):
    sweeps = record["work"].get("sweeps")
    seen = any(parse(k)[0] == "driver_join_rows_total" for k in record["counters"])
    if not sweeps or not seen:
        return None
    return counter(record, "driver_join_rows_total", route="flagged") / sweeps
