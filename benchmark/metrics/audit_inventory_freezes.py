"""Full freezes of the synced inventory per sweep:
driver_inventory_freezes_total (constraint/tpudriver.py), one per data
version a render read the whole tree in. A sweep that froze nothing
moves driver_inventory_skips_total instead, so 0 is told apart from a
program without the counters, which reads nothing."""

from benchmark.series import counter, parse

FAMILIES = ("driver_inventory_freezes_total", "driver_inventory_skips_total")


def read(record):
    sweeps = record["work"].get("sweeps")
    seen = any(parse(k)[0] in FAMILIES for k in record["counters"])
    if not sweeps or not seen:
        return None
    return counter(record, "driver_inventory_freezes_total") / sweeps
