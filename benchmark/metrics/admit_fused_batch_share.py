"""Share of the window's micro-batches that took the fused device
route: 100 x webhook_batches_total{route=fused} over all routes, each
batch counted once under its final route (webhook/server.py)."""

from benchmark.series import counter

ROUTES = ("fused", "interp", "cold", "fallback", "degraded", "partitioned", "unavailable")


def read(record):
    total = counter(record, "webhook_batches_total", route=ROUTES)
    if not total:
        return None
    return 100.0 * counter(record, "webhook_batches_total", route="fused") / total
