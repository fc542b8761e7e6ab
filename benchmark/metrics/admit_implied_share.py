"""Share of the window's dispatched reviews that a workload's pod
template implied: 100 x webhook_reviews_total{origin=implied} over
both origins (webhook/server.py MicroBatcher). Silent where the
program counts no reviews by origin."""

from benchmark.series import counter


def read(record):
    total = counter(record, "webhook_reviews_total", origin=("original", "implied"))
    if not total:
        return None
    return 100.0 * counter(record, "webhook_reviews_total", origin="implied") / total
