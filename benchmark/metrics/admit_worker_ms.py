"""The batch worker's cycle per batch, from the cut to the last future
resolved, webhook_batch_stage_seconds{stage=worker} (webhook/server.py
MicroBatcher)."""

from benchmark.series import dist


def read(record):
    n, s = dist(record, "webhook_batch_stage_seconds", stage="worker")
    return 1000.0 * s / n if n else None
