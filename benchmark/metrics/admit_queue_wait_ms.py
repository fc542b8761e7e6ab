"""A request's wait from submit() to its batch's cut,
webhook_request_stage_seconds{stage=queue_wait} (webhook/server.py
MicroBatcher)."""

from benchmark.series import dist


def read(record):
    n, s = dist(record, "webhook_request_stage_seconds", stage="queue_wait")
    return 1000.0 * s / n if n else None
