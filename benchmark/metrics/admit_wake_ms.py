"""From the batch worker resolving a request's future to its handler
thread running again, webhook_request_stage_seconds{stage=wake}
(webhook/server.py MicroBatcher.result)."""

from benchmark.series import dist


def read(record):
    n, s = dist(record, "webhook_request_stage_seconds", stage="wake")
    return 1000.0 * s / n if n else None
