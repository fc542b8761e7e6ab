"""The HTTP front door's own work per request: body read and
json.loads, then envelope encode and write,
webhook_request_stage_seconds{stage=front} (webhook/server.py)."""

from benchmark.series import dist


def read(record):
    n, s = dist(record, "webhook_request_stage_seconds", stage="front")
    return 1000.0 * s / n if n else None
