"""Mean expansion stage per expanded request: the template lookup, the
implied objects' construction and mutation, and the aggregation of
their answers, webhook_request_stage_seconds{stage=expand}
(webhook/policy.py ValidationHandler). Silent where no request was
expanded, or the program has no such stage."""

from benchmark.series import dist


def read(record):
    n, s = dist(record, "webhook_request_stage_seconds", stage="expand")
    return 1000.0 * s / n if n else None
