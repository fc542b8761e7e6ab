"""The constraint client's copy counter reader, on synthetic run records
(as test_stage_metrics.py builds them): the window's copies per sweep
on the audit path, and nothing from a program without the counter."""

import pytest

from benchmark import harness


def record(counters, sweeps=8):
    return {
        "counters": {f'client_resource_copies_total{{path="{path}"}}': v
                     for path, v in counters},
        "dists": {},
        "work": {"sweeps": sweeps},
        "trace": {"window_s": 31.0, "busy_s": 2.0},
    }


def test_reads_audit_copies_per_sweep():
    rec = record([("audit", 8 * 1000), ("webhook", 37)])
    assert harness.read_metric("audit_resource_copies", rec) == pytest.approx(1000.0)


@pytest.mark.parametrize("rec", [
    record([]),
    dict(record([("audit", 10)]), work={}),
    {"counters": {"webhook_batches_total": 250}, "dists": {}, "work": {"sweeps": 8}},
])
def test_reads_nothing_without_the_counter_or_sweeps(rec):
    assert harness.read_metric("audit_resource_copies", rec) is None


def test_reported_in_the_traced_audit_run_only():
    bench = harness.load_benchmark()
    for cell in bench["workloads"]:
        traced = {m["name"] for m in harness.cell_metrics(bench, cell, True)}
        untraced = {m["name"] for m in harness.cell_metrics(bench, cell, False)}
        assert ("audit_resource_copies" in traced) == (cell["name"] == "pods100k-c500.audit")
        assert "audit_resource_copies" not in untraced
