"""The inventory-freeze counter reader, on synthetic run records (as
test_stage_metrics.py builds them): full freezes per sweep, 0 where
every sweep rendered without the inventory, and nothing from a program
without the counters."""

import pytest

from benchmark import harness

SKIPS = 'driver_inventory_skips_total{path="audit"}'


def record(counters, sweeps=8):
    return {
        "counters": counters,
        "dists": {},
        "work": {"sweeps": sweeps},
        "trace": {"window_s": 31.0, "busy_s": 2.0},
    }


@pytest.mark.parametrize("counters,want", [
    ({"driver_inventory_freezes_total": 8}, 1.0),
    ({"driver_inventory_freezes_total": 2, SKIPS: 6}, 0.25),
    ({SKIPS: 8}, 0.0),
])
def test_reads_freezes_per_sweep(counters, want):
    assert harness.read_metric("audit_inventory_freezes", record(counters)) == pytest.approx(want)


@pytest.mark.parametrize("rec", [
    record({}),
    dict(record({SKIPS: 8}), work={}),
    record({'driver_rows_encoded_total{path="audit"}': 800}),
])
def test_reads_nothing_without_the_counters_or_sweeps(rec):
    assert harness.read_metric("audit_inventory_freezes", rec) is None


def test_listed_for_the_three_audit_cells_traced_only():
    bench = harness.load_benchmark()
    audit = {c["name"] for c in bench["workloads"] if "audit" in c["traffic"]}
    assert len(audit) == 3
    for cell in bench["workloads"]:
        traced = {m["name"] for m in harness.cell_metrics(bench, cell, True)}
        untraced = {m["name"] for m in harness.cell_metrics(bench, cell, False)}
        assert ("audit_inventory_freezes" in traced) == (cell["name"] in audit)
        assert "audit_inventory_freezes" not in untraced
