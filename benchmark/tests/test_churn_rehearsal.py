"""The two `audit_churn` cells end to end on the CPU at a small size,
through the same harness functions the command runs after its device
check, and their control."""

import json
import time

import pytest

from benchmark import control, harness
from benchmark.tests.churn import small


CELLS = {
    "refs-svc10k-ing5k.audit-churn": {
        "audit_encode_s", "audit_rows_encoded", "audit_sync_s", "audit_join_screen_s",
        "audit_join_render_s", "audit_join_flagged_rows"},
    "pods100k-c500.audit-churn": {"audit_encode_s", "audit_rows_encoded", "audit_sync_s"},
}


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("trace", [False, True])
def test_churn_cell(cell, trace):
    cfg, traffic = small(cell)
    r = harness.run_cell(cell, 2**33 + 9, 1.0, trace, time.monotonic(), cfg, traffic)
    json.dumps(r)
    assert r["correct"] and r["attempted"] >= 1 and r["failed"] == 0
    assert r["checks"]["audit_mismatches"] == {"value": 0, "limit": 0}
    if trace:
        # the CPU has no device plane: the device metrics stay silent
        assert CELLS[cell] | {"audit_aggregate_s", "audit_render_s", "audit_assemble_s"} <= set(
            r["metrics"])
        assert "audit_kernel_s" not in r["metrics"]
    else:
        assert set(r["metrics"]) == {"setup_s", "audit_sweep_s"}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_churn_control_is_not_correct(cell):
    cfg, traffic = small(cell)
    checks = control.run(cell, 11, 30.0, cfg, traffic)
    assert checks["audit_mismatches"][0] > 0
