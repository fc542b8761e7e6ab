"""The `open_loop_rollout` cell end to end on the CPU at a small size,
through the same harness functions the command runs after its device
check, and its control."""

import copy
import json
import time

import pytest

from benchmark import control, harness

CELL = "admit-workloads-c50.rollout"


def small():
    """(cfg, traffic) of the rollout cell at a rate and warm-up the CPU
    runs in seconds."""
    _, cfg, traffic = harness.load_cell(CELL)
    cfg, traffic = copy.deepcopy(cfg), copy.deepcopy(traffic)
    cfg["inventory"]["namespaces"] = 10
    traffic.update(rate_rps=40, warm_seconds=2, connections=16,
                   warm_batches={"from": 12, "to": 64, "step": 52, "samples": 1})
    return cfg, traffic


@pytest.mark.parametrize("trace", [False, True])
def test_rollout_cell(trace):
    cfg, traffic = small()
    r = harness.run_cell(CELL, 2**33 + 11, 3.0, trace, time.monotonic(), cfg, traffic)
    json.dumps(r)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0
    assert r["checks"]["answers_compared"]["value"] == r["attempted"]
    if trace:
        assert {"admit_expand_ms", "admit_implied_share", "admit_batch_size",
                "admit_queue_wait_ms"} <= set(r["metrics"])
        # every Deployment and ReplicaSet implies one Pod
        assert r["metrics"]["admit_implied_share"]["value"] == 50.0
        assert r["metrics"]["admit_expand_ms"]["value"] > 0
    else:
        assert set(r["metrics"]) == {"setup_s", "admit_p50_ms"}


def test_rollout_control_is_not_correct():
    cfg, traffic = small()
    checks = control.run(CELL, 13, 3.0, cfg, traffic)
    assert checks["admit_mismatches"][0] > 0
    assert checks["admit_missing"] == (0, 0)
