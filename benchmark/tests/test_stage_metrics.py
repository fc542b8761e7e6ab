"""The stage readers on synthetic run records: each reads its registry
family's window delta, and each reads nothing (None) from a program
that does not record the family."""

import pytest

from benchmark import harness


def series(family, **labels):
    inner = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
    return f"{family}{{{inner}}}" if inner else family


def record(counters=(), dists=(), work=None, trace=None):
    """A run record as harness.run_cell builds it: counter deltas,
    distribution deltas as {count, sum}, the window's work and the
    trace reduction."""
    return {
        "counters": {series(f, **lab): v for f, lab, v in counters},
        "dists": {series(f, **lab): {"count": n, "sum": s} for f, lab, n, s in dists},
        "work": work or {},
        "trace": trace,
    }


ADMISSION = record(
    counters=[
        ("webhook_batches_total", {"route": "fused"}, 240),
        ("webhook_batches_total", {"route": "interp"}, 14),
        ("webhook_batches_total", {"route": "cold"}, 6),
    ],
    dists=[
        ("webhook_request_stage_seconds", {"stage": "front"}, 4800, 4800 * 0.0015),
        ("webhook_request_stage_seconds", {"stage": "queue_wait"}, 4800, 4800 * 0.058),
        ("webhook_request_stage_seconds", {"stage": "wake"}, 4800, 4800 * 0.004),
        ("webhook_batch_stage_seconds", {"plane": "validation", "stage": "worker"}, 260, 260 * 0.11),
        ("webhook_batch_stage_seconds", {"plane": "validation", "stage": "augment"}, 260, 0.5),
        ("driver_phase_seconds", {"path": "webhook", "phase": "lock_wait"}, 260, 260 * 0.02),
        ("driver_phase_seconds", {"path": "audit", "phase": "lock_wait"}, 3, 9.0),
        ("process_gc_pause_seconds", {"generation": "0"}, 3000, 0.3),
        ("process_gc_pause_seconds", {"generation": "2"}, 1, 0.15),
    ],
    trace={"window_s": 30.0, "busy_s": 0.04},
)

AUDIT = record(
    dists=[
        ("client_phase_seconds", {"path": "audit", "phase": "assemble"}, 16, 8 * 0.9),
        ("client_phase_seconds", {"path": "webhook", "phase": "assemble"}, 4, 1.0),
        ("process_gc_pause_seconds", {"generation": "1"}, 40, 0.6),
    ],
    work={"sweeps": 8},
    trace={"window_s": 31.0, "busy_s": 2.0},
)

# a program without the stage timer: the route-less batch counter and
# the families the benchmark read before
PARENT = record(
    counters=[("webhook_batches_total", {}, 250)],
    dists=[
        ("webhook_batch_size", {}, 250, 4600),
        ("driver_phase_seconds", {"path": "webhook", "phase": "render"}, 250, 1.0),
    ],
    work={"sweeps": 8},
    trace={"window_s": 30.0, "busy_s": 0.04},
)


@pytest.mark.parametrize("name,want", [
    ("admit_front_ms", 1.5),
    ("admit_queue_wait_ms", 58.0),
    ("admit_wake_ms", 4.0),
    ("admit_worker_ms", 110.0),
    ("admit_lock_wait_ms", 20.0),
    ("admit_fused_batch_share", 100.0 * 240 / 260),
    ("gc_share.admit", 100.0 * 0.45 / 30.0),
])
def test_admission_readers(name, want):
    assert harness.read_metric(name, ADMISSION) == pytest.approx(want)


@pytest.mark.parametrize("name,want", [
    ("audit_assemble_s", 0.9),
    ("gc_share.audit", 100.0 * 0.6 / 31.0),
])
def test_audit_readers(name, want):
    assert harness.read_metric(name, AUDIT) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "admit_front_ms", "admit_queue_wait_ms", "admit_wake_ms", "admit_worker_ms",
    "admit_lock_wait_ms", "admit_fused_batch_share", "audit_assemble_s",
    "gc_share.admit", "gc_share.audit",
])
def test_readers_find_nothing_in_a_program_without_the_stages(name):
    assert harness.read_metric(name, PARENT) is None


def test_fused_share_stays_within_100_with_a_cold_batch():
    """A batch bounced cold is one batch under `cold`, not a second
    count beside `fused`: the share cannot pass 100."""
    rec = record(counters=[
        ("webhook_batches_total", {"route": "fused"}, 20),
        ("webhook_batches_total", {"route": "cold"}, 1),
    ])
    share = harness.read_metric("admit_fused_batch_share", rec)
    assert share == pytest.approx(100.0 * 20 / 21)
    assert share <= 100.0


def test_gc_share_uses_the_traced_window():
    """The pause sum is over the trace's window_s; without a trace (an
    untraced run) there is nothing to read."""
    rec = dict(ADMISSION, trace={"window_s": 15.0, "busy_s": 0.0})
    assert harness.read_metric("gc_share.admit", rec) == pytest.approx(100.0 * 0.45 / 15.0)
    assert harness.read_metric("gc_share.admit", dict(ADMISSION, trace=None)) is None


def test_new_metrics_are_in_their_cells():
    """Each new reader reports in the traced run of the cell it names."""
    bench = harness.load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    want = {
        "admit-c50.steady": {
            "admit_front_ms", "admit_queue_wait_ms", "admit_worker_ms", "admit_wake_ms",
            "admit_lock_wait_ms", "admit_fused_batch_share", "gc_share.admit"},
        "pods100k-c500.audit": {"audit_assemble_s", "gc_share.audit"},
    }
    for cell, names in want.items():
        traced = {m["name"] for m in harness.cell_metrics(bench, cells[cell], True)}
        untraced = {m["name"] for m in harness.cell_metrics(bench, cells[cell], False)}
        assert names <= traced
        assert not names & untraced
