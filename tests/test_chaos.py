"""Chaos suite: the admission plane's failure envelope, exercised
end-to-end through the REAL production fault points (docs/robustness.md).

What it pins:
  * the degradation ladder — fused TPU → host oracle → fail-open/closed
    verdict — never skips a rung and every rung is observable;
  * the circuit breaker trips to host-interpreter mode under persistent
    device faults and recovers via half-open probes;
  * the bounded admission queue sheds with policy-correct responses
    under overload, and deadline-expired requests are dropped BEFORE
    dispatch (satellite: deadline-propagation coverage);
  * MicroBatcher/MutateBatcher shutdown never hangs or drops a future
    even with submits racing stop() (satellite: shutdown-race coverage);
  * no chaos scenario ever admits an unconverged mutation;
  * the audit barrier/status-write failures are counted and logged with
    a trace_id (satellite: the silent-barrier fix).

Everything here is fast (no XLA compiles: the validation ladder tests
run the TpuDriver in numpy mode) and deterministic (the registry's
arm/trigger/fire semantics are counter-based, never random). Marked
`chaos` so the lane can run alone: pytest -m chaos.
"""

import threading
import time
from concurrent.futures import TimeoutError as FutureTimeout

import pytest

from gatekeeper_tpu.constraint import Backend, K8sValidationTarget, TpuDriver
from gatekeeper_tpu.faults import (
    CLOSED,
    FAULTS,
    HALF_OPEN,
    OPEN,
    CircuitBreaker,
    FaultError,
    FaultRegistry,
    ShedError,
    configure_from_env,
)
from gatekeeper_tpu.logs import CapturingLogger
from gatekeeper_tpu.metrics import MetricsRegistry
from gatekeeper_tpu.webhook.server import (
    BatchedValidationHandler,
    MicroBatcher,
)

pytestmark = pytest.mark.chaos

TARGET = "admission.k8s.gatekeeper.sh"

REQ_LABELS = """package reqlabels

violation[{"msg": msg}] {
    required := {key | key := input.parameters.labels[_]}
    provided := {key | input.review.object.metadata.labels[key]}
    missing := required - provided
    count(missing) > 0
    msg := sprintf("missing: %v", [missing])
}
"""


@pytest.fixture(autouse=True)
def _clean_faults():
    """Chaos runs must be hermetic: no armed fault outlives its test."""
    FAULTS.reset()
    yield
    FAULTS.reset()


def build_client():
    """Small real policy stack on the numpy-mode TpuDriver: both the
    fused (review_many) and host (review_host) rungs work without any
    jit compile, so ladder tests stay fast and deterministic."""
    cl = Backend(TpuDriver(use_jax=False)).new_client(K8sValidationTarget())
    cl.add_template(
        {
            "apiVersion": "templates.gatekeeper.sh/v1beta1",
            "kind": "ConstraintTemplate",
            "metadata": {"name": "reqlabels"},
            "spec": {
                "crd": {"spec": {"names": {"kind": "ReqLabels"}}},
                "targets": [{"target": TARGET, "rego": REQ_LABELS}],
            },
        }
    )
    cl.add_constraint(
        {
            "apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": "ReqLabels",
            "metadata": {"name": "need-owner"},
            "spec": {"parameters": {"labels": ["owner"]}},
        }
    )
    return cl


def admission_request(i=0, labels=None):
    obj = {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {
            "name": f"p{i}",
            "namespace": "default",
            **({"labels": labels} if labels else {}),
        },
        "spec": {"containers": [{"name": "c", "image": "nginx"}]},
    }
    return {
        "uid": f"u{i}",
        "kind": {"group": "", "version": "v1", "kind": "Pod"},
        "operation": "CREATE",
        "name": f"p{i}",
        "namespace": "default",
        "userInfo": {"username": "alice"},
        "object": obj,
    }


def counter(metrics, name, **tags):
    snap = metrics.snapshot()["counters"]
    if not tags:
        return snap.get(name, 0)
    inner = ",".join(f'{k}="{v}"' for k, v in sorted(tags.items()))
    return snap.get(f"{name}{{{inner}}}", 0)


# -- the fault registry -------------------------------------------------------


def test_registry_arm_trigger_fire_semantics():
    reg = FaultRegistry()
    reg.arm("p", mode="error", after=2, count=2)
    reg.fire("p")  # hit 1: skipped
    reg.fire("p")  # hit 2: skipped
    with pytest.raises(FaultError):
        reg.fire("p")  # hit 3: fires (1/2)
    with pytest.raises(FaultError):
        reg.fire("p")  # hit 4: fires (2/2)
    reg.fire("p")  # hit 5: count exhausted
    spec = reg.spec("p")
    assert spec.hits == 5 and spec.fired == 2
    reg.disarm("p")
    reg.fire("p")  # disarmed: no-op
    assert not reg.active()


def test_registry_hang_mode_stalls_not_crashes():
    reg = FaultRegistry()
    reg.arm("h", mode="hang", delay_s=0.05)
    t0 = time.monotonic()
    reg.fire("h")  # returns after the stall
    assert time.monotonic() - t0 >= 0.05


def test_registry_clock_jump_skew_honors_trigger():
    reg = FaultRegistry()
    reg.arm("c", mode="clock_jump", delay_s=60.0, after=1)
    assert reg.skew("c") == 0.0  # hit 1: before the jump
    assert reg.skew("c") == 60.0  # hit 2: the jump
    assert reg.skew("other") == 0.0
    assert reg.fired("c") == 1


def test_registry_env_activation_grammar():
    reg = FaultRegistry()
    armed = configure_from_env(
        reg,
        env=(
            "driver.device_dispatch=error:count=5,"
            "webhook.host_review=hang:delay=0.25,"
            "nonsense,bad=notamode,x=error:count=zzz,"
            "webhook.clock=clock_jump:delay=3600:after=2"
        ),
    )
    assert armed == 3
    assert reg.spec("driver.device_dispatch").count == 5
    assert reg.spec("webhook.host_review").delay_s == 0.25
    assert reg.spec("webhook.clock").after == 2
    assert reg.spec("bad") is None and reg.spec("x") is None


# -- the circuit breaker ------------------------------------------------------


def test_breaker_trip_halfopen_probe_recover():
    metrics = MetricsRegistry()
    clock = [0.0]
    b = CircuitBreaker(
        failure_threshold=3, recovery_seconds=30.0, metrics=metrics,
        clock=lambda: clock[0],
    )
    assert b.state == CLOSED and b.allow()
    b.record_failure()
    b.record_failure()
    b.record_success()  # success resets the consecutive count
    b.record_failure()
    b.record_failure()
    assert b.state == CLOSED
    b.record_failure()  # third consecutive: trip
    assert b.state == OPEN and not b.allow()
    clock[0] = 29.9
    assert not b.allow()
    clock[0] = 30.1  # recovery window elapsed: half-open
    assert b.state == HALF_OPEN
    assert b.allow()  # the single probe
    assert not b.allow()  # probe in flight: no second batch
    b.record_failure()  # probe failed: re-open, clock restarts
    assert b.state == OPEN
    clock[0] = 70.0
    assert b.allow()  # half-open again
    b.record_success()  # probe succeeded: closed
    assert b.state == CLOSED and b.allow()
    assert counter(
        metrics, "device_breaker_probes_total",
        plane="validation", result="failure",
    ) == 1
    assert counter(
        metrics, "device_breaker_probes_total",
        plane="validation", result="success",
    ) == 1
    assert counter(
        metrics, "device_breaker_transitions_total",
        plane="validation", from_state="closed", to_state="open",
    ) == 1
    gauges = metrics.snapshot()["gauges"]
    assert gauges.get('device_breaker_state{plane="validation"}') == 0


def test_breaker_full_second_cycle_accounting():
    """Long-run soak accounting: a complete CLOSED→OPEN→HALF_OPEN→
    CLOSED cycle followed by a SECOND trip keeps every counter exact —
    no double-counted transitions, no stuck HALF_OPEN, and fleet
    adopt() stays consistent across the cycles."""
    metrics = MetricsRegistry()
    clock = [0.0]
    b = CircuitBreaker(
        failure_threshold=3, recovery_seconds=10.0, metrics=metrics,
        clock=lambda: clock[0],
    )
    seen = []
    b.subscribe(lambda f, t: seen.append((f, t)))

    # cycle 1: trip, wait out recovery, probe succeeds
    for _ in range(3):
        b.record_failure()
    clock[0] = 10.5
    assert b.allow()  # half-open probe
    b.record_success()
    assert b.state == CLOSED
    # cycle 2: trip again, probe FAILS once, then recovers
    for _ in range(3):
        b.record_failure()
    clock[0] = 21.0
    assert b.allow()
    assert not b.allow()  # single-probe invariant holds on cycle 2
    b.record_failure()  # probe fails: back to OPEN, clock restarts
    assert b.state == OPEN
    clock[0] = 30.0  # only 9s since re-open: still OPEN
    assert not b.allow()
    clock[0] = 31.5
    assert b.allow()  # half-open again — never stuck
    b.record_success()
    assert b.state == CLOSED
    # exact transition ledger: 2 full cycles + 1 failed probe re-open
    expected = [
        (CLOSED, OPEN), (OPEN, HALF_OPEN), (HALF_OPEN, CLOSED),
        (CLOSED, OPEN), (OPEN, HALF_OPEN), (HALF_OPEN, OPEN),
        (OPEN, HALF_OPEN), (HALF_OPEN, CLOSED),
    ]
    assert seen == expected
    assert b.transitions == len(expected)
    assert counter(
        metrics, "device_breaker_transitions_total",
        plane="validation", from_state="closed", to_state="open",
    ) == 2
    assert counter(
        metrics, "device_breaker_transitions_total",
        plane="validation", from_state="half_open", to_state="closed",
    ) == 2
    assert counter(
        metrics, "device_breaker_transitions_total",
        plane="validation", from_state="half_open", to_state="open",
    ) == 1
    assert counter(
        metrics, "device_breaker_probes_total",
        plane="validation", result="success",
    ) == 2
    assert counter(
        metrics, "device_breaker_probes_total",
        plane="validation", result="failure",
    ) == 1
    snap = b.snapshot()
    assert snap["state"] == CLOSED
    assert snap["consecutive_failures"] == 0
    assert not snap["probe_in_flight"]


def test_two_concurrent_device_breakers_exact_accounting():
    """The PR 8 second-cycle accounting contract, extended to TWO
    concurrent device breakers on one plane (the fault-domain shape):
    transition ledgers key by breaker NAME, metric series key by the
    device tag, and one breaker's full trip/probe cycle leaves the
    other's ledger untouched — multi-breaker accounting stays exact."""
    metrics = MetricsRegistry()
    clock = [0.0]
    b0 = CircuitBreaker(
        failure_threshold=3, recovery_seconds=10.0, metrics=metrics,
        clock=lambda: clock[0], device=0,
    )
    b1 = CircuitBreaker(
        failure_threshold=3, recovery_seconds=10.0, metrics=metrics,
        clock=lambda: clock[0], device=1,
    )
    assert b0.name == "device:validation:0"
    assert b1.name == "device:validation:1"
    ledger = {b0.name: [], b1.name: []}
    for b in (b0, b1):
        b.subscribe(
            lambda f, t, name=b.name: ledger[name].append((f, t))
        )
    # device 1: full cycle + failed probe (the PR 8 sequence);
    # device 0: a single interleaved trip-and-recover
    for _ in range(3):
        b1.record_failure()
    b0.record_failure()
    b0.record_failure()
    b0.record_failure()
    clock[0] = 10.5
    assert b1.allow()  # device 1 half-open probe
    b1.record_failure()  # probe fails: re-open, clock restarts
    assert b0.allow()  # device 0's OWN probe slot (independent)
    b0.record_success()
    assert b0.state == CLOSED and b1.state == OPEN
    clock[0] = 21.0
    assert b1.allow()
    b1.record_success()
    assert b1.state == CLOSED
    # exact per-name ledgers: no cross-contamination
    assert ledger["device:validation:0"] == [
        (CLOSED, OPEN), (OPEN, HALF_OPEN), (HALF_OPEN, CLOSED),
    ]
    assert ledger["device:validation:1"] == [
        (CLOSED, OPEN), (OPEN, HALF_OPEN), (HALF_OPEN, OPEN),
        (OPEN, HALF_OPEN), (HALF_OPEN, CLOSED),
    ]
    # metric series separate by device tag
    assert counter(
        metrics, "device_breaker_transitions_total",
        plane="validation", device="0",
        from_state="closed", to_state="open",
    ) == 1
    assert counter(
        metrics, "device_breaker_transitions_total",
        plane="validation", device="1",
        from_state="half_open", to_state="open",
    ) == 1
    assert counter(
        metrics, "device_breaker_probes_total",
        plane="validation", device="1", result="failure",
    ) == 1
    assert counter(
        metrics, "device_breaker_probes_total",
        plane="validation", device="0", result="success",
    ) == 1
    # snapshots carry the name (readyz / soak ledger key)
    assert b0.snapshot()["name"] == "device:validation:0"
    assert b1.snapshot()["device"] == "1"


def test_breaker_adopt_consistent_across_cycles():
    """Fleet adopt() across a full local cycle: adoptions count once
    per real transition, never re-fire on a no-op peer hint, and an
    adopted HALF_OPEN can complete its own probe cycle."""
    clock = [0.0]
    b = CircuitBreaker(
        failure_threshold=3, recovery_seconds=10.0,
        clock=lambda: clock[0],
    )
    # peer OPEN while CLOSED: pre-open to HALF_OPEN, counted once
    assert b.adopt(OPEN)
    assert b.state == HALF_OPEN and b.adoptions == 1
    # repeated peer gossip of the same state is a no-op (no
    # double-count, no state churn)
    assert not b.adopt(OPEN)
    assert not b.adopt(HALF_OPEN)
    assert b.adoptions == 1 and b.transitions == 1
    # the adopted HALF_OPEN still enforces the single-probe contract
    assert b.allow()
    assert not b.allow()
    b.record_success()
    assert b.state == CLOSED
    # peer CLOSED while CLOSED: nothing to adopt
    assert not b.adopt(CLOSED)
    # second cycle: a real local trip, then peer CLOSED pulls the
    # probe forward instead of waiting out recovery_seconds
    for _ in range(3):
        b.record_failure()
    assert b.state == OPEN
    assert b.adopt(CLOSED)
    assert b.state == HALF_OPEN and b.adoptions == 2
    assert b.allow()
    b.record_failure()  # probe disagrees with the peer: re-open
    assert b.state == OPEN
    # the failed probe restarted the recovery clock; adopt still works
    assert b.adopt(CLOSED)
    assert b.state == HALF_OPEN
    b.record_success()
    assert b.state == CLOSED
    assert b.adoptions == 3
    snap = b.snapshot()
    assert snap["adoptions"] == 3
    assert snap["consecutive_failures"] == 0


# -- the degradation ladder (fused -> host -> policy envelope) ---------------


def make_stack(fail_policy="open", breaker=None, max_queue=64,
               request_timeout=5.0, window_ms=1.0):
    client = build_client()
    metrics = MetricsRegistry()
    batcher = MicroBatcher(
        client, TARGET, window_ms=window_ms, metrics=metrics,
        max_queue=max_queue, breaker=breaker,
    )
    handler = BatchedValidationHandler(
        batcher, request_timeout=request_timeout, metrics=metrics,
        fail_policy=fail_policy,
    )
    return client, metrics, batcher, handler


def test_fused_fault_degrades_to_host_with_real_answers():
    """Rung 2: a failing fused dispatch must NOT skip to the policy
    envelope — the host oracle still evaluates, so a violating pod is
    still denied and a clean pod still admitted."""
    _, metrics, batcher, handler = make_stack()
    FAULTS.arm("webhook.batch_dispatch", mode="error")
    batcher.start()
    try:
        deny = handler.handle(admission_request(0))  # no owner label
        allow = handler.handle(admission_request(1, labels={"owner": "a"}))
    finally:
        batcher.stop()
    assert not deny.allowed and deny.code == 403
    assert "need-owner" in deny.message
    assert allow.allowed
    assert batcher.batch_failures >= 1
    assert counter(metrics, "webhook_batch_failures_total") >= 1
    assert FAULTS.fired("webhook.batch_dispatch") >= 1


def test_breaker_opens_and_stops_paying_fused_attempts():
    """Persistent device faults: after K consecutive batch failures the
    breaker opens and later batches go STRAIGHT to the host rung — the
    fused fault point stops accumulating hits."""
    breaker = CircuitBreaker(failure_threshold=2, recovery_seconds=3600)
    _, metrics, batcher, handler = make_stack(breaker=breaker)
    FAULTS.arm("webhook.batch_dispatch", mode="error")
    batcher.start()
    try:
        for i in range(2):
            resp = handler.handle(admission_request(i))
            assert not resp.allowed and resp.code == 403  # host rung answers
        assert breaker.state == OPEN
        fused_attempts = FAULTS.hits("webhook.batch_dispatch")
        for i in range(3):
            resp = handler.handle(admission_request(10 + i))
            assert not resp.allowed and resp.code == 403
        # breaker open: zero further fused attempts were paid
        assert FAULTS.hits("webhook.batch_dispatch") == fused_attempts
        assert counter(
            metrics, "webhook_degraded_dispatch_total", plane="validation"
        ) >= 3
    finally:
        batcher.stop()


def test_breaker_halfopen_probe_recovers_fused_path():
    clock = [0.0]
    breaker = CircuitBreaker(
        failure_threshold=1, recovery_seconds=10.0, clock=lambda: clock[0]
    )
    _, metrics, batcher, handler = make_stack(breaker=breaker)
    FAULTS.arm("webhook.batch_dispatch", mode="error", count=1)
    batcher.start()
    try:
        handler.handle(admission_request(0))
        assert breaker.state == OPEN  # one failure, threshold 1
        clock[0] = 11.0  # recovery elapses; fault already exhausted
        resp = handler.handle(admission_request(1))  # the probe batch
        assert not resp.allowed  # still a real (denied) answer
        assert breaker.state == CLOSED  # probe succeeded: recovered
        assert batcher.batches_dispatched >= 1  # fused path serving again
    finally:
        batcher.stop()


@pytest.mark.parametrize("fail_policy,expect_allowed,expect_code", [
    ("open", True, 200),
    ("closed", False, 503),
])
def test_ladder_bottom_policy_envelope(fail_policy, expect_allowed,
                                       expect_code):
    """Rung 3: BOTH evaluation rungs down. The handler answers with the
    endpoint's fail policy — and the host rung was genuinely attempted
    first (no rung skipped)."""
    _, metrics, batcher, handler = make_stack(fail_policy=fail_policy)
    FAULTS.arm("webhook.batch_dispatch", mode="error")
    FAULTS.arm("webhook.host_review", mode="error")
    batcher.start()
    try:
        resp = handler.handle(admission_request(0))
    finally:
        batcher.stop()
    assert resp.allowed is expect_allowed
    assert resp.code == expect_code
    assert "unavailable" in resp.message
    # rung order: the fused attempt happened, THEN the host attempt
    assert FAULTS.fired("webhook.batch_dispatch") >= 1
    assert FAULTS.fired("webhook.host_review") >= 1
    assert counter(
        metrics, "webhook_unavailable_responses_total",
        plane="validation", policy=fail_policy, reason="degraded",
    ) == 1


def test_poisoned_request_stays_500_on_host_rung():
    """The envelope covers requests that were never evaluated — a
    request whose own host evaluation fails keeps its 500 even under
    fail-open (fail-open must not become error-swallowing)."""

    class _PoisonClient:
        def review_many(self, reviews, tracing=False):
            raise RuntimeError("device fault")

        def review_host(self, review):
            raise ValueError("poisoned request")

    batcher = MicroBatcher(_PoisonClient(), TARGET, window_ms=1.0)
    handler = BatchedValidationHandler(
        batcher, request_timeout=5.0, fail_policy="open"
    )
    batcher.start()
    try:
        resp = handler.handle(admission_request(0))
    finally:
        batcher.stop()
    assert not resp.allowed and resp.code == 500
    assert "poisoned request" in resp.message


# -- overload shedding --------------------------------------------------------


@pytest.mark.parametrize("fail_policy,expect_allowed,expect_code", [
    ("open", True, 200),
    ("closed", False, 503),
])
def test_overload_shed_policy_envelope(fail_policy, expect_allowed,
                                       expect_code):
    """A full admission queue sheds with the policy envelope, never a
    hang or a raw 500 (max_queue=0 makes every submit an overflow)."""
    _, metrics, batcher, handler = make_stack(
        fail_policy=fail_policy, max_queue=0
    )
    batcher.start()
    try:
        resp = handler.handle(admission_request(0))
    finally:
        batcher.stop()
    assert resp.allowed is expect_allowed
    assert resp.code == expect_code
    assert batcher.shed_count == 1
    assert counter(
        metrics, "webhook_shed_total", plane="validation",
        reason="queue_full",
    ) == 1
    assert counter(
        metrics, "webhook_unavailable_responses_total",
        plane="validation", policy=fail_policy, reason="queue_full",
    ) == 1


def test_bounded_queue_sheds_excess_without_touching_live_requests():
    client = build_client()
    batcher = MicroBatcher(
        client, TARGET, metrics=MetricsRegistry(), max_queue=2
    )
    # worker NOT started: the queue can only fill
    futs = [batcher.submit(admission_request(i)) for i in range(5)]
    shed = [f for f in futs if f.done()]
    assert len(shed) == 3 and batcher.shed_count == 3
    for f in shed:
        with pytest.raises(ShedError):
            f.result(timeout=0)
    # the 2 queued requests are still live and resolve on stop()'s drain
    batcher.stop()
    for f in futs[:2]:
        assert f.done() and isinstance(f.result(timeout=1), list)


# -- deadline propagation (satellite) ----------------------------------------


@pytest.mark.parametrize("fail_policy,expect_allowed,expect_code", [
    ("open", True, 200),
    ("closed", False, 503),
])
def test_expired_deadline_never_reaches_dispatch(fail_policy,
                                                 expect_allowed,
                                                 expect_code):
    """A request enqueued with <0 remaining budget gets the policy
    envelope and NEVER a device dispatch."""
    _, metrics, batcher, handler = make_stack(
        fail_policy=fail_policy, request_timeout=-0.5
    )
    FAULTS.arm("webhook.batch_dispatch", mode="error")  # dispatch sentinel
    batcher.start()
    try:
        resp = handler.handle(admission_request(0))
    finally:
        batcher.stop()
    assert resp.allowed is expect_allowed
    assert resp.code == expect_code
    assert "deadline" in resp.message
    assert batcher.batches_dispatched == 0
    assert FAULTS.hits("webhook.batch_dispatch") == 0  # no dispatch, ever
    assert counter(
        metrics, "webhook_shed_total", plane="validation", reason="deadline"
    ) == 1


def test_clock_jump_expires_queued_request():
    """An injected clock jump lands AFTER the deadline is computed (the
    `after=1` trigger): the very next deadline check sees the request
    expired and sheds it before any dispatch."""
    _, metrics, batcher, handler = make_stack()
    FAULTS.arm("webhook.clock", mode="clock_jump", delay_s=3600.0, after=1)
    batcher.start()
    try:
        resp = handler.handle(admission_request(0))
    finally:
        batcher.stop()
    assert resp.allowed  # fail-open envelope
    assert "deadline" in resp.message
    assert batcher.batches_dispatched == 0
    assert FAULTS.fired("webhook.clock") >= 1  # the jump was consulted
    assert counter(
        metrics, "webhook_shed_total", plane="validation", reason="deadline"
    ) == 1


# -- hung dispatch ------------------------------------------------------------


def test_hung_dispatch_gets_timeout_envelope_not_a_hang():
    """A stalled device dispatch: the caller gets the typed timeout
    within its own deadline while the worker finishes in background."""
    _, metrics, batcher, handler = make_stack(
        fail_policy="open", request_timeout=0.15
    )
    FAULTS.arm("webhook.batch_dispatch", mode="hang", delay_s=1.0, count=1)
    batcher.start()
    try:
        t0 = time.monotonic()
        resp = handler.handle(admission_request(0))
        elapsed = time.monotonic() - t0
    finally:
        batcher.stop()
    assert resp.allowed  # fail-open
    assert "timeout" in resp.message
    assert elapsed < 0.9  # answered before the stall ended
    assert counter(
        metrics, "webhook_unavailable_responses_total",
        plane="validation", policy="open", reason="timeout",
    ) == 1


# -- shutdown race (satellite) ------------------------------------------------


def _race_stop(batcher, make_request_fn, n_threads=6, per_thread=30):
    """Hammer submit() from n_threads while stop() lands mid-burst;
    every future must resolve (result or exception) — none may hang."""
    futs = []
    lock = threading.Lock()
    start = threading.Barrier(n_threads + 1)

    def worker(tid):
        start.wait()
        for i in range(per_thread):
            f = batcher.submit(make_request_fn(tid * 1000 + i))
            with lock:
                futs.append(f)

    threads = [
        threading.Thread(target=worker, args=(t,)) for t in range(n_threads)
    ]
    for t in threads:
        t.start()
    batcher.start()
    start.wait()
    time.sleep(0.005)  # let submits interleave with the running worker
    batcher.stop()  # races the in-flight submits
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    assert len(futs) == n_threads * per_thread  # none dropped
    for f in futs:
        try:
            f.result(timeout=5)
        except FutureTimeout:
            raise AssertionError("future hung across stop()")
        except Exception:
            pass  # typed shed/deadline exceptions are acceptable outcomes


def test_microbatcher_stop_submit_race_never_hangs():
    client = build_client()
    batcher = MicroBatcher(client, TARGET, window_ms=0.5)
    _race_stop(batcher, admission_request)


def test_mutatebatcher_stop_submit_race_never_hangs():
    from gatekeeper_tpu.mutation import MutationSystem
    from gatekeeper_tpu.webhook.mutate import MutateBatcher

    system = MutationSystem()
    system.upsert(
        {
            "apiVersion": "mutations.gatekeeper.sh/v1alpha1",
            "kind": "AssignMetadata",
            "metadata": {"name": "race-label"},
            "spec": {
                "location": "metadata.labels.raced",
                "parameters": {"assign": {"value": "yes"}},
            },
        }
    )
    batcher = MutateBatcher(system, window_ms=0.5)
    _race_stop(batcher, admission_request)


# -- mutation plane -----------------------------------------------------------


def make_mutate_stack(fail_policy="open", mutators=(), request_timeout=5.0):
    from gatekeeper_tpu.mutation import MutationSystem
    from gatekeeper_tpu.webhook.mutate import MutateBatcher, MutationHandler

    metrics = MetricsRegistry()
    system = MutationSystem(metrics=metrics)
    for m in mutators:
        system.upsert(m)
    batcher = MutateBatcher(system, window_ms=1.0, metrics=metrics)
    handler = MutationHandler(
        batcher, metrics=metrics, request_timeout=request_timeout,
        fail_policy=fail_policy,
    )
    return metrics, batcher, handler


LABEL_MUTATOR = {
    "apiVersion": "mutations.gatekeeper.sh/v1alpha1",
    "kind": "AssignMetadata",
    "metadata": {"name": "chaos-label"},
    "spec": {
        "location": "metadata.labels.chaos",
        "parameters": {"assign": {"value": "injected"}},
    },
}


def test_mutate_screen_fault_degrades_to_host_oracle():
    metrics, batcher, handler = make_mutate_stack(mutators=[LABEL_MUTATOR])
    FAULTS.arm("mutate.screen_dispatch", mode="error")
    # count=0 arms a passive probe: hits are counted, nothing ever fires
    FAULTS.arm("mutate.host_screen", mode="error", count=0)
    batcher.start()
    try:
        resp = handler.handle(admission_request(0))
    finally:
        batcher.stop()
    assert resp.allowed and resp.patch  # host screen still mutates
    ops = {(p["op"], p["path"]) for p in resp.patch}
    assert ("add", "/metadata/labels") in ops or any(
        "/metadata/labels" in p for _, p in ops
    )
    assert counter(metrics, "mutation_batch_failures_total") >= 1
    assert FAULTS.fired("mutate.screen_dispatch") >= 1
    assert FAULTS.hits("mutate.host_screen") >= 1  # rung order


@pytest.mark.parametrize("fail_policy,expect_allowed,expect_code", [
    ("open", True, 200),
    ("closed", False, 503),
])
def test_mutate_both_rungs_down_policy_envelope(fail_policy,
                                                expect_allowed,
                                                expect_code):
    metrics, batcher, handler = make_mutate_stack(
        fail_policy=fail_policy, mutators=[LABEL_MUTATOR]
    )
    FAULTS.arm("mutate.screen_dispatch", mode="error")
    FAULTS.arm("mutate.host_screen", mode="error")
    batcher.start()
    try:
        resp = handler.handle(admission_request(0))
    finally:
        batcher.stop()
    assert resp.allowed is expect_allowed
    assert resp.code == expect_code
    assert not resp.patch  # fail-open admits UNMUTATED, never half-mutated
    assert counter(
        metrics, "webhook_unavailable_responses_total",
        plane="mutation", policy=fail_policy, reason="degraded",
    ) == 1


def test_unconverged_mutation_never_admitted_even_failing_open():
    """The non-negotiable rung: divergence is a poisoned request, not an
    unavailability — fail-open must NOT soften it to an admit."""
    def flip(name, val, prev):
        return {
            "apiVersion": "mutations.gatekeeper.sh/v1alpha1",
            "kind": "Assign",
            "metadata": {"name": name},
            "spec": {
                "applyTo": [
                    {"groups": [""], "versions": ["v1"], "kinds": ["Pod"]}
                ],
                "location": "spec.phase",
                "parameters": {
                    "assign": {"value": val},
                    "assignIf": {"in": [None, prev]},
                },
            },
        }
    metrics, batcher, handler = make_mutate_stack(
        fail_policy="open",
        mutators=[flip("flip-a", "a", "b"), flip("flip-b", "b", "a")],
    )
    batcher.start()
    try:
        resp = handler.handle(admission_request(0))
    finally:
        batcher.stop()
    assert not resp.allowed and resp.code == 500
    assert counter(metrics, "mutation_divergence_total") >= 1


def test_mutate_deadline_expired_policy_envelope():
    metrics, batcher, handler = make_mutate_stack(
        mutators=[LABEL_MUTATOR], request_timeout=-0.5
    )
    FAULTS.arm("mutate.screen_dispatch", mode="error")  # dispatch sentinel
    batcher.start()
    try:
        resp = handler.handle(admission_request(0))
    finally:
        batcher.stop()
    assert resp.allowed and not resp.patch  # fail-open, unmutated
    assert batcher.batches_dispatched == 0
    assert FAULTS.hits("mutate.screen_dispatch") == 0
    assert counter(
        metrics, "webhook_shed_total", plane="mutation", reason="deadline"
    ) == 1


# -- audit plane (satellite: the silent-barrier fix) -------------------------


class _StubAuditClient:
    def audit(self, tracing=False):
        class _R:
            by_target = {}

        return _R()


def test_audit_barrier_failure_counted_and_logged_with_trace_id():
    from gatekeeper_tpu.audit import AuditManager
    from gatekeeper_tpu.obs import Tracer

    metrics = MetricsRegistry()
    log = CapturingLogger()
    tracer = Tracer()
    FAULTS.arm("audit.barrier", mode="error")
    mgr = AuditManager(
        _StubAuditClient(), TARGET, audit_interval=3600.0,
        metrics=metrics, logger=log, tracer=tracer,
        wait_for=lambda t: True,
    )
    mgr.start()
    assert mgr.warmed.wait(timeout=10)  # barrier failed, sweep ran anyway
    mgr.stop()
    assert counter(metrics, "audit_barrier_failures_total") == 1
    recs = [r for r in log.records if "barrier" in r["msg"]]
    assert recs and recs[0]["level"] == "error"
    assert recs[0].get("trace_id")  # correlated into /debug/traces
    assert any(
        any(s["name"] == "audit_barrier_failure" for s in t["spans"])
        for t in tracer.recent(50)
    )


def test_audit_status_write_fault_counted_sweep_survives():
    from gatekeeper_tpu.audit import AuditManager

    metrics = MetricsRegistry()
    log = CapturingLogger()
    FAULTS.arm("audit.status_write", mode="error")
    mgr = AuditManager(
        _StubAuditClient(), TARGET, metrics=metrics, logger=log
    )
    report = mgr.audit()  # must not raise
    assert report is not None
    assert mgr.sink.latest is None  # the publish was the thing that failed
    assert counter(metrics, "audit_status_write_failures_total") == 1
    assert any("publish failed" in r["msg"] for r in log.records)
    FAULTS.reset()
    report = mgr.audit()
    assert mgr.sink.latest is report  # next sweep re-publishes


# -- device fault domains (docs/robustness.md §Fault domains) ----------------


PART_NAMESPACES = ["ns-a", "ns-b", "ns-c", "ns-d"]


def build_partitioned_stack(recovery_clock, failure_threshold=2,
                            recorder=None):
    """4 constraint kinds, each matching exactly one namespace, split
    over a 4-partition plan (sorted identities -> kind i lands in
    partition i on device i): one namespace addresses one fault
    domain."""
    from gatekeeper_tpu.obs import Tracer
    from gatekeeper_tpu.parallel.partition import PartitionDispatcher

    cl = Backend(TpuDriver(use_jax=False)).new_client(K8sValidationTarget())
    for i, ns in enumerate(PART_NAMESPACES):
        kind = f"Fault{chr(65 + i)}"
        cl.add_template({
            "apiVersion": "templates.gatekeeper.sh/v1beta1",
            "kind": "ConstraintTemplate",
            "metadata": {"name": kind.lower()},
            "spec": {
                "crd": {"spec": {"names": {"kind": kind}}},
                "targets": [{
                    "target": TARGET,
                    "rego": REQ_LABELS.replace("reqlabels", kind.lower()),
                }],
            },
        })
        cl.add_constraint({
            "apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": kind,
            "metadata": {"name": f"need-owner-{ns}"},
            "spec": {
                "match": {"namespaces": [ns]},
                "parameters": {"labels": ["owner"]},
            },
        })
    metrics = MetricsRegistry()
    tracer = Tracer()
    disp = PartitionDispatcher(
        cl, TARGET, k=4, metrics=metrics, tracer=tracer,
        failure_threshold=failure_threshold, recovery_seconds=5.0,
        clock=lambda: recovery_clock[0],
        recorder=recorder,
    )
    batcher = MicroBatcher(
        cl, TARGET, window_ms=1.0, metrics=metrics, tracer=tracer,
        partitioner=disp,
    )
    handler = BatchedValidationHandler(
        batcher, request_timeout=5.0, metrics=metrics, tracer=tracer,
        fail_policy="open",
    )
    return cl, metrics, tracer, disp, batcher, handler


def ns_request(i, ns, labels=None):
    req = admission_request(i, labels=labels)
    req["namespace"] = ns
    req["object"]["metadata"]["namespace"] = ns
    return req


def test_partitioned_device_fault_isolates_constraint_subset():
    """The fault-domain acceptance e2e: device 1 of 4 faulted via the
    injection registry. Requests matching only healthy partitions stay
    on the fused path (ZERO degraded dispatches, no degraded spans);
    the faulted partition's subset degrades to host with CORRECT
    verdicts; the breaker trip quarantines the device and re-homing
    restores full fused coverage; post-disarm the half-open probe heals
    the device and the plan returns to its home assignment. The SLO
    holds throughout: every request gets a real verdict."""
    from gatekeeper_tpu.faults import device_point

    clock = [0.0]
    _, metrics, tracer, disp, batcher, handler = build_partitioned_stack(
        clock
    )
    deg = lambda: counter(  # noqa: E731
        metrics, "webhook_degraded_dispatch_total", plane="validation"
    )
    batcher.start()
    try:
        # healthy: every namespace gets fused verdicts
        for i, ns in enumerate(PART_NAMESPACES):
            resp = handler.handle(ns_request(i, ns))
            assert not resp.allowed and resp.code == 403
            assert f"need-owner-{ns}" in resp.message
        assert handler.handle(
            ns_request(9, "ns-a", labels={"owner": "x"})
        ).allowed
        assert disp.dispatches["host"] == 0
        assert disp.dispatches["failed"] == 0
        assert deg() == 0

        # device 1 sick: ns-b's subset degrades to host — with correct
        # verdicts — while every other namespace stays fused
        FAULTS.arm(device_point("driver.device_dispatch", 1),
                   mode="error")
        fused_before = disp.dispatches["fused"]
        for i, ns in enumerate(["ns-a", "ns-c", "ns-d"]):
            resp = handler.handle(ns_request(20 + i, ns))
            assert not resp.allowed and resp.code == 403
        # healthy-partition traffic paid zero degraded/host dispatches
        assert disp.dispatches["host"] == 0
        assert disp.dispatches["failed"] == 0
        assert deg() == 0
        assert disp.dispatches["fused"] == fused_before + 3
        resp = handler.handle(ns_request(30, "ns-b"))  # failure 1
        assert not resp.allowed and resp.code == 403  # host rung verdict
        assert "need-owner-ns-b" in resp.message
        assert disp.dispatches["failed"] == 1
        assert disp.dispatches["host"] == 1
        resp = handler.handle(ns_request(31, "ns-b"))  # failure 2: trip
        assert not resp.allowed and resp.code == 403
        assert disp.breaker(1).state == OPEN
        snap = disp.snapshot()
        assert snap["quarantined"] == [1]

        # quarantined: partition 1 re-homes onto a healthy device and
        # ns-b traffic is FUSED again while the chip is still sick
        failed_before = disp.dispatches["failed"]
        host_before = disp.dispatches["host"]
        labeled_fire = FAULTS.fired(
            device_point("driver.device_dispatch", 1)
        )
        resp = handler.handle(ns_request(32, "ns-b"))
        assert not resp.allowed and resp.code == 403
        assert disp.dispatches["failed"] == failed_before
        assert disp.dispatches["host"] == host_before
        plan = disp.plan()
        rehomed = plan.partitions[1]
        assert rehomed.home_device == 1 and rehomed.device != 1
        assert disp.rehomes >= 1
        # the sick device saw no further dispatches
        assert FAULTS.fired(
            device_point("driver.device_dispatch", 1)
        ) == labeled_fire

        # degraded spans: only ns-b requests carry one
        degraded_ns = set()
        for t in tracer.recent(200):
            names = {s["name"] for s in t["spans"]}
            if "degraded_subset" not in names:
                continue
            for s in t["spans"]:
                if s["name"] == "handler":
                    degraded_ns.add(s["attrs"].get("resource_namespace"))
        assert degraded_ns == {"ns-b"}

        # recovery: disarm, recovery window elapses, the probe heals
        # the device, and the plan restores the home assignment
        FAULTS.reset()
        clock[0] = 6.0
        resp = handler.handle(ns_request(40, "ns-b"))
        assert not resp.allowed and resp.code == 403
        # the probe runs on the batch worker AFTER the batch's futures
        # resolve (off the request path): wait for it to land
        deadline = time.monotonic() + 5.0
        while (
            disp.breaker(1).state != CLOSED
            and time.monotonic() < deadline
        ):
            time.sleep(0.01)
        assert disp.breaker(1).state == CLOSED
        assert disp.probes >= 1
        plan = disp.plan()
        assert all(p.device == p.home_device for p in plan.partitions)
        assert counter(
            metrics, "device_quarantine_probes_total",
            plane="validation", device="1", result="success",
        ) == 1
    finally:
        batcher.stop()
        disp.close()


def test_device_fault_trips_exactly_one_flight_record():
    """The flight-recorder chaos e2e (ISSUE 10 acceptance): a device
    fault that trips `device:validation:1` produces EXACTLY ONE flight
    record, containing the breaker transition, the quarantined
    partition's constraint keys, and >= 1 degraded-request trace —
    retrievable at /debug/flightrecords and bounded at N=16."""
    import json
    import urllib.request

    from gatekeeper_tpu.faults import device_point
    from gatekeeper_tpu.metrics import serve_metrics
    from gatekeeper_tpu.obs import FlightRecorder

    clock = [0.0]
    recorder = FlightRecorder(
        # rate limit far beyond the test window: related triggers
        # coalesce into ONE record and nothing else can slip in
        min_interval_s=300.0, debounce_s=0.15, max_records=16,
    )
    _, metrics, tracer, disp, batcher, handler = build_partitioned_stack(
        clock, recorder=recorder
    )
    recorder.tracer = tracer
    recorder.metrics = metrics
    recorder.add_source("partitions", disp.postmortem)
    batcher.start()
    try:
        # healthy traffic first (plan built, no triggers)
        for i, ns in enumerate(PART_NAMESPACES):
            assert not handler.handle(ns_request(i, ns)).allowed
        assert recorder.records() == []

        # sicken device 1: two ns-b failures trip its breaker to OPEN
        FAULTS.arm(device_point("driver.device_dispatch", 1),
                   mode="error")
        for i in range(2):
            resp = handler.handle(ns_request(30 + i, "ns-b"))
            assert not resp.allowed and resp.code == 403
        assert disp.breaker(1).state == OPEN

        # exactly one record captures (debounce + rate limit)
        deadline = time.monotonic() + 5.0
        while not recorder.records() and time.monotonic() < deadline:
            time.sleep(0.02)
        # traffic AFTER the trip must not mint more records
        assert not handler.handle(ns_request(40, "ns-b")).allowed
        recorder.flush()
        time.sleep(0.2)
        records = recorder.records()
        assert len(records) == 1, [r["trigger"] for r in records]
        record = records[0]

        # (a) the breaker transition
        assert record["trigger"] == "breaker_open"
        ctx = record["triggers"][0]["context"]
        assert ctx["breaker"] == "device:validation:1"
        assert ctx["from_state"] == CLOSED and ctx["to_state"] == OPEN

        # (b) the quarantined partition's constraint keys
        part_state = record["state"]["partitions"]
        assert part_state["quarantined"] == [1]
        assert part_state["quarantined_constraint_keys"] == [
            "FaultB/need-owner-ns-b"
        ]

        # (c) >= 1 degraded-request trace in the tail
        degraded = [
            t for t in record["trace_tail"]
            if any(s["name"] == "degraded_subset" for s in t["spans"])
        ]
        assert degraded, [
            [s["name"] for s in t["spans"]] for t in record["trace_tail"]
        ]

        # (d) retrievable via /debug/flightrecords, bound advertised
        httpd = serve_metrics(metrics, port=0, recorder=recorder)
        try:
            port = httpd.server_address[1]
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/flightrecords", timeout=5
            ) as r:
                doc = json.loads(r.read())
            assert doc["max_records"] == 16
            assert len(doc["records"]) == 1
            assert doc["records"][0]["trigger"] == "breaker_open"
        finally:
            httpd.shutdown()

        # the flight_records_total series counted the capture
        assert counter(
            metrics, "flight_records_total", trigger="breaker_open"
        ) == 1
    finally:
        FAULTS.reset()
        batcher.stop()
        disp.close()
        recorder.stop()


def test_partitioned_all_devices_dead_falls_back_to_plane_host_mode():
    """Every device breaker open: the partitioned path falls back to
    the existing whole-plane host mode (correct verdicts, degraded
    accounting) instead of wedging."""
    clock = [0.0]
    _, metrics, _, disp, batcher, handler = build_partitioned_stack(
        clock, failure_threshold=1
    )
    FAULTS.arm("driver.device_dispatch", mode="error")  # every device
    batcher.start()
    try:
        for i, ns in enumerate(PART_NAMESPACES):
            resp = handler.handle(ns_request(i, ns))
            assert not resp.allowed and resp.code == 403  # host verdicts
        assert disp.plan().all_dead
        FAULTS.reset()
        resp = handler.handle(ns_request(50, "ns-a"))
        assert not resp.allowed and resp.code == 403
        assert counter(
            metrics, "webhook_degraded_dispatch_total", plane="validation"
        ) >= 1
        # probes ran from the whole-plane host path and healed devices
        clock[0] = 6.0
        handler.handle(ns_request(51, "ns-b"))
        deadline = time.monotonic() + 5.0
        while disp.plan().all_dead and time.monotonic() < deadline:
            time.sleep(0.01)
            handler.handle(ns_request(52, "ns-c"))
        assert not disp.plan().all_dead
    finally:
        batcher.stop()
        disp.close()


# -- webhook HTTP e2e under chaos --------------------------------------------


def test_http_e2e_ladder_under_device_fault():
    """Full HTTP round trip with the fused rung down: the server still
    answers every request correctly from the host rung (the apiserver
    client never sees the fault)."""
    import json
    import urllib.request

    from gatekeeper_tpu.webhook import WebhookServer

    FAULTS.arm("webhook.batch_dispatch", mode="error")
    server = WebhookServer(build_client(), TARGET, metrics=MetricsRegistry())
    server.start()
    try:
        def post(req):
            body = json.dumps(
                {
                    "apiVersion": "admission.k8s.io/v1",
                    "kind": "AdmissionReview",
                    "request": req,
                }
            ).encode()
            r = urllib.request.Request(
                f"http://127.0.0.1:{server.port}/v1/admit",
                data=body,
                headers={"Content-Type": "application/json"},
                method="POST",
            )
            with urllib.request.urlopen(r, timeout=30) as resp:
                return json.loads(resp.read())["response"]

        deny = post(admission_request(0))
        allow = post(admission_request(1, labels={"owner": "a"}))
    finally:
        server.stop()
    assert not deny["allowed"]
    assert "need-owner" in deny["status"]["message"]
    assert allow["allowed"]
    assert FAULTS.fired("webhook.batch_dispatch") >= 1
