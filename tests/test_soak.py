"""Soak-plane tests (docs/operations.md §Soak runbook).

Three layers:
  * unit: scenario parsing/validation, the open-loop generator's
    no-back-pressure contract, window binning, leak detection, and the
    report schema/SUMMARY round-trip;
  * the ~10 s smoke scenario end-to-end (tier-1, `soak` marker): real
    WebhookServer, churn + fault + recovery, schema-checked;
  * the full minutes-long default scenario (`slow`): the generator for
    SOAK_r01-style evidence runs.

The checked-in SOAK_r01.json is schema-gated here too, so the evidence
artifact cannot drift from the reader.
"""

import json
import os
import threading
import time

import pytest

from gatekeeper_tpu.soak import (
    Scenario,
    check_soak_schema,
    default_scenario,
    monotonic_growth,
    parse_summary_line,
    run_open_loop,
    run_soak,
    smoke_scenario,
    summarize_soak,
)
from gatekeeper_tpu.soak.loadgen import Sample
from gatekeeper_tpu.soak.report import (
    aggregate_phases,
    bin_windows,
    build_report,
    leak_report,
)

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

pytestmark = pytest.mark.soak


# -- scenario ----------------------------------------------------------------


def test_scenario_roundtrip_and_validation():
    scn = default_scenario()
    scn.validate()
    again = Scenario.from_dict(scn.to_dict())
    assert again.to_dict() == scn.to_dict()
    assert again.events[0].action == "phase"


def test_scenario_rejects_unknown_action():
    with pytest.raises(ValueError, match="unknown scenario action"):
        Scenario.from_dict({
            "duration_s": 10, "rps": 5,
            "events": [{"at": 1, "action": "explode"}],
        })


def test_scenario_rejects_event_past_duration():
    with pytest.raises(ValueError, match="past duration"):
        Scenario.from_dict({
            "duration_s": 10, "rps": 5,
            "events": [{"at": 11, "action": "disarm_faults"}],
        })


def test_scenario_rejects_bad_kill_index():
    with pytest.raises(ValueError, match="out of range"):
        Scenario.from_dict({
            "duration_s": 10, "rps": 5, "replicas": 1,
            "events": [{"at": 1, "action": "kill_replica", "replica": 3}],
        })


def test_scenario_rejects_unknown_keys():
    with pytest.raises(ValueError, match="unknown scenario keys"):
        Scenario.from_dict({"duration_s": 10, "rps": 5, "nope": 1})


def test_default_scenario_has_locality_skew_phase():
    """The pruned-dispatch evidence window: default_scenario carries a
    locality_skew phase whose locality_churn event adds two namespace-
    affine constraint groups with 90/10 traffic skew."""
    scn = default_scenario()
    phases = [
        e.params.get("name") for e in scn.events if e.action == "phase"
    ]
    assert "locality_skew" in phases
    churn = [e for e in scn.events if e.action == "locality_churn"]
    assert len(churn) == 1
    assert churn[0].params.get("skew") == 0.9
    # round-trips through the strict loader like every other action
    Scenario.from_dict(scn.to_dict())


def test_locality_churn_event_skews_request_namespaces():
    """After a locality_churn event the harness's request stream lands
    skew% of traffic on the hot namespace, deterministically — and the
    namespace is consistent between the AdmissionRequest envelope and
    the object metadata."""
    from gatekeeper_tpu.soak.harness import SoakHarness

    h = SoakHarness(smoke_scenario())
    before = h._pod_request(3, False)["namespace"]
    assert before == "ns3"  # uniform mix until the event fires
    h._run_event("locality_churn", {"count": 2, "skew": 0.9})
    reqs = [h._pod_request(i, False) for i in range(100)]
    ns = [r["namespace"] for r in reqs]
    assert ns.count("ns-aff-hot") == 90
    assert ns.count("ns-aff-cold") == 10
    assert all(
        r["object"]["metadata"]["namespace"] == r["namespace"]
        for r in reqs
    )


# -- open loop ---------------------------------------------------------------


def test_open_loop_holds_rate_against_slow_system():
    """The defining property: a slow submit function must NOT slow the
    arrival rate — misses are counted, never back-pressured away."""
    calls = []

    def slow(_plane):
        calls.append(time.monotonic())
        time.sleep(0.05)
        return 200, "ok"

    load = run_open_loop(
        slow, rps=100, duration_s=1.0, deadline_s=0.01,
        seed=7, max_workers=2, drain_s=0.5,
    )
    # ~100 arrivals were scheduled even though 2 workers x 50ms can
    # only serve ~40/s — the backlog shows up as SLO misses instead
    assert load.generated > 60
    assert len(load.samples) == load.generated  # nothing silently lost
    assert load.slo_attainment() < 0.8
    unserved = [s for s in load.samples if s.outcome == "unserved"]
    assert unserved, "backlogged arrivals must be counted against SLO"


def test_open_loop_latency_includes_queue_wait():
    """Open-loop latency is measured from the SCHEDULED arrival: a
    burst that queues at the generator shows the wait (no coordinated
    omission)."""
    def slow(_plane):
        time.sleep(0.03)
        return 200, "ok"

    load = run_open_loop(
        slow, rps=60, duration_s=0.6, deadline_s=1.0,
        seed=3, max_workers=1, drain_s=3.0,
    )
    served = [s for s in load.samples if s.outcome == "ok"]
    assert served
    # with one worker at ~33/s and 60/s arriving, later requests must
    # show multi-slot queueing delays
    assert max(s.latency_s for s in served) > 0.08


def test_open_loop_is_deterministic_per_seed():
    def fast(_plane):
        return 200, "ok"

    a = run_open_loop(fast, rps=150, duration_s=0.4, deadline_s=1, seed=5)
    b = run_open_loop(fast, rps=150, duration_s=0.4, deadline_s=1, seed=5)
    assert a.generated == b.generated
    assert [s.plane for s in a.samples] == [s.plane for s in b.samples]


def test_open_loop_submit_exception_is_conn_error():
    def boom(_plane):
        raise OSError("refused")

    load = run_open_loop(boom, rps=80, duration_s=0.3, deadline_s=0.5)
    assert load.samples
    assert all(s.outcome == "conn_error" for s in load.samples)
    assert load.slo_attainment() == 0.0


# -- report ------------------------------------------------------------------


def _mk_samples(n, window_s=1.0, lat=0.01, status=200, outcome="ok"):
    return [
        Sample(
            t_rel=i * window_s / max(1, n) * 4,  # spread over 4 windows
            plane="validation",
            latency_s=lat,
            status=status,
            outcome=outcome,
        )
        for i in range(n)
    ]


def test_bin_windows_phases_and_slo():
    samples = _mk_samples(40)
    phase_at = {0.0: "steady", 2.0: "fault"}
    rows = bin_windows(samples, 4.0, 1.0, 0.05, phase_at=phase_at)
    assert len(rows) == 4
    assert rows[0]["phase"] == "steady"
    assert rows[2]["phase"] == "fault"
    assert all(r["slo_attainment"] == 1.0 for r in rows if r["requests"])
    phases = aggregate_phases(rows)
    assert [p["phase"] for p in phases] == ["steady", "fault"]


def test_bin_windows_counts_misses():
    slow = _mk_samples(20, lat=0.5)
    rows = bin_windows(slow, 4.0, 1.0, 0.05)
    assert sum(r["slo_misses"] for r in rows) == 20


def test_monotonic_growth_flags_leak_not_plateau():
    assert monotonic_growth([100, 120, 140, 160, 180, 200, 220])
    # plateau: fills then flat (a bounded cache) — must NOT flag
    assert not monotonic_growth([100, 200, 256, 256, 256, 256, 256])
    # flat with jitter — must not flag
    assert not monotonic_growth([100, 101, 99, 100, 102, 100, 101])
    # too few samples: no verdict
    assert not monotonic_growth([1, 2, 3])
    # shrinking (eviction working) — must not flag
    assert not monotonic_growth([100, 90, 95, 85, 90, 80, 85])


def test_leak_report_judges_steady_windows_only():
    windows = []
    for i in range(8):
        windows.append({"phase": "steady", "rss_kb": 1000,
                        "cache_entries": 50})
    for i in range(4):
        # churn legitimately grows the cache — must not flag
        windows.append({"phase": "churn", "rss_kb": 1000 + i * 500,
                        "cache_entries": 50 + i * 100})
    rep = leak_report(windows)
    assert rep["sufficient_steady_windows"]
    assert rep["flat"], rep["flagged"]


def test_leak_report_flags_steady_growth():
    windows = [
        {"phase": "steady", "rss_kb": 1000 + i * 400, "cache_entries": 50}
        for i in range(10)
    ]
    rep = leak_report(windows)
    assert "rss_kb" in rep["flagged"]
    assert not rep["flat"]


def test_report_schema_and_summary_roundtrip():
    from gatekeeper_tpu.soak.loadgen import OpenLoopLoad

    scn = smoke_scenario()
    load = OpenLoopLoad(
        target_rps=scn.rps, duration_s=scn.duration_s,
        deadline_s=scn.deadline_s, generated=20,
        samples=_mk_samples(20),
    )
    res = build_report(
        scn.to_dict(), load, [], [], {"seconds": {}}
    )
    assert check_soak_schema(res) == []
    line = summarize_soak(res)
    doc = parse_summary_line(line)
    assert doc["mode"] == "soak"
    assert doc["scenario"] == "soak-smoke"
    with pytest.raises(ValueError):
        parse_summary_line("SUMMARY: {\"mode\": \"webhook\"}")
    with pytest.raises(ValueError):
        parse_summary_line("not a summary")


# -- checked-in evidence -----------------------------------------------------


def test_checked_in_soak_evidence_schema():
    """SOAK_r01.json (the acceptance artifact) must parse and carry the
    SLO/shed/leak fields — and its acceptance windows must actually
    show what the ISSUE demanded of them."""
    path = os.path.join(REPO, "SOAK_r01.json")
    assert os.path.exists(path), "SOAK_r01.json evidence run missing"
    with open(path) as f:
        doc = json.load(f)
    assert check_soak_schema(doc) == []
    checks = doc["checks"]
    assert checks["fault_window_degrades_and_recovers"] is True
    assert checks["churn_zero_5xx"] is True
    assert checks["replica_kill_shed_bounded"] is True
    assert checks["leak_flat"] is True
    assert checks["steady_seconds"] >= 60.0
    assert doc["breaker_transitions"], "no breaker transitions logged"
    # the SUMMARY line regenerates and parses
    parse_summary_line(summarize_soak(doc))


# -- end-to-end smoke --------------------------------------------------------


def test_soak_smoke_scenario_end_to_end():
    """The ~10 s smoke: real WebhookServer + all three planes under
    open-loop load with churn and a fault window. Pins the schema, the
    zero-5xx churn contract, and that the breaker cycled during the
    fault. SLO numbers themselves are load-bearing only directionally
    (CI boxes jitter): fault attainment must sit below recovery."""
    res = run_soak(smoke_scenario())
    assert check_soak_schema(res) == []
    phases = {p["phase"]: p for p in res["phases"]}
    assert set(phases) >= {"steady", "churn", "fault", "recovery"}
    # churn (constraint + provider adds) must not 5xx or drop anything
    assert phases["churn"]["http_5xx"] == 0
    assert phases["churn"]["transport_errors"] == 0
    # the armed fault must visibly degrade the SLO vs recovery and
    # trip the breaker (transitions logged with timestamps/planes)
    assert phases["fault"]["slo_attainment"] < phases["recovery"][
        "slo_attainment"
    ]
    assert phases["fault"]["breaker_transitions"] > 0
    trans = res["breaker_transitions"]
    assert any(t["to"] == "open" for t in trans)
    assert any(t["to"] == "closed" for t in trans)
    # open-loop held its rate (within scheduler jitter)
    assert res["open_loop"]["achieved_rps"] > res["open_loop"][
        "target_rps"
    ] * 0.8
    # every generated arrival is accounted for
    assert res["open_loop"]["observed"] >= res["open_loop"]["generated"]
    # leak evidence sampled per window
    for w in res["windows"]:
        assert "cache_entries" in w and "trace_ring" in w
    # faults disarmed + logged
    assert res["faults"], "disarm_faults must log the fired spec"
    fired = res["faults"][0]["disarmed"]
    assert fired.get("webhook.batch_dispatch", {}).get("fired", 0) > 0
    # live SLO plane (ISSUE 17): the streaming engine measured the
    # same post-warmup traffic the offline reporter binned — the
    # report carries the shared target, the live block, and the
    # live-vs-offline agreement check must hold within tolerance
    assert "target" in res["slo"]
    live = res["slo"]["live"]
    assert live["requests_slow"] >= 50
    assert 0.0 <= live["saturation"] <= 1.0
    agree = res["checks"]["live_vs_offline_attainment"]
    assert agree["agree"] is True, agree
    # the sampler stamped the live signals into every window
    for w in res["windows"]:
        assert "slo_saturation" in w and "slo_burn_fast" in w
    # the SUMMARY line round-trips
    parse_summary_line(summarize_soak(res))


def test_soak_sdc_smoke_scenario():
    """The ~9 s verdict-integrity smoke (docs/robustness.md §Verdict
    integrity): an armed `integrity.canary[device=1]` bit-flip mid-
    steady-state must be detected by the canary tier, quarantined with
    reason `corruption`, and healed by the post-disarm golden
    self-test — all judged by the sdc_detected_and_quarantined report
    check over the per-window canary/quarantine evidence columns."""
    from gatekeeper_tpu.soak import sdc_smoke_scenario

    res = run_soak(sdc_smoke_scenario())
    assert check_soak_schema(res) == []
    check = res["checks"]["sdc_detected_and_quarantined"]
    assert check["holds"] is True, check
    # per-window evidence: mismatches recorded during the sdc phase,
    # quarantine visible in at least one window, empty at the end
    sdc_ws = [w for w in res["windows"] if w["phase"] == "sdc"]
    assert sum(w["canary_mismatches"] for w in sdc_ws) > 0
    assert any(w["quarantined_devices"] > 0 for w in res["windows"])
    assert res["windows"][-1]["quarantined_devices"] == 0
    # clean phases carry clean columns (no false positives)
    steady = [w for w in res["windows"] if w["phase"] == "steady"]
    assert all(w["canary_mismatches"] == 0 for w in steady)
    # the run still serves: no 5xx during the sdc window (healthy
    # devices keep serving fused; the sick device's partitions
    # re-home) — judged on server-side errors only
    phases = {p["phase"]: p for p in res["phases"]}
    assert phases["sdc"]["http_5xx"] == 0
    parse_summary_line(summarize_soak(res))


def test_soak_multi_tenant_smoke_deadline_vs_fifo():
    """The ~8 s multi-tenant overload smoke, both queue disciplines
    (docs/operations.md §Admission scheduling). Attainment NUMBERS are
    asserted only directionally — the smoke is overdriven on purpose
    and CI boxes jitter — but the machinery must all fire: the
    per-class sampler columns, the typed shed split, and the report
    check for each policy. The full 60 s acceptance run is
    multi_tenant_overload_scenario (slow lane / evidence runs)."""
    from gatekeeper_tpu.soak import multi_tenant_smoke_scenario

    dl = run_soak(multi_tenant_smoke_scenario("deadline"))
    assert check_soak_schema(dl) == []
    check = dl["checks"]["quiet_tenant_attainment_holds"]
    # the split the scheduler exists to produce: the quiet namespace
    # rides out the noisy tenant's overdrive (which gets capped/shed)
    assert check["noisy_shed"] > 0
    assert check["quiet_attainment"] > check["noisy_attainment"]
    # per-window evidence columns: tenant classes + typed shed counts
    assert any(
        w["tenant_classes"]["noisy"]["shed"] > 0 for w in dl["windows"]
    )
    assert any(
        (w["sched_tenant_capped"] + w["sched_predicted_miss"]) > 0
        for w in dl["windows"]
    )
    parse_summary_line(summarize_soak(dl))

    fifo = run_soak(multi_tenant_smoke_scenario("fifo"))
    assert check_soak_schema(fifo) == []
    # the baseline check is emitted with both classes measured against
    # the shared objective; `degrades` itself is only load-bearing in
    # the full 2x-overdrive scenario (a CI box serves the smoke's
    # 120 rps without breaking a sweat under either policy)
    base = fifo["checks"]["fifo_baseline_degrades"]
    assert set(base) >= {
        "quiet_attainment", "noisy_attainment", "objective", "degrades"
    }
    # FIFO emits no sched series and takes no typed sheds
    assert all(
        w["sched_tenant_capped"] == 0 and w["sched_predicted_miss"] == 0
        for w in fifo["windows"]
    )


@pytest.mark.slow
def test_soak_full_default_scenario():
    """The minutes-long evidence generator (SOAK_r01's scenario): two
    TLS replicas, fleet gossip, churn, fault, rotation, replica kill.
    Slow lane only."""
    res = run_soak(default_scenario())
    assert check_soak_schema(res) == []
    checks = res["checks"]
    assert checks["churn_zero_5xx"] is True
    assert checks["replica_kill_shed_bounded"] is True
    assert checks["steady_seconds"] >= 60.0
    assert res["breaker_transitions"]


# -- the bounded caches (satellite: bound the unbounded) ---------------------


def test_response_cache_lru_eviction_and_counters():
    from gatekeeper_tpu.externaldata.cache import ResponseCache
    from gatekeeper_tpu.metrics import MetricsRegistry

    metrics = MetricsRegistry()
    clock = [0.0]
    cache = ResponseCache(
        clock=lambda: clock[0], max_entries=4, metrics=metrics
    )
    for i in range(4):
        cache.put("p", f"k{i}", value=i, ttl=100)
    assert len(cache) == 4 and cache.evictions == 0
    # touch k0 (LRU refresh), then overflow: k1 — the LRU — must go
    cache.classify("p", ["k0"])
    cache.put("p", "k4", value=4, ttl=100)
    assert len(cache) == 4
    assert cache.evictions == 1
    states = cache.classify("p", ["k0", "k1", "k4"])
    assert states["k0"][0] == "hit"
    assert states["k1"][0] == "miss"  # evicted
    assert states["k4"][0] == "hit"
    counters = metrics.snapshot()["counters"]
    assert (
        counters.get('externaldata_cache_evictions_total{provider="p"}')
        == 1
    )


def test_response_cache_merge_respects_bound():
    from gatekeeper_tpu.externaldata.cache import ResponseCache

    cache = ResponseCache(clock=lambda: 100.0, max_entries=3)
    for i in range(3):
        cache.put("p", f"k{i}", value=i, ttl=100)
    assert cache.merge(
        {"provider": "p", "key": "peer", "value": 1, "age_s": 0,
         "ttl": 100, "stale_ttl": 0},
        origin="other",
    )
    assert len(cache) == 3
    assert cache.evictions == 1


def test_external_system_snapshot_carries_evictions():
    from gatekeeper_tpu.externaldata import ExternalDataSystem

    system = ExternalDataSystem(cache_max_entries=2)
    system.cache.put("p", "a", value=1, ttl=10)
    system.cache.put("p", "b", value=1, ttl=10)
    system.cache.put("p", "c", value=1, ttl=10)
    snap = system.snapshot()
    assert snap["cache_entries"] == 2
    assert snap["cache_evictions"] == 1


def test_driver_render_cache_bounded():
    from gatekeeper_tpu.constraint import TpuDriver
    from gatekeeper_tpu.metrics import MetricsRegistry

    driver = TpuDriver()
    metrics = MetricsRegistry()
    driver.set_metrics(metrics)
    driver.render_cache_max = 8
    cache = {}
    for i in range(20):
        driver._render_cache_put(cache, (i, 0), [])
    assert len(cache) == 8
    assert driver._render_cache_evictions == 12
    # oldest-inserted pairs are the ones gone
    assert (0, 0) not in cache and (19, 0) in cache
    assert (
        metrics.snapshot()["counters"][
            "driver_render_cache_evictions_total"
        ]
        == 12
    )
    assert driver.render_cache_size() == 0  # per-target store untouched


# -- graceful drain under load (satellite) -----------------------------------


def _drain_client():
    from gatekeeper_tpu.constraint import Backend, K8sValidationTarget
    from gatekeeper_tpu.constraint import RegoDriver
    from gatekeeper_tpu.soak.harness import (
        _PRIV_REGO,
        _POD_MATCH,
        _constraint,
        _pod_request,
        _template,
    )

    client = Backend(RegoDriver()).new_client(K8sValidationTarget())
    client.add_template(
        _template("SoakPrivileged",
                  "admission.k8s.gatekeeper.sh", _PRIV_REGO)
    )
    client.add_constraint(
        _constraint("SoakPrivileged", "d0", match=_POD_MATCH)
    )
    return client, _pod_request


def test_graceful_drain_sheds_zero_accepted_requests():
    """SIGTERM mid-load: every request the listener ACCEPTED must get a
    real 200, not a reset — readiness flips first, the in-flight wait
    holds teardown until the batchers have answered everything."""
    import urllib.request

    from gatekeeper_tpu.faults import FAULTS
    from gatekeeper_tpu.webhook.server import WebhookServer

    client, _pod_request = _drain_client()
    server = WebhookServer(client, "admission.k8s.gatekeeper.sh",
                           window_ms=5.0)
    server.start()
    drain_seen = threading.Event()
    server.on_drain(drain_seen.set)
    statuses = []
    statuses_lock = threading.Lock()
    started = threading.Barrier(9, timeout=10)

    def post(i):
        body = json.dumps({
            "apiVersion": "admission.k8s.io/v1",
            "kind": "AdmissionReview",
            "request": _pod_request(i, False),
        }).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/v1/admit",
            data=body, headers={"Content-Type": "application/json"},
            method="POST",
        )
        started.wait()
        try:
            with urllib.request.urlopen(req, timeout=15) as resp:
                code = resp.getcode()
        except Exception as e:
            code = repr(e)
        with statuses_lock:
            statuses.append(code)

    # a hang on the dispatch guarantees requests are mid-flight when
    # stop() lands (the race this regression test exists to pin)
    FAULTS.arm("webhook.batch_dispatch", mode="hang", delay_s=0.3)
    try:
        threads = [
            threading.Thread(target=post, args=(i,)) for i in range(8)
        ]
        for th in threads:
            th.start()
        started.wait()  # all 8 posts are in flight (or enqueued)
        time.sleep(0.05)
        with server._inflight_cv:
            inflight_at_stop = server._inflight
        server.stop()
        for th in threads:
            th.join(timeout=20)
    finally:
        FAULTS.reset()
    assert drain_seen.is_set(), "drain callback must fire"
    assert inflight_at_stop > 0, "test must catch requests mid-flight"
    assert statuses and all(c == 200 for c in statuses), statuses
    assert server.batcher.shed_count == 0
    # after stop, the listener is gone: new connections fail
    import urllib.error

    with pytest.raises((urllib.error.URLError, ConnectionError, OSError)):
        urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/v1/admit", data=b"{}",
            timeout=2,
        )


def test_drain_flips_readiness_before_listener_closes():
    """Ordering contract: at the moment on_drain observers run, the
    listener must still accept — that window is what lets an LB
    watching /readyz route away without a single failed connection."""
    import socket

    from gatekeeper_tpu.webhook.server import WebhookServer

    client, _ = _drain_client()
    server = WebhookServer(client, "admission.k8s.gatekeeper.sh")
    server.start()
    accepting_at_drain = []

    def probe():
        try:
            s = socket.create_connection(
                ("127.0.0.1", server.port), timeout=2
            )
            s.close()
            accepting_at_drain.append(True)
        except OSError:
            accepting_at_drain.append(False)

    server.on_drain(probe)
    assert server.ready
    server.stop()
    assert not server.ready
    assert accepting_at_drain == [True]


def test_runner_readyz_reports_draining(tmp_path):
    """Runner.stop flips /readyz to 503 via the webhook drain before
    the listener goes away."""
    import urllib.error
    import urllib.request

    from gatekeeper_tpu.constraint import Backend, K8sValidationTarget
    from gatekeeper_tpu.constraint import RegoDriver
    from gatekeeper_tpu.control import FakeCluster, Runner

    cluster = FakeCluster()
    client = Backend(RegoDriver()).new_client(K8sValidationTarget())
    runner = Runner(
        cluster, client, "admission.k8s.gatekeeper.sh",
        operations=("webhook",), readyz_port=0, fleet=False,
    )
    runner.start()
    try:
        assert runner.wait_ready(10)
        url = f"http://127.0.0.1:{runner.readyz_port}/readyz"
        with urllib.request.urlopen(url, timeout=5) as resp:
            doc = json.loads(resp.read())
        assert doc["ready"] is True
        assert doc["stats"]["draining"] is False
        runner.webhook.begin_drain()
        try:
            with urllib.request.urlopen(url, timeout=5) as resp:
                code, doc = resp.getcode(), json.loads(resp.read())
        except urllib.error.HTTPError as e:
            code, doc = e.code, json.loads(e.read())
        assert code == 503
        assert doc["ready"] is False
        assert doc["stats"]["draining"] is True
    finally:
        runner.stop()


def test_traceparent_propagates_across_two_fleet_replicas():
    """ISSUE 10 acceptance: a request sent with an inbound
    `traceparent` header gets that trace id in its admission response
    envelope, its denial log record, and the replica's
    `/debug/traces?trace_id=` lookup — on BOTH replicas of a fleet
    (2-replica soak build, shared FakeCluster gossip plane)."""
    import urllib.request

    from gatekeeper_tpu.metrics import serve_metrics
    from gatekeeper_tpu.soak.harness import SoakHarness

    scn = Scenario.from_dict({
        "name": "traceparent-smoke",
        "duration_s": 5.0,
        "rps": 10.0,
        "deadline_s": 0.5,
        "window_s": 1.0,
        "replicas": 2,
        "tls": False,
        "constraints": 3,
        "external_keys": 3,
    })
    harness = SoakHarness(scn)
    try:
        harness.build()
        assert len(harness.replicas) == 2
        for r_idx, rep in enumerate(harness.replicas):
            tid = f"{r_idx:02x}" + "ab" * 15  # 32-hex, per replica
            body = json.dumps({
                "apiVersion": "admission.k8s.io/v1",
                "kind": "AdmissionReview",
                "request": {
                    "uid": f"tp-{r_idx}",
                    "kind": {"group": "", "version": "v1",
                             "kind": "Pod"},
                    "operation": "CREATE",
                    "name": f"tp-pod-{r_idx}",
                    "namespace": "default",
                    "userInfo": {"username": "soak"},
                    "object": {
                        "apiVersion": "v1",
                        "kind": "Pod",
                        "metadata": {"name": f"tp-pod-{r_idx}",
                                     "namespace": "default"},
                        "spec": {"containers": [{
                            "name": "c",
                            "image": "reg.example/app0",
                            # privileged => SoakPrivileged denies
                            "securityContext": {"privileged": True},
                        }]},
                    },
                },
            }).encode()
            req = urllib.request.Request(
                rep.base_url + "/v1/admit",
                data=body,
                headers={
                    "Content-Type": "application/json",
                    "traceparent": f"00-{tid}-00f067aa0ba902b7-01",
                },
                method="POST",
            )
            with urllib.request.urlopen(req, timeout=30) as resp:
                doc = json.loads(resp.read())
            # denial + envelope echo on THIS replica
            assert doc["response"]["allowed"] is False
            assert doc["traceId"] == tid
            denied = rep.server.handler.denied_log
            assert denied and denied[-1]["trace_id"] == tid
            # /debug/traces?trace_id= lookup on the replica's metrics
            # plane finds the whole span tree under the inbound id
            httpd = serve_metrics(rep.metrics, port=0,
                                  tracer=rep.tracer)
            try:
                port = httpd.server_address[1]
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/debug/traces"
                    f"?trace_id={tid}",
                    timeout=5,
                ) as r:
                    traces = json.loads(r.read())["traces"]
                assert traces and traces[0]["trace_id"] == tid
                names = {
                    s["name"] for s in traces[0]["spans"]
                }
                assert "handler" in names
            finally:
                httpd.shutdown()
    finally:
        harness.stop()
