"""Verdict-integrity plane: parity + chaos battery
(docs/robustness.md §Verdict integrity).

What it pins:
  * the **canary parity battery** — canary packing + stripping leaves
    merged verdicts byte-identical to a canary-free run across
    VECTORIZED / PARTIAL_ROWS / INTERPRETER templates (the canaries DO
    ride the dispatch: the plane's batch counters prove it);
  * an injected device bit-flip (`integrity.canary[device=N]`) trips
    `PartitionDispatcher` quarantine with reason `corruption`, the plan
    re-homes, healthy devices keep serving fused;
  * the golden self-test heals ONLY a clean device
    (`integrity.selftest` keeps a still-corrupting one out);
  * warm-swap rejects on golden mismatch
    (`program_swap_rejected_total{reason="golden_mismatch"}`);
  * shadow-oracle sampling is CRC(trace_id)-deterministic across
    replicas; a divergence burst produces exactly ONE flight record
    (debounce) while every divergence keeps its decision record;
  * the chaos e2e on a real Runner: bit-flip -> detected ->
    quarantined(corruption) -> re-homed -> self-test healed, with
    `/debug/integrity` and the flight record retrievable over HTTP.

Runs in tier-1 (numpy-mode TpuDriver: no jit compiles, deterministic)
and the `integrity`/`chaos` marker lanes.
"""

import json
import time
import urllib.request

import pytest

from gatekeeper_tpu.constraint import Backend, K8sValidationTarget, TpuDriver
from gatekeeper_tpu.faults import FAULTS, device_point
from gatekeeper_tpu.integrity import (
    IntegrityPlane,
    result_digest,
    shadow_sampled,
    synth_reviews,
)
from gatekeeper_tpu.metrics import MetricsRegistry
from gatekeeper_tpu.parallel.partition import (
    PartitionDispatcher,
    build_plan,
    merge_partition_results,
)

pytestmark = [pytest.mark.chaos, pytest.mark.integrity]

TARGET = "admission.k8s.gatekeeper.sh"

V_REGO = """package intreq
violation[{"msg": msg}] {
    required := {key | key := input.parameters.labels[_]}
    provided := {key | input.review.object.metadata.labels[key]}
    missing := required - provided
    count(missing) > 0
    msg := sprintf("missing: %v", [missing])
}
"""

I_REGO = """package intdeep
violation[{"msg": msg}] {
    leaf := input.review.object.spec.l1[_].l2[_].l3[_]
    leaf == "x"
    msg := "three nested array iterations"
}
"""

P_REGO = """package intblob
violation[{"msg": msg}] {
    raw := json.marshal(input.review.object.metadata.labels)
    contains(raw, "forbidden")
    msg := "label blob contains forbidden"
}
"""

TEMPLATES = [
    ("IntReq", V_REGO, {"labels": ["owner"]}),
    ("IntDeep", I_REGO, None),
    ("IntBlob", P_REGO, None),
]


@pytest.fixture(autouse=True)
def _clean_faults():
    FAULTS.reset()
    yield
    FAULTS.reset()


def build_client(n_constraints=7):
    cl = Backend(TpuDriver(use_jax=False)).new_client(K8sValidationTarget())
    for kind, rego, _params in TEMPLATES:
        cl.add_template({
            "apiVersion": "templates.gatekeeper.sh/v1beta1",
            "kind": "ConstraintTemplate",
            "metadata": {"name": kind.lower()},
            "spec": {
                "crd": {"spec": {"names": {"kind": kind}}},
                "targets": [{"target": TARGET, "rego": rego}],
            },
        })
    for i in range(n_constraints):
        kind, _rego, params = TEMPLATES[i % len(TEMPLATES)]
        spec = {"match": {"kinds": [
            {"apiGroups": [""], "kinds": ["Pod"]}
        ]}}
        if i % 3 == 0 and kind == "IntReq":
            spec["match"]["namespaceSelector"] = {
                "matchLabels": {"team": "core"}
            }
        if params:
            spec["parameters"] = params
        cl.add_constraint({
            "apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": kind,
            "metadata": {"name": f"c{i:03d}"},
            "spec": spec,
        })
    return cl


def battery_request(i):
    labels = {}
    if i % 3 == 1:
        labels = {"owner": "a"}
    if i % 4 == 2:
        labels = {"blob": "forbidden-value"}
    spec = {"containers": [{"name": "c", "image": "nginx"}]}
    if i % 5 == 3:
        spec["l1"] = [{"l2": [{"l3": ["x", "y"]}]}]
    obj = {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {
            "name": f"p{i}",
            "namespace": f"ns-{i % 3}",
            **({"labels": labels} if labels else {}),
        },
        "spec": spec,
    }
    return {
        "uid": f"u{i}",
        "kind": {"group": "", "version": "v1", "kind": "Pod"},
        "operation": "CREATE",
        "name": f"p{i}",
        "namespace": obj["metadata"]["namespace"],
        "userInfo": {"username": "alice"},
        "object": obj,
    }


def augmented(cl, requests):
    from gatekeeper_tpu.constraint.handler import handler_for

    handler = handler_for(cl, TARGET)
    return [handler.augment_request(r) for r in requests]


def normalize(results):
    return [
        (
            r.constraint.get("kind"),
            (r.constraint.get("metadata") or {}).get("name"),
            r.msg,
        )
        for r in results
    ]


def attach_plane(cl, **kw):
    kw.setdefault("metrics", MetricsRegistry())
    plane = IntegrityPlane(**kw)
    cl._driver.set_integrity(plane)
    plane.attach_client(cl)
    return plane


def test_persisted_golden_is_keyed_by_constraint_content(tmp_path):
    """Goldens persist as program-store sidecars. A later driver that
    reaches the same constraint generation with other constraints must
    derive its own golden, not load the stale one as corruption."""
    from gatekeeper_tpu.compile import ProgramStore

    store = ProgramStore(str(tmp_path), fingerprint="fp-test")
    kind, rego, _ = TEMPLATES[0]

    def serve(labels):
        cl = Backend(TpuDriver()).new_client(K8sValidationTarget())
        cl.add_template({
            "apiVersion": "templates.gatekeeper.sh/v1beta1",
            "kind": "ConstraintTemplate",
            "metadata": {"name": kind.lower()},
            "spec": {
                "crd": {"spec": {"names": {"kind": kind}}},
                "targets": [{"target": TARGET, "rego": rego}],
            },
        })
        for i in range(3):
            cl.add_constraint({
                "apiVersion": "constraints.gatekeeper.sh/v1beta1",
                "kind": kind,
                "metadata": {"name": f"c{i}"},
                "spec": {"parameters": {"labels": labels}},
            })
        plane = attach_plane(cl, store=store)
        reviews = augmented(cl, [battery_request(i) for i in range(16)])
        assert cl.warm_review_path(reviews)
        cl.review_many(reviews)
        assert plane.canary_batches >= 1
        return plane

    assert serve(["owner"]).canary_mismatch_batches == 0
    assert serve(["team"]).canary_mismatch_batches == 0


# -- canary synthesis ---------------------------------------------------------


def test_synth_reviews_deterministic_and_violating():
    cl = build_client(6)
    drv = cl._driver
    constraints = drv._constraints(TARGET)
    a = synth_reviews(constraints, 4)
    b = synth_reviews(constraints, 4)
    assert a == b  # same constraints -> byte-identical canaries
    # at least one canary must actually VIOLATE something: an
    # all-empty golden set cannot catch suppressed violations
    interp = drv._interp_closure(TARGET, constraints)
    empty = result_digest([])
    digests = [result_digest(interp(r)) for r in a]
    assert any(d != empty for d in digests)


def test_result_digest_order_insensitive():
    cl = build_client(5)
    reviews = augmented(cl, [battery_request(2)])
    results = cl.review_many(reviews)[0].by_target[TARGET].results
    assert len(results) >= 2
    assert result_digest(results) == result_digest(list(reversed(results)))
    assert result_digest(results) != result_digest(results[:-1])


# -- the canary parity battery ------------------------------------------------


@pytest.mark.parametrize("n_constraints,k", [(4, 2), (7, 3), (17, 4)])
def test_canary_parity_battery(n_constraints, k):
    """Canary packing + stripping changes no live verdict byte: merged
    partitioned results with the integrity plane attached are identical
    to both the canary-free monolith AND a canary-free partitioned run
    — across VECTORIZED / PARTIAL_ROWS / INTERPRETER templates,
    autorejecting constraints, and all partition subsets."""
    bare = build_client(n_constraints)
    cl = build_client(n_constraints)
    plane = attach_plane(cl)
    keys = cl._driver.constraint_keys(TARGET)
    plan = build_plan(keys, k, range(k), frozenset(range(k)))
    requests = [battery_request(i) for i in range(23)]
    reviews = augmented(cl, requests)
    bare_reviews = augmented(bare, requests)
    mono = bare.review_many(bare_reviews)
    per_part = [
        cl.review_many_subset(reviews, p.subset, device=p.device)
        for p in plan.partitions
    ]
    bare_part = [
        bare.review_many_subset(bare_reviews, p.subset, device=p.device)
        for p in plan.partitions
    ]
    some_results = False
    for i in range(len(reviews)):
        merged = merge_partition_results(
            [
                (pp[i].by_target[TARGET].results
                 if TARGET in pp[i].by_target else [])
                for pp in per_part
            ],
            plan.order,
        )
        bare_merged = merge_partition_results(
            [
                (pp[i].by_target[TARGET].results
                 if TARGET in pp[i].by_target else [])
                for pp in bare_part
            ],
            plan.order,
        )
        expect = (
            mono[i].by_target[TARGET].results
            if TARGET in mono[i].by_target else []
        )
        assert normalize(merged) == normalize(expect), f"request {i}"
        assert normalize(merged) == normalize(bare_merged), f"request {i}"
        some_results = some_results or bool(expect)
    assert some_results
    # the battery must not pass vacuously: canaries actually rode along
    assert plane.canary_batches > 0 and plane.canary_rows > 0
    assert plane.canary_mismatch_batches == 0


# -- bit-flip -> corruption quarantine -> self-test heal ----------------------


def test_bitflip_trips_corruption_quarantine_and_selftest_heals():
    cl = build_client(9)
    metrics = MetricsRegistry()
    disp = PartitionDispatcher(cl, TARGET, k=3, metrics=metrics)
    plane = attach_plane(cl, metrics=metrics, quarantine_threshold=2)
    plane.attach_dispatcher(disp)
    plan = disp.plan()
    assert plan is not None and len(plan.partitions) == 3
    reviews = augmented(cl, [battery_request(i) for i in range(6)])
    sick = plan.partitions[1]

    # device 1's canaries bit-flip on every dispatch
    FAULTS.arm(device_point("integrity.canary", sick.device), mode="error")
    for _ in range(2):
        cl.review_many_subset(reviews, sick.subset, device=sick.device)
    snap = disp.snapshot()
    assert sick.device in snap["manual_quarantine"]
    assert snap["quarantine_reasons"][str(sick.device)] == "corruption"
    # re-home: the rebuilt plan moves the sick device's partitions
    replan = disp.plan()
    assert all(p.device != sick.device for p in replan.partitions)
    assert str(sick.device) in plane.snapshot()["quarantined"]

    # healthy devices keep serving fused, no ledger entries for them
    healthy = plan.partitions[0]
    out = cl.review_many_subset(reviews, healthy.subset,
                                device=healthy.device)
    assert len(out) == len(reviews)
    assert plane.snapshot()["canary"]["per_device"].get(
        str(healthy.device), {}
    ).get("consecutive", 0) == 0

    # a still-corrupting device fails its self-test and stays out
    FAULTS.arm(
        device_point("integrity.selftest", sick.device), mode="error"
    )
    assert plane.selftest(sick.device) is False
    assert sick.device in disp.snapshot()["manual_quarantine"]

    # clean hardware: golden batch replays clean -> heal
    FAULTS.reset()
    assert plane.selftest(sick.device) is True
    snap = disp.snapshot()
    assert sick.device not in snap["manual_quarantine"]
    assert snap["quarantine_reasons"] == {}
    healed = disp.plan()
    assert any(p.device == sick.device for p in healed.partitions)
    assert plane.snapshot()["selftest"] == {
        "pass": 1, "fail": 1,
        "interval_s": plane.selftest_interval_s,
    }


def test_canary_mismatch_below_threshold_does_not_quarantine():
    cl = build_client(6)
    disp = PartitionDispatcher(cl, TARGET, k=2, metrics=MetricsRegistry())
    plane = attach_plane(cl, quarantine_threshold=3)
    plane.attach_dispatcher(disp)
    plan = disp.plan()
    p = plan.partitions[0]
    reviews = augmented(cl, [battery_request(i) for i in range(4)])
    FAULTS.arm(device_point("integrity.canary", p.device), mode="error",
               count=2)
    for _ in range(3):  # 2 mismatching batches, then a clean one
        cl.review_many_subset(reviews, p.subset, device=p.device)
    snap = disp.snapshot()
    assert p.device not in snap["manual_quarantine"]
    # the clean batch reset the consecutive counter
    assert plane.snapshot()["canary"]["per_device"][str(p.device)][
        "consecutive"
    ] == 0


# -- warm-swap golden gate ----------------------------------------------------


def test_swap_gate_rejects_on_golden_mismatch():
    cl = build_client(6)
    metrics = MetricsRegistry()
    cl._driver.set_metrics(metrics)
    attach_plane(cl, metrics=metrics)
    keys = cl._driver.constraint_keys(TARGET)
    subset = frozenset(keys[:3])

    FAULTS.arm("integrity.selftest", mode="error", count=1)
    assert cl.prepare_subset(subset, device=0) is False
    counters = metrics.snapshot()["counters"]
    rejected = {
        k: v for k, v in counters.items()
        if k.startswith("program_swap_rejected_total")
        and 'reason="golden_mismatch"' in k
    }
    assert sum(rejected.values()) == 1, counters
    # the old (here: absent) program keeps serving; a clean retry swaps
    assert cl.prepare_subset(subset, device=0) is True


# -- shadow oracle ------------------------------------------------------------


def test_shadow_sampling_crc_deterministic_across_replicas():
    ids = [f"trace-{i:04d}" for i in range(400)]
    a = {t for t in ids if shadow_sampled(t, 8)}
    b = {t for t in ids if shadow_sampled(t, 8)}
    assert a == b  # same decision on every replica
    assert 0 < len(a) < len(ids)
    import zlib

    for t in ids:
        assert shadow_sampled(t, 8) == (
            zlib.crc32(t.encode()) % 8 == 0
        )
    assert not shadow_sampled(None, 8)
    assert not shadow_sampled("x", 0)


def test_shadow_divergence_decisions_and_one_flight_record_per_burst():
    from gatekeeper_tpu.obs import DecisionLog, FlightRecorder

    cl = build_client(5)
    decisions = DecisionLog()
    recorder = FlightRecorder(
        decisions=decisions, debounce_s=0.05, min_interval_s=60.0
    )
    try:
        plane = attach_plane(
            cl, decisions=decisions, recorder=recorder, shadow_sample_n=1
        )
        reviews = augmented(cl, [battery_request(i) for i in range(6)])
        live = [
            r.by_target[TARGET].results for r in cl.review_many(reviews)
        ]
        # the oracle itself is bit-flipped: every sampled admission
        # diverges (a corrupting-device model without a device)
        FAULTS.arm("integrity.shadow", mode="error")
        for i, (rv, res) in enumerate(zip(reviews, live)):
            assert plane.note_live(f"t{i}", rv, res) is True
        plane.drain_shadow()
        assert plane.shadow_divergences == len(reviews)
        # every divergence keeps a typed decision record...
        divergent = decisions.records(
            verdict="verdict_divergence", limit=100
        )
        assert len(divergent) == len(reviews)
        # ...but the burst coalesces into exactly ONE flight record
        deadline = time.monotonic() + 5.0
        records = []
        while time.monotonic() < deadline:
            records = [
                r for r in recorder.records()
                if any(
                    t.get("reason") == "verdict_divergence"
                    for t in r.get("triggers", [])
                )
            ]
            if records:
                break
            time.sleep(0.02)
        assert len(records) == 1, records
    finally:
        recorder.stop()


def test_shadow_clean_path_no_divergence():
    cl = build_client(5)
    plane = attach_plane(cl, shadow_sample_n=1)
    reviews = augmented(cl, [battery_request(i) for i in range(4)])
    live = [r.by_target[TARGET].results for r in cl.review_many(reviews)]
    for i, (rv, res) in enumerate(zip(reviews, live)):
        plane.note_live(f"t{i}", rv, res)
    plane.drain_shadow()
    assert plane.shadow_divergences == 0
    assert plane.shadow_sampled_n == len(reviews)


# -- the chaos e2e ------------------------------------------------------------


def _http_json(url):
    with urllib.request.urlopen(url, timeout=5) as resp:
        return json.loads(resp.read())


def test_integrity_e2e_bitflip_quarantine_heal_over_http():
    """The acceptance e2e on a real Runner: an injected device bit-flip
    is detected by the canary tier, quarantined with reason
    `corruption`, re-homed, and golden-self-test healed — with
    /debug/integrity, /readyz stats.integrity, and the
    verdict_divergence flight record all retrievable over HTTP."""
    from gatekeeper_tpu.control import FakeCluster, Runner

    cl = build_client(9)
    plane = IntegrityPlane(quarantine_threshold=2, shadow_sample_n=1)
    runner = Runner(
        FakeCluster(), cl, TARGET,
        audit_interval=3600.0, readyz_port=0, partitions=3,
        integrity=plane,
    )
    runner.start()
    try:
        assert runner.wait_ready(30), runner.tracker.stats()
        handler = runner.webhook.handler
        base = f"http://127.0.0.1:{runner.readyz_port}"

        for i in range(8):
            handler.handle(battery_request(i))
        clean = _http_json(f"{base}/debug/integrity")
        assert clean["canary"]["batches"] > 0
        assert clean["quarantined"] == {}

        # find a device actually serving partitions, then flip its bits
        plan = runner.webhook.partitioner.plan()
        sick = plan.partitions[0].device
        FAULTS.arm(device_point("integrity.canary", sick), mode="error")
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            for i in range(4):
                handler.handle(battery_request(100 + i))
            snap = _http_json(f"{base}/debug/integrity")
            if str(sick) in snap["quarantined"]:
                break
        snap = _http_json(f"{base}/debug/integrity")
        assert snap["quarantined"][str(sick)]["reason"] == "corruption"
        part = runner.webhook.partitioner.snapshot()
        assert part["quarantine_reasons"][str(sick)] == "corruption"
        # re-homed: live plan serves entirely off the sick device,
        # and admissions still answer (healthy devices keep serving)
        replan = runner.webhook.partitioner.plan()
        assert all(p.device != sick for p in replan.partitions)
        resp = handler.handle(battery_request(200))
        assert resp.allowed in (True, False)
        ready = _http_json(f"{base}/readyz")
        assert str(sick) in ready["stats"]["integrity"]["quarantined"]

        # heal: disarm, golden self-test replays clean
        FAULTS.reset()
        assert plane.selftest(sick) is True
        healed = _http_json(f"{base}/debug/integrity")
        assert healed["quarantined"] == {}
        assert runner.webhook.partitioner.snapshot()[
            "manual_quarantine"
        ] == []

        # shadow tier: an injected oracle divergence lands ONE flight
        # record, retrievable over HTTP with its repro bundle. The
        # recorder's min_interval rate limit may still be absorbing
        # the quarantine capture above, so keep sending fresh sampled
        # traffic until a divergence capture lands (the debounce
        # coalesces each burst; suppressed bursts are re-triggered by
        # the next one).
        FAULTS.arm("integrity.shadow", mode="error")
        deadline = time.monotonic() + 20.0
        flights = []
        i = 0
        while time.monotonic() < deadline and not flights:
            for _ in range(4):
                handler.handle(battery_request(300 + i))
                i += 1
            plane.drain_shadow()
            flights = [
                r
                for r in _http_json(
                    f"{base}/debug/flightrecords"
                )["records"]
                if any(
                    t.get("reason") == "verdict_divergence"
                    for t in r.get("triggers", [])
                )
            ]
            if not flights:
                time.sleep(0.25)
        FAULTS.reset()
        assert plane.shadow_divergences > 0
        assert len(flights) == 1, flights
        trig = [
            t for t in flights[0]["triggers"]
            if t.get("reason") == "verdict_divergence"
        ][0]
        ctx = trig.get("context", trig)
        assert ctx.get("live_digest") and ctx.get("oracle_digest")
        assert ctx.get("review")  # the repro bundle rides the record
    finally:
        FAULTS.reset()
        runner.stop()


# -- the analysis canary-derivability gate (GK-I0xx) -------------------------


def _repo_policies():
    import os

    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "deploy",
        "policies",
    )


def test_analysis_canary_gate_deploy_policies_clean():
    """Every shipped template — both targets, external-data included —
    derives a violating canary set, so the `analysis all` gate holds."""
    from gatekeeper_tpu.analysis.cli import run_canary

    assert run_canary([_repo_policies()]) == 0


def test_analysis_canary_gate_flags_underivable_template():
    """A template no canary can convict (its rego keys on a field the
    synthesis never writes) fails with GK-I001 — not silently passed."""
    from gatekeeper_tpu.analysis.canarygate import canary_lints

    doc = {
        "apiVersion": "templates.gatekeeper.sh/v1beta1",
        "kind": "ConstraintTemplate",
        "metadata": {"name": "k8sneverfires"},
        "spec": {
            "crd": {"spec": {"names": {"kind": "K8sNeverFires"}}},
            "targets": [
                {
                    "target": TARGET,
                    "rego": (
                        "package k8sneverfires\n"
                        'violation[{"msg": "no"}] {\n'
                        '  input.review.object.spec.noSuchField == "x"\n'
                        "}\n"
                    ),
                }
            ],
        },
    }
    lints = canary_lints([("mem://t.yaml", doc)], [], [])
    assert len(lints) == 1
    assert lints[0].codes == ["GK-I001"]
    assert lints[0].violating == 0


def test_analysis_canary_gate_stubs_external_data():
    """An external-data template with an UNDECLARED provider still
    derives: the gate synthesizes a stub Provider and pins responses
    (error entries for bad-keyed lookups) instead of skipping it."""
    from gatekeeper_tpu.analysis.canarygate import canary_lints

    doc = {
        "apiVersion": "templates.gatekeeper.sh/v1beta1",
        "kind": "ConstraintTemplate",
        "metadata": {"name": "k8scanaryexternal"},
        "spec": {
            "crd": {"spec": {"names": {"kind": "K8sCanaryExternal"}}},
            "targets": [
                {
                    "target": TARGET,
                    "rego": (
                        "package k8scanaryexternal\n"
                        'violation[{"msg": msg}] {\n'
                        "  images := [img | img := input.review.object"
                        ".spec.containers[_].image]\n"
                        '  response := external_data({"provider": '
                        '"nowhere-registry", "keys": images})\n'
                        "  count(response.errors) > 0\n"
                        '  msg := sprintf("denied: %v", '
                        "[response.errors])\n"
                        "}\n"
                    ),
                }
            ],
        },
    }
    lints = canary_lints([("mem://t.yaml", doc)], [], [])
    assert len(lints) == 1
    lint = lints[0]
    assert lint.external_data
    assert lint.providers == ["nowhere-registry"]
    # `:latest` canary images answer with pinned error entries, so the
    # error-gated template convicts without any network
    assert lint.ok, lint.render()
    assert lint.violating > 0


def test_analysis_canary_gate_covers_agent_target():
    """Agent-action templates derive through synth_agent_reviews with
    schema-mined default constraints — the second target is gated too."""
    from gatekeeper_tpu.analysis.canarygate import canary_lints

    doc = {
        "apiVersion": "templates.gatekeeper.sh/v1beta1",
        "kind": "ConstraintTemplate",
        "metadata": {"name": "agentcanaryargs"},
        "spec": {
            "crd": {
                "spec": {
                    "names": {"kind": "AgentCanaryArgs"},
                    "validation": {
                        "openAPIV3Schema": {
                            "properties": {
                                "required": {
                                    "type": "array",
                                    "items": {"type": "string"},
                                }
                            }
                        }
                    },
                }
            },
            "targets": [
                {
                    "target": "agent.action.gatekeeper.sh",
                    "rego": (
                        "package agentcanaryargs\n"
                        'violation[{"msg": "missing"}] {\n'
                        "  required := {a | a := input.parameters"
                        ".required[_]}\n"
                        "  present := {a | input.review.object.spec"
                        ".arguments[a]}\n"
                        "  count(required - present) > 0\n"
                        "}\n"
                    ),
                }
            ],
        },
    }
    lints = canary_lints([("mem://agent.yaml", doc)], [], [])
    assert len(lints) == 1
    assert lints[0].ok, lints[0].render()
    assert lints[0].violating > 0
