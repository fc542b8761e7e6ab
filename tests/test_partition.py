"""Device fault domains: the partition plane's unit + parity suite
(docs/robustness.md §Fault domains).

What it pins:
  * `PartitionPlan` determinism — same corpus, same plan; balanced
    round-robin split; deterministic rebalance on constraint churn and
    re-homing on quarantine (restored on heal; all-dead flagged);
  * the **partition parity battery** — merged partitioned verdicts are
    identical to the monolithic dispatch across constraint counts,
    partition counts, and template mixes (VECTORIZED + PARTIAL_ROWS +
    INTERPRETER verdicts, autorejecting constraints, and G_CAP-overflow
    requests that route per-row to the interpreter);
  * per-(device, plane) breakers — lazily created, named
    `device:<plane>:<device_id>`, snapshotted by name, registered with
    the fleet plane under the same key;
  * restage backoff through the `driver.restage[device=N]` fault point.

Runs in the chaos lane (`pytest -m chaos`) and tier-1 (numpy-mode
TpuDriver: no jit compiles, deterministic).
"""

import pytest

from gatekeeper_tpu.constraint import Backend, K8sValidationTarget, TpuDriver
from gatekeeper_tpu.constraint.driver import constraint_key
from gatekeeper_tpu.faults import CLOSED, FAULTS, device_point
from gatekeeper_tpu.metrics import MetricsRegistry
from gatekeeper_tpu.parallel.partition import (
    PartitionDispatcher,
    build_plan,
    merge_partition_results,
)

pytestmark = pytest.mark.chaos

TARGET = "admission.k8s.gatekeeper.sh"

# VECTORIZED: the required-labels shape the compiler fully fuses
V_REGO = """package partreq
violation[{"msg": msg}] {
    required := {key | key := input.parameters.labels[_]}
    provided := {key | input.review.object.metadata.labels[key]}
    missing := required - provided
    count(missing) > 0
    msg := sprintf("missing: %v", [missing])
}
"""

# INTERPRETER verdict (GK-V003): three nested array iterations
I_REGO = """package partdeep
violation[{"msg": msg}] {
    leaf := input.review.object.spec.l1[_].l2[_].l3[_]
    leaf == "x"
    msg := "three nested array iterations"
}
"""

# PARTIAL_ROWS verdict (GK-V001): json.marshal screen
P_REGO = """package partblob
violation[{"msg": msg}] {
    raw := json.marshal(input.review.object.metadata.labels)
    contains(raw, "forbidden")
    msg := "label blob contains forbidden"
}
"""

TEMPLATES = [
    ("PartReq", V_REGO, {"labels": ["owner"]}),
    ("PartDeep", I_REGO, None),
    ("PartBlob", P_REGO, None),
]


@pytest.fixture(autouse=True)
def _clean_faults():
    FAULTS.reset()
    yield
    FAULTS.reset()


def build_battery_client(n_constraints):
    """Mixed-verdict corpus: constraints cycle over the three template
    kinds; every third PartReq constraint carries a namespaceSelector
    (needs-context -> autoreject coverage on uncached namespaces)."""
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
        if i % 3 == 0 and kind == "PartReq":
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
    """Shape variety: labeled/unlabeled, deep l1/l2/l3 fanout hits for
    PartDeep, forbidden label blobs for PartBlob, and a G_CAP-overflow
    pod (70 containers) that routes per-row to the interpreter."""
    labels = {}
    if i % 3 == 1:
        labels = {"owner": "a"}
    if i % 4 == 2:
        labels = {"blob": "forbidden-value"}
    spec = {"containers": [{"name": "c", "image": "nginx"}]}
    if i % 5 == 3:
        spec["l1"] = [{"l2": [{"l3": ["x", "y"]}]}]
    if i % 7 == 4:
        spec = {"containers": [
            {"name": f"c{j}", "image": "nginx"} for j in range(70)
        ]}
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


# -- device-labeled fault points ----------------------------------------------


def test_device_point_env_string_activation():
    """`driver.device_dispatch[device=1]=error:count=5` must arm even
    though the point name contains '=': the env grammar anchors on the
    first '=' followed by a known mode, not the first '=' in the
    entry."""
    from gatekeeper_tpu.faults import FaultRegistry, configure_from_env

    reg = FaultRegistry()
    armed = configure_from_env(
        reg,
        env=(
            "driver.device_dispatch[device=1]=error:count=5,"
            "driver.restage[device=3]=hang:delay=0.25,"
            "driver.device_dispatch=error"
        ),
    )
    assert armed == 3
    spec = reg.spec(device_point("driver.device_dispatch", 1))
    assert spec is not None and spec.count == 5
    spec = reg.spec(device_point("driver.restage", 3))
    assert spec is not None and spec.mode == "hang"
    assert spec.delay_s == 0.25
    assert reg.spec("driver.device_dispatch").mode == "error"
    # labeled points are independent of the unlabeled plane point
    assert reg.spec(device_point("driver.device_dispatch", 2)) is None


# -- the plan -----------------------------------------------------------------


def test_plan_deterministic_and_balanced():
    keys = [f"Kind/{chr(97 + i)}" for i in range(17)]
    healthy = frozenset(range(4))
    p1 = build_plan(keys, 4, range(4), healthy)
    p2 = build_plan(keys, 4, range(4), healthy)
    assert [p.keys for p in p1.partitions] == [p.keys for p in p2.partitions]
    assert [p.device for p in p1.partitions] == [
        p.device for p in p2.partitions
    ]
    sizes = [len(p.keys) for p in p1.partitions]
    assert max(sizes) - min(sizes) <= 1  # balanced round-robin
    # every key lands in exactly one partition
    seen = [k for p in p1.partitions for k in p.keys]
    assert sorted(seen) == sorted(keys)
    # churn: a new key rebalances deterministically
    p3 = build_plan(keys + ["Kind/zz"], 4, range(4), healthy)
    assert sorted(
        k for p in p3.partitions for k in p.keys
    ) == sorted(keys + ["Kind/zz"])


def test_plan_rehomes_on_quarantine_and_flags_all_dead():
    keys = [f"K/{i}" for i in range(8)]
    sick1 = build_plan(keys, 4, range(4), frozenset({0, 2, 3}))
    assert not sick1.all_dead
    for p in sick1.partitions:
        if p.home_device == 1:
            assert p.device in (0, 2, 3)  # re-homed
        else:
            assert p.device == p.home_device  # untouched
    dead = build_plan(keys, 4, range(4), frozenset())
    assert dead.all_dead
    healed = build_plan(keys, 4, range(4), frozenset(range(4)))
    assert all(p.device == p.home_device for p in healed.partitions)


def test_plan_fewer_constraints_than_partitions():
    plan = build_plan(["A/x"], 4, range(4), frozenset(range(4)))
    assert len(plan.partitions) == 1
    assert plan.partitions[0].keys == ("A/x",)
    empty = build_plan([], 4, range(4), frozenset(range(4)))
    assert empty.partitions == []


# -- the parity battery -------------------------------------------------------


@pytest.mark.parametrize("n_constraints,k", [
    (1, 1), (1, 4), (4, 2), (7, 3), (17, 4), (17, 7),
])
def test_partition_parity_battery(n_constraints, k):
    """Merged partitioned verdicts == monolithic verdicts, request by
    request — order included (autorejects first, then evaluation
    results, in global constraint order) — across VECTORIZED /
    PARTIAL_ROWS / INTERPRETER templates, needs-context constraints,
    and overflow rows."""
    cl = build_battery_client(n_constraints)
    driver = cl._driver
    keys = driver.constraint_keys(TARGET)
    assert len(keys) == n_constraints
    plan = build_plan(keys, k, range(k), frozenset(range(k)))
    reviews = augmented(cl, [battery_request(i) for i in range(23)])
    mono = cl.review_many(reviews)
    per_part = [
        cl.review_many_subset(reviews, p.subset, device=p.device)
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
        expect = (
            mono[i].by_target[TARGET].results
            if TARGET in mono[i].by_target else []
        )
        assert normalize(merged) == normalize(expect), f"request {i}"
        some_results = some_results or bool(expect)
    assert some_results  # the battery must not pass vacuously


def test_partition_match_mask_scopes_subsets():
    cl = build_battery_client(6)
    driver = cl._driver
    keys = driver.constraint_keys(TARGET)
    reviews = augmented(cl, [battery_request(i) for i in range(6)])
    # one subset per constraint: the mask for a PartDeep-only subset
    # must clear requests with no deep structure and no autoreject path
    masks = cl.partition_match_mask(
        reviews, [frozenset([key]) for key in keys]
    )
    assert len(masks) == len(keys)
    assert all(len(m) == len(reviews) for m in masks)
    # whole-corpus subset: every request matches something (Pod kinds)
    full = cl.partition_match_mask(reviews, [frozenset(keys)])
    assert all(full[0])


def test_host_subset_scoped_to_partition():
    cl = build_battery_client(6)
    keys = cl._driver.constraint_keys(TARGET)
    reviews = augmented(cl, [battery_request(1)])  # labeled, no blob
    full = cl.review_host(reviews[0])
    sub = cl.review_host(reviews[0], subset=frozenset(keys[:2]))
    full_keys = {
        constraint_key(r.constraint)
        for r in full.by_target[TARGET].results
    }
    sub_keys = {
        constraint_key(r.constraint)
        for r in sub.by_target[TARGET].results
    }
    assert sub_keys <= set(keys[:2])
    assert sub_keys == {k for k in full_keys if k in set(keys[:2])}


# -- the dispatcher -----------------------------------------------------------


def test_dispatcher_breakers_named_per_device_and_fleet_registered():
    cl = build_battery_client(4)
    metrics = MetricsRegistry()
    disp = PartitionDispatcher(
        cl, TARGET, k=4, metrics=metrics, plane="validation"
    )

    class _Fleet:
        def __init__(self):
            self.registered = {}

        def register_breaker(self, name, breaker):
            self.registered[name] = breaker

    fleet = _Fleet()
    b1 = disp.breaker(1)
    disp.set_fleet(fleet)  # existing breakers register
    b3 = disp.breaker(3)  # future breakers register on creation
    assert b1.name == "device:validation:1"
    assert b3.name == "device:validation:3"
    assert set(fleet.registered) == {
        "device:validation:1", "device:validation:3",
    }
    snap = disp.snapshot()
    assert set(snap["breakers"]) == {
        "device:validation:1", "device:validation:3",
    }
    assert snap["breakers"]["device:validation:1"]["name"] == (
        "device:validation:1"
    )
    # per-device gauge series exist side by side
    gauges = metrics.snapshot()["gauges"]
    assert gauges.get(
        'device_breaker_state{device="1",plane="validation"}'
    ) == 0
    assert gauges.get(
        'device_breaker_state{device="3",plane="validation"}'
    ) == 0
    disp.close()


def test_device_breakers_gossip_across_fleet():
    """Per-device breaker state is a fleet property: a chip sick on one
    replica (its device:validation:<id> breaker OPEN) pre-opens the
    SAME device's breaker on a peer replica to HALF_OPEN via FleetState
    gossip — one probe instead of rediscovering the outage — while
    every other device's breaker stays CLOSED."""
    from gatekeeper_tpu.control.events import FakeCluster
    from gatekeeper_tpu.faults import HALF_OPEN, OPEN
    from gatekeeper_tpu.fleet import FleetPlane

    cluster = FakeCluster()
    cl_a = build_battery_client(4)
    cl_b = build_battery_client(4)
    plane_a = FleetPlane(cluster, "rep-a", publish_interval_s=0.01)
    plane_b = FleetPlane(cluster, "rep-b", publish_interval_s=0.01)
    disp_a = PartitionDispatcher(cl_a, TARGET, k=4)
    disp_b = PartitionDispatcher(cl_b, TARGET, k=4)
    disp_a.set_fleet(plane_a)
    disp_b.set_fleet(plane_b)
    # both replicas know the same device ids (breakers created lazily)
    for d in range(4):
        disp_a.breaker(d)
        disp_b.breaker(d)
    plane_a.start()
    plane_b.start()
    try:
        for _ in range(3):
            disp_a.breaker(1).record_failure()
        assert disp_a.breaker(1).state == OPEN
        import time as _t

        deadline = _t.monotonic() + 5.0
        while (
            disp_b.breaker(1).state != HALF_OPEN
            and _t.monotonic() < deadline
        ):
            _t.sleep(0.02)
        assert disp_b.breaker(1).state == HALF_OPEN  # adopted the trip
        for d in (0, 2, 3):
            assert disp_b.breaker(d).state == CLOSED  # untouched
        # the registered names surface in stats.fleet
        assert "device:validation:1" in plane_b.snapshot()["breakers"]
    finally:
        plane_a.stop()
        plane_b.stop()
        disp_a.close()
        disp_b.close()


def test_dispatcher_plan_rebuilds_on_churn_and_quarantine():
    cl = build_battery_client(8)
    disp = PartitionDispatcher(cl, TARGET, k=4)
    plan1 = disp.plan()
    assert plan1 is not None and len(plan1.partitions) == 4
    assert disp.plan() is plan1  # cached while nothing changed
    # constraint churn rebuilds deterministically
    cl.add_constraint({
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "PartReq",
        "metadata": {"name": "churn"},
        "spec": {"parameters": {"labels": ["owner"]}},
    })
    plan2 = disp.plan()
    assert plan2 is not plan1
    assert sum(len(p.keys) for p in plan2.partitions) == 9
    # manual quarantine re-homes; heal restores
    disp.quarantine(2)
    plan3 = disp.plan()
    moved = [p for p in plan3.partitions if p.home_device == 2]
    assert moved and all(p.device != 2 for p in moved)
    assert disp.rehomes >= 1
    disp.heal(2)
    plan4 = disp.plan()
    assert all(p.device == p.home_device for p in plan4.partitions)
    disp.close()


def test_restage_fault_backs_off_then_recovers():
    clock = [0.0]
    cl = build_battery_client(4)
    disp = PartitionDispatcher(
        cl, TARGET, k=4, clock=lambda: clock[0],
        restage_backoff_s=1.0, metrics=MetricsRegistry(),
    )
    plan = disp.plan()
    part = plan.partitions[1]
    FAULTS.arm(device_point("driver.restage", part.device), mode="error",
               count=1)
    assert not disp.ensure_staged(part)  # fault: backoff armed
    assert disp.restage_failures == 1
    assert not disp.ensure_staged(part)  # inside backoff: no attempt
    assert FAULTS.hits(device_point("driver.restage", part.device)) == 1
    clock[0] = 1.5  # backoff elapsed; fault count exhausted
    assert disp.ensure_staged(part)
    assert disp.ensure_staged(part)  # cached staged token
    disp.close()


def test_all_dead_plan_flag():
    cl = build_battery_client(4)
    disp = PartitionDispatcher(cl, TARGET, k=2)
    disp.quarantine(0)
    disp.quarantine(1)
    plan = disp.plan()
    assert plan.all_dead
    disp.heal(0)
    assert not disp.plan().all_dead
    disp.close()


# -- batch-aware pruned dispatch (mask-gated skipping + guided planner) -------
#
# The `pruning` marker's home: parity of the mask-sliced fast path
# against the monolith across skip combinations (none / some / all
# partitions skipped, autoreject + needs-context rows, G_CAP-overflow
# rows), the decision-log facts a skipped partition must report, and
# the cost/locality-guided planner's co-location + balance contract —
# all on the numpy driver, tier-1 safe (no device, no jit).

pruned = pytest.mark.pruning

AFFINE_NAMESPACES = ("ns-hot", "ns-cold")


def counter(metrics, name, **tags):
    snap = metrics.snapshot()["counters"]
    total = 0
    for key, v in snap.items():
        if not key.startswith(name):
            continue
        if all(f'{k}="{val}"' in key for k, val in tags.items()):
            total += v
    return total


def build_affine_client(n_per_ns=3):
    """Namespace-affine corpus: `n_per_ns` required-labels constraints
    per namespace group (identical match blocks within a group -> one
    locality token each -> the guided planner co-locates them), plus
    one needs-context constraint (namespaceSelector -> autoreject on
    uncached namespaces) in its own locality group."""
    cl = Backend(TpuDriver(use_jax=False)).new_client(K8sValidationTarget())
    cl.add_template({
        "apiVersion": "templates.gatekeeper.sh/v1beta1",
        "kind": "ConstraintTemplate",
        "metadata": {"name": "affreq"},
        "spec": {
            "crd": {"spec": {"names": {"kind": "AffReq"}}},
            "targets": [{
                "target": TARGET,
                "rego": V_REGO.replace("partreq", "affreq"),
            }],
        },
    })
    for ns in AFFINE_NAMESPACES:
        for i in range(n_per_ns):
            cl.add_constraint({
                "apiVersion": "constraints.gatekeeper.sh/v1beta1",
                "kind": "AffReq",
                "metadata": {"name": f"req-{ns}-{i}"},
                "spec": {
                    "match": {
                        "kinds": [{"apiGroups": [""], "kinds": ["Pod"]}],
                        "namespaces": [ns],
                    },
                    "parameters": {"labels": ["owner"]},
                },
            })
    cl.add_constraint({
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "AffReq",
        "metadata": {"name": "req-nssel"},
        "spec": {
            "match": {
                "kinds": [{"apiGroups": [""], "kinds": ["Pod"]}],
                "namespaceSelector": {"matchLabels": {"team": "core"}},
            },
            "parameters": {"labels": ["owner"]},
        },
    })
    return cl


def affine_request(i, ns):
    """battery_request shape variety scoped to one namespace: labeled/
    unlabeled rows plus a G_CAP-overflow pod (70 containers -> per-row
    interpreter route) every 4th request."""
    req = battery_request(i)
    if i % 4 == 3:
        req["object"]["spec"] = {"containers": [
            {"name": f"c{j}", "image": "nginx"} for j in range(70)
        ]}
    req["namespace"] = ns
    req["object"]["metadata"]["namespace"] = ns
    return req


def dispatch_pruned_batch(batcher, requests, ctxs=None):
    """Drive ONE batch through MicroBatcher._dispatch (the partitioned
    fast path when a partitioner is attached) and return each request's
    result list — deterministic, no worker-thread timing."""
    import time as _time
    from concurrent.futures import Future

    stamp = (_time.time(), _time.perf_counter())
    batch = [
        (r, Future(), (ctxs[i] if ctxs else None), stamp, None, None)
        for i, r in enumerate(requests)
    ]
    batcher._dispatch(batch)
    return [item[1].result(timeout=30) for item in batch]


@pruned
@pytest.mark.parametrize("batch_ns,expect_skips", [
    # all-hot traffic: the cold group's partition is mask-skipped
    (["ns-hot"] * 6, True),
    # mixed traffic touches both groups: nothing to skip
    (["ns-hot", "ns-cold"] * 3, False),
])
def test_pruned_dispatch_parity_with_partition_skips(batch_ns,
                                                     expect_skips):
    """The tentpole contract: partitions whose mask row is empty are
    not dispatched (no device call, rows_dispatched drops to zero) and
    merged verdicts stay identical to the monolith — including
    autoreject/needs-context rows and G_CAP-overflow rows."""
    from gatekeeper_tpu.webhook.server import MicroBatcher

    cl = build_affine_client()
    metrics = MetricsRegistry()
    disp = PartitionDispatcher(cl, TARGET, k=3, metrics=metrics)
    batcher = MicroBatcher(
        cl, TARGET, metrics=metrics, partitioner=disp,
    )
    requests = [affine_request(i, ns) for i, ns in enumerate(batch_ns)]
    reviews = augmented(cl, requests)
    mono = cl.review_many(reviews)
    results = dispatch_pruned_batch(batcher, requests)

    plan = disp.plan()
    masks = cl.partition_match_mask(
        reviews, [p.subset for p in plan.partitions]
    )
    skipped = {p.index for p in plan.partitions if not any(masks[p.index])}
    touched = len(plan.partitions) - len(skipped)
    assert bool(skipped) == expect_skips
    some_results = False
    for i in range(len(requests)):
        expect = (
            mono[i].by_target[TARGET].results
            if TARGET in mono[i].by_target else []
        )
        assert normalize(results[i]) == normalize(expect), f"request {i}"
        some_results = some_results or bool(expect)
    assert some_results  # never vacuous

    # telemetry: the batch touched exactly the non-skipped partitions
    stats = disp.touched_stats()
    assert stats["batches"] == 1
    assert stats["p50"] == touched and stats["max"] == touched
    # the pruning-efficiency series: a skipped partition dispatched
    # ZERO rows; a touched one only its mask-selected rows
    for p in plan.partitions:
        d = counter(metrics, "dispatch_rows_dispatched_total",
                    partition=str(p.index))
        t = counter(metrics, "dispatch_rows_total",
                    partition=str(p.index))
        assert t == len(p.keys) * len(requests)
        if p.index in skipped:
            assert d == 0
        else:
            assert d == len(p.keys) * sum(masks[p.index])
    # a skipped partition is counted as such, never as a device call
    if skipped:
        assert counter(metrics, "device_partition_dispatch_total",
                       route="skipped") == len(skipped)
    batcher.stop()
    disp.close()


@pruned
def test_pruned_dispatch_all_partitions_skipped():
    """A batch nothing in the corpus matches (and no autoreject path
    selects) dispatches ZERO partitions — and still answers every
    request, identically to the monolith (all-empty verdicts)."""
    from gatekeeper_tpu.webhook.server import MicroBatcher

    cl = build_affine_client()
    # drop the needs-context constraint's autoreject path by serving
    # cached namespace data: every namespace is known, unlabeled
    ns_getter = lambda ns: {  # noqa: E731
        "metadata": {"name": ns, "labels": {}}
    }
    metrics = MetricsRegistry()
    disp = PartitionDispatcher(cl, TARGET, k=3, metrics=metrics)
    batcher = MicroBatcher(
        cl, TARGET, metrics=metrics, partitioner=disp,
        namespace_getter=ns_getter,
    )
    requests = [affine_request(i, "ns-other") for i in range(4)]
    handler = batcher.target_handler
    reviews = [handler.augment_request(r, ns_getter) for r in requests]
    mono = cl.review_many(reviews)
    results = dispatch_pruned_batch(batcher, requests)
    for i in range(len(requests)):
        expect = (
            mono[i].by_target[TARGET].results
            if TARGET in mono[i].by_target else []
        )
        assert normalize(results[i]) == normalize(expect)
        assert results[i] == []  # ns-other matches nothing
    stats = disp.touched_stats()
    assert stats["batches"] == 1 and stats["p50"] == 0
    assert counter(metrics, "device_partition_dispatch_total",
                   route="skipped") == len(disp.plan().partitions)
    assert counter(metrics, "dispatch_rows_dispatched_total") == 0
    assert counter(metrics, "dispatch_rows_total") > 0
    batcher.stop()
    disp.close()


@pruned
def test_pruned_dispatch_parity_battery_no_skips():
    """The whole-corpus battery (VECTORIZED + PARTIAL_ROWS +
    INTERPRETER + needs-context autorejects + overflow rows) through
    the pruned path: every partition matches Pod traffic, so nothing
    skips — and verdicts still merge identical to the monolith."""
    from gatekeeper_tpu.webhook.server import MicroBatcher

    cl = build_battery_client(9)
    disp = PartitionDispatcher(cl, TARGET, k=4)
    batcher = MicroBatcher(cl, TARGET, partitioner=disp)
    requests = [battery_request(i) for i in range(23)]
    reviews = augmented(cl, requests)
    mono = cl.review_many(reviews)
    results = dispatch_pruned_batch(batcher, requests)
    some = False
    for i in range(len(requests)):
        expect = (
            mono[i].by_target[TARGET].results
            if TARGET in mono[i].by_target else []
        )
        assert normalize(results[i]) == normalize(expect), f"request {i}"
        some = some or bool(expect)
    assert some
    stats = disp.touched_stats()
    assert stats["p50"] == len(disp.plan().partitions)  # all touched
    batcher.stop()
    disp.close()


@pruned
def test_decision_facts_report_skipped_partitions_zero_rows():
    """Decision-log fact check: a mask-skipped partition appears in
    `partitions_skipped`, never in `partitions_matched`, and
    contributes ZERO rows to the request's `rows_dispatched`."""
    from types import SimpleNamespace

    from gatekeeper_tpu.obs import DecisionLog
    from gatekeeper_tpu.webhook.server import MicroBatcher

    cl = build_affine_client()
    log = DecisionLog(allow_sample_n=1, max_per_s=0)
    disp = PartitionDispatcher(cl, TARGET, k=3)
    batcher = MicroBatcher(cl, TARGET, partitioner=disp, decisions=log)
    requests = [affine_request(i, "ns-hot") for i in range(4)]
    ctxs = [SimpleNamespace(trace_id=f"{i:032x}") for i in range(4)]
    reviews = augmented(cl, requests)
    dispatch_pruned_batch(batcher, requests, ctxs)
    plan = disp.plan()
    masks = cl.partition_match_mask(
        reviews, [p.subset for p in plan.partitions]
    )
    skipped = {p.index for p in plan.partitions if not any(masks[p.index])}
    assert skipped  # all-hot traffic must leave the cold group cold
    keycount = {p.index: len(p.keys) for p in plan.partitions}
    for i, ctx in enumerate(ctxs):
        rec = log.record_decision(
            "validation", "deny", trace_id=ctx.trace_id
        )
        assert rec is not None
        assert set(rec["partitions_skipped"]) == skipped
        assert skipped.isdisjoint(rec["partitions_matched"])
        assert rec["partitions_touched"] == (
            len(plan.partitions) - len(skipped)
        )
        matched = [
            p.index for p in plan.partitions if masks[p.index][i]
        ]
        assert rec["partitions_matched"] == matched
        assert rec["rows_dispatched"] == sum(
            keycount[j] for j in matched
        )
        assert rec["rows_total"] == sum(keycount.values())
        # skipped partitions contribute zero dispatched rows
        assert rec["rows_dispatched"] <= rec["rows_total"] - sum(
            keycount[j] for j in skipped
        )
    batcher.stop()
    disp.close()


# -- the cost/locality-guided planner (tier-1 smoke, no device) --------------


@pruned
def test_guided_planner_colocates_and_balances_synthetic_costs():
    """Planner smoke on a synthetic attribution table: keys sharing a
    locality token land in ONE partition (hot-key co-location), and
    greedy LPT keeps per-partition cost deterministic and balanced."""
    from gatekeeper_tpu.parallel.partition import build_plan

    keys = [f"K/c{i:02d}" for i in range(12)]
    groups = {  # token -> member indices
        "g-a": [0, 1, 2, 3], "g-b": [4, 5], "g-c": [6, 7],
        "g-d": [8], "g-e": [9, 10], "g-f": [11],
    }
    locality = {
        keys[i]: tok for tok, idxs in groups.items() for i in idxs
    }
    # measured device seconds: group costs 10, 9, 2, 2, 1, 1
    per_group = {"g-a": 10.0, "g-b": 9.0, "g-c": 2.0,
                 "g-d": 2.0, "g-e": 1.0, "g-f": 1.0}
    costs = {
        keys[i]: per_group[tok] / len(idxs)
        for tok, idxs in groups.items() for i in idxs
    }
    plan = build_plan(
        keys, 3, range(3), frozenset(range(3)),
        costs=costs, locality=locality,
    )
    assert len(plan.partitions) == 3
    # co-location: no locality group straddles partitions
    home_of = {}
    for p in plan.partitions:
        for key in p.keys:
            home_of.setdefault(locality[key], set()).add(p.index)
    assert all(len(parts) == 1 for parts in home_of.values())
    # LPT balance on the synthetic costs: loads are {10, 9, 6}
    loads = sorted(
        (sum(costs[key] for key in p.keys) for p in plan.partitions),
        reverse=True,
    )
    assert [round(x) for x in loads] == [10, 9, 6]
    # determinism: same inputs, same plan
    again = build_plan(
        keys, 3, range(3), frozenset(range(3)),
        costs=costs, locality=locality,
    )
    assert [p.keys for p in again.partitions] == [
        p.keys for p in plan.partitions
    ]
    # every key lands exactly once
    seen = [key for p in plan.partitions for key in p.keys]
    assert sorted(seen) == sorted(keys)


@pruned
def test_guided_planner_splits_one_hot_group_to_fill_partitions():
    """One locality token across the whole corpus (every constraint
    matches the same reviews): the planner degenerates to a balanced
    split — mask-gating can't prune, but parallelism is preserved."""
    from gatekeeper_tpu.parallel.partition import build_plan

    keys = [f"K/c{i}" for i in range(8)]
    locality = {key: "same" for key in keys}
    plan = build_plan(
        keys, 4, range(4), frozenset(range(4)),
        costs={key: 1.0 for key in keys}, locality=locality,
    )
    sizes = sorted(len(p.keys) for p in plan.partitions)
    assert len(plan.partitions) == 4
    assert max(sizes) - min(sizes) <= 1
    assert sorted(
        key for p in plan.partitions for key in p.keys
    ) == sorted(keys)


@pruned
def test_blend_costs_prefers_measured_and_rescales_static():
    from gatekeeper_tpu.parallel.partition import _blend_costs

    keys = ["K/a", "K/b", "K/c"]
    assert _blend_costs(keys, None, None) is None
    assert _blend_costs(keys, None, {}) is None
    # static only passes through
    static = {"K/a": 2.0, "K/b": 4.0, "K/c": 6.0}
    assert _blend_costs(keys, static, {}) == static
    # measured wins where present; unmeasured keys rescale so the two
    # populations are comparable (static mean matched to measured mean)
    blended = _blend_costs(keys, static, {"K/a": 0.5})
    assert blended["K/a"] == 0.5
    scale = 0.5 / 2.0  # measured mean over static mean of measured keys
    assert blended["K/b"] == pytest.approx(4.0 * scale)
    assert blended["K/c"] == pytest.approx(6.0 * scale)


@pruned
def test_dispatcher_plans_from_synthetic_attribution_table():
    """End-to-end planner smoke: a fake attributor's measured table
    steers the plan (hot constraints co-located by locality, measured
    cost shares surfaced in /debug/partitions' plan_table) — no
    device, tier-1 safe."""
    cl = build_affine_client(n_per_ns=3)
    keys = cl._driver.constraint_keys(TARGET)

    class _FakeAttributor:
        def table(self, k=None):
            return {"rows": [
                {"kind": key.split("/")[0], "name": key.split("/")[1],
                 "seconds": 0.5 if "ns-hot" in key else 0.01}
                for key in keys
            ]}

    disp = PartitionDispatcher(
        cl, TARGET, k=3, attributor=_FakeAttributor(), replica="r7",
    )
    plan = disp.plan()
    assert plan is not None and len(plan.partitions) == 3
    # the hot namespace group is co-located in one partition
    hot_parts = {
        p.index for p in plan.partitions
        for key in p.keys if "ns-hot" in key
    }
    cold_parts = {
        p.index for p in plan.partitions
        for key in p.keys if "ns-cold" in key
    }
    assert len(hot_parts) == 1 and len(cold_parts) == 1
    assert hot_parts != cold_parts
    table = disp.plan_table()
    assert table["replica"] == "r7"
    assert table["k"] == 3 and len(table["partitions"]) == 3
    by_index = {row["index"]: row for row in table["partitions"]}
    hot_row = by_index[next(iter(hot_parts))]
    cold_row = by_index[next(iter(cold_parts))]
    # measured share: the hot group dominates device seconds
    assert hot_row["measured_cost_share"] > cold_row[
        "measured_cost_share"
    ]
    assert hot_row["home_device"] is not None
    assert set(hot_row["keys"]) == {
        key for key in keys if "ns-hot" in key
    }
    # static share present too (every key has a static cost)
    assert hot_row["static_cost_share"] is not None
    disp.close()
