"""Audit from cache while the synced inventory churns, on the CPU at a
small size: the Runner over a FakeCluster, Services and Ingresses (or
Pods) replaced through the watch path between sweeps, every sweep's
report against the benchmark's plain reference (which imports nothing
of the program), the stale-audit control, and the join and churn
stages the driver and the sync controller record."""

import time
from collections import Counter

import pytest

from benchmark import control, harness
from benchmark.reference import joins
from benchmark.tests.churn import small
from gatekeeper_tpu.constraint import Backend, K8sValidationTarget, TpuDriver
from gatekeeper_tpu.control import FakeCluster, Runner
from gatekeeper_tpu.metrics import MetricsRegistry

REFS = "refs-svc10k-ing5k.audit-churn"
PODS = "pods100k-c500.audit-churn"
TARGET = "admission.k8s.gatekeeper.sh"


def run(cell, seed):
    cfg, traffic = small(cell)
    return harness.run_cell(cell, seed, 60.0, False, time.monotonic(), cfg, traffic)


@pytest.mark.parametrize("cell,seed", [(REFS, 2**33 + 25), (PODS, 2**31 + 7)])
def test_churned_sweeps_match_the_reference(cell, seed):
    """Every churned sweep agrees with the reference computed for the
    inventory that sweep saw; the schedule runs out after 3 steps."""
    r = run(cell, seed)
    assert r["checks"]["audit_mismatches"] == {"value": 0, "limit": 0}
    assert r["correct"] and r["failed"] == 0
    assert r["attempted"] == 3
    assert r["checks"]["sweeps_compared"]["value"] == 3
    assert set(r["metrics"]) == {"setup_s", "audit_sweep_s"}


def test_reference_holds_every_refs_violation():
    """The small referential inventory violates all four constraints,
    and churn moves the join results between steps."""
    kind = harness.load_kind({"kind": "audit_churn"})
    cfg, traffic = small(REFS)
    inventory = kind.Inventory(cfg, traffic, 5)
    want = kind.Expectation(cfg, inventory)
    before = want.want()
    assert {k.split("/")[0] for k in before} == {
        "K8sUniqueServiceSelector", "K8sUniqueIngressHost",
        "K8sBlockNodePort", "K8sRequiredLabels"}
    for step in inventory.steps[:3]:
        want.apply(step)
    assert want.want() != before


@pytest.mark.parametrize("cell", [REFS, PODS])
def test_stale_audit_control_is_not_correct(cell):
    cfg, traffic = small(cell)
    checks = control.run(cell, 11, 30.0, cfg, traffic)
    assert checks["audit_mismatches"][0] > 0


def test_reference_joins_follow_the_rego():
    """One message per conflicting partner Service, one per shared
    host; the identical() guard excludes the object itself; Ingresses
    of both groups join."""
    def svc(name, ns, sel, api="v1"):
        return {"apiVersion": api, "kind": "Service",
                "metadata": {"name": name, "namespace": ns}, "spec": {"selector": sel}}

    def ing(name, ns, hosts, group="networking.k8s.io"):
        return {"apiVersion": f"{group}/v1beta1", "kind": "Ingress",
                "metadata": {"name": name, "namespace": ns},
                "spec": {"rules": [{"host": h} for h in hosts]}}

    got = joins.unique_service_selector([
        svc("a", "n1", {"app": "x", "tier": "web"}),
        svc("b", "n2", {"tier": "web", "app": "x"}),
        svc("c", "n1", {"app": "x", "tier": "web"}),
        svc("d", "n1", {"app": "y"}),
    ])
    assert got[("n1", "a")] == [
        "same selector as service <b> in namespace <n2>",
        "same selector as service <c> in namespace <n1>",
    ]
    assert ("n1", "d") not in got
    got = joins.unique_ingress_host([
        ing("a", "n1", ["h1", "h2"]),
        ing("b", "n2", ["h2"], group="extensions"),
        ing("c", "n3", ["h1"], group="apps"),  # not an Ingress group
    ])
    assert got == {
        ("n1", "a"): ["ingress host conflicts with an existing ingress <h2>"],
        ("n2", "b"): ["ingress host conflicts with an existing ingress <h2>"],
    }


def _counters(reg, family):
    return {k: v for k, v in reg.snapshot()["counters"].items()
            if k.split("{")[0] == family}


def _phase_count(reg, phase):
    return sum(d["count"] for k, d in reg.snapshot()["distributions"].items()
               if k.startswith("driver_phase_seconds{") and f'phase="{phase}"' in k)


def test_join_and_churn_stages_are_recorded():
    """A sweep after a synced change re-encodes the corpus once
    (corpus_encode, rows encoded), screens the joins (join_screen) and
    renders each flagged join pair on the interpreter (render_pruned,
    the flagged route); each watch event is one sync.apply."""
    kind = harness.load_kind({"kind": "audit_churn"})
    cfg, traffic = small(REFS)
    inventory = kind.Inventory(cfg, traffic, 3)
    cluster = FakeCluster()
    for obj in (inventory.templates + inventory.constraints + [inventory.config]
                + inventory.objects()):
        cluster.apply(obj)
    client = Backend(TpuDriver(use_jax=False)).new_client(K8sValidationTarget())
    reg = MetricsRegistry()
    runner = Runner(cluster, client, TARGET, operations=("audit",), audit_interval=1e9,
                    audit_from_cache=True, readyz_port=None, metrics=reg)
    runner.start()
    try:
        assert runner.wait_ready(timeout=60)
        assert runner.audit.warmed.wait(60)
        synced = sum(d["count"] for k, d in reg.snapshot()["distributions"].items()
                     if k.startswith("sync_duration_seconds"))
        assert synced == len(inventory.objects())
        encodes = _phase_count(reg, "corpus_encode")
        deleted, created = inventory.steps[0]
        for obj in deleted:
            cluster.delete(obj)
        for obj in created:
            cluster.apply(obj)
        assert runner.watch_mgr.wait_idle(60)
        synced2 = sum(d["count"] for k, d in reg.snapshot()["distributions"].items()
                      if k.startswith("sync_duration_seconds"))
        assert synced2 - synced == len(deleted) + len(created)
        rows0 = _counters(reg, "driver_join_rows_total")
        runner.audit.audit()
        assert _phase_count(reg, "corpus_encode") == encodes + 1
        assert _phase_count(reg, "join_screen") >= 1
        rows = _counters(reg, "driver_join_rows_total")
        flagged = {k: v - rows0.get(k, 0) for k, v in rows.items() if "flagged" in k}
        services = len(inventory.churned["Service"])
        # the derived selector key gets duplicate bits like the leaf
        # host key: only Services whose selector another Service of the
        # swept inventory shares are flagged, and shared-host Ingresses
        def ident(o):
            return o["metadata"]["namespace"], o["metadata"]["name"]

        gone = {ident(o) for o in deleted}
        live = [o for o in inventory.churned["Service"] if ident(o) not in gone]
        live += [o for o in created if o["kind"] == "Service"]
        keys = Counter(tuple(sorted(o["spec"]["selector"].items())) for o in live)
        paired = sum(keys[tuple(sorted(o["spec"]["selector"].items()))] >= 2 for o in live)
        sel = [v for k, v in flagged.items() if "K8sUniqueServiceSelector" in k]
        host = [v for k, v in flagged.items() if "K8sUniqueIngressHost" in k]
        assert sel == [paired] and 0 < paired < services
        assert host and 0 < host[0] < len(inventory.churned["Ingress"])
        assert _phase_count(reg, "render_pruned") >= paired + host[0]
        encoded = _counters(reg, "driver_rows_encoded_total")
        assert encoded['driver_rows_encoded_total{path="audit"}'] == 2 * len(inventory.objects())
    finally:
        runner.stop()


def test_a_sync_that_drops_deletions_is_not_correct(monkeypatch):
    """The comparison catches a program that audits a stale inventory:
    the sync controller drops every watch DELETED event."""
    from gatekeeper_tpu.control import controllers
    from gatekeeper_tpu.control.events import DELETED

    sink = controllers.SyncController.sink

    def drop_deletes(self, ev):
        if ev.type != DELETED:
            sink(self, ev)

    monkeypatch.setattr(controllers.SyncController, "sink", drop_deletes)
    r = run(REFS, 2**33 + 25)
    assert not r["correct"]
    assert r["checks"]["audit_mismatches"]["value"] > 0


def test_review_join_work_counts_under_the_webhook_path():
    """A fused review batch that needs the synced inventory's corpus
    and renders its flagged join pairs records that work under
    path="webhook": no audit series moves."""
    from gatekeeper_tpu.constraint import tpudriver
    from test_tpu_driver import make_constraint, referential_template

    drv = TpuDriver()
    drv._kick_warm = lambda target, inputs: None  # no background compile
    reg = MetricsRegistry()
    drv.set_metrics(reg)
    client = Backend(drv).new_client(K8sValidationTarget())
    for kind in ("K8sUniqueServiceSelector", "K8sUniqueIngressHost"):
        client.add_template(referential_template(kind))
        client.add_constraint(make_constraint(kind, kind.lower()))

    def svc(name, ns, app):
        return {"apiVersion": "v1", "kind": "Service",
                "metadata": {"name": name, "namespace": ns}, "spec": {"selector": {"app": app}}}

    def ing(name, ns, host):
        return {"apiVersion": "networking.k8s.io/v1beta1", "kind": "Ingress",
                "metadata": {"name": name, "namespace": ns},
                "spec": {"rules": [{"host": host}]}}

    synced = [svc(f"s{i}", "ns1", f"a{i % 10}") for i in range(20)]
    synced += [ing(f"i{i}", "ns1", f"h{i}.example.com") for i in range(10)]
    for obj in synced:
        client.add_data(obj)
    batch = [svc(f"new{i}", "ns2", f"a{i}") for i in range(tpudriver.MIN_DEVICE_BATCH)]
    batch += [ing("new", "ns2", "h0.example.com")]
    assert client.warm_review_path(batch)
    responses = client.review_many(batch)
    assert drv.last_route() == "fused"
    counts = [len(r.by_target[TARGET].results) for r in responses]
    # a new Service shares its selector with two synced ones while
    # i < 10; the new Ingress shares one synced host
    assert counts == [2] * 10 + [0] * (len(batch) - 11) + [1]
    phases = [k for k in reg.snapshot()["distributions"] if k.startswith("driver_phase_seconds{")]
    for phase in ("corpus_encode", "join_screen", "render_pruned"):
        assert any(f'phase="{phase}"' in k and 'path="webhook"' in k for k in phases), phase
    assert not any('path="audit"' in k for k in phases)
    assert _counters(reg, "driver_rows_encoded_total") == {
        'driver_rows_encoded_total{path="webhook"}': len(synced)}
