"""The TPU driver's fused path freezes the synced inventory only when a
render reads it: a template whose violations render from compiled
branch plans never freezes it, one that renders on the plain
interpreter against data.inventory freezes it once per data version,
and both agree with RegoDriver before and after a synced change."""

import pytest

from gatekeeper_tpu.constraint import (
    Backend,
    K8sValidationTarget,
    RegoDriver,
    TpuDriver,
)
from gatekeeper_tpu.metrics import MetricsRegistry
from test_tpu_driver import TARGET, canon, make_constraint, namespace, pod

# renders every violation from its branch plan: no inventory read
OWNER = """package ownerlabel

violation[{"msg": msg}] {
    not input.review.object.metadata.labels.owner
    msg := sprintf("%v has no owner", [input.review.object.metadata.name])
}
"""

# a lookup into data.inventory with no prune plan: each violating pair
# renders on the interpreter against the whole synced tree
KNOWN_CLASS = """package knownclass

violation[{"msg": msg}] {
    pc := input.review.object.spec.priorityClassName
    not data.inventory.cluster["scheduling.k8s.io/v1"]["PriorityClass"][pc]
    msg := sprintf("unknown priority class %v", [pc])
}
"""


def priority_class(name):
    return {"apiVersion": "scheduling.k8s.io/v1", "kind": "PriorityClass",
            "metadata": {"name": name}}


def build(driver, kind, rego):
    client = Backend(driver).new_client(K8sValidationTarget())
    client.add_template({
        "apiVersion": "templates.gatekeeper.sh/v1beta1",
        "kind": "ConstraintTemplate",
        "metadata": {"name": kind.lower()},
        "spec": {
            "crd": {"spec": {"names": {"kind": kind}}},
            "targets": [{"target": TARGET, "rego": rego}],
        },
    })
    client.add_constraint(make_constraint(kind, "c"))
    client.add_data(namespace("default"))
    client.add_data(priority_class("high"))
    for i in range(24):
        spec = {"priorityClassName": "low" if i % 4 == 0 else "high"} if i % 2 == 0 else None
        client.add_data(pod(f"p{i}", labels={"owner": "me"} if i % 3 else None,
                            spec_extra=spec))
    return client


def churn(client):
    """A synced change that moves both templates' results."""
    client.add_data(priority_class("low"))
    client.remove_data(pod("p3"))
    client.add_data(pod("fresh", spec_extra={"priorityClassName": "mid"}))


def freezes(reg):
    return reg.snapshot()["counters"].get("driver_inventory_freezes_total", 0)


@pytest.mark.parametrize("kind,rego,per_version", [
    ("OwnerLabel", OWNER, 0),
    ("KnownClass", KNOWN_CLASS, 1),
], ids=["compiled-render", "interpreter-render"])
def test_sweep_freezes_the_inventory_only_when_a_render_reads_it(kind, rego, per_version):
    rego_client = build(RegoDriver(), kind, rego)
    drv = TpuDriver()
    reg = MetricsRegistry()
    drv.set_metrics(reg)
    tpu = build(drv, kind, rego)

    def sweep():
        n = freezes(reg)
        got = tpu.audit().by_target[TARGET].results
        want = rego_client.audit().by_target[TARGET].results
        assert canon(got) == canon(want)
        assert got
        return canon(want), freezes(reg) - n

    before, n = sweep()
    assert n == per_version
    unpruned = drv.stats["interp_rendered_pairs"] - drv.stats["pruned_renders"]
    if per_version:
        assert unpruned > 0
    else:
        assert unpruned == 0 and drv.stats["host_rendered_pairs"] > 0
    assert sweep() == (before, 0)  # same data version: nothing re-frozen
    churn(tpu)
    churn(rego_client)
    after, n = sweep()
    assert after != before  # a stale inventory would show here
    assert n == per_version
    skips = reg.snapshot()["counters"].get('driver_inventory_skips_total{path="audit"}', 0)
    # the repeat sweep renders from the render cache; a compiled-render
    # sweep never reads the inventory at all
    assert skips == (1 if per_version else 3)
