"""The constraint client's violating resources: within one call, the
results of one review object share one resource, a JSON round-trip copy
of the object that is apart from the synced cache and from the review
(constraint/client.py `_handle_violations`, docs/targets.md)."""

import copy
import json

import pytest

from gatekeeper_tpu.agentaction import AgentActionTarget
from gatekeeper_tpu.constraint import (
    AdmissionRequest,
    Backend,
    Client,
    K8sValidationTarget,
    RegoDriver,
    TpuDriver,
)
from gatekeeper_tpu.constraint.types import Result
from gatekeeper_tpu.metrics import MetricsRegistry

TARGET = "admission.k8s.gatekeeper.sh"

REQ_LABEL = """package reqlabel

violation[{"msg": msg}] {
    not input.review.object.metadata.labels[input.parameters.label]
    msg := sprintf("%v lacks %v", [input.review.object.metadata.name, input.parameters.label])
}
"""

LABELS = ("a", "b", "c")


def make_client(driver):
    client = Backend(driver).new_client(K8sValidationTarget())
    client.add_template({
        "apiVersion": "templates.gatekeeper.sh/v1beta1",
        "kind": "ConstraintTemplate",
        "metadata": {"name": "reqlabel"},
        "spec": {
            "crd": {"spec": {"names": {"kind": "ReqLabel"}}},
            "targets": [{"target": TARGET, "rego": REQ_LABEL}],
        },
    })
    for label in LABELS:
        client.add_constraint({
            "apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": "ReqLabel",
            "metadata": {"name": f"need-{label}"},
            "spec": {
                "match": {"kinds": [{"apiGroups": [""], "kinds": ["Pod"]}]},
                "parameters": {"label": label},
            },
        })
    return client


def pod(name, labels):
    return {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {"name": name, "namespace": "default",
                     "labels": {k: "x" for k in labels}},
        "spec": {"containers": [{"name": "c", "image": "nginx",
                                 "ports": [{"containerPort": 80}]}]},
    }


def request(obj):
    return AdmissionRequest({
        "uid": obj["metadata"]["name"],
        "kind": {"group": "", "version": "v1", "kind": "Pod"},
        "operation": "CREATE",
        "name": obj["metadata"]["name"],
        "namespace": "default",
        "userInfo": {"username": "alice"},
        "object": obj,
    })


# three violations (no label), one (lacks c), none
CORPUS = {"three": (), "one": ("a", "b"), "clean": LABELS}


def expected_resource(obj):
    want = json.loads(json.dumps(obj))
    want["apiVersion"], want["kind"] = "v1", "Pod"
    return want


def by_object(results):
    out = {}
    for r in results:
        out.setdefault(r.review["object"]["metadata"]["name"], []).append(r)
    return out


@pytest.fixture(params=["rego", "tpu"])
def driver(request):
    return RegoDriver() if request.param == "rego" else TpuDriver()


def synced(client):
    """The audit corpus: CORPUS as synced Pods plus their Namespace."""
    objs = {name: pod(name, labels) for name, labels in CORPUS.items()}
    client.add_data({"apiVersion": "v1", "kind": "Namespace",
                     "metadata": {"name": "default"}})
    for obj in objs.values():
        client.add_data(obj)
    return objs


def test_audit_shares_one_resource_per_violating_object(driver):
    client = make_client(driver)
    objs = synced(client)
    pristine = copy.deepcopy(objs)
    groups = by_object(client.audit().results())
    assert sorted((k, len(v)) for k, v in groups.items()) == [
        ("one", 1), ("three", 3)]
    three, one = groups["three"], groups["one"]
    assert all(r.resource is three[0].resource for r in three)
    assert one[0].resource is not three[0].resource
    for name, rs in groups.items():
        assert rs[0].resource == expected_resource(objs[name])
        assert rs[0].resource is not rs[0].review["object"]

    # the copy is apart from the review and from the synced cache
    res = three[0].resource
    res["metadata"]["labels"]["a"] = "mutated"
    res["spec"]["containers"][0]["ports"].append({"containerPort": 1})
    assert three[0].review["object"] == pristine["three"]
    path = ["external", TARGET, "namespace", "default", "v1", "Pod", "three"]
    assert driver.storage.get(path) == pristine["three"]
    again = by_object(client.audit().results())
    assert again["three"][0].resource == expected_resource(pristine["three"])


REVIEW_PATHS = {
    "review": lambda c, objs: [
        c.review(request(o)).results() for o in objs],
    "review_host": lambda c, objs: [
        c.review_host(request(o)).results() for o in objs],
    "review_many": lambda c, objs: [
        r.results() for r in c.review_many([request(o) for o in objs])],
    "review_many_subset": lambda c, objs: [
        r.results() for r in c.review_many_subset(
            [request(o) for o in objs],
            frozenset(c._driver.constraint_keys(TARGET)))],
}


@pytest.mark.parametrize("path", sorted(REVIEW_PATHS))
def test_review_paths_copy_each_object_once(path):
    """Two requests of equal content in one batch are two objects: two
    copies, each shared by that object's three results."""
    client = make_client(TpuDriver(use_jax=False))
    objs = [pod("twin", ()), pod("twin", ())]
    per_obj = REVIEW_PATHS[path](client, objs)
    assert [len(rs) for rs in per_obj] == [3, 3]
    for rs, obj in zip(per_obj, objs):
        assert all(r.resource is rs[0].resource for r in rs)
        assert rs[0].resource == expected_resource(obj)
    assert per_obj[0][0].resource is not per_obj[1][0].resource


def test_review_many_copies_twins_apart_on_both_drivers(driver):
    client = make_client(driver)
    twins = [pod("twin", ()), pod("twin", ())]
    first, second = (r.results() for r in
                     client.review_many([request(o) for o in twins]))
    assert first[0].resource == second[0].resource
    assert first[0].resource is not second[0].resource


def copies(reg, path):
    return reg.snapshot()["counters"].get(
        f'client_resource_copies_total{{path="{path}"}}', 0)


def test_copies_counter_counts_objects_not_violations():
    reg = MetricsRegistry()
    client = make_client(TpuDriver(use_jax=False, metrics=reg))
    synced(client)
    results = client.audit().results()
    assert len(results) == 4
    assert copies(reg, "audit") == 2
    client.audit()
    assert copies(reg, "audit") == 4
    client.review_many([request(pod("p", ())), request(pod("q", ("a",)))])
    assert copies(reg, "webhook") == 2
    # a call without violations records nothing
    client.review(request(pod("ok", LABELS)))
    assert copies(reg, "webhook") == 2


@pytest.mark.parametrize("review,why", [
    ("not-a-map", "could not cast review as map"),
    ({"object": {}}, "review\\[kind\\] does not exist"),
    ({"kind": {"group": "", "version": 1, "kind": "Pod"}, "object": {}},
     "is not a string"),
    ({"kind": {"group": "", "version": "v1", "kind": "Pod"}},
     "no object or oldObject"),
])
def test_malformed_review_still_raises(review, why):
    target = K8sValidationTarget()
    with pytest.raises(ValueError, match=why):
        target.handle_violation(Result(review=review))
    results = [Result(review=review), Result(review=review)]
    with pytest.raises(ValueError, match=why):
        Client._handle_violations(target, results)
    assert all(r.resource is None for r in results)


def test_old_object_review_shares_its_copy():
    """A DELETE review carries only oldObject; it is the copy's source."""
    old = pod("gone", ())
    review = {"kind": {"group": "", "version": "v1", "kind": "Pod"},
              "oldObject": old}
    results = [Result(review=review) for _ in range(3)]
    assert Client._handle_violations(K8sValidationTarget(), results) == 1
    assert all(r.resource is results[0].resource for r in results)
    assert results[0].resource == expected_resource(old)
    assert results[0].resource is not old


def test_agent_handler_results_share_one_resource():
    """Every handler gets the sharing: the agent target's results of one
    review share one resource, and two reviews get two."""
    review = {"name": "call-1", "object": {"spec": {"agent": "a1", "tool": "t"}}}
    other = copy.deepcopy(review)
    results = [Result(review=review) for _ in range(2)] + [Result(review=other)]
    assert Client._handle_violations(AgentActionTarget(), results) == 2
    assert results[0].resource is results[1].resource
    assert results[2].resource == results[0].resource
    assert results[2].resource is not results[0].resource
    assert results[0].resource["spec"]["tool"] == "t"
