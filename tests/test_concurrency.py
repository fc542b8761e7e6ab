"""Driver cache coherence under concurrent churn (VERDICT r3 weak #6).

The control plane mutates templates/constraints/data from watch threads
while audit sweeps and webhook review batches dispatch concurrently
(client.go:73 constraintsMux / local.go:63 modulesMux posture). These
tests drive the TpuDriver's generation-counter discipline directly:
worker threads churn the Client while audit()/review_many() hammer the
evaluation paths; nothing may raise, every observed result must be
consistent with SOME churn state (constraints that never existed can
never appear), and once churn stops the driver must converge to exactly
the serial ground truth (no stale corpus/constraint-set/render-cache
entries).
"""

import threading

import pytest

from gatekeeper_tpu.constraint import (
    AugmentedUnstructured,
    Backend,
    K8sValidationTarget,
    RegoDriver,
    TpuDriver,
)

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

DENY_REPO = """package denyrepo

violation[{"msg": msg}] {
    container := input.review.object.spec.containers[_]
    startswith(container.image, input.parameters.registry)
    msg := sprintf("bad registry on %v", [container.name])
}
"""


def template(kind, rego):
    return {
        "apiVersion": "templates.gatekeeper.sh/v1beta1",
        "kind": "ConstraintTemplate",
        "metadata": {"name": kind.lower()},
        "spec": {
            "crd": {"spec": {"names": {"kind": kind}}},
            "targets": [{"target": TARGET, "rego": rego}],
        },
    }


def constraint(kind, name, params):
    return {
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": kind,
        "metadata": {"name": name},
        "spec": {
            "match": {"kinds": [{"apiGroups": [""], "kinds": ["Pod"]}]},
            "parameters": params,
        },
    }


def pod(name, labels=None, image="nginx"):
    return {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {
            "name": name,
            "namespace": "default",
            "labels": labels or {},
        },
        "spec": {"containers": [{"name": "c", "image": image}]},
    }


@pytest.mark.parametrize("use_jax", [False, True])
def test_churn_while_evaluating(use_jax):
    drv = TpuDriver(use_jax=use_jax)
    client = Backend(drv).new_client(K8sValidationTarget())
    client.add_template(template("ChurnLabels", REQ_LABELS))
    client.add_template(template("ChurnRepo", DENY_REPO))
    client.add_constraint(
        constraint("ChurnLabels", "need-owner", {"labels": ["owner"]})
    )
    for i in range(60):
        client.add_data(
            pod(
                f"p{i}",
                labels={} if i % 5 == 0 else {"owner": "me"},
                image="evil/x" if i % 7 == 0 else "nginx",
            )
        )

    errors = []
    stop = threading.Event()
    valid_constraints = {
        "ChurnLabels/need-owner",
        "ChurnLabels/need-team",
        "ChurnRepo/no-evil",
    }

    def churn():
        i = 0
        try:
            while not stop.is_set():
                i += 1
                # constraint churn
                if i % 3 == 0:
                    client.add_constraint(
                        constraint(
                            "ChurnRepo", "no-evil", {"registry": "evil/"}
                        )
                    )
                elif i % 3 == 1:
                    client.remove_constraint(
                        constraint(
                            "ChurnRepo", "no-evil", {"registry": "evil/"}
                        )
                    )
                # data churn
                client.add_data(pod(f"extra{i % 4}", labels={}))
                if i % 2:
                    client.remove_data(pod(f"extra{i % 4}"))
                # template param-set churn
                client.add_constraint(
                    constraint(
                        "ChurnLabels",
                        "need-team",
                        {"labels": ["team"] if i % 2 else ["team", "env"]},
                    )
                )
        except Exception as e:  # pragma: no cover - failure surface
            errors.append(e)

    def evaluate():
        try:
            while not stop.is_set():
                results = client.audit().by_target[TARGET].results
                for r in results:
                    kind = (r.constraint or {}).get("kind")
                    name = ((r.constraint or {}).get("metadata") or {}).get(
                        "name"
                    )
                    assert f"{kind}/{name}" in valid_constraints, (
                        f"ghost constraint {kind}/{name}"
                    )
                reviews = [
                    AugmentedUnstructured(pod(f"rv{j}", labels={}))
                    for j in range(14)
                ]
                for resp in client.review_many(reviews):
                    for r in resp.by_target[TARGET].results:
                        assert r.msg, "empty violation message"
        except Exception as e:  # pragma: no cover - failure surface
            errors.append(e)

    threads = [
        threading.Thread(target=churn),
        threading.Thread(target=churn),
        threading.Thread(target=evaluate),
        threading.Thread(target=evaluate),
    ]
    for t in threads:
        t.start()
    import time

    time.sleep(4.0 if use_jax else 2.0)
    stop.set()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive(), "worker wedged"
    assert not errors, errors

    # convergence: settle the churned state deterministically, then the
    # TPU driver must agree bit-for-bit with a fresh serial interpreter
    client.add_constraint(
        constraint("ChurnRepo", "no-evil", {"registry": "evil/"})
    )
    client.add_constraint(
        constraint("ChurnLabels", "need-team", {"labels": ["team"]})
    )
    for i in range(4):
        client.remove_data(pod(f"extra{i}"))

    ref = Backend(RegoDriver()).new_client(K8sValidationTarget())
    ref.add_template(template("ChurnLabels", REQ_LABELS))
    ref.add_template(template("ChurnRepo", DENY_REPO))
    ref.add_constraint(
        constraint("ChurnLabels", "need-owner", {"labels": ["owner"]})
    )
    ref.add_constraint(
        constraint("ChurnRepo", "no-evil", {"registry": "evil/"})
    )
    ref.add_constraint(
        constraint("ChurnLabels", "need-team", {"labels": ["team"]})
    )
    for i in range(60):
        ref.add_data(
            pod(
                f"p{i}",
                labels={} if i % 5 == 0 else {"owner": "me"},
                image="evil/x" if i % 7 == 0 else "nginx",
            )
        )

    key = lambda r: (  # noqa: E731
        r.msg,
        (r.constraint.get("metadata") or {}).get("name"),
        repr(r.review),
    )
    want = sorted(key(r) for r in ref.audit().by_target[TARGET].results)
    got = sorted(key(r) for r in client.audit().by_target[TARGET].results)
    assert got == want


def test_warm_compile_touches_shared_tables_only_under_mutex():
    """warm_review_path compiles without the serving mutex; the vocab
    tables and the kernel's device-resident table buffers it shares
    with audit sweeps must still only be synced/staged under it."""
    import sys
    import time

    drv = TpuDriver()
    client = Backend(drv).new_client(K8sValidationTarget())
    client.add_template(template("WarmLabels", REQ_LABELS))
    client.add_template(template("WarmRepo", DENY_REPO))
    client.add_constraint(
        constraint("WarmLabels", "need-owner", {"labels": ["owner"]})
    )
    client.add_constraint(constraint("WarmRepo", "no-evil", {"registry": "e/"}))
    for i in range(40):
        client.add_data(pod(f"p{i}", labels={"owner": "me"}))

    unlocked = []

    def guard(fn, name):
        def wrapped(*a, **kw):
            if not drv._mutex._is_owned():
                unlocked.append(name)
            return fn(*a, **kw)
        return wrapped

    drv.patterns.sync = guard(drv.patterns.sync, "patterns.sync")
    drv.tables.sync = guard(drv.tables.sync, "tables.sync")
    drv.kernel._tables_device = guard(drv.kernel._tables_device, "tables")

    errors = []
    stop = threading.Event()

    def loop(step):
        i = 0
        try:
            while not stop.is_set():
                i += 1
                step(i)
        except Exception as e:  # pragma: no cover - failure surface
            errors.append(e)

    def warm(i):
        client.warm_review_path([
            AugmentedUnstructured(pod(f"w{i}-{j}", labels={f"k{i}": "v"}))
            for j in range(16 + i % 3 * 20)
        ])

    def audit(i):
        client.audit()

    def churn(i):
        client.add_data(pod(f"new{i}", labels={f"l{i}": f"v{i}"}))

    saved = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [
            threading.Thread(target=loop, args=(f,))
            for f in (warm, warm, audit, churn)
        ]
        for t in threads:
            t.start()
        time.sleep(3.0)
        stop.set()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive(), "worker wedged"
    finally:
        sys.setswitchinterval(saved)
    assert not errors, errors
    assert not unlocked, sorted(set(unlocked))
