"""Workload expansion (docs/expansion.md): an ExpansionTemplate makes the
webhook review a workload's pod template as the Pod it implies, and
answer the workload with that Pod's violations.

The system's answers, on the interpreter driver and the TPU driver,
batched and one request at a time (the fused and the interpreter
routes on the TPU driver), equal the plain reference
`benchmark/reference/expansion.py` on seeded workloads of every kind
the template covers, a kind it does not, an excluded namespace, pod
templates with and without labels, the template's enforcementAction
set and unset, an Assign mutator applied to the implied Pod, and bare
Pods in the same batch. Templates are added, replaced and deleted
while a Runner serves, and a malformed one is rejected."""

import copy
import json
import os
import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import pytest

from benchmark.gen import policy as gen
from benchmark.gen import workloads
from benchmark import reference
from benchmark.reference import expansion as ref
from gatekeeper_tpu.constraint import Backend, K8sValidationTarget, RegoDriver, TpuDriver
from gatekeeper_tpu.control import Excluder, FakeCluster, Runner
from gatekeeper_tpu.expansion import (
    ExpansionSystem,
    ExpansionTemplateError,
    implied_object,
    template_from_obj,
)
from gatekeeper_tpu.metrics import MetricsRegistry
from gatekeeper_tpu.mutation import MutationSystem
from gatekeeper_tpu.webhook.server import WebhookServer

TARGET = gen.TARGET
CFG_PATH = os.path.join(gen.ROOT, "benchmark", "configs", "admit-workloads-c50.json")
with open(CFG_PATH) as _f:
    CFG = json.load(_f)
TEMPLATES, CONFIG = gen.load_policy(CFG)
CONSTRAINTS = gen.make_constraints(TEMPLATES, 16, CFG["policy"])
(EXPAND,) = gen.load_yaml_docs(CFG["expansion_templates"], "ExpansionTemplate")
NS_NAMES = gen.namespace_names(4) + ["gatekeeper-system"]
NAMESPACES = {n: gen.make_namespace(n) for n in NS_NAMES}
POLICY = reference.Policy(CONSTRAINTS)
SEED = 2**33 + 29


def template(action=None, name="expand-deployments"):
    t = copy.deepcopy(EXPAND)
    t["metadata"]["name"] = name
    if action is not None:
        t["spec"]["enforcementAction"] = action
    return t


def make_client(driver):
    drv = RegoDriver() if driver == "rego" else TpuDriver(use_jax=True)
    client = Backend(drv).new_client(K8sValidationTarget())
    for t in TEMPLATES:
        client.add_template(t)
    for c in CONSTRAINTS:
        client.add_constraint(c)
    for ns in NAMESPACES.values():
        client.add_data(ns)
    return client


def excluder():
    exc = Excluder()
    exc.add(CONFIG["spec"]["match"])
    return exc


def workload(kind, name, ns, defect=None, i=0, **kw):
    t = workloads.pod_template(i, ns, defect)
    t["metadata"]["labels"]["app"] = name
    return workloads.workload_request(
        workloads.make_workload(kind, name, ns, t, **kw), f"uid-{name}", "alice")


def cases(seed):
    """Seeded requests: each covered kind clean and with a defect, the
    uncovered CronJob and Service, a parent in an excluded namespace,
    pod templates without labels and with a name of their own, a
    Deployment with no pod template, and bare Pods."""
    rng = gen.new_rng(seed, "expansion")
    ns = NS_NAMES[1:4]
    out = []
    for kind in ("Deployment", "ReplicaSet", "StatefulSet", "DaemonSet", "Job",
                 "ReplicationController"):
        for defect in (None, rng.choice(gen.DEFECTS)):
            out.append(workload(kind, f"{kind.lower()}-{defect or 'clean'}",
                                rng.choice(ns), defect, rng.randrange(1000)))
    cron = workload("Job", "nightly", ns[0], "privileged")
    obj = cron["object"]
    obj.update(apiVersion="batch/v1", kind="CronJob")
    obj["spec"] = {"schedule": "0 0 * * *",
                   "jobTemplate": {"spec": {"template": obj["spec"]["template"]}}}
    cron["kind"] = {"group": "batch", "version": "v1", "kind": "CronJob"}
    out.append(cron)
    svc = gen.make_service(7, ns[1], node_port=True)
    out.append({"uid": "uid-svc", "kind": {"group": "", "version": "v1", "kind": "Service"},
                "operation": "CREATE", "name": svc["metadata"]["name"], "namespace": ns[1],
                "userInfo": {"username": "alice"}, "object": svc})
    out.append(workload("Deployment", "in-gk-system", "gatekeeper-system", "privileged"))
    bare = workload("Deployment", "no-labels", ns[2], "dockerhub-image")
    bare["object"]["spec"]["template"]["metadata"] = {}
    out.append(bare)
    named = workload("Deployment", "named", ns[0], "latest-tag")
    named["object"]["spec"]["template"]["metadata"]["name"] = "named-pod"
    out.append(named)
    empty = workload("Deployment", "no-template", ns[1])
    del empty["object"]["spec"]["template"]
    out.append(empty)
    for i, defect in enumerate((None, "privileged", "foreign-repo")):
        pod = gen.make_pod(i, ns[i], defect, prefix="bare")
        out.append({"uid": f"uid-pod-{i}", "kind": {"group": "", "version": "v1", "kind": "Pod"},
                    "operation": "CREATE", "name": pod["metadata"]["name"],
                    "namespace": ns[i], "userInfo": {"username": "alice"}, "object": pod})
    rng.shuffle(out)
    return out


def verdict(resp):
    lines = sorted(filter(None, resp.message.split("\n"))) if not resp.allowed else []
    return resp.allowed, resp.code, lines


def expected(request, templates, mutate=None):
    return ref.admission_expectation(request, POLICY, CONFIG, templates, mutate)


def routes(reg):
    out = {}
    for k, v in reg.snapshot()["counters"].items():
        if k.startswith("webhook_batches_total{"):
            route = k.split('route="')[1].split('"')[0]
            out[route] = out.get(route, 0) + int(v)
    return out


@pytest.fixture(scope="module", params=["rego", "tpu"])
def client(request):
    return request.param, make_client(request.param)


def serve(client, expansion, reg, window_ms=2.0):
    server = WebhookServer(client, TARGET, excluder=excluder(),
                           namespace_getter=NAMESPACES.get, metrics=reg,
                           window_ms=window_ms, expansion_system=expansion)
    server.start()
    return server


def warm(client, server, requests):
    """Compile the fused path for these requests' rows (each workload
    and the Pods it implies), as the benchmark's set-up does."""
    exp = server.handler.expansion
    rows = []
    for r in requests:
        rows += [r] + [imp.request for imp in exp.expand(r, NAMESPACES.get)]
    aug = server.batcher.target_handler.augment_request
    assert client.warm_review_path([aug(r, NAMESPACES.get) for r in rows])


def handle_all(server, requests, batched):
    if not batched:
        return [server.handler.handle(r) for r in requests]
    # one burst inside one batch window: one entry per request
    with ThreadPoolExecutor(len(requests)) as pool:
        return list(pool.map(server.handler.handle, requests))


ACTIONS = [None, "dryrun", "deny"]


@pytest.mark.parametrize("action", ACTIONS)
@pytest.mark.parametrize("batched", [True, False], ids=["batched", "one-by-one"])
def test_answers_equal_the_reference(client, action, batched):
    driver, client = client
    t = template(action)
    exp = ExpansionSystem()
    exp.upsert(t)
    reg = MetricsRegistry()
    requests = cases(SEED + ACTIONS.index(action))
    server = serve(client, exp, reg, window_ms=500.0 if batched else 2.0)
    try:
        if driver == "tpu" and batched:
            warm(client, server, requests)
        got = [verdict(r) for r in handle_all(server, requests, batched)]
    finally:
        server.stop()
    want = [expected(r, [t]) for r in requests]
    assert got == want
    # the defects are found, the implied Pods' denials among them
    assert any("[Implied by expand-deployments]" in ln for _, _, lines in want for ln in lines) \
        == (action != "dryrun")
    if driver == "tpu":
        seen = routes(reg)
        if batched:
            assert seen.get("fused", 0) >= 1, seen
        else:
            assert set(seen) == {"interp"}, seen


def test_a_mutator_changes_the_implied_pod_before_validation(client):
    """An Assign that clears `privileged` on every Pod container: the
    Deployment whose template is privileged is admitted, as the Pods
    its ReplicaSet creates would be."""
    driver, client = client
    mutation = MutationSystem()
    mutation.upsert({
        "apiVersion": "mutations.gatekeeper.sh/v1alpha1",
        "kind": "Assign",
        "metadata": {"name": "unprivileged"},
        "spec": {
            "applyTo": [{"groups": [""], "versions": ["v1"], "kinds": ["Pod"]}],
            "location": "spec.containers[name:*].securityContext.privileged",
            "parameters": {"assign": {"value": False}},
        },
    })

    def unprivileged(obj):
        obj = copy.deepcopy(obj)
        for c in obj["spec"].get("containers", []):
            c.setdefault("securityContext", {})["privileged"] = False
        return obj

    exp = ExpansionSystem(mutation_system=mutation)
    exp.upsert(template())
    requests = [workload(k, f"m-{k.lower()}-{d}", "team-000", d, i)
                for i, (k, d) in enumerate([("Deployment", "privileged"),
                                            ("StatefulSet", "privileged"),
                                            ("Deployment", "privileged-init"),
                                            ("Job", "memory-12gi")] * 4)]
    reg = MetricsRegistry()
    server = serve(client, exp, reg, window_ms=500.0)
    try:
        if driver == "tpu":
            warm(client, server, requests)
        got = [verdict(r) for r in handle_all(server, requests, True)]
    finally:
        server.stop()
    assert got == [expected(r, [template()], unprivileged) for r in requests]
    assert got[0] == (True, 200, [])  # mutated: no longer privileged
    assert got != [expected(r, [template()]) for r in requests]


def test_implied_pod_shape():
    t = template_from_obj(template())
    req = workload("Deployment", "web", "team-001", i=3, replicas=4)
    req["object"]["spec"]["template"]["metadata"]["namespace"] = "elsewhere"
    obj = implied_object(req, t)
    assert obj["apiVersion"] == "v1" and obj["kind"] == "Pod"
    # named as the parent; in the parent's namespace; the template's labels
    assert obj["metadata"]["name"] == "web"
    assert obj["metadata"]["namespace"] == "team-001"
    assert obj["metadata"]["labels"]["app"] == "web"
    assert obj["spec"] is req["object"]["spec"]["template"]["spec"]
    (imp,) = _expand(req)
    r = imp.request
    assert r["kind"] == {"group": "", "version": "v1", "kind": "Pod"}
    assert r["resource"]["resource"] == "pods"
    assert (r["operation"], r["userInfo"], r["namespace"], r["uid"]) == (
        req["operation"], req["userInfo"], req["namespace"], req["uid"])
    assert r == ref.expand(req, [template()])[0][1]


def _expand(req):
    exp = ExpansionSystem()
    exp.upsert(template())
    return exp.expand(req)


BAD = {
    "no applyTo": lambda s: s.pop("applyTo"),
    "empty kinds": lambda s: s["applyTo"][0].update(kinds=[]),
    "templateSource not a path": lambda s: s.update(templateSource="spec..template"),
    "generatedGVK without a kind": lambda s: s["generatedGVK"].pop("kind"),
    "applies to its own output": lambda s: s["applyTo"].append(
        {"groups": [""], "versions": ["v1"], "kinds": ["Pod"]}),
    "unknown enforcementAction": lambda s: s.update(enforcementAction="warn"),
}


@pytest.mark.parametrize("defect", sorted(BAD))
def test_malformed_template_is_rejected_at_admission(defect):
    bad = template()
    BAD[defect](bad["spec"])
    with pytest.raises(ExpansionTemplateError):
        template_from_obj(bad)
    client = make_client("rego")
    server = serve(client, ExpansionSystem(), MetricsRegistry())
    try:
        resp = server.handler.handle({
            "uid": "t", "kind": {"group": "expansion.gatekeeper.sh", "version": "v1alpha1",
                                 "kind": "ExpansionTemplate"},
            "operation": "CREATE", "name": "expand-deployments",
            "userInfo": {"username": "alice"}, "object": bad})
        good = server.handler.handle({
            "uid": "t2", "kind": {"group": "expansion.gatekeeper.sh", "version": "v1alpha1",
                                  "kind": "ExpansionTemplate"},
            "operation": "CREATE", "name": "expand-deployments",
            "userInfo": {"username": "alice"}, "object": template()})
    finally:
        server.stop()
    assert (resp.allowed, resp.code) == (False, 422)
    assert (good.allowed, good.code) == (True, 200)


def wait_for(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.02)
    return cond()


def test_templates_added_replaced_and_deleted_while_serving():
    """Through the Runner's controller over a FakeCluster: a template
    loaded at boot, replaced with `enforcementAction: dryrun`, replaced
    by a malformed version (rejected, counted, not loaded), restored,
    and deleted; the answers follow each step."""
    cluster = FakeCluster()
    for obj in TEMPLATES + CONSTRAINTS + [CONFIG] + list(NAMESPACES.values()) + [template()]:
        cluster.apply(obj)
    runner = Runner(cluster, make_client("rego"), TARGET, operations=("webhook",),
                    audit_interval=1e9, readyz_port=None)
    runner.start()
    try:
        assert runner.wait_ready(timeout=30)
        exp = runner.expansion_system
        req = workload("Deployment", "web", "team-000", "privileged")

        def answer():
            return verdict(runner.webhook.handler.handle(req))

        def gauge(state):
            return runner.metrics.snapshot()["gauges"].get(
                f'expansion_templates{{state="{state}"}}')

        assert answer() == expected(req, [template()]) and not answer()[0]
        cluster.apply(template("dryrun"))
        assert wait_for(lambda: answer()[0])
        assert answer() == expected(req, [template("dryrun")])
        bad = template()
        bad["spec"]["templateSource"] = ""
        cluster.apply(bad)
        assert wait_for(lambda: gauge("rejected") == 1)
        assert gauge("loaded") == 0 and not exp.active
        assert answer() == expected(req, []) == (True, 200, [])
        cluster.apply(template())
        assert wait_for(lambda: not answer()[0])
        assert answer() == expected(req, [template()])
        assert (gauge("loaded"), gauge("rejected")) == (1, 0)
        cluster.delete(template())
        assert wait_for(lambda: answer()[0])
        assert not exp.active and gauge("loaded") == 0
    finally:
        runner.stop()


def test_denied_over_http_with_the_implied_message():
    """`/v1/admit` over HTTP: a Deployment with a privileged container
    in its pod template is denied with the implied Pod's message; its
    reviews are counted by origin and the expansion stage observed once."""
    reg = MetricsRegistry()
    exp = ExpansionSystem(metrics=reg)
    exp.upsert(template())
    server = serve(make_client("rego"), exp, reg)
    req = workload("Deployment", "web", "team-000", "privileged")
    body = json.dumps({"apiVersion": "admission.k8s.io/v1", "kind": "AdmissionReview",
                       "request": req}).encode()
    try:
        with urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{server.port}/v1/admit", data=body,
                headers={"Content-Type": "application/json"})) as f:
            out = json.load(f)["response"]
    finally:
        server.stop()
    assert out["allowed"] is False and out["status"]["code"] == 403
    lines = sorted(out["status"]["message"].split("\n"))
    assert lines == expected(req, [template()])[2]
    assert all("] [Implied by expand-deployments] Privileged container" in ln
               for ln in lines)
    snap = reg.snapshot()
    assert snap["counters"]['webhook_reviews_total{origin="original"}'] == 1
    assert snap["counters"]['webhook_reviews_total{origin="implied"}'] == 1
    assert snap["distributions"][
        'webhook_request_stage_seconds{stage="expand"}']["count"] == 1
    assert snap["gauges"]['expansion_templates{state="loaded"}'] == 1


def test_no_template_takes_todays_path():
    """With no template loaded the handler never looks one up: no
    expansion stage, no implied review, answers as without expansion."""
    reg = MetricsRegistry()
    exp = ExpansionSystem()
    calls = []
    exp.expand = lambda *a, **k: calls.append(a) or []
    server = serve(make_client("rego"), exp, reg)
    req = workload("Deployment", "web", "team-000", "privileged")
    try:
        resp = server.handler.handle(req)
    finally:
        server.stop()
    assert (resp.allowed, calls) == (True, [])
    snap = reg.snapshot()
    assert 'webhook_request_stage_seconds{stage="expand"}' not in snap["distributions"]
    assert 'webhook_reviews_total{origin="implied"}' not in snap["counters"]


def test_one_entry_per_workload_in_the_batcher():
    """A workload and its implied Pod are one batcher entry: a burst of
    n workloads is one batch of 2n rows under one future each."""
    from gatekeeper_tpu.webhook.server import MicroBatcher

    client = make_client("rego")
    batcher = MicroBatcher(client, TARGET, window_ms=300.0,
                           namespace_getter=NAMESPACES.get, metrics=MetricsRegistry())
    seen = []
    real = client.review_many
    client.review_many = lambda reviews: seen.append(len(reviews)) or real(reviews)
    exp = ExpansionSystem()
    exp.upsert(template())
    reqs = [workload("Deployment", f"d{i}", "team-001", "privileged", i) for i in range(5)]
    batcher.start()
    try:
        futs = [batcher.submit([r] + [imp.request for imp in exp.expand(r)]) for r in reqs]
        answers = [f.result(timeout=30) for f in futs]
    finally:
        batcher.stop()
    assert seen == [10]
    assert [len(a) for a in answers] == [2] * 5
    # the Deployment violates nothing; its Pod each K8sPSPPrivileged
    assert all(a[0] == [] and a[1] and all("Privileged" in r.msg for r in a[1])
               for a in answers)


def test_shipped_example_is_the_benchmark_template():
    """deploy/expansion/'s example loads, and is the template the
    benchmark's configuration runs."""
    path = os.path.join(gen.ROOT, "deploy", "expansion", "expansiontemplate.yaml")
    (shipped,) = gen.load_yaml_docs([path], "ExpansionTemplate")
    assert shipped == EXPAND
    t = template_from_obj(shipped)
    assert ("apps", "v1", "Deployment") in t.apply_to and ("batch", "v1", "Job") in t.apply_to
    assert t.generated_gvk == ("", "v1", "Pod") and t.enforcement_action is None


def test_grouped_entries_under_concurrent_submitters():
    """Many threads submit grouped entries of 1-4 rows while the worker
    cuts batches: every future resolves, once, to its own rows' results
    in order (switch interval shortened to shake out lost updates)."""
    import sys

    from gatekeeper_tpu.constraint.types import Response, Responses, Result
    from gatekeeper_tpu.webhook.server import MicroBatcher

    class EchoClient:
        """Answers each review with one result naming its request uid."""

        def review_many(self, reviews):
            return [Responses(by_target={TARGET: Response(target=TARGET, results=[
                Result(msg=r.admission_request["uid"])])}) for r in reviews]

    batcher = MicroBatcher(EchoClient(), TARGET, window_ms=1.0, max_batch=16)
    batcher.start()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    errors, done = [], []

    def submitter(t):
        try:
            for i in range(40):
                n = 1 + (t + i) % 4
                rows = [{"uid": f"{t}-{i}-{j}", "namespace": "team-000"} for j in range(n)]
                entry = rows if n > 1 else rows[0]
                got = batcher.result(batcher.submit(entry), 30)
                want = [[r["uid"]] for r in rows] if n > 1 else [rows[0]["uid"]]
                got = [[x.msg for x in g] for g in got] if n > 1 else [x.msg for x in got]
                if got != want:
                    errors.append((got, want))
            done.append(t)
        except Exception as e:  # reported below
            errors.append(e)

    threads = [threading.Thread(target=submitter, args=(t,)) for t in range(24)]
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
        batcher.stop()
    assert not any(th.is_alive() for th in threads)
    assert errors == [] and len(done) == len(threads)
