"""Seeded workload resources and rollout traffic: Deployments (and the
other kinds an ExpansionTemplate covers) whose pod templates are the
admission pool's Pods (gen/policy.py `make_pod`), and the ReplicaSets
the deployment controller creates for them.

A rollout is a stream of application syncs, each a burst of
Deployment CREATEs in one namespace that share one pod template (one
chart applied by Argo CD, Flux or Helm): the same images, registry,
resources and annotations, with names, `app` labels and replicas of
their own. Every admitted Deployment is followed by its ReplicaSet,
from the deployment controller's account.

Pure Python: the load generator's child process may import this module
and must never import JAX or the program.
"""

import copy
import hashlib
import json

from benchmark.gen import openloop
from benchmark.gen import policy as gen

CONTROLLER = "system:serviceaccount:kube-system:deployment-controller"

# kind -> apiVersion of the workload kinds the tests build
WORKLOAD_API = {
    "Deployment": "apps/v1", "ReplicaSet": "apps/v1", "StatefulSet": "apps/v1",
    "DaemonSet": "apps/v1", "Job": "batch/v1", "ReplicationController": "v1",
}


def pod_template(i, namespace, defect=None):
    """The pod template of make_pod(i, namespace, defect): its labels,
    annotations and spec, without a name or namespace."""
    pod = gen.make_pod(i, namespace, defect)
    meta = pod["metadata"]
    return {"metadata": {"labels": meta["labels"], "annotations": meta["annotations"]},
            "spec": pod["spec"]}


def make_workload(kind, name, namespace, template, replicas=1, labels=None):
    """A workload of `kind` whose `spec.template` is `template`."""
    labels = dict(labels or {"app": name})
    spec = {"template": template}
    if kind != "Job":
        spec["selector"] = {"matchLabels": dict(labels)}
    if kind not in ("DaemonSet", "Job"):
        spec["replicas"] = replicas
    return {
        "apiVersion": WORKLOAD_API[kind],
        "kind": kind,
        "metadata": {"name": name, "namespace": namespace, "labels": labels},
        "spec": spec,
    }


def template_hash(template):
    """A stand-in for the controller's pod-template-hash: 10 hex digits
    of the template's content."""
    return hashlib.sha256(json.dumps(template, sort_keys=True).encode()).hexdigest()[:10]


def make_replicaset(deployment):
    """The ReplicaSet the deployment controller creates for a
    Deployment: its pod template plus the `pod-template-hash` label."""
    meta = deployment["metadata"]
    template = copy.deepcopy(deployment["spec"]["template"])
    h = template_hash(template)
    template["metadata"].setdefault("labels", {})["pod-template-hash"] = h
    labels = dict(meta["labels"], **{"pod-template-hash": h})
    rs = make_workload("ReplicaSet", f"{meta['name']}-{h}", meta["namespace"], template,
                       deployment["spec"]["replicas"], labels)
    rs["metadata"]["ownerReferences"] = [{
        "apiVersion": "apps/v1", "kind": "Deployment", "name": meta["name"],
        "controller": True, "blockOwnerDeletion": True,
    }]
    return rs


def workload_request(obj, uid, username):
    """A CREATE AdmissionRequest for `obj`."""
    api = obj["apiVersion"]
    group, version = api.split("/") if "/" in api else ("", api)
    kind = obj["kind"]
    return {
        "uid": uid,
        "kind": {"group": group, "version": version, "kind": kind},
        "resource": {"group": group, "version": version, "resource": kind.lower() + "s"},
        "operation": "CREATE",
        "name": obj["metadata"]["name"],
        "namespace": obj["metadata"]["namespace"],
        "userInfo": {"username": username},
        "object": obj,
    }


def iter_syncs(params, n_namespaces, rng, prefix="rollout"):
    """Application syncs, without end: (namespace, [Deployment]).
    Burst sizes are uniform on burst_min..burst_max: each block of that
    many syncs holds every size once, in an order the seed draws, so
    every seed asks the same amount of work. One Deployment in
    `1 / defect_share` carries one defect in its copy of the sync's pod
    template, the classes cycled (as steady.json's)."""
    names = gen.namespace_names(n_namespaces)[1:]
    every_bad = round(1 / params["defect_share"])
    lo, hi = params["replicas"]
    s = d = 0
    while True:
        block = list(range(params["burst_min"], params["burst_max"] + 1))
        rng.shuffle(block)
        for size in block:
            ns = names[rng.randrange(len(names))]
            t = rng.randrange(10**6)  # a fresh pod template for the sync
            deployments = []
            for j in range(size):
                defect = None
                if d % every_bad == every_bad - 1:
                    defect = gen.DEFECTS[(d // every_bad) % len(gen.DEFECTS)]
                d += 1
                template = pod_template(t, ns, defect)
                name = f"{prefix}-s{s:05d}-{j:02d}"
                template["metadata"]["labels"]["app"] = name
                deployments.append(make_workload(
                    "Deployment", name, ns, template, rng.randint(lo, hi)))
            s += 1
            yield ns, deployments


def make_rollout(n_reviews, seconds, params, n_namespaces, seed, admitted, prefix="rollout"):
    """(requests, offsets): application syncs at Poisson instants until
    the Deployments and their ReplicaSets reach `n_reviews`; each
    sync's Deployments `burst_gap_s` apart, each Deployment that
    `admitted(request)` admits followed by its ReplicaSet a uniform
    `replicaset_delay_s` later. The arrivals fit in `seconds`."""
    rng = gen.new_rng(seed, prefix)
    stream = iter_syncs(params, n_namespaces, rng, prefix)
    syncs, events, total = [], [], 0
    while total < n_reviews:
        _, deployments = next(stream)
        reqs = []
        for dep in deployments:
            req = workload_request(dep, "", "bench-user")
            rs = make_replicaset(dep) if admitted(req) else None
            reqs.append((req, rs))
            total += 1 + (rs is not None)
        syncs.append(reqs)
    lo_d, hi_d = params["replicaset_delay_s"]
    tail = params["burst_max"] * params["burst_gap_s"] + hi_d
    starts = openloop.arrival_offsets(len(syncs) / (seconds - tail), seconds - tail, f"{seed}:{prefix}")
    for start, reqs in zip(starts, syncs):
        for j, (req, rs) in enumerate(reqs):
            t = start + j * params["burst_gap_s"]
            events.append((t, req))
            if rs is not None:
                events.append((t + rng.uniform(lo_d, hi_d),
                               workload_request(rs, "", CONTROLLER)))
    events.sort(key=lambda e: e[0])
    requests = []
    for k, (_, req) in enumerate(events):
        req["uid"] = f"{prefix}-{k:06d}"
        requests.append(req)
    return requests, [t for t, _ in events]
