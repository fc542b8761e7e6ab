"""Seeded Services and Ingresses for the referential audit deployment:
the objects the library's uniqueserviceselector and uniqueingresshost
templates join over, in Kubernetes' own shapes.

Every share is a fixed count of each batch the seed only places: a
batch `k` of `n` objects carries floor(share * n * (k + 1)) -
floor(share * n * k) of a kind of object, so a run of batches holds
the share exactly and every seed asks the same amount of work.

Pure Python, like `policy`: it never imports JAX or the program.
"""

import math

from benchmark.gen.policy import namespace_names

SELECTOR_KEYS = ("tier", "component")
TIERS = ("web", "api", "db", "cache", "queue")


def quota(share, n, k):
    """Members of a share that fall in batch k of n objects."""
    return math.floor(share * n * (k + 1)) - math.floor(share * n * k)


def pair_quota(share, n, k):
    """Pairs of a share (two objects each) that fall in batch k."""
    return math.floor(share * n * (k + 1) / 2) - math.floor(share * n * k / 2)


def _labels(app, team, owner):
    labels = {"app": app}
    if owner:
        labels["owner"] = team
    return labels


def make_service(i, namespace, selector, node_port=False, owner=True, headless=False):
    """Service `svc-<i>` (`-headless` for the headless half of a pair)."""
    name = f"svc-{i:06d}" + ("-headless" if headless else "")
    spec = {
        "type": "NodePort" if node_port else "ClusterIP",
        "selector": dict(selector),
        "ports": [{"name": "http", "port": 80, "targetPort": 8080, "protocol": "TCP"}],
    }
    if headless:
        spec["clusterIP"] = "None"
    return {
        "apiVersion": "v1",
        "kind": "Service",
        "metadata": {
            "name": name,
            "namespace": namespace,
            "labels": _labels(selector["app"], f"team-{i % 7}", owner),
        },
        "spec": spec,
    }


def make_services(n, start, k, inv, rng):
    """Batch k of n Services numbered from `start`. A share of them
    (`selector_pair_share`) stand in same-namespace pairs, a client
    and a headless Service with one selector (a StatefulSet's); every
    other selector is unique in the cluster. `nodeport_share` are
    NodePort, `no_owner_share` lack the `owner` label."""
    pairs = pair_quota(inv["selector_pair_share"], n, k)
    units = n - pairs
    paired = set(rng.sample(range(units), pairs))
    names = namespace_names(inv["namespaces"])[1:]
    out = []
    i = start
    for u in range(units):
        ns = names[rng.randrange(len(names))]
        selector = {"app": f"app-{i:06d}"}
        for key in rng.sample(SELECTOR_KEYS, rng.randrange(3)):
            selector[key] = TIERS[rng.randrange(len(TIERS))]
        out.append(make_service(i, ns, selector))
        if u in paired:
            out.append(make_service(i, ns, selector, headless=True))
        i += 1
    clients = [s for s in out if "clusterIP" not in s["spec"]]
    for s in rng.sample(clients, quota(inv["nodeport_share"], n, k)):
        s["spec"]["type"] = "NodePort"
    for s in rng.sample(out, quota(inv["no_owner_share"], n, k)):
        del s["metadata"]["labels"]["owner"]
    return out


def make_ingress(i, namespace, hosts, group, owner=True):
    return {
        "apiVersion": f"{group}/v1beta1",
        "kind": "Ingress",
        "metadata": {
            "name": f"ing-{i:06d}",
            "namespace": namespace,
            "labels": _labels(f"app-{i:06d}", f"team-{i % 7}", owner),
        },
        "spec": {
            "rules": [
                {"host": h, "http": {"paths": [{"path": "/", "backend": {
                    "serviceName": f"svc-{i:06d}", "servicePort": 80}}]}}
                for h in hosts
            ],
        },
    }


def make_ingresses(n, start, k, inv, rng):
    """Batch k of n Ingresses numbered from `start`, alternating between
    the extensions and networking.k8s.io groups, each with 1 to 3 rules
    of one host. A share (`shared_host_share`) stand in pairs across
    two namespaces that share one host; every other host is unique.
    `no_owner_share` lack the `owner` label."""
    names = namespace_names(inv["namespaces"])[1:]
    out = []
    for j in range(n):
        i = start + j
        ns = names[rng.randrange(len(names))]
        hosts = [f"h{i:06d}-{r}.example.com" for r in range(1 + rng.randrange(3))]
        group = ("extensions", "networking.k8s.io")[i % 2]
        out.append(make_ingress(i, ns, hosts, group))
    pairs = pair_quota(inv["shared_host_share"], n, k)
    members = rng.sample(range(n), 2 * pairs)
    for a, b in zip(members[::2], members[1::2]):
        first, second = out[a], out[b]
        if first["metadata"]["namespace"] == second["metadata"]["namespace"]:
            second["metadata"]["namespace"] = next(
                x for x in names if x != first["metadata"]["namespace"])
        second["spec"]["rules"][0]["host"] = first["spec"]["rules"][0]["host"]
    for s in rng.sample(out, quota(inv["no_owner_share"], n, k)):
        del s["metadata"]["labels"]["owner"]
    return out
