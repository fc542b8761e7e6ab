"""The library's two referential templates, k8suniqueserviceselector and
k8suniqueingresshost (library/general/uniqueserviceselector and
uniqueingresshost of the gatekeeper-library), in plain Python: each
reviewed object joined against the synced inventory.

Like the rest of the reference it imports nothing of the program. Each
rule takes the synced objects (the inventory: every object the Config
syncs, namespaced ones under their namespace) and answers, per
reviewed object, its violation messages as the Rego's `violation` set
holds them: one message per distinct value of `msg`.
"""

import re


def _api_version(obj):
    return obj.get("apiVersion") or ""


def _key(obj):
    meta = obj.get("metadata") or {}
    return meta.get("namespace") or "", meta.get("name") or ""


def flatten_selector(obj):
    """flatten_selector: "key:value" of each selector entry, sorted, joined
    by ","; undefined (None) when a value is not a string (concat)."""
    selector = (obj.get("spec") or {}).get("selector")
    items = selector.items() if isinstance(selector, dict) else ()
    if any(not isinstance(k, str) or not isinstance(v, str) for k, v in items):
        return None
    return ",".join(sorted(f"{k}:{v}" for k, v in items))


def unique_service_selector(inventory):
    """K8sUniqueServiceSelector: a core v1 Service whose selector equals
    that of another namespaced Service (any namespace), one message per
    such Service, named by its inventory key. -> {(ns, name): [msg]}"""
    services = [o for o in inventory
                if o.get("kind") == "Service" and (o.get("metadata") or {}).get("namespace")]
    by_selector = {}
    for s in services:
        sel = flatten_selector(s)
        if sel is not None:
            by_selector.setdefault(sel, []).append(s)
    out = {}
    for s in services:
        if _api_version(s) != "v1":
            continue  # the review's kind must be group "", version v1
        sel = flatten_selector(s)
        if sel is None:
            continue
        msgs = set()
        for other in by_selector[sel]:
            # identical(): same namespace, name, kind and apiVersion
            if _key(other) == _key(s) and _api_version(other) == _api_version(s):
                continue
            ns, name = _key(other)
            msgs.add(f"same selector as service <{name}> in namespace <{ns}>")
        if msgs:
            out[_key(s)] = sorted(msgs)
    return out


REVIEW_GROUP = re.compile(r"^(extensions|networking.k8s.io)$")
OTHER_API = re.compile(r"^(extensions|networking.k8s.io)/.+$")


def _hosts(obj):
    rules = (obj.get("spec") or {}).get("rules")
    if isinstance(rules, dict):
        rules = list(rules.values())
    if not isinstance(rules, list):
        return []
    return [r["host"] for r in rules if isinstance(r, dict) and "host" in r]


def unique_ingress_host(inventory):
    """K8sUniqueIngressHost: an Ingress of the extensions or
    networking.k8s.io group with a rule host that another such Ingress
    (other namespace or name) also carries, one message per such host.
    -> {(ns, name): [msg]}"""
    ingresses = [o for o in inventory
                 if o.get("kind") == "Ingress" and (o.get("metadata") or {}).get("namespace")]
    carriers = {}
    for other in ingresses:
        if OTHER_API.search(_api_version(other)):
            for host in _hosts(other):
                carriers.setdefault(host, set()).add(_key(other))
    out = {}
    for ing in ingresses:
        group = _api_version(ing).rpartition("/")[0]
        if not REVIEW_GROUP.search(group):
            continue
        msgs = {
            f"ingress host conflicts with an existing ingress <{host}>"
            for host in _hosts(ing)
            if carriers.get(host, set()) - {_key(ing)}
        }
        if msgs:
            out[_key(ing)] = sorted(msgs)
    return out


# constraint kind -> rule over the synced inventory
RULES = {
    "K8sUniqueServiceSelector": unique_service_selector,
    "K8sUniqueIngressHost": unique_ingress_host,
}
