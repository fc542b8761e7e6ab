"""The plain reference for workload expansion: what Gatekeeper must
answer for a workload that an ExpansionTemplate covers
(expansion.gatekeeper.sh/v1alpha1; Gatekeeper's docs, "Validating
Workload Resources using ExpansionTemplate").

For each template whose `applyTo` names the request's kind, the object
at `templateSource` becomes an object of the `generatedGVK`: its
metadata the template's, in the parent's namespace, named as the
template names it, else as the parent is named; its spec the
template's. It is reviewed under the parent's operation, user and
namespace, as the resource the kind's lowercase plural names. Its
violations join the parent's, each message prefixed
`[Implied by <template>]`, under the template's `enforcementAction`
when it sets one. A parent with nothing at `templateSource` implies
nothing.

Imports nothing of the program.
"""

from benchmark import reference


def _gvk(kind):
    return kind.get("group", ""), kind.get("version", ""), kind.get("kind", "")


def covers(template, request):
    """Does the template's applyTo name the request's kind?"""
    group, version, kind = _gvk(request.get("kind") or {})
    return any(
        group in rule.get("groups", []) and version in rule.get("versions", [])
        and kind in rule.get("kinds", [])
        for rule in template["spec"]["applyTo"]
    )


def implied_request(template, request, mutate=None):
    """The implied AdmissionRequest, or None when the parent has no
    object at `templateSource`. `mutate(obj) -> obj` stands for the
    mutators that cover the implied object."""
    parent = request.get("object") or {}
    src = parent
    for key in template["spec"]["templateSource"].split("."):
        src = src.get(key) if isinstance(src, dict) else None
    if not isinstance(src, dict):
        return None
    gen = template["spec"]["generatedGVK"]
    group, version, kind = gen.get("group", ""), gen["version"], gen["kind"]
    meta = dict(src.get("metadata") or {})
    namespace = request.get("namespace") or (parent.get("metadata") or {}).get("namespace")
    if namespace:
        meta["namespace"] = namespace
    if not meta.get("name"):
        meta["name"] = request.get("name") or (parent.get("metadata") or {}).get("name") or ""
    obj = {"apiVersion": f"{group}/{version}" if group else version, "kind": kind,
           "metadata": meta}
    if "spec" in src:
        obj["spec"] = src["spec"]
    if mutate is not None:
        obj = mutate(obj)
    return {
        "uid": request.get("uid"),
        "kind": {"group": group, "version": version, "kind": kind},
        "resource": {"group": group, "version": version, "resource": kind.lower() + "s"},
        "operation": request.get("operation"),
        "userInfo": request.get("userInfo"),
        "namespace": request.get("namespace"),
        "name": meta["name"],
        "object": obj,
    }


def expand(request, templates, mutate=None):
    """[(template, implied request)] for every template covering the
    request, in template-name order."""
    out = []
    for t in sorted(templates, key=lambda t: t["metadata"]["name"]):
        if covers(t, request):
            implied = implied_request(t, request, mutate)
            if implied is not None:
                out.append((t, implied))
    return out


def admission_expectation(request, policy, config, templates, mutate=None):
    """(allowed, code, sorted deny lines) for one AdmissionRequest, its
    implied requests included."""
    allowed, code, lines = reference.admission_expectation(request, policy, config)
    if reference.config_excludes(config, "webhook", request.get("object") or {}):
        return allowed, code, lines
    lines = list(lines)
    for template, implied in expand(request, templates, mutate):
        name = template["metadata"]["name"]
        override = template["spec"].get("enforcementAction")
        for _, cname, action, msg in policy.evaluate(implied["object"]):
            if (override or action) == "deny":
                lines.append(f"[denied by {cname}] [Implied by {name}] {msg}")
    lines.sort()
    return (not lines), (403 if lines else 200), lines
