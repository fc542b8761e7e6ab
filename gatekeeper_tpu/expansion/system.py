"""The expansion registry and the implied review.

`ExpansionSystem` holds the loaded ExpansionTemplates keyed by the
parent's GVK. `expand(request)` builds, for each template that covers
the request's kind, the implied request: the resource template at
`templateSource` turned into an object of the `generatedGVK`, passed
through the mutators whose match covers it (mutate-then-validate), in
the parent's namespace and under the parent's operation and user.
`aggregate` puts the implied reviews' results back on the parent's:
each message prefixed `[Implied by <template>]`, and the template's
`enforcementAction`, when set, in place of the constraint's
(docs/expansion.md).

Reads are lock-free: every change swaps in a new GVK index, so a
request sees one consistent template set, and with no template loaded
`active` is False and the webhook skips the lookup altogether.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

from .template import (
    ExpansionTemplate,
    ExpansionTemplateError,
    GVKTuple,
    template_from_obj,
)


class Implied(NamedTuple):
    template: ExpansionTemplate
    request: Dict[str, Any]  # the implied AdmissionRequest


def implied_object(
    request: Dict[str, Any], template: ExpansionTemplate
) -> Optional[Dict[str, Any]]:
    """The object the parent's resource template stands for, or None
    when the parent has no object at `templateSource`. Its metadata is
    the template's, in the parent's namespace, named as the template
    names it, else as the parent is named. Its spec is the template's,
    shared read-only with the parent."""
    parent = request.get("object")
    src: Any = parent
    for key in template.template_source:
        src = src.get(key) if isinstance(src, dict) else None
    if not isinstance(src, dict):
        return None
    parent_meta = parent.get("metadata") or {}
    meta = dict(src.get("metadata") or {})
    namespace = request.get("namespace") or parent_meta.get("namespace")
    if namespace:
        meta["namespace"] = namespace
    if not meta.get("name"):
        meta["name"] = request.get("name") or parent_meta.get("name") or ""
    group, version, kind = template.generated_gvk
    obj = {
        "apiVersion": f"{group}/{version}" if group else version,
        "kind": kind,
        "metadata": meta,
    }
    if "spec" in src:
        obj["spec"] = src["spec"]
    return obj


def implied_request(
    request: Dict[str, Any], template: ExpansionTemplate, obj: Dict[str, Any]
) -> Dict[str, Any]:
    """The parent's AdmissionRequest re-aimed at the implied object: its
    uid, operation, userInfo, namespace and dryRun kept, its kind and
    resource the generated GVK's (the resource is the kind's lowercase
    plural, exact for Pod)."""
    group, version, kind = template.generated_gvk
    out = {
        k: request[k]
        for k in ("uid", "operation", "userInfo", "namespace", "dryRun")
        if k in request
    }
    out.update(
        kind={"group": group, "version": version, "kind": kind},
        resource={"group": group, "version": version,
                  "resource": kind.lower() + "s"},
        name=obj["metadata"]["name"],
        object=obj,
    )
    return out


def aggregate(parent_results: Sequence[Any],
              implied: Sequence[Tuple[Implied, Sequence[Any]]]) -> List[Any]:
    """The parent's results followed by each implied review's, those
    prefixed `[Implied by <template>]` and under the template's
    enforcementAction when it sets one (upstream pkg/expansion's
    aggregation). Results are copied, never changed in place."""
    out = list(parent_results)
    for imp, results in implied:
        action = imp.template.enforcement_action
        prefix = f"[Implied by {imp.template.name}] "
        out += [
            dataclasses.replace(
                r, msg=prefix + (r.msg or ""),
                enforcement_action=action or r.enforcement_action,
            )
            for r in results
        ]
    return out


class ExpansionSystem:
    """Loaded ExpansionTemplates by parent GVK; the controller keeps it
    in step with the cluster, the webhook expands through it."""

    def __init__(self, mutation_system=None, metrics=None):
        self.mutation_system = mutation_system
        self.metrics = metrics
        self._lock = threading.Lock()
        self._templates: Dict[str, ExpansionTemplate] = {}
        self.rejected: Dict[str, str] = {}  # template name -> error
        self._by_gvk: Dict[GVKTuple, Tuple[ExpansionTemplate, ...]] = {}
        self.active = False

    # -- registry ------------------------------------------------------------

    def upsert(self, obj: Dict[str, Any]) -> ExpansionTemplate:
        """Load (or replace) a template. A malformed one raises
        ExpansionTemplateError, and replaces the version it updates
        with nothing: the cluster no longer holds that version."""
        name = ((obj or {}).get("metadata") or {}).get("name") or ""
        try:
            template = template_from_obj(obj)
        except ExpansionTemplateError as e:
            with self._lock:
                self._templates.pop(name, None)
                self.rejected[name] = str(e)
                self._rebuild_locked()
            raise
        with self._lock:
            self._templates[template.name] = template
            self.rejected.pop(template.name, None)
            self._rebuild_locked()
        return template

    def remove(self, name: str) -> None:
        with self._lock:
            self._templates.pop(name, None)
            self.rejected.pop(name, None)
            self._rebuild_locked()

    def _rebuild_locked(self) -> None:
        by_gvk: Dict[GVKTuple, List[ExpansionTemplate]] = {}
        for name in sorted(self._templates):
            t = self._templates[name]
            for gvk in t.apply_to:
                by_gvk.setdefault(gvk, []).append(t)
        self._by_gvk = {k: tuple(v) for k, v in by_gvk.items()}
        self.active = bool(self._by_gvk)
        if self.metrics is not None:
            self.metrics.gauge(
                "expansion_templates", len(self._templates), state="loaded"
            )
            self.metrics.gauge(
                "expansion_templates", len(self.rejected), state="rejected"
            )

    # -- expansion -----------------------------------------------------------

    def expand(
        self,
        request: Dict[str, Any],
        namespace_getter: Optional[Callable[[str], Optional[dict]]] = None,
    ) -> List[Implied]:
        """The implied requests of `request`, one per template covering
        its kind that finds a resource template in it; [] for any other
        request. Each implied object has been through the mutators that
        match it."""
        kind = request.get("kind") or {}
        templates = self._by_gvk.get(
            (kind.get("group", ""), kind.get("version", ""), kind.get("kind", ""))
        )
        out = []
        for t in templates or ():
            obj = implied_object(request, t)
            if obj is not None:
                out.append(Implied(t, self._mutate(
                    implied_request(request, t, obj), namespace_getter)))
        return out

    def _mutate(self, request: Dict[str, Any], namespace_getter) -> Dict[str, Any]:
        """The implied request with its object through the mutators
        whose match and applyTo cover it (host screen, fixpoint apply;
        a ConvergenceError propagates: such an object is never
        validated, as it would never be admitted)."""
        system = self.mutation_system
        if system is None or not system.count():
            return request
        review = dict(request)
        namespace = request.get("namespace") or ""
        ns_obj = namespace_getter(namespace) if namespace and namespace_getter else None
        if ns_obj is not None:
            review["_unstable"] = {"namespace": ns_obj}
        muts, matrix = system.screen_host([review])
        selected = [m for j, m in enumerate(muts) if matrix[j, 0]]
        if not selected:
            return request
        mutated, _ = system.apply(request["object"], review, selected)
        return dict(request, object=mutated)
