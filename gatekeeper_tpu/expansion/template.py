"""The ExpansionTemplate type (expansion.gatekeeper.sh/v1alpha1) and its
validation.

An ExpansionTemplate names the workload kinds it applies to
(`spec.applyTo`: groups x versions x kinds), the dotted path of the
resource template inside them (`spec.templateSource`, e.g.
`spec.template`), the kind the template stands for
(`spec.generatedGVK`, e.g. v1 Pod) and, optionally, the
`enforcementAction` that replaces the constraint's for violations of
the implied resource (docs/expansion.md).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

EXPANSION_GROUP = "expansion.gatekeeper.sh"
EXPANSION_KIND = "ExpansionTemplate"

# the enforcement actions this build evaluates (policy.py)
ACTIONS = ("deny", "dryrun")

GVKTuple = Tuple[str, str, str]


class ExpansionTemplateError(ValueError):
    """A malformed ExpansionTemplate: rejected at admission of the
    template and never loaded by the controller."""


@dataclass(frozen=True)
class ExpansionTemplate:
    name: str
    apply_to: Tuple[GVKTuple, ...]  # (group, version, kind) of parents
    template_source: Tuple[str, ...]  # path of the template in a parent
    generated_gvk: GVKTuple
    enforcement_action: Optional[str] = None


def _strings(value: Any, what: str) -> Tuple[str, ...]:
    if not isinstance(value, list) or not value:
        raise ExpansionTemplateError(f"{what} must be a non-empty list")
    if not all(isinstance(v, str) for v in value):
        raise ExpansionTemplateError(f"{what} must hold strings")
    return tuple(value)


def template_from_obj(obj: Dict[str, Any]) -> ExpansionTemplate:
    """Parse and validate an ExpansionTemplate object; raises
    ExpansionTemplateError on anything malformed."""
    if not isinstance(obj, dict):
        raise ExpansionTemplateError("ExpansionTemplate is not an object")
    group = (obj.get("apiVersion") or "").partition("/")[0]
    if group != EXPANSION_GROUP or obj.get("kind") != EXPANSION_KIND:
        raise ExpansionTemplateError(
            f"not an {EXPANSION_GROUP} {EXPANSION_KIND}: "
            f"{obj.get('apiVersion')!r} {obj.get('kind')!r}"
        )
    name = (obj.get("metadata") or {}).get("name")
    if not isinstance(name, str) or not name:
        raise ExpansionTemplateError("ExpansionTemplate has no name")
    spec = obj.get("spec")
    if not isinstance(spec, dict):
        raise ExpansionTemplateError(f"{name}: spec must be an object")

    rules = spec.get("applyTo")
    if not isinstance(rules, list) or not rules:
        raise ExpansionTemplateError(f"{name}: applyTo must be a non-empty list")
    apply_to = []
    for i, rule in enumerate(rules):
        if not isinstance(rule, dict):
            raise ExpansionTemplateError(f"{name}: applyTo[{i}] must be an object")
        groups = _strings(rule.get("groups"), f"{name}: applyTo[{i}].groups")
        versions = _strings(rule.get("versions"), f"{name}: applyTo[{i}].versions")
        kinds = _strings(rule.get("kinds"), f"{name}: applyTo[{i}].kinds")
        if any(not v for v in versions) or any(not k for k in kinds):
            raise ExpansionTemplateError(
                f"{name}: applyTo[{i}] has an empty version or kind"
            )
        apply_to += [(g, v, k) for g in groups for v in versions for k in kinds]

    source = spec.get("templateSource")
    path = tuple(source.split(".")) if isinstance(source, str) else ()
    if not path or not all(path):
        raise ExpansionTemplateError(
            f"{name}: templateSource must be a dotted path, got {source!r}"
        )

    gen = spec.get("generatedGVK")
    if not isinstance(gen, dict):
        raise ExpansionTemplateError(f"{name}: generatedGVK must be an object")
    generated = (gen.get("group") or "", gen.get("version"), gen.get("kind"))
    if not all(isinstance(x, str) for x in generated) or not all(generated[1:]):
        raise ExpansionTemplateError(
            f"{name}: generatedGVK needs a version and a kind"
        )
    if generated in apply_to:
        raise ExpansionTemplateError(
            f"{name}: a template cannot apply to its own generatedGVK "
            f"{generated}"
        )

    action = spec.get("enforcementAction") or None
    if action is not None and action not in ACTIONS:
        raise ExpansionTemplateError(
            f"{name}: enforcementAction must be one of {ACTIONS}, got {action!r}"
        )
    return ExpansionTemplate(
        name=name,
        apply_to=tuple(dict.fromkeys(apply_to)),
        template_source=path,
        generated_gvk=generated,
        enforcement_action=action,
    )
