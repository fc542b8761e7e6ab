"""Workload expansion: ExpansionTemplates (expansion.gatekeeper.sh).

A Deployment, ReplicaSet, StatefulSet, DaemonSet or Job carries a pod
template; the Pod-kind constraints never see it until the controller
creates the Pods. An ExpansionTemplate tells the webhook to review the
Pod such a template implies alongside its parent, and to answer the
parent with the implied Pod's violations (docs/expansion.md).

  * `template` — the ExpansionTemplate type and its validation.
  * `system`   — the registry by parent GVK, `expand(request)` (the
                 implied object, mutated before it is validated) and
                 `aggregate` (the `[Implied by <name>]` prefix and the
                 action override).
"""

from .system import (  # noqa: F401
    ExpansionSystem,
    Implied,
    aggregate,
    implied_object,
    implied_request,
)
from .template import (  # noqa: F401
    ExpansionTemplate,
    ExpansionTemplateError,
    template_from_obj,
)
