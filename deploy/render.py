"""Templated deployment manifests from one values source — the
helm-chart equivalent (reference: charts/gatekeeper/ values.yaml +
templates/; there is no helm binary in this toolchain, so the chart is a
Python generator with the same knob surface).

    python deploy/render.py                        # defaults -> stdout
    python deploy/render.py --set replicas=3 --set image.tag=v0.2.0
    python deploy/render.py --values my-values.yaml

`deploy/gatekeeper-tpu.yaml` is the rendered DEFAULTS (kept in sync by
tests/test_deploy_render.py); edit values here, not the output.

Design notes carried over from the hand-written manifest:
  * operations split (pkg/operations/operations.go:15-19): separate
    webhook + audit Deployments, each holding full replicated policy
    state; the audit pod schedules onto a TPU node (the fused sweep is
    the throughput path), webhook pods are CPU replicas;
  * webhook replicas default to 3 (docs/fleet.md): the cert store is
    the SHARED Secret (`certSecret`, load-or-create + conflict retry,
    rotation picked up by peers without restart — certs.go:119-181
    behaviorally), the cache/breaker state plane gossips through
    FleetState CRs, and a PodDisruptionBudget keeps at least one
    replica through voluntary disruption;
  * the compile-cache volume turns pod restarts into warm boots. XLA
    reads every entry on it, so share one volume only among identical
    hosts (docs/compile.md): a mixed node pool needs a volume (or
    subPath) per pool. Ready gates on state replay only
    (serve-while-compiling), so a cold cache degrades latency briefly,
    never availability;
  * RBAC is a scoped ClusterRole (read-everything + CRUD on CRDs,
    gatekeeper.sh groups, Events, the VWH, cert Secrets), mirroring
    gatekeeper-manager-role — never cluster-admin (ADVICE r4).
"""

from __future__ import annotations

import argparse
import copy
import sys
from typing import Any, Dict, List

import yaml

DEFAULT_VALUES: Dict[str, Any] = {
    "namespace": "gatekeeper-system",
    "image": {
        "repository": "gatekeeper-tpu",
        "tag": "latest",
        "pullPolicy": "IfNotPresent",
    },
    # webhook pods (CPU, latency path): HA by default now that certs
    # live in the shared Secret and cache/breaker state gossips through
    # the fleet plane (docs/fleet.md)
    "replicas": 3,
    # the Secret-backed shared cert store (fleet.SecretCertStore); ""
    # falls back to pod-local emptyDir certs (single-replica debugging)
    "certSecret": "gatekeeper-webhook-server-cert",
    # minimum webhook replicas that must survive voluntary disruption
    "pdbMinAvailable": 1,
    "auditInterval": 60,
    "constraintViolationsLimit": 20,
    "auditFromCache": False,
    "disableValidatingWebhook": False,
    "logDenies": True,
    "emitAdmissionEvents": True,
    "emitAuditEvents": True,
    # None -> [namespace]: gatekeeper's own namespace must stay exempt
    # or a restrictive constraint can deny recreation of the webhook
    # pod itself (self-deadlock)
    "exemptNamespaces": None,
    "webhookPort": 8443,
    "healthPort": 9090,
    "prometheusPort": 8888,
    "webhookTimeoutSeconds": 3,
    # fail-open (policy.go:80): audit is the backstop
    "webhookFailurePolicy": "Ignore",
    "vwhName": "gatekeeper-validating-webhook-configuration",
    # mutation plane (/v1/mutate): fail-open like validation — a missed
    # mutation is corrected by nothing, but blocking all admission on a
    # mutation-webhook outage is worse (reference default Ignore)
    "disableMutation": False,
    "mutationFailurePolicy": "Ignore",
    "mwhName": "gatekeeper-mutating-webhook-configuration",
    "minDeviceBatch": None,  # GATEKEEPER_TPU_MIN_DEVICE_BATCH override
    "nodeSelector": {},  # webhook pods
    "tolerations": [],
    "resources": {},  # webhook container resources
    "audit": {
        # one replica on a TPU node: the 100k x 500 fused sweep
        "nodeSelector": {
            "cloud.google.com/gke-tpu-accelerator": "tpu-v5-lite-podslice",
            "cloud.google.com/gke-tpu-topology": "1x1",
        },
        "tolerations": [],
        "resources": {"limits": {"google.com/tpu": "1"}},
    },
    # emptyDir by default; set to a PVC claim name for persistent warm
    # XLA compile caches across pod restarts (JAX_COMPILATION_CACHE_DIR
    # in the image; docs/compile.md).
    "compileCachePVC": None,
}


def _merge(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    out = dict(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _merge(out[k], v)
        else:
            out[k] = v
    return out


def _set_path(values: Dict[str, Any], dotted: str, raw: str) -> None:
    node = values
    parts = dotted.split(".")
    for p in parts[:-1]:
        node = node.setdefault(p, {})
    node[parts[-1]] = yaml.safe_load(raw)


def _cache_volume(v):
    if v["compileCachePVC"]:
        return {
            "name": "xla-cache",
            "persistentVolumeClaim": {"claimName": v["compileCachePVC"]},
        }
    return {"name": "xla-cache", "emptyDir": {}}


def _container(v, name: str, args: List[str]):
    env = [
        {
            "name": "POD_NAME",
            "valueFrom": {"fieldRef": {"fieldPath": "metadata.name"}},
        }
    ]
    if v["minDeviceBatch"] is not None:
        env.append(
            {
                "name": "GATEKEEPER_TPU_MIN_DEVICE_BATCH",
                "value": str(v["minDeviceBatch"]),
            }
        )
    return {
        "name": name,
        "image": f"{v['image']['repository']}:{v['image']['tag']}",
        "imagePullPolicy": v["image"]["pullPolicy"],
        "args": args,
        "env": env,
    }


def _deployment(v, name: str, operation: str, spec_pod: Dict[str, Any],
                replicas: int):
    labels = {"gatekeeper.sh/operation": operation}
    return {
        "apiVersion": "apps/v1",
        "kind": "Deployment",
        "metadata": {"name": name, "namespace": v["namespace"]},
        "spec": {
            "replicas": replicas,
            # distinct dicts: a shared reference makes the YAML dumper
            # emit anchors/aliases that confuse downstream tooling
            "selector": {"matchLabels": dict(labels)},
            "template": {
                "metadata": {"labels": labels},
                "spec": {
                    "serviceAccountName": "gatekeeper-admin",
                    **spec_pod,
                },
            },
        },
    }


def _crd(group: str, kind: str, plural: str, scope: str,
         versions: List[str]):
    """Structural CRD with an open schema — the framework validates
    content itself (constraint-kind CRDs are created at runtime by the
    template controller; these are the base CRDs the chart ships,
    charts/gatekeeper/templates/*-customresourcedefinition.yaml)."""
    return {
        "apiVersion": "apiextensions.k8s.io/v1",
        "kind": "CustomResourceDefinition",
        "metadata": {"name": f"{plural}.{group}"},
        "spec": {
            "group": group,
            "names": {
                "kind": kind,
                "listKind": f"{kind}List",
                "plural": plural,
                "singular": kind.lower(),
            },
            "scope": scope,
            "versions": [
                {
                    "name": ver,
                    "served": True,
                    "storage": ver == versions[0],
                    "schema": {
                        "openAPIV3Schema": {
                            "type": "object",
                            "x-kubernetes-preserve-unknown-fields": True,
                        }
                    },
                    "subresources": {"status": {}},
                }
                for ver in versions
            ],
        },
    }


def render(values: Dict[str, Any] | None = None) -> List[Dict[str, Any]]:
    """Values -> list of manifest documents."""
    v = _merge(DEFAULT_VALUES, values or {})
    ns = v["namespace"]

    docs: List[Dict[str, Any]] = [
        _crd("templates.gatekeeper.sh", "ConstraintTemplate",
             "constrainttemplates", "Cluster", ["v1beta1", "v1alpha1"]),
        _crd("config.gatekeeper.sh", "Config", "configs", "Namespaced",
             ["v1alpha1"]),
        _crd("status.gatekeeper.sh", "ConstraintPodStatus",
             "constraintpodstatuses", "Namespaced", ["v1beta1"]),
        _crd("status.gatekeeper.sh", "ConstraintTemplatePodStatus",
             "constrainttemplatepodstatuses", "Namespaced", ["v1beta1"]),
        _crd("status.gatekeeper.sh", "MutatorPodStatus",
             "mutatorpodstatuses", "Namespaced", ["v1beta1"]),
        _crd("status.gatekeeper.sh", "ProviderPodStatus",
             "providerpodstatuses", "Namespaced", ["v1beta1"]),
        # external-data Providers (docs/externaldata.md): out-of-band
        # lookup endpoints the external_data builtin resolves through,
        # batched per micro-batch by the webhook pods
        _crd("externaldata.gatekeeper.sh", "Provider", "providers",
             "Cluster", ["v1alpha1"]),
        # fleet state plane (docs/fleet.md): one CR per webhook replica
        # gossiping external-data cache entries + breaker trips
        _crd("fleet.gatekeeper.sh", "FleetState", "fleetstates",
             "Namespaced", ["v1alpha1"]),
        # the mutation CRDs (pkg/mutation in the reference; the TPU
        # build screens their Match specs with the same kernel as
        # constraints)
        _crd("mutations.gatekeeper.sh", "Assign", "assign", "Cluster",
             ["v1alpha1"]),
        _crd("mutations.gatekeeper.sh", "AssignMetadata", "assignmetadata",
             "Cluster", ["v1alpha1"]),
        _crd("mutations.gatekeeper.sh", "ModifySet", "modifyset", "Cluster",
             ["v1alpha1"]),
        {
            "apiVersion": "v1",
            "kind": "Namespace",
            "metadata": {"name": ns},
        },
        {
            "apiVersion": "v1",
            "kind": "ServiceAccount",
            "metadata": {"name": "gatekeeper-admin", "namespace": ns},
        },
        {
            "apiVersion": "rbac.authorization.k8s.io/v1",
            "kind": "ClusterRole",
            "metadata": {"name": "gatekeeper-tpu-manager-role"},
            "rules": [
                # audit's discovery-list mode + config sync watch every
                # listable kind
                {
                    "apiGroups": ["*"],
                    "resources": ["*"],
                    "verbs": ["get", "list", "watch"],
                },
                {
                    "apiGroups": ["apiextensions.k8s.io"],
                    "resources": ["customresourcedefinitions"],
                    "verbs": ["create", "delete", "get", "list", "patch",
                              "update", "watch"],
                },
                {
                    "apiGroups": [
                        "config.gatekeeper.sh",
                        "constraints.gatekeeper.sh",
                        "externaldata.gatekeeper.sh",
                        "fleet.gatekeeper.sh",
                        "mutations.gatekeeper.sh",
                        "templates.gatekeeper.sh",
                        "status.gatekeeper.sh",
                    ],
                    "resources": ["*"],
                    "verbs": ["create", "delete", "get", "list", "patch",
                              "update", "watch"],
                },
                # workload expansion (docs/expansion.md): the runner
                # watches ExpansionTemplates; their CRD ships in
                # deploy/expansion/
                {
                    "apiGroups": ["expansion.gatekeeper.sh"],
                    "resources": ["expansiontemplates"],
                    "verbs": ["get", "list", "watch"],
                },
                {
                    "apiGroups": [""],
                    "resources": ["events"],
                    "verbs": ["create", "patch", "update", "get"],
                },
                {
                    "apiGroups": [""],
                    "resources": ["secrets"],
                    "verbs": ["create", "delete", "get", "list", "patch",
                              "update", "watch"],
                },
                {
                    "apiGroups": ["admissionregistration.k8s.io"],
                    "resources": ["validatingwebhookconfigurations",
                                  "mutatingwebhookconfigurations"],
                    "verbs": ["create", "get", "list", "patch", "update",
                              "watch"],
                },
            ],
        },
        {
            "apiVersion": "rbac.authorization.k8s.io/v1",
            "kind": "ClusterRoleBinding",
            "metadata": {"name": "gatekeeper-admin"},
            "roleRef": {
                "apiGroup": "rbac.authorization.k8s.io",
                "kind": "ClusterRole",
                "name": "gatekeeper-tpu-manager-role",
            },
            "subjects": [
                {
                    "kind": "ServiceAccount",
                    "name": "gatekeeper-admin",
                    "namespace": ns,
                }
            ],
        },
        {
            "apiVersion": "v1",
            "kind": "Service",
            "metadata": {
                "name": "gatekeeper-webhook-service",
                "namespace": ns,
            },
            "spec": {
                "selector": {"gatekeeper.sh/operation": "webhook"},
                "ports": [{"port": 443, "targetPort": v["webhookPort"]}],
            },
        },
    ]
    if v["certSecret"]:
        # the shared cert store (docs/fleet.md): shipped EMPTY — the
        # first replica to boot wins the load-or-create race and
        # populates it; peers adopt its CA and pick up rotations from
        # the watch without restart (certs.go:119-181 behaviorally)
        docs.append(
            {
                "apiVersion": "v1",
                "kind": "Secret",
                "metadata": {"name": v["certSecret"], "namespace": ns},
                "type": "Opaque",
            }
        )
        # HA is only real if voluntary disruption cannot drain every
        # webhook replica at once
        docs.append(
            {
                "apiVersion": "policy/v1",
                "kind": "PodDisruptionBudget",
                "metadata": {
                    "name": "gatekeeper-webhook-pdb",
                    "namespace": ns,
                },
                "spec": {
                    "minAvailable": v["pdbMinAvailable"],
                    "selector": {
                        "matchLabels": {
                            "gatekeeper.sh/operation": "webhook"
                        }
                    },
                },
            }
        )

    webhook_args = [
        "--operation=webhook",
        "--operation=status",
        f"--port={v['webhookPort']}",
        f"--health-addr-port={v['healthPort']}",
        f"--prometheus-port={v['prometheusPort']}",
    ]
    if v["certSecret"]:
        # Secret-backed shared cert store; certs are fetched/offered
        # through the API, no pod-local cert volume remains
        webhook_args.append(f"--cert-secret={v['certSecret']}")
        webhook_args.append(f"--fleet-namespace={ns}")
    if v["logDenies"]:
        webhook_args.append("--log-denies")
    if v["emitAdmissionEvents"]:
        webhook_args.append("--emit-admission-events")
    if not v["disableValidatingWebhook"]:
        webhook_args.append(f"--vwh-name={v['vwhName']}")
    exempt = v["exemptNamespaces"]
    if exempt is None:
        exempt = [ns]
    webhook_args += [f"--exempt-namespace={e}" for e in exempt]
    webhook_ctr = _container(v, "webhook", webhook_args)
    webhook_ctr["ports"] = [{"containerPort": v["webhookPort"]}]
    webhook_ctr["readinessProbe"] = {
        "httpGet": {"path": "/readyz", "port": v["healthPort"]},
        "periodSeconds": 5,
        "failureThreshold": 12,
    }
    # the Secret-backed store needs NO cert volume: artifacts flow
    # through the API and the rotator caches them in a process-private
    # temp dir. The pod-local emptyDir path survives only for the
    # explicit certSecret="" opt-out (single-replica debugging).
    webhook_ctr["volumeMounts"] = [
        {"name": "xla-cache", "mountPath": "/cache"},
    ]
    webhook_vols = [_cache_volume(v)]
    if not v["certSecret"]:
        webhook_ctr["volumeMounts"].insert(
            0, {"name": "certs", "mountPath": "/certs"}
        )
        webhook_ctr["args"].append("--cert-dir=/certs")
        webhook_vols.insert(0, {"name": "certs", "emptyDir": {}})
    if v["resources"]:
        webhook_ctr["resources"] = v["resources"]
    webhook_pod: Dict[str, Any] = {
        "containers": [webhook_ctr],
        "volumes": webhook_vols,
    }
    if v["nodeSelector"]:
        webhook_pod["nodeSelector"] = v["nodeSelector"]
    if v["tolerations"]:
        webhook_pod["tolerations"] = v["tolerations"]
    docs.append(
        _deployment(v, "gatekeeper-webhook", "webhook", webhook_pod,
                    v["replicas"])
    )

    audit_args = [
        "--operation=audit",
        "--operation=status",
        f"--health-addr-port={v['healthPort']}",
        f"--prometheus-port={v['prometheusPort']}",
        f"--audit-interval={v['auditInterval']}",
        f"--constraint-violations-limit={v['constraintViolationsLimit']}",
    ]
    if v["auditFromCache"]:
        audit_args.append("--audit-from-cache")
    if v["emitAuditEvents"]:
        audit_args.append("--emit-audit-events")
    audit_ctr = _container(v, "audit", audit_args)
    audit_ctr["resources"] = v["audit"]["resources"]
    audit_ctr["readinessProbe"] = {
        "httpGet": {"path": "/readyz", "port": v["healthPort"]},
        "periodSeconds": 10,
        "failureThreshold": 60,
    }
    audit_ctr["volumeMounts"] = [
        {"name": "xla-cache", "mountPath": "/cache"},
    ]
    audit_pod: Dict[str, Any] = {
        "containers": [audit_ctr],
        "volumes": [_cache_volume(v)],
    }
    if v["audit"]["nodeSelector"]:
        audit_pod["nodeSelector"] = v["audit"]["nodeSelector"]
    if v["audit"]["tolerations"]:
        audit_pod["tolerations"] = v["audit"]["tolerations"]
    docs.append(
        _deployment(v, "gatekeeper-audit", "audit", audit_pod, 1)
    )

    # one namespace-exclusion selector shared VERBATIM by the validating
    # and mutating configs (namespaces opted out with the ignore label
    # must skip BOTH planes, or a mutated-but-unvalidated object slips
    # through the gap)
    def _ns_exclusions():
        return {
            "matchExpressions": [
                {
                    "key": "admission.gatekeeper.sh/ignore",
                    "operator": "DoesNotExist",
                }
            ]
        }

    if not v["disableValidatingWebhook"]:
        docs.append(
            {
                "apiVersion": "admissionregistration.k8s.io/v1",
                "kind": "ValidatingWebhookConfiguration",
                "metadata": {"name": v["vwhName"]},
                "webhooks": [
                    {
                        "name": "validation.gatekeeper.sh",
                        "admissionReviewVersions": ["v1"],
                        "sideEffects": "None",
                        "failurePolicy": v["webhookFailurePolicy"],
                        "timeoutSeconds": v["webhookTimeoutSeconds"],
                        "namespaceSelector": _ns_exclusions(),
                        "clientConfig": {
                            # caBundle injected + self-healed by the
                            # running pods (--vwh-name, CaBundleInjector)
                            "service": {
                                "name": "gatekeeper-webhook-service",
                                "namespace": ns,
                                "path": "/v1/admit",
                            }
                        },
                        "rules": [
                            {
                                "apiGroups": ["*"],
                                "apiVersions": ["*"],
                                "operations": ["CREATE", "UPDATE"],
                                "resources": ["*"],
                            }
                        ],
                    },
                    {
                        "name": "check-ignore-label.gatekeeper.sh",
                        "admissionReviewVersions": ["v1"],
                        "sideEffects": "None",
                        "failurePolicy": "Fail",
                        "clientConfig": {
                            "service": {
                                "name": "gatekeeper-webhook-service",
                                "namespace": ns,
                                "path": "/v1/admitlabel",
                            }
                        },
                        "rules": [
                            {
                                "apiGroups": [""],
                                "apiVersions": ["*"],
                                "operations": ["CREATE", "UPDATE"],
                                "resources": ["namespaces"],
                            }
                        ],
                    },
                ],
            }
        )
    if not v["disableMutation"]:
        docs.append(
            {
                "apiVersion": "admissionregistration.k8s.io/v1",
                "kind": "MutatingWebhookConfiguration",
                "metadata": {"name": v["mwhName"]},
                "webhooks": [
                    {
                        "name": "mutation.gatekeeper.sh",
                        "admissionReviewVersions": ["v1"],
                        "sideEffects": "None",
                        "failurePolicy": v["mutationFailurePolicy"],
                        "timeoutSeconds": v["webhookTimeoutSeconds"],
                        # exclusions MATCH the validating config above
                        "namespaceSelector": _ns_exclusions(),
                        "reinvocationPolicy": "Never",
                        "clientConfig": {
                            "service": {
                                "name": "gatekeeper-webhook-service",
                                "namespace": ns,
                                "path": "/v1/mutate",
                            }
                        },
                        "rules": [
                            {
                                "apiGroups": ["*"],
                                "apiVersions": ["*"],
                                "operations": ["CREATE", "UPDATE"],
                                "resources": ["*"],
                            }
                        ],
                    },
                ],
            }
        )
    return [copy.deepcopy(d) for d in docs]


HEADER = """\
# GENERATED by deploy/render.py — edit values there, not this file.
# The operations-split deployment (3 HA webhook CPU replicas with the
# Secret-backed fleet cert store + PodDisruptionBudget, one audit pod
# on a TPU node), scoped RBAC, base CRDs (incl. the mutation kinds and
# the FleetState gossip plane), Service, and the fail-open Validating +
# Mutating webhook configurations (shared namespace exclusions). See
# deploy/render.py's docstring for the design rationale and
# charts/gatekeeper parity notes; docs/fleet.md for the fleet plane.
"""


def render_text(values: Dict[str, Any] | None = None) -> str:
    return HEADER + yaml.safe_dump_all(
        render(values), sort_keys=False, default_flow_style=False
    )


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="render.py", description=__doc__)
    p.add_argument("--values", help="YAML values file merged over defaults")
    p.add_argument(
        "--set", action="append", default=[],
        help="dotted override, e.g. --set image.tag=v0.2.0",
    )
    args = p.parse_args(argv)
    values: Dict[str, Any] = {}
    if args.values:
        with open(args.values) as f:
            values = yaml.safe_load(f) or {}
    for item in args.set:
        key, _, raw = item.partition("=")
        _set_path(values, key, raw)
    sys.stdout.write(render_text(values))
    return 0


if __name__ == "__main__":
    sys.exit(main())
