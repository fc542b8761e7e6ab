"""SoakHarness: the system under test + the scenario executor.

Builds 1..N REAL `WebhookServer` replicas over HTTP(S) — validation,
mutation, and agent-review planes live on every replica — with a
self-contained policy corpus (no reference-library dependency: a soak
must run on any machine), an in-process stub external-data provider,
and, for multi-replica runs, the PR-7 fleet plane over one FakeCluster
(shared Secret-backed certs, cache gossip, breaker gossip).

The run is three concurrent machines:

  * the open-loop generator (loadgen.py) posting Poisson arrivals
    round-robin over the ACTIVE replicas — the load-balancer model:
    a replica leaves rotation the instant its readiness flips
    (`WebhookServer.on_drain`), which is exactly what a real LB
    watching /readyz does;
  * the scenario timer executing timeline events (constraint churn,
    provider/mutator adds, fault arm/disarm against the PR-4 registry,
    cert rotation through the fleet store, graceful replica kill);
  * the window sampler recording server-side counters + the leak
    series (RSS, cache entries + evictions, trace-ring size, metrics
    series count, render-cache size) once per reporting window.

The reporter (report.py) joins all three streams into the evidence
artifact; `run_soak(scenario)` is the one-call entry.
"""

from __future__ import annotations

import itertools
import json
import ssl
import threading
import time
import urllib.error
import urllib.request
from typing import Any, Dict, List, Optional

from ..faults import FAULTS, CircuitBreaker
from .loadgen import CLIENT_TIMEOUT, CONN_ERROR, run_open_loop
from .report import build_report
from .scenario import Scenario

K8S_TARGET = "admission.k8s.gatekeeper.sh"
SOAK_PROVIDER = "soak-registry"

_PRIV_REGO = """package soakprivileged

violation[{"msg": msg}] {
    input.review.object.spec.containers[_].securityContext.privileged
    msg := "privileged container"
}
"""

_EXT_REGO = """package soakexternal

violation[{"msg": msg}] {
    images := [img | img := input.review.object.spec.containers[_].image]
    response := external_data({"provider": "soak-registry", "keys": images})
    count(response.errors) > 0
    msg := sprintf("image verification failed: %v", [response.errors])
}
"""

_AGENT_REGO = """package soakagentshell

allowed_cmd(c) { c == input.parameters.allowed[_] }
violation[{"msg": msg}] {
    cmd := input.review.object.spec.arguments.command
    not allowed_cmd(cmd)
    msg := sprintf("shell command <%v> is outside the allowlist", [cmd])
}
"""

# churn templates get a distinct package + kind per add
_CHURN_REGO = """package soakchurn{n}

violation[{{"msg": msg}}] {{
    input.review.object.metadata.labels["soak-churn-{n}"] == "deny"
    msg := "churn label denied"
}}
"""


def _template(kind: str, target: str, rego: str) -> Dict[str, Any]:
    return {
        "apiVersion": "templates.gatekeeper.sh/v1beta1",
        "kind": "ConstraintTemplate",
        "metadata": {"name": kind.lower()},
        "spec": {
            "crd": {"spec": {"names": {"kind": kind}}},
            "targets": [{"target": target, "rego": rego}],
        },
    }


def _constraint(kind: str, name: str, match=None, params=None):
    spec: Dict[str, Any] = {}
    if match is not None:
        spec["match"] = match
    if params is not None:
        spec["parameters"] = params
    return {
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": kind,
        "metadata": {"name": name},
        "spec": spec,
    }


_POD_MATCH = {"kinds": [{"apiGroups": [""], "kinds": ["Pod"]}]}


def _pod_request(i: int, violating: bool, external_keys: int = 12):
    """A synthetic UPDATE AdmissionRequest whose image cycles the
    external-data key universe (steady state = pure cache hits)."""
    image = f"reg.example/app{i % external_keys}"
    return {
        "uid": f"soak-{i}",
        "kind": {"group": "", "version": "v1", "kind": "Pod"},
        "operation": "UPDATE",
        "name": f"pod{i}",
        "namespace": f"ns{i % 7}",
        "userInfo": {"username": "soak"},
        "object": {
            "apiVersion": "v1",
            "kind": "Pod",
            "metadata": {
                "name": f"pod{i}", "namespace": f"ns{i % 7}",
                "labels": {"app": f"svc{i % 5}"},
            },
            "spec": {
                "containers": [{
                    "name": "main",
                    "image": image,
                    "securityContext": (
                        {"privileged": True} if violating else {}
                    ),
                }],
            },
        },
    }


def _assign_metadata(name: str, label: str) -> Dict[str, Any]:
    return {
        "apiVersion": "mutations.gatekeeper.sh/v1alpha1",
        "kind": "AssignMetadata",
        "metadata": {"name": name},
        "spec": {
            "match": {"scope": "Namespaced"},
            "location": f"metadata.labels.{label}",
            "parameters": {"assign": {"value": "soak"}},
        },
    }


class _StubProvider:
    """In-process provider HTTP endpoint: answers the ProviderRequest
    protocol, counts outbound fetches (the bounded-refetch evidence),
    flags keys containing \"bad\"."""

    def __init__(self):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        self.fetches = 0
        self.keys_fetched = 0
        outer = self

        class _H(BaseHTTPRequestHandler):
            def do_POST(self):  # noqa: N802
                n = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(n) or b"{}")
                keys = ((body.get("request") or {}).get("keys")) or []
                outer.fetches += 1
                outer.keys_fetched += len(keys)
                payload = json.dumps({
                    "response": {
                        "items": [
                            {"key": k, "error": "unsigned"}
                            if "bad" in k
                            else {"key": k, "value": f"ok:{k}"}
                            for k in keys
                        ],
                        "systemError": "",
                    }
                }).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)

            def log_message(self, *args):
                pass

        self._httpd = ThreadingHTTPServer(("127.0.0.1", 0), _H)
        self.url = f"http://127.0.0.1:{self._httpd.server_address[1]}/v"
        threading.Thread(
            target=self._httpd.serve_forever, daemon=True
        ).start()

    def stop(self):
        self._httpd.shutdown()
        self._httpd.server_close()


class _Replica:
    """One webhook pod: client + driver + mutation/agent/external
    systems + the serving WebhookServer, plus its fleet attachments."""

    def __init__(self, name: str):
        self.name = name
        self.active = True  # in LB rotation
        self.metrics = None
        self.tracer = None
        self.client = None
        self.driver = None
        self.external = None
        self.mutation_system = None
        self.server = None
        self.fleet_plane = None
        self.rotator = None
        self.partitioner = None  # device fault domains (partitions > 0)
        self.attributor = None  # per-constraint device-time accounting
        self.recorder = None  # trip-triggered postmortem capture
        self.decisions = None  # per-admission decision log
        self.slo = None  # live streaming SLO engine (obs/slo.py)
        self.corpus = None  # corpus static-analysis plane
        self.integrity = None  # verdict-integrity plane (canary/SDC)

    @property
    def base_url(self) -> str:
        return f"{self.server.scheme}://127.0.0.1:{self.server.port}"


class SoakHarness:
    def __init__(self, scenario: Scenario, err=None):
        import sys

        scenario.validate()
        self.scenario = scenario
        self.err = err if err is not None else sys.stderr
        self.replicas: List[_Replica] = []
        self.stub = _StubProvider()
        self.cluster = None  # FakeCluster when fleet/tls is in play
        self.transitions: List[Dict[str, Any]] = []
        self.faults_log: List[Dict[str, Any]] = []
        self.events_log: List[Dict[str, Any]] = []
        self._window_samples: List[Dict[str, Any]] = []
        self._churn_n = itertools.count(1)
        # locality-skewed traffic: (hot_ns, cold_ns, skew) once a
        # locality_churn event fires; None = the uniform ns{i%7} mix
        self._locality: Optional[tuple] = None
        self._req_n = itertools.count()
        self._rr = itertools.count()  # LB round-robin cursor
        self._t0 = time.monotonic()  # re-stamped at load start
        self._stop = threading.Event()
        self._saved_min_batch = None
        # per-window SLO-breach detection (flight-recorder trigger):
        # _submit counts outcomes, the sampler judges each window
        self._win_lock = threading.Lock()
        self._win_total = 0
        self._win_failed = 0
        # client-side TLS: availability is what the soak measures; the
        # chain-validation contract is pinned by tests/test_fleet.py,
        # so the LB model skips verification and keeps serving across
        # CA rotations exactly like an apiserver with a caBundle lag
        self._ssl_ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
        self._ssl_ctx.check_hostname = False
        self._ssl_ctx.verify_mode = ssl.CERT_NONE

    def _log(self, msg: str) -> None:
        print(f"soak: {msg}", file=self.err, flush=True)

    # -- build ----------------------------------------------------------------

    def build(self) -> None:
        scn = self.scenario
        if scn.min_device_batch is not None:
            # the run-scoped form of GATEKEEPER_TPU_MIN_DEVICE_BATCH:
            # at soak arrival rates micro-batches are small, and the
            # adaptive floor would keep every batch on the interpreter
            # — lowering it is what puts the REAL fused path under soak
            from ..constraint import tpudriver as _td

            self._saved_min_batch = _td.MIN_DEVICE_BATCH
            _td.MIN_DEVICE_BATCH = int(scn.min_device_batch)
        if scn.replicas > 1 or scn.tls:
            from ..control.events import FakeCluster

            self.cluster = FakeCluster()
        for i in range(scn.replicas):
            self.replicas.append(self._build_replica(f"soak-{i}"))
        self._log(
            f"built {len(self.replicas)} replica(s), "
            f"tls={scn.tls}, constraints={scn.constraints}"
        )

    def _build_replica(self, name: str) -> _Replica:
        from ..agentaction import AgentActionTarget
        from ..constraint import Backend, K8sValidationTarget, TpuDriver
        from ..externaldata import ExternalDataSystem
        from ..metrics import MetricsRegistry
        from ..mutation import MutationSystem
        from ..obs import CostAttributor, DecisionLog, FlightRecorder, Tracer
        from ..webhook.server import WebhookServer

        scn = self.scenario
        rep = _Replica(name)
        rep.metrics = MetricsRegistry()
        # small ring: warmup saturates it BEFORE the measured windows,
        # so the leak sampler sees a full (flat) ring, not a filling one
        rep.tracer = Tracer(max_traces=128)
        rep.driver = TpuDriver()
        rep.driver.set_metrics(rep.metrics)  # phase split + telemetry
        # replica-tagged attribution + flight recorder: multi-replica
        # runs stitch per-replica timelines from the replica field on
        # records and cost tables (docs/observability.md)
        rep.attributor = CostAttributor(metrics=rep.metrics, replica=name)
        rep.driver.set_attributor(rep.attributor)
        # replica-tagged decision log: per-window decision-loss and
        # route mix ride the sampler; a small ring keeps the leak
        # series honest (warmup saturates it before measurement)
        rep.decisions = DecisionLog(metrics=rep.metrics, replica=name)
        rep.recorder = FlightRecorder(
            tracer=rep.tracer,
            attributor=rep.attributor,
            metrics=rep.metrics,
            decisions=rep.decisions,
            replica=name,
        )
        # live SLO engine, judging every admission against the SAME
        # target the offline reporter scores (the scenario's deadline
        # contract + any `slo` overrides) — the live_vs_offline soak
        # check compares the two planes after the run
        from ..obs import SloEngine

        rep.slo = SloEngine(
            target=scn.slo_target(),
            metrics=rep.metrics,
            recorder=rep.recorder,
            replica=name,
        )
        rep.decisions.slo = rep.slo
        rep.client = Backend(rep.driver).new_client(
            K8sValidationTarget(), AgentActionTarget()
        )
        # verdict-integrity plane (docs/robustness.md §Verdict
        # integrity): canary rows ride every padded dispatch and a
        # CRC-sampled shadow oracle re-checks live verdicts — the sdc
        # scenario's bit-flip detection + corruption-quarantine story
        from ..integrity import IntegrityPlane

        rep.integrity = IntegrityPlane(
            metrics=rep.metrics,
            decisions=rep.decisions,
            recorder=rep.recorder,
            quarantine_threshold=2,
        )
        rep.driver.set_integrity(rep.integrity)
        rep.integrity.attach_client(rep.client)
        rep.recorder.add_source("integrity", rep.integrity.snapshot)
        rep.external = ExternalDataSystem(metrics=rep.metrics)
        if self.cluster is not None:
            from ..fleet import FleetPlane

            rep.fleet_plane = FleetPlane(
                self.cluster, name,
                metrics=rep.metrics, publish_interval_s=0.1,
            )
            rep.fleet_plane.attach_cache(rep.external)
        rep.external.upsert({
            "apiVersion": "externaldata.gatekeeper.sh/v1alpha1",
            "kind": "Provider",
            "metadata": {"name": SOAK_PROVIDER},
            "spec": {
                "url": self.stub.url,
                "timeout": 5,
                "failurePolicy": "Ignore",
                "cacheTTLSeconds": 3600,
                "negativeCacheTTLSeconds": 3600,
            },
        })
        rep.client.set_external_data(rep.external)
        rep.client.add_template(
            _template("SoakPrivileged", K8S_TARGET, _PRIV_REGO)
        )
        rep.client.add_template(
            _template("SoakExternal", K8S_TARGET, _EXT_REGO)
        )
        for i in range(scn.constraints):
            rep.client.add_constraint(
                _constraint("SoakPrivileged", f"w{i}", match=_POD_MATCH)
            )
        rep.client.add_constraint(
            _constraint("SoakExternal", "ext", match=_POD_MATCH)
        )
        from ..agentaction import TARGET_NAME as AGENT_TARGET

        rep.client.add_template(
            _template("SoakAgentShell", AGENT_TARGET, _AGENT_REGO)
        )
        rep.client.add_constraint(
            _constraint(
                "SoakAgentShell", "shell",
                match={"tools": ["shell.*"]},
                params={"allowed": ["ls", "cat"]},
            )
        )
        rep.mutation_system = MutationSystem(metrics=rep.metrics)
        rep.mutation_system.upsert(_assign_metadata("soak-base", "soak"))
        from ..analysis.corpus import CorpusPlane

        # corpus static-analysis plane (docs/analysis.md §Corpus
        # analysis): recomputed in the background when churn moves the
        # policy generation — the sampler's maybe_recompute() poll
        # mirrors production's /readyz-driven kick, never the request
        # path — and the partition planner consumes prunable_keys for
        # verdict-safe static pruning
        rep.corpus = CorpusPlane(
            rep.client,
            mutation_system=rep.mutation_system,
            external_data=rep.external,
            metrics=rep.metrics,
        )
        rep.corpus.refresh()

        rotator = None
        if scn.tls:
            import tempfile

            from ..fleet import FleetCertRotator, SecretCertStore

            store = SecretCertStore(
                self.cluster, name="soak-webhook-cert",
                namespace="gatekeeper-system", replica_id=name,
                metrics=rep.metrics,
            )
            rotator = FleetCertRotator(
                tempfile.mkdtemp(prefix=f"gk-soak-{name}-"), store,
                metrics=rep.metrics,
            )
            rotator.ensure()
            rotator.start()
        rep.rotator = rotator

        rep.server = WebhookServer(
            rep.client,
            K8S_TARGET,
            agent_review=True,
            mutation_system=rep.mutation_system,
            metrics=rep.metrics,
            tracer=rep.tracer,
            tls=scn.tls,
            rotator=rotator,
            window_ms=scn.window_ms,
            request_timeout=max(5.0, scn.deadline_s * 8),
            # denial records carry trace ids (the traceparent
            # propagation acceptance reads them)
            log_denies=True,
            recorder=rep.recorder,
            decision_log=rep.decisions,
            # admission scheduling (docs/operations.md §Admission
            # scheduling): the scenario's policy on every batcher
            # plane, fed by the replica's own streaming SLO engine
            # (saturation feedback) and cost attributor (batch cost
            # prediction seeds)
            sched_policy=scn.sched_policy,
            slo=rep.slo,
            attributor=rep.attributor,
            integrity=rep.integrity,
        )
        rep.recorder.add_source(
            "webhook", lambda rep=rep: {
                "shed": rep.server.batcher.shed_count,
                "batch_failures": rep.server.batcher.batch_failures,
            },
        )
        # scenario-tuned breakers (the stock 30 s recovery would spend
        # a whole fault window waiting): share metrics/tracer so the
        # transition series and spans land in the same registries
        br = scn.breaker

        def _ledger_subscribe(breaker, plane, replica):
            # transition ledger keyed by breaker NAME: multi-breaker
            # planes (one per device) stay exactly accounted instead of
            # collapsing into one per-plane stream
            breaker.subscribe(
                lambda f, t, breaker=breaker, plane=plane, replica=replica: (
                    self.transitions.append({
                        "t_s": round(time.monotonic() - self._t0, 3),
                        "replica": replica,
                        "plane": plane,
                        "breaker": breaker.name,
                        "from": f,
                        "to": t,
                    })
                )
            )

        for batcher, plane in (
            (rep.server.batcher, "validation"),
            (rep.server.mutate_batcher, "mutation"),
            (rep.server.agent_batcher, "agent"),
        ):
            if batcher is None:
                continue
            if plane == "validation" and scn.partitions:
                # device fault domains replace the single validation
                # breaker: per-(device, plane) breakers live in the
                # PartitionDispatcher (docs/robustness.md §Fault
                # domains)
                continue
            breaker = CircuitBreaker(
                failure_threshold=int(br.get("failure_threshold", 3)),
                recovery_seconds=float(br.get("recovery_seconds", 5.0)),
                plane=plane,
                metrics=rep.metrics,
                tracer=rep.tracer,
                recorder=rep.recorder,
            )
            batcher.breaker = breaker
            _ledger_subscribe(breaker, plane, name)
            if rep.fleet_plane is not None:
                rep.fleet_plane.register_breaker(
                    f"device:{plane}", breaker
                )
        if scn.partitions:
            from ..parallel.partition import PartitionDispatcher

            disp = PartitionDispatcher(
                rep.client,
                K8S_TARGET,
                k=scn.partitions,
                plane="validation",
                metrics=rep.metrics,
                tracer=rep.tracer,
                failure_threshold=int(br.get("failure_threshold", 3)),
                recovery_seconds=float(br.get("recovery_seconds", 5.0)),
                breaker_listener=lambda b, replica=name: (
                    _ledger_subscribe(b, "validation", replica)
                ),
                recorder=rep.recorder,
                corpus=rep.corpus,
            )
            rep.partitioner = disp
            rep.recorder.add_source("partitions", disp.postmortem)
            # compile_storm postmortems capture the program-store state
            # table + per-partition signatures (docs/compile.md)
            rep.recorder.add_source("programs", disp.programs_table)
            rep.server.partitioner = disp  # server.stop() closes it
            rep.server.batcher.partitioner = disp
            rep.server.batcher.breaker = None
            # corruption quarantine needs the dispatcher to re-home a
            # bit-flipping device's partitions (built after the server,
            # so the server's own attach above never saw it)
            rep.integrity.attach_dispatcher(disp)
            if rep.fleet_plane is not None:
                # per-device breakers gossip under their
                # device:validation:<id> keys as they are created
                disp.set_fleet(rep.fleet_plane)
        if rep.fleet_plane is not None:
            rep.fleet_plane.start()
        # the LB model: readiness flip takes the replica out of
        # rotation BEFORE the listener closes (graceful drain)
        rep.server.on_drain(
            lambda rep=rep: setattr(rep, "active", False)
        )
        rep.server.start()
        return rep

    # -- request bodies -------------------------------------------------------

    def _pod_request(self, i: int, violating: bool) -> Dict[str, Any]:
        req = _pod_request(i, violating, self.scenario.external_keys)
        tn = self.scenario.tenants
        if tn is not None:
            # two-tenant mix (multi_tenant_overload): a deterministic
            # noisy/quiet namespace split — the scheduler's fair-share
            # quotas key on the namespace, and the sampler reads each
            # class's attainment/shed from the decision log
            frac = float(tn.get("noisy_fraction", 0.75))
            ns = (
                str(tn.get("noisy_ns", "ns-noisy"))
                if (i % 100) < int(round(frac * 100))
                else str(tn.get("quiet_ns", "ns-quiet"))
            )
            req["namespace"] = ns
            req["object"]["metadata"]["namespace"] = ns
        loc = self._locality
        if loc is not None:
            # deterministic 90/10 (skew) namespace split: the hot
            # group's partitions stay hot, the cold group's sit mask-
            # skipped for most batches
            hot, cold, skew = loc
            ns = hot if (i % 100) < int(round(skew * 100)) else cold
            req["namespace"] = ns
            req["object"]["metadata"]["namespace"] = ns
        return req

    def _body(self, plane: str) -> bytes:
        i = next(self._req_n)
        scn = self.scenario
        violating = (i % 997) / 997.0 < scn.violating_fraction
        if plane == "agent":
            doc = {
                "apiVersion": "agentaction.gatekeeper.sh/v1",
                "kind": "AgentActionReview",
                "request": {
                    "uid": f"call-{i}",
                    "id": f"call-{i}",
                    "agent": f"planner-{i % 3}",
                    "session": f"s-{i % 11}",
                    "tool": "shell.exec",
                    "arguments": {
                        "command": "rm" if violating else "ls"
                    },
                    "capabilities": ["exec"],
                    "skill": {"name": "fs-tools", "publisher": "acme",
                              "signed": True, "digest": "sha256:abc"},
                },
            }
        else:
            doc = {
                "apiVersion": "admission.k8s.io/v1",
                "kind": "AdmissionReview",
                "request": self._pod_request(i, violating),
            }
        return json.dumps(doc).encode()

    _PATHS = {
        "validation": "/v1/admit",
        "mutation": "/v1/mutate",
        "agent": "/v1/agent/review",
    }

    def _submit(self, plane: str):
        """One open-loop request: round-robin over ACTIVE replicas,
        POST, classify. Returns (status, outcome) for the generator.
        Outcomes also feed the per-window SLO-breach detector (a bad
        window trips a flight-recorder postmortem)."""
        status, outcome = self._submit_once(plane)
        with self._win_lock:
            self._win_total += 1
            if status != 200:
                self._win_failed += 1
        return status, outcome

    def _submit_once(self, plane: str):
        live = [r for r in self.replicas if r.active]
        if not live:
            return 0, CONN_ERROR
        rep = live[next(self._rr) % len(live)]
        body = self._body(plane)
        timeout = max(5.0, self.scenario.deadline_s * 8)
        url = rep.base_url + self._PATHS[plane]
        req = urllib.request.Request(
            url, data=body,
            headers={"Content-Type": "application/json"}, method="POST",
        )
        try:
            with urllib.request.urlopen(
                req, timeout=timeout,
                context=self._ssl_ctx if self.scenario.tls else None,
            ) as resp:
                doc = json.loads(resp.read())
            allowed = bool(
                ((doc.get("response") or {}).get("allowed", False))
            )
            return 200, ("ok" if allowed else "denied")
        except urllib.error.HTTPError as e:
            return int(e.code), f"http_{e.code}"
        except TimeoutError:
            return 0, CLIENT_TIMEOUT
        except urllib.error.URLError as e:
            if isinstance(getattr(e, "reason", None), TimeoutError):
                return 0, CLIENT_TIMEOUT
            return 0, CONN_ERROR
        except (ConnectionError, OSError):
            return 0, CONN_ERROR

    # -- scenario actions -----------------------------------------------------

    def _run_event(self, action: str, params: Dict[str, Any]) -> None:
        if action == "phase":
            return  # phases only label reporting windows
        if action == "add_constraints":
            count = int(params.get("count", 25))
            stamp = next(self._churn_n)  # unique names across adds
            for rep in self.replicas:
                for j in range(count):
                    rep.client.add_constraint(_constraint(
                        "SoakPrivileged", f"churn{stamp}-{j}",
                        match=_POD_MATCH,
                    ))
        elif action == "locality_churn":
            # two namespace-affine constraint groups: identical match
            # blocks within a group give one locality token each, so
            # the guided planner co-locates them — and the traffic
            # skew applied in _pod_request makes one group hot while
            # the other's partitions sit mask-skipped
            count = int(params.get("count", 10))
            hot = str(params.get("hot_ns", "ns-aff-hot"))
            cold = str(params.get("cold_ns", "ns-aff-cold"))
            skew = float(params.get("skew", 0.9))
            stamp = next(self._churn_n)
            for rep in self.replicas:
                for ns in (hot, cold):
                    for j in range(count):
                        rep.client.add_constraint(_constraint(
                            "SoakPrivileged",
                            f"aff{stamp}-{ns}-{j}",
                            match={**_POD_MATCH, "namespaces": [ns]},
                        ))
            self._locality = (hot, cold, skew)
        elif action == "add_template":
            n = next(self._churn_n)
            kind = f"SoakChurn{n}"
            rego = _CHURN_REGO.format(n=n)
            for rep in self.replicas:
                rep.client.add_template(_template(kind, K8S_TARGET, rego))
                rep.client.add_constraint(
                    _constraint(kind, f"churn-t{n}", match=_POD_MATCH)
                )
        elif action == "ingest_wave":
            # template ingest burst (docs/compile.md): `count` new
            # template kinds + constraints land while traffic flows.
            # Each new kind compiles exactly once; signature-unchanged
            # partitions carry forward and churned ones restage in the
            # background — the `ingest_zero_degraded` report check pins
            # zero degraded dispatches and zero 5xx through the wave.
            count = int(params.get("count", 500))
            for _ in range(count):
                n = next(self._churn_n)
                kind = f"SoakChurn{n}"
                rego = _CHURN_REGO.format(n=n)
                for rep in self.replicas:
                    rep.client.add_template(
                        _template(kind, K8S_TARGET, rego)
                    )
                    rep.client.add_constraint(
                        _constraint(kind, f"wave-t{n}", match=_POD_MATCH)
                    )
        elif action == "add_provider":
            n = next(self._churn_n)
            for rep in self.replicas:
                rep.external.upsert({
                    "apiVersion": "externaldata.gatekeeper.sh/v1alpha1",
                    "kind": "Provider",
                    "metadata": {"name": f"soak-extra-{n}"},
                    "spec": {
                        "url": self.stub.url,
                        "timeout": 5,
                        "failurePolicy": "Ignore",
                        "cacheTTLSeconds": 600,
                    },
                })
        elif action == "add_mutator":
            n = next(self._churn_n)
            for rep in self.replicas:
                rep.mutation_system.upsert(
                    _assign_metadata(f"soak-churn-{n}", f"soak-{n}")
                )
        elif action == "arm_fault":
            FAULTS.arm(
                params["point"],
                mode=params.get("mode", "error"),
                count=int(params.get("count", -1)),
                after=int(params.get("after", 0)),
                delay_s=float(params.get("delay", 0.05)),
            )
        elif action == "disarm_faults":
            snap = FAULTS.snapshot()
            self.faults_log.append({
                "t_s": round(time.monotonic() - self._t0, 3),
                "disarmed": snap,
            })
            FAULTS.reset()
        elif action == "rotate_certs":
            rep = next(
                (r for r in self.replicas if r.active and r.rotator),
                None,
            )
            if rep is None:
                self._log("rotate_certs: no TLS rotator (no-op)")
                return
            rot = rep.rotator
            rec, _won = rot.store.offer(
                rot.generate_pair(),
                expected_generation=rot.cert_generation,
            )
            rot._install_record(rec)
            self._log(
                f"rotated certs via {rep.name} -> generation "
                f"{rot.cert_generation}"
            )
        elif action == "quarantine_device":
            dev = int(params.get("device", 1))
            for rep in self.replicas:
                if rep.partitioner is not None:
                    rep.partitioner.quarantine(dev)
        elif action == "heal_device":
            dev = int(params.get("device", 1))
            for rep in self.replicas:
                if rep.partitioner is not None:
                    rep.partitioner.heal(dev)
        elif action == "selftest_device":
            # golden self-test: the only heal path for a corruption
            # quarantine (docs/robustness.md §Verdict integrity)
            dev = int(params.get("device", 1))
            for rep in self.replicas:
                if rep.integrity is not None:
                    ok = rep.integrity.selftest(dev)
                    self._log(
                        f"selftest device={dev} on {rep.name}: "
                        f"{'pass' if ok else 'fail'}"
                    )
        elif action == "kill_replica":
            idx = int(params.get("replica", 0))
            rep = self.replicas[idx]
            rep.active = False  # LB-out first (readiness model)

            def _graceful():
                # graceful drain: readiness already flipped; the server
                # closes its listener and completes in-flight requests
                rep.server.stop()
                if rep.fleet_plane is not None:
                    rep.fleet_plane.stop()
                if rep.rotator is not None:
                    rep.rotator.stop()

            threading.Thread(
                target=_graceful, name=f"gk-soak-kill-{rep.name}",
                daemon=True,
            ).start()
        else:  # pragma: no cover - Scenario.validate rejects these
            raise ValueError(f"unknown action {action!r}")

    def _event_loop(self) -> None:
        for ev in self.scenario.events:
            while not self._stop.is_set():
                delay = (self._t0 + ev.at_s) - time.monotonic()
                if delay <= 0:
                    break
                self._stop.wait(min(delay, 0.2))
            if self._stop.is_set():
                return
            t_rel = round(time.monotonic() - self._t0, 3)
            try:
                self._run_event(ev.action, ev.params)
                self._log(f"event t={t_rel}s: {ev.action} {ev.params}")
                self.events_log.append({
                    "t_s": t_rel, "action": ev.action, **ev.params,
                })
            except Exception as e:
                self._log(f"event t={t_rel}s {ev.action} FAILED: {e}")
                self.events_log.append({
                    "t_s": t_rel, "action": ev.action,
                    "error": str(e), **ev.params,
                })

    # -- per-window sampling --------------------------------------------------

    def _rss_kb(self) -> Optional[int]:
        try:
            with open("/proc/self/status") as f:
                for line in f:
                    if line.startswith("VmRSS:"):
                        return int(line.split()[1])
        except OSError:
            return None
        return None

    def _cumulative(self) -> Dict[str, Any]:
        """Cumulative server-side counters + instantaneous gauges,
        summed over replicas (dead replicas keep their last counts —
        diffs stay correct)."""
        shed = failures = cache_entries = cache_evictions = 0
        trace_ring = metrics_series = render_cache = 0
        cert_gen = metrics_dropped = 0
        dec_recorded = dec_dropped = dec_sampled = dec_ring = 0
        dec_routes: Dict[str, int] = {}
        pt_p50 = pt_max = None  # pruned-dispatch width across replicas
        # live SLO plane (obs/slo.py): saturation is the WORST replica
        # (the autoscaler scales on the hottest pod), attainment is
        # request-weighted across replicas, breaches/burning aggregate
        slo_sat = None
        slo_burning = False
        slo_breaches = 0
        slo_fast_n = slo_fast_ok = 0.0
        slo_slow_n = slo_slow_ok = 0.0
        slo_burn_fast = 0.0
        degraded = 0  # webhook_degraded_dispatch_total across planes
        program_swaps = program_carryforwards = program_compiles = 0
        corpus_recomputes = 0  # corpus-analysis background refreshes
        # admission scheduler (gatekeeper_tpu/sched): shed split by
        # typed reason + per-tenant-class attainment read straight
        # from the decision log's full-stream tenant counters
        sched_pred = sched_capped = sched_qfull = sched_throttled = 0
        # verdict-integrity plane: canary mismatch batches + shadow
        # divergences (cumulative), corruption-quarantined devices
        # (instantaneous) — the sdc check's evidence columns
        canary_mism = shadow_div = quarantined_now = 0
        tn = self.scenario.tenants or {}
        quiet_ns = str(tn.get("quiet_ns", "ns-quiet"))
        noisy_ns = str(tn.get("noisy_ns", "ns-noisy"))
        tclass = {
            "quiet": {"count": 0, "ok": 0, "shed": 0},
            "noisy": {"count": 0, "ok": 0, "shed": 0},
        }
        for rep in self.replicas:
            for b in (
                rep.server.batcher,
                rep.server.mutate_batcher,
                rep.server.agent_batcher,
                rep.server.agent_mutate_batcher,
            ):
                if b is not None:
                    shed += b.shed_count
                    failures += b.batch_failures
                    sched = getattr(b, "sched", None)
                    if sched is not None:
                        ss = sched.snapshot()
                        sched_pred += ss["sheds"]["predicted_miss"]
                        sched_capped += ss["sheds"]["tenant_capped"]
                        sched_qfull += ss["sheds"]["queue_full"]
                        sched_throttled += sum(
                            t["throttled"]
                            for t in ss["tenants"].values()
                        )
            if self.scenario.tenants and rep.decisions is not None:
                for key, row in rep.decisions.tenant_stats().items():
                    name = key.split("/", 1)[-1]
                    cls = (
                        "quiet" if name == quiet_ns
                        else "noisy" if name == noisy_ns
                        else None
                    )
                    if cls is None:
                        continue
                    tclass[cls]["count"] += row["count"]
                    tclass[cls]["ok"] += row["ok"]
                    tclass[cls]["shed"] += row["shed"]
            cache_entries += len(rep.external.cache)
            cache_evictions += rep.external.cache.evictions
            trace_ring += rep.tracer.size()["ring"]
            metrics_series += rep.metrics.series_count()
            # the cardinality cap's drop count: series_count staying
            # flat WITH drops accruing means the cap is holding (the
            # bounded-registry evidence), not that churn stopped
            metrics_dropped += sum(
                rep.metrics.dropped_series().values()
            )
            size_fn = getattr(rep.driver, "render_cache_size", None)
            if size_fn is not None:
                render_cache += size_fn()
            if rep.rotator is not None:
                cert_gen = max(cert_gen, rep.rotator.cert_generation)
            if rep.decisions is not None:
                # decision-plane health: recorded vs lost (rate-gated
                # drops + denial-log drops = "decision loss") and the
                # cumulative route mix, diffed per window below
                dsnap = rep.decisions.snapshot()
                dec_recorded += dsnap["recorded"]
                dec_dropped += (
                    dsnap["dropped"] + dsnap["denial_log_dropped"]
                )
                dec_sampled += dsnap["sampled_out"]
                dec_ring += dsnap["retained"]
                for route, n in dsnap["routes"].items():
                    dec_routes[route] = dec_routes.get(route, 0) + n
            if rep.slo is not None:
                auto = rep.slo.autoscaler()
                s = auto.get("saturation")
                if s is not None:
                    slo_sat = s if slo_sat is None else max(slo_sat, s)
                slo_burning = slo_burning or bool(auto.get("burning"))
                slo_breaches += int(auto.get("breaches") or 0)
                ssnap = rep.slo.snapshot()
                for p in ssnap["planes"].values():
                    slo_burn_fast = max(
                        slo_burn_fast, p["burn_rate_fast"]
                    )
                    if p["attainment_fast"] is not None:
                        slo_fast_n += p["requests_fast"]
                        slo_fast_ok += (
                            p["attainment_fast"] * p["requests_fast"]
                        )
                    if p["attainment_slow"] is not None:
                        slo_slow_n += p["requests_slow"]
                        slo_slow_ok += (
                            p["attainment_slow"] * p["requests_slow"]
                        )
            # degraded dispatches (breaker-open / all-dead host
            # routing): the ingest_zero_degraded check's evidence —
            # host-rung routing during a background restage does NOT
            # count here, only genuine degradation does
            try:
                msnap = rep.metrics.snapshot()
            except Exception:
                msnap = {}
            counters = msnap.get("counters", {})
            degraded += sum(
                v for k, v in counters.items()
                if k.startswith("webhook_degraded_dispatch_total")
            )
            drv = rep.driver
            program_swaps += int(getattr(drv, "subset_swaps", 0) or 0)
            program_carryforwards += int(
                getattr(drv, "subset_carryforwards", 0) or 0
            )
            program_compiles += int(
                getattr(drv, "program_compiles", 0) or 0
            )
            if rep.corpus is not None:
                # the sampler IS the recompute kick (production's
                # /readyz poll): a generation-compare + time-compare,
                # with the analysis itself on a background thread —
                # churn waves trigger one debounced recompute, never
                # one per add and never request-path work
                try:
                    rep.corpus.maybe_recompute()
                    corpus_recomputes += int(rep.corpus.recomputes)
                except Exception:
                    pass
            if rep.integrity is not None:
                try:
                    isnap = rep.integrity.snapshot()
                    canary_mism += isnap["canary"]["mismatch_batches"]
                    shadow_div += isnap["shadow"]["divergences"]
                    quarantined_now += len(isnap["quarantined"])
                except Exception:
                    pass
            if rep.partitioner is not None:
                # pruning width (mask-gated partition skipping): p50/
                # max partitions touched per batch over the recent
                # window — the locality_skew phase's evidence series
                st = rep.partitioner.touched_stats()
                if st["p50"] is not None:
                    pt_p50 = (
                        st["p50"] if pt_p50 is None
                        else max(pt_p50, st["p50"])
                    )
                    pt_max = (
                        st["max"] if pt_max is None
                        else max(pt_max, st["max"])
                    )
        return {
            "shed_cum": shed,
            "batch_failures_cum": failures,
            "transitions_cum": len(self.transitions),
            "fetches_cum": self.stub.fetches,
            "cache_entries": cache_entries,
            "cache_evictions": cache_evictions,
            "trace_ring": trace_ring,
            "metrics_series": metrics_series,
            "metrics_dropped": metrics_dropped,
            "render_cache": render_cache,
            "rss_kb": self._rss_kb(),
            "cert_generation": cert_gen,
            "decisions_cum": dec_recorded,
            "decisions_dropped_cum": dec_dropped,
            "decisions_sampled_out_cum": dec_sampled,
            "decision_ring": dec_ring,
            "decision_routes_cum": dec_routes,
            "partitions_touched_p50": pt_p50,
            "partitions_touched_max": pt_max,
            "degraded_cum": degraded,
            "sched_predicted_miss_cum": sched_pred,
            "sched_tenant_capped_cum": sched_capped,
            "sched_queue_full_cum": sched_qfull,
            "sched_throttled_cum": sched_throttled,
            "tenant_class_cum": tclass,
            "program_swaps_cum": program_swaps,
            "program_carryforwards_cum": program_carryforwards,
            "program_compiles_cum": program_compiles,
            "corpus_recomputes_cum": corpus_recomputes,
            "canary_mismatch_cum": canary_mism,
            "shadow_divergence_cum": shadow_div,
            "quarantined_devices": quarantined_now,
            # live SLO plane (obs/slo.py)
            "slo_saturation": slo_sat,
            "slo_burning": slo_burning,
            "slo_breaches_cum": slo_breaches,
            "slo_burn_fast": round(slo_burn_fast, 3),
            "slo_live_attainment_fast": (
                slo_fast_ok / slo_fast_n if slo_fast_n else None
            ),
            "slo_live_attainment_slow": (
                slo_slow_ok / slo_slow_n if slo_slow_n else None
            ),
            "slo_live_requests_slow": int(slo_slow_n),
        }

    def _sampler_loop(self) -> None:
        scn = self.scenario
        n_windows = max(1, int(round(scn.duration_s / scn.window_s)))
        prev = self._cumulative()
        for i in range(n_windows):
            target = self._t0 + (i + 1) * scn.window_s
            while not self._stop.is_set():
                delay = target - time.monotonic()
                if delay <= 0:
                    break
                self._stop.wait(min(delay, 0.2))
            cur = self._cumulative()
            self._window_samples.append({
                "shed": cur["shed_cum"] - prev["shed_cum"],
                "batch_failures": (
                    cur["batch_failures_cum"]
                    - prev["batch_failures_cum"]
                ),
                "breaker_transitions": (
                    cur["transitions_cum"] - prev["transitions_cum"]
                ),
                "fetches": cur["fetches_cum"] - prev["fetches_cum"],
                "cache_entries": cur["cache_entries"],
                "cache_evictions": cur["cache_evictions"],
                "trace_ring": cur["trace_ring"],
                "metrics_series": cur["metrics_series"],
                "metrics_dropped": cur["metrics_dropped"],
                "render_cache": cur["render_cache"],
                "rss_kb": cur["rss_kb"],
                "cert_generation": cur["cert_generation"],
                # decision-plane per-window view: records kept vs lost
                # (rate-gate + denial-log drops), the bounded-ring leak
                # series, and the route mix this window served
                "decisions": (
                    cur["decisions_cum"] - prev["decisions_cum"]
                ),
                "decisions_dropped": (
                    cur["decisions_dropped_cum"]
                    - prev["decisions_dropped_cum"]
                ),
                "decisions_sampled_out": (
                    cur["decisions_sampled_out_cum"]
                    - prev["decisions_sampled_out_cum"]
                ),
                "decision_ring": cur["decision_ring"],
                "decision_routes": {
                    route: n - prev["decision_routes_cum"].get(route, 0)
                    for route, n in cur["decision_routes_cum"].items()
                },
                # pruning width at this window's close (running p50/
                # max over the dispatcher's recent-batch window)
                "partitions_touched_p50": (
                    cur["partitions_touched_p50"]
                ),
                "partitions_touched_max": (
                    cur["partitions_touched_max"]
                ),
                # compile plane (docs/compile.md): degraded dispatches
                # this window (the ingest check's evidence), plus the
                # swap/carry-forward/compile activity behind the wave
                "degraded_dispatches": (
                    cur["degraded_cum"] - prev["degraded_cum"]
                ),
                # admission scheduler: typed shed split this window +
                # the per-tenant-class attainment/shed deltas read
                # from the decision log (multi_tenant_overload's
                # evidence columns)
                "sched_predicted_miss": (
                    cur["sched_predicted_miss_cum"]
                    - prev["sched_predicted_miss_cum"]
                ),
                "sched_tenant_capped": (
                    cur["sched_tenant_capped_cum"]
                    - prev["sched_tenant_capped_cum"]
                ),
                "sched_queue_full": (
                    cur["sched_queue_full_cum"]
                    - prev["sched_queue_full_cum"]
                ),
                "sched_throttled": (
                    cur["sched_throttled_cum"]
                    - prev["sched_throttled_cum"]
                ),
                "tenant_classes": {
                    cls: {
                        "requests": (
                            cur["tenant_class_cum"][cls]["count"]
                            - prev["tenant_class_cum"][cls]["count"]
                        ),
                        "ok": (
                            cur["tenant_class_cum"][cls]["ok"]
                            - prev["tenant_class_cum"][cls]["ok"]
                        ),
                        "shed": (
                            cur["tenant_class_cum"][cls]["shed"]
                            - prev["tenant_class_cum"][cls]["shed"]
                        ),
                    }
                    for cls in ("quiet", "noisy")
                } if self.scenario.tenants else None,
                "program_swaps": (
                    cur["program_swaps_cum"] - prev["program_swaps_cum"]
                ),
                "program_carryforwards": (
                    cur["program_carryforwards_cum"]
                    - prev["program_carryforwards_cum"]
                ),
                "program_compiles": (
                    cur["program_compiles_cum"]
                    - prev["program_compiles_cum"]
                ),
                # corpus analysis (docs/analysis.md): debounced
                # background recomputes completed this window — the
                # ingest_corpus_recompute check's evidence
                "corpus_recomputes": (
                    cur["corpus_recomputes_cum"]
                    - prev["corpus_recomputes_cum"]
                ),
                # verdict-integrity plane (docs/robustness.md §Verdict
                # integrity): canary mismatch batches + shadow-oracle
                # divergences this window, and how many devices sit in
                # corruption quarantine at the window's close — the
                # sdc_detected_and_quarantined check's evidence
                "canary_mismatches": (
                    cur["canary_mismatch_cum"]
                    - prev["canary_mismatch_cum"]
                ),
                "shadow_divergences": (
                    cur["shadow_divergence_cum"]
                    - prev["shadow_divergence_cum"]
                ),
                "quarantined_devices": cur["quarantined_devices"],
                # live SLO plane at this window's close: worst-replica
                # saturation, live fast-window attainment/burn, any
                # plane in the burning state, breaches fired this
                # window (each breach = one slo_breach flight record)
                "slo_saturation": cur["slo_saturation"],
                "slo_burning": cur["slo_burning"],
                "slo_burn_fast": cur["slo_burn_fast"],
                "slo_live_attainment": (
                    cur["slo_live_attainment_fast"]
                ),
                "slo_breaches": (
                    cur["slo_breaches_cum"] - prev["slo_breaches_cum"]
                ),
            })
            prev = cur
            # per-window SLO-breach detector: a window whose failure
            # rate crosses the threshold trips one postmortem on every
            # active replica (the recorders rate-limit the storm)
            with self._win_lock:
                total, failed = self._win_total, self._win_failed
                self._win_total = self._win_failed = 0
            if total >= 20 and failed / total > 0.2:
                for rep in self.replicas:
                    if rep.recorder is not None and rep.active:
                        rep.recorder.trigger(
                            "slo_window_breach",
                            window=i,
                            requests=total,
                            failed=failed,
                            failure_rate=round(failed / total, 4),
                        )
            if self._stop.is_set():
                return

    # -- device-time split ----------------------------------------------------

    def _device_time_split(self) -> Dict[str, Any]:
        """Aggregate the driver's phase_seconds metric across replicas:
        where a second of admission work actually went — host
        flatten/encode vs device execution vs violation render. This is
        the utilization denominator ROADMAP item 1/3 speed work is
        judged against."""
        import re

        totals: Dict[str, float] = {}
        rx = re.compile(r'phase="([a-z_]+)"')
        for rep in self.replicas:
            dists = rep.metrics.snapshot()["distributions"]
            for key, d in dists.items():
                if not key.startswith("driver_phase_seconds"):
                    continue
                m = rx.search(key)
                if not m:
                    continue
                totals[m.group(1)] = (
                    totals.get(m.group(1), 0.0) + float(d["sum"])
                )
        total = sum(totals.values())
        out: Dict[str, Any] = {
            "seconds": {k: round(v, 4) for k, v in sorted(totals.items())}
        }
        if total > 0:
            out["fractions"] = {
                k: round(v / total, 4) for k, v in sorted(totals.items())
            }
            # the utilization headline: device share of total work
            out["device_fraction"] = round(
                totals.get("device_dispatch", 0.0) / total, 4
            )
        return out

    # -- warmup / run / teardown ----------------------------------------------

    def warmup(self) -> float:
        """Closed-loop pre-load: compile the fused routes and fill the
        external-data cache so the measured windows start from steady
        state (cold compile belongs to readiness, not to the SLO)."""
        from concurrent.futures import ThreadPoolExecutor

        t0 = time.monotonic()
        for rep in self.replicas:
            try:
                rep.server.warmup()
            except Exception:
                pass
        with ThreadPoolExecutor(max_workers=16) as ex:
            for plane, n in (
                ("validation", 96), ("mutation", 32), ("agent", 32)
            ):
                list(ex.map(lambda _i: self._submit(plane), range(n)))
        # serial pass: open-loop arrivals make batch sizes 1-2, whose
        # pad buckets differ from the concurrent burst's — compile them
        # here, not inside the first measured window
        for plane in ("validation", "agent", "mutation"):
            for _ in range(4 * max(1, len(self.replicas))):
                self._submit(plane)
        return time.monotonic() - t0

    def run(self) -> Dict[str, Any]:
        scn = self.scenario
        self.build()
        warm_s = self.warmup()
        # live SLO windows restart here: warmup traffic (all-good,
        # closed-loop) would otherwise inflate live attainment over
        # what the offline reporter bins from the measured run — the
        # cost EWMA warmup primed is kept
        for rep in self.replicas:
            if rep.slo is not None:
                rep.slo.reset_windows()
        self._log(f"warmup {warm_s:.1f}s; starting open loop "
                  f"@{scn.rps}rps for {scn.duration_s}s")
        self._t0 = time.monotonic()
        threads = [
            threading.Thread(
                target=self._event_loop, name="gk-soak-events",
                daemon=True,
            ),
            threading.Thread(
                target=self._sampler_loop, name="gk-soak-sampler",
                daemon=True,
            ),
        ]
        for th in threads:
            th.start()
        try:
            load = run_open_loop(
                self._submit,
                rps=scn.rps,
                duration_s=scn.duration_s,
                deadline_s=scn.deadline_s,
                planes=scn.planes,
                seed=scn.seed,
            )
        finally:
            self._stop.set()
            for th in threads:
                th.join(timeout=5)
            FAULTS.reset()
        split = self._device_time_split()
        capacity = None
        if scn.capacity:
            capacity = run_capacity_model(
                scn.capacity, scn.deadline_s, err=self.err
            )
        # per-replica flight-recorder summaries: the postmortems the
        # run tripped (breaker opens, quarantines, SLO breaches, shed
        # bursts), replica-tagged so multi-replica timelines stitch
        flight = []
        for rep in self.replicas:
            if rep.recorder is None:
                continue
            rep.recorder.flush(timeout=1.0)
            flight.append({
                "replica": rep.name,
                **rep.recorder.snapshot(),
                "triggers": [
                    r["trigger"] for r in rep.recorder.records()
                ],
            })
        report = build_report(
            scn.to_dict(),
            load,
            self._window_samples,
            self.transitions,
            split,
            capacity=capacity,
            faults_log=self.faults_log,
            live_slo=self._live_slo_summary(),
            extra={
                "events_log": self.events_log,
                "warmup_seconds": round(warm_s, 1),
                "provider_fetches_total": self.stub.fetches,
                "flight_records": flight,
                "sched": self._sched_summary(),
            },
        )
        return report

    def _sched_summary(self) -> Dict[str, Any]:
        """End-of-run admission-scheduler rollup: per-replica plane
        snapshots (the same document /debug/sched serves) plus the
        decision-log per-tenant attainment split the acceptance checks
        read."""
        out: Dict[str, Any] = {
            "policy": self.scenario.sched_policy,
            "replicas": [],
            "tenant_stats": {},
        }
        for rep in self.replicas:
            if rep.server is not None and hasattr(
                rep.server, "sched_snapshot"
            ):
                out["replicas"].append({
                    "replica": rep.name,
                    "planes": rep.server.sched_snapshot(),
                })
            if rep.decisions is not None:
                for key, row in rep.decisions.tenant_stats().items():
                    agg = out["tenant_stats"].setdefault(
                        key, {"count": 0, "ok": 0, "miss": 0, "shed": 0}
                    )
                    for f in ("count", "ok", "miss", "shed"):
                        agg[f] += row[f]
        for row in out["tenant_stats"].values():
            row["attainment"] = (
                round(row["ok"] / row["count"], 4)
                if row["count"] else None
            )
        return out

    def _live_slo_summary(self) -> Optional[Dict[str, Any]]:
        """End-of-run rollup of the per-replica streaming SLO engines:
        slow-window attainment (request-weighted across replicas) is
        what the live_vs_offline check compares against the offline
        reporter; saturation/headroom are the autoscaler signals the
        capacity model cross-checks."""
        cum = self._cumulative()
        if not any(rep.slo is not None for rep in self.replicas):
            return None
        headroom = None
        arrival = 0.0
        cost = None
        for rep in self.replicas:
            if rep.slo is None:
                continue
            util = rep.slo.snapshot()["utilization"]
            arrival += util["arrival_rps"] or 0.0
            h = util["estimated_headroom_rps"]
            if h is not None:
                headroom = h if headroom is None else headroom + h
            c = util["device_seconds_per_row_ewma"]
            if c is not None:
                cost = c if cost is None else max(cost, c)
        return {
            "attainment_fast": cum["slo_live_attainment_fast"],
            "attainment_slow": cum["slo_live_attainment_slow"],
            "requests_slow": cum["slo_live_requests_slow"],
            "saturation": cum["slo_saturation"],
            "burning": cum["slo_burning"],
            "breaches": cum["slo_breaches_cum"],
            "arrival_rps": round(arrival, 2),
            "estimated_headroom_rps": headroom,
            "device_seconds_per_row_ewma": cost,
        }

    def stop(self) -> None:
        self._stop.set()
        FAULTS.reset()
        if self._saved_min_batch is not None:
            from ..constraint import tpudriver as _td

            _td.MIN_DEVICE_BATCH = self._saved_min_batch
            self._saved_min_batch = None
        for rep in self.replicas:
            try:
                if rep.server is not None:
                    rep.server.stop()
            except Exception:
                pass
            if rep.fleet_plane is not None:
                rep.fleet_plane.stop()
            if rep.rotator is not None:
                rep.rotator.stop()
            if rep.integrity is not None:
                rep.integrity.close()
            if rep.recorder is not None:
                rep.recorder.stop()
        self.stub.stop()


def run_capacity_model(
    cfg: Dict[str, Any], deadline_s: float, err=None
) -> List[Dict[str, Any]]:
    """Max sustainable rps at the p99 SLO vs constraint count: for each
    count, step the open-loop rate up the configured levels until a
    probe window's attainment drops below 99% — the last passing level
    is the capacity. Handler-level (no HTTP client noise): this models
    ENGINE capacity; the sustained-run numbers include transport."""
    import sys

    from ..constraint import Backend, K8sValidationTarget, TpuDriver
    from ..webhook.server import BatchedValidationHandler, MicroBatcher

    err = err if err is not None else sys.stderr
    counts = list(cfg.get("constraint_counts", [10, 100]))
    levels = list(cfg.get("rps_levels", [25, 50, 100, 200]))
    probe_s = float(cfg.get("probe_s", 3.0))
    out: List[Dict[str, Any]] = []
    for n_con in counts:
        client = Backend(TpuDriver()).new_client(K8sValidationTarget())
        client.add_template(
            _template("SoakPrivileged", K8S_TARGET, _PRIV_REGO)
        )
        for i in range(n_con):
            client.add_constraint(
                _constraint("SoakPrivileged", f"c{i}", match=_POD_MATCH)
            )
        batcher = MicroBatcher(client, K8S_TARGET, window_ms=2.0)
        handler = BatchedValidationHandler(batcher, request_timeout=30)
        batcher.start()
        counter = itertools.count()

        def submit(_plane: str):
            i = next(counter)
            resp = handler.handle(_pod_request(i, violating=(i % 10 == 0)))
            return 200, ("ok" if resp.allowed else "denied")

        row: Dict[str, Any] = {"constraints": n_con, "levels": []}
        max_ok = None
        try:
            # warm the route + jit buckets outside the measurement
            from ..constraint import AugmentedReview

            client.warm_review_path([
                AugmentedReview(_pod_request(i, False))
                for i in range(16)
            ])
            run_open_loop(
                submit, rps=min(levels), duration_s=1.0,
                deadline_s=deadline_s,
            )
            for rps in levels:
                load = run_open_loop(
                    submit, rps=rps, duration_s=probe_s,
                    deadline_s=deadline_s,
                )
                lats = sorted(s.latency_s for s in load.samples)
                p99 = lats[int(0.99 * (len(lats) - 1))] if lats else 0.0
                att = load.slo_attainment()
                row["levels"].append({
                    "rps": rps,
                    "achieved_rps": load.achieved_rps,
                    "p99_ms": round(p99 * 1e3, 2),
                    "attainment": round(att, 4),
                })
                print(
                    f"soak capacity: c={n_con} rps={rps} "
                    f"p99={p99 * 1e3:.1f}ms att={att:.3f}",
                    file=err, flush=True,
                )
                if att >= 0.99 and p99 <= deadline_s:
                    max_ok = rps
                else:
                    break
        finally:
            batcher.stop()
        row["max_rps_at_slo"] = max_ok
        out.append(row)
    return out


def run_soak(scenario: Scenario, err=None) -> Dict[str, Any]:
    """Build, run, and tear down one soak scenario; returns the report
    (report.py's schema; `summarize_soak` renders the SUMMARY line)."""
    harness = SoakHarness(scenario, err=err)
    try:
        return harness.run()
    finally:
        harness.stop()
