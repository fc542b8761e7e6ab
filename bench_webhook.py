"""Admission-webhook replay bench: BASELINE config #4.

Mirrors the reference harness BenchmarkValidationHandler
(pkg/webhook/policy_benchmark_test.go:233-329): PSP-style constraint
load, synthesized UPDATE AdmissionRequests, handler-level measurement
(the Go benchmark calls Handle directly too — no HTTP client in the
loop). Replays N requests at several concurrencies through the
micro-batching handler and reports p50/p99 latency, throughput, and
batch occupancy.

Standalone: python bench_webhook.py [N_REQUESTS] [N_CONSTRAINTS]
Also importable by bench.py (run_webhook_bench).
"""

import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

TARGET = "admission.k8s.gatekeeper.sh"
LIB = "/root/reference/library"

WEBHOOK_MIX = [
    (f"{LIB}/pod-security-policy/privileged-containers",
     "K8sPSPPrivilegedContainer", None),
    (f"{LIB}/pod-security-policy/host-namespaces",
     "K8sPSPHostNamespace", None),
    (f"{LIB}/pod-security-policy/capabilities", "K8sPSPCapabilities",
     {"allowedCapabilities": ["CHOWN"], "requiredDropCapabilities": []}),
    (f"{LIB}/general/allowedrepos", "K8sAllowedRepos",
     {"repos": ["nginx", "gcr.io/prod"]}),
    (f"{LIB}/general/requiredlabels", "K8sRequiredLabels",
     {"labels": [{"key": "app"}]}),
]


# repo-local fallback mix for containers without the reference
# checkout: the shipped reference-library bundle, same constraint
# shape and the same 100%-violating stress coverage (privileged +
# repos + labels all trip on make_request's violating pod)
LOCAL_BUNDLE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "deploy", "policies", "reference-library.yaml",
)
LOCAL_MIX = [
    ("K8sPSPPrivileged", None),
    ("K8sAllowedRepos", {"repos": ["nginx", "gcr.io/prod"]}),
    ("K8sRequiredLabels", {"labels": [{"key": "app"}]}),
    ("K8sBlockNodePort", None),
]


def _load_template(path):
    import yaml

    with open(path) as f:
        return yaml.safe_load(f)


def _webhook_mix():
    """[(template_doc, kind, params)] — the reference checkout's mix
    when present, else the shipped reference-library bundle."""
    if os.path.isdir(LIB):
        return [
            (_load_template(f"{tdir}/template.yaml"), kind, params)
            for tdir, kind, params in WEBHOOK_MIX
        ]
    import yaml

    with open(LOCAL_BUNDLE) as f:
        docs = [d for d in yaml.safe_load_all(f) if d]
    by_kind = {d["spec"]["crd"]["spec"]["names"]["kind"]: d for d in docs}
    return [
        (by_kind[kind], kind, params)
        for kind, params in LOCAL_MIX
        if kind in by_kind
    ]


def build_webhook_client(driver, n_constraints):
    from gatekeeper_tpu.constraint import Backend, K8sValidationTarget

    mix = _webhook_mix()
    client = Backend(driver).new_client(K8sValidationTarget())
    for doc, _kind, _params in mix:
        client.add_template(doc)
    for i in range(n_constraints):
        _doc, kind, params = mix[i % len(mix)]
        # namespace affinity aligned with make_request's ns{i % 11}: a
        # constraint governs one namespace, so the locality planner can
        # co-locate each namespace's constraints and mask-gated pruned
        # dispatch pays only the partitions a batch's namespaces touch
        # (the reference's per-team constraint scoping, at bench scale)
        spec = {"match": {
            "kinds": [{"apiGroups": [""], "kinds": ["Pod"]}],
            "namespaces": [f"ns{i % 11}"],
        }}
        if params is not None:
            spec["parameters"] = params
        client.add_constraint(
            {
                "apiVersion": "constraints.gatekeeper.sh/v1beta1",
                "kind": kind,
                "metadata": {"name": f"w{i}"},
                "spec": spec,
            }
        )
    return client


def make_request(i, violating=True):
    """UPDATE AdmissionRequest like the reference's benchmark generator
    (policy_benchmark_test.go:197-231); `violating` pods trip every
    template in the mix (the reference replays 100% violation rate)."""
    sc = {"privileged": True} if violating else {}
    labels = {} if violating else {"app": f"svc{i % 7}"}
    image = "docker.io/evil" if violating else "nginx"
    spec_extra = {"hostPID": True} if violating else {}
    obj = {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {
            "name": f"req{i}",
            "namespace": f"ns{i % 11}",
            "labels": labels,
        },
        "spec": {
            "containers": [
                {
                    "name": "main",
                    "image": image,
                    "securityContext": sc,
                    **(
                        {}
                        if violating
                        else {"resources": {"limits": {"cpu": "1",
                                                       "memory": "1Gi"}}}
                    ),
                }
            ],
            **spec_extra,
        },
    }
    return {
        "uid": f"uid-{i}",
        "kind": {"group": "", "version": "v1", "kind": "Pod"},
        "operation": "UPDATE",
        "name": obj["metadata"]["name"],
        "namespace": obj["metadata"]["namespace"],
        "userInfo": {"username": "bench"},
        "object": obj,
        "oldObject": obj,
    }


def _warm_route(client):
    """Synchronously compile the fused review route so replays measure
    the compiled path (serve-while-compiling otherwise serves cold
    batches on the interpreter and compiles in the background)."""
    from gatekeeper_tpu.constraint import AugmentedReview

    client.warm_review_path(
        [AugmentedReview(make_request(i)) for i in range(16)]
    )


def replay(handler, requests, concurrency):
    lat = np.zeros(len(requests))

    def one(i):
        t0 = time.perf_counter()
        resp = handler.handle(requests[i])
        lat[i] = time.perf_counter() - t0
        return resp.allowed

    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=concurrency) as ex:
        allowed = list(ex.map(one, range(len(requests))))
    wall = time.perf_counter() - t0
    return {
        "concurrency": concurrency,
        "requests": len(requests),
        "wall_seconds": round(wall, 3),
        "throughput_rps": round(len(requests) / wall, 1),
        "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 2),
        "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 2),
        "denied": int(sum(not a for a in allowed)),
    }


def run_webhook_bench(n_requests=10_000, n_constraints=50, err=sys.stderr):
    from gatekeeper_tpu.constraint import RegoDriver, TpuDriver
    from gatekeeper_tpu.webhook.server import (
        BatchedValidationHandler,
        MicroBatcher,
    )

    # CPU baseline: serial handler over the interpreter driver (the
    # reference's architecture: one interpreted query per request) on a
    # subsample, scaled
    from gatekeeper_tpu.webhook import ValidationHandler

    cpu_n = min(200, n_requests)
    cpu_client = build_webhook_client(RegoDriver(), n_constraints)
    cpu_handler = ValidationHandler(cpu_client, TARGET)
    cpu_reqs = [make_request(i) for i in range(cpu_n)]
    cpu_handler.handle(cpu_reqs[0])  # warm
    t0 = time.perf_counter()
    for r in cpu_reqs:
        cpu_handler.handle(r)
    cpu_wall = time.perf_counter() - t0
    cpu = {
        "requests": cpu_n,
        "throughput_rps": round(cpu_n / cpu_wall, 1),
        "p50_ms": round(cpu_wall / cpu_n * 1e3, 2),
    }
    print(f"webhook cpu baseline (python interp): {cpu}", file=err)
    # same interp handler under the measured concurrencies, so the
    # fused-vs-interp CROSSOVER is computed like-for-like (VERDICT r4
    # #2: the concurrency where the fused path starts winning)
    interp_by_conc = {}
    n_sub = min(600, n_requests)
    interp_reqs = (cpu_reqs * -(-n_sub // cpu_n))[:n_sub]
    for conc in (8, 128):
        r = replay(cpu_handler, interp_reqs, conc)
        interp_by_conc[conc] = r["throughput_rps"]
        print(
            f"webhook interp concurrent: c={conc} "
            f"rps={r['throughput_rps']} p50={r['p50_ms']}ms",
            file=err,
        )

    from gatekeeper_tpu.obs import Tracer, span_breakdown

    client = build_webhook_client(TpuDriver(), n_constraints)
    # every replayed request is traced; the per-span-name percentile
    # table (span_breakdown) attributes the p99 to its cost center —
    # queue wait vs flatten/encode vs device dispatch vs render
    tracer = Tracer(max_traces=8192)
    batcher = MicroBatcher(client, TARGET, window_ms=2.0, tracer=tracer)
    handler = BatchedValidationHandler(
        batcher, request_timeout=60, tracer=tracer
    )
    batcher.start()
    try:
        # flip the serve-while-compiling route to warm SYNCHRONOUSLY
        # first (a cold device-sized batch otherwise serves on the
        # interpreter and only kicks a background compile), then warm
        # the jit across the occupancy buckets BOTH concurrency
        # profiles produce (batch-size buckets differ between c=8 and
        # c=128; compiles inside the measured replay would skew p99)
        _warm_route(client)
        warm = [make_request(i) for i in range(256)]
        replay(handler, warm, 64)
        replay(handler, [make_request(i) for i in range(512)], 128)
        replay(
            handler,
            [make_request(i, violating=False) for i in range(512)],
            128,
        )
        tracer.clear()  # warmup traces must not pollute the breakdown

        out = []
        # two violation profiles:
        #  * 100% violating — the reference harness's stress shape
        #    (every violating pair renders; since r4 this is the
        #    COMPILED message path, engine/render.py, not per-pair
        #    interpretation);
        #  * 0% violating — the steady-state admission shape where the
        #    fused device screen answers allow without any host render.
        # Lower concurrencies replay subsamples: per-batch fixed costs
        # make full 10k replays take minutes without changing p50.
        for violating in (True, False):
            requests = [
                make_request(i, violating=violating)
                for i in range(n_requests)
            ]
            hi_n = max(1500, n_requests // 6) if violating else (
                max(4000, n_requests // 2)
            )
            for conc, n_sub in ((8, max(400, n_requests // 25)),
                                (128, hi_n)):
                batcher.batches_dispatched = 0
                batcher.requests_batched = 0
                r = replay(handler, requests[:n_sub], conc)
                r["violating"] = violating
                r["batch_occupancy"] = round(
                    batcher.requests_batched
                    / max(1, batcher.batches_dispatched),
                    1,
                )
                out.append(r)
                print(f"webhook replay: {r}", file=err)
        breakdown = span_breakdown(tracer.recent(8192))
        print(f"webhook span breakdown (ms): {breakdown}", file=err)
    finally:
        batcher.stop()
    # explicit crossover: the lowest measured concurrency where the
    # fused device path out-serves the per-request interpreter (below
    # it, MIN_DEVICE_BATCH adaptive routing keeps admission on the
    # interpreter deliberately)
    crossover = None
    for conc in sorted(interp_by_conc):
        fused_rps = next(
            (
                r["throughput_rps"]
                for r in out
                if r["violating"] and r["concurrency"] == conc
            ),
            None,
        )
        if fused_rps is not None and fused_rps > interp_by_conc[conc]:
            crossover = conc
            break
    result = {
        "cpu_python_interp": cpu,
        "interp_rps_by_concurrency": interp_by_conc,
        "fused_vs_interp_crossover_concurrency": crossover,
        "tpu_batched": out,
        # per-span-name p50/p99/max over every measured request: the
        # diagnosable form of the p99 cliff (which cost center blew up)
        "span_breakdown_ms": breakdown,
    }
    print(
        f"fused-vs-interp crossover concurrency: {crossover} "
        f"(interp rps {interp_by_conc})",
        file=err,
    )
    return result


def _mutator_mix(n_mutators):
    """Synthesized mutator load: cycles the three kinds with varied
    match specs so screening exercises the kernel's dimensions."""
    out = []
    for i in range(n_mutators):
        which = i % 3
        if which == 0:
            out.append({
                "apiVersion": "mutations.gatekeeper.sh/v1alpha1",
                "kind": "AssignMetadata",
                "metadata": {"name": f"bm-label-{i}"},
                "spec": {
                    "match": {"scope": "Namespaced"},
                    "location": f"metadata.labels.bench-{i}",
                    "parameters": {"assign": {"value": f"v{i}"}},
                },
            })
        elif which == 1:
            out.append({
                "apiVersion": "mutations.gatekeeper.sh/v1alpha1",
                "kind": "Assign",
                "metadata": {"name": f"bm-assign-{i}"},
                "spec": {
                    "applyTo": [{"groups": [""], "versions": ["v1"],
                                 "kinds": ["Pod"]}],
                    "match": {"kinds": [{"apiGroups": [""],
                                         "kinds": ["Pod"]}],
                              "namespaces": [f"ns{j}" for j in range(11)]},
                    "location": "spec.containers[name: *].imagePullPolicy",
                    "parameters": {"assign": {"value": "Always"}},
                },
            })
        else:
            out.append({
                "apiVersion": "mutations.gatekeeper.sh/v1alpha1",
                "kind": "ModifySet",
                "metadata": {"name": f"bm-set-{i}"},
                "spec": {
                    "applyTo": [{"groups": [""], "versions": ["v1"],
                                 "kinds": ["Pod"]}],
                    "match": {"kinds": [{"apiGroups": [""],
                                         "kinds": ["Pod"]}]},
                    "location": "spec.containers[name: main].args",
                    "parameters": {"operation": "merge",
                                   "values": {"fromList": [f"--flag{i}"]}},
                },
            })
    return out


def run_mutate_bench(n_requests=10_000, n_mutators=30, err=sys.stderr):
    """The mutate-plane replay (`--mutate`): p50/p99/throughput of
    /v1/mutate's handler path at the measured concurrencies, plus the
    per-span breakdown (queue_wait / screen_dispatch / apply_fixpoint /
    render_patch) so the next BENCH round captures the second admission
    plane with the same cost-center attribution as validation."""
    from gatekeeper_tpu.metrics import MetricsRegistry
    from gatekeeper_tpu.mutation import MutationSystem
    from gatekeeper_tpu.obs import Tracer, span_breakdown
    from gatekeeper_tpu.webhook.mutate import MutateBatcher, MutationHandler

    metrics = MetricsRegistry()
    tracer = Tracer(max_traces=8192)
    system = MutationSystem(metrics=metrics)
    for m in _mutator_mix(n_mutators):
        system.upsert(m)
    batcher = MutateBatcher(
        system, window_ms=2.0, metrics=metrics, tracer=tracer
    )
    handler = MutationHandler(
        batcher, metrics=metrics, request_timeout=60, tracer=tracer
    )
    batcher.start()
    out = []
    try:
        # warm the screen's jit buckets across both concurrency profiles
        replay(handler, [make_request(i) for i in range(256)], 64)
        replay(handler, [make_request(i) for i in range(512)], 128)
        tracer.clear()
        for conc, n_sub in ((8, max(400, n_requests // 25)),
                            (128, max(1500, n_requests // 6))):
            batcher.batches_dispatched = 0
            batcher.requests_batched = 0
            requests = [make_request(i) for i in range(n_sub)]
            r = replay(handler, requests, conc)
            del r["denied"]  # mutate allows; patch presence is the signal
            r["batch_occupancy"] = round(
                batcher.requests_batched
                / max(1, batcher.batches_dispatched),
                1,
            )
            r["screen_dispatches"] = system.screen_dispatches
            out.append(r)
            print(f"mutate replay: {r}", file=err)
        breakdown = span_breakdown(tracer.recent(8192))
        print(f"mutate span breakdown (ms): {breakdown}", file=err)
    finally:
        batcher.stop()
    snap = metrics.snapshot()
    return {
        "mutators": n_mutators,
        "replays": out,
        "span_breakdown_ms": breakdown,
        "fixpoint_iterations": snap["distributions"].get(
            "mutation_fixpoint_iterations", {}
        ),
        "patch_bytes": snap["distributions"].get("mutation_patch_bytes", {}),
    }


_CHAOS_REGO = """package chaosbench

violation[{"msg": msg}] {
    input.review.object.spec.containers[_].securityContext.privileged
    msg := "privileged container"
}
"""


def build_chaos_client(driver, n_constraints):
    """Self-contained policy load (no reference-library dependency):
    the chaos bench measures the failure ENVELOPE — shed rate, breaker
    behavior, degraded-mode latency — not the policy mix, so one
    inline template with n constraint instances is the right corpus
    and keeps --chaos runnable on any machine."""
    from gatekeeper_tpu.constraint import Backend, K8sValidationTarget

    client = Backend(driver).new_client(K8sValidationTarget())
    client.add_template({
        "apiVersion": "templates.gatekeeper.sh/v1beta1",
        "kind": "ConstraintTemplate",
        "metadata": {"name": "chaosbench"},
        "spec": {
            "crd": {"spec": {"names": {"kind": "ChaosBench"}}},
            "targets": [{"target": TARGET, "rego": _CHAOS_REGO}],
        },
    })
    for i in range(n_constraints):
        client.add_constraint({
            "apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": "ChaosBench",
            "metadata": {"name": f"cb{i}"},
            "spec": {"match": {"kinds": [
                {"apiGroups": [""], "kinds": ["Pod"]}
            ]}},
        })
    return client


def run_chaos_bench(n_requests=3000, n_constraints=20, err=sys.stderr):
    """The `--chaos` replay (docs/robustness.md): drive the admission
    plane through three phases — clean, device-faulted, recovered — and
    report p50/p99, shed rate, degraded dispatches, and circuit-breaker
    transitions per phase. The faulted phase arms the REAL
    `webhook.batch_dispatch` fault point, so the measured p99 is the
    host-oracle degraded mode the breaker buys (vs paying a doomed
    fused attempt per batch)."""
    from gatekeeper_tpu.constraint import TpuDriver
    from gatekeeper_tpu.faults import FAULTS, CircuitBreaker
    from gatekeeper_tpu.metrics import MetricsRegistry
    from gatekeeper_tpu.webhook.server import (
        BatchedValidationHandler,
        MicroBatcher,
    )

    metrics = MetricsRegistry()
    client = build_chaos_client(TpuDriver(), n_constraints)
    breaker = CircuitBreaker(
        failure_threshold=3, recovery_seconds=1.0, metrics=metrics
    )
    batcher = MicroBatcher(
        client, TARGET, window_ms=2.0, metrics=metrics,
        max_queue=512, breaker=breaker,
    )
    handler = BatchedValidationHandler(
        batcher, request_timeout=10, metrics=metrics, fail_policy="open"
    )
    n_sub = max(400, n_requests // 6)
    out = []
    batcher.start()
    try:
        _warm_route(client)
        replay(handler, [make_request(i) for i in range(512)], 128)

        def run_phase(name):
            shed0 = batcher.shed_count
            fail0 = batcher.batch_failures
            trans0 = breaker.transitions
            snap0 = metrics.snapshot()["counters"]
            deg_key = 'webhook_degraded_dispatch_total{plane="validation"}'
            deg0 = snap0.get(deg_key, 0)
            r = replay(
                handler, [make_request(i) for i in range(n_sub)], 128
            )
            snap1 = metrics.snapshot()["counters"]
            r.update(
                phase=name,
                shed=batcher.shed_count - shed0,
                shed_rate=round((batcher.shed_count - shed0) / n_sub, 4),
                batch_failures=batcher.batch_failures - fail0,
                degraded_dispatches=snap1.get(deg_key, 0) - deg0,
                breaker_transitions=breaker.transitions - trans0,
                breaker_state=breaker.state,
            )
            out.append(r)
            print(f"chaos phase: {r}", file=err)

        run_phase("clean")
        FAULTS.arm("webhook.batch_dispatch", mode="error")
        run_phase("device_fault")
        FAULTS.reset()
        time.sleep(1.2)  # recovery window: next batch is the probe
        run_phase("recovered")
    finally:
        batcher.stop()
        FAULTS.reset()
    return {
        "constraints": n_constraints,
        "fail_policy": "open",
        "max_queue": batcher.max_queue,
        "breaker": breaker.snapshot(),
        "phases": out,
    }


def run_slo_bench(n_requests=1800, n_constraints=20, err=sys.stderr):
    """The `--slo` replay (docs/observability.md §SLO & saturation):
    the streaming SLO engine watching a clean → device-faulted →
    recovered cycle through the decision-log seam. Reports per-phase
    live attainment/burn/saturation, the breach count (the fault phase
    must fire exactly one slo_breach flight record — hysteresis), and
    the autoscaler headline (saturation, headroom) after recovery.
    Short burn windows scale the 1 min/15 min production policy down
    to bench wall-clock; the arithmetic is identical."""
    from gatekeeper_tpu.constraint import TpuDriver
    from gatekeeper_tpu.faults import FAULTS, CircuitBreaker
    from gatekeeper_tpu.metrics import MetricsRegistry
    from gatekeeper_tpu.obs import (
        DecisionLog,
        FlightRecorder,
        SloEngine,
        SloTarget,
    )
    from gatekeeper_tpu.webhook.server import (
        BatchedValidationHandler,
        MicroBatcher,
    )

    from gatekeeper_tpu.obs import Tracer

    metrics = MetricsRegistry()
    client = build_chaos_client(TpuDriver(), n_constraints)
    tracer = Tracer(max_traces=128)
    decisions = DecisionLog(metrics=metrics, replica="slo-bench")
    recorder = FlightRecorder(
        tracer=tracer, metrics=metrics, decisions=decisions,
        replica="slo-bench",
    )
    # the deadline leaves room for replay queueing at this
    # concurrency: the clean phase must attain so the fault phase's
    # burn (error verdicts) is what crosses the threshold
    target = SloTarget(
        objective=0.99,
        deadline_s=1.5,
        fast_window_s=2.0,
        slow_window_s=10.0,
    )
    slo = SloEngine(
        target=target, metrics=metrics, recorder=recorder,
        replica="slo-bench",
    )
    decisions.slo = slo
    # deliberately NO circuit breaker: the chaos lane shows the
    # breaker absorbing this fault (host-oracle degraded mode keeps
    # the SLO); this lane measures the SLO plane itself, so the fault
    # must be allowed to fail requests and burn budget
    batcher = MicroBatcher(
        client, TARGET, window_ms=2.0, metrics=metrics,
        max_queue=512, decisions=decisions,
    )
    handler = BatchedValidationHandler(
        batcher, request_timeout=10, metrics=metrics,
        fail_policy="open", decision_log=decisions, tracer=tracer,
    )
    n_sub = max(300, n_requests // 6)
    out = []
    batcher.start()
    try:
        _warm_route(client)
        replay(handler, [make_request(i) for i in range(512)], 128)
        # warmup traffic out of the windows: the phases below are the
        # measurement
        slo.reset_windows()

        def run_phase(name):
            r = replay(
                handler, [make_request(i) for i in range(n_sub)], 64
            )
            snap = slo.snapshot()
            plane = snap["planes"].get("validation") or {}
            r.update(
                phase=name,
                slo_attainment=plane.get("attainment_fast"),
                burn_rate_fast=plane.get("burn_rate_fast"),
                saturation=snap["utilization"]["saturation"],
                burning=snap["burning"],
                breaches=snap["breaches"],
            )
            out.append(r)
            print(f"slo phase: {r}", file=err)

        run_phase("clean")
        # the degradation ladder absorbs a lone batch_dispatch fault
        # (the host-oracle rung still answers within deadline), so the
        # SLO stays green — correct, but this lane measures the SLO
        # plane itself. Fail BOTH rungs, like smoke_scenario's fault
        # phase: requests resolve EvaluationUnavailable ("unavailable"
        # verdict = shed), which the engine counts against the budget.
        FAULTS.arm("webhook.batch_dispatch", mode="error")
        FAULTS.arm("webhook.host_review", mode="error")
        run_phase("device_fault")
        FAULTS.reset()
        # let the fault-phase errors age out of the fast window so the
        # recovered phase measures the recovered system (and the
        # hysteresis latch clears below clear_threshold)
        time.sleep(target.fast_window_s + 0.2)
        run_phase("recovered")
    finally:
        batcher.stop()
        FAULTS.reset()
        recorder.flush(timeout=1.0)
        recorder.stop()
    snap = slo.snapshot()
    plane = snap["planes"].get("validation") or {}
    util = snap["utilization"]
    return {
        "constraints": n_constraints,
        "target": target.to_dict(),
        "phases": out,
        "slo_attainment": plane.get("attainment_slow"),
        "burn_rate_fast": plane.get("burn_rate_fast"),
        "saturation": util["saturation"],
        "headroom_rps": util["estimated_headroom_rps"],
        "burning": snap["burning"],
        "breaches": snap["breaches"],
        "error_budget_remaining": snap["error_budget_remaining"],
        "breach_records": [
            r["trigger"] for r in recorder.records()
            if r.get("trigger") == "slo_breach"
        ],
    }


def run_integrity_bench(n_requests=1800, n_constraints=20, k=3,
                        err=sys.stderr):
    """The `--integrity` lane (docs/robustness.md §Verdict integrity):
    the verdict-integrity plane through a clean → injected-SDC →
    self-test-healed cycle on partitioned dispatch. Reports the shadow
    divergence rate, the canary packing overhead (p50 delta vs the
    SAME corpus with the plane detached — canaries ride padding slots,
    so the contract is ≤3%), and the detection latency from arming the
    device bit-flip to corruption quarantine."""
    from gatekeeper_tpu.constraint import TpuDriver
    from gatekeeper_tpu.constraint import tpudriver as _td
    from gatekeeper_tpu.faults import FAULTS, device_point
    from gatekeeper_tpu.integrity import IntegrityPlane
    from gatekeeper_tpu.metrics import MetricsRegistry
    from gatekeeper_tpu.obs import DecisionLog, FlightRecorder, Tracer
    from gatekeeper_tpu.parallel.partition import PartitionDispatcher
    from gatekeeper_tpu.webhook.server import (
        BatchedValidationHandler,
        MicroBatcher,
    )

    metrics = MetricsRegistry()
    driver = TpuDriver()
    client = build_chaos_client(driver, n_constraints)
    tracer = Tracer(max_traces=128)
    decisions = DecisionLog(metrics=metrics, replica="integrity-bench")
    recorder = FlightRecorder(
        tracer=tracer, metrics=metrics, decisions=decisions,
        replica="integrity-bench",
    )
    disp = PartitionDispatcher(
        client, TARGET, k=k, metrics=metrics,
        failure_threshold=3, recovery_seconds=1.0,
    )
    plane = IntegrityPlane(
        metrics=metrics, decisions=decisions, recorder=recorder,
        quarantine_threshold=2, shadow_sample_n=8,
    )
    plane.attach_client(client)
    plane.attach_dispatcher(disp)
    batcher = MicroBatcher(
        client, TARGET, window_ms=2.0, metrics=metrics,
        max_queue=512, partitioner=disp, integrity=plane,
    )
    handler = BatchedValidationHandler(
        batcher, request_timeout=10, metrics=metrics,
        fail_policy="open", tracer=tracer,
    )
    n_sub = max(300, n_requests // 6)
    phases = []

    def run_phase(name, **extra):
        r = replay(
            handler, [make_request(i) for i in range(n_sub)], 64
        )
        snap = plane.snapshot()
        r.update(
            phase=name,
            canary_batches=snap["canary"]["batches"],
            canary_mismatch_batches=snap["canary"]["mismatch_batches"],
            quarantined=sorted(snap["quarantined"]),
            **extra,
        )
        phases.append(r)
        print(f"integrity phase: {r}", file=err)
        return r

    saved_min_batch = _td.MIN_DEVICE_BATCH
    _td.MIN_DEVICE_BATCH = 1  # keep micro-batches on the device path
    batcher.start()
    try:
        _warm_route(client)
        # warm with the exact phase workload (same corpus, same
        # concurrency): a different batch-shape mix would leave compile
        # buckets cold and bill them to the baseline phase
        for _ in range(2):
            replay(handler, [make_request(i) for i in range(n_sub)], 64)

        # canary overhead: the same corpus, plane detached vs attached
        # (the baseline replays first so cache warmth favors the
        # canaried run, making the reported overhead conservative)
        base = run_phase("baseline_detached")
        driver.set_integrity(plane)
        clean = run_phase("clean")
        overhead = (
            (clean["p50_ms"] - base["p50_ms"]) / base["p50_ms"]
            if base["p50_ms"] else 0.0
        )

        # injected SDC: one device's canary rows bit-flip every batch;
        # detection latency = arm -> corruption quarantine trip
        plan = disp.plan()
        sick = plan.partitions[0].device
        t_arm = time.monotonic()
        FAULTS.arm(device_point("integrity.canary", sick), mode="error")
        sdc = run_phase("injected_sdc", sick_device=sick)
        snap = plane.snapshot()
        q = snap["quarantined"].get(str(sick))
        detection_s = (
            round((time.monotonic() - t_arm) - q["for_s"], 3)
            if q else None
        )
        sdc["detection_latency_s"] = detection_s

        # heal: disarm the flip, golden self-test replays clean
        FAULTS.reset()
        healed = plane.selftest(sick)
        run_phase("selftest_healed", selftest_pass=healed)
        plane.drain_shadow()
    finally:
        _td.MIN_DEVICE_BATCH = saved_min_batch
        batcher.stop()
        plane.close()
        FAULTS.reset()
        recorder.stop()
    snap = plane.snapshot()
    sampled = snap["shadow"]["sampled"]
    return {
        "constraints": n_constraints,
        "partitions": k,
        "phases": phases,
        "divergence_rate": round(
            snap["shadow"]["divergences"] / sampled, 4
        ) if sampled else 0.0,
        "shadow_sampled": sampled,
        "canary_overhead_frac": round(overhead, 4),
        "detection_latency_s": detection_s,
        "selftest_healed": bool(healed),
        "canary": snap["canary"],
        "selftest": snap["selftest"],
    }


def _sched_request(i, cls):
    """A bench request pinned to one of two tenant namespaces: the
    25% "quiet" class (well-behaved, inside its fair share) vs the 75%
    "noisy" class (the overload driver). Both object and oldObject
    share the metadata dict, so one namespace write covers the
    decision-log tenant seam and the scheduler quota key."""
    req = make_request(i)
    ns = f"ns-{cls}"
    req["namespace"] = ns
    req["object"]["metadata"]["namespace"] = ns
    return req


def run_sched_bench(duration_s=6.0, rps=600.0, n_constraints=20,
                    err=sys.stderr):
    """The `--sched` lane (docs/operations.md §Admission scheduling):
    the SAME open-loop two-tenant overload driven first through the
    legacy FIFO queue, then through the deadline scheduler. Headline:
    the per-class attainment split (FIFO lets the noisy tenant starve
    the quiet one; the scheduler caps the noisy tenant at its fair
    share), and the shed split (predictive `predicted_miss` sheds vs
    FIFO's blind `queue_full` tail-drops).

    Overload is forced, not hoped for: the review path is throttled to
    a fixed per-row device cost (~3 ms/row ≈ 333 rps real capacity vs
    600 offered), the scheduler's cost model is floored to that same
    cost (the bench knob standing in for a warm attribution EWMA, and
    seeded into the SLO engine so saturation reads hot from t=0), and
    the scheduler's overload thresholds are lowered so the ~6 s phase
    reliably crosses them."""
    import itertools
    import threading

    from gatekeeper_tpu.constraint import TpuDriver
    from gatekeeper_tpu.faults import FAULTS
    from gatekeeper_tpu.metrics import MetricsRegistry
    from gatekeeper_tpu.obs import DecisionLog, SloEngine, SloTarget
    from gatekeeper_tpu.sched import BatchCostModel
    from gatekeeper_tpu.soak.loadgen import run_open_loop
    from gatekeeper_tpu.webhook.server import (
        BatchedValidationHandler,
        MicroBatcher,
    )

    deadline_s = 0.5
    per_row_s = 3e-3
    phases = []
    for policy in ("fifo", "deadline"):
        metrics = MetricsRegistry()
        client = build_webhook_client(TpuDriver(), n_constraints)
        # throttle the review path to a fixed per-row device cost so
        # real capacity (1/per_row_s ≈ 333 rps) sits well under the
        # offered rate AND matches the scheduler's cost model exactly
        # — the predicted-miss arithmetic is judged against reality
        def throttled_review_many(reviews, _inner=client.review_many):
            time.sleep(per_row_s * len(reviews))
            return _inner(reviews)

        client.review_many = throttled_review_many
        decisions = DecisionLog(metrics=metrics, replica=f"sched-{policy}")
        target = SloTarget(
            objective=0.99,
            deadline_s=deadline_s,
            fast_window_s=2.0,
            slow_window_s=10.0,
        )
        slo = SloEngine(
            target=target, metrics=metrics, replica=f"sched-{policy}"
        )
        decisions.slo = slo
        batcher = MicroBatcher(
            client, TARGET, window_ms=2.0, metrics=metrics,
            max_queue=256, max_batch=64, decisions=decisions,
            sched_policy=policy, slo=slo,
        )
        # bench knobs (see docstring): deterministic per-row cost floor
        # so predicted-miss arithmetic has a live cost model from t=0,
        # and lowered overload thresholds so the short phase crosses
        # them; production uses the attribution-fed defaults
        batcher.sched.cost = BatchCostModel(
            slo=slo, per_row_fn=lambda: per_row_s
        )
        batcher.sched.overload_saturation = 0.5
        batcher.sched.burning_saturation = 0.4
        handler = BatchedValidationHandler(
            batcher, request_timeout=deadline_s, metrics=metrics,
            fail_policy="open", decision_log=decisions,
        )
        counter = itertools.count()
        lock = threading.Lock()
        per_class = {"quiet": [], "noisy": []}

        def submit(plane):
            i = next(counter)
            cls = "quiet" if i % 4 == 0 else "noisy"
            req = _sched_request(i, cls)
            t0 = time.perf_counter()
            try:
                resp = handler.handle(req)
                status = 200
                outcome = "ok" if resp.allowed else "denied"
            except Exception:
                status, outcome = 500, "conn_error"
            lat = time.perf_counter() - t0
            with lock:
                per_class[cls].append(lat)
            return status, outcome

        batcher.start()
        try:
            _warm_route(client)
            replay(
                handler,
                [_sched_request(i, "noisy" if i % 4 else "quiet")
                 for i in range(128)],
                32,
            )
            slo.reset_windows()
            # seed the saturation signal with the throttled cost so the
            # feedback loop reads hot from the first arrivals
            slo.note_cost(per_row_s, rows=1)
            # warmup traffic already hit the decision log; the phase's
            # per-class split is the DELTA against this baseline
            base = decisions.tenant_stats()
            per_class["quiet"].clear()
            per_class["noisy"].clear()
            load = run_open_loop(
                submit, rps=rps, duration_s=duration_s,
                deadline_s=deadline_s, seed=99,
            )
        finally:
            batcher.stop()
            FAULTS.reset()
        stats = decisions.tenant_stats()
        classes = {}
        for cls in ("quiet", "noisy"):
            key = f"validation/ns-{cls}"
            row = stats.get(key) or {}
            b = base.get(key) or {}
            cnt = row.get("count", 0) - b.get("count", 0)
            ok = row.get("ok", 0) - b.get("ok", 0)
            shed = row.get("shed", 0) - b.get("shed", 0)
            lats = per_class[cls]
            classes[cls] = {
                "requests": cnt,
                "ok": ok,
                "shed": shed,
                "attainment": round(ok / cnt, 4) if cnt else None,
                "p50_ms": (
                    round(float(np.percentile(lats, 50)) * 1e3, 2)
                    if lats else None
                ),
                "p99_ms": (
                    round(float(np.percentile(lats, 99)) * 1e3, 2)
                    if lats else None
                ),
            }
        snap = batcher.sched.snapshot()
        phase = {
            "phase": policy,
            "generated": load.generated,
            "achieved_rps": load.achieved_rps,
            "open_loop_attainment": round(load.slo_attainment(), 4),
            "classes": classes,
            "sheds": snap["sheds"],
            "admitted": snap["admitted"],
            "overloaded": snap["overloaded"],
            "saturation": snap["saturation"],
            "tenants": snap["tenants"],
        }
        phases.append(phase)
        print(f"sched phase: {policy} classes={classes} "
              f"sheds={snap['sheds']}", file=err)

    fifo, dl = phases[0], phases[1]
    atts = [
        c["attainment"] for c in dl["classes"].values()
        if c["attainment"] is not None
    ]
    return {
        "constraints": n_constraints,
        "target_rps": rps,
        "duration_s": duration_s,
        "deadline_s": deadline_s,
        "phases": phases,
        # headline: the deadline phase's per-class split, the worst
        # per-tenant attainment under the scheduler (bench_compare
        # watches it down-bad), and predictive vs blind shed counts
        "quiet_p50_ms": dl["classes"]["quiet"]["p50_ms"],
        "quiet_p99_ms": dl["classes"]["quiet"]["p99_ms"],
        "noisy_p50_ms": dl["classes"]["noisy"]["p50_ms"],
        "noisy_p99_ms": dl["classes"]["noisy"]["p99_ms"],
        "quiet_attainment": dl["classes"]["quiet"]["attainment"],
        "noisy_attainment": dl["classes"]["noisy"]["attainment"],
        "tenant_attainment_min": min(atts) if atts else None,
        "predicted_miss_shed": dl["sheds"].get("predicted_miss", 0),
        "blind_shed": fifo["sheds"].get("queue_full", 0),
    }


def build_partition_client(driver, n_constraints):
    """Policy load for the --partitions lane: ONE template, n
    constraints named w000..wNNN (zero-padded so the driver's sorted
    identity order is numeric), constraint j matching ONLY namespace
    part-ns-<j % 4>. Round-robin partitioning over the sorted identity
    list puts global index j in partition j % k — so with k=4 every
    partition's constraints match exactly one namespace, and the bench
    can address one fault domain with one namespace."""
    from gatekeeper_tpu.constraint import Backend, K8sValidationTarget

    client = Backend(driver).new_client(K8sValidationTarget())
    client.add_template({
        "apiVersion": "templates.gatekeeper.sh/v1beta1",
        "kind": "ConstraintTemplate",
        "metadata": {"name": "partbench"},
        "spec": {
            "crd": {"spec": {"names": {"kind": "PartBench"}}},
            "targets": [{
                "target": TARGET,
                "rego": _CHAOS_REGO.replace("chaosbench", "partbench"),
            }],
        },
    })
    for i in range(n_constraints):
        client.add_constraint({
            "apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": "PartBench",
            "metadata": {"name": f"w{i:03d}"},
            "spec": {"match": {
                "kinds": [{"apiGroups": [""], "kinds": ["Pod"]}],
                "namespaces": [f"part-ns-{i % 4}"],
            }},
        })
    return client


def part_request(i, ns_idx, violating=True):
    r = make_request(i, violating)
    ns = f"part-ns-{ns_idx}"
    r["namespace"] = ns
    r["object"]["metadata"]["namespace"] = ns
    if "oldObject" in r:
        r["oldObject"]["metadata"]["namespace"] = ns
    return r


def _normalize_results(results):
    return [
        (
            r.constraint.get("kind"),
            (r.constraint.get("metadata") or {}).get("name"),
            r.msg,
        )
        for r in results
    ]


def run_partitions_bench(n_requests=2000, n_constraints=40, k=4,
                         err=sys.stderr):
    """The `--partitions` lane (docs/robustness.md §Fault domains):
    partitioned program dispatch with per-device breakers and
    quarantine. Phases: per-partition fused latency, a healthy-subsets
    phase with ONE device faulted (requests matching only healthy
    partitions must show ZERO degraded dispatches), a mixed sick-device
    phase (degraded coverage fraction + time to re-homed full fused
    coverage), and post-disarm recovery (probe heals, plan restores
    home devices). Also spot-checks partition parity: merged
    per-partition verdicts == the monolithic dispatch."""
    from gatekeeper_tpu.constraint import TpuDriver
    from gatekeeper_tpu.faults import FAULTS, device_point
    from gatekeeper_tpu.metrics import MetricsRegistry
    from gatekeeper_tpu.parallel.partition import (
        PartitionDispatcher,
        merge_partition_results,
    )
    from gatekeeper_tpu.webhook.server import (
        BatchedValidationHandler,
        MicroBatcher,
    )

    metrics = MetricsRegistry()
    client = build_partition_client(TpuDriver(), n_constraints)
    disp = PartitionDispatcher(
        client, TARGET, k=k, metrics=metrics,
        failure_threshold=3, recovery_seconds=1.0,
    )
    batcher = MicroBatcher(
        client, TARGET, window_ms=2.0, metrics=metrics,
        max_queue=512, partitioner=disp,
    )
    handler = BatchedValidationHandler(
        batcher, request_timeout=10, metrics=metrics, fail_policy="open"
    )
    n_sub = max(256, n_requests // 6)
    phases = []
    deg_key = 'webhook_degraded_dispatch_total{plane="validation"}'

    def run_phase(name, requests, concurrency=64):
        d0 = dict(disp.dispatches)
        deg0 = metrics.snapshot()["counters"].get(deg_key, 0)
        r = replay(handler, requests, concurrency)
        d1 = disp.dispatches
        deltas = {
            route: d1.get(route, 0) - d0.get(route, 0)
            for route in ("fused", "host", "failed", "skipped")
        }
        total = deltas["fused"] + deltas["host"] + deltas["failed"]
        r.update(
            phase=name,
            partition_dispatches=deltas,
            degraded_dispatches=(
                metrics.snapshot()["counters"].get(deg_key, 0) - deg0
            ),
            degraded_coverage_fraction=round(
                (deltas["host"] + deltas["failed"]) / total, 4
            ) if total else 0.0,
            quarantined=list(disp.snapshot()["quarantined"]),
        )
        phases.append(r)
        print(f"partitions phase: {r}", file=err)
        return r

    def mixed(n, start=0):
        return [part_request(start + i, i % 4) for i in range(n)]

    batcher.start()
    try:
        _warm_route(client)
        plan = disp.plan()
        for p in plan.partitions:
            disp.ensure_staged(p)
        # warm each partition's sub-program kernels off the clock
        warm_reviews = [
            batcher.target_handler.augment_request(r)
            for r in mixed(32)
        ]
        for p in plan.partitions:
            client.review_many_subset(warm_reviews, p.subset,
                                      device=p.device)
        # parity spot check: merged partitioned == monolithic, request
        # by request (the full property battery lives in the chaos lane)
        mono = client.review_many(warm_reviews)
        per_part = [
            client.review_many_subset(warm_reviews, p.subset,
                                      device=p.device)
            for p in plan.partitions
        ]
        parity_ok = True
        for i in range(len(warm_reviews)):
            merged = merge_partition_results(
                [
                    (pp[i].by_target.get(TARGET).results
                     if TARGET in pp[i].by_target else [])
                    for pp in per_part
                ],
                plan.order,
            )
            expect = (
                mono[i].by_target[TARGET].results
                if TARGET in mono[i].by_target else []
            )
            if _normalize_results(merged) != _normalize_results(expect):
                parity_ok = False
        # per-partition fused latency (direct subset dispatch)
        per_partition = []
        for p in plan.partitions:
            lat = []
            for _ in range(12):
                t0 = time.perf_counter()
                client.review_many_subset(warm_reviews, p.subset,
                                          device=p.device)
                lat.append(time.perf_counter() - t0)
            per_partition.append({
                "partition": p.index,
                "device": p.device,
                "constraints": len(p.keys),
                "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 2),
                "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 2),
            })
            print(f"partition {p.index}: {per_partition[-1]}", file=err)

        run_phase("fused_healthy", mixed(n_sub))
        # ONE device sick; requests that match only HEALTHY partitions
        # must pay nothing: zero degraded dispatches, zero host routes
        FAULTS.arm(device_point("driver.device_dispatch", 1),
                   mode="error")
        t_fault = time.monotonic()
        healthy = run_phase(
            "sick_device_healthy_subsets",
            [part_request(i, [0, 2, 3][i % 3]) for i in range(n_sub)],
        )
        # mixed traffic: ns-1's subset degrades to host, the device-1
        # breaker trips, quarantine re-homes its partition, and full
        # fused coverage returns while the chip is still sick
        recovery_s = None
        waves = 0
        fault0 = dict(disp.dispatches)
        while waves < 40:
            d0 = dict(disp.dispatches)
            replay(handler, mixed(128, start=waves * 128), 64)
            waves += 1
            degraded = (
                disp.dispatches.get("host", 0) - d0.get("host", 0)
                + disp.dispatches.get("failed", 0) - d0.get("failed", 0)
            )
            if degraded == 0:
                recovery_s = round(time.monotonic() - t_fault, 3)
                break
        fault1 = dict(disp.dispatches)
        fault_deltas = {
            route: fault1.get(route, 0) - fault0.get(route, 0)
            for route in ("fused", "host", "failed")
        }
        fault_total = sum(fault_deltas.values())
        fault_coverage = (
            round(
                (fault_deltas["host"] + fault_deltas["failed"])
                / fault_total, 4,
            )
            if fault_total else 0.0
        )
        run_phase("sick_device_rehomed", mixed(n_sub))
        # disarm: the quarantined device's half-open probe heals it and
        # the plan restores the home assignment
        FAULTS.reset()
        time.sleep(1.2)
        run_phase("recovered", mixed(n_sub))
        restored = all(
            p.device == p.home_device
            for p in disp.plan().partitions
        )
    finally:
        batcher.stop()
        disp.close()
        FAULTS.reset()
    return {
        "partitions": k,
        "constraints": n_constraints,
        "plan": disp.snapshot()["plan"],
        "per_partition": per_partition,
        "parity_ok": parity_ok,
        "healthy_subset_degraded": (
            healthy["degraded_dispatches"]
            + healthy["partition_dispatches"]["host"]
        ),
        "degraded_coverage_fraction": fault_coverage,
        "recovery_s": recovery_s,
        "home_restored": restored,
        "dispatcher": disp.snapshot(),
        "phases": phases,
    }


_CHURN_BENCH_REGO = """package churnbench{n}

violation[{{"msg": msg}}] {{
    input.review.object.spec.containers[_].securityContext.privileged
    msg := "churn{n}: privileged container"
}}
"""


def run_churn_bench(n_requests=600, wave_sizes=(10, 50, 500), k=4,
                    err=sys.stderr):
    """The `--churn` lane (docs/compile.md §Bench): template ingest
    waves against a partitioned plan under live admission load. Per
    wave size it reports ingest-to-serve latency (first template add ->
    every partition's swapped program serving fused again) plus the
    zero-downtime counters: degraded dispatches and in-process 5xx
    (handler exceptions) during the wave must both be zero — in-flight
    batches ride the old programs or the host rung while the shadow
    slot compiles."""
    from gatekeeper_tpu.constraint import TpuDriver
    from gatekeeper_tpu.metrics import MetricsRegistry
    from gatekeeper_tpu.parallel.partition import PartitionDispatcher
    from gatekeeper_tpu.webhook.server import (
        BatchedValidationHandler,
        MicroBatcher,
    )

    metrics = MetricsRegistry()
    client = build_partition_client(TpuDriver(), 16)
    driver = client._driver
    disp = PartitionDispatcher(
        client, TARGET, k=k, metrics=metrics,
        failure_threshold=3, recovery_seconds=1.0,
    )
    batcher = MicroBatcher(
        client, TARGET, window_ms=2.0, metrics=metrics,
        max_queue=512, partitioner=disp,
    )
    handler = BatchedValidationHandler(
        batcher, request_timeout=10, metrics=metrics, fail_policy="open"
    )
    deg_key = 'webhook_degraded_dispatch_total{plane="validation"}'

    def replay_counting(requests, concurrency=64):
        """replay() that counts handler exceptions — what the HTTP
        plane would surface as 5xx — instead of propagating them."""
        lat = np.zeros(len(requests))
        errs = np.zeros(len(requests), bool)

        def one(i):
            t0 = time.perf_counter()
            try:
                handler.handle(requests[i])
            except Exception:
                errs[i] = True
            lat[i] = time.perf_counter() - t0

        with ThreadPoolExecutor(max_workers=concurrency) as ex:
            list(ex.map(one, range(len(requests))))
        return {
            "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 2),
            "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 2),
            "http_5xx": int(errs.sum()),
        }

    def all_ready():
        plan = disp.plan()
        ready = getattr(driver, "subset_ready", None)
        if ready is None:
            return True
        return all(ready(TARGET, p.subset) for p in plan.partitions)

    def mixed(n, start=0):
        return [part_request(start + i, i % 4) for i in range(n)]

    churn_n = 0
    waves = []
    batcher.start()
    try:
        _warm_route(client)
        for p in disp.plan().partitions:
            disp.ensure_staged(p)
        replay_counting(mixed(max(128, n_requests // 4)))
        for wave in wave_sizes:
            deg0 = metrics.snapshot()["counters"].get(deg_key, 0)
            c0 = getattr(driver, "program_compiles", 0)
            s0 = getattr(driver, "subset_swaps", 0)
            cf0 = getattr(driver, "subset_carryforwards", 0)
            http_5xx = 0
            t0 = time.perf_counter()
            for _ in range(wave):
                churn_n += 1
                kind = f"ChurnBench{churn_n}"
                client.add_template({
                    "apiVersion": "templates.gatekeeper.sh/v1beta1",
                    "kind": "ConstraintTemplate",
                    "metadata": {"name": kind.lower()},
                    "spec": {
                        "crd": {"spec": {"names": {"kind": kind}}},
                        "targets": [{
                            "target": TARGET,
                            "rego": _CHURN_BENCH_REGO.format(n=churn_n),
                        }],
                    },
                })
                client.add_constraint({
                    "apiVersion": "constraints.gatekeeper.sh/v1beta1",
                    "kind": kind,
                    "metadata": {"name": f"wave-{churn_n}"},
                    "spec": {"match": {
                        "kinds": [{"apiGroups": [""], "kinds": ["Pod"]}],
                        "namespaces": [f"part-ns-{churn_n % 4}"],
                    }},
                })
            # serve through the churn: traffic keeps flowing while the
            # changed partitions shadow-compile and swap; readiness is
            # every partition of the NEW plan serving its new program
            ingest_to_serve_ms = None
            rounds = 0
            while rounds < 120:
                r = replay_counting(mixed(128, start=rounds * 128))
                http_5xx += r["http_5xx"]
                rounds += 1
                if all_ready():
                    ingest_to_serve_ms = round(
                        (time.perf_counter() - t0) * 1e3, 1
                    )
                    break
            steady = replay_counting(mixed(max(128, n_requests // 4)))
            http_5xx += steady["http_5xx"]
            row = {
                "wave": wave,
                "ingest_to_serve_ms": ingest_to_serve_ms,
                "degraded_dispatches": (
                    metrics.snapshot()["counters"].get(deg_key, 0) - deg0
                ),
                "http_5xx": http_5xx,
                "compiles": getattr(driver, "program_compiles", 0) - c0,
                "swaps": getattr(driver, "subset_swaps", 0) - s0,
                "carryforwards": (
                    getattr(driver, "subset_carryforwards", 0) - cf0
                ),
                "serve_rounds": rounds,
                "steady_p50_ms": steady["p50_ms"],
                "steady_p99_ms": steady["p99_ms"],
            }
            waves.append(row)
            print(f"churn wave: {row}", file=err)
    finally:
        batcher.stop()
        disp.close()
    return {
        "partitions": k,
        "waves": waves,
        "ingest_to_serve_ms": (
            waves[-1]["ingest_to_serve_ms"] if waves else None
        ),
        "degraded_dispatches": sum(
            w["degraded_dispatches"] for w in waves
        ),
        "http_5xx": sum(w["http_5xx"] for w in waves),
        "compiles": sum(w["compiles"] for w in waves),
        "swaps": sum(w["swaps"] for w in waves),
        "compile_plane": (
            driver.compile_plane_stats()
            if hasattr(driver, "compile_plane_stats") else None
        ),
    }


_EXTERNAL_REGO = """package externalbench

violation[{"msg": msg}] {
    images := [img | img := input.review.object.spec.containers[_].image]
    response := external_data({"provider": "bench-provider", "keys": images})
    count(response.errors) > 0
    msg := sprintf("verification failed: %v", [response.errors])
}
"""


class _StubProviderHTTP:
    """Stdlib stub provider for the --external lane: answers the
    ProviderRequest protocol, counts every outbound fetch (the
    batching-contract number this bench reports), and marks keys
    containing "bad" with an error entry."""

    def __init__(self, latency_s=0.0):
        import json as _json
        import threading
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        self.fetches = 0
        self.keys_fetched = 0
        self.latency_s = latency_s
        outer = self

        class _H(BaseHTTPRequestHandler):
            def do_POST(self):  # noqa: N802
                n = int(self.headers.get("Content-Length", 0))
                body = _json.loads(self.rfile.read(n) or b"{}")
                keys = ((body.get("request") or {}).get("keys")) or []
                outer.fetches += 1
                outer.keys_fetched += len(keys)
                if outer.latency_s:
                    time.sleep(outer.latency_s)
                payload = _json.dumps({
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


def run_external_bench(n_requests=3000, n_keys=7, err=sys.stderr):
    """The `--external` replay (docs/externaldata.md): admission load
    whose policy consults an external-data provider through the batch
    plane. Reports p50/p99, cache hit rate, and fetches-per-batch —
    the numbers that prove lookups ride the micro-batch instead of
    breaking it (steady state: hit rate -> 1.0, fetches/batch -> 0)."""
    import threading

    from gatekeeper_tpu.constraint import (
        Backend,
        K8sValidationTarget,
        TpuDriver,
    )
    from gatekeeper_tpu.externaldata import ExternalDataSystem
    from gatekeeper_tpu.metrics import MetricsRegistry
    from gatekeeper_tpu.webhook.server import (
        BatchedValidationHandler,
        MicroBatcher,
    )

    stub = _StubProviderHTTP()
    metrics = MetricsRegistry()
    system = ExternalDataSystem(metrics=metrics)
    system.upsert({
        "apiVersion": "externaldata.gatekeeper.sh/v1alpha1",
        "kind": "Provider",
        "metadata": {"name": "bench-provider"},
        "spec": {
            "url": stub.url,
            "timeout": 5,
            "failurePolicy": "Ignore",
            "cacheTTLSeconds": 3600,
            "negativeCacheTTLSeconds": 3600,
        },
    })
    client = Backend(TpuDriver()).new_client(K8sValidationTarget())
    client.set_external_data(system)
    client.add_template({
        "apiVersion": "templates.gatekeeper.sh/v1beta1",
        "kind": "ConstraintTemplate",
        "metadata": {"name": "externalbench"},
        "spec": {
            "crd": {"spec": {"names": {"kind": "ExternalBench"}}},
            "targets": [{"target": TARGET, "rego": _EXTERNAL_REGO}],
        },
    })
    client.add_constraint({
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "ExternalBench",
        "metadata": {"name": "eb"},
        "spec": {"match": {"kinds": [
            {"apiGroups": [""], "kinds": ["Pod"]}
        ]}},
    })

    def ext_request(i, violating=False):
        r = make_request(i, violating=False)
        key = f"bad.img/{i % n_keys}" if violating else f"reg.example/app{i % n_keys}"
        r["object"]["spec"]["containers"][0]["image"] = key
        return r

    batcher = MicroBatcher(client, TARGET, window_ms=2.0, metrics=metrics)
    handler = BatchedValidationHandler(
        batcher, request_timeout=30, metrics=metrics
    )
    out = []
    batcher.start()
    try:
        _warm_route(client)

        def run_phase(name, violating):
            f0, b0 = system.fetch_count, batcher.batches_dispatched
            snap0 = metrics.snapshot()["counters"]
            lk = "externaldata_cache_lookups_total"
            hits0 = sum(
                v for k, v in snap0.items()
                if k.startswith(lk) and 'result="hit"' in k
            )
            total0 = sum(
                v for k, v in snap0.items() if k.startswith(lk)
            )
            r = replay(
                handler,
                [ext_request(i, violating) for i in range(max(400, n_requests // 3))],
                128,
            )
            snap1 = metrics.snapshot()["counters"]
            hits1 = sum(
                v for k, v in snap1.items()
                if k.startswith(lk) and 'result="hit"' in k
            )
            total1 = sum(
                v for k, v in snap1.items() if k.startswith(lk)
            )
            batches = max(1, batcher.batches_dispatched - b0)
            r.update(
                phase=name,
                fetches=system.fetch_count - f0,
                fetches_per_batch=round(
                    (system.fetch_count - f0) / batches, 3
                ),
                cache_hit_rate=round(
                    (hits1 - hits0) / max(1, total1 - total0), 4
                ),
            )
            out.append(r)
            print(f"external phase: {r}", file=err)

        run_phase("cold_allow", violating=False)
        run_phase("warm_allow", violating=False)
        run_phase("warm_deny", violating=True)
    finally:
        batcher.stop()
        stub.stop()
    return {
        "keys": n_keys,
        "provider_fetches": system.fetch_count,
        "provider_keys_fetched": stub.keys_fetched,
        "stale_serves": system.stale_serves,
        "phases": out,
    }


def run_fleet_bench(n_requests=1200, n_keys=24, err=sys.stderr):
    """The `--fleet` replay (docs/fleet.md): cold-fetch amplification
    of the external-data plane as the webhook scales horizontally. A
    load balancer spreads identical traffic over every replica, so
    WITHOUT the fleet cache plane each of N replicas pays its own cold
    fetch per key — amplification N. WITH the plane, the first replica
    to fetch publishes and peers merge: amplification stays ~1.

    Phases: n1 (one replica, the floor), n2_isolated (two replicas, no
    fleet — the regression this subsystem removes), n2_fleet (two
    replicas gossiping through one FakeCluster). Reports fetches per
    key for each and the headline cold_fetch_amplification ratio."""
    from gatekeeper_tpu.constraint import (
        Backend,
        K8sValidationTarget,
        TpuDriver,
    )
    from gatekeeper_tpu.control.events import FakeCluster
    from gatekeeper_tpu.externaldata import ExternalDataSystem
    from gatekeeper_tpu.fleet import FleetPlane
    from gatekeeper_tpu.metrics import MetricsRegistry
    from gatekeeper_tpu.webhook.server import (
        BatchedValidationHandler,
        MicroBatcher,
    )

    stub = _StubProviderHTTP()

    def build_replica(fleet_plane=None):
        metrics = MetricsRegistry()
        system = ExternalDataSystem(metrics=metrics)
        if fleet_plane is not None:
            fleet_plane.attach_cache(system)
        system.upsert({
            "apiVersion": "externaldata.gatekeeper.sh/v1alpha1",
            "kind": "Provider",
            "metadata": {"name": "bench-provider"},
            "spec": {
                "url": stub.url,
                "timeout": 5,
                "failurePolicy": "Ignore",
                "cacheTTLSeconds": 3600,
                "negativeCacheTTLSeconds": 3600,
            },
        })
        client = Backend(TpuDriver()).new_client(K8sValidationTarget())
        client.set_external_data(system)
        client.add_template({
            "apiVersion": "templates.gatekeeper.sh/v1beta1",
            "kind": "ConstraintTemplate",
            "metadata": {"name": "externalbench"},
            "spec": {
                "crd": {"spec": {"names": {"kind": "ExternalBench"}}},
                "targets": [{"target": TARGET, "rego": _EXTERNAL_REGO}],
            },
        })
        client.add_constraint({
            "apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": "ExternalBench",
            "metadata": {"name": "eb"},
            "spec": {"match": {"kinds": [
                {"apiGroups": [""], "kinds": ["Pod"]}
            ]}},
        })
        batcher = MicroBatcher(
            client, TARGET, window_ms=2.0, metrics=metrics
        )
        handler = BatchedValidationHandler(
            batcher, request_timeout=30, metrics=metrics
        )
        batcher.start()
        return system, batcher, handler

    def ext_request(i):
        r = make_request(i, violating=False)
        r["object"]["spec"]["containers"][0]["image"] = (
            f"reg.example/app{i % n_keys}"
        )
        return r

    phases = []

    def run_phase(name, handlers, planes=()):
        """Drive every handler with the SAME key universe (the load-
        balancer model) and count fleet-wide outbound fetches."""
        f0 = stub.fetches
        n_sub = max(n_keys * 4, n_requests // max(1, len(handlers)))
        rows = []
        for j, handler in enumerate(handlers):
            if j > 0 and planes:
                # the LB does not barrier on gossip, but a steady-state
                # fleet has had a publish interval between cold bursts;
                # give the plane one propagation window
                deadline = time.monotonic() + 5.0
                while (
                    time.monotonic() < deadline
                    and planes[j].cache_merged < n_keys
                ):
                    time.sleep(0.01)
            rows.append(
                replay(handler, [ext_request(i) for i in range(n_sub)], 64)
            )
        fetches = stub.fetches - f0
        r = {
            "phase": name,
            "replicas": len(handlers),
            "keys": n_keys,
            "fetches": fetches,
            "fetches_per_key": round(fetches / n_keys, 3),
            "p50_ms": max(row["p50_ms"] for row in rows),
            "p99_ms": max(row["p99_ms"] for row in rows),
        }
        phases.append(r)
        print(f"fleet phase: {r}", file=err)
        return r

    # n1: one replica — the cold-fetch floor (1 fetch per key)
    sys1, b1, h1 = build_replica()
    try:
        _warm_route(b1.client)
        r1 = run_phase("n1", [h1])
    finally:
        b1.stop()

    # n2_isolated: two replicas, no fleet — every replica re-pays
    stub.fetches = 0
    sys_a, b_a, h_a = build_replica()
    sys_b, b_b, h_b = build_replica()
    try:
        _warm_route(b_a.client)
        r2i = run_phase("n2_isolated", [h_a, h_b])
    finally:
        b_a.stop()
        b_b.stop()

    # n2_fleet: two replicas gossiping through one cluster
    stub.fetches = 0
    cluster = FakeCluster()
    p_a = FleetPlane(cluster, "bench-a", publish_interval_s=0.02)
    p_b = FleetPlane(cluster, "bench-b", publish_interval_s=0.02)
    sys_fa, b_fa, h_fa = build_replica(p_a)
    sys_fb, b_fb, h_fb = build_replica(p_b)
    p_a.start()
    p_b.start()
    try:
        _warm_route(b_fa.client)
        r2f = run_phase(
            "n2_fleet", [h_fa, h_fb], planes=[p_a, p_b]
        )
    finally:
        p_a.stop()
        p_b.stop()
        b_fa.stop()
        b_fb.stop()
        stub.stop()

    return {
        "keys": n_keys,
        "phases": phases,
        "fetches_per_key_n1": r1["fetches_per_key"],
        "fetches_per_key_n2_isolated": r2i["fetches_per_key"],
        "fetches_per_key_n2_fleet": r2f["fetches_per_key"],
        # the headline: how much extra cold-fetch cost the second
        # replica adds WITH the fleet plane (1.0 = none)
        "cold_fetch_amplification": round(
            r2f["fetches_per_key"] / max(r1["fetches_per_key"], 1e-9), 3
        ),
        "cache_merged": p_b.cache_merged + p_a.cache_merged,
    }


_ATTR_LABELS_REGO = """package attrlabels

violation[{"msg": msg}] {
    required := {key | key := input.parameters.labels[_]}
    provided := {key | input.review.object.metadata.labels[key]}
    missing := required - provided
    count(missing) > 0
    msg := sprintf("missing: %v", [missing])
}
"""

_ATTR_REPOS_REGO = """package attrrepos

violation[{"msg": msg}] {
    image := input.review.object.spec.containers[_].image
    not startswith(image, input.parameters.repo)
    msg := sprintf("image outside allowed repo: %v", [image])
}
"""


def build_attribution_client(driver, n_constraints, n_dead=0):
    """Self-contained policy load for the --attribution lane (no
    reference-library dependency): three templates of DIFFERENT static
    cost — a one-clause privileged check, a set-difference label check,
    and a per-container repo prefix check — cycled across n
    constraints, so the cost table has real weight variation to rank."""
    from gatekeeper_tpu.constraint import Backend, K8sValidationTarget

    client = Backend(driver).new_client(K8sValidationTarget())
    mix = (
        ("AttrPrivileged",
         _CHAOS_REGO.replace("chaosbench", "attrprivileged"), None),
        ("AttrLabels", _ATTR_LABELS_REGO, {"labels": ["app", "owner"]}),
        ("AttrRepos", _ATTR_REPOS_REGO, {"repo": "nginx"}),
    )
    for kind, rego, _params in mix:
        client.add_template({
            "apiVersion": "templates.gatekeeper.sh/v1beta1",
            "kind": "ConstraintTemplate",
            "metadata": {"name": kind.lower()},
            "spec": {
                "crd": {"spec": {"names": {"kind": kind}}},
                "targets": [{"target": TARGET, "rego": rego}],
            },
        })
    for i in range(n_constraints):
        kind, _rego, params = mix[i % len(mix)]
        # namespace affinity aligned with make_request's ns{i % 11}
        # (same scoping as build_webhook_client): gives the locality
        # planner real structure to co-locate, so the lane measures
        # pruned dispatch with falling dispatch_efficiency instead of
        # an unprunable all-match corpus
        spec = {"match": {
            "kinds": [{"apiGroups": [""], "kinds": ["Pod"]}],
            "namespaces": [f"ns{i % 11}"],
        }}
        if params is not None:
            spec["parameters"] = params
        client.add_constraint({
            "apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": kind,
            "metadata": {"name": f"a{i:04d}"},
            "spec": spec,
        })
    # provably-dead rows for the static-pruning lane: namespaces fully
    # excluded (corpus dead-match proof, GK-C006) with no
    # namespaceSelector, so the corpus pass marks them prunable and
    # the planner drops the rows before partitioning —
    # rows_excluded_static in the rung must equal n_dead
    for i in range(n_dead):
        kind, _rego, params = mix[i % len(mix)]
        spec = {"match": {
            "kinds": [{"apiGroups": [""], "kinds": ["Pod"]}],
            "scope": "Namespaced",
            "namespaces": ["ns-dead"],
            "excludedNamespaces": ["ns-dead"],
        }}
        if params is not None:
            spec["parameters"] = params
        client.add_constraint({
            "apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": kind,
            "metadata": {"name": f"dead{i:02d}"},
            "spec": spec,
        })
    return client


def _device_seconds_total(metrics):
    """Sum of driver_phase_seconds{phase=device_dispatch} across label
    sets — the measured device-execute total the attribution sums
    check compares against."""
    total = 0.0
    for key, d in metrics.snapshot()["distributions"].items():
        if key.startswith("driver_phase_seconds") and (
            'phase="device_dispatch"' in key
        ):
            total += float(d["sum"])
    return total


def _dispatch_rows(metrics):
    """(dispatched, total) constraint-row sums across partitions from
    the decision plane's pruning-efficiency counters —
    dispatch_efficiency = dispatched/total is ROADMAP item 1's
    headline instrument (falling with constraint count = pruning is
    working)."""
    dispatched = total = 0.0
    for key, v in metrics.snapshot()["counters"].items():
        if key.startswith("dispatch_rows_dispatched_total"):
            dispatched += float(v)
        elif key.startswith("dispatch_rows_total"):
            total += float(v)
    return dispatched, total


def run_attribution_bench(rungs=(10, 50, 200), n_requests=1200, k=4,
                          profile=False, err=sys.stderr):
    """The `--attribution` lane (docs/observability.md §Cost
    attribution): run the constraint ladder through the partitioned
    micro-batching handler with the CostAttributor wired, and report
    per rung (a) the top-10 costliest constraints — item 1's pruning
    target list — and (b) the sums check: attributed per-constraint
    device seconds vs the measured device-execute total (must agree
    within 10%; the model changes WHO is charged, never HOW MUCH).
    `--profile` additionally captures a JAX/XPlane device profile
    DURING the largest rung's measured replay."""
    from gatekeeper_tpu.analysis.corpus import CorpusPlane
    from gatekeeper_tpu.constraint import TpuDriver
    from gatekeeper_tpu.control.runner import capture_jax_profile
    from gatekeeper_tpu.metrics import MetricsRegistry
    from gatekeeper_tpu.obs import CostAttributor, DecisionLog, Tracer
    from gatekeeper_tpu.parallel.partition import PartitionDispatcher
    from gatekeeper_tpu.webhook.server import (
        BatchedValidationHandler,
        MicroBatcher,
    )

    out = []
    prof = None
    overhead = None
    for n_con in rungs:
        metrics = MetricsRegistry()
        driver = TpuDriver()
        driver.set_metrics(metrics)
        attributor = CostAttributor(metrics=metrics)
        driver.set_attributor(attributor)
        client = build_attribution_client(driver, n_con, n_dead=3)
        # corpus plane: the verdict-safe static-pruning input — the
        # seeded dead rows are proved dead once, synchronously, before
        # the measured replays (production recomputes on churn; the
        # bench corpus is static after load)
        corpus_plane = CorpusPlane(client, metrics=metrics,
                                   debounce_s=0.0)
        corpus_report = corpus_plane.refresh()
        # tracing is always-on in production and the decision plane
        # joins its dispatch facts by trace id — both ride every
        # measured rung (the ≤5% p50 overhead budget is measured below
        # as an off/on phase pair with the tracer on throughout)
        tracer = Tracer(max_traces=2048)
        decisions = DecisionLog(metrics=metrics, max_per_s=0)
        # partition count scales with the corpus (floor k): bigger
        # corpora split finer, so the locality planner can isolate each
        # namespace group and mask-gated pruning drives
        # dispatch_efficiency DOWN as constraint count grows — the
        # inverse of the pre-pruning flat-1.0 ladder
        k_rung = min(n_con, max(k, n_con // 8), 64)
        disp = PartitionDispatcher(
            client, TARGET, k=k_rung, metrics=metrics,
            tracer=tracer, attributor=attributor, corpus=corpus_plane,
        )
        batcher = MicroBatcher(
            client, TARGET, window_ms=2.0, metrics=metrics,
            partitioner=disp, decisions=decisions, tracer=tracer,
        )
        handler = BatchedValidationHandler(
            batcher, request_timeout=60, decision_log=decisions,
            tracer=tracer,
        )
        batcher.start()
        try:
            _warm_route(client)
            replay(handler, [make_request(i) for i in range(256)], 64)
            replay(handler, [make_request(i) for i in range(512)], 128)
            if n_con == max(rungs):
                # decision-plane overhead at the largest rung: the same
                # replay with the plane detached, then reattached — the
                # acceptance budget is ≤5% on p50
                batcher.decisions = None
                handler.decision_log = None
                n_off = max(400, n_requests // 3)
                r_off = replay(
                    handler,
                    [make_request(i) for i in range(n_off)], 128,
                )
                batcher.decisions = decisions
                handler.decision_log = decisions
                overhead = {"constraints": n_con, "off": r_off}
            attributor.reset()
            dev0 = _device_seconds_total(metrics)
            rows0 = _dispatch_rows(metrics)
            capture = []
            if profile and n_con == max(rungs):
                # one XPlane capture riding the measured replay: the
                # profile shows the fused dispatch under REAL load, not
                # an idle device (the --enable-pprof endpoint's bench
                # counterpart; single rung, single capture)
                th = ThreadPoolExecutor(max_workers=1)
                fut = th.submit(capture_jax_profile, 2.0)
                capture.append((th, fut))
            n_sub = max(400, n_requests // 3)
            r = replay(
                handler, [make_request(i) for i in range(n_sub)], 128
            )
            for th, fut in capture:
                prof = fut.result(timeout=90)
                th.shutdown(wait=False)
            measured = _device_seconds_total(metrics) - dev0
            attributed = attributor.snapshot()["total_device_seconds"]
            rows1 = _dispatch_rows(metrics)
            rows_dispatched = rows1[0] - rows0[0]
            rows_total = rows1[1] - rows0[1]
            top = attributor.top(10)
            sums_ok = bool(
                measured > 0
                and abs(attributed - measured) <= 0.10 * measured
            )
            touched = disp.touched_stats()
            plan_now = disp.plan()
            rows_excluded = len(
                getattr(plan_now, "excluded_static", ()) or ()
            )
            rung = {
                "constraints": n_con,
                "partitions": k_rung,
                # pruning telemetry: of the plan's partitions, how many
                # a batch actually dispatched to (p50/max over the
                # rung's replays)
                "partitions_touched_p50": touched.get("p50"),
                "partitions_touched_max": touched.get("max"),
                "replay": {
                    key: r[key]
                    for key in ("requests", "throughput_rps",
                                "p50_ms", "p99_ms")
                },
                "measured_device_seconds": round(measured, 6),
                "attributed_device_seconds": round(attributed, 6),
                "attribution_ratio": (
                    round(attributed / measured, 4) if measured else None
                ),
                "sums_ok": sums_ok,
                # the pruning-efficiency headline (ROADMAP item 1):
                # constraint-rows dispatched / total over the measured
                # replay — falling with constraint count is what batch-
                # aware pruned dispatch will be judged by
                "rows_dispatched": int(rows_dispatched),
                "rows_total": int(rows_total),
                "dispatch_efficiency": (
                    round(rows_dispatched / rows_total, 4)
                    if rows_total else None
                ),
                # verdict-safe static pruning (corpus pass): provably-
                # dead rows the planner excluded before partitioning,
                # and the corpus diagnostic count backing the proof
                "rows_excluded_static": rows_excluded,
                "corpus_diagnostics": sum(
                    (corpus_report.counts() or {}).values()
                ),
                "decisions": decisions.snapshot(),
                "top_costs": top,
            }
            if overhead is not None and overhead.get(
                "constraints"
            ) == n_con and "on" not in overhead:
                overhead["on"] = {
                    key: r[key] for key in ("p50_ms", "p99_ms")
                }
                p_off = overhead["off"]["p50_ms"]
                overhead["p50_overhead_frac"] = (
                    round(r["p50_ms"] / p_off - 1.0, 4) if p_off else None
                )
                rung["decision_overhead"] = overhead
            out.append(rung)
            top3 = [f"{t['kind']}/{t['name']}" for t in top[:3]]
            print(
                f"attribution rung c={n_con}: measured="
                f"{measured:.4f}s attributed={attributed:.4f}s "
                f"sums_ok={sums_ok} "
                f"dispatch_efficiency={rung['dispatch_efficiency']} "
                f"rows_excluded_static={rows_excluded} "
                f"top={top3}",
                file=err,
            )
        finally:
            batcher.stop()
            disp.close()
    return {"rungs": out, "profile": prof, "decision_overhead": overhead}


# the reference harness's constraint-count ladder
# (pkg/webhook/policy_benchmark_test.go:265-276)
LADDER = (5, 10, 50, 100, 200, 1000, 2000)


def run_constraint_ladder(err=sys.stderr, rungs=LADDER, budget_s=None,
                          profile=False):
    """Latency-vs-policy-count curve (VERDICT r4 #3): p50/p99/rps per
    constraint-count rung for both serving paths — the serial
    Python-interpreter handler (the reference's architecture, measured
    serially like the Go b.N loop) and the fused micro-batching handler
    (c=128). 100%-violating
    requests, the reference harness's stress shape.

    budget_s bounds total wall time: rungs run SMALL, MID, LARGE first
    (a truncated run still spans the curve, and the first two samples
    feed an affine fixed+marginal cost fit before the big rung), then
    alternating fill. A rung is deferred when the fit's 1.5x-padded
    estimate exceeds the remaining budget, and deferred rungs are
    re-evaluated on later passes as samples sharpen the fit — an
    overrun must degrade the curve, not erase the whole artifact (the
    r4 lesson applied to time)."""
    from gatekeeper_tpu.constraint import RegoDriver, TpuDriver
    from gatekeeper_tpu.webhook import ValidationHandler
    from gatekeeper_tpu.webhook.server import (
        BatchedValidationHandler,
        MicroBatcher,
    )

    # small, mid, large first (curve coverage under truncation AND two
    # spread samples for the affine cost model before the big rung),
    # then alternating fill
    remaining = sorted(rungs)
    order: list = []
    if len(remaining) >= 3:
        order.append(remaining.pop(0))
        # true midpoint EXCLUDING the max: with 3 rungs this must pick
        # the middle one, not the largest, or the affine fit gets no
        # second spread sample before the big rung
        order.append(remaining.pop((len(remaining) - 1) // 2))
        order.append(remaining.pop(-1))
    while remaining:
        order.append(remaining.pop(0))
        if remaining:
            order.append(remaining.pop(-1))
    t_start = time.perf_counter()
    samples: list = []  # (n_constraints, wall_seconds)

    def estimate(n_next: float) -> float:
        """Predicted rung wall: affine in constraint count once two
        spread samples exist. A pure count-ratio scale from the
        cheapest rung books its FIXED overhead (client build, warmup,
        replay floor) as marginal cost and over-skips the big rungs by
        ~10x; the affine fit separates the two."""
        lo = min(samples)
        hi = max(samples)
        if hi[0] > lo[0]:
            marginal = max(0.0, (hi[1] - lo[1]) / (hi[0] - lo[0]))
            fixed = max(0.0, lo[1] - marginal * lo[0])
            est = fixed + marginal * n_next
        else:
            # one sample: its wall is mostly fixed overhead, so a raw
            # count-ratio scale over-skips the calibration (mid) rung;
            # cap the ratio effect — worst case we overspend one
            # bounded rung and every later estimate has real data
            est = hi[1] * min(n_next / hi[0], 4.0)
        # never cheaper than a smaller rung already measured
        return max(est, hi[1] if n_next >= hi[0] else lo[1]) * 1.5

    out = []
    queue = list(order)
    progress = True
    while queue and progress:
        progress = False
        deferred = []
        for n_con in queue:
            if budget_s is not None:
                elapsed = time.perf_counter() - t_start
                fits = (
                    budget_s >= 30
                    if not samples
                    else elapsed + estimate(n_con) <= budget_s
                )
                if not fits:
                    # re-evaluated next pass: early estimates (one
                    # sample) are crude; later samples sharpen the
                    # affine fit and may admit this rung after all
                    deferred.append(n_con)
                    continue
            progress = True
            t_rung = time.perf_counter()
            rung = {"constraints": n_con}

            # interpreter path, serial (subsample scaled: per-request cost
            # grows with the rung)
            cpu_n = max(25, min(200, 20_000 // n_con))
            cpu_handler = ValidationHandler(
                build_webhook_client(RegoDriver(), n_con), TARGET
            )
            reqs = [make_request(i) for i in range(cpu_n)]
            cpu_handler.handle(reqs[0])  # warm
            t0 = time.perf_counter()
            lat = np.zeros(cpu_n)
            for i, r in enumerate(reqs):
                t1 = time.perf_counter()
                cpu_handler.handle(r)
                lat[i] = time.perf_counter() - t1
            wall = time.perf_counter() - t0
            rung["interp"] = {
                "requests": cpu_n,
                "throughput_rps": round(cpu_n / wall, 1),
                "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 2),
                "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 2),
            }

            # fused micro-batching path, c=128 — partitioned with the
            # cost/locality planner so mask-gated pruning is ON (the
            # default fast path): each batch dispatches only the
            # partitions its namespaces touch
            from gatekeeper_tpu.parallel.partition import (
                PartitionDispatcher,
            )

            client = build_webhook_client(TpuDriver(), n_con)
            ladder_disp = PartitionDispatcher(
                client, TARGET, k=min(n_con, max(4, n_con // 8), 64),
            )
            batcher = MicroBatcher(
                client, TARGET, window_ms=2.0, partitioner=ladder_disp,
            )
            handler = BatchedValidationHandler(batcher, request_timeout=60)
            batcher.start()
            try:
                _warm_route(client)
                replay(handler, [make_request(i) for i in range(512)], 128)
                capture = None
                if profile and not any("profile" in r for r in out):
                    # --profile: one JAX/XPlane capture riding THIS
                    # rung's measured fused replay — a device profile
                    # under real load, not an idle trace (the
                    # /debug/profile endpoint's ladder counterpart)
                    from gatekeeper_tpu.control.runner import (
                        capture_jax_profile,
                    )

                    _pex = ThreadPoolExecutor(max_workers=1)
                    capture = (_pex, _pex.submit(capture_jax_profile, 2.0))
                n_sub = 1500
                r = replay(handler, [make_request(i) for i in range(n_sub)], 128)
                rung["fused"] = {
                    k: r[k]
                    for k in ("requests", "throughput_rps", "p50_ms", "p99_ms")
                }
                rung["fused"]["partitions_touched"] = (
                    ladder_disp.touched_stats()
                )
                # IR feature-liveness headline: dead token slots the
                # encoder dropped before padding across this rung's
                # batches (0 = masking off or nothing provable)
                _drv = getattr(client, "_driver", None)
                rung["fused"]["columns_skipped_static"] = int(
                    getattr(_drv, "columns_skipped_static", 0) or 0
                )
                if capture is not None:
                    _pex, fut = capture
                    rung["profile"] = fut.result(timeout=90)
                    _pex.shutdown(wait=False)
                    print(
                        f"ladder profile captured: {rung['profile']}",
                        file=err,
                    )
            finally:
                batcher.stop()
                ladder_disp.close()

            wall = time.perf_counter() - t_rung
            samples.append((n_con, wall))
            rung["wall_seconds"] = round(wall, 1)
            print(f"constraint ladder rung: {rung}", file=err)
            out.append(rung)
        queue = deferred
    truncated = queue
    if truncated:
        print(
            f"constraint ladder truncated by time budget; skipped rungs "
            f"{sorted(truncated)}",
            file=err,
        )
    # rows stay homogeneous (BENCH_r* consumers index r["constraints"]);
    # truncation is reported out-of-band
    return sorted(out, key=lambda r: r["constraints"]), sorted(truncated)


def _summarize(mode, res):
    """One short driver-parseable line with the headline numbers: the
    full JSON line has outgrown capture buffers before (BENCH_r05's
    parsed: null), so the compact SUMMARY survives truncation. The
    schema is the shared per-mode contract in gatekeeper_tpu/summary.py
    (tests/test_summary_contract.py round-trips every mode through the
    strict reader)."""
    from gatekeeper_tpu.summary import REQUIRED_FIELDS, format_summary

    head = {}
    try:
        if mode == "webhook":
            row = next(
                (r for r in res.get("tpu_batched", [])
                 if r.get("violating") and r.get("concurrency") == 8),
                None,
            ) or (res.get("tpu_batched") or [{}])[0]
            head.update(
                p50_ms=row.get("p50_ms"), p99_ms=row.get("p99_ms"),
                throughput_rps=row.get("throughput_rps"),
            )
        elif mode == "ladder":
            rungs = res.get("rungs") or []
            head.update(
                rungs=len(rungs), skipped=res.get("skipped"),
                last=rungs[-1] if rungs else None,
            )
            prof = next(
                (r["profile"] for r in rungs if r.get("profile")), None
            )
            if prof:
                head["profile_trace_dir"] = prof.get("trace_dir")
        elif mode == "attribution":
            rungs = res.get("rungs") or []
            head["rungs"] = len(rungs)
            head["sums_ok"] = all(r.get("sums_ok") for r in rungs)
            # per-rung pruning efficiency (ROADMAP item 1's gauge):
            # dispatched/total constraint rows at every rung
            head["dispatch_efficiency"] = {
                str(r["constraints"]): r.get("dispatch_efficiency")
                for r in rungs
            }
            # partition touch counts per rung (the pruning width gauge
            # next to the efficiency depth gauge)
            head["partitions_touched_p50"] = {
                str(r["constraints"]): r.get("partitions_touched_p50")
                for r in rungs
            }
            head["partitions_touched_max"] = {
                str(r["constraints"]): r.get("partitions_touched_max")
                for r in rungs
            }
            # verdict-safe static pruning per rung: dead rows the
            # planner dropped (down = regression: the corpus pass
            # stopped proving the seeded dead rows) and the corpus
            # diagnostic count (up = new corpus findings)
            head["rows_excluded_static"] = {
                str(r["constraints"]): r.get("rows_excluded_static")
                for r in rungs
            }
            head["corpus_diagnostics"] = {
                str(r["constraints"]): r.get("corpus_diagnostics")
                for r in rungs
            }
            if rungs:
                last = max(rungs, key=lambda r: r["constraints"])
                head["constraints"] = last["constraints"]
                head["attribution_ratio"] = last.get("attribution_ratio")
                # the acceptance headline: the top-10 costliest
                # constraints at the largest rung — item 1's target list
                head["top10"] = [
                    f"{t['kind']}/{t['name']}"
                    for t in (last.get("top_costs") or [])[:10]
                ]
            oh = res.get("decision_overhead")
            if oh:
                head["decision_overhead_p50_frac"] = oh.get(
                    "p50_overhead_frac"
                )
            prof = res.get("profile")
            if prof:
                head["profile_trace_dir"] = prof.get("trace_dir")
        elif mode == "churn":
            waves = res.get("waves") or []
            head["waves"] = len(waves)
            if waves:
                head["wave"] = waves[-1].get("wave")
            head["ingest_to_serve_ms"] = res.get("ingest_to_serve_ms")
            head["degraded_dispatches"] = res.get("degraded_dispatches")
            head["http_5xx"] = res.get("http_5xx")
            head["compiles"] = res.get("compiles")
            head["swaps"] = res.get("swaps")
        elif mode == "slo":
            head["phases"] = len(res.get("phases") or [])
            for k in ("slo_attainment", "saturation", "burn_rate_fast",
                      "headroom_rps", "breaches", "burning",
                      "error_budget_remaining"):
                if k in res:
                    head[k] = res[k]
        elif mode == "integrity":
            head["phases"] = len(res.get("phases") or [])
            for k in ("divergence_rate", "canary_overhead_frac",
                      "detection_latency_s", "selftest_healed",
                      "shadow_sampled"):
                if k in res:
                    head[k] = res[k]
        elif mode == "sched":
            head["phases"] = len(res.get("phases") or [])
            for k in ("quiet_p50_ms", "quiet_p99_ms", "noisy_p50_ms",
                      "noisy_p99_ms", "quiet_attainment",
                      "noisy_attainment", "tenant_attainment_min",
                      "predicted_miss_shed", "blind_shed"):
                if k in res:
                    head[k] = res[k]
        elif mode == "mutate":
            replays = res.get("replays") or []
            if replays:
                last = replays[-1]
                for k in ("p50_ms", "p99_ms", "throughput_rps",
                          "batch_occupancy"):
                    if k in last:
                        head[k] = last[k]
        elif isinstance(res, dict):
            phases = res.get("phases")
            if isinstance(phases, list) and phases:
                head["phases"] = len(phases)
                last = phases[-1]
                for k in ("phase", "p50_ms", "p99_ms", "throughput_rps",
                          "shed_rate", "cache_hit_rate",
                          "fetches_per_batch"):
                    if k in last:
                        head[k] = last[k]
            for k in ("p50_ms", "p99_ms", "throughput_rps", "shed_rate",
                      "hit_rate", "fetches_per_batch",
                      "fetches_per_key_n1", "fetches_per_key_n2_isolated",
                      "fetches_per_key_n2_fleet",
                      "cold_fetch_amplification",
                      "partitions", "parity_ok",
                      "healthy_subset_degraded",
                      "degraded_coverage_fraction", "recovery_s",
                      "home_restored"):
                if k in res:
                    head[k] = res[k]
    except Exception as e:  # the summary must never kill the artifact
        head["error"] = str(e)
    # the contract guarantee: every required headline key is PRESENT
    # (null when a truncated/failed run could not measure it) — the
    # strict reader keys on presence, not truthiness
    for f in REQUIRED_FIELDS.get(mode, ()):
        head.setdefault(f, None)
    return format_summary(mode, head)


def run_soak_bench(argv, err=sys.stderr):
    """The `--soak` lane (docs/operations.md §Soak runbook): minutes of
    open-loop Poisson load with a declarative churn/fault/kill timeline,
    reported as SLO attainment, shed rate, breaker transitions, the
    device-time split, a capacity model, and leak evidence.

        python bench_webhook.py --soak                    # full default
        python bench_webhook.py --soak --smoke            # ~10 s smoke
        python bench_webhook.py --soak --scenario f.json  # custom
        python bench_webhook.py --soak 120 80             # duration rps
    """
    from gatekeeper_tpu.soak import (
        default_scenario,
        load_scenario,
        run_soak,
        smoke_scenario,
    )

    if "--scenario" in argv:
        path = argv[argv.index("--scenario") + 1]
        scn = load_scenario(path)
    elif "--smoke" in argv:
        scn = smoke_scenario()
    else:
        scn = default_scenario()
        pos = [a for a in argv[1:] if not a.startswith("--")]
        if pos:
            scn.duration_s = float(pos[0])
        if len(pos) > 1:
            scn.rps = float(pos[1])
        scn.validate()
    print(f"soak scenario: {scn.name} duration={scn.duration_s}s "
          f"rps={scn.rps} replicas={scn.replicas}", file=err)
    return run_soak(scn, err=err)


if __name__ == "__main__":
    import json

    if "--soak" in sys.argv:
        from gatekeeper_tpu.soak import summarize_soak

        res = run_soak_bench(sys.argv)
        print(json.dumps(res))
        print(summarize_soak(res))
    elif "--ladder" in sys.argv:
        rows, skipped = run_constraint_ladder(
            profile="--profile" in sys.argv
        )
        res = {"rungs": rows, "skipped": skipped}
        print(json.dumps(res))
        print(_summarize("ladder", res))
    elif "--attribution" in sys.argv:
        pos = [a for a in sys.argv[1:] if not a.startswith("--")]
        n_req = int(pos[0]) if pos else 1_200
        rungs = (
            tuple(int(x) for x in pos[1].split(","))
            if len(pos) > 1
            else (10, 50, 200)
        )
        res = run_attribution_bench(
            rungs=rungs, n_requests=n_req,
            profile="--profile" in sys.argv,
        )
        print(json.dumps(res))
        print(_summarize("attribution", res))
    elif "--chaos" in sys.argv:
        pos = [a for a in sys.argv[1:] if not a.startswith("--")]
        n_req = int(pos[0]) if pos else 3_000
        n_con = int(pos[1]) if len(pos) > 1 else 20
        res = run_chaos_bench(n_req, n_con)
        print(json.dumps(res))
        print(_summarize("chaos", res))
    elif "--partitions" in sys.argv:
        pos = [a for a in sys.argv[1:] if not a.startswith("--")]
        n_req = int(pos[0]) if pos else 2_000
        n_con = int(pos[1]) if len(pos) > 1 else 40
        n_parts = int(pos[2]) if len(pos) > 2 else 4
        res = run_partitions_bench(n_req, n_con, n_parts)
        print(json.dumps(res))
        print(_summarize("partitions", res))
    elif "--churn" in sys.argv:
        pos = [a for a in sys.argv[1:] if not a.startswith("--")]
        n_req = int(pos[0]) if pos else 600
        sizes = (
            tuple(int(x) for x in pos[1].split(","))
            if len(pos) > 1
            else (10, 50, 500)
        )
        n_parts = int(pos[2]) if len(pos) > 2 else 4
        res = run_churn_bench(n_req, sizes, n_parts)
        print(json.dumps(res))
        print(_summarize("churn", res))
    elif "--external" in sys.argv:
        pos = [a for a in sys.argv[1:] if not a.startswith("--")]
        n_req = int(pos[0]) if pos else 3_000
        n_keys = int(pos[1]) if len(pos) > 1 else 7
        res = run_external_bench(n_req, n_keys)
        print(json.dumps(res))
        print(_summarize("external", res))
    elif "--fleet" in sys.argv:
        pos = [a for a in sys.argv[1:] if not a.startswith("--")]
        n_req = int(pos[0]) if pos else 1_200
        n_keys = int(pos[1]) if len(pos) > 1 else 24
        res = run_fleet_bench(n_req, n_keys)
        print(json.dumps(res))
        print(_summarize("fleet", res))
    elif "--mutate" in sys.argv:
        pos = [a for a in sys.argv[1:] if not a.startswith("--")]
        n_req = int(pos[0]) if pos else 10_000
        n_mut = int(pos[1]) if len(pos) > 1 else 30
        res = run_mutate_bench(n_req, n_mut)
        print(json.dumps(res))
        print(_summarize("mutate", res))
    elif "--slo" in sys.argv:
        pos = [a for a in sys.argv[1:] if not a.startswith("--")]
        n_req = int(pos[0]) if pos else 1_800
        n_con = int(pos[1]) if len(pos) > 1 else 20
        res = run_slo_bench(n_req, n_con)
        print(json.dumps(res))
        print(_summarize("slo", res))
    elif "--integrity" in sys.argv:
        pos = [a for a in sys.argv[1:] if not a.startswith("--")]
        n_req = int(pos[0]) if pos else 1_800
        n_con = int(pos[1]) if len(pos) > 1 else 20
        k = int(pos[2]) if len(pos) > 2 else 3
        res = run_integrity_bench(n_req, n_con, k)
        print(json.dumps(res))
        print(_summarize("integrity", res))
    elif "--sched" in sys.argv:
        pos = [a for a in sys.argv[1:] if not a.startswith("--")]
        dur = float(pos[0]) if pos else 6.0
        rps = float(pos[1]) if len(pos) > 1 else 600.0
        res = run_sched_bench(dur, rps)
        print(json.dumps(res))
        print(_summarize("sched", res))
    else:
        n_req = int(sys.argv[1]) if len(sys.argv) > 1 else 10_000
        n_con = int(sys.argv[2]) if len(sys.argv) > 2 else 50
        res = run_webhook_bench(n_req, n_con)
        print(json.dumps(res))
        print(_summarize("webhook", res))
