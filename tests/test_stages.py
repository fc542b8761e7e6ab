"""Stage timing (obs.stage, docs/observability.md §Profiler
annotations): the admission batch cycle's request and batch stages,
each batch counted once under its final route, audit observations
that do not grow with the violations, the process's garbage-collection
hook, and the `gk.*` annotations a profiler capture holds."""

import gc
import glob
import json
import os
import threading
import time
import urllib.request
from concurrent.futures import Future

import pytest

from gatekeeper_tpu.constraint import Backend, K8sValidationTarget, TpuDriver
from gatekeeper_tpu.constraint.types import Response, Responses
from gatekeeper_tpu.faults import FAULTS
from gatekeeper_tpu.metrics import MetricsRegistry
from gatekeeper_tpu.obs import GcPauses, stage

pytestmark = pytest.mark.obs

TARGET = "admission.k8s.gatekeeper.sh"

REQ_LABELS = """package reqlabels

violation[{"msg": msg}] {
    required := {key | key := input.parameters.labels[_]}
    provided := {key | input.review.object.metadata.labels[key]}
    missing := required - provided
    count(missing) > 0
    msg := sprintf("missing: %v", [missing])
}
"""

BATCH_STAGES = ("worker", "augment", "review", "bookkeeping", "resolve")


def pod_request(i, labels=None):
    name = f"p{i}"
    return {
        "uid": f"u{i}",
        "kind": {"group": "", "version": "v1", "kind": "Pod"},
        "operation": "CREATE",
        "name": name,
        "namespace": "default",
        "userInfo": {"username": "alice"},
        "object": {
            "apiVersion": "v1",
            "kind": "Pod",
            "metadata": {"name": name, "namespace": "default",
                         "labels": labels or {}},
            "spec": {"containers": [{"name": "c", "image": "nginx"}]},
        },
    }


def make_client(use_jax=False):
    client = Backend(TpuDriver(use_jax=use_jax)).new_client(
        K8sValidationTarget()
    )
    client.add_template({
        "apiVersion": "templates.gatekeeper.sh/v1beta1",
        "kind": "ConstraintTemplate",
        "metadata": {"name": "reqlabels"},
        "spec": {
            "crd": {"spec": {"names": {"kind": "ReqLabels"}}},
            "targets": [{"target": TARGET, "rego": REQ_LABELS}],
        },
    })
    client.add_constraint({
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "ReqLabels",
        "metadata": {"name": "need-owner"},
        "spec": {
            "match": {"kinds": [{"apiGroups": [""], "kinds": ["Pod"]}]},
            "parameters": {"labels": ["owner"]},
        },
    })
    return client


class StubClient:
    """Answers every review with no violation; records batch sizes."""

    def __init__(self):
        self.batches = []

    def review_many(self, reviews):
        self.batches.append(len(reviews))
        return [
            Responses(by_target={TARGET: Response(target=TARGET)})
            for _ in reviews
        ]


def dist_counts(reg, family):
    """{series key: observation count} of one distribution family."""
    return {
        k: d["count"]
        for k, d in reg.snapshot()["distributions"].items()
        if k.split("{")[0] == family
    }


def stage_count(reg, family, **labels):
    want = [f'{k}="{v}"' for k, v in labels.items()]
    return sum(
        n for k, n in dist_counts(reg, family).items()
        if all(w in k for w in want)
    )


def routes(reg):
    """{route: count} of webhook_batches_total."""
    out = {}
    for k, v in reg.snapshot()["counters"].items():
        if k.startswith("webhook_batches_total{"):
            out[k.split('route="')[1].split('"')[0]] = v
    return out


def test_batch_through_microbatcher_observes_each_stage_once():
    """One batch of four through the worker: every batch stage is
    observed once, queue_wait and wake once per request, and the batch
    counts once under its route."""
    from gatekeeper_tpu.webhook.server import MicroBatcher

    reg = MetricsRegistry()
    stub = StubClient()
    batcher = MicroBatcher(stub, TARGET, window_ms=200.0, metrics=reg)
    batcher.start()
    try:
        futs = [batcher.submit(pod_request(i)) for i in range(4)]
        for fut in futs:
            assert batcher.result(fut, 10) == []
    finally:
        batcher.stop()
    assert stub.batches == [4]
    for name in BATCH_STAGES:
        assert stage_count(
            reg, "webhook_batch_stage_seconds", stage=name,
            plane="validation",
        ) == 1, name
    for name in ("queue_wait", "wake"):
        assert stage_count(
            reg, "webhook_request_stage_seconds", stage=name
        ) == 4, name
    # the stub has no driver: an n=4 batch would take the interpreter
    assert routes(reg) == {"interp": 1}
    assert stage_count(reg, "webhook_batch_size", route="interp") == 1


def test_concurrent_submitters_count_every_request_and_batch_once():
    """Many handler threads against one worker, with thread switches
    forced often: every request observes queue_wait and wake once, and
    the batches counted are exactly the batches evaluated."""
    import sys

    from gatekeeper_tpu.webhook.server import MicroBatcher

    reg = MetricsRegistry()
    stub = StubClient()
    batcher = MicroBatcher(stub, TARGET, window_ms=1.0, metrics=reg)
    batcher.start()
    n_threads, per_thread = 32, 5
    errors = []

    def handler(t):
        try:
            for k in range(per_thread):
                fut = batcher.submit(pod_request(t * per_thread + k))
                assert batcher.result(fut, 10) == []
        except Exception as e:  # reported below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [
            threading.Thread(target=handler, args=(t,))
            for t in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
        batcher.stop()
    assert not errors, errors
    total = n_threads * per_thread
    assert sum(stub.batches) == total
    for name in ("queue_wait", "wake"):
        assert stage_count(
            reg, "webhook_request_stage_seconds", stage=name
        ) == total
    assert sum(routes(reg).values()) == len(stub.batches)
    assert stage_count(reg, "webhook_batch_stage_seconds", stage="worker") \
        == len(stub.batches)


def test_http_front_observed_once_per_request():
    """Each POST observes `front` once (decode + respond), alongside
    queue_wait and wake; responses are unchanged."""
    from gatekeeper_tpu.webhook.server import WebhookServer

    reg = MetricsRegistry()
    server = WebhookServer(StubClient(), TARGET, window_ms=1.0, metrics=reg)
    server.start()
    try:
        for i in range(3):
            body = json.dumps({
                "apiVersion": "admission.k8s.io/v1",
                "kind": "AdmissionReview",
                "request": pod_request(i),
            }).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{server.port}/v1/admit", data=body,
                headers={"Content-Type": "application/json"},
                method="POST",
            )
            with urllib.request.urlopen(req, timeout=30) as resp:
                doc = json.loads(resp.read())
            assert doc["response"]["allowed"] is True
            assert doc["response"]["uid"] == f"u{i}"
        # a body that is not JSON is still answered, with a 500
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/v1/admit", data=b"{not json",
            method="POST",
        )
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(req, timeout=30)
        assert err.value.code == 500
    finally:
        server.stop()
    assert stage_count(reg, "webhook_request_stage_seconds", stage="front") == 4
    for name in ("queue_wait", "wake"):
        assert stage_count(
            reg, "webhook_request_stage_seconds", stage=name
        ) == 3


def dispatch(batcher, requests):
    """One batch straight through MicroBatcher._dispatch; -> results."""
    stamp = (time.time(), time.perf_counter())
    batch = [(r, Future(), None, stamp, None, None) for r in requests]
    batcher._dispatch(batch)
    out = []
    for item in batch:
        try:
            out.append(item[1].result(timeout=30))
        except Exception as e:
            out.append(e)
    return out


def test_cold_and_small_batches_count_once_under_their_route():
    """A batch of 5 takes the interpreter and a device-sized batch on a
    driver not yet compiled is served cold: each counts once, under
    `interp` and `cold` — never also as fused — and both are observed
    in webhook_batch_size as before."""
    from gatekeeper_tpu.constraint import tpudriver
    from gatekeeper_tpu.webhook.server import MicroBatcher

    client = make_client(use_jax=True)
    drv = client._driver
    drv._kick_warm = lambda target, inputs: None  # no background compile
    reg = MetricsRegistry()
    drv.set_metrics(reg)
    batcher = MicroBatcher(client, TARGET, metrics=reg)
    small = dispatch(batcher, [pod_request(i) for i in range(5)])
    cold = dispatch(batcher, [
        pod_request(i) for i in range(tpudriver.MIN_DEVICE_BATCH)
    ])
    assert all(len(r) == 1 for r in small + cold)  # every pod lacks owner
    assert routes(reg) == {"interp": 1, "cold": 1}
    assert drv.cold_batches == 1
    assert stage_count(reg, "webhook_batch_size") == 2
    # once compiled, the same batch takes the device: one more batch,
    # under `fused` alone
    requests = [pod_request(i) for i in range(tpudriver.MIN_DEVICE_BATCH)]
    reviews = [batcher.target_handler.augment_request(r) for r in requests]
    assert client.warm_review_path(reviews)
    assert all(len(r) == 1 for r in dispatch(batcher, requests))
    assert routes(reg) == {"interp": 1, "cold": 1, "fused": 1}
    assert stage_count(reg, "webhook_batch_size") == 3
    # every batch took the serving mutex once, at its outermost lock
    assert stage_count(
        reg, "driver_phase_seconds", phase="lock_wait", path="webhook"
    ) == 3


def test_ring_spans_carry_the_driver_stage_stamps():
    """The ring's flatten_encode / device_dispatch / render spans are
    the driver's own stage stamps for this batch: in order, inside the
    dispatch window, each as long as the driver measured it."""
    from gatekeeper_tpu.obs import Tracer
    from gatekeeper_tpu.webhook.server import MicroBatcher

    client = make_client(use_jax=True)
    requests = [pod_request(i) for i in range(12)]
    tracer = Tracer()
    batcher = MicroBatcher(client, TARGET, tracer=tracer)
    assert client.warm_review_path(
        [batcher.target_handler.augment_request(r) for r in requests]
    )
    root = tracer.start_span("handler")
    stamp = (time.time(), time.perf_counter())
    batch = [(r, Future(), root.context, stamp, None, None) for r in requests]
    batcher._dispatch(batch)
    with root:
        pass
    phases = client._driver.thread_stats()["phase_seconds"]
    (trace,) = [t for t in tracer.recent(50) if t["trace_id"] == root.trace_id]
    spans = {}
    for sp in trace["spans"]:
        spans.setdefault(sp["name"], sp)
    order = ["flatten_encode", "device_dispatch", "render"]
    disp = spans["dispatch"]
    for name in order:
        sp = spans[name]
        assert sp["parent_id"] == disp["span_id"]
        assert disp["start"] <= sp["start"] <= sp["end"] <= disp["end"]
        assert sp["end"] - sp["start"] == pytest.approx(phases[name], abs=1e-5)
    for a, b in zip(order, order[1:]):
        assert spans[a]["end"] == pytest.approx(spans[b]["start"], abs=1e-6)


def test_failed_rungs_count_once_and_keep_batch_size_rule():
    """A fused failure answered on the host rung counts once as
    `fallback`; with the host rung down too, once as `unavailable`.
    Neither is observed in webhook_batch_size (today's rule: only
    batches an evaluation answered whole)."""
    from gatekeeper_tpu.webhook.server import MicroBatcher

    reg = MetricsRegistry()
    batcher = MicroBatcher(make_client(), TARGET, metrics=reg, breaker=False)
    try:
        FAULTS.arm("webhook.batch_dispatch", mode="error")
        out = dispatch(batcher, [pod_request(0), pod_request(1)])
        assert all(len(r) == 1 for r in out)
        FAULTS.arm("webhook.host_review", mode="error")
        out = dispatch(batcher, [pod_request(2)])
        assert isinstance(out[0], Exception)
    finally:
        FAULTS.reset()
    assert routes(reg) == {"fallback": 1, "unavailable": 1}
    assert stage_count(reg, "webhook_batch_size") == 0


def sweep_observations(pods):
    """Observations the registry takes over one audit sweep of `pods`
    Pods, every one violating."""
    from gatekeeper_tpu.audit import AuditManager

    client = make_client()
    for i in range(pods):
        client.add_data({
            "apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": f"p{i}", "namespace": "default"},
            "spec": {"containers": [{"name": "c", "image": "nginx"}]},
        })
    reg = MetricsRegistry()
    client._driver.set_metrics(reg)
    mgr = AuditManager(client, TARGET, metrics=reg)
    before = {k: d["count"] for k, d in reg.snapshot()["distributions"].items()}
    report = mgr.audit()
    assert report.total_violations == pods
    after = reg.snapshot()["distributions"]
    return {k: d["count"] - before.get(k, 0) for k, d in after.items()}


def test_audit_observations_do_not_grow_with_violations():
    """No stage is timed per violation: a sweep with ten times the
    violations takes exactly the same observations."""
    few, many = sweep_observations(4), sweep_observations(40)
    assert few == many
    # the client's assemble stage: the driver's flattening and the
    # handle_violation loop, once each per sweep
    key = 'client_phase_seconds{path="audit",phase="assemble"}'
    assert few[key] == 2
    assert few['driver_phase_seconds{path="audit",phase="lock_wait"}'] == 1


def test_runner_start_and_stop_add_and_remove_the_gc_hook():
    from gatekeeper_tpu.control import FakeCluster, Runner

    runner = Runner(
        FakeCluster(), make_client(), TARGET, operations=("audit",),
        audit_interval=3600.0,
    )
    before = list(gc.callbacks)
    runner.start()
    try:
        hook = runner._gc_pauses
        assert hook._on in gc.callbacks
        gc.collect()
        assert stage_count(
            runner.metrics, "process_gc_pause_seconds", generation="2"
        ) >= 1
    finally:
        runner.stop()
    assert hook._on not in gc.callbacks
    assert gc.callbacks == before


def test_gc_pause_defers_while_the_registry_is_held():
    """A collection that starts while the registry's lock is held (here
    by this very thread) must not wait on it: its pause is kept and
    recorded by the next collection."""
    reg = MetricsRegistry()
    hook = GcPauses(reg)
    hook.install()
    try:
        with reg._lock:
            gc.collect()
        assert stage_count(reg, "process_gc_pause_seconds") == 0
        gc.collect()
        assert stage_count(
            reg, "process_gc_pause_seconds", generation="2"
        ) == 2
    finally:
        hook.remove()


def test_stage_times_observes_and_stamps():
    reg = MetricsRegistry()
    with stage("batch.augment", reg, "webhook_batch_stage_seconds",
               stage="augment", plane="validation") as st:
        time.sleep(0.002)
    assert st.end - st.start == st.seconds >= 0.002
    d = reg.snapshot()["distributions"][
        'webhook_batch_stage_seconds{plane="validation",stage="augment"}'
    ]
    assert d["count"] == 1 and d["sum"] == pytest.approx(st.seconds)
    with stage("driver.render") as st:  # annotation only
        pass
    assert len(reg.snapshot()["distributions"]) == 1


def host_events(log_dir):
    """Names of the host-plane events of the capture under log_dir."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True
    )
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(ev.name for ev in line.events)
    return names


def test_profiler_capture_holds_the_stage_annotations(tmp_path):
    """Under a jax.profiler capture on the CPU, the host plane holds
    the batch, driver and client leaves by their fixed names — none
    with keyword arguments folded in."""
    import jax

    from gatekeeper_tpu.webhook.server import MicroBatcher

    batcher = MicroBatcher(make_client(), TARGET)
    jax.profiler.start_trace(str(tmp_path))
    try:
        done = []
        # the batch worker is another thread, as in serving
        t = threading.Thread(
            target=lambda: done.append(dispatch(batcher, [pod_request(0)]))
        )
        t.start()
        t.join(30)
    finally:
        jax.profiler.stop_trace()
    assert not t.is_alive() and done
    names = host_events(str(tmp_path))
    for want in ("gk.batch.augment", "gk.driver.flatten_encode",
                 "gk.client.assemble"):
        assert want in names, sorted(n for n in names if n.startswith("gk"))
    assert not [n for n in names if n.startswith("gk.") and "#" in n]
