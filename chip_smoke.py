"""Chip smoke: the audit sweep and the admission webhook on one TPU chip.

Drives the serving process a user runs (a `Runner` over a `FakeCluster`
in place of the API server, README "Boot a serving process") at the
north star's size: the 8 in-repo templates, 500 constraints, and the
Config of deploy/policies/constraints.yaml syncing 100,000 Pods plus
their Namespaces, made from --seed with ~1% of the Pods violating.

  1. ingest: the Runner replays the cluster into the TPU driver;
  2. first sweep: the audit loop's from-cache sweep, the single fused
     sweep over the whole corpus, beside the Runner's own warm thread;
  3. warm compile: the fused review path compiles for the admission
     shapes, synchronously, before any request is sent;
  4. steady sweep: the same sweep again, warm;
  5. admission: a few hundred AdmissionReviews POSTed over HTTP to
     /v1/admit in concurrent bursts far above MIN_DEVICE_BATCH.

Verdicts are compared with the plain reference, the in-repo Rego
interpreter (`RegoDriver`), on seeded samples. The run fails when the
platform is not `tpu`, a verdict differs, or any rung of the serving
ladder (cold batch, degraded or fallback dispatch, render error, shed)
was taken. The last line of stdout is the JSON result and is printed
only on success.

`--four-chips` runs only the sharded audit: the same corpus over
`audit_mesh(4, c_shards)` for c_shards in {1, 2, 4}, each compared with
the single-device sweep on one of the same chips.
"""

import argparse
import faulthandler
import http.client
import json
import os
import random
import signal
import sys
import threading
import time
from dataclasses import dataclass

TARGET = "admission.k8s.gatekeeper.sh"
ROOT = os.path.dirname(os.path.abspath(__file__))
TEMPLATE_FILES = (
    "deploy/policies/templates.yaml",
    "deploy/policies/reference-library.yaml",
)
CONFIG_FILE = "deploy/policies/constraints.yaml"
WATCHDOG_S = 1100  # dump every thread's stack and exit 1 past this

POD_MATCH = {"kinds": [{"apiGroups": [""], "kinds": ["Pod"]}]}
# per template kind: (parameter variants cycled over its constraints,
# match, enforcementAction) — the actions and the kube-system exclusion
# follow deploy/policies/constraints.yaml
POLICY = {
    "GTRequiredAnnotations": (
        [
            {"annotations": ["team.example.com/owner"]},
            {"annotations": [
                "team.example.com/owner", "team.example.com/cost-center",
            ]},
        ],
        {**POD_MATCH, "excludedNamespaces": ["kube-system"]},
        "deny",
    ),
    "GTDeniedImageRegistries": (
        [
            {"registries": ["docker.io/", "index.docker.io/"]},
            {"registries": ["docker.io/", "public.ecr.aws/"]},
        ],
        POD_MATCH,
        "deny",
    ),
    "GTNoLatestTag": ([None], POD_MATCH, "dryrun"),
    "GTMemoryLimitCeiling": (
        [{"maxMemory": "8Gi"}, {"maxMemory": "16Gi"}], POD_MATCH, "deny",
    ),
    "K8sRequiredLabels": (
        [
            {"labels": [{"key": "app"}, {"key": "owner"}]},
            {"labels": [{"key": "env", "allowedRegex": "^(prod|dev)$"}]},
        ],
        POD_MATCH,
        "deny",
    ),
    "K8sAllowedRepos": (
        [
            {"repos": ["registry.example.com/"]},
            {"repos": ["registry.example.com/", "gcr.io/prod/"]},
        ],
        POD_MATCH,
        "deny",
    ),
    "K8sBlockNodePort": (
        [None], {"kinds": [{"apiGroups": [""], "kinds": ["Service"]}]},
        "deny",
    ),
    "K8sPSPPrivileged": ([None], POD_MATCH, "deny"),
}

# one defect per violating Pod; every class violates at least one
# template, and the audit sample covers each class
DEFECTS = (
    "privileged", "privileged-init", "no-owner-annotation",
    "dockerhub-image", "latest-tag", "untagged-image", "memory-12gi",
    "memory-unparseable", "no-owner-label", "env-label-regex",
    "foreign-repo",
)


@dataclass
class Scale:
    """Sizes of one run. The defaults are the chip run's; the CPU
    rehearsal passes small ones."""

    pods: int = 100_000
    constraints: int = 500
    namespaces: int = 100
    violating_share: float = 0.01
    bursts: int = 5
    burst_size: int = 64
    audit_sample: int = 120
    admission_sample: int = 64
    first_sweep_timeout_s: float = 900.0


class SmokeFailure(Exception):
    pass


def say(msg):
    print(msg, flush=True)


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


# -- policy and data ----------------------------------------------------------


def load_policy():
    """(templates, Config) from the deploy/policies manifests."""
    import yaml

    templates = []
    for rel in TEMPLATE_FILES:
        with open(os.path.join(ROOT, rel)) as f:
            templates += [
                d for d in yaml.safe_load_all(f)
                if isinstance(d, dict) and d.get("kind") == "ConstraintTemplate"
            ]
    with open(os.path.join(ROOT, CONFIG_FILE)) as f:
        (config,) = [
            d for d in yaml.safe_load_all(f)
            if isinstance(d, dict) and d.get("kind") == "Config"
        ]
    kinds = [t["spec"]["crd"]["spec"]["names"]["kind"] for t in templates]
    check(
        sorted(kinds) == sorted(POLICY),
        f"templates {kinds} do not match the smoke's policy table",
    )
    return templates, config


def make_constraints(templates, n):
    kinds = [t["spec"]["crd"]["spec"]["names"]["kind"] for t in templates]
    out = []
    for i in range(n):
        kind = kinds[i % len(kinds)]
        variants, match, action = POLICY[kind]
        spec = {"match": match, "enforcementAction": action}
        params = variants[(i // len(kinds)) % len(variants)]
        if params is not None:
            spec["parameters"] = params
        out.append({
            "apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": kind,
            "metadata": {"name": f"{kind.lower()}-{i:04d}"},
            "spec": spec,
        })
    return out


def namespace_names(scale):
    return ["kube-system"] + [
        f"team-{j:03d}" for j in range(scale.namespaces)
    ]


def make_namespace(name):
    return {
        "apiVersion": "v1",
        "kind": "Namespace",
        "metadata": {"name": name, "labels": {"tier": name.split("-")[0]}},
    }


def _container(name, image, memory, privileged=False):
    return {
        "name": name,
        "image": image,
        "resources": {"limits": {"cpu": "500m", "memory": memory}},
        "securityContext": {"privileged": privileged},
    }


def make_pod(i, namespace, defect=None, prefix="pod"):
    """A compliant two-container Pod, or one carrying `defect`. Every
    Pod has the same shape, so admission batches share shape buckets."""
    team = f"team-{i % 7}"
    labels = {"app": f"app-{i % 50}", "owner": team,
              "env": "prod" if i % 2 else "dev"}
    annotations = {"team.example.com/owner": team,
                   "team.example.com/cost-center": f"cc-{i % 13}"}
    main = _container(
        "main", f"registry.example.com/{team}/app:v1.{i % 9}",
        f"{1 + i % 4}Gi",
    )
    side = _container("proxy", "registry.example.com/mesh/proxy:v2.1", "256Mi")
    spec = {"containers": [main, side]}
    if defect == "privileged":
        main["securityContext"]["privileged"] = True
    elif defect == "privileged-init":
        spec["initContainers"] = [_container(
            "init", "registry.example.com/mesh/init:v1", "64Mi", True,
        )]
    elif defect == "no-owner-annotation":
        del annotations["team.example.com/owner"]
    elif defect == "dockerhub-image":
        main["image"] = "docker.io/library/nginx:1.25"
    elif defect == "latest-tag":
        main["image"] = "registry.example.com/app:latest"
    elif defect == "untagged-image":
        main["image"] = "registry.example.com/app"
    elif defect == "memory-12gi":
        main["resources"]["limits"]["memory"] = "12Gi"
    elif defect == "memory-unparseable":
        main["resources"]["limits"]["memory"] = "12 GiB"
    elif defect == "no-owner-label":
        del labels["owner"]
    elif defect == "env-label-regex":
        labels["env"] = "staging"
    elif defect == "foreign-repo":
        main["image"] = "quay.io/someone/app:v3"
    return {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {
            "name": f"{prefix}-{i:06d}",
            "namespace": namespace,
            "labels": labels,
            "annotations": annotations,
        },
        "spec": spec,
    }


def make_inventory(scale, rng):
    """(pods, {pod name -> defect}) with ~violating_share violators,
    every defect class present."""
    n_bad = max(len(DEFECTS), int(scale.pods * scale.violating_share))
    bad = sorted(rng.sample(range(scale.pods), n_bad))
    defect_of = {i: DEFECTS[k % len(DEFECTS)] for k, i in enumerate(bad)}
    names = namespace_names(scale)
    pods = []
    for i in range(scale.pods):
        # every 500th Pod lands in kube-system, which the
        # required-annotations constraints exclude
        ns = "kube-system" if i % 500 == 499 else names[1 + i % scale.namespaces]
        pods.append(make_pod(i, ns, defect_of.get(i)))
    return pods, {pods[i]["metadata"]["name"]: d for i, d in defect_of.items()}


def make_service(i, namespace, node_port):
    return {
        "apiVersion": "v1",
        "kind": "Service",
        "metadata": {"name": f"svc-{i:06d}", "namespace": namespace},
        "spec": {
            "type": "NodePort" if node_port else "ClusterIP",
            "selector": {"app": f"app-{i % 50}"},
            "ports": [{"port": 80}],
        },
    }


def make_admission_requests(scale, rng):
    """AdmissionRequests for new objects: Pods (a third carry a defect)
    and every tenth a Service, half of them NodePort."""
    names = namespace_names(scale)[1:]
    out = []
    for i in range(scale.bursts * scale.burst_size):
        ns = names[rng.randrange(len(names))]
        if i % 10 == 9:
            obj = make_service(i, ns, node_port=i % 20 == 19)
        else:
            defect = (
                DEFECTS[rng.randrange(len(DEFECTS))]
                if rng.random() < 1 / 3 else None
            )
            obj = make_pod(i, ns, defect, prefix="admit")
        out.append({
            "uid": f"smoke-{i:05d}",
            "kind": {"group": "", "version": "v1", "kind": obj["kind"]},
            "operation": "CREATE",
            "name": obj["metadata"]["name"],
            "namespace": ns,
            "userInfo": {"username": "smoke-user"},
            "object": obj,
        })
    return out


# -- reference ----------------------------------------------------------------


def new_client(driver, templates, constraints):
    from gatekeeper_tpu.constraint import Backend, K8sValidationTarget

    client = Backend(driver).new_client(K8sValidationTarget())
    for t in templates:
        client.add_template(t)
    for c in constraints:
        client.add_constraint(c)
    return client


def result_key(r):
    res = r.resource if isinstance(r.resource, dict) else {}
    meta = res.get("metadata") or {}
    con = r.constraint or {}
    return (
        res.get("kind", ""), meta.get("namespace", ""), meta.get("name", ""),
        con.get("kind", ""), (con.get("metadata") or {}).get("name", ""),
        r.enforcement_action, r.msg,
    )


def compare_keys(got, want, what):
    got, want = sorted(got), sorted(want)
    if got != want:
        missing = sorted(set(want) - set(got))[:3]
        extra = sorted(set(got) - set(want))[:3]
        raise SmokeFailure(
            f"{what}: {len(got)} results vs the interpreter's {len(want)}; "
            f"missing {missing}, extra {extra}"
        )


def audit_sample(pods, defect_of, scale, rng):
    """Pods covering every defect class (up to 8 each), topped up with
    compliant ones to `audit_sample`."""
    by_class = {}
    for p in pods:
        d = defect_of.get(p["metadata"]["name"])
        if d is not None:
            by_class.setdefault(d, []).append(p)
    check(set(by_class) == set(DEFECTS), "a defect class has no Pod")
    sample = []
    for d in DEFECTS:
        sample += by_class[d][:8]
    clean = [p for p in pods if p["metadata"]["name"] not in defect_of]
    sample += rng.sample(clean, max(0, scale.audit_sample - len(sample)))
    return sample


# -- one chip -----------------------------------------------------------------


def _counter(metrics, name, **labels):
    """Sum of a counter family's series matching `labels`."""
    total = 0.0
    for key, v in metrics.snapshot()["counters"].items():
        fam, _, rest = key.partition("{")
        if fam == name and all(f'{k}="{v_}"' in rest for k, v_ in labels.items()):
            total += v
    return total


def _fused_dispatches(metrics):
    return sum(
        _counter(metrics, "program_cache_total", op=op, result=r)
        for op in ("need", "need_all") for r in ("hit", "miss")
    )


def _post_burst(port, requests):
    """POST each request concurrently, one connection per request,
    released together; -> responses in request order."""
    out = [None] * len(requests)
    gate = threading.Barrier(len(requests))

    def one(k):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
        try:
            body = json.dumps({
                "apiVersion": "admission.k8s.io/v1",
                "kind": "AdmissionReview",
                "request": requests[k],
            })
            conn.connect()
            gate.wait(timeout=120)
            conn.request("POST", "/v1/admit", body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            out[k] = (resp.status, json.loads(resp.read()))
        except Exception as e:  # reported as a failed request below
            out[k] = (None, repr(e))
        finally:
            conn.close()

    threads = [threading.Thread(target=one, args=(k,)) for k in range(len(requests))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return out


def _verdict(response):
    """(allowed, code, sorted deny lines) of an admission response."""
    status = response.get("status") or {}
    lines = sorted(filter(None, (status.get("message") or "").split("\n")))
    return bool(response.get("allowed")), status.get("code", 200), lines


def one_chip_smoke(scale, seed):
    from gatekeeper_tpu.constraint import RegoDriver, TpuDriver
    from gatekeeper_tpu.constraint import tpudriver
    from gatekeeper_tpu.control import FakeCluster, Runner
    from gatekeeper_tpu.native import last_build_error, load_flatten_native
    from gatekeeper_tpu.webhook.policy import ValidationHandler

    rng = random.Random(seed)
    native = load_flatten_native() is not None
    say(f"native_flattener: {'loaded' if native else 'python fallback'}"
        + ("" if native else f" ({last_build_error})"))

    t0 = time.perf_counter()
    templates, config = load_policy()
    constraints = make_constraints(templates, scale.constraints)
    pods, defect_of = make_inventory(scale, rng)
    namespaces = [make_namespace(n) for n in namespace_names(scale)]
    cluster = FakeCluster()
    for obj in templates + constraints + [config] + namespaces + pods:
        cluster.apply(obj)
    say(f"setup: {len(templates)} templates, {len(constraints)} constraints, "
        f"{len(pods)} pods ({len(defect_of)} violating), "
        f"{len(namespaces)} namespaces in {time.perf_counter() - t0:.3f}s")

    driver = TpuDriver()
    client = new_client(driver, [], [])
    runner = Runner(
        cluster, client, TARGET,
        operations=("webhook", "audit"),
        audit_interval=1e9,  # one loop sweep; the rest are driven here
        audit_from_cache=True,
        readyz_port=None,
        fail_policy="closed",
    )
    warm_thread = None
    try:
        t0 = time.perf_counter()
        runner.start()
        warm_thread = runner._warm_thread
        check(runner.wait_ready(timeout=1800), "ingestion did not settle")
        t_ingest = time.perf_counter() - t0
        say(f"phase ingest: {t_ingest:.3f}s")

        # first sweep: the audit loop's own, right after ingestion,
        # concurrent with the runner's warm thread (the product's boot)
        check(runner.audit.warmed.wait(scale.first_sweep_timeout_s),
              f"first audit sweep did not finish: {runner.audit.last_error!r}")
        say(f"phase first_sweep: {runner.audit.audit_duration_seconds:.3f}s")
        deadline = time.monotonic() + scale.first_sweep_timeout_s
        while not runner.webhook.warm and time.monotonic() < deadline:
            time.sleep(0.1)
        check(runner.webhook.warm, "the runner's warm thread did not finish")
        say(f"runner warm thread done {time.perf_counter() - t0:.3f}s "
            f"after start")

        # warm compile: the admission shapes, synchronously and alone; a
        # compile failure here would otherwise leave serving on the
        # interpreter
        requests = make_admission_requests(scale, rng)
        t0 = time.perf_counter()
        handler = runner.webhook.batcher.target_handler
        warm_reviews = [
            handler.augment_request(r, runner._get_namespace)
            for r in requests[: scale.burst_size]
        ]
        ok = client.warm_review_path(warm_reviews)
        t_warm = time.perf_counter() - t0
        say(f"phase warm_compile: {t_warm:.3f}s")
        check(ok and driver.review_path_warm(TARGET),
              f"review path did not warm: {driver.last_warm_error!r}")

        t0 = time.perf_counter()
        report = runner.audit.audit()
        t_steady = time.perf_counter() - t0
        stats = dict(driver.stats)
        say(f"phase steady_sweep: {t_steady:.3f}s "
            f"({report.total_violations} violations)")
        say(f"steady_sweep split: {stats.get('phase_seconds')}")
        say(f"compiled_pairs: {stats.get('compiled_pairs')} "
            f"interp_pairs: {stats.get('interp_pairs')}")
        n_res = len(pods) + len(namespaces)
        check(stats.get("n_reviews") == n_res,
              f"sweep covered {stats.get('n_reviews')} of {n_res} resources")
        check(stats.get("compiled_pairs", 0) > 0, "no compiled pairs")
        fallback = stats.get("fallback_codes") or {}
        say(f"interpreter-routed templates: {fallback or 'none'}")
        check(stats.get("interp_pairs", 0) == 0 or fallback,
              "interpreter pairs without an analyzer-routed template")

        # audit verdicts against the interpreter on a seeded sample
        results = client.audit().by_target[TARGET].results
        check(len(results) == report.total_violations,
              f"client sweep {len(results)} != audit report "
              f"{report.total_violations}")
        sample = audit_sample(pods, defect_of, scale, rng)
        names = {(p["metadata"]["namespace"], p["metadata"]["name"]) for p in sample}
        ref = new_client(RegoDriver(), templates, constraints)
        for ns in namespaces:
            ref.add_data(ns)
        for p in sample:
            ref.add_data(p)
        want = [result_key(r) for r in ref.audit().by_target[TARGET].results]
        got = [k for k in map(result_key, results) if (k[1], k[2]) in names]
        compare_keys(got, want, f"audit sample of {len(sample)} pods")
        say(f"audit_reference: {len(sample)} pods x {len(constraints)} "
            f"constraints, {len(want)} violations identical")

        # admission over HTTP, in concurrent bursts
        cold0 = driver.cold_batches
        fused0 = _fused_dispatches(runner.metrics)
        port = runner.webhook.port
        answers = []
        t0 = time.perf_counter()
        for b in range(scale.bursts):
            burst = requests[b * scale.burst_size:(b + 1) * scale.burst_size]
            answers += _post_burst(port, burst)
        t_admit = time.perf_counter() - t0
        fused = _fused_dispatches(runner.metrics) - fused0
        cold = driver.cold_batches - cold0
        denied = 0
        for req, (status, body) in zip(requests, answers):
            check(status == 200, f"{req['uid']}: HTTP {status} {body}")
            denied += not body["response"]["allowed"]
        say(f"phase admission: {t_admit:.3f}s for {len(requests)} reviews "
            f"({denied} denied) in {scale.bursts} bursts of "
            f"{scale.burst_size}")
        say(f"fused_dispatches: {fused:.0f} cold_batches: {cold}")
        check(fused > 0, "no admission batch took the fused route")
        check(cold == 0, f"{cold} admission batches were served cold")

        ref_handler = ValidationHandler(
            ref, TARGET, namespace_getter=runner._get_namespace
        )
        picks = sorted(rng.sample(range(len(requests)), scale.admission_sample))
        for k in picks:
            want_v = ref_handler.handle(requests[k]).to_dict()
            got_v = answers[k][1]["response"]
            check(_verdict(got_v) == _verdict(want_v),
                  f"{requests[k]['uid']}: webhook {got_v} != "
                  f"interpreter {want_v}")
        say(f"admission_reference: {len(picks)} reviews identical")

        # no rung of the serving ladder may have been taken
        batcher = runner.webhook.batcher
        ladder = {
            "webhook_degraded_dispatch_total": _counter(
                runner.metrics, "webhook_degraded_dispatch_total"),
            "webhook_batch_failures": batcher.batch_failures,
            "shed": batcher.shed_count,
            "driver_render_errors_total": driver._render_errors,
            "analyzer_mismatches": driver.analyzer_mismatches,
        }
        say(f"ladder: {ladder}")
        check(not any(ladder.values()), f"serving ladder taken: {ladder}")
    finally:
        runner.stop()
        if warm_thread is not None:
            warm_thread.join()
    peak = _peak_bytes()
    say(f"peak_bytes_in_use: {peak}")
    say(f"MIN_DEVICE_BATCH: {tpudriver.MIN_DEVICE_BATCH}")


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


# -- four chips ---------------------------------------------------------------


def four_chip_audit(scale, seed, n_devices=4):
    """The sharded sweep for c_shards in {1, 2, 4} against the
    single-device sweep on one of the same chips."""
    from gatekeeper_tpu.constraint import TpuDriver
    from gatekeeper_tpu.parallel import audit_mesh

    rng = random.Random(seed)
    templates, _ = load_policy()
    constraints = make_constraints(templates, scale.constraints)
    pods, defect_of = make_inventory(scale, rng)
    data = [make_namespace(n) for n in namespace_names(scale)] + pods

    def sweep(mesh, label):
        t0 = time.perf_counter()
        driver = TpuDriver(mesh=mesh)
        client = new_client(driver, templates, constraints)
        for obj in data:
            client.add_data(obj)
        t_ingest = time.perf_counter() - t0
        t0 = time.perf_counter()
        first = client.audit().by_target[TARGET].results
        t_first = time.perf_counter() - t0
        t0 = time.perf_counter()
        res = client.audit().by_target[TARGET].results
        t_steady = time.perf_counter() - t0
        check(sorted(map(result_key, first)) == sorted(map(result_key, res)),
              f"{label}: first and steady sweeps differ")
        say(f"{label}: ingest {t_ingest:.3f}s first_sweep {t_first:.3f}s "
            f"steady_sweep {t_steady:.3f}s {len(res)} violations "
            f"compiled_pairs {driver.stats.get('compiled_pairs')} "
            f"interp_pairs {driver.stats.get('interp_pairs')}")
        check(driver.stats.get("compiled_pairs", 0) > 0,
              f"{label}: no compiled pairs")
        return sorted(map(result_key, res))

    ref = sweep(None, "single-device")
    check(len(ref) > 0, "single-device sweep found no violations")
    for c_shards in (1, 2, 4):
        mesh = audit_mesh(n_devices, c_shards=c_shards)
        got = sweep(mesh, f"mesh c_shards={c_shards} n_shards="
                          f"{n_devices // c_shards}")
        check(got == ref, f"c_shards={c_shards}: sharded sweep differs "
                          f"from the single-device sweep")
        say(f"c_shards={c_shards}: identical to single-device "
            f"({len(ref)} violations)")
    say(f"peak_bytes_in_use (device 0): {_peak_bytes()}")


# -- entry --------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded audit over four chips")
    args = ap.parse_args(argv)
    want = 4 if args.four_chips else 1
    try:
        import jax

        devs = jax.devices()
        # a wedged run still ends inside the 1200 s budget, and a kill
        # from outside still shows where every thread was; registered
        # after backend init so the TPU runtime's own handler is chained
        err = sys.__stderr__
        faulthandler.register(signal.SIGTERM, err, all_threads=True, chain=True)
        faulthandler.dump_traceback_later(WATCHDOG_S, exit=True, file=err)
        say(f"jax.devices(): {devs}")
        check(devs[0].platform == "tpu",
              f"platform is {devs[0].platform!r}, not 'tpu'")
        # the result line's count is the mode's chip count, so the
        # mode needs exactly that many devices visible
        check(len(devs) == want, f"{len(devs)} devices visible, this mode "
                                 f"needs exactly {want}")
        if args.four_chips:
            four_chip_audit(Scale(), args.seed, want)
        else:
            one_chip_smoke(Scale(), args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        faulthandler.cancel_dump_traceback_later()
        faulthandler.unregister(signal.SIGTERM)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
