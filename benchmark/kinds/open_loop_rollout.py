"""Traffic kind `open_loop_rollout`: rollout bursts of workload
resources, POSTed open loop over persistent HTTPS connections to the
Runner's `/v1/admit`, with the configuration's ExpansionTemplates
loaded, from the same spawned child as `open_loop_admission`.

Application syncs arrive at Poisson instants; each is a burst of
Deployment CREATEs of one chart, `burst_gap_s` apart, in one namespace,
sharing one pod template (gen/workloads.py); each Deployment the
reference admits is followed by its ReplicaSet from the deployment
controller. No Pod is sent: `admit-c50.steady` measures those.

Parameters (traffic/<mix>.json):
- `rate_rps`: the window's mean reviews per second, Deployments and
  ReplicaSets together; syncs are drawn until round(rate_rps *
  seconds) reviews are reached, and their arrivals spread over the
  window;
- `rollout`: `burst_min`..`burst_max` Deployments per sync,
  `burst_gap_s` apart, `defect_share` of them with one defect in the
  pod template, `replicas` lo..hi, and each ReplicaSet a uniform
  `replicaset_delay_s` after its Deployment;
- `deadline_s`, `wait_s`, `connections`, `warm_timeout_s`,
  `warm_seconds`: as `open_loop_admission`'s;
- `warm_batches`: review row counts `from`..`to` by `step`, `samples`
  of each, that set-up compiles through the client's review warm-up,
  each row slice taken from workloads and the Pods they imply, in the
  order a batch holds them.

Set-up checks that the program answers a Deployment whose pod template
runs a privileged container with the implied Pod's denial: a program
that does not expand workloads fails there, before any warm-up. The
check compares every answer with `reference/expansion.py`.
"""

import time

from benchmark import harness, reference
from benchmark.gen import policy as gen
from benchmark.gen import workloads
from benchmark.harness import log
from benchmark.reference import expansion as expansion_ref

_admission = harness.load_kind({"kind": "open_loop_admission"})
offer = _admission.offer
classify = _admission.classify


class State(_admission.State):
    def __init__(self, cfg, traffic, seed, prog, templates):
        super().__init__(cfg, traffic, seed, prog)
        self.templates = templates


def expansion_templates(cfg):
    return gen.load_yaml_docs(cfg["expansion_templates"], "ExpansionTemplate")


def _verdict(templates):
    def verdict(request, policy, config):
        return expansion_ref.admission_expectation(request, policy, config, templates)
    return verdict


def _rollout(cfg, traffic, seed, seconds, inputs, templates, prefix, n_reviews=None):
    policy = reference.Policy(inputs.constraints)
    verdict = _verdict(templates)

    def admitted(request):
        return verdict(request, policy, inputs.config)[0]

    if n_reviews is None:
        n_reviews = round(traffic["rate_rps"] * seconds)
    return workloads.make_rollout(
        n_reviews, seconds, traffic["rollout"],
        cfg["inventory"]["namespaces"], seed, admitted, prefix=prefix)


def make_load(cfg, traffic, seed, seconds, inputs=None):
    """(requests, offsets, warm requests, warm offsets): the window's
    rollout, and `warm_seconds` of the same rate that it never sends."""
    inputs = inputs or harness.Inputs(cfg, seed)
    templates = expansion_templates(cfg)
    requests, offsets = _rollout(cfg, traffic, seed, seconds, inputs, templates, "rollout")
    warm, warm_offsets = _rollout(cfg, traffic, f"{seed}:warm", traffic["warm_seconds"],
                                  inputs, templates, "warm")
    return requests, offsets, warm, warm_offsets


def _probe(prog, cfg):
    """A Deployment whose pod template runs a privileged container has
    to be denied with its implied Pod's violation."""
    ns = gen.namespace_names(cfg["inventory"]["namespaces"])[1]
    dep = workloads.make_workload(
        "Deployment", "probe", ns, workloads.pod_template(0, ns, "privileged"))
    resp = prog.runner.webhook.handler.handle(
        workloads.workload_request(dep, "probe", "bench-user"))
    if resp.allowed or "[Implied by " not in resp.message:
        raise RuntimeError(
            "the program admits a Deployment whose pod template is privileged "
            f"(allowed={resp.allowed}, {resp.message!r}): it does not expand "
            "workload resources")


def warm_batches(prog, cfg, traffic, seed, templates):
    """Compile the review path for every row count the window's batches
    can form, through `Client.warm_review_path`, on slices of the rows
    (each workload and the Pods it implies, in arrival order) of a
    rollout drawn as the window's is, from a seed the window never uses."""
    from gatekeeper_tpu.constraint.handler import handler_for

    wb = traffic["warm_batches"]
    rng = gen.new_rng(seed, "warm-batches")
    requests, _ = _rollout(cfg, traffic, f"{seed}:warm-batches", traffic["warm_seconds"],
                           prog, templates, "rollout", n_reviews=wb["to"])
    rows = []
    for req in requests:
        rows += [req] + [r for _, r in expansion_ref.expand(req, templates)]
    handler = handler_for(prog.client, gen.TARGET)
    rows = [handler.augment_request(r) for r in rows]
    t0, calls = time.monotonic(), 0
    for n in range(wb["from"], wb["to"] + 1, wb["step"]):
        for _ in range(wb["samples"]):
            start = rng.randrange(len(rows) - n + 1)
            if not prog.client.warm_review_path(rows[start:start + n]):
                raise RuntimeError(f"review warm-up failed at {n} rows: "
                                   f"{getattr(prog.driver, 'last_warm_error', None)!r}")
            calls += 1
    log(f"warm batches: {calls} in {time.monotonic() - t0:.3f}s")


def setup(cfg, traffic, seed):
    prog = harness.Program(cfg, seed)
    templates = expansion_templates(cfg)
    for t in templates:
        prog.runner.cluster.apply(t)
    state = State(cfg, traffic, seed, prog, templates)
    prog.start()
    try:
        _probe(prog, cfg)
        log("expansion probe denied")
        deadline = time.monotonic() + traffic["warm_timeout_s"]
        while not prog.runner.webhook.warm and time.monotonic() < deadline:
            time.sleep(0.05)
        if not prog.runner.webhook.warm:
            raise RuntimeError("the runner's warm thread did not finish")
        log("runner warm thread done")
        warm_batches(prog, cfg, traffic, seed, templates)
    except BaseException:
        prog.stop()
        raise
    return state


def window(state, seconds, tracer, start_window):
    """-> (attempted, failed, work, record extras)"""
    load = make_load(state.cfg, state.traffic, state.seed, seconds, state.prog)
    state.requests = load[0]
    out = offer(state.prog, state.traffic, load, seconds, start_window)
    lat, failed, state.answers, state.missing = classify(out["records"], state.traffic["deadline_s"])
    work = {"requests": len(lat), "failed": failed,
            "deployments": sum(r["kind"]["kind"] == "Deployment" for r in state.requests),
            "lateness_max_s": out["lateness_max_s"], "lateness_mean_s": out["lateness_mean_s"]}
    return len(lat), failed, work, {"latencies_s": lat}


def check(state):
    return _admission.compare(state.prog, state.requests, state.answers, state.missing,
                              verdict=_verdict(state.templates))


def control(cfg, traffic, seed, seconds):
    """The reference with one guarantee broken, in the program's place:
    workloads are answered without their implied Pods, as a webhook
    that does not expand them answers. -> checks"""
    inputs = harness.Inputs(cfg, seed)
    requests = make_load(cfg, traffic, seed, seconds, inputs)[0]
    pol = reference.Policy(inputs.constraints)
    answers = {k: (r["uid"], *reference.admission_expectation(r, pol, inputs.config))
               for k, r in enumerate(requests)}
    return _admission.compare(inputs, requests, answers, 0, verdict=_verdict(expansion_templates(cfg)))
