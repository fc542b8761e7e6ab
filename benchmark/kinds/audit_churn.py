"""Traffic kind `audit_churn`: a cluster that changes between audit
sweeps. Each step deletes `churn_share` of every churned kind's objects
(the seed picks which) and creates as many fresh ones with new names,
through the FakeCluster as the API server would, so that they travel
watch -> SyncController -> Client.add_data/remove_data; it waits until
the sync has landed in the driver (`bench.churn`), then runs one whole
`AuditManager.audit()` (`bench.sweep`). Steps run back to back from
the window's start; the step in flight at the close finishes.

The churned kinds are the configuration's synced kinds other than
Namespace: Pods for `pods100k-c500`, Services and Ingresses for
`refs-svc10k-ing5k`. Fresh objects come from the same generators at
the same shares (violations, selector pairs, shared hosts), and every
step's deletions and creations are made from the seed in set-up, so
the window only applies them. Set-up ends with one churned sweep.

Parameters (traffic/<mix>.json): `first_sweep_timeout_s`,
`churn_share`, `max_steps` (steps made in set-up; a window that uses
them all ends early), `sync_timeout_s`, `control_steps`.
"""

import time

from benchmark import harness, reference
from benchmark.gen import policy as gen
from benchmark.gen import refs
from benchmark.harness import log
from benchmark.reference import joins

SWEEPS = harness.load_kind({"kind": "audit_sweeps"})


def churned_kinds(cfg):
    return [k for k in cfg["sync_kinds"] if k != "Namespace"]


def object_key(obj):
    meta = obj.get("metadata") or {}
    return (obj.get("apiVersion"), obj.get("kind"), meta.get("namespace") or "",
            meta.get("name") or "")


class Inventory:
    """A configuration's policy, its synced objects and the churn
    schedule, all made from the seed."""

    def __init__(self, cfg, traffic, seed, inputs=None):
        inputs = inputs or harness.Inputs(cfg, seed)
        self.templates, self.config = inputs.templates, inputs.config
        self.constraints, self.namespaces = inputs.constraints, inputs.namespaces
        inv = cfg["inventory"]
        rng = gen.new_rng(seed, "refs")
        self.churned = {"Pod": inputs.pods}
        if inv.get("services"):
            self.churned["Service"] = refs.make_services(inv["services"], 0, 0, inv, rng)
        if inv.get("ingresses"):
            self.churned["Ingress"] = refs.make_ingresses(inv["ingresses"], 0, 0, inv, rng)
        self.churned = {k: self.churned[k] for k in churned_kinds(cfg)}
        self.steps = self._schedule(cfg, traffic, seed)

    def objects(self):
        return self.namespaces + [o for objs in self.churned.values() for o in objs]

    def _schedule(self, cfg, traffic, seed):
        """[(deleted objects, created objects)] for `max_steps` steps."""
        inv = cfg["inventory"]
        rng = gen.new_rng(seed, "churn")
        live = {k: list(v) for k, v in self.churned.items()}
        steps = []
        for step in range(traffic["max_steps"]):
            deleted, created = [], []
            for kind, objs in live.items():
                n = round(len(self.churned[kind]) * traffic["churn_share"])
                picked = sorted(rng.sample(range(len(objs)), n))
                fresh = self._fresh(kind, step, n, inv, rng)
                deleted += [objs[i] for i in picked]
                created += fresh
                # the live set keeps its size: fresh objects take the
                # deleted ones' places
                for i, obj in zip(picked, fresh):
                    objs[i] = obj
                objs += fresh[len(picked):]
            steps.append((deleted, created))
        return steps

    def _fresh(self, kind, step, n, inv, rng):
        """Batch `step` of n fresh objects, numbered past the initial
        inventory and every earlier batch."""
        start = len(self.churned[kind]) + step * n
        if kind == "Service":
            return refs.make_services(n, start, step, inv, rng)
        if kind == "Ingress":
            return refs.make_ingresses(n, start, step, inv, rng)
        # as gen.make_inventory: the seed picks which Pods violate,
        # their defect classes cycle over the batches
        names = gen.namespace_names(inv["namespaces"])
        share = inv["violating_share"]
        bad = sorted(rng.sample(range(n), refs.quota(share, n, step)))
        before = refs.quota(share, n * step, 0)
        defect_of = {j: gen.DEFECTS[(before + b) % len(gen.DEFECTS)] for b, j in enumerate(bad)}
        every = inv["kube_system_every"]
        pods = []
        for j in range(n):
            i = start + j
            ns = "kube-system" if i % every == every - 1 else names[1 + i % inv["namespaces"]]
            pods.append(gen.make_pod(i, ns, defect_of.get(j)))
        return pods


class Expectation:
    """The reference's audit of the synced inventory as it changes:
    per-object constraints through `reference.Policy`, kept per object;
    the joins recomputed over the whole inventory on each read."""

    def __init__(self, cfg, inputs):
        self.config = inputs.config
        self.msg_size = cfg["runner"]["msg_size"]
        self.joined = [c for c in inputs.constraints if c["kind"] in joins.RULES]
        self.policy = reference.Policy(
            [c for c in inputs.constraints if c["kind"] not in joins.RULES])
        self.live = {}
        self.per_object = {}  # constraint key -> set of entries
        for obj in inputs.objects():
            self.add(obj)

    def _entries(self, obj):
        if reference.config_excludes(self.config, "audit", obj):
            return []
        meta = obj.get("metadata") or {}
        return [(f"{ckind}/{cname}", (
            obj.get("kind") or "", meta.get("namespace") or "", meta.get("name") or "",
            reference.truncate(msg, self.msg_size), action))
            for ckind, cname, action, msg in self.policy.evaluate(obj)]

    def add(self, obj):
        self.live[object_key(obj)] = obj
        for key, entry in self._entries(obj):
            self.per_object.setdefault(key, set()).add(entry)

    def remove(self, obj):
        del self.live[object_key(obj)]
        for key, entry in self._entries(obj):
            self.per_object[key].discard(entry)

    def apply(self, step):
        deleted, created = step
        for obj in deleted:
            self.remove(obj)
        for obj in created:
            self.add(obj)

    def want(self):
        """{"<Kind>/<name>": set of (kind, namespace, name, msg, action)}"""
        out = {k: set(v) for k, v in self.per_object.items() if v}
        synced = [o for o in self.live.values()
                  if not reference.config_excludes(self.config, "sync", o)]
        for c in self.joined:
            hits = joins.RULES[c["kind"]](synced)
            spec = c.get("spec") or {}
            action = spec.get("enforcementAction") or "deny"
            entries = set()
            for obj in synced:
                _, kind, ns, name = object_key(obj)
                if ((ns, name) not in hits
                        or not reference.matches(spec.get("match") or {}, obj)
                        or reference.config_excludes(self.config, "audit", obj)):
                    continue
                for msg in hits[(ns, name)]:
                    entries.add((kind, ns, name, reference.truncate(msg, self.msg_size), action))
            if entries:
                out[f"{c['kind']}/{c['metadata']['name']}"] = entries
        return out


class State:
    def __init__(self, cfg, traffic, seed, prog, inventory):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.prog, self.inventory = prog, inventory
        self.applied = 0  # steps applied to the cluster
        self.sweeps = []  # (steps applied when the sweep ran, summary)


def apply_step(state):
    """Apply the next step through the cluster; wait for the sync."""
    deleted, created = state.inventory.steps[state.applied]
    runner = state.prog.runner
    for obj in deleted:
        runner.cluster.delete(obj)
    for obj in created:
        runner.cluster.apply(obj)
    if not runner.watch_mgr.wait_idle(state.traffic["sync_timeout_s"]):
        raise RuntimeError("churn did not sync")
    state.applied += 1


def setup(cfg, traffic, seed):
    t0 = time.monotonic()
    prog = harness.Program(cfg, seed)
    inventory = Inventory(cfg, traffic, seed, prog)
    for kind, objs in inventory.churned.items():
        if kind != "Pod":  # the Program applied its Pods
            for obj in objs:
                prog.runner.cluster.apply(obj)
    log(f"inventory and churn schedule: {time.monotonic() - t0:.3f}s")
    state = State(cfg, traffic, seed, prog, inventory)
    prog.start()
    audit = prog.runner.audit
    if not audit.warmed.wait(traffic["first_sweep_timeout_s"]):
        raise RuntimeError(f"first audit sweep did not finish: {audit.last_error!r}")
    log(f"first sweep: {audit.audit_duration_seconds:.3f}s")
    t0 = time.monotonic()
    apply_step(state)
    audit.audit()
    log(f"churned sweep in set-up: {time.monotonic() - t0:.3f}s")
    return state


SPLIT = ("driver_join_rows_total{", "driver_phase_seconds{", "audit_phase_seconds{",
         "client_phase_seconds{", "client_resource_copies_total{", "sync_duration_seconds",
         "process_gc_pause_seconds{")


def split_state(metrics):
    """{series: value} of the families a churned sweep's split reads:
    counter values, distribution sums."""
    snap = metrics.snapshot()
    out = {k: v for k, v in snap["counters"].items() if k.startswith(SPLIT)}
    out.update({k: d["sum"] for k, d in snap["distributions"].items() if k.startswith(SPLIT)})
    return out


def window(state, seconds, tracer, start_window):
    """-> (attempted, failed, work, record extras)"""
    audit = state.prog.runner.audit
    split0 = split_state(state.prog.runner.metrics)
    failed = 0
    t_window = start_window()
    while True:
        if state.applied == len(state.inventory.steps):
            log("churn schedule used up: the window ends early")
            break
        with tracer.span("churn"):
            apply_step(state)
        with tracer.span("sweep"):
            try:
                report = audit.audit()
            except Exception as e:  # a failed sweep counts in `failed`
                log(f"sweep failed: {e!r}")
                failed += 1
                report = None
        if report is not None:
            state.sweeps.append((state.applied, SWEEPS.report_summary(report)))
        if time.monotonic() - t_window >= seconds:
            break
    sweeps = len(state.sweeps) + failed
    elapsed = time.monotonic() - t_window
    split = split_state(state.prog.runner.metrics)
    log("per sweep: " + ", ".join(
        f"{k} {(v - split0.get(k, 0)) / max(sweeps, 1):.6f}" for k, v in sorted(split.items())
        if v != split0.get(k, 0)))
    work = {"sweeps": sweeps, "failed": failed, "elapsed_s": elapsed,
            "steps": state.applied}
    return sweeps, failed, work, {}


def compare(cfg, inventory, sweeps):
    """Each sweep's report against the reference for the inventory that
    sweep saw: the initial objects and the steps applied before it.
    -> checks"""
    cap = cfg["runner"]["constraint_violations_limit"]
    want = Expectation(cfg, inventory)
    applied = 0
    bad = 0
    for steps, summary in sweeps:
        for step in inventory.steps[applied:steps]:
            want.apply(step)
        applied = steps
        bad += SWEEPS.compare_one(summary, want.want(), cap)
    return {"audit_mismatches": (bad, 0), "sweeps_compared": (len(sweeps), None)}


def check(state):
    return compare(state.cfg, state.inventory, state.sweeps)


def reference_summary(want, cap):
    """The reference's report as the program would give it."""
    statuses = {k: (len(v), sorted(v)[:cap]) for k, v in want.items()}
    by_action = {}
    for v in want.values():
        for *_, action in v:
            by_action[action] = by_action.get(action, 0) + 1
    return {"total": sum(by_action.values()), "by_action": by_action, "statuses": statuses}


def control(cfg, traffic, seed, seconds):
    """The reference with one guarantee broken, in the program's place:
    a stale audit, each sweep reporting the inventory as it stood before
    that step's churn. -> checks"""
    inventory = Inventory(cfg, traffic, seed)
    cap = cfg["runner"]["constraint_violations_limit"]
    stale = Expectation(cfg, inventory)
    sweeps = []
    for k in range(traffic["control_steps"]):
        sweeps.append((k + 1, reference_summary(stale.want(), cap)))
        stale.apply(inventory.steps[k])
    return compare(cfg, inventory, sweeps)
