"""Differential tests: compiled template programs vs the interpreter.

For each supported reference-library template, compile it (per constraint
params) into a token-table program and check the per-resource violation
COUNT matches interpreter evaluation of the same rewritten module on a
corpus of synthetic reviews. This is the correctness gate for the
Rego-subset compiler (gatekeeper_tpu/engine/symbolic.py).
"""

import os

import numpy as np
import pytest
import yaml

from gatekeeper_tpu.engine.patterns import PatternRegistry
from gatekeeper_tpu.engine.programs import ProgramEvaluator, compile_program
from gatekeeper_tpu.engine.symbolic import CompilerEnv, CompileUnsupported
from gatekeeper_tpu.engine.tables import StrTables
from gatekeeper_tpu.flatten import Vocab, encode_token_table
from gatekeeper_tpu.rego.interp import Interpreter, Undefined
from gatekeeper_tpu.rego.parser import parse_module
from gatekeeper_tpu.rego.rewrite import rewrite_module

from test_tpu_driver import referential_template

REFERENCE = "/root/reference"
LIB = f"{REFERENCE}/library"


# in-repo ports of the library's two inventory joins, by library dir
REFERENTIAL = {
    "general/uniqueingresshost": "K8sUniqueIngressHost",
    "general/uniqueserviceselector": "K8sUniqueServiceSelector",
}


def load_template_rego(path: str) -> str:
    return open(path).read()


def library_rego(tdir: str) -> str:
    """A library template's Rego: the in-repo port where one exists
    (deploy/library/referential.yaml), else the library's src.rego."""
    if tdir not in REFERENTIAL:
        return load_template_rego(f"{LIB}/{tdir}/src.rego")
    return referential_template(REFERENTIAL[tdir])["spec"]["targets"][0]["rego"]


def pod(containers=None, init_containers=None, labels=None, spec_extra=None,
        name="p"):
    spec = {}
    if containers is not None:
        spec["containers"] = containers
    if init_containers is not None:
        spec["initContainers"] = init_containers
    if spec_extra:
        spec.update(spec_extra)
    meta = {"name": name}
    if labels is not None:
        meta["labels"] = labels
    return {
        "kind": {"group": "", "version": "v1", "kind": "Pod"},
        "name": name,
        "namespace": "default",
        "object": {"metadata": meta, "spec": spec},
    }


def ctr(name="c", image="nginx", sc=None, resources=None, extra=None):
    c = {"name": name, "image": image}
    if sc is not None:
        c["securityContext"] = sc
    if resources is not None:
        c["resources"] = resources
    if extra:
        c.update(extra)
    return c


PODS = [
    pod(containers=[ctr()]),
    pod(containers=[ctr(sc={"privileged": True})]),
    pod(containers=[ctr(sc={"privileged": False})]),
    pod(
        containers=[ctr("a", sc={"privileged": True}), ctr("b")],
        init_containers=[ctr("i", sc={"privileged": True})],
    ),
    pod(containers=[], labels={"app": "web", "owner": "me"}),
    pod(containers=[ctr()], labels={"gatekeeper": "ok"}),
    pod(containers=[ctr()], labels={"gatekeeper": "NOT-ok!!"}),
    pod(containers=[ctr()], spec_extra={"hostPID": True}),
    pod(containers=[ctr()], spec_extra={"hostIPC": True, "hostPID": False}),
    pod(containers=[ctr()], spec_extra={"hostNetwork": True}),
    pod(
        containers=[
            ctr(
                "caps",
                sc={
                    "capabilities": {
                        "add": ["NET_ADMIN", "SYS_TIME"],
                        "drop": ["KILL"],
                    }
                },
            ),
            ctr("nocaps"),
        ]
    ),
    pod(
        containers=[
            ctr("x", sc={"capabilities": {"add": ["CHOWN"], "drop": ["ALL"]}})
        ]
    ),
    # container names are unique per pod (a K8s API invariant the
    # compiled counter's no-msg-dedup approximation relies on)
    pod(
        containers=[
            ctr("good", image="gcr.io/mine/app:1"),
            ctr("bad", image="docker.io/evil"),
        ]
    ),
    pod(containers=[ctr(resources={"limits": {"cpu": "100m", "memory": "1Gi"}})]),
    pod(containers=[ctr(resources={"limits": {"cpu": "2", "memory": "4Gi"}})]),
    pod(containers=[ctr(resources={"limits": {"cpu": "weird", "memory": "x"}})]),
    pod(containers=[ctr(resources={"limits": {"memory": "512Mi"}})]),
    pod(containers=[ctr(resources={"limits": {"cpu": 1.5}})]),
    pod(containers=[ctr(resources={})]),
    pod(containers=[ctr(resources={"limits": {"cpu": "", "memory": ""}})]),
    # ingress shapes for httpsonly
    {
        "kind": {"group": "extensions", "version": "v1beta1", "kind": "Ingress"},
        "name": "ing1",
        "object": {
            "metadata": {
                "name": "ing1",
                "annotations": {"kubernetes.io/ingress.allow-http": "false"},
            },
            "spec": {"tls": [{"secretName": "s"}]},
        },
    },
    {
        "kind": {"group": "networking.k8s.io", "version": "v1", "kind": "Ingress"},
        "name": "ing2",
        "object": {"metadata": {"name": "ing2"}, "spec": {"rules": []}},
    },
    {
        "kind": {"group": "extensions", "version": "v1beta1", "kind": "Ingress"},
        "name": "ing3",
        "object": {
            "metadata": {"name": "ing3"},
            "spec": {"tls": []},
        },
    },
    # degenerate shapes
    pod(containers=None),
    {"kind": {"group": "", "version": "v1", "kind": "Pod"}, "name": "empty",
     "object": {}},
]


def make_env():
    vocab = Vocab()
    patterns = PatternRegistry(vocab)
    tables = StrTables(vocab)
    return vocab, patterns, tables


def compile_and_count(src, params, reviews, oracle_interp=None, pkg=None,
                      use_jax=False):
    vocab, patterns, tables = make_env()
    mod = parse_module(src)
    rewrite_module(mod)

    def oracle_fn(fn_name, value):
        probe = (
            f"package __probe\nout := data.{pkg}.{fn_name}(input.arg)\n"
        )
        oracle_interp.add_module("__probe", probe)
        ctx = oracle_interp.make_context(
            {"arg": value, "parameters": params}, {}
        )
        v = oracle_interp.eval_rule_extent(["__probe"], "out", ctx)
        if v is Undefined:
            return None, False
        from gatekeeper_tpu.rego.values import thaw

        return thaw(v), True

    env = CompilerEnv(
        vocab,
        patterns,
        tables,
        oracle_fn=oracle_fn if oracle_interp else None,
        oracle_ns=pkg or "t",
    )
    prog = compile_program(env, [mod], params)
    table = encode_token_table(reviews, vocab)
    patterns.sync()
    tables.sync()
    tok = {
        "spath": table.spath,
        "idx0": table.idx0,
        "idx1": table.idx1,
        "kind": table.kind,
        "vid": table.vid,
        "vnum": table.vnum,
    }
    if use_jax:
        ev = ProgramEvaluator(patterns, tables, use_jax=True)
        return ev.eval_jax([prog], tok, g=8)[0]
    ev = ProgramEvaluator(patterns, tables, use_jax=False)
    return ev.eval_np(prog, tok, g=8)


def oracle_count(src, params, reviews):
    interp = Interpreter()
    interp.add_module("t", src)
    pkg = interp.modules["t"].package
    out = []
    for r in reviews:
        vios = interp.query_violations(
            list(pkg), {"review": r, "parameters": params}, {}
        )
        out.append(len(vios))
    return np.array(out), interp, ".".join(pkg)


def assert_template_agrees(src_path, params, reviews=PODS, use_jax=False):
    src = load_template_rego(src_path)
    want, interp, pkg = oracle_count(src, params, reviews)
    got = compile_and_count(
        src, params, reviews, oracle_interp=interp, pkg=pkg, use_jax=use_jax
    )
    if not np.array_equal(got, want):
        bad = [
            (i, int(got[i]), int(want[i]))
            for i in range(len(want))
            if got[i] != want[i]
        ]
        raise AssertionError(
            f"{os.path.basename(os.path.dirname(src_path))}: "
            f"params={params} mismatches (idx, compiled, oracle): {bad}"
        )


def test_privileged_containers():
    assert_template_agrees(
        f"{LIB}/pod-security-policy/privileged-containers/src.rego", {}
    )


def test_host_namespaces():
    assert_template_agrees(
        f"{LIB}/pod-security-policy/host-namespaces/src.rego", {}
    )


def test_host_network_ports():
    assert_template_agrees(
        f"{LIB}/pod-security-policy/host-network-ports/src.rego",
        {"hostNetwork": False},
    )


def test_required_labels():
    assert_template_agrees(
        f"{LIB}/general/requiredlabels/src.rego",
        {"labels": [{"key": "gatekeeper", "allowedRegex": "^[a-z]+$"}]},
    )
    assert_template_agrees(
        f"{LIB}/general/requiredlabels/src.rego",
        {"labels": [{"key": "app"}, {"key": "owner"}]},
    )


def test_capabilities():
    assert_template_agrees(
        f"{LIB}/pod-security-policy/capabilities/src.rego",
        {
            "allowedCapabilities": ["CHOWN"],
            "requiredDropCapabilities": ["ALL"],
        },
    )
    assert_template_agrees(
        f"{LIB}/pod-security-policy/capabilities/src.rego",
        {"allowedCapabilities": ["*"], "requiredDropCapabilities": []},
    )


def test_allowed_repos():
    assert_template_agrees(
        f"{LIB}/general/allowedrepos/src.rego",
        {"repos": ["gcr.io/mine"]},
    )


def test_https_only():
    assert_template_agrees(f"{LIB}/general/httpsonly/src.rego", {})


def test_container_limits():
    assert_template_agrees(
        f"{LIB}/general/containerlimits/src.rego",
        {"cpu": "1", "memory": "2Gi"},
    )


# ---------------------------------------------------------------------------
# Full-library battery: every library/*/*/src.rego template either
# differentially matches the oracle (numpy AND jax backends) or is
# asserted to raise CompileUnsupported (-> interpreter fallback in the
# TPU driver). VERDICT r1 item 4.

EXTRA_PODS = [
    # probes (requiredprobes)
    pod(containers=[ctr("np")]),
    pod(containers=[ctr("lp", extra={"livenessProbe": {"tcpSocket": {"port": 1}}})]),
    pod(containers=[ctr(
        "both",
        extra={
            "livenessProbe": {"tcpSocket": {"port": 1}},
            "readinessProbe": {"httpGet": {"path": "/", "port": 2}},
        },
    )]),
    pod(containers=[ctr("empty", extra={"livenessProbe": {}})]),
    # resource ratios (containerresourceratios)
    pod(containers=[ctr(resources={
        "limits": {"cpu": "4", "memory": "4Gi"},
        "requests": {"cpu": "1", "memory": "1Gi"},
    })]),
    pod(containers=[ctr(resources={
        "limits": {"cpu": "1", "memory": "1Gi"},
        "requests": {"cpu": "1", "memory": "1Gi"},
    })]),
    pod(containers=[ctr(resources={"limits": {"cpu": "2"}, "requests": {}})]),
    # privilege escalation
    pod(containers=[ctr(sc={"allowPrivilegeEscalation": False})]),
    pod(containers=[ctr(sc={"allowPrivilegeEscalation": True})]),
    # proc mount
    pod(containers=[ctr(sc={"procMount": "Unmasked"})]),
    pod(containers=[ctr(sc={"procMount": "Default"})]),
    # read-only rootfs
    pod(containers=[ctr(sc={"readOnlyRootFilesystem": True})]),
    pod(containers=[ctr(sc={"readOnlyRootFilesystem": False})]),
    # selinux (pod + container level)
    pod(
        containers=[ctr(sc={"seLinuxOptions": {"level": "s0", "role": "r"}})],
        spec_extra={"securityContext": {"seLinuxOptions": {"level": "s1"}}},
    ),
    pod(containers=[ctr()], spec_extra={
        "securityContext": {"seLinuxOptions": {"level": "s0"}}
    }),
    # users (runAsUser)
    pod(containers=[ctr(sc={"runAsUser": 5})]),
    pod(containers=[ctr(sc={"runAsUser": 0})]),
    pod(
        containers=[ctr()],
        spec_extra={"securityContext": {"runAsUser": 100}},
    ),
    # sysctls
    pod(containers=[ctr()], spec_extra={
        "securityContext": {"sysctls": [
            {"name": "kernel.shm_rmid_forced", "value": "0"},
            {"name": "net.core.somaxconn", "value": "1024"},
        ]}
    }),
    # fsgroup
    pod(containers=[ctr()], spec_extra={"securityContext": {"fsGroup": 5}}),
    pod(containers=[ctr()], spec_extra={"securityContext": {"fsGroup": 2000}}),
    # volumes / flexvolumes / hostPath
    pod(containers=[ctr()], spec_extra={"volumes": [
        {"name": "v1", "hostPath": {"path": "/tmp/x"}},
        {"name": "v2", "configMap": {"name": "cm"}},
    ]}),
    pod(containers=[ctr()], spec_extra={"volumes": [
        {"name": "fv", "flexVolume": {"driver": "example/cifs"}},
    ]}),
    pod(
        containers=[ctr(extra={"volumeMounts": [
            {"name": "hp", "mountPath": "/data"},
        ]})],
        spec_extra={"volumes": [
            {"name": "hp", "hostPath": {"path": "/etc/foo"}},
        ]},
    ),
    # host network/ports
    pod(containers=[ctr(extra={"ports": [{"containerPort": 80, "hostPort": 80}]})],
        spec_extra={"hostNetwork": True}),
    pod(containers=[ctr(extra={"ports": [{"containerPort": 9000, "hostPort": 9000}]})]),
    # seccomp/apparmor style annotations (exercises fallback templates'
    # corpora too once they compile)
    {
        "kind": {"group": "", "version": "v1", "kind": "Pod"},
        "name": "ann",
        "namespace": "default",
        "object": {
            "metadata": {
                "name": "ann",
                "annotations": {
                    "seccomp.security.alpha.kubernetes.io/pod": "runtime/default",
                    "container.seccomp.security.alpha.kubernetes.io/c1": "localhost/x",
                    "container.apparmor.security.beta.kubernetes.io/c1": "runtime/default",
                },
            },
            "spec": {"containers": [{"name": "c1", "image": "nginx"}]},
        },
    },
]

ALL_PODS = PODS + EXTRA_PODS

# template dir (under library/) -> list of param sets to test; None in
# FALLBACK means the compiler must raise CompileUnsupported for it
TEMPLATE_PARAMS = {
    "general/allowedrepos": [{"repos": ["gcr.io/mine"]}, {"repos": []}],
    "general/containerlimits": [{"cpu": "1", "memory": "2Gi"}],
    "general/containerresourceratios": [{"ratio": "2"}, {"ratio": "4.0"}],
    "general/httpsonly": [{}],
    "general/requiredlabels": [
        {"labels": [{"key": "gatekeeper", "allowedRegex": "^[a-z]+$"}]},
    ],
    "general/requiredprobes": [
        {"probes": ["livenessProbe", "readinessProbe"],
         "probeTypes": ["tcpSocket", "httpGet", "exec"]},
        {"probes": ["livenessProbe"], "probeTypes": ["httpGet"]},
    ],
    "pod-security-policy/allow-privilege-escalation": [{}],
    # annotation x container joins: compiled via token-space key
    # iteration under the container axis (rank-3 join) with split/
    # sprintf id-transforms; row-level safety flags cover the
    # annotations-is-actually-an-array corner
    "pod-security-policy/apparmor": [
        {"allowedProfiles": ["runtime/default"]},
        {"allowedProfiles": ["runtime/default", "localhost/x"]},
    ],
    "pod-security-policy/seccomp": [
        {"allowedProfiles": ["runtime/default"]},
        {"allowedProfiles": ["*"]},
    ],
    "pod-security-policy/capabilities": [
        {"allowedCapabilities": ["CHOWN"], "requiredDropCapabilities": ["ALL"]},
    ],
    "pod-security-policy/flexvolume-drivers": [
        {"allowedFlexVolumes": [{"driver": "example/cifs"}]},
        {"allowedFlexVolumes": []},
    ],
    "pod-security-policy/forbidden-sysctls": [
        {"forbiddenSysctls": ["kernel.shm_rmid_forced"]},
        {"forbiddenSysctls": ["net.*"]},
        {"forbiddenSysctls": ["*"]},
    ],
    "pod-security-policy/fsgroup": [
        {"rule": "MustRunAs", "ranges": [{"min": 1, "max": 10}]},
        {"rule": "MayRunAs", "ranges": [{"min": 1, "max": 1999}]},
        {"rule": "RunAsAny"},
    ],
    "pod-security-policy/host-namespaces": [{}],
    "pod-security-policy/host-network-ports": [
        {"hostNetwork": False, "min": 0, "max": 100},
        {"hostNetwork": True, "min": 80, "max": 8080},
    ],
    "pod-security-policy/privileged-containers": [{}],
    "pod-security-policy/proc-mount": [
        # "*" is not a testable param: get_allowed_proc_mount's clauses 3
        # and 4 both fire for it (conflicting outputs — an eval error in
        # OPA as well)
        {"procMount": "Default"}, {"procMount": "Unmasked"},
    ],
    "pod-security-policy/read-only-root-filesystem": [{}],
    "pod-security-policy/selinux": [
        {"allowedSELinuxOptions": [{"level": "s0"}]},
        {"allowedSELinuxOptions": [{"level": "s0", "role": "r"}]},
    ],
    "pod-security-policy/users": [
        {"runAsUser": {"rule": "MustRunAs", "ranges": [{"min": 1, "max": 10}]}},
        {"runAsUser": {"rule": "MustRunAsNonRoot"}},
        {"runAsUser": {"rule": "RunAsAny"}},
    ],
    "pod-security-policy/volumes": [
        {"volumes": ["configMap", "secret"]},
        {"volumes": ["*"]},
    ],
}

# outside the PRECISE subset -> compile as screens: over-approximating
# programs whose flagged pairs the driver re-checks via the interpreter
# (symbolic.InventoryDependent). The differential contract for screens
# is superset-ness, not equality.
SCREEN_TEMPLATES = {
    "general/uniqueingresshost": {},        # data.inventory join
    "general/uniqueserviceselector": {},    # data.inventory join
    "pod-security-policy/host-filesystem":  # volumes x volumeMounts join
        {"allowedHostPaths": [{"pathPrefix": "/tmp", "readOnly": True}]},
}


def _all_template_dirs():
    import glob as _glob

    dirs = []
    for src in sorted(_glob.glob(f"{LIB}/*/*/src.rego")):
        d = os.path.dirname(src)
        dirs.append(os.path.relpath(d, LIB))
    return dirs


def test_template_inventory_is_exhaustive():
    """Every library template is either differentially tested (precise)
    or registered as a screen template (superset-tested)."""
    known = set(TEMPLATE_PARAMS) | set(SCREEN_TEMPLATES)
    assert set(_all_template_dirs()) == known


@pytest.mark.parametrize(
    "tdir,params",
    [(t, p) for t, ps in sorted(TEMPLATE_PARAMS.items()) for p in ps],
    ids=lambda v: v if isinstance(v, str) else repr(v)[:40],
)
@pytest.mark.parametrize("use_jax", [False, True], ids=["np", "jax"])
def test_library_template_compiled(tdir, params, use_jax):
    assert_template_agrees(
        f"{LIB}/{tdir}/src.rego", params, reviews=ALL_PODS, use_jax=use_jax
    )


@pytest.mark.parametrize("tdir", sorted(SCREEN_TEMPLATES), ids=str)
def test_library_template_screens(tdir):
    """Screen templates compile (screen=True) and their counts are a
    SUPERSET of the oracle's on the pod corpus: wherever the oracle
    finds >=1 violation the screen must flag the review (pairs the
    screen flags get exact interpreter re-checks in the driver, so
    over-flagging is a perf cost, under-flagging a correctness bug)."""
    src = library_rego(tdir)
    params = SCREEN_TEMPLATES[tdir]
    want, interp, pkg = oracle_count(src, params, ALL_PODS)
    vocab, patterns, tables = make_env()
    mod = parse_module(src)
    rewrite_module(mod)
    env = CompilerEnv(vocab, patterns, tables)
    from gatekeeper_tpu.engine.programs import compile_program as _cp

    prog = _cp(env, [mod], params)
    assert prog.screen is True
    table = encode_token_table(ALL_PODS, vocab)
    patterns.sync()
    tables.sync()
    tok = {
        "spath": table.spath,
        "idx0": table.idx0,
        "idx1": table.idx1,
        "kind": table.kind,
        "vid": table.vid,
        "vnum": table.vnum,
    }
    ev = ProgramEvaluator(patterns, tables, use_jax=False)
    got = ev.eval_np(prog, tok, g=8)
    missed = [
        (i, int(got[i]), int(want[i]))
        for i in range(len(want))
        if want[i] > 0 and got[i] == 0
    ]
    assert not missed, f"screen under-approximates: {missed}"
