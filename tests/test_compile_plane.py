"""Incremental compile plane suite (docs/compile.md).

What it pins:
  * the **fingerprint gate** — archived artifacts attested for a
    foreign machine fingerprint, tampered payloads, unknown schema
    versions, and unattested payloads are rejected with the right
    `program_store_rejected_total{reason}` label and NEVER copied into
    the store root, which is XLA's cache dir;
  * the **attest -> adopt roundtrip** — artifacts this machine produced
    are content-addressed into the store and re-adopted (hits) by an
    identical fingerprint, rejected by a different one; entries already
    in the root at adopt() are never attested (no relabelling);
  * **plan-diff recompiles** — churning N of K partitions compiles
    exactly N programs (`driver.program_compiles` asserted) while the
    K-N unchanged partitions carry their staged sets forward;
  * **mid-swap faults** — a `compile.swap` fault between shadow stage
    and atomic swap leaves the OLD sub-program serving (same cached
    object, swap counters unmoved) and the next restage lands clean;
  * the **compile_storm flight trigger** — restage backlog or a burst
    of restage failures captures one record embedding the `programs`
    source.

Runs in tier-1 and alone via `pytest -m compile` (numpy-mode TpuDriver:
no jit compiles, deterministic).
"""

import hashlib
import json
import os
import time

import pytest

from gatekeeper_tpu.compile import (
    SCHEMA_VERSION,
    ProgramStore,
    machine_fingerprint,
    store_from_env,
)
from gatekeeper_tpu.constraint import Backend, K8sValidationTarget, TpuDriver
from gatekeeper_tpu.faults import FAULTS, FaultError
from gatekeeper_tpu.metrics import MetricsRegistry
from gatekeeper_tpu.obs.flightrecorder import FlightRecorder
from gatekeeper_tpu.parallel.partition import PartitionDispatcher

pytestmark = pytest.mark.compile

TARGET = "admission.k8s.gatekeeper.sh"
PATH = f'hooks["{TARGET}"].violation'

# VECTORIZED required-labels shape; package renamed per kind so every
# template kind owns a distinct IR (distinct content hash)
_REGO_BASE = """package compileplaneN
violation[{"msg": msg}] {
    required := {key | key := input.parameters.labels[_]}
    provided := {key | input.review.object.metadata.labels[key]}
    missing := required - provided
    count(missing) > 0
    msg := sprintf("missing: %v", [missing])
}
"""


@pytest.fixture(autouse=True)
def _clean_faults():
    FAULTS.reset()
    yield
    FAULTS.reset()


def _add_kind(cl, kind, n, labels=("owner",)):
    cl.add_template({
        "apiVersion": "templates.gatekeeper.sh/v1beta1",
        "kind": "ConstraintTemplate",
        "metadata": {"name": kind.lower()},
        "spec": {
            "crd": {"spec": {"names": {"kind": kind}}},
            "targets": [{
                "target": TARGET,
                "rego": _REGO_BASE.replace(
                    "compileplaneN", f"compileplane{n}"
                ),
            }],
        },
    })
    _add_constraint(cl, kind, labels)


def _add_constraint(cl, kind, labels):
    cl.add_constraint({
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": kind,
        "metadata": {"name": f"c-{kind.lower()}"},
        "spec": {
            "match": {"kinds": [{"apiGroups": [""], "kinds": ["Pod"]}]},
            "parameters": {"labels": list(labels)},
        },
    })


def make_client(kinds):
    cl = Backend(TpuDriver(use_jax=False)).new_client(K8sValidationTarget())
    for n, kind in enumerate(kinds):
        _add_kind(cl, kind, n)
    return cl


def _key(kind):
    return f"{kind}/c-{kind.lower()}"


# -- plan-diff recompiles ----------------------------------------------------


def test_churn_n_of_k_partitions_compiles_exactly_n():
    """The acceptance contract: K=4 single-kind partitions staged, then
    2 of them churned (new parameters -> new program key) — exactly 2
    programs compile, exactly 2 subsets swap, the other 2 carry
    forward with zero restage."""
    kinds = ["CplA", "CplB", "CplC", "CplD"]
    cl = make_client(kinds)
    drv = cl._driver
    subsets = {k: frozenset([_key(k)]) for k in kinds}
    for k in kinds:
        assert cl.prepare_subset(subsets[k]) is True
    compiles0 = drv.program_compiles
    swaps0 = drv.subset_swaps
    carry0 = drv.subset_carryforwards
    # churn 2 of 4: replacing the constraint's parameters changes those
    # subsets' signatures (and program keys); the other 2 are untouched
    for k in kinds[:2]:
        _add_constraint(cl, k, labels=("team",))
    for k in kinds:
        assert cl.prepare_subset(subsets[k]) is True
    assert drv.program_compiles - compiles0 == 2
    assert drv.subset_swaps - swaps0 == 2
    assert drv.subset_carryforwards - carry0 == 2
    # and the counters surface through the debug/flightrecord view
    stats = drv.compile_plane_stats()
    assert stats["subset_swaps"] == drv.subset_swaps
    assert stats["subset_carryforwards"] == drv.subset_carryforwards


def test_unrelated_churn_keeps_subset_signatures_stable():
    """A subset's content signature covers ONLY its members: churn
    elsewhere in the corpus does not move it (the carry-forward
    license), while a member change does."""
    cl = make_client(["CplE", "CplF"])
    drv = cl._driver
    fs = frozenset([_key("CplE")])
    sig0 = drv.subset_signature(TARGET, fs)
    _add_kind(cl, "CplNew", 99)  # unrelated: new template + constraint
    assert drv.subset_signature(TARGET, fs) == sig0
    _add_constraint(cl, "CplE", labels=("tier",))  # member change
    assert drv.subset_signature(TARGET, fs) != sig0


# -- mid-swap fault ----------------------------------------------------------


def test_mid_swap_fault_leaves_old_program_serving():
    """A fault at `compile.swap` (between shadow stage and the atomic
    swap) must leave the OLD sub-program cached and serving: same
    object, swap counters unmoved. After disarm the restage lands and
    the new set answers with the new parameters."""
    cl = make_client(["CplG"])
    drv = cl._driver
    fs = frozenset([_key("CplG")])
    assert cl.prepare_subset(fs) is True
    old_cs = drv._cset_sub[(TARGET, fs)]
    swaps0 = drv.subset_swaps
    gen0 = drv.swap_generation()

    pod = {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {"name": "p", "namespace": "ns",
                     "labels": {"team": "core"}},
        "spec": {"containers": [{"name": "c", "image": "nginx"}]},
    }
    # violates {"labels": ["owner"]} (old params), satisfies ["team"]
    (before,) = cl.review_many_subset([pod], fs)
    assert len(before.by_target[TARGET].results) == 1

    _add_constraint(cl, "CplG", labels=("team",))
    FAULTS.arm("compile.swap", mode="error")
    with pytest.raises(FaultError):
        drv.prepare_subset(PATH, fs)
    assert FAULTS.fired("compile.swap") == 1
    # old entry intact: same object, nothing swapped
    assert drv._cset_sub[(TARGET, fs)] is old_cs
    assert drv.subset_swaps == swaps0
    assert drv.swap_generation() == gen0
    # disarm: the retry stages and swaps clean, new params now serve
    FAULTS.reset()
    assert drv.prepare_subset(PATH, fs) is True
    assert drv.subset_swaps == swaps0 + 1
    assert drv._cset_sub[(TARGET, fs)] is not old_cs
    (after,) = cl.review_many_subset([pod], fs)
    assert after.by_target[TARGET].results == []


# -- the fingerprint gate ----------------------------------------------------


def _write_artifact(root, payload, fingerprint, schema=SCHEMA_VERSION,
                    filename="xla_cache_entry", tamper=False, meta=True):
    art = os.path.join(root, "artifacts")
    os.makedirs(art, exist_ok=True)
    sha = hashlib.sha256(payload).hexdigest()
    with open(os.path.join(art, f"{sha}.bin"), "wb") as f:
        f.write(payload + (b"-tampered" if tamper else b""))
    if meta:
        with open(os.path.join(art, f"{sha}.meta.json"), "w") as f:
            json.dump({
                "schema": schema,
                "sha256": sha,
                "filename": filename,
                "fingerprint": fingerprint,
                "jaxlib": "none",
                "created": 0,
            }, f)
    return sha


def _cache_entries(root):
    return sorted(
        n for n in os.listdir(root)
        if os.path.isfile(os.path.join(root, n))
    )


def test_fingerprint_gate_rejects_and_counts_never_loads(tmp_path):
    """One artifact per reject reason, plus one valid one: adopt()
    copies ONLY the valid artifact into the root (XLA's cache dir) and
    counts every rejection under its closed-set reason label. Entries
    already in the root and non-payload files in the archive are left
    alone and not counted."""
    root = str(tmp_path / "store")
    _write_artifact(root, b"good-artifact", "fp-me", filename="prog-good")
    _write_artifact(root, b"foreign-artifact", "fp-other",
                    filename="prog-foreign")
    _write_artifact(root, b"tampered-artifact", "fp-me", tamper=True,
                    filename="prog-tampered")
    _write_artifact(root, b"future-artifact", "fp-me",
                    schema=SCHEMA_VERSION + 1, filename="prog-future")
    _write_artifact(root, b"orphan-payload", "fp-me", meta=False)
    with open(os.path.join(root, "artifacts", "canary-0.json"), "w") as f:
        f.write("{}")
    with open(os.path.join(root, "prog-cached"), "wb") as f:
        f.write(b"cache-entry")

    reg = MetricsRegistry()
    store = ProgramStore(root, metrics=reg, fingerprint="fp-me")
    res = {"adopted": 1, "rejected": 4}
    assert store.rejected == {
        "fingerprint_mismatch": 1,
        "corrupt": 1,
        "schema": 1,
        "unattested": 1,
    }
    assert store.hits == res["adopted"]
    # ONLY the valid artifact was added to the dir XLA loads from
    assert _cache_entries(root) == ["prog-cached", "prog-good"]
    with open(os.path.join(root, "prog-good"), "rb") as f:
        assert f.read() == b"good-artifact"
    # counted under the reason label on the shared registry
    counters = reg.snapshot()["counters"]
    for reason, n in store.rejected.items():
        key = f'program_store_rejected_total{{reason="{reason}"}}'
        assert counters.get(key) == n
    assert counters.get("program_store_hits_total") == 1
    # the adoption table carries the verdicts for /debug/programs
    table = store.table()
    assert {r["reason"] for r in table if r["status"] == "rejected"} == {
        "fingerprint_mismatch", "corrupt", "schema", "unattested",
    }


def test_attest_roundtrip_same_fingerprint_adopts_foreign_rejects(
    tmp_path,
):
    """An artifact this machine attested is re-adopted by an identical
    fingerprint (restart survival) and rejected by a different one,
    which does not attest it as its own either."""
    root = str(tmp_path / "store")
    a = ProgramStore(root, fingerprint="fp-a")
    with open(os.path.join(a.root, "prog-0"), "wb") as f:
        f.write(b"compiled-on-a")
    assert a.attest() == 1
    assert a.saves == 1
    assert a.attest() == 0  # incremental: nothing new

    a2 = ProgramStore(root, fingerprint="fp-a")
    assert a2.hits == 1
    assert a2.rejected["fingerprint_mismatch"] == 0

    b = ProgramStore(root, fingerprint="fp-b")
    assert b.rejected["fingerprint_mismatch"] == 1
    assert b.hits == 0
    assert b.attest() == 0


def test_attest_claims_only_entries_compiled_after_adopt(tmp_path):
    """Cache entries found in the root at adopt() (another host's, on a
    shared volume) are never attested under this machine's fingerprint;
    entries that appear afterwards (this process's compiles) are."""
    root = str(tmp_path / "store")
    os.makedirs(root)
    with open(os.path.join(root, "prog-elsewhere"), "wb") as f:
        f.write(b"compiled-elsewhere")
    store = ProgramStore(root, fingerprint="fp-me")
    with open(os.path.join(root, "prog-mine"), "wb") as f:
        f.write(b"compiled-here")
    assert store.attest() == 1
    metas = []
    for n in os.listdir(store.artifacts_dir):
        if n.endswith(".meta.json"):
            with open(os.path.join(store.artifacts_dir, n)) as f:
                metas.append(json.load(f))
    assert [(m["filename"], m["fingerprint"]) for m in metas] == [
        ("prog-mine", "fp-me"),
    ]


def test_machine_fingerprint_and_store_from_env(tmp_path, monkeypatch):
    fp = machine_fingerprint(probe_device=False)
    assert fp["digest"] == machine_fingerprint(probe_device=False)["digest"]
    for k in ("platform", "cpu_flags", "jaxlib", "device_kind"):
        assert k in fp
    # the tier-1 kill switch (tests/conftest.py sets it globally)
    monkeypatch.setenv("GATEKEEPER_TPU_NO_COMPILE_CACHE", "1")
    assert store_from_env() is None
    monkeypatch.delenv("GATEKEEPER_TPU_NO_COMPILE_CACHE")
    monkeypatch.setenv(
        "JAX_COMPILATION_CACHE_DIR", str(tmp_path / "envstore")
    )
    store = store_from_env()
    assert store is not None
    assert store.root == str(tmp_path / "envstore")


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(tmp_path, monkeypatch, from_env):
    """The effective jax_compilation_cache_dir is JAX_COMPILATION_CACHE_DIR
    when set, else the fixed in-checkout `.cache/jax` — never a path
    the program invents."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    import gatekeeper_tpu.constraint.tpudriver as td
    from gatekeeper_tpu.compile import compile_cache_dir

    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = str(tmp_path / "jaxcache")
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(checkout, ".cache", "jax")
    assert compile_cache_dir() == want
    monkeypatch.delenv("GATEKEEPER_TPU_NO_COMPILE_CACHE")
    monkeypatch.setattr(td, "_CACHE_ENABLED", False)
    monkeypatch.setattr(td, "_STORE", None)
    names = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_persistent_cache_min_compile_time_secs",
    )
    saved = {n: getattr(jax.config, n) for n in names}
    try:
        store = td._enable_compile_cache()
        assert store is not None
        assert jax.config.jax_compilation_cache_dir == want
        assert store.root == want
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
        cc.reset_cache()


# -- dispatcher integration --------------------------------------------------


def test_dispatcher_programs_table_and_churn_staging():
    """/debug/programs' source: per-partition signature/staged/ready
    rows, and after a template ingest the new kind compiles exactly
    once while staging converges back to every-partition-staged."""
    metrics = MetricsRegistry()
    cl = make_client(["CplH", "CplI", "CplJ", "CplK"])
    drv = cl._driver
    disp = PartitionDispatcher(cl, TARGET, k=2, metrics=metrics)
    try:
        plan = disp.plan()
        for p in plan.partitions:
            assert disp.ensure_staged(p)
        doc = disp.programs_table()
        assert doc["plane"] == "validation"
        assert doc["staging_in_flight"] == 0
        rows = doc["partitions"]
        assert len(rows) == 2
        assert all(r["staged"] and r["ready"] for r in rows)
        assert all(r["signature"] for r in rows)
        assert {r["signature"] for r in rows} == {
            drv.subset_signature(TARGET, p.subset)
            for p in plan.partitions
        }
        assert doc["compile_plane"]["subset_swaps"] == drv.subset_swaps

        compiles0 = drv.program_compiles
        _add_kind(cl, "CplIngest", 77)  # one new template kind
        plan2 = disp.plan()
        for p in plan2.partitions:
            assert disp.ensure_staged(p)
        # exactly the ONE new kind compiled; existing programs were
        # reused from the shared cache whatever the re-split did
        assert drv.program_compiles - compiles0 == 1
        doc2 = disp.programs_table()
        assert all(
            r["staged"] and r["ready"] for r in doc2["partitions"]
        )
    finally:
        disp.close()


# -- compile_storm flight trigger --------------------------------------------


def _wait_records(rec, timeout_s=3.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if rec.records():
            return rec.records()
        time.sleep(0.01)
    return rec.records()


def test_compile_storm_fires_on_backlog_and_embeds_programs_source():
    rec = FlightRecorder(
        min_interval_s=0.0, debounce_s=0.0,
        compile_storm_threshold=3,
    )
    rec.add_source("programs", lambda: {"store": {"entries": 2}})
    # a recompile backlog at the threshold fires immediately
    rec.note_restage_failure(plane="validation", backlog=3)
    records = _wait_records(rec)
    assert records, "compile_storm backlog trigger did not capture"
    record = records[-1]
    assert record["trigger"] == "compile_storm"
    ctx = record["triggers"][0]["context"]
    assert ctx["backlog"] == 3 and ctx["plane"] == "validation"
    assert record["state"]["programs"] == {"store": {"entries": 2}}
    rec.stop()


def test_compile_storm_fires_on_restage_failure_burst():
    rec = FlightRecorder(
        min_interval_s=0.0, debounce_s=0.0,
        compile_storm_threshold=3, compile_storm_window_s=30.0,
    )
    rec.note_restage_failure(backlog=0)
    rec.note_restage_failure(backlog=0)
    assert not rec.records()
    rec.note_restage_failure(backlog=0)  # third failure in the window
    records = _wait_records(rec)
    assert records and records[-1]["trigger"] == "compile_storm"
    rec.stop()
