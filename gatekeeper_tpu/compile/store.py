"""Content-addressed program store beside the persistent compile cache.

Layout under the store root, which is `compile_cache_dir()`
(JAX_COMPILATION_CACHE_DIR, else `<checkout>/.cache/jax`) and is also
XLA's `jax_compilation_cache_dir`::

    <root>/
      <XLA cache entries>    # written and read by XLA directly
      artifacts/
        <sha256>.bin         # content-addressed copy of an entry
        <sha256>.meta.json   # attestation: schema, fingerprint, sha256,
                             # original cache filename, jaxlib, created

`adopt()` is the gate on the archive: it walks `artifacts/`, verifies
each payload's sha256 against its attestation, and copies only
fingerprint-matching artifacts into the root. Everything else is
rejected-and-counted (`program_store_rejected_total{reason}` with
reason ∈ fingerprint_mismatch | corrupt | schema | unattested) and never
copied. The gate cannot keep XLA from reading an entry another host
wrote into the root itself: one cache directory may be shared only by
identical hosts.

`attest()` is the reverse edge: it content-addresses the entries that
appeared in the root after `adopt()` (this process's own compiles) with
this machine's fingerprint. Entries already in the root at `adopt()` are
never attested, so an entry some other host wrote is not relabelled as
this machine's.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import platform
import shutil
import threading
import time
from typing import Any, Dict, List, Optional, Union

_log = logging.getLogger("gatekeeper_tpu.compile")

SCHEMA_VERSION = 1

_META_SUFFIX = ".meta.json"
_PAYLOAD_SUFFIX = ".bin"

# reasons are a closed set so the metric label can't explode and the
# docs/metrics.md row can enumerate them
REJECT_REASONS = ("fingerprint_mismatch", "corrupt", "schema", "unattested")


def _cpu_flags_digest() -> str:
    """Stable digest of the CPU feature set (the ISA surface an AOT
    artifact may depend on). /proc/cpuinfo `flags` on x86, `Features`
    on arm64; falls back to the machine string off-Linux."""
    flags = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                low = line.lower()
                if low.startswith("flags") or low.startswith("features"):
                    flags = " ".join(
                        sorted(set(line.split(":", 1)[1].split()))
                    )
                    break
    except OSError:
        pass
    if not flags:
        flags = platform.processor() or platform.machine() or "unknown"
    return hashlib.sha256(flags.encode()).hexdigest()[:16]


def machine_fingerprint(probe_device: bool = True) -> Dict[str, str]:
    """Identity of the artifact-consuming machine: platform + CPU
    feature set + jaxlib version + accelerator kind, plus the sha256
    `digest` over all components. `probe_device=False` skips the JAX
    device probe (it can trigger backend init) for device-free tests."""
    comp: Dict[str, str] = {
        "platform": f"{platform.system()}-{platform.machine()}",
        "cpu_flags": _cpu_flags_digest(),
        "jaxlib": "none",
        "device_kind": "none",
    }
    try:
        import jaxlib  # type: ignore

        comp["jaxlib"] = str(getattr(jaxlib, "__version__", "unknown"))
    except Exception:
        pass
    if probe_device:
        try:
            import jax

            devs = jax.devices()
            if devs:
                comp["device_kind"] = str(
                    getattr(devs[0], "device_kind", devs[0].platform)
                )
        except Exception:
            pass
    comp["digest"] = hashlib.sha256(
        json.dumps(
            {k: v for k, v in comp.items() if k != "digest"},
            sort_keys=True,
        ).encode()
    ).hexdigest()
    return comp


def _sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


class ProgramStore:
    """Fingerprint-gated wrapper around the persistent compile cache.

    Thread-safe; one instance per process (the driver holds it). The
    `fingerprint` parameter accepts a full component dict or a bare
    digest string — the latter is the device-free test override."""

    def __init__(
        self,
        root: str,
        metrics: Optional[Any] = None,
        fingerprint: Optional[Union[Dict[str, str], str]] = None,
        replica: Optional[str] = None,
        adopt: bool = True,
        probe_device: bool = True,
    ):
        self.root = os.path.abspath(os.path.expanduser(root))
        self.metrics = metrics
        self.replica = replica
        fp = fingerprint
        if fp is None:
            fp = machine_fingerprint(probe_device=probe_device)
        if isinstance(fp, str):
            fp = {"digest": fp}
        self.fingerprint: Dict[str, str] = dict(fp)
        self.fp_digest: str = self.fingerprint["digest"]
        self.artifacts_dir = os.path.join(self.root, "artifacts")
        os.makedirs(self.artifacts_dir, exist_ok=True)
        self._lock = threading.Lock()
        # cache filename -> sha256, for entries of THIS fingerprint
        # already attested (so attest() is incremental)
        self._attested: Dict[str, str] = {}
        # root entries present at adopt() and not adopted by it: of
        # unknown origin, so attest() never claims them
        self._preexisting: set = set()
        self.hits = 0
        self.misses = 0
        self.saves = 0
        self.rejected: Dict[str, int] = {r: 0 for r in REJECT_REASONS}
        # last adopt() verdict per artifact, for /debug/programs and the
        # compile_storm flight record
        self._rows: List[Dict[str, Any]] = []
        if adopt:
            self.adopt()

    # ------------------------------------------------------------------
    # counters (one literal call site per metric — the metrics-contract
    # scan in tests/test_metrics_contract.py keys on these)

    def _note_hit(self) -> None:
        self.hits += 1
        if self.metrics is not None:
            self.metrics.record("program_store_hits_total", 1)

    def note_miss(self) -> None:
        """Called by the driver when a program had to be compiled (no
        adoptable artifact covered it)."""
        with self._lock:
            self.misses += 1
        if self.metrics is not None:
            self.metrics.record("program_store_misses_total", 1)

    def _note_save(self) -> None:
        self.saves += 1
        if self.metrics is not None:
            self.metrics.record("program_store_saves_total", 1)

    def _note_reject(self, reason: str) -> None:
        self.rejected[reason] = self.rejected.get(reason, 0) + 1
        if self.metrics is not None:
            self.metrics.record(
                "program_store_rejected_total", 1, reason=reason
            )

    def _note_entries(self, n: int) -> None:
        if self.metrics is not None:
            self.metrics.gauge("program_store_entries", n)

    # ------------------------------------------------------------------

    def adopt(self) -> Dict[str, int]:
        """Validate every stored artifact and copy the ones attested
        for THIS machine into the root (XLA's cache dir).
        Returns {adopted, rejected} counts for this pass. Never raises:
        a broken artifact is a rejection, not an exception."""
        adopted = 0
        rejected = 0
        rows: List[Dict[str, Any]] = []
        with self._lock:
            try:
                names = sorted(os.listdir(self.artifacts_dir))
            except OSError:
                names = []
            metas = [n for n in names if n.endswith(_META_SUFFIX)]
            claimed = set()
            for meta_name in metas:
                meta_path = os.path.join(self.artifacts_dir, meta_name)
                sha_from_name = meta_name[: -len(_META_SUFFIX)]
                row: Dict[str, Any] = {
                    "artifact": sha_from_name[:12],
                    "status": "",
                    "reason": "",
                }
                verdict = self._validate_locked(
                    meta_path, sha_from_name, row
                )
                claimed.add(sha_from_name + _PAYLOAD_SUFFIX)
                if verdict is None:
                    rejected += 1
                else:
                    filename, payload = verdict
                    dst = os.path.join(self.root, filename)
                    try:
                        if not os.path.exists(dst):
                            shutil.copyfile(payload, dst)
                        self._attested[filename] = sha_from_name
                        adopted += 1
                        row["status"] = "adopted"
                        self._note_hit()
                    except OSError as e:
                        rejected += 1
                        row["status"] = "rejected"
                        row["reason"] = "corrupt"
                        row["error"] = str(e)
                        self._note_reject("corrupt")
                rows.append(row)
            # payloads with no attestation are never copied (other
            # files here, e.g. the integrity plane's canary sidecars,
            # are not payloads)
            for n in names:
                if not n.endswith(_PAYLOAD_SUFFIX) or n in claimed:
                    continue
                rejected += 1
                rows.append({
                    "artifact": n[:12],
                    "status": "rejected",
                    "reason": "unattested",
                })
                self._note_reject("unattested")
            try:
                self._preexisting.update(
                    n for n in os.listdir(self.root)
                    if n not in self._attested
                )
            except OSError:
                pass
            self._rows = rows
            self._note_entries(len(self._attested))
        return {"adopted": adopted, "rejected": rejected}

    def _validate_locked(self, meta_path, sha_from_name, row):
        """One artifact through the gate. Returns (filename, payload
        path) when loadable on THIS machine, else None after counting
        the rejection and filling `row`."""
        try:
            with open(meta_path) as f:
                meta = json.load(f)
            if not isinstance(meta, dict):
                raise ValueError("meta is not an object")
        except Exception:
            row["status"] = "rejected"
            row["reason"] = "corrupt"
            self._note_reject("corrupt")
            return None
        if meta.get("schema") != SCHEMA_VERSION:
            row["status"] = "rejected"
            row["reason"] = "schema"
            self._note_reject("schema")
            return None
        sha = meta.get("sha256")
        filename = meta.get("filename")
        fp = meta.get("fingerprint")
        if (
            not isinstance(sha, str)
            or not isinstance(filename, str)
            or not isinstance(fp, str)
            or sha != sha_from_name
            or os.path.basename(filename) != filename
        ):
            row["status"] = "rejected"
            row["reason"] = "schema"
            self._note_reject("schema")
            return None
        row["filename"] = filename
        row["fingerprint"] = fp[:12]
        payload = os.path.join(
            self.artifacts_dir, sha + _PAYLOAD_SUFFIX
        )
        try:
            actual = _sha256_file(payload)
        except OSError:
            actual = ""
        if actual != sha:
            row["status"] = "rejected"
            row["reason"] = "corrupt"
            self._note_reject("corrupt")
            return None
        # the fingerprint gate proper: content is intact but was
        # compiled by a different machine class — never hand it to XLA
        if fp != self.fp_digest:
            row["status"] = "rejected"
            row["reason"] = "fingerprint_mismatch"
            self._note_reject("fingerprint_mismatch")
            return None
        return filename, payload

    def attest(self) -> int:
        """Content-address the XLA cache entries that appeared since
        adopt() and write their attestation. Returns the number of
        newly attested artifacts."""
        new = 0
        with self._lock:
            try:
                names = sorted(os.listdir(self.root))
            except OSError:
                return 0
            for filename in names:
                if filename in self._attested or filename in self._preexisting:
                    continue
                src = os.path.join(self.root, filename)
                if not os.path.isfile(src):
                    continue
                try:
                    sha = _sha256_file(src)
                    payload = os.path.join(
                        self.artifacts_dir, sha + _PAYLOAD_SUFFIX
                    )
                    if not os.path.exists(payload):
                        shutil.copyfile(src, payload)
                    meta = {
                        "schema": SCHEMA_VERSION,
                        "sha256": sha,
                        "filename": filename,
                        "fingerprint": self.fp_digest,
                        "jaxlib": self.fingerprint.get("jaxlib", "none"),
                        "created": time.time(),
                    }
                    tmp = os.path.join(
                        self.artifacts_dir,
                        f".{sha}{_META_SUFFIX}.tmp",
                    )
                    with open(tmp, "w") as f:
                        json.dump(meta, f, sort_keys=True)
                    os.replace(
                        tmp,
                        os.path.join(
                            self.artifacts_dir, sha + _META_SUFFIX
                        ),
                    )
                except OSError as e:
                    _log.warning(
                        "program store: attest failed for %s: %s",
                        filename, e,
                    )
                    continue
                self._attested[filename] = sha
                new += 1
                self._note_save()
            self._note_entries(len(self._attested))
        return new

    # ------------------------------------------------------------------
    # introspection (for /debug/programs and the flight recorder)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "root": self.root,
                "fingerprint": self.fp_digest,
                "fingerprint_components": {
                    k: v
                    for k, v in self.fingerprint.items()
                    if k != "digest"
                },
                "entries": len(self._attested),
                "hits": self.hits,
                "misses": self.misses,
                "saves": self.saves,
                "rejected": dict(self.rejected),
            }

    def table(self) -> List[Dict[str, Any]]:
        """Per-artifact adoption verdicts from the last adopt() pass."""
        with self._lock:
            return [dict(r) for r in self._rows]


# the checkout this package runs from; the default cache lives inside it
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def compile_cache_dir() -> str:
    """Where compiled programs go: JAX_COMPILATION_CACHE_DIR when set,
    else the fixed, git-ignored `<checkout>/.cache/jax`."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _CHECKOUT, ".cache", "jax"
    )


def store_from_env(
    metrics: Optional[Any] = None,
    replica: Optional[str] = None,
) -> Optional[ProgramStore]:
    """Build the process store over compile_cache_dir(), which is also
    XLA's cache dir. GATEKEEPER_TPU_NO_COMPILE_CACHE=1 -> None (set by
    tests/conftest.py so tier-1 never touches disk)."""
    if os.environ.get("GATEKEEPER_TPU_NO_COMPILE_CACHE") == "1":
        return None
    root = compile_cache_dir()
    try:
        return ProgramStore(root, metrics=metrics, replica=replica)
    except OSError as e:
        _log.warning("program store unavailable at %s: %s", root, e)
        return None
