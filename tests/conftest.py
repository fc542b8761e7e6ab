"""Test configuration.

Forces JAX onto a virtual 8-device CPU platform BEFORE jax is imported
anywhere, so sharding tests exercise real multi-device code paths (and
__graft_entry__.dryrun_multichip gets its 8 devices) without a chip.
The chip itself is exercised by chip_smoke.py.
"""

import os
import sys

# force, not setdefault: tests run on the deterministic local 8-device
# CPU mesh even on a host whose environment selects the TPU
os.environ["JAX_PLATFORMS"] = "cpu"

# tests must exercise the real oracle/compile paths, not warm disk
# memos — and must not pollute the user-level cache dirs
os.environ["GATEKEEPER_TPU_NO_COMPILE_CACHE"] = "1"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REFERENCE = "/root/reference"


def reference_available() -> bool:
    return os.path.isdir(REFERENCE)


# -- shared stub external-data provider (docs/externaldata.md) --------------


class StubProviderServer:
    """In-process HTTP provider speaking the ProviderRequest/
    ProviderResponse protocol. Every outbound fetch is recorded in
    `requests` (a list of key lists) — the fetch COUNT is the batching
    contract the external-data tests pin. Behavior knobs:

      * `responder(key) -> item dict` — default echoes the key as its
        value, and keys containing "bad" get an error entry;
      * `fail = True` — respond 500 (provider outage);
      * `hang_s` — sleep before answering (tail-latency stall);
      * `system_error` — set the response-level systemError field.
    """

    def __init__(self):
        import json
        import threading
        import time
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        self.requests = []
        self.fail = False
        self.hang_s = 0.0
        self.system_error = ""
        self.responder = lambda key: (
            {"key": key, "error": "unsigned"}
            if "bad" in key
            else {"key": key, "value": f"ok:{key}"}
        )
        outer = self

        class _Handler(BaseHTTPRequestHandler):
            def do_POST(self):  # noqa: N802
                length = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(length) or b"{}")
                keys = ((body.get("request") or {}).get("keys")) or []
                outer.requests.append(list(keys))
                if outer.hang_s:
                    time.sleep(outer.hang_s)
                if outer.fail:
                    self.send_response(500)
                    self.end_headers()
                    return
                payload = json.dumps(
                    {
                        "apiVersion": "externaldata.gatekeeper.sh/v1alpha1",
                        "kind": "ProviderResponse",
                        "response": {
                            "items": [outer.responder(k) for k in keys],
                            "systemError": outer.system_error,
                        },
                    }
                ).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)

            def log_message(self, *args):
                pass

        self._httpd = ThreadingHTTPServer(("127.0.0.1", 0), _Handler)
        self.url = f"http://127.0.0.1:{self._httpd.server_address[1]}/validate"
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True
        )
        self._thread.start()

    @property
    def fetch_count(self) -> int:
        return len(self.requests)

    def provider_obj(self, name="stub-provider", **spec_overrides):
        spec = {
            "url": self.url,
            "timeout": 5,
            "failurePolicy": "Ignore",
            "cacheTTLSeconds": 300,
            "negativeCacheTTLSeconds": 300,
        }
        spec.update(spec_overrides)
        return {
            "apiVersion": "externaldata.gatekeeper.sh/v1alpha1",
            "kind": "Provider",
            "metadata": {"name": name},
            "spec": spec,
        }

    def stop(self):
        self._httpd.shutdown()
        self._thread.join(timeout=5)


def _stub_provider_impl():
    server = StubProviderServer()
    try:
        yield server
    finally:
        server.stop()


try:
    import pytest

    stub_provider = pytest.fixture(_stub_provider_impl)
except ImportError:  # pragma: no cover - conftest outside pytest
    pass
