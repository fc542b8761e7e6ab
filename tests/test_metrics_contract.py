"""Metrics-contract test: every metric name the code emits must be
documented in docs/metrics.md, and every documented Prometheus series
must still exist in the code — both directions, so the doc can be
trusted as the dashboard-building contract and removed metrics cannot
leave stale doc rows behind.

Scope: literal first arguments of MetricsRegistry record/gauge/observe/
try_observe/timed calls, the literal family (third argument) of the
obs.stage timer, plus the TpuDriver._count counter helper, anywhere
under gatekeeper_tpu/. Dynamically-named metrics would evade the scan — the
codebase deliberately has none (one call site per measurement,
pkg/metrics/record.go style), and this test is what keeps it that way.
"""

import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
PKG = os.path.join(HERE, os.pardir, "gatekeeper_tpu")
DOC = os.path.join(HERE, os.pardir, "docs", "metrics.md")

# .record("name" / .gauge("name" / .observe("name" / .try_observe(
# "name" / .timed("name" (whitespace/newlines after the paren allowed),
# stage(<name>, <metrics>, "family", and the driver's _count("name")
# counter helper
EMIT_RE = re.compile(
    r'\.(?:record|gauge|observe|try_observe|timed)\(\s*"([a-z][a-z0-9_]*)"'
)
STAGE_RE = re.compile(
    r'\bstage\(\s*[^,()]+,\s*[^,()]+,\s*"([a-z][a-z0-9_]*)"'
)
COUNT_HELPER_RE = re.compile(r'self\._count\(\s*"([a-z][a-z0-9_]*)"')

# doc rows: | `name` | <type> | ... with a real metric type in the
# second column (the engine-stats table has no type column and is
# intentionally out of scope)
DOC_RE = re.compile(
    r"^\|\s*`([a-z][a-z0-9_]*)`\s*\|\s*"
    r"(counter|gauge|distribution|histogram|summary)\s*\|",
    re.M,
)


def emitted_metric_names():
    names = {}
    for root, _dirs, files in os.walk(PKG):
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(root, fname)
            with open(path) as f:
                src = f.read()
            rel = os.path.relpath(path, os.path.dirname(PKG))
            for rx in (EMIT_RE, STAGE_RE, COUNT_HELPER_RE):
                for m in rx.finditer(src):
                    names.setdefault(m.group(1), set()).add(rel)
    return names


def documented_metric_names():
    with open(DOC) as f:
        text = f.read()
    return {m.group(1): m.group(2) for m in DOC_RE.finditer(text)}


def test_scan_is_alive():
    """Guard the guard: if the regexes rot, the contract test would
    vacuously pass on two empty sets."""
    emitted = emitted_metric_names()
    documented = documented_metric_names()
    assert len(emitted) >= 20, sorted(emitted)
    assert len(documented) >= 20, sorted(documented)
    # spot-check both scanners on known-stable names
    assert "request_count" in emitted
    assert "request_count" in documented
    assert "program_compile_seconds" in emitted
    assert "driver_cold_batches_total" in emitted  # _count helper path
    assert "client_phase_seconds" in emitted  # obs.stage path


def test_every_emitted_metric_is_documented():
    emitted = emitted_metric_names()
    documented = documented_metric_names()
    undocumented = {
        name: sorted(files)
        for name, files in emitted.items()
        if name not in documented
    }
    assert not undocumented, (
        "metrics emitted in code but missing from docs/metrics.md: "
        f"{undocumented}"
    )


def test_every_documented_metric_is_emitted():
    emitted = emitted_metric_names()
    documented = documented_metric_names()
    stale = sorted(set(documented) - set(emitted))
    assert not stale, (
        "docs/metrics.md documents metrics no longer emitted anywhere "
        f"under gatekeeper_tpu/: {stale}"
    )


# exposition-lint grammar: one sample line — name{labels} value, with
# an optional OpenMetrics exemplar (`# {label="v"} value [ts]`) tail
_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9.eE+-]+"
    r'( # \{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"\}'
    r" -?[0-9.eE+-]+( -?[0-9.eE+-]+)?)?$"
)
_EXEMPLAR_RE = re.compile(
    r' # \{trace_id="[0-9a-zA-Z]+"\} -?[0-9.eE+-]+ -?[0-9.eE+-]+$'
)


def test_exposition_validity_lint():
    """Exposition lint: a registry exercising every series shape —
    counters, gauges, histograms (with an exemplar), summaries,
    min/max companions, a multi-label-set family, and the cardinality
    guard's drop counter — renders to text with (a) exactly one
    # HELP and one # TYPE per family, HELP-before-TYPE-before-samples,
    (b) every sample line matching the exposition grammar, and (c)
    every exemplar in OpenMetrics syntax on a _bucket line."""
    from gatekeeper_tpu.metrics import MetricsRegistry

    reg = MetricsRegistry(max_series_per_family=4)
    reg.describe("request_count", "requests handled")
    for status in ("allow", "deny"):
        reg.record("request_count", 2, admission_status=status)
    reg.gauge("device_breaker_state", 1, plane="validation")
    reg.observe("request_duration_seconds", 0.004,
                exemplar="4bf92f3577b34da6a3ce929d0e0e4736",
                admission_status="allow")
    reg.observe("request_duration_seconds", 7.5,
                admission_status="deny")
    reg.set_buckets("webhook_batch_size", ())
    reg.observe("webhook_batch_size", 17)  # bucketless summary
    for i in range(9):  # trips the 4-series cap -> drop counter series
        reg.record("constraint_device_seconds_total", 0.1,
                   kind="K", name=f"c{i}", partition="0")
    text = reg.prometheus_text()
    lines = text.splitlines()
    assert lines, text

    helps = [ln.split()[2] for ln in lines if ln.startswith("# HELP")]
    types = [ln.split()[2] for ln in lines if ln.startswith("# TYPE")]
    assert len(helps) == len(set(helps)), "duplicate # HELP lines"
    assert len(types) == len(set(types)), "duplicate # TYPE lines"
    assert set(helps) == set(types)

    seen_meta = set()
    for ln in lines:
        if ln.startswith("# HELP"):
            seen_meta.add(ln.split()[2])
            continue
        if ln.startswith("# TYPE"):
            assert ln.split()[2] in seen_meta, f"TYPE before HELP: {ln}"
            continue
        assert _SAMPLE_RE.match(ln), f"unparseable sample line: {ln!r}"
        family = ln.split("{")[0].split(" ")[0]
        base = family
        for suffix in ("_bucket", "_count", "_sum", "_min", "_max"):
            if family.endswith(suffix):
                base = family[: -len(suffix)]
        assert any(
            m in (family, base) or family.startswith(m)
            for m in seen_meta
        ), f"sample before its HELP: {ln!r}"
        if " # {" in ln:
            assert "_bucket{" in ln, f"exemplar off a bucket line: {ln!r}"
            assert _EXEMPLAR_RE.search(ln), f"bad exemplar syntax: {ln!r}"

    # the exemplar actually rendered, and the guard's drop counter too
    assert any(_EXEMPLAR_RE.search(ln) for ln in lines)
    assert any(
        ln.startswith(
            "gatekeeper_metrics_dropped_series_total"
        )
        for ln in lines
    ), text
