"""Bench trajectory gate: diff two bench runs and flag regressions.

BENCH_r01..r05 accumulated as unjudged history — nothing compared run
N to run N-1, so a 2x p99 regression would merge silently as "the new
baseline". This tool is the missing gate:

    python bench_compare.py BASE CANDIDATE [--threshold 0.20]

BASE/CANDIDATE are either full bench JSON artifacts (BENCH_*.json,
`bench_webhook.py --ladder` output, a soak report) or raw captured run
logs containing a `SUMMARY:` line (gatekeeper_tpu/summary.py contract
— truncated captures still compare on their summaries). The two docs
are flattened to comparable metric paths and judged directionally:

  * latency (`p50_ms`/`p99_ms`) and `dispatch_efficiency` regress when
    they RISE beyond the threshold (more milliseconds; more of the
    corpus dispatched per request = pruning got worse);
  * throughput (`throughput_rps`) and `slo_attainment`/
    `cache_hit_rate` regress when they FALL beyond it.

Output: one JSON report (regressions / improvements / unchanged
counts, worst offender first) plus a human table on stderr; exit code
1 when any regression crossed the threshold — wire it after a bench
run and the trajectory is judged instead of archived.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# metric leaf names worth judging, with regression direction:
# +1 = higher is worse (latency, dispatched rows), -1 = lower is worse
WATCHED: Dict[str, int] = {
    "p50_ms": +1,
    "p99_ms": +1,
    "worst_window_p99_ms": +1,
    "dispatch_efficiency": +1,
    # pruning width: more partitions touched per batch = less pruning
    "partitions_touched_p50": +1,
    "partitions_touched_max": +1,
    "shed_rate": +1,
    "cold_fetch_amplification": +1,
    # incremental compile plane: slower ingest-to-serve or ANY
    # degraded/5xx during an ingest wave is a regression
    "ingest_to_serve_ms": +1,
    "degraded_dispatches": +1,
    "http_5xx": +1,
    "throughput_rps": -1,
    "slo_attainment": -1,
    # live SLO plane (obs/slo.py): higher saturation at the same load
    # = less headroom for the autoscaler (the --slo lane's headline)
    "saturation": +1,
    "cache_hit_rate": -1,
    # corpus static analysis (ISSUE 15): fewer statically-excluded
    # dead rows = the corpus pass stopped proving the seeded dead
    # constraints (pruning regression); more corpus diagnostics = new
    # cross-plane findings in the bench corpus
    "rows_excluded_static": -1,
    "corpus_diagnostics": +1,
    # IR static analysis (ISSUE 16): fewer dead token slots dropped by
    # the feature-liveness mask = the IR pass stopped proving columns
    # dead (host-encode cost regression)
    "columns_skipped_static": -1,
    # admission scheduler (--sched lane): the worst per-tenant
    # attainment under the deadline policy dropping = a quota/EDF
    # regression; fewer predictive sheds under the same overload = the
    # scheduler fell back to blind tail-drops
    "tenant_attainment_min": -1,
    "predicted_miss_shed": -1,
    # verdict-integrity plane (--integrity lane): a rising shadow
    # divergence rate means fused verdicts drift from the host oracle;
    # rising canary overhead means the packed rows stopped riding free
    # padding slots (the ≤3% p50 contract) — both up-bad
    "divergence_rate": +1,
    "canary_overhead_frac": +1,
}

# context keys that make a row's path stable across runs (rungs and
# phases are lists — a bare index would misalign when a rung is
# skipped by a time budget)
_KEY_FIELDS = ("constraints", "phase", "concurrency", "violating",
               "partition", "mode", "replicas", "wave")


def _flatten(node: Any, path: str, out: Dict[str, float]) -> None:
    if isinstance(node, dict):
        ctx = ".".join(
            f"{k}={node[k]}" for k in _KEY_FIELDS if k in node
        )
        base = f"{path}[{ctx}]" if ctx else path
        for k, v in node.items():
            if k in WATCHED and isinstance(v, (int, float)) and not (
                isinstance(v, bool)
            ):
                out[f"{base}.{k}"] = float(v)
            elif k in WATCHED and isinstance(v, dict):
                # keyed form (the attribution summary's per-rung
                # dispatch_efficiency map): one row per sub-key
                for sub, sv in v.items():
                    if isinstance(sv, (int, float)) and not isinstance(
                        sv, bool
                    ):
                        out[f"{base}.{k}[{sub}]"] = float(sv)
            else:
                _flatten(v, f"{base}.{k}" if base else k, out)
    elif isinstance(node, list):
        for item in node:
            # list position carries no identity; the ctx keys do
            _flatten(item, path, out)


def flatten_metrics(doc: Dict[str, Any]) -> Dict[str, float]:
    """{stable path -> value} for every watched metric in a bench doc.
    Duplicate paths (two rows with identical context) keep the LAST —
    deterministic, and real artifacts key rows by the ctx fields."""
    out: Dict[str, float] = {}
    _flatten(doc, "", out)
    return out


def _balanced_objects(
    text: str, anchor: str = '{"constraints":'
) -> List[Dict[str, Any]]:
    """Complete JSON objects recovered from a truncated capture tail.

    BENCH_r0x captures keep only the LAST bytes of a run's stdout, so
    the enclosing doc is cut mid-object and `json.loads` fails — but
    every ladder rung row inside it is still a complete `{"constraints":
    N, ...}` object. Brace-scanning from each anchor recovers them (rung
    rows carry no braces inside strings), which is what lets the
    trajectory gate judge r05-era tails against structured artifacts."""
    rows: List[Dict[str, Any]] = []
    i = text.find(anchor)
    while i != -1:
        depth = 0
        for j in range(i, len(text)):
            ch = text[j]
            if ch == "{":
                depth += 1
            elif ch == "}":
                depth -= 1
                if depth == 0:
                    try:
                        obj = json.loads(text[i : j + 1])
                        if isinstance(obj, dict):
                            rows.append(obj)
                    except ValueError:
                        pass
                    break
        i = text.find(anchor, i + 1)
    return rows


def _recover_capture(doc: Dict[str, Any]) -> Dict[str, Any]:
    """A comparable doc out of a BENCH_r0x capture ({n, cmd, rc, tail,
    parsed}): the parsed artifact when the capture got one, else
    whatever survives in the tail — a SUMMARY line, a parseable JSON
    line, or complete ladder rung objects fished out of the truncated
    stream."""
    from gatekeeper_tpu.summary import find_summary

    parsed = doc.get("parsed")
    if isinstance(parsed, dict):
        return parsed
    tail = doc.get("tail")
    if isinstance(tail, str) and tail:
        rec = find_summary(tail)
        if rec is not None:
            return rec
        for line in tail.splitlines():
            line = line.strip()
            if line.startswith("{"):
                try:
                    obj = json.loads(line)
                    if isinstance(obj, dict):
                        return obj
                except ValueError:
                    continue
        rows = _balanced_objects(tail)
        if rows:
            return {"webhook_constraint_ladder": rows}
    return doc


def load_run(path: str) -> Dict[str, Any]:
    """A bench doc from a file: JSON artifact (BENCH_r0x captures are
    unwrapped/recovered), or a run log whose last SUMMARY line becomes
    the doc (the truncation-survivor path)."""
    from gatekeeper_tpu.summary import find_summary

    with open(path) as f:
        text = f.read()
    try:
        doc = json.loads(text)
        if isinstance(doc, dict):
            if "tail" in doc and "parsed" in doc:
                return _recover_capture(doc)
            return doc
    except ValueError:
        pass
    doc = find_summary(text)
    if doc is None:
        # last resort: first parseable JSON line (bench stdout is
        # `json.dumps(res)` then the SUMMARY line)
        for line in text.splitlines():
            line = line.strip()
            if line.startswith("{"):
                try:
                    parsed = json.loads(line)
                    if isinstance(parsed, dict):
                        return parsed
                except ValueError:
                    continue
        raise ValueError(
            f"{path}: neither a JSON artifact nor a SUMMARY-bearing log"
        )
    return doc


def compare_runs(
    base: Dict[str, Any],
    cand: Dict[str, Any],
    threshold: float = 0.20,
) -> Dict[str, Any]:
    """Judge candidate vs base. A metric regresses when it moves in
    its bad direction by more than `threshold` (relative; tiny bases
    under 1e-9 are skipped — a 0→0.001 ratio is noise, not signal)."""
    b = flatten_metrics(base)
    c = flatten_metrics(cand)
    shared = sorted(set(b) & set(c))
    regressions: List[Dict[str, Any]] = []
    improvements: List[Dict[str, Any]] = []
    unchanged = 0
    for key in shared:
        leaf = key.rsplit(".", 1)[-1].split("[", 1)[0]
        direction = WATCHED[leaf]
        bv, cv = b[key], c[key]
        if abs(bv) < 1e-9:
            unchanged += 1
            continue
        delta = (cv - bv) / abs(bv)
        bad = delta * direction  # positive = moved the wrong way
        row = {
            "metric": key,
            "base": bv,
            "candidate": cv,
            "delta_frac": round(delta, 4),
        }
        if bad > threshold:
            regressions.append(row)
        elif bad < -threshold:
            improvements.append(row)
        else:
            unchanged += 1
    regressions.sort(
        key=lambda r: -abs(r["delta_frac"])
    )
    improvements.sort(key=lambda r: -abs(r["delta_frac"]))
    return {
        "threshold": threshold,
        "compared": len(shared),
        "unchanged": unchanged,
        "regressions": regressions,
        "improvements": improvements,
        "ok": not regressions,
    }


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        description="diff two bench runs; exit 1 on regression"
    )
    p.add_argument("base", help="baseline artifact or run log")
    p.add_argument("candidate", help="candidate artifact or run log")
    p.add_argument(
        "--threshold", type=float, default=0.20,
        help="relative move that counts as a regression (default 0.20)",
    )
    args = p.parse_args(argv)
    report = compare_runs(
        load_run(args.base), load_run(args.candidate),
        threshold=args.threshold,
    )
    print(json.dumps(report, indent=2))
    for row in report["regressions"]:
        print(
            f"REGRESSION {row['metric']}: {row['base']} -> "
            f"{row['candidate']} ({row['delta_frac']:+.1%})",
            file=sys.stderr,
        )
    if report["ok"]:
        print(
            f"bench_compare: {report['compared']} metrics compared, "
            f"no regressions past {args.threshold:.0%}",
            file=sys.stderr,
        )
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
