"""Program-IR static analysis plane (analysis/ir.py, GK-P01x).

Three layers under test: the abstract interpreter's diagnostics over
synthetic programs (provable facts only — every code asserted here is
a soundness claim), the pad-equivalence liveness proof and its
encoder-side mask, and the `ir` CLI mode + checked-in baseline over
the shipped policy corpus.
"""

import json
import os
from types import SimpleNamespace

import numpy as np

from gatekeeper_tpu.analysis.cli import run
from gatekeeper_tpu.analysis.ir import (
    analyze_program,
    corpus_liveness,
    ir_from_docs,
    program_liveness,
    row_feature_pids,
)
from gatekeeper_tpu.engine.exprs import (
    ECapture,
    EConstSlot,
    ELit,
    EMap,
    EReduce,
    ESelPattern,
    ETokCol,
    e_and,
    e_cmp,
)
from gatekeeper_tpu.engine.programs import Program

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEPLOY = os.path.join(REPO, "deploy", "policies")
IR_BASELINE = os.path.join(DEPLOY, "ir-baseline.json")


def prog(expr, consts=None, branches=None, flags=(), screen=False):
    return Program(
        expr=expr,
        consts=dict(consts or {}),
        signature=(),
        screen=screen,
        branches=branches,
        flags=tuple(flags),
    )


def _codes(diags):
    return sorted({d.code for d in diags})


# -- abstract interpretation ---------------------------------------------------


def test_always_firing_rule_gk_p010():
    diags, _ = analyze_program("s", "K", prog(ELit(2.0)))
    assert _codes(diags) == ["GK-P010"]


def test_never_firing_rule_gk_p011():
    diags, _ = analyze_program("s", "K", prog(ELit(0.0)))
    assert _codes(diags) == ["GK-P011"]


def test_unknown_outcome_no_verdict_diag():
    # violation count rides an actual token read: nothing provable
    expr = EReduce(ESelPattern(0), "sum")
    diags, certs = analyze_program("s", "K", prog(expr))
    assert diags == [] and certs == []


def test_unused_const_slot_gk_p012():
    expr = EReduce(ESelPattern(0), "sum")
    diags, _ = analyze_program(
        "s", "K", prog(expr, consts={"i0": np.array(3.0)})
    )
    assert _codes(diags) == ["GK-P012"]
    assert "i0" in diags[0].message


def test_read_const_slot_not_flagged():
    expr = EReduce(e_cmp(">", EConstSlot("i0"), ETokCol("vnum")), "sum")
    diags, _ = analyze_program(
        "s", "K", prog(expr, consts={"i0": np.array(3.0)})
    )
    assert diags == []


def test_interval_noop_check_gk_p013():
    # param 5 > literal 0 is a constant-True comparison fed by a
    # parameter slot: the check is a no-op whatever the parameter did
    cmp_ = e_cmp(">", EConstSlot("t"), ELit(0.0))
    expr = EReduce(e_and(cmp_, ESelPattern(0)), "sum")
    diags, _ = analyze_program(
        "s", "K", prog(expr, consts={"t": np.array(5.0)})
    )
    assert "GK-P013" in _codes(diags)
    assert any("constant True" in d.message for d in diags)


def test_dead_branch_gk_p014_and_certificates():
    dead_cond = e_cmp("<", EConstSlot("g"), ELit(0.0))
    live_cond = ELit(1.0)
    branches = (
        SimpleNamespace(cond=dead_cond, plan=None),
        SimpleNamespace(cond=live_cond, plan=None),
    )
    expr = EReduce(ESelPattern(0), "sum")
    diags, certs = analyze_program(
        "s", "K",
        prog(expr, consts={"g": np.array(5.0)}, branches=branches),
    )
    # the dead branch is both a diagnostic and a "dead" certificate;
    # the constant-True branch is an "always" certificate (condition
    # test elidable in a residual program)
    p014 = [d for d in diags if d.code == "GK-P014"]
    assert len(p014) == 1 and p014[0].path == "branches[0]"
    folds = {(c.branch, c.fold) for c in certs}
    assert folds == {(0, "dead"), (1, "always")}


# -- pad-equivalence liveness --------------------------------------------------


def test_selpattern_program_maskable():
    expr = EReduce(ESelPattern(3), "sum")
    pl = program_liveness(prog(expr))
    assert pl.maskable and pl.pids == frozenset({3})


def test_raw_tokcol_reduce_not_maskable():
    # reducing a raw column over the token axis: dead != pad (kind is
    # real at a dead token, -1 at pad), so no masking proof exists
    expr = EReduce(ETokCol("kind"), "max")
    pl = program_liveness(prog(expr))
    assert not pl.maskable
    assert any("dead and pad" in v for v in pl.violations)


def test_maskfill_contract_restores_maskability():
    # the engine/symbolic.py "maskfill" contract: where(mask, col, init)
    # with a pattern-gated mask is pad-equivalent even over a raw column
    fill = EMap(
        lambda np_, m, v: np_.where(m, v, 0.0),
        [ESelPattern(2), ETokCol("vnum")],
        "maskfill",
    )
    pl = program_liveness(prog(EReduce(fill, "max")))
    assert pl.maskable and pl.pids == frozenset({2})


def test_capture_gather_is_pad_equivalent():
    expr = EReduce(ECapture(7), "max")
    pl = program_liveness(prog(expr))
    assert pl.maskable and pl.pids == frozenset({7})


def test_corpus_liveness_unions_and_keep_alls():
    a = prog(EReduce(ESelPattern(1), "sum"))
    b = prog(EReduce(ESelPattern(4), "sum"))
    bad = prog(EReduce(ETokCol("kind"), "max"))
    assert corpus_liveness([a, b, None]) == frozenset({1, 4})
    assert corpus_liveness([a, bad]) is None  # one refusal poisons all
    assert corpus_liveness([a], extra_pids=(9,)) == frozenset({1, 9})


def test_row_feature_pids_parses_invdup_names():
    pids = row_feature_pids(
        ["invdup:3:11:0:5+8", "extdata:whatever", "invdup:bad", "other"]
    )
    assert pids == frozenset({3, 11, 5, 8})


def test_row_feature_pids_skips_invkey_names():
    """An "invkey:" feature (a derived join key) reads raw reviews: its
    guard pattern ids name identity paths, not token columns, so it
    keeps no pattern live; invdup names beside it parse as before."""
    pids = row_feature_pids([
        "invkey:K:0123456789ab:flatten_selector:namespace:1:"
        "10=15+17=0+18=make_apiversion(19)",
        "invdup:3:11:0:5+8",
    ])
    assert pids == frozenset({3, 11, 5, 8})


def test_derived_key_join_compiles_the_invkey_feature():
    """uniqueserviceselector's flatten_selector join compiles to one
    invkey feature scoped to its kind and parameters, carrying the
    identical() guard: four identity equalities, the last through the
    make_apiversion helper."""
    import re

    import yaml

    from gatekeeper_tpu.constraint import Backend, K8sValidationTarget, TpuDriver

    with open(os.path.join(REPO, "deploy", "library", "referential.yaml")) as f:
        (tmpl,) = [
            d for d in yaml.safe_load_all(f)
            if d and d["metadata"]["name"] == "k8suniqueserviceselector"
        ]
    drv = TpuDriver(use_jax=False)
    client = Backend(drv).new_client(K8sValidationTarget())
    client.add_template(tmpl)
    client.add_constraint({
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "K8sUniqueServiceSelector", "metadata": {"name": "u"},
        "spec": {},
    })
    (prog,) = drv._constraint_set("admission.k8s.gatekeeper.sh").programs
    (name,) = prog.row_features
    assert re.fullmatch(
        r"invkey:K8sUniqueServiceSelector:[0-9a-f]{12}:flatten_selector:"
        r"namespace:1:(\d+=\d+\+){3}\d+=make_apiversion\(\d+\)",
        name,
    ), name
    guards = [g.split("=") for g in name.split(":")[6].split("+")]
    assert [drv.patterns.segs(int(lhs)) for lhs, _ in guards] == [
        ("object", "metadata", "namespace"), ("object", "metadata", "name"),
        ("object", "kind"), ("object", "apiVersion"),
    ]
    assert [drv.patterns.segs(int(rhs.strip(")").split("(")[-1]))
            for _, rhs in guards] == [
        ("namespace",), ("name",), ("kind", "kind"), ("kind",),
    ]


def test_non_maskable_program_reported_gk_p016():
    rep = ir_from_docs([])  # empty: exercise the report shape
    assert rep.liveness["programs"] == 0
    diags, _ = analyze_program(
        "s", "K", prog(EReduce(ESelPattern(0), "sum"))
    )
    assert diags == []


# -- encoder mask --------------------------------------------------------------


def _objs():
    return [
        {
            "metadata": {"name": f"p{i}", "labels": {"app": "web"}},
            "spec": {
                "containers": [
                    {"name": "c", "image": f"nginx:{i}"},
                    {"name": "d", "image": "redis"},
                ],
                "hostNetwork": bool(i % 2),
            },
        }
        for i in range(5)
    ]


def test_mask_token_table_drops_dead_columns():
    from gatekeeper_tpu.flatten.encoder import (
        encode_token_table,
        mask_token_table,
    )
    from gatekeeper_tpu.flatten.vocab import Vocab

    v = Vocab()
    table = encode_token_table(_objs(), v)

    keep_prefix = "p:spec.containers"
    masked, skipped = mask_token_table(
        table, lambda pid: v.string(pid).startswith(keep_prefix)
    )
    assert skipped > 0
    # every surviving token kept its full column tuple, in row order
    for r in range(table.spath.shape[0]):
        src = [
            (
                int(table.spath[r, c]),
                int(table.idx0[r, c]),
                int(table.idx1[r, c]),
                int(table.kind[r, c]),
                int(table.vid[r, c]),
                float(table.vnum[r, c]),
            )
            for c in range(table.spath.shape[1])
            if table.spath[r, c] >= 0
            and v.string(int(table.spath[r, c])).startswith(keep_prefix)
        ]
        n = int(masked.n_tokens[r])
        assert n == len(src)
        got = [
            (
                int(masked.spath[r, c]),
                int(masked.idx0[r, c]),
                int(masked.idx1[r, c]),
                int(masked.kind[r, c]),
                int(masked.vid[r, c]),
                float(masked.vnum[r, c]),
            )
            for c in range(n)
        ]
        assert got == src
        # pads after the kept prefix
        assert (masked.spath[r, n:] == -1).all()
    assert np.array_equal(masked.overflow, table.overflow)


def test_mask_token_table_keep_everything_is_identity():
    from gatekeeper_tpu.flatten.encoder import (
        encode_token_table,
        mask_token_table,
    )
    from gatekeeper_tpu.flatten.vocab import Vocab

    v = Vocab()
    table = encode_token_table(_objs(), v)
    masked, skipped = mask_token_table(table, lambda pid: True)
    assert skipped == 0 and masked is table


def test_mask_token_table_preserves_overflow():
    """Truncated rows lost arbitrary live tokens at the ORIGINAL L;
    they must keep routing to the interpreter even when the filtered
    row looks small."""
    from gatekeeper_tpu.flatten.encoder import (
        encode_token_table,
        mask_token_table,
    )
    from gatekeeper_tpu.flatten.vocab import Vocab

    v = Vocab()
    table = encode_token_table(_objs(), v, max_len=4)
    assert table.overflow.any()
    masked, skipped = mask_token_table(
        table, lambda pid: not v.string(pid).startswith("p:metadata")
    )
    assert skipped > 0
    assert np.array_equal(masked.overflow, table.overflow)


# -- offline corpus runner + CLI ----------------------------------------------


def test_ir_shipped_policies_hold_the_baseline(capsys):
    rc = run(["ir", DEPLOY, "--baseline", IR_BASELINE])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "OK:" in out
    assert "keep_all=False" in out


def test_ir_baseline_manifest_is_current():
    import yaml

    with open(IR_BASELINE) as f:
        recorded = json.load(f)["ir"]
    docs = []
    for root, _dirs, files in os.walk(DEPLOY):
        for fn in sorted(files):
            if fn.endswith((".yaml", ".yml")):
                with open(os.path.join(root, fn)) as f:
                    docs.extend(
                        d
                        for d in yaml.safe_load_all(f)
                        if isinstance(d, dict)
                    )
    report = ir_from_docs(docs)
    assert {l.id: sorted(l.codes) for l in report.lints} == recorded
    # the shipped corpus is maskable end to end: this is what turns the
    # driver's column skipping on, so pin it
    assert report.liveness["keep_all"] is False
    assert report.liveness["maskable"] == report.liveness["programs"] > 0
    assert 0 < report.liveness["live_patterns"] < (
        report.liveness["patterns_total"]
    )


def test_ir_shipped_dead_parameter_is_a_true_positive():
    """The one GK-P012 in the baseline: net-fetch-domains burns consts
    its compiled program never reads (the allowlist fold happens at
    compile time). If this goes clean the analyzer got WEAKER or the
    policy changed — both worth a look."""
    with open(IR_BASELINE) as f:
        recorded = json.load(f)["ir"]
    assert recorded[
        "constraint:AgentNetworkDomains/net-fetch-domains"
    ] == ["GK-P012"]


IR_PROBE = """apiVersion: templates.gatekeeper.sh/v1beta1
kind: ConstraintTemplate
metadata:
  name: irprobegate
spec:
  crd:
    spec:
      names:
        kind: IrProbeGate
  targets:
    - target: admission.k8s.gatekeeper.sh
      rego: |
        package irprobegate
        violation[{"msg": msg}] {
          input.parameters.limit > 0
          msg := "gated"
        }
---
apiVersion: constraints.gatekeeper.sh/v1beta1
kind: IrProbeGate
metadata:
  name: never-fires
spec:
  parameters:
    limit: -3
"""


def test_ir_flagged_then_baselined(tmp_path, capsys):
    (tmp_path / "probe.yaml").write_text(IR_PROBE)
    rc = run(["ir", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "GK-P011" in out
    pinned = tmp_path / "pinned.json"
    rc = run(["ir", str(tmp_path), "--write-baseline", str(pinned)])
    assert rc == 1  # flagged until the baseline accepts it
    rc = run(["ir", str(tmp_path), "--baseline", str(pinned)])
    assert rc == 0
    capsys.readouterr()


def test_ir_json_output(tmp_path, capsys):
    (tmp_path / "probe.yaml").write_text(IR_PROBE)
    rc = run(["ir", str(tmp_path), "--json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1
    codes = {s["id"]: s["codes"] for s in payload["ir"]}
    assert codes["constraint:IrProbeGate/never-fires"] == ["GK-P011"]
    assert codes["template:IrProbeGate"] == []


def test_ir_none_found(tmp_path):
    assert run(["ir", str(tmp_path)]) == 2


def test_ir_fused_taxonomy_reports_reason_slugs(tmp_path, capsys):
    """A template off the fused path surfaces its CompileUnsupported
    Reason slug in the GK-P015 diagnostic, not a bare exception."""
    (tmp_path / "t.yaml").write_text(
        """apiVersion: templates.gatekeeper.sh/v1beta1
kind: ConstraintTemplate
metadata:
  name: irprobeoff
spec:
  crd:
    spec:
      names:
        kind: IrProbeOff
  targets:
    - target: admission.k8s.gatekeeper.sh
      rego: |
        package irprobeoff
        violation[{"msg": msg}] {
          walk(input.review.object, [path, value])
          value == "forbidden"
          msg := sprintf("%v", [path])
        }
"""
    )
    rc = run(["ir", str(tmp_path), "--json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1
    (row,) = [s for s in payload["ir"] if s["id"] == "template:IrProbeOff"]
    assert row["codes"] == ["GK-P015"]
    assert "reason=" in row["diagnostics"][0]["path"]
