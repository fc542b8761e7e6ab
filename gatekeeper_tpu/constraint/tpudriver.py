"""TpuDriver: the compiled, batched evaluation engine behind the Driver
boundary.

This is the TPU counterpart of the reference's sole driver implementation
(vendor/.../frameworks/constraint/pkg/client/drivers/local/local.go:48-394,
behind drivers/interface.go:21-39). Where `local` answers every
`hooks[...].audit` query by interpreting one Rego cross-join over the whole
data cache, TpuDriver evaluates the same query as two fused device
dispatches over dense tensors:

  1. the constraint x resource **match matrix** (`engine/matchkernel.py`) —
     the vectorized form of `matching_constraints`
     (pkg/target/target_template_source.go:27-44), and
  2. the batch of **compiled template programs** (`engine/programs.py`) —
     per-(template, params) violation counters produced by the symbolic
     Rego compiler (`engine/symbolic.py`), all traced into one jitted
     callable.

Violating (constraint, resource) pairs come back as a sparse index set;
only those pairs are re-evaluated host-side with the interpreter to render
exact violation messages/details (violations are sparse in steady state,
so host work is O(violations), not O(C x N)).

Hybrid routing (the `Driver` boundary makes this natural — SURVEY §7
"hard parts"):
  * templates outside the compilable Rego subset raise
    `CompileUnsupported` at mount/first-use and are routed per-template to
    the interpreter (`RegoDriver._eval_template`), restricted to
    kernel-matched reviews;
  * resources whose array fanout exceeds the device bucket cap
    (`G_CAP`) are routed per-row to the interpreter, so EGroup's bounded
    fanout can never silently drop violations (fail-closed routing).

Bit-for-bit result parity with RegoDriver over the constraint-client
battery is enforced by tests/test_tpu_driver.py.
"""

from __future__ import annotations

import hashlib
import json
import re
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..analysis import (
    CODE_MISMATCH,
    INTERPRETER as V_INTERPRETER,
    VectorizabilityReport,
    analyze_modules,
)
from ..engine.matchkernel import matchspec_to_np
from ..faults import FaultError, device_point, fire
from ..engine.patterns import PatternRegistry, _match
from ..engine.programs import Program, ProgramEvaluator, compile_program
from ..engine.symbolic import CompilerEnv, CompileUnsupported
from ..engine.tables import StrTables
from ..flatten.encoder import (
    _bucket,
    batch_review_features,
    encode_token_table,
    mask_token_table,
    unesc_seg,
)
from ..flatten.vocab import Vocab
from ..obs.tracer import stage
from ..rego import ast as A
from ..rego.interp import RegoError, Undefined, _call_function
from ..rego.values import freeze, thaw
from . import hooks as H
from .driver import (
    _HOOK_RE,
    RegoDriver,
    _autoreject_result,
    _cname,
    constraint_key,
)
from .types import Response, Result

_TEMPLATE_PREFIX_RE = re.compile(r'^templates\["([^"]+)"\]\["([^"]+)"\]$')

# Array-axis fanout cap for device evaluation. Objects with more than
# G_CAP elements on a lifted array axis (e.g. a pod with >G_CAP
# containers) are routed to the interpreter instead of being evaluated
# with truncated fanout (ADVICE r1: EGroup drops tokens with idx >= g).
G_CAP = 64

# Resource-axis chunk for device dispatch: bounds the [N, L, G]
# intermediates EGroup materializes and keeps one stable jit shape that
# every chunk (padded) reuses.
N_CHUNK = 32768

# Adaptive micro-batch routing: review batches below this size evaluate
# serially on the interpreter — a per-request interp review costs ~10ms
# while a fused device dispatch pays a fixed cost per batch (augment,
# encode/stage, device call, render); large batches amortize it.
# Tunable per deployment via GATEKEEPER_TPU_MIN_DEVICE_BATCH; the value
# for a directly attached chip is not measured yet.
import os as _os
import threading as _threading
import time as _time

MIN_DEVICE_BATCH = int(
    _os.environ.get("GATEKEEPER_TPU_MIN_DEVICE_BATCH", "12")
)

# Pair-aware floor for SUBSET dispatches (pruned partitions): a mask-
# sliced sub-batch is small in reviews but DENSE in (review, constraint)
# pairs — the locality planner co-locates exactly the constraints those
# reviews match — so row count alone mis-routes it to the serial
# interpreter, which then pays every pair at interpreter cost. A subset
# batch below MIN_DEVICE_BATCH rows still takes the device when its
# review x constraint pair volume clears this floor.
MIN_DEVICE_PAIRS = int(
    _os.environ.get("GATEKEEPER_TPU_MIN_DEVICE_PAIRS", "256")
)


def _params_key(params: Any) -> str:
    return json.dumps(params, sort_keys=True, default=str)


_CACHE_ENABLED = False
# process-wide ProgramStore (gatekeeper_tpu/compile), rooted at XLA's
# persistent cache dir
_STORE = None


def _enable_compile_cache():
    """Persistent XLA compilation cache: template ingest re-pays minutes
    of XLA compile per fresh process otherwise (the reference's
    interpreter has no compile step to amortize; this engine does).
    Opt out with GATEKEEPER_TPU_NO_COMPILE_CACHE=1; place it with
    JAX_COMPILATION_CACHE_DIR (default `<checkout>/.cache/jax`).

    The content-addressed program store (docs/compile.md) is rooted at
    that same directory, which XLA reads and writes directly. Returns
    the store (None = caching disabled)."""
    global _CACHE_ENABLED, _STORE
    if _CACHE_ENABLED:
        return _STORE
    _CACHE_ENABLED = True
    try:
        from ..compile import store_from_env

        store = store_from_env()
        if store is None:
            return None
        import jax

        jax.config.update("jax_compilation_cache_dir", store.root)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
        _STORE = store
    except Exception:
        _STORE = None  # cache is an optimization; never fail construction
    return _STORE


@dataclass
class _Corpus:
    """Encoded audit corpus, cached across sweeps until data changes."""

    data_gen: int
    reviews: List[Any]
    tok: Dict[str, np.ndarray]
    fb_dev: Dict[str, Any]
    g: int  # first-level array fanout bucket (idx0)
    row_fallback: np.ndarray  # [N] bool: route row to interpreter
    # second-level fanout bucket (idx1): mounts-per-container etc. are
    # typically tiny, and the g01 one-hot scales with g * g1 — bucketing
    # idx1 separately keeps it small (VERDICT perf watch-item)
    g1: int = 8
    # [(start, StagedBatch)] device-resident chunks; staged lazily at
    # first dispatch, reused every sweep until the corpus changes
    staged: Optional[List[Tuple[int, Any]]] = None
    # computed per-row screen features (invdup join bits), host copies
    row_feats: Optional[Dict[str, np.ndarray]] = None
    # per-pattern join-key value counts (pid -> (counts, has_fallback))
    value_counts: Optional[Dict[int, Any]] = None
    # ephemeral vocab overlay (webhook batches): the batch's novel
    # strings + their pattern/table rows, never interned globally
    vocab: Any = None  # OverlayVocab for ephemeral corpora, else None
    v_base: int = 0
    # provably-dead token slots dropped by the IR feature-liveness mask
    # before padding (analysis/ir.py); 0 when encoded keep-all
    skipped_static: int = 0
    ov_member: Optional[np.ndarray] = None  # [B_pad, P] bool
    ov_capture: Optional[np.ndarray] = None  # [B_pad, P] int32
    ov_tabs: Optional[Dict[str, np.ndarray]] = None  # name -> [B_pad]
    # external-data key extraction cache (feature name -> per-row
    # {provider -> keys} | None): keys are corpus-constant, but the
    # BITS derived from them track the live response cache and are
    # recomputed per dispatch (_extdata_row_bits)
    ext_keys: Optional[Dict[str, Any]] = None


@dataclass
class _ConstraintSet:
    """Compiled constraint-side tensors, cached until constraints change."""

    constraint_gen: int
    constraints: List[Dict[str, Any]]
    ms: Dict[str, np.ndarray]
    programs: List[Optional[Program]]  # index-aligned; None => fallback
    prog_rows: List[int]  # constraint index -> row in compiled stack (-1)
    policy: Optional[Any] = None  # StagedPolicy, device-resident
    # kind -> analyzer diagnostic code for interpreter-routed templates
    # (CODE_MISMATCH when the analyzer predicted compilable but the
    # compiler disagreed)
    fallback_codes: Dict[str, str] = None  # type: ignore[assignment]
    # content signature of this (sub)set's constraints + template IR
    # (docs/compile.md): a constraint-generation bump whose signature is
    # unchanged carries the staged policy forward instead of restaging
    signature: Optional[str] = None
    # IR feature-liveness over this set's programs (analysis/ir.py),
    # computed once per set: False = not yet computed, None = keep-all
    # (some program failed the pad-equivalence proof), frozenset = live
    # pattern indices
    live_pids: Any = False


class TpuDriver(RegoDriver):
    """Compiled-engine driver: device-batched audit/review, interpreter
    fallback for the uncompilable remainder."""

    def __init__(self, use_jax: bool = True, mesh=None, metrics=None):
        super().__init__()
        # program store over the compile cache (docs/compile.md); None when
        # caching is disabled (tests) or the store root is unwritable
        self.program_store = _enable_compile_cache() if use_jax else None
        # optional MetricsRegistry: per-template verdict gauges +
        # fallback-reason counters land here when wired (Runner calls
        # set_metrics; tests construct with metrics=)
        self.metrics = metrics
        # (target, kind) -> VectorizabilityReport, computed once per
        # mounted module set (the admission-time analyzer, re-run here
        # so the driver owns its routing decision even for modules
        # mounted without going through Client.add_template)
        self._analysis: Dict[Tuple[str, str], VectorizabilityReport] = {}
        # analyzer-says-compilable but CompileUnsupported raised: the
        # consistency assertion the old try/except routing became
        self.analyzer_mismatches = 0
        # (target, kind) -> diagnostic code for interpreter-routed
        # templates (machine-readable fallback reason)
        self._fallback_codes: Dict[Tuple[str, str], str] = {}
        self.vocab = Vocab()
        self.patterns = PatternRegistry(self.vocab)
        self.tables = StrTables(self.vocab)
        self.use_jax = use_jax
        self.evaluator = ProgramEvaluator(
            self.patterns, self.tables, use_jax=use_jax
        )
        if use_jax:
            from ..parallel.sharding import FusedAuditKernel

            self.kernel = FusedAuditKernel(
                self.patterns, self.tables, mesh=mesh
            )
            self.kernel.metrics = metrics  # compile/cache telemetry
        else:
            self.kernel = None
        # (target, kind) -> rewritten template modules
        self._kind_modules: Dict[Tuple[str, str], List[A.Module]] = {}
        # (target, kind, params_key) -> Program | None (None = fallback)
        self._programs: Dict[Tuple[str, str, str], Optional[Program]] = {}
        self._data_gen = 0
        self._constraint_gen = 0
        self._corpus: Dict[str, _Corpus] = {}  # per target
        self._cset: Dict[str, _ConstraintSet] = {}
        # partition-scoped constraint subsets (docs/robustness.md
        # §Fault domains): (target, frozenset of constraint keys) ->
        # independently staged/dispatchable _ConstraintSet. Bounded —
        # plan churn (quarantine/re-home) mints new subsets and the
        # stale ones must not pin device policy state forever.
        self._cset_sub: Dict[Tuple[str, frozenset], _ConstraintSet] = {}
        self._cset_sub_max = 64
        # rendered-pair cache for the persistent audit corpus: identical
        # (constraint, review, inventory) inputs render identical results,
        # so violating pairs that persist across sweeps skip the
        # interpreter re-render; invalidated wholesale on any data or
        # constraint generation change
        self._render_cache: Dict[
            str, Tuple[Tuple[int, int], Dict[Tuple[int, int], List[Result]]]
        ] = {}
        # render-cache bound (docs/robustness.md §soak): within one
        # (data, constraint) generation the pair space is corpus x
        # constraints — a huge synced corpus under sustained audit must
        # evict oldest-cached pairs, never grow without bound
        self.render_cache_max = int(
            _os.environ.get("GATEKEEPER_TPU_RENDER_CACHE_MAX", "65536")
        )
        self._render_cache_evictions = 0
        # instrumentation for tests/bench: compiled-path pair evaluations
        # vs interpreter fallback evaluations in the last query
        self.stats: Dict[str, int] = {}
        # serve-while-compiling (VERDICT r4 #4): the fused review path
        # serves only once its kernels are compiled for the current
        # constraint generation; until then device-sized batches route
        # to the interpreter and a background thread compiles, then the
        # route swaps atomically (the reference is Ready as soon as
        # state replays, pkg/readiness/ready_tracker.go:138-173 — its
        # interpreter has no compile step to hide)
        self._review_warm: Dict[str, int] = {}  # target -> constraint_gen
        self._warming: set = set()
        self._warm_lock = _threading.Lock()
        self.cold_batches = 0  # device-sized batches served cold (interp)
        # per calling thread: the route its last review batch took
        # (`last_route`) and its last evaluation's stats with the stage
        # stamps (`thread_stats`) — the batch worker reads its own
        # dispatch, never another thread's
        self._local = _threading.local()
        self.last_warm_error: Optional[BaseException] = None
        self._render_errors = 0  # compiled-render bugs degraded to interp
        # derived-key prune render caches (uniqueserviceselector-style
        # joins): key index per data generation + oracle contexts
        self._prune_indexes: Dict[Tuple, Tuple[int, Any]] = {}
        self._prune_oracles: Dict[Tuple, Any] = {}
        self._hot_redispatches = 0  # chunks rerun for compaction overflow
        # externaldata.ExternalDataSystem (set_external_data): the
        # batch plane for external_data lookups — key prefetch per
        # micro-batch + the extdata row-feature screen
        self.external_data = None
        # obs.CostAttributor (set_attributor): per-constraint
        # device-time accounting — every dispatch's measured
        # device-execute window is apportioned over the constraints it
        # evaluated by the static cost model (_static_cost), labeled
        # with the partition that paid it (docs/observability.md
        # §Cost attribution)
        self.attributor = None
        # integrity.IntegrityPlane (set_integrity): canary rows packed
        # into each dispatch's padding slots + the golden swap gate
        # (docs/robustness.md §Verdict integrity). None = no canary
        # packing, no golden gate — the plain-driver test default.
        self.integrity = None
        # incremental compile plane (docs/compile.md): template IR
        # hashes + per-subset content signatures drive minimal
        # recompiles — churn restages only partitions whose signature
        # changed, and staged sub-programs swap atomically
        self._ir_hashes: Dict[Tuple[str, str], str] = {}
        self._sig_cache: Dict[Tuple[str, frozenset], Tuple[int, str]] = {}
        self._swap_gen = 0
        self.program_compiles = 0  # compile_program invocations
        self.subset_swaps = 0  # shadow sets atomically swapped live
        self.subset_carryforwards = 0  # gen bumps served by signature
        # IR static-analysis plane (analysis/ir.py): ephemeral review
        # batches encode under the constraint set's feature-liveness
        # mask, dropping token columns no compiled program can read
        # before padding. Disabled via env for parity audits; the
        # persistent audit corpus always encodes keep-all (it is cached
        # per DATA generation and must survive constraint churn).
        self.liveness_enabled = (
            _os.environ.get("GATEKEEPER_TPU_NO_STATIC_LIVENESS", "") == ""
        )
        self.columns_skipped_static = 0  # cumulative dead slots dropped
        self.liveness_batches = 0  # batches encoded under a live mask
        # target -> (constraint_gen, IrReport): lazily computed, pre-
        # populated across warm swaps by attach_ir_report
        self._ir_reports: Dict[str, Tuple[int, Any]] = {}

    # -- module/data bookkeeping (cache invalidation) ------------------------

    def put_modules(self, prefix: str, modules: Sequence[A.Module]) -> None:
        super().put_modules(prefix, modules)
        m = _TEMPLATE_PREFIX_RE.match(prefix)
        if m:
            target, kind = m.group(1), m.group(2)
            with self._mutex:
                self._kind_modules[(target, kind)] = list(modules)
                self._drop_programs(target, kind)

    def delete_modules(self, prefix: str) -> int:
        n = super().delete_modules(prefix)
        m = _TEMPLATE_PREFIX_RE.match(prefix)
        if m:
            target, kind = m.group(1), m.group(2)
            with self._mutex:
                self._kind_modules.pop((target, kind), None)
                self._drop_programs(target, kind)
        return n

    def _drop_programs(self, target: str, kind: str) -> None:
        for key in [k for k in self._programs if k[0] == target and k[1] == kind]:
            del self._programs[key]
        self._analysis.pop((target, kind), None)
        self._fallback_codes.pop((target, kind), None)
        self._ir_hashes.pop((target, kind), None)
        self._ir_reports.pop(target, None)
        for cache in (self._prune_oracles, self._prune_indexes):
            for key in [
                k for k in cache if k[0] == target and k[1] == kind
            ]:
                del cache[key]
        # derived-key bits carry the kind, not the template's body: a
        # replaced template must not reuse its predecessor's bits
        corpus = self._corpus.get(target)
        if corpus is not None:
            stale = f"invkey:{kind}:"
            row_dev = getattr(corpus.staged, "row_dev", None)
            for feats in (corpus.row_feats, row_dev):
                for name in [n for n in feats or () if n.startswith(stale)]:
                    del feats[name]
        self._cset.pop(target, None)
        # a template (module) change produces new programs: the warm
        # flag keys on this generation, so bumping it here drops the
        # review route cold and the background re-warm loop proactively
        # compiles the NEW policy (without it, the stale flag left
        # re-warming to the first unlucky admission batch)
        self._constraint_gen += 1

    def put_data(self, path: str, data: Any) -> None:
        super().put_data(path, data)
        self._note_data_change(path)

    def delete_data(self, path: str) -> bool:
        existed = super().delete_data(path)
        self._note_data_change(path)
        return existed

    def _note_data_change(self, path: str) -> None:
        with self._mutex:
            p = path.lstrip("/")
            if p.startswith("external") or not p:
                self._data_gen += 1
            if p.startswith("constraints") or not p:
                self._constraint_gen += 1

    # -- program compilation -------------------------------------------------

    def _make_oracle(self, target: str, kind: str, params: Any):
        """Interpreter-backed helper-function oracle for the symbolic
        compiler: evaluates pure template helpers (canonify_cpu and
        friends) to build per-vocab-entry lookup tables.

        The package node and evaluation context are built once and
        reused across the whole table fill — the fill runs the oracle
        per vocab entry (hundreds of thousands of calls on a large
        corpus), and the shared context's function-result cache also
        memoizes the helpers' own inner calls (mem_multiple & co)."""
        node = self.interp._pkg_node(["templates", target, kind], create=False)
        if node is None:
            return lambda fn_name, value: (None, False)
        ctx = self.interp.make_context({"parameters": params}, {})

        def oracle_fn(fn_name: str, value: Any, extra=None):
            if extra is not None:
                # multi-arg tableized call: consts with the per-vocab
                # value substituted at the symbolic slot
                sym_idx, consts = extra
                call_args = [freeze(c) for c in consts]
                call_args[sym_idx] = freeze(value)
            else:
                call_args = [freeze(value)]
            try:
                v = _call_function(ctx, None, node, fn_name, call_args)
            except RegoError:
                return None, False
            if v is Undefined:
                return None, False
            return thaw(v), True

        return oracle_fn

    def set_external_data(self, system) -> None:
        """Wire the process's ExternalDataSystem (Runner/tests): the
        driver prefetches each batch's deduped keys through it and
        fills the extdata row-feature screens from its cache."""
        self.external_data = system

    def set_metrics(self, metrics) -> None:
        """Late metrics wiring (Runner builds its registry after the
        driver); also re-exports verdicts already analyzed."""
        self.metrics = metrics
        if self.kernel is not None:
            self.kernel.metrics = metrics
        if (
            self.program_store is not None
            and self.program_store.metrics is None
        ):
            self.program_store.metrics = metrics
        for (_t, kind), rep in self._analysis.items():
            self._export_verdict(kind, rep)

    def set_attributor(self, attributor) -> None:
        """Wire an obs.CostAttributor: from here on every dispatch's
        device-execute time is apportioned per constraint."""
        self.attributor = attributor

    def set_integrity(self, plane) -> None:
        """Wire an integrity.IntegrityPlane: from here on every fused
        dispatch packs canary rows into its padding slots and reports
        their verdict digests, and prepare_subset gates warm-swaps on
        a golden-batch replay (docs/robustness.md §Verdict integrity).
        """
        self.integrity = plane
        if plane is not None:
            plane.bind_driver(self)

    def _interp_closure(self, target: str, constraints):
        """Host-interpreter ground-truth closure for canary golden
        derivation: evaluates ONE review against exactly the constraint
        set the fused dispatch serves. Called under the serving mutex
        (reentrant) the first time a signature needs its golden set."""
        def interp(review):
            with self._mutex:
                return RegoDriver._violation(
                    self, target, {"review": review}, None,
                    constraints=list(constraints),
                )
        return interp

    def _canary_pack(
        self, target: str, sigkey: str, constraints, reviews,
        handler, ns_cache, rej_constraints,
    ):
        """Append up to K canary reviews into the padding slots this
        dispatch's shape bucket already wastes (the _stage_corpus
        padding formula — packing canaries never grows the staged
        shape, so the device cost is zero). Returns (reviews,
        canary_autorejects): the caller strips the canary tail from
        the eval split and reports it via _canary_check AFTER releasing
        the serving mutex. Canary autorejects mirror the live path so
        the digest compares against the interpreter golden, which
        includes autoreject results."""
        integ = self.integrity
        if integ is None:
            return reviews, []
        n = len(reviews)
        chunk = min(N_CHUNK, _bucket(n, lo=64))
        padded = chunk * -(-n // chunk)
        slots = padded - n
        if slots <= 0:
            return reviews, []
        try:
            canaries = integ.canaries_for(
                target, sigkey, constraints,
                self._interp_closure(target, constraints), slots,
            )
        except Exception:
            return reviews, []
        if not canaries:
            return reviews, []
        canary_autorej: List[List[Result]] = []
        for r in canaries:
            out: List[Result] = []
            if rej_constraints and handler.review_autorejects(r, ns_cache):
                out = [
                    _autoreject_result(c, r) for c in rej_constraints
                ]
            canary_autorej.append(out)
        return list(reviews) + list(canaries), canary_autorej

    def _canary_check(
        self, target: str, sigkey: str, device, split, canary_autorej,
        subset=None,
    ):
        """Digest-compare the stripped canary verdicts against the
        golden set (off the serving mutex — the plane may trip
        dispatcher quarantine)."""
        integ = self.integrity
        if integ is None or not canary_autorej:
            return
        try:
            integ.check_canaries(
                target, sigkey, device,
                [
                    auto + ev
                    for auto, ev in zip(canary_autorej, split)
                ],
                subset=subset,
            )
        except Exception:
            pass  # integrity accounting must never fail a dispatch

    @staticmethod
    def _static_cost(program) -> float:
        """Analyzer/compiler-derived static cost weight for one
        constraint: program expression rows (the compiled DAG's
        structural signature length plus its constant-tensor payload)
        × row-feature width (each per-row feature plane is another
        device-resident operand the dispatch streams). Interpreter-
        routed constraints (program None) weigh a flat 1 — they cost
        HOST time per matching pair; their device share should read
        ~0, but they must still appear in the table so the target list
        for pruning is complete."""
        if program is None:
            return 1.0
        rows = max(1, len(program.signature))
        consts = 0
        try:
            consts = sum(
                int(np.size(v)) for v in program.consts.values()
            )
        except Exception:
            pass
        width = 1 + len(program.row_features)
        return float((rows + consts) * width)

    def _attribute_dispatch(
        self, cs, device_seconds: float, partition
    ) -> None:
        """Feed one measured device-execute window to the attributor,
        apportioned over `cs`'s constraints by static weight. Called
        under the serving mutex — the attributor does dict math only."""
        if self.attributor is None or device_seconds <= 0.0:
            return
        try:
            entries = []
            for c, prog in zip(cs.constraints, cs.programs):
                meta = c.get("metadata") or {}
                entries.append((
                    str(c.get("kind", "")),
                    str(meta.get("name", "")),
                    self._static_cost(prog),
                ))
            self.attributor.note_dispatch(
                entries, device_seconds, partition=partition
            )
        except Exception:
            pass  # accounting must never fail a dispatch

    def attach_report(
        self, target: str, kind: str, report: VectorizabilityReport
    ) -> None:
        """Re-attach the admission-time analyzer report after a module
        swap. put_modules drops _analysis/_fallback_codes for the kind
        (warm-swap invalidation) and nothing repopulated them until the
        next dispatch lazily re-analyzed — so /readyz verdicts and the
        fallback-code table went blank under churn. Client.add_template
        hands its already-computed report straight back so the verdict
        (and its routing provenance) survives the recompile window."""
        if report is None:
            return
        with self._mutex:
            self._analysis[(target, kind)] = report
            if not report.compilable:
                self._fallback_codes[(target, kind)] = (
                    report.primary_code() or "GK-V007"
                )
        self._export_verdict(kind, report)

    def template_report(
        self, target: str, kind: str
    ) -> Optional[VectorizabilityReport]:
        """The analyzer's verdict for a mounted template (None when the
        kind has no modules mounted). Computed once per module set."""
        key = (target, kind)
        rep = self._analysis.get(key)
        if rep is None:
            mods = self._kind_modules.get(key)
            if mods is None:
                return None
            rep = analyze_modules(kind, mods)
            self._analysis[key] = rep
            self._export_verdict(kind, rep)
        return rep

    def _export_verdict(self, kind: str, rep: VectorizabilityReport):
        if self.metrics is None:
            return
        self.metrics.gauge(
            "template_vectorization", 1, kind=kind, verdict=rep.verdict
        )
        for code in rep.codes:
            n = sum(1 for d in rep.diagnostics if d.code == code)
            self.metrics.gauge(
                "template_analysis_diagnostics", n, kind=kind, code=code
            )

    def _note_fallback(self, kind: str, code: str) -> None:
        if self.metrics is not None:
            self.metrics.record(
                "template_fallback_total", 1, kind=kind, code=code
            )

    def _count(self, name: str, value: float = 1, **tags) -> None:
        """Counter increment alongside the in-object stat counters —
        the Prometheus view of cold_batches/_hot_redispatches/
        _render_errors, incremented at the same sites."""
        if self.metrics is not None:
            self.metrics.record(name, value, **tags)

    def _inventory(self, target: str) -> Any:
        """RegoDriver's frozen inventory, each full freeze of the synced
        tree (a cache miss: a new data version) counted as
        driver_inventory_freezes_total."""
        cached = self._frozen_inv.get(target)
        if cached is None or cached[0] != self._data_version:
            self._count("driver_inventory_freezes_total")
        return super()._inventory(target)

    def _render_cache_put(
        self, cache: Dict[Tuple[int, int], List[Result]],
        key: Tuple[int, int], results: List[Result],
    ) -> None:
        """Bounded insert into a per-target rendered-pair cache:
        oldest-cached pair evicted (dict insertion order) when the
        bound is hit, counted so a soak's leak check can distinguish a
        bounded churning cache from a growing one."""
        if len(cache) >= self.render_cache_max:
            cache.pop(next(iter(cache)), None)
            self._render_cache_evictions += 1
            self._count("driver_render_cache_evictions_total")
        cache[key] = results

    def render_cache_size(self) -> int:
        """Total cached rendered pairs across targets (soak sampling)."""
        return sum(len(c[1]) for c in self._render_cache.values())

    def _program_for(
        self, target: str, constraint: Dict[str, Any]
    ) -> Optional[Program]:
        kind = constraint.get("kind")
        if not isinstance(kind, str):
            return None
        mods = self._kind_modules.get((target, kind))
        if mods is None:
            return None
        params = H.constraint_parameters(constraint)
        key = (target, kind, _params_key(params))
        if key in self._programs:
            return self._programs[key]
        # verdict-first routing: the static analyzer decides whether
        # compilation is even attempted. INTERPRETER/INVALID templates
        # route immediately with their diagnostic code; for templates
        # the analyzer calls compilable, CompileUnsupported is no
        # longer a routing mechanism — it is a counted consistency
        # failure (analyzer promised compilability).
        report = self.template_report(target, kind)
        if report is not None and not report.compilable:
            code = report.primary_code() or "GK-V007"
            self._fallback_codes[(target, kind)] = code
            self._note_fallback(kind, code)
            self._programs[key] = None
            return None
        extdata_feature = None
        if report is not None:
            mode = getattr(report, "extdata_mode", lambda: None)()
            if mode is not None:
                # feature encoding consumed by _extdata_row_bits:
                # extdata:<kind>:<err|all>
                extdata_feature = f"extdata:{kind}:{mode}"
        env = CompilerEnv(
            self.vocab,
            self.patterns,
            self.tables,
            oracle_fn=self._make_oracle(target, kind, params),
            oracle_ns=f"{kind}|{key[2]}",
            oracle_ns_shared=f"{target}|{kind}",
            template_kind=kind,
            extdata_feature=extdata_feature,
        )
        # an actual compile is happening: nothing in memory covered this
        # (target, kind, params) — the plan-diff battery asserts churn
        # of N kinds pays exactly N of these
        self.program_compiles += 1
        self._count("program_store_compiles_total", kind=kind)
        if self.program_store is not None:
            self.program_store.note_miss()
        try:
            prog = compile_program(env, mods, params)
        except CompileUnsupported as e:
            # consistency assertion: analyzer-vs-compiler disagreement
            # is a bug signal, surfaced via counter + metric + log
            self.analyzer_mismatches += 1
            self._fallback_codes[(target, kind)] = CODE_MISMATCH
            self._note_fallback(kind, CODE_MISMATCH)
            if self.metrics is not None:
                self.metrics.record(
                    "analyzer_compile_mismatch_total", 1, kind=kind
                )
            import logging

            logging.getLogger("gatekeeper_tpu.analysis").warning(
                "analyzer/compiler disagreement: %s predicted "
                "compilable but compilation gave up: %s",
                kind,
                e,
            )
            prog = None
        self._programs[key] = prog
        return prog

    # -- constraint-side tensors ---------------------------------------------

    def _constraint_set(self, target: str) -> Optional[_ConstraintSet]:
        cs = self._cset.get(target)
        if cs is not None and cs.constraint_gen == self._constraint_gen:
            return cs
        constraints = self._constraints(target)
        if not constraints:
            self._cset.pop(target, None)
            return None
        ms = self._handler(target).compile_match_specs(
            constraints, self.vocab
        )
        programs = [self._program_for(target, c) for c in constraints]
        # evict programs for (kind, params) pairs no longer referenced by
        # any live constraint — param churn must not accumulate programs
        live = {
            (target, c.get("kind"), _params_key(H.constraint_parameters(c)))
            for c in constraints
        }
        for key in [
            k for k in self._programs if k[0] == target and k not in live
        ]:
            del self._programs[key]
        prog_rows: List[int] = []
        row = 0
        for p in programs:
            if p is None:
                prog_rows.append(-1)
            else:
                prog_rows.append(row)
                row += 1
        fallback_codes = {
            c["kind"]: self._fallback_codes.get((target, c["kind"]))
            for c, p in zip(constraints, programs)
            if p is None and isinstance(c.get("kind"), str)
        }
        cs = _ConstraintSet(
            constraint_gen=self._constraint_gen,
            constraints=constraints,
            ms=matchspec_to_np(ms),
            programs=programs,
            prog_rows=prog_rows,
            fallback_codes={
                k: v or "GK-V007" for k, v in fallback_codes.items()
            },
        )
        self._cset[target] = cs
        return cs

    def constraint_generation(self) -> int:
        return self._constraint_gen

    def constraint_costs(self, target: str) -> Dict[str, float]:
        """Static-cost planner weights: the compiled program's analyzer
        cost (see _static_cost) per constraint key. Lazily compiles via
        the shared `_programs` cache, so a warm driver pays nothing and
        a cold one pays the compile it would pay on first dispatch
        anyway. The partition planner blends these with measured
        attributor seconds when available."""
        with self._mutex:
            return {
                constraint_key(c): self._static_cost(
                    self._program_for(target, c)
                )
                for c in self._constraints(target)
            }

    # -- incremental compile plane (docs/compile.md) -------------------------

    def _ir_hash(self, target: str, kind: str) -> str:
        """Content hash of a template's rewritten IR modules. AST nodes
        are plain dataclasses, so repr() is a stable structural
        rendering; memoized until put/delete_modules drops the kind."""
        key = (target, kind)
        h = self._ir_hashes.get(key)
        if h is None:
            mods = self._kind_modules.get(key)
            h = (
                hashlib.sha256(repr(mods).encode()).hexdigest()[:16]
                if mods is not None
                else ""
            )
            self._ir_hashes[key] = h
        return h

    def _subset_signature(self, target: str, subset: frozenset) -> str:
        """Content signature of one partition's sub-program: per member
        constraint, (key, template IR hash, constraint payload), plus
        the store's machine fingerprint. Two constraint generations
        with equal signatures stage byte-identical sub-programs, which
        is what licenses the carry-forward (no restage, no recompile).
        Memoized per constraint generation (caller holds the mutex)."""
        key = (target, subset)
        hit = self._sig_cache.get(key)
        if hit is not None and hit[0] == self._constraint_gen:
            return hit[1]
        parts = []
        for c in self._constraints(target):
            ck = constraint_key(c)
            if ck not in subset:
                continue
            kind = c.get("kind")
            parts.append((
                ck,
                self._ir_hash(
                    target, kind if isinstance(kind, str) else ""
                ),
                json.dumps(c, sort_keys=True, default=str),
            ))
        parts.sort()
        fp = (
            self.program_store.fp_digest
            if self.program_store is not None
            else ""
        )
        sig = hashlib.sha256(
            json.dumps([fp, parts]).encode()
        ).hexdigest()[:16]
        if len(self._sig_cache) >= 4 * self._cset_sub_max:
            self._sig_cache.pop(next(iter(self._sig_cache)), None)
        self._sig_cache[key] = (self._constraint_gen, sig)
        return sig

    def _golden_key(self, target: str, constraints, signature=None) -> str:
        """Key of a constraint set's golden canary batch: its content
        signature, never the bare generation — goldens persist as
        program-store sidecars, and a later process that reaches the
        same generation with other constraints must not load them."""
        return signature or self._subset_signature(
            target, frozenset(constraint_key(c) for c in constraints)
        )

    def subset_signature(self, target: str, subset) -> str:
        """Public (dispatcher-facing) form of `_subset_signature`."""
        with self._mutex:
            return self._subset_signature(target, frozenset(subset))

    def subset_ready(self, target: str, subset) -> bool:
        """True when `subset`'s sub-program can serve a fused dispatch
        RIGHT NOW without compiling or staging: its constraint set is
        cached with a staged policy and its content signature matches
        the current constraint corpus. Drivers without a device kernel
        have nothing to stage and are always ready. The dispatcher uses
        this to decide sync vs background restage (docs/compile.md)."""
        if not self.use_jax or self.kernel is None:
            return True
        with self._mutex:
            fs = frozenset(subset)
            cs = self._cset_sub.get((target, fs))
            if cs is None or cs.policy is None:
                return False
            if cs.constraint_gen == self._constraint_gen:
                return True
            return (
                cs.signature is not None
                and cs.signature == self._subset_signature(target, fs)
            )

    def swap_generation(self) -> int:
        """Monotonic count of atomic sub-program swaps (prepare_subset
        landing a shadow set live) — /debug/programs surfaces it."""
        return self._swap_gen

    def compile_plane_stats(self) -> Dict[str, Any]:
        """Compile-plane counters + program-store view, the driver side
        of /debug/programs and the compile_storm flight record."""
        with self._mutex:
            out: Dict[str, Any] = {
                "constraint_generation": self._constraint_gen,
                "swap_generation": self._swap_gen,
                "program_compiles": self.program_compiles,
                "subset_swaps": self.subset_swaps,
                "subset_carryforwards": self.subset_carryforwards,
                "analyzer_mismatches": self.analyzer_mismatches,
            }
        store = self.program_store
        if store is not None:
            out["store"] = store.stats()
        return out

    def _subset_cset(
        self, target: str, subset: frozenset
    ) -> Optional[_ConstraintSet]:
        """Partition-scoped _ConstraintSet: only `subset`'s constraints,
        with its own match tensors and (lazily staged) device policy —
        the independently compilable/dispatchable sub-program behind one
        fault domain. Programs come from the shared `_programs` cache
        (a subset never re-compiles what the monolith compiled), and —
        unlike `_constraint_set` — no program eviction runs here: the
        subset view must never evict programs the full set still uses.

        Generation bumps whose content signature is unchanged carry the
        cached set (and its staged policy) forward instead of
        rebuilding: churn elsewhere in the corpus costs THIS partition
        nothing (docs/compile.md)."""
        key = (target, subset)
        cs = self._cset_sub.get(key)
        if cs is not None and cs.constraint_gen == self._constraint_gen:
            return cs
        sig = self._subset_signature(target, subset)
        if cs is not None and cs.signature is not None and cs.signature == sig:
            cs.constraint_gen = self._constraint_gen
            self.subset_carryforwards += 1
            self._count("program_carryforward_total", target=target)
            return cs
        cs = self._build_subset_cset(target, subset, sig)
        if cs is None:
            self._cset_sub.pop(key, None)
            return None
        while len(self._cset_sub) >= self._cset_sub_max:
            self._cset_sub.pop(next(iter(self._cset_sub)), None)
        self._cset_sub[key] = cs
        return cs

    def _build_subset_cset(
        self, target: str, subset: frozenset, sig: Optional[str] = None
    ) -> Optional[_ConstraintSet]:
        """Construct (but do NOT cache) a subset constraint set — the
        shared builder behind `_subset_cset` and `prepare_subset`'s
        shadow slot, which must never replace the live entry before its
        policy is staged."""
        constraints = [
            c for c in self._constraints(target)
            if constraint_key(c) in subset
        ]
        if not constraints:
            return None
        ms = self._handler(target).compile_match_specs(
            constraints, self.vocab
        )
        programs = [self._program_for(target, c) for c in constraints]
        prog_rows: List[int] = []
        row = 0
        for p in programs:
            if p is None:
                prog_rows.append(-1)
            else:
                prog_rows.append(row)
                row += 1
        fallback_codes = {
            c["kind"]: self._fallback_codes.get((target, c["kind"]))
            for c, p in zip(constraints, programs)
            if p is None and isinstance(c.get("kind"), str)
        }
        return _ConstraintSet(
            constraint_gen=self._constraint_gen,
            constraints=constraints,
            ms=matchspec_to_np(ms),
            programs=programs,
            prog_rows=prog_rows,
            fallback_codes={
                k: v or "GK-V007" for k, v in fallback_codes.items()
            },
            signature=(
                sig
                if sig is not None
                else self._subset_signature(target, subset)
            ),
        )

    # -- corpus encoding -----------------------------------------------------

    def _encode_reviews(
        self,
        target: str,
        reviews: List[Any],
        ns_cache: Dict[str, Any],
        vocab: Any = None,
        keep_fn: Optional[Callable[[int], bool]] = None,
    ) -> Tuple[
        Dict[str, np.ndarray], Dict[str, Any], int, np.ndarray, int
    ]:
        """`vocab` overrides the intern target — ephemeral review batches
        pass an OverlayVocab so batch churn never grows the base.
        Review-feature extraction is the target handler's (the K8s and
        agent targets share the engine encoding via their IR reviews).

        `keep_fn` (spath vocab id -> bool) is the IR feature-liveness
        mask: provably-dead token columns are dropped and survivors
        compacted BEFORE the L/G bucketing below, so padding and the
        one-hot group contraction shrink with the live set. Overflow is
        decided by the unfiltered encode (a truncated row already lost
        arbitrary live tokens and must keep routing to the
        interpreter); everything downstream sees only the filtered
        table, so fewer G_CAP clips after filtering is strictly more
        fused coverage, never a verdict change."""
        if vocab is None:
            vocab = self.vocab
        handler = self._handler(target)
        table = encode_token_table(reviews, vocab)
        skipped = 0
        if keep_fn is not None:
            table, skipped = mask_token_table(table, keep_fn)
            if skipped:
                self.columns_skipped_static += skipped
                self.liveness_batches += 1
                self._count(
                    "columns_skipped_static_total", skipped, target=target
                )
        feats = [
            handler.encode_review_features(r, ns_cache, vocab)
            for r in reviews
        ]
        fb = batch_review_features(feats)
        tok = {
            "spath": table.spath,
            "idx0": table.idx0,
            "idx1": table.idx1,
            "kind": table.kind,
            "vid": table.vid,
            "vnum": table.vnum,
        }
        max_i0 = int(np.asarray(table.idx0).max(initial=-1))
        max_i1 = int(np.asarray(table.idx1).max(initial=-1))
        g = _bucket(max(max_i0 + 1, 1), lo=8)
        g1 = _bucket(max(max_i1 + 1, 1), lo=4)
        row_fallback = np.asarray(table.overflow).copy()
        if fb.label_overflow is not None:
            row_fallback |= fb.label_overflow
        if g > G_CAP:
            g = G_CAP
            row_fallback |= (table.idx0 >= G_CAP).any(axis=1)
        if g1 > G_CAP:
            g1 = G_CAP
            row_fallback |= (table.idx1 >= G_CAP).any(axis=1)
        return tok, _features_np(fb), (g, g1), row_fallback, skipped

    def _audit_corpus(
        self, target: str, path: str = "audit"
    ) -> Optional[_Corpus]:
        corpus = self._corpus.get(target)
        if corpus is not None and corpus.data_gen == self._data_gen:
            return corpus
        # any synced-data change drops the corpus: the next sweep
        # re-encodes every cached object
        with stage("driver.corpus_encode", self.metrics,
                   "driver_phase_seconds", phase="corpus_encode",
                   path=path):
            external = self.storage.get(["external", target], {})
            reviews = list(
                self._handler(target).iter_cached_reviews(external)
            )
            if not reviews:
                self._corpus.pop(target, None)
                return None
            ns_cache = self._ns_cache(target)
            tok, fb_dev, (g, g1), row_fallback, _ = self._encode_reviews(
                target, reviews, ns_cache
            )
            corpus = _Corpus(
                data_gen=self._data_gen,
                reviews=reviews,
                tok=tok,
                fb_dev=fb_dev,
                g=g,
                g1=g1,
                row_fallback=row_fallback,
            )
            # classify the freshly interned path entries NOW: callers
            # probe membership (_pattern_tokens) straight after building
            # the corpus
            self.patterns.sync()
            self.tables.sync()
            self._corpus[target] = corpus
        self._count("driver_rows_encoded_total", len(reviews), path=path)
        return corpus

    def _ephemeral_corpus(
        self,
        target: str,
        cs: _ConstraintSet,
        reviews: List[Any],
        ns_cache: Dict[str, Any],
        coarse_feats: bool = False,
    ) -> _Corpus:
        """Encode a review batch against an OverlayVocab and build its
        pattern/table overlay blocks. The base vocab, patterns, and
        tables never change, so steady-state admission pays no global
        table growth, no device re-uploads, and no jit churn — the
        batch ships its own few-hundred-row overlay instead.

        coarse_feats=True (warm path) skips the audit-corpus pre-encode
        that inventory-screen row features normally force — the warmup
        dispatch only needs the right SHAPES, so it uses all-ones
        (route-everything, sound) feature bits instead of stalling the
        serving mutex on a full corpus encode."""
        from ..flatten.vocab import OverlayVocab

        # base must be at its fixed point BEFORE the overlay snapshot,
        # or overlay ids alias base ids assigned later in this call.
        # Inventory-screen row features encode the persistent audit
        # corpus mid-evaluation — pre-encode it now if any program will
        # need it (cached per data generation, so this is one-time).
        if not coarse_feats and any(
            not f.startswith("invkey:")
            for p in cs.programs
            if p is not None
            for f in p.row_features
        ):
            self._audit_corpus(target, path="webhook")
        self.patterns.sync()
        self.tables.sync()
        overlay = OverlayVocab(self.vocab)
        keep_fn = self._liveness_keep_fn(cs, overlay)
        tok, fb_dev, (g, g1), row_fallback, skipped = self._encode_reviews(
            target, reviews, ns_cache, vocab=overlay, keep_fn=keep_fn
        )
        v_base = overlay.base_len
        # fill table rows + pattern rows for overlay entries to a fixed
        # point (transforms and captured segments intern new overlay
        # strings as they go)
        tab_parts: List[Dict[str, np.ndarray]] = []
        mem_parts: List[np.ndarray] = []
        cap_parts: List[np.ndarray] = []
        cur = v_base
        while cur < len(overlay):
            end = len(overlay)
            tab_parts.append(self.tables.fill_overlay(overlay, cur, end))
            m, c = self.patterns.classify_overlay(overlay, cur, end)
            mem_parts.append(m)
            cap_parts.append(c)
            cur = end
        b = len(overlay) - v_base
        b_pad = _bucket(max(b, 1), lo=128)
        p = self.patterns.n_patterns
        ov_member = np.zeros((b_pad, p), bool)
        ov_capture = np.full((b_pad, p), -1, np.int32)
        if b:
            ov_member[:b] = np.concatenate(mem_parts, axis=0)
            ov_capture[:b] = np.concatenate(cap_parts, axis=0)
        ov_tabs: Dict[str, np.ndarray] = {}
        if tab_parts and tab_parts[0]:
            for name in tab_parts[0]:
                col = np.concatenate([t[name] for t in tab_parts])
                padded = np.zeros((b_pad,), col.dtype)
                padded[:b] = col
                ov_tabs[name] = padded
        return _Corpus(
            data_gen=-1,
            reviews=reviews,
            tok=tok,
            fb_dev=fb_dev,
            g=g,
            g1=g1,
            row_fallback=row_fallback,
            vocab=overlay,
            v_base=v_base,
            ov_member=ov_member,
            ov_capture=ov_capture,
            ov_tabs=ov_tabs,
            skipped_static=skipped,
        )

    # -- IR static-analysis plane (analysis/ir.py) ---------------------------

    def _cs_live_pids(self, cs: _ConstraintSet) -> Optional[frozenset]:
        """Live pattern indices over this set's compiled programs,
        computed once per set and cached on it. None means keep-all:
        some program failed the pad-equivalence proof (or the analysis
        itself failed — refuse, never guess)."""
        if cs.live_pids is False:
            from ..analysis.ir import corpus_liveness

            try:
                cs.live_pids = corpus_liveness(cs.programs)
            except Exception:
                cs.live_pids = None
        return cs.live_pids

    def _liveness_keep_fn(
        self, cs: _ConstraintSet, vocab: Any
    ) -> Optional[Callable[[int], bool]]:
        """Token keep-predicate for encoding a batch that only this
        set's programs will read: spath vocab id -> does the path match
        ANY live pattern. None disables filtering (liveness off, or the
        set is not provably maskable). Subset sets get their own
        (tighter) mask — each subset dispatch encodes its own ephemeral
        corpus, so set-scoped liveness is sound."""
        if not self.liveness_enabled:
            return None
        live = self._cs_live_pids(cs)
        if live is None:
            return None
        pat_segs = [self.patterns.segs(p) for p in sorted(live)]
        memo: Dict[int, bool] = {}

        def keep(pid: int) -> bool:
            hit = memo.get(pid)
            if hit is None:
                s = vocab.string(pid)
                if isinstance(s, str) and s.startswith("p:"):
                    segs = s[2:].split(".")
                    hit = any(_match(ps, segs)[0] for ps in pat_segs)
                else:
                    hit = True  # not a path entry: refuse to drop
                memo[pid] = hit
            return hit

        return keep

    def liveness_stats(self) -> Dict[str, Any]:
        """Liveness-plane counters, the driver side of decision facts
        and /debug/partitions."""
        return {
            "enabled": self.liveness_enabled,
            "columns_skipped_static": self.columns_skipped_static,
            "liveness_batches": self.liveness_batches,
        }

    def ir_report(self, target: str):
        """IR static-analysis report (analysis/ir.py IrReport) over the
        target's current compiled constraint set: GK-P0xx diagnostics,
        fused-path taxonomy, liveness summary, and specialization
        certificates. Lazily computed once per constraint generation;
        attach_ir_report pre-populates across warm swaps (the
        attach_report contract)."""
        ent = self._ir_reports.get(target)
        if ent is not None and ent[0] == self._constraint_gen:
            return ent[1]
        from ..analysis.ir import ir_from_programs

        with self._mutex:
            cs = self._constraint_set(target)
            if cs is None:
                return None
            gen = self._constraint_gen
            items = []
            for c, prog in zip(cs.constraints, cs.programs):
                kind = c.get("kind")
                name = (c.get("metadata") or {}).get("name", "")
                items.append(
                    (
                        f"constraint:{kind}/{name}",
                        kind,
                        prog,
                        H.constraint_parameters(c),
                    )
                )
            rep = ir_from_programs(items, fallback_codes=cs.fallback_codes)
            rep.liveness["patterns_total"] = self.patterns.n_patterns
            self._ir_reports[target] = (gen, rep)
        return rep

    def attach_ir_report(self, target: str, report: Any) -> None:
        """Re-attach an already-computed IR report after a module swap,
        so the IR plane (stats.analysis.ir, /debug views) never goes
        blank under churn — the attach_report contract."""
        if report is None:
            return
        with self._mutex:
            self._ir_reports[target] = (self._constraint_gen, report)

    # -- device dispatch -----------------------------------------------------

    def _stage_corpus(self, corpus: _Corpus):
        """Slice/pad the encoded corpus into uniform fixed-shape chunks,
        stack them on a leading chunk axis, and ship to device once
        (StackedCorpus); sweeps then run as ONE device execution against
        resident operands — no host->device traffic and a single
        round-trip in steady state."""
        if corpus.staged is not None:
            return corpus.staged
        n = len(corpus.reviews)
        chunk = min(N_CHUNK, _bucket(n, lo=64))
        if self.metrics is not None:
            # device-batch shape telemetry: bucketed chunk shapes trade
            # padded rows for jit-shape stability — occupancy % and
            # waste rows quantify what that trade costs per staging
            padded = chunk * -(-n // chunk)
            path = "audit" if corpus.data_gen >= 0 else "webhook"
            self.metrics.observe(
                "batch_occupancy_percent", 100.0 * n / padded, path=path
            )
            self.metrics.record(
                "padding_waste_rows_total", padded - n, path=path
            )
        chunks = []
        for start in range(0, n, chunk):
            end = min(start + chunk, n)
            pad = chunk - (end - start)
            fb_c = {
                k: _pad_rows(v[start:end], pad)
                for k, v in corpus.fb_dev.items()
            }
            tok_c = {
                k: _pad_rows(v[start:end], pad, fill=0 if k == "vnum" else -1)
                for k, v in corpus.tok.items()
            }
            chunks.append(
                (fb_c, tok_c, corpus.row_fallback[start:end], end - start)
            )
        ov = None
        if corpus.ov_member is not None:
            ov = {
                "member": corpus.ov_member,
                "capture": corpus.ov_capture,
                "tabs": corpus.ov_tabs,
            }
        corpus.staged = self.kernel.stage_corpus_stacked(
            chunks, ov=ov, v_base=corpus.v_base
        )
        return corpus.staged

    def _stage_need(self, target: str, cs: _ConstraintSet, corpus: _Corpus):
        """Stage the policy, corpus, row features and table operands of
        one sweep: -> (policy, stacked corpus, sweep args). Touches the
        kernel's and tables' shared state, so callers hold the mutex."""
        if cs.policy is None:
            cs.policy = self.kernel.stage_policy(cs.programs, cs.ms)
        stacked = self._stage_corpus(corpus)
        needed = sorted(
            {
                f
                for p in cs.programs
                if p is not None
                for f in p.row_features
            }
        )
        if needed:
            feats = self._row_feature_bits(target, cs, corpus, needed)
            self.kernel.stage_row_feats(
                stacked, feats,
                volatile=[n for n in needed if n.startswith("extdata:")],
            )
        return (
            cs.policy, stacked, self.kernel.need_all_args(cs.policy, stacked)
        )

    def _need_pairs(
        self, target: str, cs: _ConstraintSet, corpus: _Corpus,
        require_compiled: bool = False, staged=None,
    ) -> Tuple[List[Tuple[int, int]], int, int]:
        """Sparse evaluation: -> (review-major (n, c) pairs needing
        interpreter work, compiled_pairs, interp_pairs). With
        require_compiled, raises ColdKernel instead of compiling a
        missing (policy, shape-bucket) jit entry. `staged` is a
        _stage_need result taken under the mutex by a caller that
        dispatches without it (warm_review_path)."""
        from ..parallel.sharding import decode_need

        policy, stacked, args = staged or self._stage_need(target, cs, corpus)
        # named fault point (docs/robustness.md): "error" simulates a
        # failing device dispatch, "hang" a stalled one — exercised by
        # the chaos suite to drive the real degradation ladder
        fire("driver.device_dispatch")
        # the whole sweep: one device execution, one fetch
        packed, hot, n_hot, sc, si = self.kernel.dispatch_need_all(
            policy, stacked, (corpus.g, corpus.g1),
            require_compiled=require_compiled, args=args,
        )
        pairs: List[Tuple[int, int]] = []
        stat_c = int(sc.sum())
        stat_i = int(si.sum())
        for ci in range(stacked.k):
            start = ci * stacked.chunk
            if int(n_hot[ci]) > hot.shape[1]:
                # more violating rows than the compaction window: rare
                # (adversarial corpora); re-dispatch this chunk alone
                p_c, h_c, _nh, _sc, _si = self._redispatch_chunk(
                    policy, corpus, stacked, ci, int(n_hot[ci]),
                    require_compiled=require_compiled,
                )
                n_loc, c_is = decode_need(p_c, h_c, policy.c_pad)
            else:
                n_loc, c_is = decode_need(
                    packed[ci], hot[ci], policy.c_pad
                )
            pairs.extend(zip((start + n_loc).tolist(), c_is.tolist()))
        return pairs, stat_c, stat_i

    def _row_feature_bits(
        self, target: str, cs: _ConstraintSet, corpus: _Corpus,
        names: List[str],
    ) -> Dict[str, np.ndarray]:
        """Per-row screen refinement bits for inventory join templates.

        "invdup:<leaf>:<mirror>:<se>:<guards>" semantics (sound
        over-approximations of the join truth; encoding produced by
        symbolic.Compiler._compile_clause):
          * the row's candidate values are its tokens at the LEAF
            pattern; partners are counted at the MIRROR pattern (the
            partner-side path proved by symbolic._mirror_pattern_for —
            same pattern for self-joins, a "?"-generalized one when the
            inventory walk iterates vars);
          * persistent audit corpus (reviews ARE the inventory): a
            value carried by >=2 distinct rows at the mirror pattern
            can conflict. The threshold 2 is only sound when <se>=1 (a
            proven `not identical(obj, input.review)` guard excludes
            the self-partner) AND the row carries every <guards>
            identity field the proof needs (rows missing one can join
            themselves); otherwise the threshold drops to 1;
          * ephemeral review batch (webhook): the row holds a value at
            the leaf pattern present ANYWHERE in the synced inventory
            at the mirror pattern (exclusions re-checked exactly by the
            interpreter render).

        "invkey:" features do the same for a derived join key, the
        helper's value over the whole object (_invkey_bits).
        """
        if corpus.row_feats is None:
            corpus.row_feats = {}
        out: Dict[str, np.ndarray] = {}
        # alias guard: if the BASE vocab grew past an ephemeral corpus's
        # overlay snapshot (a path _ephemeral_corpus's pre-encode did not
        # anticipate), overlay ids numerically collide with the new base
        # ids and every id comparison below is unsound — degrade to the
        # coarse screen (route everything) instead of guessing
        if corpus.vocab is not None and len(self.vocab) > corpus.v_base:
            ones = np.ones(len(corpus.reviews), bool)
            return {name: ones for name in names}
        for name in names:
            if name.startswith("extdata:"):
                # never cached in row_feats: the bits track the LIVE
                # response cache (TTL expiry between sweeps must route
                # rows back to the interpreter re-check)
                out[name] = self._extdata_row_bits(target, corpus, name)
                continue
            cached = corpus.row_feats.get(name)
            if cached is not None:
                out[name] = cached
                continue
            if name.startswith("invkey:"):
                feat = self._invkey_bits(target, cs, corpus, name)
            else:
                with stage("driver.join_screen", self.metrics,
                           "driver_phase_seconds", phase="join_screen",
                           path="audit" if corpus.data_gen >= 0
                           else "webhook"):
                    feat = self._invdup_bits(target, corpus, name)
            corpus.row_feats[name] = feat
            out[name] = feat
        return out

    def _invdup_bits(
        self, target: str, corpus: _Corpus, name: str
    ) -> np.ndarray:
        """One "invdup:" feature's bits over the corpus's rows
        (_row_feature_bits)."""
        parts = name.split(":")
        leaf_pid, mirror_pid = int(parts[1]), int(parts[2])
        self_excl = parts[3] == "1"
        gpids = [int(x) for x in parts[4].split("+") if x]
        if corpus.data_gen >= 0:
            counts, inv_fb = self._pattern_value_counts(
                corpus, mirror_pid
            )
            # a fallback (token-overflow) row's keys are invisible
            # to the counts: its partner would see count 1 — drop
            # the threshold so single-count carriers still route
            thresh = 2 if (self_excl and not inv_fb) else 1
        else:
            with_inv = self._audit_corpus(target, path="webhook")
            if with_inv is None:
                counts, inv_fb = None, False
            else:
                counts, inv_fb = self._pattern_value_counts(
                    with_inv, mirror_pid
                )
            thresh = 1
        sel, vids = self._pattern_tokens(corpus, leaf_pid)
        if counts is None:
            feat = np.zeros(len(corpus.reviews), bool)
        elif inv_fb and corpus.data_gen < 0:
            # inventory keys partially invisible: reviews cannot be
            # screened against it — route everything (coarse, sound)
            feat = np.ones(len(corpus.reviews), bool)
        else:
            dup = counts >= thresh
            safe = np.minimum(np.maximum(vids, 0), dup.shape[0] - 1)
            hit = sel & (vids >= 0) & (vids < dup.shape[0]) & dup[safe]
            feat = hit.any(axis=1)
            if corpus.data_gen >= 0 and thresh >= 2 and gpids:
                # rows missing a guard identity field void the
                # self-exclusion proof: keep them routed
                has_all = np.ones(len(corpus.reviews), bool)
                for gp in gpids:
                    gsel, gvids = self._pattern_tokens(corpus, gp)
                    has_all &= (gsel & (gvids >= 0)).any(axis=1)
                feat |= ~has_all
        # fallback rows (overflow etc.) must stay routed
        feat |= np.asarray(corpus.row_fallback, bool)
        return feat

    def _invkey_bits(
        self, target: str, cs: _ConstraintSet, corpus: _Corpus, name: str
    ) -> np.ndarray:
        """One "invkey:<kind>:<digest>:<fn>:<tree>:<se>:<guards>"
        feature's bits (symbolic.Compiler._invkey_feature): the clause
        joins fn(input.review.object) with fn(<object walked in tree>).

        Persistent audit corpus: the key index the pruned render reads
        (_keyed_index, once per data generation) holds every object's
        key, so a row's key is its own synced copy's (the corpus reviews
        hold the tree's objects). A row is set when two or more objects
        carry its key, or one does and the self-exclusion proof fails
        for it: <se>=0, or the row's review disagrees with its object
        on a guard identity field (_identity_holds). A row whose key is
        undefined cannot join (0); a row outside the indexed tree keeps
        the coarse bit. Ephemeral webhook batches get the coarse screen
        (all ones): the interpreter render re-checks every matched row.
        """
        n = len(corpus.reviews)
        parts = name.split(":")
        owner = next(
            (
                c for c, p in zip(cs.constraints, cs.programs)
                if p is not None and name in p.row_features
            ),
            None,
        )
        if (
            corpus.data_gen < 0
            or corpus.data_gen != self._data_gen
            or len(parts) != 7
            or owner is None
        ):
            return np.ones(n, bool)
        fn, tree, self_excl = parts[3], parts[4], parts[5] == "1"
        kind = owner.get("kind")
        params = H.constraint_parameters(owner)
        index, keys = self._keyed_index(
            target, kind, params, {"fn": fn, "tree": tree}, "audit"
        )
        with stage("driver.join_screen", self.metrics,
                   "driver_phase_seconds", phase="join_screen",
                   path="audit"):
            checks = []
            for item in parts[6].split("+") if parts[6] else ():
                lhs, rhs = item.split("=", 1)
                helper = None
                if rhs.endswith(")"):
                    helper, rhs = rhs[:-1].split("(", 1)
                checks.append((
                    self.patterns.segs(int(lhs)),
                    self.patterns.segs(int(rhs)),
                    helper,
                ))
            oracle = self._prune_oracle(target, kind, params)
            memo: Dict[Tuple[str, str], Any] = {}
            feat = np.zeros(n, bool)
            for i, review in enumerate(corpus.reviews):
                obj = review.get("object") if isinstance(review, dict) else None
                key = keys.get(id(obj), _UNINDEXED)
                if key is _UNINDEXED:
                    feat[i] = True
                elif key is not _UNDEFINED_KEY:
                    carriers = len(index[key])
                    feat[i] = carriers >= 2 or (
                        carriers == 1 and not (
                            self_excl and self._identity_holds(
                                review, checks, oracle, memo
                            )
                        )
                    )
            # fallback rows (overflow etc.) must stay routed
            feat |= np.asarray(corpus.row_fallback, bool)
        return feat

    @staticmethod
    def _identity_holds(review, checks, oracle, memo) -> bool:
        """Does `not identical(<review's own object>, review)` fail, by
        the guard's equalities (symbolic._self_identity_checks)? Each
        side is a string at a review-rooted path, the right one through
        its pure helper when named (memoised per argument)."""
        for lhs, rhs, helper in checks:
            a = _walk_segs(review, lhs)
            b = _walk_segs(review, rhs)
            if helper is not None and b is not _MISSING:
                mk = (helper, json.dumps(b, sort_keys=True, default=str))
                if mk not in memo:
                    v, defined = oracle(helper, b)
                    memo[mk] = v if defined else _MISSING
                b = memo[mk]
            if not isinstance(a, str) or a != b:
                return False
        return True

    def _pattern_tokens(self, corpus: _Corpus, pid: int):
        member = np.asarray(self.patterns.member)
        spath = corpus.tok["spath"]
        vids = corpus.tok["vid"]
        width = member.shape[1]
        safe = np.minimum(np.maximum(spath, 0), max(width - 1, 0))
        sel = (spath >= 0) & (spath < width) & member[pid][safe]
        if corpus.ov_member is not None:
            # ephemeral batches carry overlay path entries (novel label/
            # annotation keys) whose membership lives in the batch blocks
            loc = spath - corpus.v_base
            b = corpus.ov_member.shape[0]
            safe_loc = np.clip(loc, 0, max(b - 1, 0))
            ov = (loc >= 0) & (loc < b) & corpus.ov_member[safe_loc, pid]
            sel = np.where(loc >= 0, ov, sel)
        return sel, vids

    def _pattern_value_counts(self, corpus: _Corpus, pid: int):
        """-> ([V] int distinct-row counts per value id at tokens
        matching pattern `pid`, any_fallback_rows). Cached on the corpus
        (the ephemeral webhook path reuses the persistent inventory's
        counts across requests)."""
        if corpus.value_counts is None:
            corpus.value_counts = {}
        cached = corpus.value_counts.get(pid)
        if cached is not None:
            return cached
        sel, vids = self._pattern_tokens(corpus, pid)
        valid = sel & (vids >= 0)
        rows, cols = np.nonzero(valid)
        tv = vids[rows, cols]
        if tv.size == 0:
            counts = np.zeros((len(self.vocab),), np.int64)
        else:
            pairs = np.unique(rows.astype(np.int64) * (tv.max() + 1) + tv)
            uniq_vids = pairs % (tv.max() + 1)
            counts = np.bincount(uniq_vids, minlength=len(self.vocab))
        result = (counts, bool(np.asarray(corpus.row_fallback).any()))
        corpus.value_counts[pid] = result
        return result

    # -- external data (docs/externaldata.md) --------------------------------

    def _extdata_row_bits(
        self, target: str, corpus: _Corpus, name: str
    ) -> np.ndarray:
        """Per-row screen bits for an "extdata:<kind>:<mode>" feature.

        Key extraction (analyzer-recorded input-derived keys
        expressions, evaluated per review) is cached on the corpus; the
        batch's deduped union feeds ONE system.prefetch per dispatch —
        that call IS the one-outbound-fetch-per-(provider, batch)
        contract for the fused path. Bits:
          * mode "err" (provably error-gated templates): True iff some
            key of the row is NOT a clean cache hit — clean rows can
            never produce an error entry, so they stay fused;
          * mode "all": all-ones (the feature exists to drive
            prefetch; violations may depend on response values, so
            every matching row re-checks).
        """
        n = len(corpus.reviews)
        ones = np.ones(n, bool)
        # warm_review_path seeds coarse all-ones bits: the warmup batch
        # only needs the right SHAPES, and its synthetic reviews must
        # never leak warmup keys into a real provider fetch
        if corpus.row_feats and name in corpus.row_feats:
            return corpus.row_feats[name]
        system = self.external_data
        parts = name.split(":")
        kind = parts[1] if len(parts) > 1 else ""
        mode = parts[2] if len(parts) > 2 else "all"
        if system is None or not kind:
            return ones
        report = self.template_report(target, kind)
        calls = getattr(report, "external_calls", None) if report else None
        if not calls:
            return ones
        if corpus.ext_keys is None:
            corpus.ext_keys = {}
        per_row = corpus.ext_keys.get(name)
        if per_row is None:
            from ..externaldata.extract import extract_keys

            per_row = []
            for review in corpus.reviews:
                wants: Optional[Dict[str, set]] = {}
                for call in calls:
                    if not call.extractable or not call.provider:
                        wants = None
                        break
                    keys = extract_keys(self.interp, call, review)
                    if keys is None:
                        wants = None
                        break
                    wants.setdefault(call.provider, set()).update(keys)
                per_row.append(wants)
            corpus.ext_keys[name] = per_row
        union: Dict[str, set] = {}
        for wants in per_row:
            if wants:
                for p, ks in wants.items():
                    union.setdefault(p, set()).update(ks)
        if union:
            system.prefetch(union)
        if mode != "err":
            return ones
        bits = np.zeros(n, bool)
        for i, wants in enumerate(per_row):
            if wants is None:
                bits[i] = True  # unextractable row: route it (sound)
                continue
            for p, ks in wants.items():
                if any(not system.probe_clean(p, k) for k in ks):
                    bits[i] = True
                    break
        bits |= np.asarray(corpus.row_fallback, bool)
        return bits

    def _prefetch_external(self, target: str, reviews: Sequence[Any]):
        """Batch-plane prefetch for every external-data template in the
        constraint set: extract + dedupe the batch's keys, then at most
        one outbound fetch per provider. Best-effort — resolution
        answers failures per the provider's failurePolicy."""
        system = self.external_data
        if system is None:
            return
        try:
            from ..externaldata.extract import batch_wants

            wants_total: Dict[str, set] = {}
            with self._mutex:
                for (t, kind) in list(self._kind_modules):
                    if t != target:
                        continue
                    rep = self.template_report(t, kind)
                    calls = getattr(rep, "external_calls", None)
                    if not calls:
                        continue
                    w = batch_wants(self.interp, calls, reviews)
                    if w:
                        for p, ks in w.items():
                            wants_total.setdefault(p, set()).update(ks)
            if wants_total:
                # OUTSIDE the serving mutex: a slow provider must stall
                # only this batch, never the whole admission plane
                system.prefetch(wants_total)
        except Exception:
            pass

    def _redispatch_chunk(self, policy, corpus: _Corpus, stacked, ci: int,
                          n_hot: int, require_compiled: bool = False):
        """Overflow path: one chunk had more violating rows than the
        compaction window — rerun just that chunk with room. The row
        feature planes ride along (ADVICE r3: dropping them widens the
        screen, so the rerun could flag more hot rows than the refined
        n_hot the cap was sized from); the cap still doubles until the
        rerun's own n_hot fits, so no hot row is ever truncated."""
        from ..parallel.sharding import StagedBatch

        self._hot_redispatches += 1
        self._count("driver_hot_redispatch_total")
        r_cap = 1 << (n_hot - 1).bit_length()
        batch = StagedBatch(
            fb_dev={k: v[ci] for k, v in stacked.fb_dev.items()},
            tok_dev={k: v[ci] for k, v in stacked.tok_dev.items()},
            row_fb=stacked.row_fb[ci],
            n_valid=stacked.n_valids[ci],
            key=("chunkview", stacked.key, stacked.chunk),
        )
        row_in = {
            k: v[ci] for k, v in (stacked.row_dev or {}).items()
        }
        while True:
            out = self.kernel.dispatch_need(
                policy, batch, (corpus.g, corpus.g1), r_cap=r_cap, row_in=row_in,
                ov_in=stacked.ov_dev, v_base=stacked.v_base,
                require_compiled=require_compiled,
            )
            if out[2] <= min(r_cap, stacked.chunk):
                return out
            r_cap = min(2 * r_cap, stacked.chunk)

    def _need_pairs_np(self, target, cs, corpus, ns_cache, n):
        """Numpy path (use_jax=False): same pair semantics, eager host
        eval — used by tests that pin device/host equivalence."""
        fire("driver.device_dispatch")
        compiled = [p for p in cs.programs if p is not None]
        handler = self._handler(target)
        match = np.zeros((len(cs.constraints), n), bool)
        for i, c in enumerate(cs.constraints):
            for j, r in enumerate(corpus.reviews):
                match[i, j] = handler.matches_constraint(c, r, ns_cache)
        prog_rows_arr = np.asarray(cs.prog_rows, np.int64)
        compiled_c = prog_rows_arr >= 0
        row_fb = np.asarray(corpus.row_fallback[:n], bool)
        viol = np.zeros((len(cs.constraints), n), bool)
        if compiled:
            overlay = _corpus_overlay(corpus)
            needed = sorted(
                {f for p in compiled for f in p.row_features}
            )
            row = (
                self._row_feature_bits(target, cs, corpus, needed)
                if needed
                else None
            )
            counts = np.stack(
                [self.evaluator.eval_np(
                    p, corpus.tok, g=(corpus.g, corpus.g1), overlay=overlay,
                    row=row)
                 for p in compiled],
                axis=0,
            )
            viol[compiled_c] = counts[prog_rows_arr[compiled_c]] > 0
        fallback_pair = ~compiled_c[:, None] | row_fb[None, :]
        need = match & (viol | fallback_pair)
        pairs = [(int(a), int(b)) for a, b in np.argwhere(need.T)]
        stat_c = int((match & ~fallback_pair).sum())
        stat_i = int((match & fallback_pair).sum())
        return pairs, stat_c, stat_i

    # -- hook overrides ------------------------------------------------------

    def _violation(
        self, target: str, input: Dict[str, Any], trace: Optional[List[str]]
    ) -> List[Result]:
        review = H.hook_get_default(input, "review", {})
        handler = self._handler(target)
        constraints = self._constraints(target)
        if not constraints:
            return []
        ns_cache = self._ns_cache(target)
        results: List[Result] = []
        if handler.review_autorejects(review, ns_cache):
            for constraint in constraints:
                if handler.constraint_needs_context(constraint):
                    results.append(_autoreject_result(constraint, review))
                    if trace is not None:
                        trace.append(f"autoreject: {_cname(constraint)}")
        results.extend(
            self._eval_reviews(target, [review], trace, corpus=None)
        )
        return results

    def query_many(
        self, path: str, inputs: Sequence[Any], tracing: bool = False
    ) -> List[Response]:
        """Batched violation hook: every review in `inputs` evaluates in
        one fused device dispatch (the webhook micro-batch path). Other
        hooks and tracing queries fall back to the serial default."""
        from .driver import _HOOK_RE

        self._local.route = None
        self._local.stats = {}
        m = _HOOK_RE.match(path)
        if (
            m is None
            or m.group(2) != "violation"
            or tracing
            or not self.use_jax
        ):
            return super().query_many(path, inputs, tracing)
        target = m.group(1)
        if self.external_data is not None:
            # batch plane: open a fresh fetch epoch and prefetch the
            # batch's deduped keys (one outbound fetch per provider)
            # BEFORE routing — both the fused screen and the
            # interpreter rung then serve from the response cache
            self.external_data.begin_batch()
            self._prefetch_external(
                target,
                [H.hook_get_default(i or {}, "review", {}) for i in inputs],
            )
        cold = len(inputs) >= MIN_DEVICE_BATCH and not self.review_path_warm(
            target
        )
        if cold:
            # serve-while-compiling: don't block this batch on a jit
            # compile (tens of seconds cold) — serve it on the
            # interpreter and compile in the background; once warm the
            # route swaps to the fused path
            self.cold_batches += 1
            self._count("driver_cold_batches_total")
            self._kick_warm(target, inputs)
        if cold or len(inputs) < MIN_DEVICE_BATCH:
            # adaptive routing: a tiny batch finishes faster on the
            # serial interpreter than a device round trip would take
            # (results are bit-identical by the driver-parity contract)
            self._local.route = "cold" if cold else "interp"
            with self._serving_mutex("webhook"), stage("driver.interp"):
                return [
                    Response(
                        target=target,
                        results=RegoDriver._violation(
                            self, target, i or {}, None
                        ),
                    )
                    for i in inputs
                ]
        self._local.route = "fused"
        return self._query_many_device(target, inputs)

    def last_route(self) -> Optional[str]:
        """The route the calling thread's last `query_many` took:
        `fused` (device dispatch), `interp` (a batch under
        MIN_DEVICE_BATCH on the interpreter), `cold` (a device-sized
        batch served on the interpreter while its kernels compile), or
        None (not a fused-capable query)."""
        return getattr(self._local, "route", None)

    def thread_stats(self) -> Dict[str, Any]:
        """The calling thread's last evaluation's `stats`, with the
        perf_counter stamps of its stages under `phase_stamps`; {} when
        its last `query_many` ran no fused evaluation. Unlike `stats`,
        another thread's query never overwrites it."""
        return getattr(self._local, "stats", None) or {}

    @contextmanager
    def _serving_mutex(self, path: str):
        """Hold the serving mutex, observing the time spent blocked for
        it in `driver_phase_seconds{phase=lock_wait,path}` (0 when it
        was free). For the outermost acquisition of a review or audit
        query only: inner ones are re-entrant and never wait."""
        wait = 0.0
        if not self._mutex.acquire(blocking=False):
            t0 = _time.perf_counter()
            self._mutex.acquire()
            wait = _time.perf_counter() - t0
        try:
            if self.metrics is not None:
                self.metrics.observe(
                    "driver_phase_seconds", wait, phase="lock_wait",
                    path=path,
                )
            yield
        finally:
            self._mutex.release()

    def query(
        self, path: str, input: Any = None, tracing: bool = False
    ) -> Response:
        m = _HOOK_RE.match(path)
        if m is None or m.group(2) != "audit":
            return super().query(path, input, tracing)
        with self._serving_mutex("audit"):
            return super().query(path, input, tracing)

    def query_host(
        self, path: str, input: Any = None, subset=None
    ) -> Response:
        """The host-oracle rung of the degradation ladder: evaluate on
        the INTERPRETER, never touching the device — the path the
        webhook's circuit breaker degrades to when the fused dispatch
        is failing (a faulted device must not be paid a second doomed
        attempt per request). `subset` scopes the evaluation to one
        partition's constraints (docs/robustness.md §Fault domains), so
        a single sick device degrades ONLY its constraint subset to the
        interpreter while every other partition stays fused. Results
        are bit-identical to the fused path by the driver-parity
        contract."""
        m = _HOOK_RE.match(path)
        if m is None:
            raise ValueError(f"unsupported query path: {path!r}")
        target, hook = m.group(1), m.group(2)
        with self._mutex:
            if hook == "violation":
                constraints = None
                if subset is not None:
                    sub = frozenset(subset)
                    constraints = [
                        c for c in self._constraints(target)
                        if constraint_key(c) in sub
                    ]
                results = RegoDriver._violation(
                    self, target, input or {}, None,
                    constraints=constraints,
                )
            else:
                results = RegoDriver._audit(self, target, None)
        return Response(target=target, results=results)

    # -- partitioned dispatch (docs/robustness.md §Fault domains) ------------

    def query_many_subset(
        self, path: str, inputs: Sequence[Any], subset, device: int = 0,
        partition=None,
    ) -> List[Response]:
        """Partition-scoped fused dispatch: evaluate ONLY `subset`'s
        constraints for every input, as one device execution attributed
        to logical `device`. The device-labeled fault point
        (`driver.device_dispatch[device=N]`) gates the whole partition
        dispatch, so the chaos suite can sicken exactly one fault
        domain. Small batches keep the adaptive interpreter route (same
        policy as `query_many`; results identical by the parity
        contract). Merged across a plan's partitions, results are
        bit-identical to the monolithic dispatch
        (`parallel.partition.merge_partition_results` + the partition
        parity battery)."""
        m = _HOOK_RE.match(path)
        if m is None or m.group(2) != "violation":
            raise ValueError(f"unsupported partition query path: {path!r}")
        target = m.group(1)
        fire(device_point("driver.device_dispatch", device))
        self._local.stats = {}
        with self._serving_mutex("webhook"):
            cs = self._subset_cset(target, frozenset(subset))
            if cs is None:
                return [
                    Response(target=target, results=[]) for _ in inputs
                ]
            if (
                self.use_jax
                and len(inputs) < MIN_DEVICE_BATCH
                and len(inputs) * len(cs.constraints) < MIN_DEVICE_PAIRS
            ):
                # adaptive routing, pair-aware (MIN_DEVICE_PAIRS): a
                # tiny SPARSE batch finishes faster on the serial
                # interpreter than a device round trip would take, but
                # a mask-sliced sub-batch is dense — few reviews, many
                # matching constraints — and belongs on the device
                with stage("driver.interp"):
                    return [
                        Response(
                            target=target,
                            results=RegoDriver._violation(
                                self, target, i or {}, None,
                                constraints=cs.constraints,
                            ),
                        )
                        for i in inputs
                    ]
            handler = self._handler(target)
            ns_cache = self._ns_cache(target)
            reviews = [
                H.hook_get_default(i or {}, "review", {}) for i in inputs
            ]
            rej_constraints = [
                c for c in cs.constraints
                if handler.constraint_needs_context(c)
            ]
            autorejects: List[List[Result]] = []
            for review in reviews:
                out: List[Result] = []
                if rej_constraints and handler.review_autorejects(
                    review, ns_cache
                ):
                    out = [
                        _autoreject_result(c, review)
                        for c in rej_constraints
                    ]
                autorejects.append(out)
            # verdict-integrity canaries ride in the padding slots this
            # bucket already wastes; their results are stripped below,
            # BEFORE any merge — a canary verdict is never a policy
            # outcome (docs/robustness.md §Verdict integrity)
            n_live = len(reviews)
            sigkey = self._golden_key(target, cs.constraints, cs.signature)
            reviews, canary_autorej = self._canary_pack(
                target, sigkey, cs.constraints, reviews,
                handler, ns_cache, rej_constraints,
            )
            split = self._eval_reviews_split(
                target, reviews, None, None, cset=cs,
                partition=(partition if partition is not None else device),
            )
            canary_split = split[n_live:]
            split = split[:n_live]
        self._canary_check(
            target, sigkey, device, canary_split, canary_autorej,
            subset=frozenset(subset),
        )
        return [
            Response(target=target, results=auto + ev)
            for auto, ev in zip(autorejects, split)
        ]

    def prepare_subset(self, path: str, subset, device: int = 0) -> bool:
        """Stage one partition's sub-program onto its device: build the
        subset constraint set and upload its policy tensors. This is
        the restage step of quarantine re-homing — the device-labeled
        fault point (`driver.restage[device=N]`) makes restage failure
        injectable, and the quarantine manager retries with backoff
        while the subset serves from the host rung.

        Incremental compile plane (docs/compile.md): a signature-
        unchanged subset carries its staged policy across the
        generation bump (no restage). A changed subset builds a SHADOW
        constraint set, stages its policy OFF the serving mutex — in-
        flight batches keep dispatching the old sub-program meanwhile —
        then atomically swaps it live. The `compile.swap` fault point
        sits between stage and swap: an injected mid-swap failure
        leaves the old sub-program serving. Returns False (not an
        error) when the corpus churned again mid-stage — the caller's
        next restage pass picks up the newer generation."""
        m = _HOOK_RE.match(path)
        if m is None or m.group(2) != "violation":
            raise ValueError(f"unsupported partition query path: {path!r}")
        target = m.group(1)
        fs = frozenset(subset)
        fire(device_point("driver.restage", device))
        with self._mutex:
            gen = self._constraint_gen
            cs = self._cset_sub.get((target, fs))
            if cs is not None and cs.constraint_gen != gen:
                sig = self._subset_signature(target, fs)
                if cs.signature is not None and cs.signature == sig:
                    cs.constraint_gen = gen
                    self.subset_carryforwards += 1
                    self._count("program_carryforward_total", target=target)
                else:
                    cs = None
            if cs is not None:
                if (
                    self.use_jax
                    and self.kernel is not None
                    and cs.policy is None
                ):
                    cs.policy = self.kernel.stage_policy(cs.programs, cs.ms)
                return True
            shadow = self._build_subset_cset(target, fs)
            if shadow is None:
                self._cset_sub.pop((target, fs), None)
                return True
        # OFF the mutex: the policy upload / XLA compile — the live
        # entry (if any) keeps serving fused dispatches throughout
        if self.use_jax and self.kernel is not None:
            shadow.policy = self.kernel.stage_policy(
                shadow.programs, shadow.ms
            )
        # golden swap gate (docs/robustness.md §Verdict integrity): the
        # staged shadow sub-program must reproduce the golden canary
        # digests before it may swap live — a corrupting compile or a
        # bad staged tensor is rejected here, with the OLD sub-program
        # still serving
        if self.integrity is not None and shadow.constraints:
            if not self._golden_gate(target, shadow):
                self._count(
                    "program_swap_rejected_total",
                    reason="golden_mismatch", target=target,
                )
                return False
        # mid-swap fault point: failure here must leave the old
        # sub-program live (tests/test_compile_plane.py)
        fire("compile.swap")
        with self._mutex:
            if self._constraint_gen != gen:
                return False
            while len(self._cset_sub) >= self._cset_sub_max:
                self._cset_sub.pop(next(iter(self._cset_sub)), None)
            self._cset_sub[(target, fs)] = shadow
            self._swap_gen += 1
            self.subset_swaps += 1
            self._count("program_swap_total", target=target)
        if self.program_store is not None:
            try:
                self.program_store.attest()
            except Exception:
                pass
        return True

    def _golden_gate(self, target: str, shadow) -> bool:
        """Warm-swap integrity gate: replay the golden canary batch
        through the STAGED (not yet live) sub-program and digest-compare
        against the interpreter-pinned golden set. The
        `integrity.selftest` fault point injects a corrupting shadow
        slot. A shape bucket with no compiled kernel yet skips the
        fused replay (ColdKernel) — the canary tier catches corruption
        on the first live dispatch instead."""
        from ..integrity.canary import result_digest
        from ..parallel.sharding import ColdKernel

        integ = self.integrity
        sigkey = self._golden_key(
            target, shadow.constraints, shadow.signature
        )
        try:
            entry = integ.golden_for(
                target, sigkey, shadow.constraints,
                self._interp_closure(target, shadow.constraints),
            )
        except Exception:
            entry = None
        if entry is None or not entry.get("reviews"):
            return True
        try:
            fire("integrity.selftest")
        except FaultError:
            return False
        with self._mutex:
            handler = self._handler(target)
            ns_cache = self._ns_cache(target)
        rej_constraints = [
            c for c in shadow.constraints
            if handler.constraint_needs_context(c)
        ]
        try:
            split = self._eval_reviews_split(
                target, list(entry["reviews"]), None, None,
                require_compiled=True, cset=shadow,
            )
        except ColdKernel:
            return True
        except Exception:
            return False
        got = []
        for review, ev in zip(entry["reviews"], split):
            auto: List[Result] = []
            if rej_constraints and handler.review_autorejects(
                review, ns_cache
            ):
                auto = [
                    _autoreject_result(c, review) for c in rej_constraints
                ]
            got.append(result_digest(auto + ev))
        return got == entry["digests"][: len(got)]

    # -- serve-while-compiling (cold-start) ----------------------------------

    def review_path_warm(self, target: str) -> bool:
        """True when the fused review dispatch is compiled for the
        CURRENT constraint generation (numpy mode has no compile)."""
        if not self.use_jax:
            return True
        return self._review_warm.get(target) == self._constraint_gen

    def _kick_warm(self, target: str, inputs: Sequence[Any]) -> None:
        """Start (at most one per target) a background compile of the
        fused review path, shaped by the live batch that found it cold."""
        with self._warm_lock:
            if target in self._warming:
                return
            self._warming.add(target)
        reviews = [
            H.hook_get_default(i or {}, "review", {}) for i in inputs
        ]

        def run():
            try:
                self.warm_review_path(target, reviews)
            except Exception:
                pass  # best-effort; the next cold batch re-kicks
            finally:
                with self._warm_lock:
                    self._warming.discard(target)

        # NON-daemon: the interpreter joins it at exit. A daemon thread
        # killed mid-XLA-compile during teardown aborts the whole
        # process (SIGABRT, 'FATAL: exception not rethrown') — a passing
        # test run or a finished bench child would die rc=134.
        _threading.Thread(
            target=run, name=f"gk-warm-{target}", daemon=False
        ).start()

    def warm_review_path(
        self, target: str, reviews: Sequence[Any]
    ) -> bool:
        """Compile the fused review dispatch for `reviews`' batch/shape
        buckets WITHOUT holding the serving mutex during the compile.

        Phase 1 (under the mutex, fast): snapshot the compiled programs
        into a throwaway _ConstraintSet and encode the ephemeral corpus
        with COARSE (all-ones) row-feature bits — the jit is shaped by
        the presence of feature planes, not their values, so the warm
        never stalls the mutex on a full audit-corpus encode (ADVICE
        r5 review). Phase 2 (lock-free): run the device dispatch — XLA
        compilation happens here while the interpreter keeps serving.
        Phase 3 (under the mutex): mark the route warm iff the
        constraint generation is unchanged — the atomic swap. Phase 4
        (under the mutex, best-effort): precompute the audit corpus +
        true feature bits so the first REAL device batch doesn't pay
        that one-time encode inline."""
        if not self.use_jax:
            return True
        reviews = list(reviews)
        if not reviews:
            return False
        with self._mutex:
            gen = self._constraint_gen
            cs_live = self._constraint_set(target)
            if cs_live is None:
                # nothing to compile: an empty policy set serves warm
                self._review_warm[target] = gen
                return True
            ns_cache = self._ns_cache(target)
            cs = _ConstraintSet(
                constraint_gen=cs_live.constraint_gen,
                constraints=cs_live.constraints,
                ms=cs_live.ms,
                programs=cs_live.programs,
                prog_rows=cs_live.prog_rows,
                # reuse the staged policy when present (read-only device
                # state, content-keyed): re-staging per warm re-uploads
                # ms_dev/stacked_consts into a throwaway for nothing
                policy=cs_live.policy,
            )
            corpus = self._ephemeral_corpus(
                target, cs, reviews, ns_cache, coarse_feats=True
            )
            self.patterns.sync()
            self.tables.sync()
            needed = sorted(
                {
                    f
                    for p in cs.programs
                    if p is not None
                    for f in p.row_features
                }
            )
            if needed:
                # coarse bits: route everything (sound); shapes match
                # the real dispatch so the compile is reusable
                ones = np.ones(len(corpus.reviews), bool)
                corpus.row_feats = {name: ones for name in needed}
            # staging syncs the shared vocab tables and device-resident
            # table buffers: it runs here, under the mutex, so the
            # lock-free compile below races no audit sweep
            try:
                staged = self._stage_need(target, cs, corpus)
            except Exception as e:
                self.last_warm_error = e
                return False
        try:
            # named fault point: "hang" simulates an XLA compile stall
            # (tens of seconds is realistic), "error" a compile failure
            # — the serving route must stay on the interpreter either way
            fire("driver.compile")
            self._need_pairs(target, cs, corpus, staged=staged)
        except Exception as e:
            # serving stays on the interpreter; the cause stays here
            self.last_warm_error = e
            return False
        warmed = False
        with self._mutex:
            if self._constraint_gen == gen:
                self._review_warm[target] = gen
                warmed = True
        # extdata bits are volatile (they track the live response
        # cache) and extraction on warmup reviews would leak synthetic
        # warmup keys into a real provider fetch — only the
        # corpus-constant invdup bits are worth precomputing here
        precompute = [n for n in needed if not n.startswith("extdata:")]
        if warmed and precompute:
            # pay the one-time audit-corpus encode + true feature bits
            # HERE (background thread) rather than inline in the first
            # real device batch; admission briefly queues behind this
            # acquisition, which is the pre-existing per-data-generation
            # cost — not the per-boot compile this method removes
            try:
                with self._mutex:
                    real = self._ephemeral_corpus(
                        target, cs, reviews[:1], self._ns_cache(target)
                    )
                    self._row_feature_bits(target, cs, real, precompute)
            except Exception:
                pass
        # content-address + attest whatever the compile just landed in
        # the XLA cache dir, so identical machines can adopt it
        if warmed and self.program_store is not None:
            try:
                self.program_store.attest()
            except Exception:
                pass
        return warmed

    def _query_many_device(
        self, target: str, inputs: Sequence[Any]
    ) -> List[Response]:
        with self._serving_mutex("webhook"):
            # the dispatch's preparation: autoreject factors and the
            # integrity canaries packed into the padding slots
            with stage("driver.prepare"):
                handler = self._handler(target)
                constraints = self._constraints(target)
                ns_cache = self._ns_cache(target)
                reviews = [
                    H.hook_get_default(i or {}, "review", {}) for i in inputs
                ]
                # autoreject factors (match.needs_ns_selector docstring):
                # the constraint half is per CONSTRAINT, the cache-miss half
                # per REVIEW — O(R + C), not the O(R x C) loop the predicate
                # naively implies (VERDICT r2 weak #9)
                rej_constraints = [
                    c for c in constraints
                    if handler.constraint_needs_context(c)
                ]
                autorejects: List[List[Result]] = []
                for review in reviews:
                    out: List[Result] = []
                    if rej_constraints and handler.review_autorejects(
                        review, ns_cache
                    ):
                        out = [
                            _autoreject_result(constraint, review)
                            for constraint in rej_constraints
                        ]
                    autorejects.append(out)
                from ..parallel.sharding import ColdKernel

                # monolithic dispatch canaries: same padding-slot packing
                # as the partitioned path, golden keyed by the whole set's
                # content signature, all attributed to logical device 0
                n_live = len(reviews)
                sigkey = self._golden_key(target, constraints)
                reviews, canary_autorej = self._canary_pack(
                    target, sigkey, constraints, reviews,
                    handler, ns_cache, rej_constraints,
                )
            try:
                split = self._eval_reviews_split(
                    target, reviews, None, None, require_compiled=True
                )
                canary_split = split[n_live:]
                split = split[:n_live]
            except ColdKernel:
                # novel shape bucket before its kernel compiled: serve
                # this batch on the interpreter and compile it in the
                # background (holding every admission on an inline XLA
                # compile would blow the webhook deadline)
                self.cold_batches += 1
                self._count("driver_cold_batches_total")
                self._local.route = "cold"
                self._kick_warm(target, inputs)
                with stage("driver.interp"):
                    split = [
                        RegoDriver._violation(self, target, i or {}, None)
                        for i in inputs
                    ]
                # interp route already emits autoreject results
                return [
                    Response(target=target, results=r) for r in split
                ]
        with stage("driver.canary"):
            self._canary_check(
                target, sigkey, 0, canary_split, canary_autorej
            )
        return [
            Response(target=target, results=auto + ev)
            for auto, ev in zip(autorejects, split)
        ]

    def _audit(self, target: str, trace: Optional[List[str]]) -> List[Result]:
        with self._mutex:
            corpus = self._audit_corpus(target)
        if corpus is None:
            self.stats = {}
            return []
        if self.external_data is not None:
            # each sweep is one batch epoch: the corpus's deduped keys
            # fetch once; flagged rows then render from the cache
            self.external_data.begin_batch()
            self._prefetch_external(target, corpus.reviews)
        split = self._eval_reviews_split(
            target, corpus.reviews, trace, corpus
        )
        # the sweep's result assembly, with the client's per-violation
        # post-processing (Client.audit)
        with stage("client.assemble", self.metrics, "client_phase_seconds",
                   phase="assemble", path="audit"):
            return [r for sub in split for r in sub]

    def _eval_reviews(
        self,
        target: str,
        reviews: List[Any],
        trace: Optional[List[str]],
        corpus: Optional[_Corpus],
    ) -> List[Result]:
        split = self._eval_reviews_split(target, reviews, trace, corpus)
        return [r for sub in split for r in sub]

    def _eval_reviews_split(
        self,
        target: str,
        reviews: List[Any],
        trace: Optional[List[str]],
        corpus: Optional[_Corpus],
        require_compiled: bool = False,
        cset: Optional[_ConstraintSet] = None,
        partition=None,
    ) -> List[List[Result]]:
        """Shared compiled-path evaluation: match x programs on device,
        interpreter rendering of the sparse violating pairs; results
        grouped per review (review-major order preserved).
        require_compiled propagates to the kernel dispatch: ColdKernel
        escapes (before any result is produced) when this batch's shape
        bucket has no compiled entry yet. `cset` overrides the target's
        full constraint set with a partition-scoped one
        (query_many_subset); `partition` labels the cost-attribution
        rows this dispatch's device time lands in. Its three stages are
        annotated `gk.driver.<phase>` (obs.stage) and timed into
        `driver_phase_seconds`, contiguous from the call's start."""
        t_start = _time.perf_counter()
        with self._mutex:
            with stage("driver.flatten_encode") as encode:
                cs = (
                    cset if cset is not None
                    else self._constraint_set(target)
                )
                if cs is None:
                    self.stats = {}
                    return [[] for _ in reviews]
                ns_cache = self._ns_cache(target)
                if corpus is None:
                    corpus = self._ephemeral_corpus(
                        target, cs, reviews, ns_cache
                    )
                self.patterns.sync()
                self.tables.sync()
            c_count = len(cs.constraints)
            n_count = len(reviews)
            path = "audit" if corpus.data_gen >= 0 else "webhook"
            with stage("driver.device_dispatch") as dispatch:
                if self.use_jax:
                    pairs, stat_c, stat_i = self._need_pairs(
                        target, cs, corpus,
                        require_compiled=require_compiled,
                    )
                else:
                    pairs, stat_c, stat_i = self._need_pairs_np(
                        target, cs, corpus, ns_cache, n_count
                    )
            with stage("driver.render") as render:
                # only the sparse pair set needing interpreter work is
                # visited in Python — violating compiled pairs (count > 0)
                # plus every matched fallback pair, review-major (matching
                # RegoDriver._audit's emit order)
                render_cache: Optional[Dict[Tuple[int, int], List[Result]]]
                render_cache = None
                if corpus.data_gen >= 0 and trace is None:
                    gens = (self._data_gen, self._constraint_gen)
                    cached = self._render_cache.get(target)
                    if cached is None or cached[0] != gens:
                        cached = (gens, {})
                        self._render_cache[target] = cached
                    render_cache = cached[1]
                # compiled-render pre-pass (VERDICT r3 #1): exact programs'
                # violating pairs render from their branch plans via one
                # numpy evaluation over the violating rows — no interpreter.
                # Pairs the plans cannot prove exact fall through below.
                # Traced requests keep the interpreter route so their traces
                # carry the per-pair evaluation lines.
                host_rendered: Dict[Tuple[int, int], List[Result]] = {}
                if trace is None:
                    uncached = [
                        p
                        for p in pairs
                        if render_cache is None or p not in render_cache
                    ]
                    host_rendered = self._host_render_pairs(
                        cs, corpus, uncached, reviews
                    )
                per_review: List[List[Result]] = [[] for _ in reviews]
                n_results = 0
                n_host = 0
                n_interp_render = 0
                n_pruned = 0
                n_cache_hits = 0
                frozen: Dict[int, Any] = {}  # review idx -> frozen review
                # the whole synced tree, frozen at the first unpruned
                # interpreter render (_inventory caches it per data
                # version): compiled and pruned renders never read it
                inventory = None
                for n_i, c_i in pairs:
                    out = None
                    if render_cache is not None:
                        out = render_cache.get((n_i, c_i))
                        if out is not None:
                            n_cache_hits += 1
                    if out is None:
                        out = host_rendered.get((n_i, c_i))
                        if out is not None:
                            n_host += 1
                        else:
                            fr = frozen.get(n_i)
                            if fr is None:
                                fr = frozen[n_i] = freeze(reviews[n_i])
                            prog = cs.programs[c_i]
                            prune = prog.prune if prog is not None else None
                            if prune is not None:
                                out = self._render_pruned(
                                    target, cs.constraints[c_i],
                                    reviews[n_i], prune, trace, fr, path
                                )
                                n_pruned += 1
                            else:
                                if inventory is None:
                                    inventory = self._inventory(target)
                                out = self._eval_template(
                                    target, cs.constraints[c_i], reviews[n_i],
                                    inventory, trace, frozen_review=fr
                                )
                            n_interp_render += 1
                        if render_cache is not None:
                            self._render_cache_put(
                                render_cache, (n_i, c_i), out
                            )
                    per_review[n_i].extend(out)
                    n_results += len(out)
            # the per-query cost-center split: how long this evaluation
            # spent flattening/encoding reviews into tensors (not the
            # synced inventory's freeze: the render builds that when a
            # pair needs it), in the
            # fused device dispatch (host wall time: transfers, device
            # execution, pair extraction, any inline jit compile), and
            # rendering violation messages. The micro-batch bridge
            # stamps these into its requests' trace spans.
            phase_stamps = {
                "flatten_encode": (t_start, encode.end),
                "device_dispatch": (encode.end, dispatch.end),
                "render": (dispatch.end, render.end),
            }
            phase_seconds = {
                phase: end - begin
                for phase, (begin, end) in phase_stamps.items()
            }
            # per-constraint device-time accounting: the measured
            # device-execute window, apportioned by static cost over
            # the constraint set this dispatch evaluated
            self._attribute_dispatch(
                cs, phase_seconds["device_dispatch"], partition
            )
            self.stats = {
                "compiled_pairs": stat_c,
                "interp_pairs": stat_i,
                "n_reviews": n_count,
                "n_constraints": c_count,
                "n_results": n_results,
                "host_rendered_pairs": n_host,
                "interp_rendered_pairs": n_interp_render,
                "pruned_renders": n_pruned,
                # violating pairs answered straight from the render
                # cache (the decision log's per-request cache fact)
                "render_cache_hits": n_cache_hits,
                "render_errors": self._render_errors,
                "render_cache_evictions": self._render_cache_evictions,
                "hot_redispatches": self._hot_redispatches,
                # dead token slots the IR liveness mask dropped from
                # THIS batch's encode (0 for the keep-all audit corpus)
                "columns_skipped_static": int(
                    getattr(corpus, "skipped_static", 0)
                ),
                "phase_seconds": phase_seconds,
                "phase_stamps": phase_stamps,
                # machine-readable WHY for every wholesale-interpreter
                # template in this query's constraint set
                "fallback_codes": dict(cs.fallback_codes or {}),
                "analyzer_mismatches": self.analyzer_mismatches,
            }
            self._local.stats = self.stats
            if self.metrics is not None:
                m = self.metrics
                m.record("driver_pairs_total", stat_c, route="compiled",
                         path=path)
                m.record("driver_pairs_total", stat_i, route="interp",
                         path=path)
                m.record("driver_render_total", n_host, route="host")
                m.record("driver_render_total",
                         n_interp_render - n_pruned, route="interp")
                m.record("driver_render_total", n_pruned, route="pruned")
                if inventory is None:
                    m.record("driver_inventory_skips_total", 1, path=path)
                for phase, dt in phase_seconds.items():
                    m.observe("driver_phase_seconds", dt, phase=phase,
                              path=path)
                self._count_join_rows(cs, pairs, n_count)
            if trace is not None:
                trace.append(
                    f"tpu dispatch: {self.stats['compiled_pairs']} compiled "
                    f"pairs, {self.stats['interp_pairs']} interpreter pairs"
                )
            return per_review

    def _count_join_rows(
        self, cs: _ConstraintSet, pairs: List[Tuple[int, int]], n_rows: int
    ) -> None:
        """driver_join_rows_total per inventory-join template (a screen
        with a prune plan or a duplicate-key row feature): every row the
        screen ran over, and the rows it flagged for the interpreter."""
        joins = {
            c_i: cs.constraints[c_i].get("kind")
            for c_i, p in enumerate(cs.programs)
            if p is not None and p.screen and (
                p.prune is not None
                or any(f.startswith("invdup:") for f in p.row_features)
            )
        }
        if not joins:
            return
        flagged: Dict[str, int] = {}
        for _n_i, c_i in pairs:
            kind = joins.get(c_i)
            if kind is not None:
                flagged[kind] = flagged.get(kind, 0) + 1
        for kind in set(joins.values()):
            screened = n_rows * sum(k == kind for k in joins.values())
            self._count("driver_join_rows_total", screened, template=kind,
                        route="screened")
            self._count("driver_join_rows_total", flagged.get(kind, 0),
                        template=kind, route="flagged")

    # -- derived-key prune rendering -----------------------------------------

    def _prune_oracle(self, target: str, kind: str, params: Any):
        key = (target, kind, _params_key(params))
        cached = self._prune_oracles.get(key)
        if cached is None:
            cached = self._make_oracle(target, kind, params)
            self._prune_oracles[key] = cached
        return cached

    @staticmethod
    def _collect_path_values(node: Any, segs: Tuple[str, ...]) -> List[Any]:
        """All SCALAR values reachable from `node` along a path whose
        wildcard segments ("#" array level, "*" object key, "?" either)
        iterate every child. Used host-side for both sides of a
        path-form prune plan: inventory objects (index keys) and the
        review object (lookup keys). Collecting a superset is sound —
        the interpreter re-checks candidates — so "#"/"?" iterate both
        dicts and lists rather than discriminating."""
        out: List[Any] = []
        frontier = [node]
        for seg in segs:
            nxt: List[Any] = []
            wild = seg in ("#", "*", "?")
            for n in frontier:
                if wild:
                    if isinstance(n, dict):
                        nxt.extend(n.values())
                    elif isinstance(n, list):
                        nxt.extend(n)
                elif isinstance(n, dict):
                    key = unesc_seg(seg)
                    if key in n:
                        nxt.append(n[key])
            frontier = nxt
            if not frontier:
                break
        for v in frontier:
            if isinstance(v, (str, int, float, bool)) or v is None:
                out.append(v)
        return out

    @staticmethod
    def _plan_key(plan: Dict[str, Any]) -> Tuple:
        if "fn" in plan:
            return ("fn", plan["fn"], plan["tree"])
        return ("path", plan["path"], plan["review_pattern"], plan["tree"])

    def _prune_index(
        self, target: str, kind: str, params: Any, plan: Dict[str, Any],
        path: str = "audit",
    ):
        """{frozen key -> [(path segs, obj)]} over the inventory tree —
        built once per data generation. fn-form plans evaluate the
        join's pure Rego helper host-side (flatten_selector,
        /root/reference/library/general/uniqueserviceselector/src.rego);
        path-form plans collect the values at the join's relative path
        (spec.rules[_].host,
        /root/reference/library/general/uniqueingresshost/src.rego) —
        one object indexes under EACH of its keys."""
        return self._keyed_index(target, kind, params, plan, path)[0]

    def _keyed_index(
        self, target: str, kind: str, params: Any, plan: Dict[str, Any],
        path: str,
    ):
        """-> (_prune_index's index, {id(object) -> its frozen key, or
        _UNDEFINED_KEY} for fn-form plans, else None)."""
        ikey = (target, kind, _params_key(params)) + self._plan_key(plan)
        cached = self._prune_indexes.get(ikey)
        if cached is not None and cached[0] == self._data_gen:
            return cached[1], cached[2]
        with stage("driver.join_screen", self.metrics,
                   "driver_phase_seconds", phase="join_screen", path=path):
            return self._build_prune_index(target, kind, params, plan, ikey)

    def _build_prune_index(self, target, kind, params, plan, ikey):
        """The key index _keyed_index caches, built over the tree."""
        fn = plan.get("fn")
        oracle = (
            self._prune_oracle(target, kind, params)
            if fn is not None
            else None
        )
        depth = 4 if plan["tree"] == "namespace" else 3
        tree = self.storage.get(["external", target, plan["tree"]], {})
        index: Dict[Any, List[Tuple[Tuple[str, ...], Any]]] = {}
        keys: Optional[Dict[int, Any]] = {} if oracle is not None else None

        def rec(node, segs):
            if len(segs) == depth:
                if oracle is not None:
                    k, defined = oracle(fn, node)
                    if defined:
                        fk = freeze(k)
                        index.setdefault(fk, []).append((segs, node))
                        keys[id(node)] = fk
                    else:
                        keys[id(node)] = _UNDEFINED_KEY
                else:
                    entry = (segs, node)
                    seen = set()
                    for k in self._collect_path_values(node, plan["path"]):
                        fk = freeze(k)
                        if fk not in seen:
                            seen.add(fk)
                            index.setdefault(fk, []).append(entry)
                return
            if isinstance(node, dict):
                for key2, child in node.items():
                    rec(child, segs + (key2,))

        if isinstance(tree, dict):
            rec(tree, ())
        # the index holds every keyed object, so no id above is reused
        # while the entry lives
        self._prune_indexes[ikey] = (self._data_gen, index, keys)
        return index, keys

    def _render_pruned(
        self,
        target: str,
        constraint: Dict[str, Any],
        review: Any,
        plan: Dict[str, Any],
        trace: Optional[List[str]],
        frozen_review: Any,
        path: str,
    ) -> List[Result]:
        """Interpreter render against a PRUNED inventory: only the
        derived-key index's candidates for this review's key(s). Sound
        because the compile proved the violating clause implies the
        candidate and the review side share a key — F(candidate) ==
        F(review subdoc) for fn-form plans, path-values(candidate) ∩
        path-values(review) ≠ ∅ for path-form — and no other clause
        touches the inventory, so candidates are the only objects that
        can appear in any violation."""
        kind = constraint.get("kind")
        params = H.constraint_parameters(constraint)
        # the key index is join-screen work, built once per data
        # generation: render_pruned times this pair's render alone
        index = self._prune_index(target, kind, params, plan, path)
        with stage("driver.render_pruned", self.metrics,
                   "driver_phase_seconds", phase="render_pruned", path=path):
            candidates: List[Tuple[Tuple[str, ...], Any]] = []
            if "fn" in plan:
                cur: Any = review
                for seg in plan["review_prefix"]:
                    if not isinstance(cur, dict) or seg not in cur:
                        cur = None
                        break
                    cur = cur[seg]
                if cur is not None:
                    oracle = self._prune_oracle(target, kind, params)
                    k, defined = oracle(plan["fn"], cur)
                    if defined:
                        candidates = index.get(freeze(k), [])
            else:
                # path-form: candidates = union over the review's key
                # values (spec.rules[_].host yields one key per rule),
                # deduped by inventory path so a shared-host candidate
                # appears once
                keys = {
                    freeze(k)
                    for k in self._collect_path_values(
                        review, plan["review_pattern"]
                    )
                }
                seen_segs = set()
                for fk in keys:
                    for segs, obj in index.get(fk, []):
                        if segs not in seen_segs:
                            seen_segs.add(segs)
                            candidates.append((segs, obj))
            pruned_tree: Dict[str, Any] = {}
            for segs, obj in candidates:
                node = pruned_tree
                for seg in segs[:-1]:
                    node = node.setdefault(seg, {})
                node[segs[-1]] = obj
            pruned_inv = freeze({plan["tree"]: pruned_tree})
            return self._eval_template(
                target, constraint, review, pruned_inv, trace,
                frozen_review=frozen_review,
            )

    # -- compiled message rendering ------------------------------------------

    def _host_render_pairs(
        self,
        cs: _ConstraintSet,
        corpus: _Corpus,
        pairs: List[Tuple[int, int]],
        reviews: List[Any],
    ) -> Dict[Tuple[int, int], List[Result]]:
        """Render violating pairs of exact programs from their compiled
        branch plans (engine/render.py): one numpy branch evaluation per
        (program, violating-row-subset), then per-row message decoding
        from the token table + raw review — the interpreter never runs.
        Rows/pairs the plans cannot prove exact are omitted (the caller
        falls back per pair). Violation objects render once per
        (program, row) and fan out to every constraint sharing the
        program (identical params => identical violations; only the
        constraint/enforcement fields differ)."""
        out: Dict[Tuple[int, int], List[Result]] = {}
        by_prog: Dict[int, Tuple[Program, List[Tuple[int, int]]]] = {}
        for n_i, c_i in pairs:
            p = cs.programs[c_i]
            if p is None or not p.branches:
                continue
            if corpus.row_fallback[n_i]:
                continue  # overflow rows: interpreter territory
            ent = by_prog.get(id(p))
            if ent is None:
                ent = by_prog[id(p)] = (p, [])
            ent[1].append((n_i, c_i))
        if not by_prog:
            return out
        from ..engine.exprs import EvalCtx
        from ..engine.render import RenderSet

        member = np.asarray(self.patterns.member)
        capture = np.asarray(self.patterns.capture)
        tabs = {k: np.asarray(v) for k, v in self.tables.arrays().items()}
        overlay = _corpus_overlay(corpus)
        ov = overlay or {}
        corpus_vocab = corpus.vocab if corpus.vocab is not None else self.vocab
        for prog, plist in by_prog.values():
            rows = sorted({n for n, _ in plist})
            pos = {n: i for i, n in enumerate(rows)}
            idx = np.asarray(rows, np.int64)
            tok_slice = {k: v[idx] for k, v in corpus.tok.items()}
            ctx = EvalCtx(
                np=np,
                tok=tok_slice,
                pat_member=member,
                pat_capture=capture,
                str_tables=tabs,
                consts=prog.consts,
                g0=corpus.g,
                g1=corpus.g1,
                v_base=ov.get("v_base"),
                ov_member=ov.get("member"),
                ov_capture=ov.get("capture"),
                ov_tabs=ov.get("tabs"),
            )
            try:
                rset = RenderSet(prog, ctx, corpus_vocab)
                row_objs = {
                    n: rset.render_row(pos[n], reviews[n]) for n in rows
                }
            except Exception:
                # a plan evaluation bug must degrade to the interpreter,
                # never fail the sweep; surfaced via stats for tests
                self._render_errors += 1
                self._count("driver_render_errors_total")
                continue
            for n_i, c_i in plist:
                objs = row_objs.get(n_i)
                if objs is None:
                    continue
                out[(n_i, c_i)] = _results_from_objs(
                    objs, cs.constraints[c_i], reviews[n_i]
                )
        return out


# _keyed_index's key map: an object whose helper value is undefined,
# and (absent from the map) one outside the indexed tree
_UNDEFINED_KEY = object()
_UNINDEXED = object()
_MISSING = object()  # an undefined value in _identity_holds


def _walk_segs(node: Any, segs: Tuple[str, ...]) -> Any:
    """The value at literal (escaped) pattern segments, else _MISSING."""
    for seg in segs:
        key = unesc_seg(seg)
        if not isinstance(node, dict) or key not in node:
            return _MISSING
        node = node[key]
    return node


def _corpus_overlay(corpus: _Corpus) -> Optional[Dict[str, Any]]:
    """Vocab-overlay ctx blocks for host/numpy evaluation paths."""
    if corpus.ov_member is None:
        return None
    return {
        "v_base": corpus.v_base,
        "member": corpus.ov_member,
        "capture": corpus.ov_capture,
        "tabs": corpus.ov_tabs,
    }


def _results_from_objs(
    objs: List[Any], constraint: Dict[str, Any], review: Any
) -> List[Result]:
    """Frozen violation objects -> Result list, mirroring the hook's
    shape exactly (RegoDriver._eval_template): msg-less violations drop,
    details default {} (client/regolib/src.go:23-42)."""
    from ..rego.values import thaw

    enforcement = H.enforcement_action(constraint)
    out: List[Result] = []
    for v in objs:
        tv = thaw(v)
        if not isinstance(tv, dict) or "msg" not in tv:
            continue
        out.append(
            Result(
                msg=tv["msg"],
                metadata={"details": H.hook_get_default(tv, "details", {})},
                constraint=constraint,
                review=review,
                enforcement_action=enforcement,
            )
        )
    return out


def _features_np(fb) -> Dict[str, np.ndarray]:
    """FeatureBatch -> plain numpy dict (same keys the kernel takes);
    chunks are sliced host-side and shipped per dispatch."""
    return {
        "group_id": np.asarray(fb.group_id),
        "kind_id": np.asarray(fb.kind_id),
        "kind_defined": np.asarray(fb.kind_defined),
        "is_ns": np.asarray(fb.is_ns),
        "has_namespace": np.asarray(fb.has_namespace),
        "ns_name_id": np.asarray(fb.ns_name_id),
        "obj_present": np.asarray(fb.obj_present),
        "old_present": np.asarray(fb.old_present),
        "obj_labels": np.asarray(fb.obj_labels),
        "old_labels": np.asarray(fb.old_labels),
        "nssel_defined": np.asarray(fb.nssel_defined),
        "nssel_labels": np.asarray(fb.nssel_labels),
        "nssel_empty": np.asarray(fb.nssel_empty),
    }


def _pad_rows(a: np.ndarray, pad: int, fill=None) -> np.ndarray:
    if pad <= 0:
        return a
    shape = (pad,) + a.shape[1:]
    if fill is None:
        if a.dtype == bool:
            fill_val = False
        else:
            fill_val = -1
    else:
        fill_val = fill
    padrows = np.full(shape, fill_val, a.dtype)
    return np.concatenate([a, padrows], axis=0)
