"""Micro-batching admission server.

The reference webhook evaluates one AdmissionReview per goroutine behind
a shared RWMutex (pkg/webhook/policy.go:141, drivers/local/local.go:303)
— concurrency without batching. The TPU path inverts that: concurrent
requests are coalesced for up to `window_ms` (or until `max_batch`) and
the whole batch is evaluated in ONE fused device dispatch via
`Client.review_many` (SURVEY §2.4 row 3's micro-batching bridge).

`WebhookServer` is a stdlib HTTP server serving /v1/admit and
/v1/admitlabel with AdmissionReview JSON — the in-process stand-in for
the Go webhook pod. With `tls=True` it terminates HTTPS with a
rotating self-signed CA + server cert (`certs.CertRotator`, the
pkg/webhook/certs.go counterpart).

Failure semantics preserve the reference's fail-open design (SURVEY §5)
and make the whole degradation ladder explicit (docs/robustness.md):
a failed fused batch falls back to per-request HOST-interpreter
evaluation (never a second doomed device attempt), a circuit breaker
(`faults.CircuitBreaker`) short-circuits the fused path entirely after
K consecutive batch failures, and only a request whose own host
evaluation also fails gets an error response — one poisoned request can
no longer 500 a whole batch. Overload protection: the admission queue
is bounded (`max_queue`) with load shedding, and requests carry their
caller deadline so an already-expired request is shed before dispatch
instead of evaluated and discarded. Shed/degraded requests get the
endpoint's fail-open/fail-closed envelope, not a raw 500.
"""

from __future__ import annotations

import json
import threading
import time
from concurrent.futures import Future
from concurrent.futures import TimeoutError as _FutureTimeout
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

from ..faults import (
    CircuitBreaker,
    DeadlineExceeded,
    EvaluationTimeout,
    EvaluationUnavailable,
    FaultError,
    ShedError,
    fire,
    skew,
)
from ..obs.tracer import (
    derive_trace_id,
    format_traceparent,
    parse_traceparent,
    stage,
)
from .namespacelabel import NamespaceLabelHandler
from .policy import AdmissionResponse, ValidationHandler

# the K8s webhook timeoutSeconds ceiling is 30s and Gatekeeper deploys
# with 3s; our per-request deadline stays safely under the ceiling
DEFAULT_REQUEST_TIMEOUT = 10.0

# bounded admission queue: at max_batch=256 and low-ms batch drains this
# is seconds of backlog — anything deeper is already past every caller
# deadline, so evaluating it would be pure waste (shed instead)
DEFAULT_MAX_QUEUE = 2048


def review_envelope(
    review: Dict[str, Any], request: Dict[str, Any], resp,
    trace_id: Optional[str] = None,
) -> Dict[str, Any]:
    """The one AdmissionReview response envelope, shared by every
    endpoint (admit / admitlabel / mutate): echoes the request's
    apiVersion (falling back to admission/v1) and uid, and carries the
    handler's response dict — including `patchType`/`patch` when the
    response has one — so the three endpoints can never drift. With a
    trace id (inbound `traceparent` or the admission-UID derivation)
    the envelope echoes it as `traceId`, so the caller can join its
    admission verdict to `/debug/traces?trace_id=` and the denial log
    without guessing (docs/observability.md §Trace propagation)."""
    out = {
        "apiVersion": review.get("apiVersion", "admission.k8s.io/v1"),
        "kind": "AdmissionReview",
        "response": resp.to_dict(uid=request.get("uid")),
    }
    if trace_id is not None:
        out["traceId"] = trace_id
    return out


class _AdmissionFuture(Future):
    """A pending admission's future, stamped on the perf_counter clock
    when submitted, when its batch is cut and when the batch worker
    resolves it: the request's queue_wait and wake stages."""

    def __init__(self):
        super().__init__()
        self.submitted = time.perf_counter()
        self.cut: Optional[float] = None
        self.resolved: Optional[float] = None

    def set_result(self, result) -> None:
        self.resolved = time.perf_counter()
        super().set_result(result)

    def set_exception(self, exception) -> None:
        self.resolved = time.perf_counter()
        super().set_exception(exception)


class _PartitionReview(NamedTuple):
    """What a partitioned batch's evaluation leaves for its bookkeeping
    and for resolving its futures."""

    masks: List[List[bool]]  # per partition, per request: may match
    host_parts: List[Any]  # partitions served on the host rung
    skipped_parts: List[int]  # partition indexes no request touched
    sel_by_part: Dict[int, List[int]]  # partition -> requests dispatched
    degraded_reqs: Dict[int, List[int]]  # request -> host-rung partitions
    errors: Dict[int, Exception]  # request -> its failure
    answers: Dict[int, List[Any]]  # request -> merged results


def _join_rows(outer: Future, rows: List[Future]) -> None:
    """Resolve `outer` with the rows' results, in order, once every row
    is resolved; with the first row's exception if any row failed."""
    lock = threading.Lock()
    left = [len(rows)]

    def done(_):
        with lock:
            left[0] -= 1
            if left[0]:
                return
        errors = [f.exception() for f in rows if f.exception() is not None]
        if errors:
            outer.set_exception(errors[0])
        else:
            outer.set_result([f.result() for f in rows])

    for f in rows:
        f.add_done_callback(done)


class MicroBatcher:
    """Collects admission requests into batches for fused evaluation.

    submit() returns a Future resolving to the request's results list.
    A background worker drains the queue every `window_ms` (or as soon
    as `max_batch` requests are pending) and runs one
    `Client.review_many` call for the whole batch.

    A workload and its implied requests (docs/expansion.md) are
    submitted as one entry, a list of 1 + n requests under one future:
    they ride the same dispatch as rows of their own, and the future
    resolves to one results list per row.
    """

    # the plane tag on shed/breaker/queue metrics (MutateBatcher
    # overrides with "mutation")
    plane = "validation"

    def __init__(
        self,
        client,
        target: str,
        window_ms: float = 2.0,
        max_batch: int = 256,
        namespace_getter: Optional[Callable[[str], Optional[dict]]] = None,
        metrics=None,
        tracer=None,
        # bounded admission queue (overload shedding); None = unbounded
        max_queue: Optional[int] = DEFAULT_MAX_QUEUE,
        # device circuit breaker: None = construct the default; False =
        # disabled; or pass a faults.CircuitBreaker to share/observe
        breaker=None,
        # device fault domains (docs/robustness.md §Fault domains): a
        # parallel.partition.PartitionDispatcher replaces the single
        # per-plane breaker with per-(device, plane) breakers — batches
        # fan out over constraint-subset partitions, a failed partition
        # degrades ONLY its subset to the host rung, and quarantined
        # devices re-home their partitions onto healthy ones
        partitioner=None,
        # obs.FlightRecorder: shed bursts (and the default breaker's
        # OPEN transitions) trip postmortem captures
        recorder=None,
        # obs.DecisionLog: the batch worker stashes per-request
        # dispatch facts (route, partition set dispatched vs
        # mask-skipped, rows_dispatched/rows_total, cache/fetch
        # counts, device-time share) under each request's trace id;
        # the handler claims them when it records the decision
        # (docs/observability.md §Decision log)
        decisions=None,
        # admission scheduling (docs/operations.md §Admission
        # scheduling): "fifo" is bit-compatible with the pre-scheduler
        # queue; "deadline" turns on EDF batch formation, predictive
        # shedding, and per-tenant fair-share quotas. slo (SloEngine)
        # feeds the overload/saturation loop and the batch cost EWMA;
        # attributor seeds the cost model before the EWMA warms.
        sched_policy: str = "fifo",
        slo=None,
        attributor=None,
        # integrity.IntegrityPlane: post-response shadow-oracle
        # sampling — a deterministic CRC(trace_id) fraction of live
        # admissions re-evaluates asynchronously on the host
        # interpreter (docs/robustness.md §Verdict integrity)
        integrity=None,
    ):
        self.client = client
        self.target = target
        self.partitioner = partitioner
        self.recorder = recorder
        self.decisions = decisions
        self.integrity = integrity
        # (constraint generation, corpus size) cache for rows facts
        self._rows_cache: Optional[Tuple[Any, int]] = None
        if partitioner is not None and breaker is None:
            # the per-device breaker bank replaces the plane breaker
            breaker = False
        # the target handler owns serving-plane review construction
        # (K8s: AdmissionRequest -> AugmentedReview; agent: tool-call
        # record -> AgentAction); client=None planes (MutateBatcher)
        # build their own reviews in _dispatch, and clients without a
        # target registry (test fakes) get the default handler
        if client is not None:
            from ..constraint.handler import handler_for

            self.target_handler = handler_for(client, target)
        else:
            self.target_handler = None
        self.window = window_ms / 1000.0
        self.max_batch = max_batch
        self.max_queue = max_queue
        self.namespace_getter = namespace_getter
        self.metrics = metrics
        # obs.Tracer: the batch worker stamps queue-wait + dispatch +
        # render spans into EVERY member request's trace (the shared
        # batch window, recorded per trace so each is self-contained)
        self.tracer = tracer
        if breaker is None:
            breaker = CircuitBreaker(
                plane=self.plane, metrics=metrics, tracer=tracer,
                recorder=recorder,
            )
        self.breaker: Optional[CircuitBreaker] = breaker or None
        # the admission scheduler owns enqueue-side admit/shed and the
        # dispatch-side batch cut; its clock is the batcher's skewed
        # deadline clock so chaos clock jumps steer it too
        from ..sched import AdmissionScheduler

        self.sched = AdmissionScheduler(
            plane=self.plane,
            policy=sched_policy,
            max_queue=max_queue,
            clock=self._now,
            slo=slo,
            attributor=attributor,
            metrics=metrics,
        )
        # (request, future, span ctx | None, (wall, perf) submit stamp,
        #  monotonic deadline | None, scheduler tenant key | None)
        self._pending: List[
            Tuple[Dict[str, Any], Future, Any, Tuple, Optional[float],
                  Optional[str]]
        ] = []
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        self.batches_dispatched = 0
        self.requests_batched = 0
        self.batch_failures = 0
        self.shed_count = 0

    def start(self) -> None:
        if self._thread is None:
            self._stop = False
            self._thread = threading.Thread(target=self._loop, daemon=True)
            self._thread.start()

    def stop(self) -> None:
        with self._lock:
            self._stop = True
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        # in-flight requests must not hang until their caller timeout:
        # dispatch whatever the worker left behind
        with self._lock:
            leftover = self._pending
            self._pending = []
        if leftover:
            self._dispatch(leftover)

    def _now(self) -> float:
        """The batcher's deadline clock: monotonic plus any injected
        clock-jump skew (fault point `webhook.clock`) so chaos runs can
        simulate NTP steps without touching the real clock."""
        return time.monotonic() + skew("webhook.clock")

    # -- decision facts (docs/observability.md §Decision log) ----------------

    def _corpus_rows(self) -> Optional[int]:
        """Constraint-corpus size (the rows_total denominator), cached
        per constraint generation so the hot path pays one generation
        read per batch, not a key listing."""
        drv = getattr(self.client, "_driver", None) if self.client else None
        keys_fn = getattr(drv, "constraint_keys", None)
        if keys_fn is None:
            return None
        gen_fn = getattr(drv, "constraint_generation", None)
        gen = gen_fn() if gen_fn is not None else None
        cached = self._rows_cache
        if cached is None or cached[0] != gen:
            try:
                cached = self._rows_cache = (gen, len(keys_fn(self.target)))
            except Exception:
                return None
        return cached[1]

    def _driver_route(self, n: int) -> str:
        """What the driver will actually do with an n-request batch:
        `fused` (device dispatch) or `interp` (the adaptive small-batch
        / cold-compile interpreter route) — the route fact a decision
        record explains a request's latency with."""
        drv = getattr(self.client, "_driver", None) if self.client else None
        if drv is None or not getattr(drv, "use_jax", False):
            return "interp"
        from ..constraint import tpudriver as _td

        warm_fn = getattr(drv, "review_path_warm", None)
        warm = warm_fn(self.target) if warm_fn is not None else True
        if n < _td.MIN_DEVICE_BATCH or not warm:
            return "interp"
        return "fused"

    def _note_rows(self, partition, rows_dispatched, rows_total) -> None:
        """The pruning-efficiency series (ROADMAP item 1's instrument):
        constraint-rows actually dispatched vs the full corpus, per
        partition — `dispatch_efficiency = dispatched/total` falling
        with constraint count is what batch-aware pruned dispatch will
        be judged by."""
        if self.metrics is None or not rows_total:
            return
        self.metrics.record(
            "dispatch_rows_dispatched_total", rows_dispatched,
            plane=self.plane, partition=str(partition),
        )
        self.metrics.record(
            "dispatch_rows_total", rows_total,
            plane=self.plane, partition=str(partition),
        )

    def _dispatch_stats(self) -> Dict[str, Any]:
        """The driver's stats of this worker thread's last evaluation
        (`thread_stats`; a driver without it gives its shared `stats`)."""
        drv = getattr(self.client, "_driver", None) if self.client else None
        own = getattr(drv, "thread_stats", None)
        stats = own() if own is not None else getattr(drv, "stats", None)
        return stats if isinstance(stats, dict) else {}

    def _driver_consumption(self) -> Dict[str, Any]:
        """Per-batch consumption facts from the driver's last dispatch
        stats: render-cache hits and the batch's device-dispatch window
        (apportioned per request by the caller)."""
        stats = self._dispatch_stats()
        out: Dict[str, Any] = {}
        if "render_cache_hits" in stats:
            out["cache_hits"] = stats["render_cache_hits"]
        phases = stats.get("phase_seconds") or {}
        if "device_dispatch" in phases:
            out["device_seconds"] = phases["device_dispatch"]
        return out

    def _note_decisions(
        self, batch, route: str, rows_dispatched=None, rows_total=None,
        extdata_fetches: Optional[int] = None, per_request=None,
        columns_skipped_static: Optional[int] = None,
    ) -> None:
        """Stash dispatch facts for every traced member request. Batch-
        shared facts (route, rows, fetches, device share) apply to all;
        `per_request` maps batch index -> overriding facts (the
        partitioned path's per-request partition sets)."""
        if self.decisions is None:
            return
        cons = self._driver_consumption()
        dev = cons.pop("device_seconds", None)
        base: Dict[str, Any] = {"route": route, "batch_size": len(batch)}
        base.update(cons)
        if rows_total is not None:
            base["rows_total"] = rows_total
            base["rows_dispatched"] = (
                rows_dispatched if rows_dispatched is not None
                else rows_total
            )
        if extdata_fetches is not None:
            base["extdata_fetches"] = extdata_fetches
        if columns_skipped_static is not None:
            # dead token slots the IR liveness mask dropped from this
            # batch's encode (docs/analysis.md §IR analysis)
            base["columns_skipped_static"] = columns_skipped_static
        if dev is not None and batch:
            # the batch's measured device window split evenly across
            # members — the request-level share of what the constraint-
            # level CostAttributor accounts exactly
            base["device_seconds_share"] = round(dev / len(batch), 9)
        for i, (_, _, ctx, _, _, _) in enumerate(batch):
            tid = getattr(ctx, "trace_id", None)
            if tid is None:
                continue
            facts = base
            if per_request is not None and i in per_request:
                facts = dict(base)
                facts.update(per_request[i])
            self.decisions.note_dispatch(tid, **facts)

    def _extdata_fetch_count(self) -> int:
        ed = getattr(self.client, "external_data", None) if self.client \
            else None
        return int(getattr(ed, "fetch_count", 0) or 0)

    def _liveness_skipped_count(self) -> int:
        """Driver-cumulative count of provably-dead token slots the IR
        feature-liveness mask dropped before padding (analysis/ir.py);
        dispatch sites report the per-batch delta as a decision fact."""
        drv = getattr(self.client, "_driver", None) if self.client else None
        return int(getattr(drv, "columns_skipped_static", 0) or 0)

    def _shed(self, fut: Future, exc: Exception, reason: str,
              ctx=None, sub_wall: Optional[float] = None) -> None:
        """Resolve a future without evaluation: counted, traced, and
        typed so the handler answers with the fail policy envelope."""
        with self._lock:  # sheds race from concurrent submit threads
            self.shed_count += 1
        if self.metrics is not None:
            self.metrics.record(
                "webhook_shed_total", 1, plane=self.plane, reason=reason
            )
        if self.tracer is not None and ctx is not None:
            now = time.time()
            self.tracer.record_span(
                "shed", sub_wall if sub_wall is not None else now, now,
                parent=ctx, reason=reason, plane=self.plane,
            )
        if self.recorder is not None:
            # shed-burst detection: the recorder counts; crossing its
            # threshold trips ONE postmortem capture for the storm
            try:
                self.recorder.note_shed(self.plane)
            except Exception:
                pass
        fut.set_exception(exc)

    def submit(self, request, span_ctx=None,
               deadline: Optional[float] = None, tenant=None) -> Future:
        """Enqueue for the next fused dispatch: one request, or a list
        of a parent request and its implied ones. `deadline` is a
        monotonic timestamp (the caller's remaining budget): a request
        that is already expired — or expires while queued — is shed
        with DeadlineExceeded instead of ever reaching a dispatch.
        `tenant` is the decision-log tenant identity, extracted BEFORE
        enqueue so shed verdicts carry it and the scheduler's
        fair-share quotas account exactly."""
        fut = _AdmissionFuture()
        stamp = (time.time(), time.perf_counter())
        if deadline is not None and self._now() >= deadline:
            # expired before enqueue: never pay queue + dispatch for an
            # answer nobody is waiting for
            self._shed(
                fut,
                DeadlineExceeded("request deadline expired before enqueue"),
                "deadline", ctx=span_ctx, sub_wall=stamp[0],
            )
            return fut
        shed_exc = victim_item = victim_exc = None
        with self._lock:
            stopped = self._stop
            if not stopped:
                key, shed_exc, victim = self.sched.offer(
                    self._pending, tenant=tenant, deadline=deadline,
                    now=self._now(),
                )
                if shed_exc is None:
                    if victim is not None:
                        # predictive shedding under a full queue: the
                        # queued request that provably cannot make its
                        # deadline goes, not the viable newcomer
                        idx, victim_exc = victim
                        victim_item = self._pending.pop(idx)
                    self._pending.append(
                        (request, fut, span_ctx, stamp, deadline, key)
                    )
                    n = len(self._pending)
        if stopped:
            # worker is gone (and stop() may have already drained its
            # leftovers): dispatch inline so the caller never hangs
            self._dispatch(
                [(request, fut, span_ctx, stamp, deadline, None)]
            )
            return fut
        if victim_item is not None:
            _, vfut, vctx, vstamp, _, _ = victim_item
            self._shed(
                vfut, victim_exc, victim_exc.reason,
                ctx=vctx, sub_wall=vstamp[0],
            )
        if shed_exc is not None:
            self._shed(
                fut, shed_exc, getattr(shed_exc, "reason", "queue_full"),
                ctx=span_ctx, sub_wall=stamp[0],
            )
        else:
            if self.metrics is not None:
                self.metrics.gauge(
                    "admission_queue_depth", n, plane=self.plane
                )
            if n == 1 or n >= self.max_batch:
                self._wake.set()
        return fut

    def _loop(self) -> None:
        while True:
            # idle: block until the first request (or stop) arrives —
            # no fixed-cadence wakeups while the queue is empty
            self._wake.wait()
            self._wake.clear()
            if self._stop:
                return
            # a batch has started forming: coalesce for up to `window`,
            # cut short when max_batch fills
            deadline = time.monotonic() + self.window
            while not self._stop:
                with self._lock:
                    n = len(self._pending)
                remaining = deadline - time.monotonic()
                if n >= self.max_batch or remaining <= 0:
                    break
                self._wake.wait(remaining)
                self._wake.clear()
            with self._lock:
                # the scheduler cuts the batch: fifo takes everything
                # in arrival order (the pre-scheduler swap); deadline
                # policy orders EDF and defers requests that would blow
                # the earliest member deadline to the next window
                batch, rest = self.sched.cut(
                    self._pending, self.max_batch, now=self._now()
                )
                self._pending = rest
            t_cut = time.perf_counter()
            for item in batch:
                item[1].cut = t_cut
            if rest:
                # deferred work exists: start the next window now
                self._wake.set()
            if self.metrics is not None:
                self.metrics.gauge(
                    "admission_queue_depth", len(rest), plane=self.plane
                )
            if batch:
                self._dispatch(batch)
                # the worker's whole cycle for this batch: cut to the
                # last future resolved (and, partitioned, its probes)
                if self.metrics is not None:
                    self.metrics.observe(
                        "webhook_batch_stage_seconds",
                        time.perf_counter() - t_cut,
                        stage="worker", plane=self.plane,
                    )
            if self._stop:
                return

    def _strip_expired(self, batch):
        """Deadline propagation: requests whose caller deadline expired
        while queued are shed here — before any dispatch — instead of
        evaluated and discarded."""
        now = self._now()
        live = []
        for item in batch:
            _, fut, ctx, stamp, deadline = item[:5]
            if deadline is not None and now >= deadline:
                self._shed(
                    fut,
                    DeadlineExceeded(
                        "request deadline expired while queued"
                    ),
                    "deadline", ctx=ctx, sub_wall=stamp[0],
                )
            else:
                live.append(item)
        return live

    def _stage(self, name: str, annotate: bool = True):
        """One stage of the batch worker (obs.stage): observed once per
        batch in `webhook_batch_stage_seconds{stage,plane}` and, unless
        it only encloses other stages, annotated `gk.batch.<name>`."""
        return stage(
            "batch." + name if annotate else None, self.metrics,
            "webhook_batch_stage_seconds", stage=name, plane=self.plane,
        )

    def _count_batch(self, route: str, size: Optional[int] = None) -> None:
        """Count one batch, once, under the route it finally took;
        `size` also observes `webhook_batch_size` (the batches that
        reached an evaluation that answered them all)."""
        if self.metrics is None:
            return
        self.metrics.record("webhook_batches_total", 1, route=route)
        if size is not None:
            self.metrics.observe("webhook_batch_size", size, route=route)

    def _batch_route(self, n: int) -> str:
        """The route the driver reports for this worker's last batch
        (`fused`, `interp` or `cold`), else what an n-request batch
        would take (`_driver_route`)."""
        drv = getattr(self.client, "_driver", None) if self.client else None
        last = getattr(drv, "last_route", None)
        return (last() if last is not None else None) or self._driver_route(n)

    def result(self, fut: Future, timeout: Optional[float]):
        """`fut.result(timeout)` for a future this batcher resolves,
        then the request's stages on the calling (handler) thread, in
        `webhook_request_stage_seconds{stage}`: `queue_wait` (submit to
        the batch cut) and `wake` (resolved to this thread running
        again)."""
        try:
            return fut.result(timeout=timeout)
        finally:
            now = time.perf_counter()
            cut = getattr(fut, "cut", None)
            resolved = getattr(fut, "resolved", None)
            if self.metrics is not None and cut is not None:
                self.metrics.observe(
                    "webhook_request_stage_seconds", cut - fut.submitted,
                    stage="queue_wait",
                )
            if self.metrics is not None and resolved is not None:
                self.metrics.observe(
                    "webhook_request_stage_seconds", now - resolved,
                    stage="wake",
                )

    def _rows(self, batch):
        """The batch with each grouped entry (a list of a parent request
        and its implied ones) split into one row per request, each row
        under a future of its own that the entry's future joins; counts
        the rows by origin in `webhook_reviews_total`."""
        rows = []
        for item in batch:
            request, fut = item[0], item[1]
            if not isinstance(request, list):
                rows.append(item)
                continue
            subs = [Future() for _ in request]
            _join_rows(fut, subs)
            rows += [(r, f) + tuple(item[2:]) for r, f in zip(request, subs)]
        if self.metrics is not None:
            self.metrics.record(
                "webhook_reviews_total", len(batch), origin="original"
            )
            if len(rows) > len(batch):
                self.metrics.record(
                    "webhook_reviews_total", len(rows) - len(batch),
                    origin="implied",
                )
        return rows

    def _dispatch(self, batch) -> None:
        batch = self._strip_expired(batch)
        if not batch:
            return
        batch = self._rows(batch)
        wall0, t0 = time.time(), time.perf_counter()
        with self._stage("augment"):
            reviews = [
                self.target_handler.augment_request(
                    request, self.namespace_getter
                )
                for request, _, _, _, _, _ in batch
            ]
        if self.partitioner is not None:
            plan = None
            try:
                plan = self.partitioner.plan()
            except Exception:
                plan = None  # plan failure: monolithic path still serves
            if plan is not None and plan.partitions:
                self._dispatch_partitioned(batch, reviews, plan, wall0, t0)
                return
        breaker = self.breaker
        if breaker is not None and not breaker.allow():
            # breaker open: the fused path has been failing — go
            # straight to the host-interpreter degraded mode, paying
            # zero doomed device attempts for this batch
            if self.metrics is not None:
                self.metrics.record(
                    "webhook_degraded_dispatch_total", 1, plane=self.plane
                )
            self._dispatch_host(batch, reviews, wall0, t0, route="degraded")
            return
        fetch0 = self._extdata_fetch_count()
        skip0 = self._liveness_skipped_count()
        try:
            fire("webhook.batch_dispatch")
            # not annotated: the client's and driver's stages inside are
            with self._stage("review", annotate=False):
                all_responses = self.client.review_many(reviews)
        except Exception:
            # fused-path failure: degrade to the host-oracle rung so
            # one poisoned request (or a device fault) cannot fail the
            # whole batch — requests still get correct answers and only
            # their own failure surfaces to them
            if breaker is not None:
                breaker.record_failure()
            self.batch_failures += 1
            if self.metrics is not None:
                self.metrics.record("webhook_batch_failures_total", 1)
            self._dispatch_host(batch, reviews, wall0, t0, route="fallback")
            return
        if breaker is not None:
            breaker.record_success()
        self.batches_dispatched += 1
        self.requests_batched += len(batch)
        with self._stage("bookkeeping"):
            self._count_batch(self._batch_route(len(reviews)), len(batch))
            self._record_spans(batch, wall0, t0, route="batched")
            # dispatch-explain facts: the monolithic dispatch evaluates
            # the whole corpus for every member (no pruning: dispatched
            # == total under the "mono" partition label)
            rows = self._corpus_rows()
            rows_total = rows * len(batch) if rows is not None else None
            if rows_total is not None:
                self._note_rows("mono", rows_total, rows_total)
            self._note_decisions(
                batch, self._driver_route(len(reviews)),
                rows_dispatched=rows, rows_total=rows,
                extdata_fetches=self._extdata_fetch_count() - fetch0,
                columns_skipped_static=(
                    self._liveness_skipped_count() - skip0
                ),
            )
            answers = []
            for (_, _, ctx, _, _, _), review, responses in zip(
                batch, reviews, all_responses
            ):
                resp = responses.by_target.get(self.target)
                results = resp.results if resp is not None else []
                answers.append(results)
                self._note_integrity(ctx, review, results, route="batched")
        with self._stage("resolve"):
            for (_, fut, _, _, _, _), results in zip(batch, answers):
                fut.set_result(results)

    def _note_integrity(self, ctx, review, results, **facts) -> None:
        """Offer one served admission to the verdict-integrity plane's
        shadow oracle (CRC-sampled, asynchronous, post-response —
        docs/robustness.md §Verdict integrity). Never fails a request."""
        if self.integrity is None:
            return
        try:
            self.integrity.note_live(
                getattr(ctx, "trace_id", None), review, results,
                plane=self.plane, **facts,
            )
        except Exception:
            pass

    @staticmethod
    def _ensure_staged_nowait(part, p) -> bool:
        """ensure_staged without blocking the admission batch on a
        compile: churned sub-programs restage in the background while
        this batch serves from the host rung (docs/compile.md). The
        TypeError fallback keeps older/duck-typed dispatchers working."""
        try:
            return part.ensure_staged(p, wait=False)
        except TypeError:
            return part.ensure_staged(p)

    def _dispatch_partitioned(self, batch, reviews, plan,
                              wall0: float, t0: float) -> None:
        """Fault-domain dispatch (docs/robustness.md §Fault domains):
        fan the batch out over the plan's constraint-subset partitions,
        each gated by its device's breaker. A failed/open partition
        degrades ONLY its constraint subset — and only the requests
        that subset matches — to the host-interpreter rung; healthy
        partitions stay fused. Merged results are bit-identical to the
        monolithic dispatch (the partition parity battery pins it)."""
        part = self.partitioner
        if plan.all_dead:
            # the whole device fleet is quarantined: fall back to the
            # existing whole-plane host mode — and still run probes, or
            # nothing would ever bring a device back
            if self.metrics is not None:
                self.metrics.record(
                    "webhook_degraded_dispatch_total", 1, plane=self.plane
                )
            self._dispatch_host(batch, reviews, wall0, t0, route="degraded")
            part.run_probes(reviews)
            return
        try:
            fire("webhook.batch_dispatch")
        except Exception:
            # a whole-plane fault (the unlabeled point): every device
            # pays a failure — this is the pre-partition behavior and
            # keeps existing chaos scenarios meaningful
            for p in plan.partitions:
                part.breaker(p.device).record_failure()
            self.batch_failures += 1
            if self.metrics is not None:
                self.metrics.record("webhook_batch_failures_total", 1)
            self._dispatch_host(batch, reviews, wall0, t0, route="fallback")
            part.run_probes(reviews)
            return
        fetch0 = self._extdata_fetch_count()
        skip0 = self._liveness_skipped_count()
        # not annotated: the partition dispatches' driver stages are
        with self._stage("review", annotate=False):
            out = self._review_partitions(reviews, plan)
        self.batches_dispatched += 1
        self.requests_batched += len(batch)
        with self._stage("bookkeeping"):
            self._note_partitioned(
                batch, reviews, plan, wall0, t0, out, fetch0, skip0
            )
        with self._stage("resolve"):
            for i, (_, fut, _, _, _, _) in enumerate(batch):
                if i in out.errors:
                    fut.set_exception(out.errors[i])
                else:
                    fut.set_result(out.answers[i])
        part.run_probes(reviews)

    def _review_partitions(self, reviews, plan) -> _PartitionReview:
        """Evaluate a batch over the plan's partitions: mask-skip the
        untouched ones, dispatch the healthy ones fused, and serve the
        failed or open ones on the host rung, scoped to their subsets
        and matching requests; merge each answered request's results."""
        from ..parallel.partition import merge_partition_results

        part = self.partitioner
        client = self.client
        prefetch = getattr(client, "prefetch_external", None)
        if prefetch is not None:
            # one deduped external-data fetch epoch for the whole batch
            # (every partition dispatch then serves from the cache)
            try:
                prefetch(reviews)
            except Exception:
                pass
        try:
            masks = client.partition_match_mask(
                reviews, [p.subset for p in plan.partitions]
            )
        except Exception:
            # sound fallback: every partition sees every request
            masks = [[True] * len(reviews) for _ in plan.partitions]
        fused: List[Any] = []
        host_parts: List[Any] = []
        skipped_parts: List[int] = []
        for p, mask in zip(plan.partitions, masks):
            if not any(mask):
                # nothing in this batch touches the partition: zero
                # cost, zero degraded dispatches — the blast-radius
                # contract for requests matching only healthy subsets
                part.note_dispatch("skipped", p.device)
                skipped_parts.append(p.index)
                continue
            br = part.breaker(p.device)
            if not br.allow():
                if self.metrics is not None:
                    self.metrics.record(
                        "webhook_degraded_dispatch_total", 1,
                        plane=self.plane,
                    )
                host_parts.append(p)
            elif not self._ensure_staged_nowait(part, p):
                # restage not complete (re-home backoff, or a churned
                # sub-program compiling in the background,
                # docs/compile.md): host rung — correct verdicts from
                # the interpreter, NOT a degraded dispatch — until the
                # swap lands
                host_parts.append(p)
            else:
                fused.append((p, br))

        # mask-sliced dispatch: each partition evaluates ONLY the
        # requests its mask row selects — the requests that can produce
        # a result from its subset. Unselected rows contribute zero
        # results by the mask's definition, so padding them back as
        # empty lists keeps merged verdicts bit-identical to the
        # monolith while rows_dispatched drops to the matched cells.
        sel_by_part = {
            p.index: [i for i, hit in enumerate(masks[p.index]) if hit]
            for p in plan.partitions
        }

        def run_one(p, br):
            sel = sel_by_part[p.index]
            try:
                return p, br, client.review_many_subset(
                    [reviews[i] for i in sel], p.subset,
                    device=p.device, partition=p.index,
                ), None
            except Exception as e:
                return p, br, None, e

        executor = part.executor if len(fused) > 1 else None
        if executor is not None:
            outcomes = list(executor.map(lambda a: run_one(*a), fused))
        else:
            outcomes = [run_one(p, br) for p, br in fused]
        # partition index -> per-request result lists
        part_results: Dict[int, List[List[Any]]] = {}
        for p, br, resps, exc in outcomes:
            if exc is None:
                br.record_success()
                part.note_dispatch("fused", p.device)
                rows: List[List[Any]] = [[] for _ in reviews]
                for i, responses in zip(sel_by_part[p.index], resps):
                    resp = responses.by_target.get(self.target)
                    rows[i] = resp.results if resp is not None else []
                part_results[p.index] = rows
            else:
                br.record_failure()
                self.batch_failures += 1
                if self.metrics is not None:
                    self.metrics.record("webhook_batch_failures_total", 1)
                part.note_dispatch("failed", p.device)
                host_parts.append(p)
        # host rung, scoped: only the degraded partitions' subsets, and
        # only the requests those subsets match
        errors: Dict[int, Exception] = {}
        degraded_reqs: Dict[int, List[int]] = {}
        for p in host_parts:
            try:
                fire("webhook.host_review")
            except FaultError as e:
                for i, hit in enumerate(masks[p.index]):
                    if hit:
                        errors.setdefault(i, EvaluationUnavailable(str(e)))
                part.note_dispatch("host", p.device)
                continue
            rows = [[] for _ in reviews]
            for i, review in enumerate(reviews):
                if not masks[p.index][i]:
                    continue
                degraded_reqs.setdefault(i, []).append(p.index)
                try:
                    responses = client.review_host(review, subset=p.subset)
                    resp = responses.by_target.get(self.target)
                    rows[i] = resp.results if resp is not None else []
                except Exception as e:
                    errors[i] = e
            part_results[p.index] = rows
            part.note_dispatch("host", p.device)
        answers = {
            i: merge_partition_results(
                [rows[i] for rows in part_results.values()],
                plan.order,
            )
            for i in range(len(reviews)) if i not in errors
        }
        return _PartitionReview(
            masks, host_parts, skipped_parts, sel_by_part, degraded_reqs,
            errors, answers,
        )

    def _note_partitioned(self, batch, reviews, plan, wall0, t0, out,
                          fetch0, skip0) -> None:
        """A partitioned batch's bookkeeping: its count, ring spans,
        pruning series, decision facts and integrity samples."""
        part = self.partitioner
        masks, host_parts, skipped_parts = (
            out.masks, out.host_parts, out.skipped_parts
        )
        sel_by_part, degraded_reqs = out.sel_by_part, out.degraded_reqs
        self._count_batch("partitioned", len(batch))
        self._record_spans(
            batch, wall0, t0,
            route="batched" if not host_parts else "partitioned",
        )
        if self.tracer is not None and degraded_reqs:
            # per-REQUEST degraded accounting: only requests whose
            # verdict was (partly) served from the host rung carry the
            # span — requests matching only healthy partitions show a
            # pure fused trace (the chaos e2e pins this)
            wall1 = wall0 + (time.perf_counter() - t0)
            for i, pidx in degraded_reqs.items():
                ctx = batch[i][2]
                if ctx is not None:
                    self.tracer.record_span(
                        "degraded_subset", wall0, wall1, parent=ctx,
                        plane=self.plane, partitions=sorted(pidx),
                    )
        # dispatch-explain facts (docs/observability.md §Decision log):
        # per-partition pruning-efficiency series — fused and host
        # partitions both evaluate only their mask-selected requests, a
        # mask-skipped partition nothing — plus the per-request
        # partition set and mask-derived rows
        host_idx = {p.index for p in host_parts}
        n_rev = len(reviews)
        key_count = {p.index: len(p.keys) for p in plan.partitions}
        corpus_rows = sum(key_count.values())
        touched = len(plan.partitions) - len(skipped_parts)
        note_touched = getattr(part, "note_batch_touched", None)
        if note_touched is not None:
            note_touched(touched, len(plan.partitions))
        for p, mask in zip(plan.partitions, masks):
            if p.index in skipped_parts:
                dispatched = 0
            else:
                dispatched = key_count[p.index] * len(
                    sel_by_part[p.index]
                )
            self._note_rows(
                p.index, dispatched, key_count[p.index] * n_rev
            )
        if self.decisions is not None:
            per_request: Dict[int, Dict[str, Any]] = {}
            for i in range(n_rev):
                matched = [
                    p.index
                    for p in plan.partitions
                    if masks[p.index][i]
                ]
                facts: Dict[str, Any] = {
                    "partitions_matched": matched,
                    "partitions_skipped": list(skipped_parts),
                    "partitions_touched": touched,
                    "rows_total": corpus_rows,
                    # provably-dead rows the corpus analyzer removed
                    # from the plan before dispatch ("why didn't this
                    # constraint fire" — docs/analysis.md)
                    "rows_excluded_static": len(
                        getattr(plan, "excluded_static", ()) or ()
                    ),
                    # the per-request rows pruned dispatch pays:
                    # constraint rows of the partitions this request's
                    # mask actually selects
                    "rows_dispatched": sum(
                        key_count[j] for j in matched
                    ),
                }
                if i in degraded_reqs:
                    facts["route"] = "degraded"
                    facts["partitions_degraded"] = sorted(
                        degraded_reqs[i]
                    )
                per_request[i] = facts
            self._note_decisions(
                batch, self._driver_route(n_rev),
                extdata_fetches=self._extdata_fetch_count() - fetch0,
                per_request=per_request,
                columns_skipped_static=(
                    self._liveness_skipped_count() - skip0
                ),
            )
        for i, merged in out.answers.items():
            self._note_integrity(
                batch[i][2], reviews[i], merged, route="partitioned",
            )

    def _dispatch_host(self, batch, reviews, wall0: float, t0: float,
                       route: str) -> None:
        """The host-oracle rung of the degradation ladder: per-request
        INTERPRETER evaluation (`Client.review_host` — never a second
        device attempt). A request whose own host evaluation fails
        keeps its error (a poisoned request is still a 500); only when
        the host plane itself is down does the batch fall to the final
        rung — the typed EvaluationUnavailable that the handler answers
        with the endpoint's fail-open/fail-closed envelope."""
        try:
            fire("webhook.host_review")
        except FaultError as e:
            self._count_batch("unavailable")
            for _, fut, _, _, _, _ in batch:
                fut.set_exception(EvaluationUnavailable(str(e)))
            self._record_spans(batch, wall0, t0, route="unavailable")
            self._note_decisions(batch, "unavailable")
            return
        self._count_batch(route)
        prefetch = getattr(self.client, "prefetch_external", None)
        if prefetch is not None:
            # a breaker-open batch never reached the fused path's
            # prefetch: dedupe + fetch the batch's external-data keys
            # once HERE so the per-request host evaluations below hit
            # the cache (one outbound fetch per provider per batch on
            # the degraded rung too)
            try:
                prefetch(reviews)
            except Exception:
                pass
        host = getattr(self.client, "review_host", None)
        if host is None:
            host = self.client.review
        fetch0 = self._extdata_fetch_count()
        # each request is answered as soon as its own evaluation ends;
        # annotated here (`gk.batch.review`): no driver stage runs inside
        with self._stage("review"):
            for review, (_, fut, _, _, _, _) in zip(reviews, batch):
                try:
                    responses = host(review)
                    resp = responses.by_target.get(self.target)
                    fut.set_result(
                        resp.results if resp is not None else []
                    )
                except Exception as e:
                    fut.set_exception(e)
        with self._stage("bookkeeping"):
            self._record_spans(batch, wall0, t0, route=route)
            # host rung facts: every corpus row still evaluates, on the
            # interpreter — "degraded" when the breaker steered here,
            # "host" when a failed fused attempt fell back
            rows = self._corpus_rows()
            if rows is not None:
                self._note_rows(
                    "mono", rows * len(batch), rows * len(batch)
                )
            self._note_decisions(
                batch, "degraded" if route == "degraded" else "host",
                rows_dispatched=rows, rows_total=rows,
                extdata_fetches=self._extdata_fetch_count() - fetch0,
            )

    def _record_spans(self, batch, wall0: float, t0: float, route: str) -> None:
        """Stamp this batch's shared timing window into every traced
        member request: queue_wait (submit -> dispatch start), dispatch
        (the batch's evaluation), and its flatten_encode /
        device_dispatch / render children at the times the driver ran
        them for this batch, on this worker thread (its perf_counter
        stage stamps, placed on the wall clock through the dispatch
        start's (wall0, t0) pair)."""
        if self.tracer is None:
            return
        wall1 = wall0 + (time.perf_counter() - t0)
        stats = self._dispatch_stats()
        stamps = stats.get("phase_stamps") or {}
        if not stamps or min(b for b, _ in stamps.values()) < t0:
            # this thread's last evaluation was not this batch's (the
            # interpreter and host routes run none)
            stats, stamps = {}, {}
        attrs: Dict[str, Any] = {
            k: stats[k]
            for k in ("compiled_pairs", "interp_pairs", "n_results")
            if k in stats
        }
        spans = {
            phase: (wall0 + (b - t0), wall0 + (e - t0))
            for phase, (b, e) in stamps.items()
        }
        # on the interpreter route rendering is inlined in the
        # evaluation: render is then a point span at the window's end
        render = spans.get("render", (wall1, wall1))
        for _, _, ctx, (sub_wall, _sub_perf), _, _ in batch:
            if ctx is None:
                continue
            self.tracer.record_span(
                "queue_wait", sub_wall, wall0, parent=ctx
            )
            d_ctx = self.tracer.record_span(
                "dispatch", wall0, wall1, parent=ctx,
                batch_size=len(batch), route=route, **attrs
            )
            for phase in ("flatten_encode", "device_dispatch"):
                if phase in spans:
                    self.tracer.record_span(
                        phase, *spans[phase], parent=d_ctx
                    )
            self.tracer.record_span("render", *render, parent=d_ctx)


class BatchedValidationHandler(ValidationHandler):
    """ValidationHandler whose review path goes through the batcher."""

    def __init__(
        self,
        batcher: MicroBatcher,
        request_timeout: float = DEFAULT_REQUEST_TIMEOUT,
        **kwargs,
    ):
        super().__init__(
            batcher.client,
            batcher.target,
            namespace_getter=batcher.namespace_getter,
            **kwargs,
        )
        self.batcher = batcher
        self.request_timeout = request_timeout

    def _review(
        self, request: Dict[str, Any], tracing: bool = False, span=None,
        origin: str = "original",
    ) -> List[Any]:
        if tracing:
            # traced requests bypass the batcher: traces are per-request
            # by definition (the driver's batched path declines tracing)
            return super()._review(
                request, tracing=True, span=span, origin=origin
            )
        return self._submit(request, span)

    def _review_group(
        self, requests: List[Dict[str, Any]], tracing: bool = False,
        span=None,
    ) -> List[List[Any]]:
        """A parent and its implied requests go to the batcher as ONE
        entry, so they ride the same fused dispatch."""
        if tracing:
            return super()._review_group(requests, tracing=True, span=span)
        return self._submit(list(requests), span)

    def _submit(self, request, span):
        """Submit one request, or a parent and its implied requests (a
        list, whose first member names the tenant), and wait for the
        answer."""
        ctx = getattr(span, "context", None)
        # deadline propagation: the request's remaining budget rides to
        # the batch worker so expiry is checked BEFORE dispatch. Tenant
        # identity is extracted BEFORE enqueue too — shed verdicts must
        # carry it, and the scheduler's quotas key on it.
        budget = self.request_timeout
        deadline = self.batcher._now() + budget
        parent = request[0] if isinstance(request, list) else request
        tenant = {
            "namespace": parent.get("namespace", ""),
            "username": (parent.get("userInfo") or {}).get(
                "username", ""
            ),
        }
        fut = self.batcher.submit(
            request, span_ctx=ctx, deadline=deadline, tenant=tenant
        )
        try:
            return self.batcher.result(fut, budget)
        except _FutureTimeout:
            # a hung dispatch (device stall): the caller gets the typed
            # unavailability — answered per fail policy — while the
            # worker finishes or dies in the background
            raise EvaluationTimeout(
                f"admission evaluation exceeded {budget:.3f}s"
            ) from None


class WebhookServer:
    """Stdlib HTTP(S) server: POST /v1/admit and /v1/admitlabel with
    AdmissionReview JSON bodies. `tls=True` terminates HTTPS with the
    rotating self-signed pair from `certs.CertRotator` (cert_dir
    defaults to a per-server temp dir)."""

    def __init__(
        self,
        client,
        target: str,
        port: int = 0,
        excluder=None,
        namespace_getter=None,
        exempt_namespaces=None,
        window_ms: float = 2.0,
        metrics=None,
        tls: bool = False,
        cert_dir: Optional[str] = None,
        # pre-built cert rotator (fleet.FleetCertRotator for the
        # Secret-backed shared store); None builds a pod-local
        # CertRotator in cert_dir. When the rotator exposes on_rotate
        # (the fleet one does), a rotation — our own OR a peer's —
        # re-loads the live SSL context so new handshakes serve the new
        # pair WITHOUT a restart.
        rotator=None,
        request_timeout: float = DEFAULT_REQUEST_TIMEOUT,
        trace_config=None,
        event_sink=None,
        emit_admission_events: bool = False,
        log_denies: bool = False,
        logger=None,
        tracer=None,
        # mutation.MutationSystem: wires the /v1/mutate plane (None =
        # endpoint returns 404, validation-only pod)
        mutation_system=None,
        # overload / degradation envelope (docs/robustness.md):
        # fail_policy is what a shed/expired/unevaluable request gets —
        # "open" (allow; the reference's failurePolicy: Ignore posture)
        # or "closed" (deny 503); max_queue bounds the admission queue
        fail_policy: str = "open",
        max_queue: Optional[int] = DEFAULT_MAX_QUEUE,
        # "127.0.0.1" keeps tests hermetic; in-cluster serving must bind
        # the pod IP surface ("0.0.0.0" via run.py) or the apiserver and
        # kubelet probes can never connect
        bind_addr: str = "127.0.0.1",
        # agent-action plane (docs/targets.md): True wires
        # POST /v1/agent/review over the client's registered
        # AgentActionTarget; agent_mutation_system additionally screens
        # and rewrites tool-call arguments before validation
        agent_review: bool = False,
        agent_mutation_system=None,
        # graceful drain (docs/robustness.md): seconds stop() holds the
        # listener OPEN after flipping readiness, so a load balancer
        # watching /readyz routes away before connections start failing
        # (the preStop-sleep pattern; 0 = flip-and-close immediately)
        drain_grace_s: float = 0.0,
        # device fault domains (docs/robustness.md §Fault domains):
        # split the constraint corpus into this many partitions, each
        # on its own logical device with its own breaker — a sick
        # device sheds only its constraint subset, not the plane.
        # 0/None keeps the monolithic dispatch + single plane breaker.
        partitions: Optional[int] = None,
        partition_devices: Optional[int] = None,
        # obs.FlightRecorder: threaded to the batchers (shed bursts),
        # the plane breaker, and the partitioner's per-device breakers
        # so a trip anywhere on this server captures one postmortem
        recorder=None,
        # obs.DecisionLog: per-admission "why" records across every
        # plane this server mounts (validation / mutation / agent);
        # None = decision plane off (docs/observability.md §Decision
        # log; bench_webhook --attribution measures the on/off delta)
        decision_log=None,
        # obs.CostAttributor: measured per-constraint device seconds
        # feed the partition planner (cost/locality-guided plan builds
        # instead of round-robin); replica tags /debug/partitions the
        # way /debug/costs is tagged
        attributor=None,
        replica: Optional[str] = None,
        # analysis.corpus.CorpusPlane: feeds the partition planner its
        # provably-dead (verdict-safe prunable) constraint keys
        corpus=None,
        # admission scheduling (docs/operations.md §Admission
        # scheduling): policy for every plane's batcher — "fifo" is the
        # bit-compatible rollback path, "deadline" enables EDF batch
        # formation + predictive shedding + fair-share quotas. slo is
        # the obs.SloEngine feeding the overload/saturation loop.
        sched_policy: str = "fifo",
        slo=None,
        # integrity.IntegrityPlane (docs/robustness.md §Verdict
        # integrity): shadow-oracle sampling on the validation batcher
        # + corruption-quarantine wiring to the partitioner
        integrity=None,
        # expansion.ExpansionSystem (docs/expansion.md): workloads are
        # reviewed with the resources their templates imply
        expansion_system=None,
    ):
        self.client = client  # warmup() compiles through it
        self.metrics = metrics
        self.tracer = tracer
        self.recorder = recorder
        self.decision_log = decision_log
        self.request_timeout = request_timeout
        self.drain_grace_s = drain_grace_s
        self.partitioner = None
        if partitions:
            from ..parallel.partition import PartitionDispatcher

            self.partitioner = PartitionDispatcher(
                client,
                target,
                k=partitions,
                devices=partition_devices,
                plane="validation",
                metrics=metrics,
                tracer=tracer,
                recorder=recorder,
                attributor=attributor,
                replica=replica,
                corpus=corpus,
            )
        # graceful-drain state: `draining` flips BEFORE the listener
        # closes (readiness consults it), in-flight HTTP requests are
        # counted so stop() can wait for them, and on_drain callbacks
        # let the control plane (Runner readyz, a soak harness's LB
        # model) observe the flip at its exact ordering point
        self.draining = False
        self._inflight = 0
        self._inflight_cv = threading.Condition()
        self._drain_callbacks: List[Callable[[], None]] = []
        self.batcher = MicroBatcher(
            client, target, window_ms=window_ms,
            namespace_getter=namespace_getter,
            metrics=metrics, tracer=tracer,
            max_queue=max_queue,
            partitioner=self.partitioner,
            recorder=recorder,
            decisions=decision_log,
            sched_policy=sched_policy,
            slo=slo,
            attributor=attributor,
            integrity=integrity,
        )
        self.integrity = integrity
        if integrity is not None:
            # the mismatch ledger needs the dispatcher to trip
            # corruption quarantine; the shadow oracle re-evaluates
            # through the serving client's host rung
            try:
                integrity.attach_client(client)
                if self.partitioner is not None:
                    integrity.attach_dispatcher(self.partitioner)
            except Exception:
                pass
        self.mutate_batcher = None
        self.mutation_handler = None
        if mutation_system is not None:
            # local import: mutate.py imports from this module
            from .mutate import MutateBatcher, MutationHandler

            self.mutate_batcher = MutateBatcher(
                mutation_system, window_ms=window_ms,
                namespace_getter=namespace_getter,
                metrics=metrics, tracer=tracer,
                max_queue=max_queue,
                decisions=decision_log,
                sched_policy=sched_policy,
                slo=slo,
                attributor=attributor,
            )
            self.mutation_handler = MutationHandler(
                self.mutate_batcher,
                excluder=excluder,
                metrics=metrics,
                request_timeout=request_timeout,
                logger=logger,
                tracer=tracer,
                fail_policy=fail_policy,
                decision_log=decision_log,
            )
        self.handler = BatchedValidationHandler(
            self.batcher, excluder=excluder, metrics=metrics,
            request_timeout=request_timeout,
            trace_config=trace_config,
            event_sink=event_sink,
            emit_admission_events=emit_admission_events,
            log_denies=log_denies,
            logger=logger,
            tracer=tracer,
            fail_policy=fail_policy,
            decision_log=decision_log,
            expansion=expansion_system,
        )
        self.label_handler = NamespaceLabelHandler(exempt_namespaces)
        self.agent_batcher = None
        self.agent_mutate_batcher = None
        self.agent_handler = None
        if agent_review:
            from ..agentaction import make_agent_plane

            (
                self.agent_batcher,
                self.agent_mutate_batcher,
                self.agent_handler,
            ) = make_agent_plane(
                client,
                window_ms=window_ms,
                mutation_system=agent_mutation_system,
                metrics=metrics,
                tracer=tracer,
                logger=logger,
                fail_policy=fail_policy,
                request_timeout=request_timeout,
                max_queue=max_queue,
                decision_log=decision_log,
                sched_policy=sched_policy,
                slo=slo,
                attributor=attributor,
            )
        outer = self

        class _Handled(Exception):
            """Control flow: response already written by the branch."""

        class _Handler(BaseHTTPRequestHandler):
            # HTTP/1.1 keep-alive: every response already carries an
            # explicit Content-Length, so persistent connections are
            # safe — sequential admissions from one client reuse a
            # single socket instead of paying setup per request.
            # Chunked bodies are not produced or accepted.
            protocol_version = "HTTP/1.1"

            def do_POST(self):  # noqa: N802
                # in-flight accounting: an ACCEPTED request must finish
                # even when stop() runs concurrently — the drain waits
                # on this counter before tearing the batchers down
                with outer._inflight_cv:
                    outer._inflight += 1
                try:
                    self._do_post()
                finally:
                    with outer._inflight_cv:
                        outer._inflight -= 1
                        outer._inflight_cv.notify_all()

            def _do_post(self):
                length = self.headers.get("Content-Length", "0").strip()
                if "Transfer-Encoding" in self.headers or not (
                    length.isascii() and length.isdigit()
                ):
                    # the body's end can't be found (non-integer or
                    # negative length, chunked): reading would raise or
                    # block until the peer closes, so answer and drop
                    # the connection, whose next byte is unknown
                    self._send(400, json.dumps({
                        "error": "request body needs a non-negative "
                        "integer Content-Length and no Transfer-Encoding"
                    }).encode(), close=True)
                    return
                length = int(length)
                # the front door's own work: decode here, respond below,
                # one `front` observation per request
                with stage("http.decode") as decode:
                    body = self.rfile.read(length)
                    try:
                        review, bad = json.loads(body), None
                    except Exception as e:  # answered with a 500 below
                        review, bad = None, e
                trace_id = None
                resp = None
                status = 200
                try:
                    if bad is not None:
                        raise bad
                    request = review.get("request") or {}
                    # W3C trace propagation (docs/observability.md):
                    # an inbound `traceparent` names the request's
                    # trace; without one the admission UID derives a
                    # deterministic id — either way the id rides the
                    # handler's root span, the denial log, the response
                    # envelope, and /debug/traces?trace_id=
                    trace_id = parse_traceparent(
                        self.headers.get("traceparent")
                    ) or derive_trace_id(request.get("uid"))
                    if self.path == "/v1/admitlabel":
                        resp = outer.label_handler.handle(request)
                    elif self.path == "/v1/mutate":
                        if outer.mutation_handler is None:
                            payload = json.dumps(
                                {"error": "mutation not enabled"}
                            ).encode()
                            status = 404
                            raise _Handled()
                        resp = outer.mutation_handler.handle(
                            request, trace_id=trace_id
                        )
                    elif self.path == "/v1/agent/review":
                        if outer.agent_handler is None:
                            payload = json.dumps(
                                {"error": "agent review not enabled"}
                            ).encode()
                            status = 404
                            raise _Handled()
                        resp = outer.agent_handler.handle(
                            request, trace_id=trace_id
                        )
                    else:
                        resp = outer.handler.handle(
                            request, trace_id=trace_id
                        )
                except _Handled:
                    pass
                except Exception as e:
                    payload = json.dumps({"error": str(e)}).encode()
                    status = 500
                with stage("http.respond") as respond:
                    if resp is not None:
                        try:
                            payload = json.dumps(
                                review_envelope(
                                    review, request, resp,
                                    trace_id=trace_id,
                                )
                            ).encode()
                        except Exception as e:
                            payload = json.dumps({"error": str(e)}).encode()
                            status = 500
                    self._send(status, payload, trace_id=trace_id)
                if outer.metrics is not None:
                    outer.metrics.observe(
                        "webhook_request_stage_seconds",
                        decode.seconds + respond.seconds, stage="front",
                    )

            def _send(self, status, payload, trace_id=None, close=False):
                self.send_response(status)
                try:
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(payload)))
                    if trace_id is not None:
                        self.send_header(
                            "traceparent", format_traceparent(trace_id)
                        )
                    if close:
                        # also sets self.close_connection
                        self.send_header("Connection", "close")
                    self.end_headers()
                    self.wfile.write(payload)
                except (BrokenPipeError, ConnectionResetError):
                    # client gave up (its own timeout) mid-response —
                    # nothing to salvage, and the handler thread must
                    # not die noisily
                    pass

            def log_message(self, *args):  # silence default stderr spam
                pass

        class _Server(ThreadingHTTPServer):
            # the stdlib default backlog (5) resets bursts of
            # concurrent connections — exactly the micro-batching
            # workload; deep enough for a full batch window
            request_queue_size = 512
            daemon_threads = True

        self._httpd = _Server((bind_addr, port), _Handler)
        self.rotator = rotator
        self._ssl_ctx = None
        if tls:
            import ssl
            import tempfile

            from .certs import CertRotator

            if self.rotator is None:
                if cert_dir is None:
                    cert_dir = tempfile.mkdtemp(prefix="gk-certs-")
                self.rotator = CertRotator(cert_dir)
            cert_path, key_path = self.rotator.ensure()  # CertsMounted gate
            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ctx.load_cert_chain(cert_path, key_path)
            self._ssl_ctx = ctx
            self._httpd.socket = ctx.wrap_socket(
                self._httpd.socket, server_side=True
            )
            # rotation pickup without restart: SSLContext is live — a
            # re-load swaps the pair for every handshake AFTER this
            # point while established connections finish on the old one
            on_rotate = getattr(self.rotator, "on_rotate", None)
            if on_rotate is not None:
                on_rotate(self._reload_tls)
        self.scheme = "https" if tls else "http"
        self.port = self._httpd.server_address[1]
        self._thread: Optional[threading.Thread] = None
        self.warm = False

    def _reload_tls(self) -> None:
        if self._ssl_ctx is None or self.rotator is None:
            return
        try:
            self._ssl_ctx.load_cert_chain(
                self.rotator.cert_path, self.rotator.key_path
            )
        except Exception:
            # a torn read is impossible (atomic-rename installs), but a
            # rotation racing deletion must not kill serving — the old
            # pair keeps serving until the next successful reload
            pass

    def start(self) -> None:
        self.batcher.start()
        if self.mutate_batcher is not None:
            self.mutate_batcher.start()
        if self.agent_batcher is not None:
            self.agent_batcher.start()
        if self.agent_mutate_batcher is not None:
            self.agent_mutate_batcher.start()
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True
        )
        self._thread.start()

    def warmup(self, sample_objects=None) -> float:
        """Pre-compile the fused review path for common batch shapes so
        the first real admission request doesn't pay the jit compile
        inside its deadline (first compile is tens of seconds on TPU;
        the reference has no analog — its interpreter has no compile
        step, but it DOES gate Ready on state ingestion; compile warmth
        is this engine's equivalent). Returns seconds spent."""
        from ..constraint.handler import handler_for

        t0 = time.monotonic()
        handler = handler_for(self.client, self.batcher.target)
        if sample_objects is None:
            # the target supplies shape-covering synthetic requests
            requests = handler.sample_requests(192)
        else:
            requests = sample_objects
        reviews = [handler.augment_request(r) for r in requests]
        # device-sized batches covering the common occupancy buckets
        # (row counts bucket at 64/128/256; sub-device-threshold batches
        # route to the interpreter and need no compile).
        # warm_review_path compiles WITHOUT holding the driver's serving
        # mutex, so admission keeps flowing on the interpreter route
        # until the compiled route swaps in (serve-while-compiling).
        # The attribute/callable resolution stays OUTSIDE the try: a
        # silently-swallowed AttributeError here turned the whole warmup
        # into a no-op for a full round; only the compile itself is
        # best-effort.
        warm = self.client.warm_review_path
        for batch in (reviews[:16], reviews[:100], reviews):
            try:
                warm(batch)
            except Exception:
                pass  # warmup is best-effort; serving works unwarmed
        self.warm = True
        return time.monotonic() - t0

    def sched_snapshot(self) -> Dict[str, Any]:
        """Per-plane admission-scheduler state: the `/readyz`
        `stats.sched` and `/debug/sched` document (docs/operations.md
        §Admission scheduling)."""
        out: Dict[str, Any] = {"validation": self.batcher.sched.snapshot()}
        if self.mutate_batcher is not None:
            out["mutation"] = self.mutate_batcher.sched.snapshot()
        if self.agent_batcher is not None:
            out["agent"] = self.agent_batcher.sched.snapshot()
        if self.agent_mutate_batcher is not None:
            out["agent_mutation"] = (
                self.agent_mutate_batcher.sched.snapshot()
            )
        return out

    # -- graceful drain (docs/robustness.md) ---------------------------------

    @property
    def ready(self) -> bool:
        """Serving readiness: False from the instant a drain begins —
        the signal a load balancer / kubelet readiness probe needs
        BEFORE the listener goes away."""
        return not self.draining

    def on_drain(self, callback: Callable[[], None]) -> None:
        """Register a callback fired when draining flips (before the
        listener closes). Used by the Runner's readyz plane and by
        harnesses modeling a load balancer."""
        self._drain_callbacks.append(callback)

    def begin_drain(self) -> None:
        """Flip not-ready. Idempotent; does NOT close anything — the
        listener keeps accepting (and the batchers keep evaluating)
        until stop() proceeds, so a request racing the flip still gets
        a real answer instead of a reset."""
        if self.draining:
            return
        self.draining = True
        for cb in list(self._drain_callbacks):
            try:
                cb()
            except Exception:
                pass  # observers must not be able to wedge the drain

    def _await_inflight(self, timeout: float) -> bool:
        deadline = time.monotonic() + timeout
        with self._inflight_cv:
            while self._inflight > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._inflight_cv.wait(timeout=min(remaining, 0.1))
        return True

    def stop(self, drain_grace_s: Optional[float] = None) -> None:
        """Graceful shutdown, in the only order that sheds zero
        accepted requests: (1) readiness flips not-ready, (2) the
        drain grace lets the LB react while the listener still
        accepts, (3) the listener closes, (4) in-flight requests —
        which block on batch futures — complete because the batchers
        are STILL RUNNING, and only then (5) the batchers stop
        (dispatching any leftovers inline) and the socket is released.
        A SIGTERM mid-load therefore answers everything it accepted."""
        grace = self.drain_grace_s if drain_grace_s is None else drain_grace_s
        self.begin_drain()
        if grace > 0:
            time.sleep(grace)
        self._httpd.shutdown()
        # bounded by the request envelope: no accepted request can
        # legitimately outlive its own timeout + a dispatch window
        self._await_inflight(min(self.request_timeout + 1.0, 15.0))
        self.batcher.stop()
        if self.mutate_batcher is not None:
            self.mutate_batcher.stop()
        if self.agent_batcher is not None:
            self.agent_batcher.stop()
        if self.agent_mutate_batcher is not None:
            self.agent_mutate_batcher.stop()
        if self.partitioner is not None:
            self.partitioner.close()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        # release the listening socket fd: a soak that restarts
        # replicas repeatedly must not leak one fd per lifecycle
        try:
            self._httpd.server_close()
        except Exception:
            pass
