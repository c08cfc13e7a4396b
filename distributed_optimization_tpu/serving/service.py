"""The simulation service: submit configs, get RunTrace manifests back.

``SimulationService`` is the Python front end of the serving subsystem
(docs/SERVING.md) — the daemon (``serving/daemon.py``) is a thin HTTP shim
over it:

- ``submit(config)`` validates the request (strict field check + the
  frozen config's own cross-field validation; malformed requests raise
  ``ServingError`` with the validation message, they never enter the
  queue) and enqueues it. The queue is bounded (``max_pending``), and so
  is the finished-request history (``max_done`` — a long-lived daemon
  rotates out old results instead of retaining every payload forever).
- a scheduler loop (``start()`` / the daemon) or an explicit ``drain()``
  coalesces pending requests within a wait window into ``run_batch``
  cohorts (``serving/coalescer.py``), executes each cohort through the
  process executable cache, and resolves every request to its own
  per-replica slice.
- each finished request carries its ``BackendRunResult`` AND a
  schema-versioned ``RunTrace`` manifest whose health block records the
  serving facts (cache hit, compile seconds saved, cohort size, queue
  wait) — the JSONL the daemon streams back.

Failure isolation: an exception while executing one plan (e.g. a config
that passes field validation but is rejected by the backend, like a robust
budget exceeding the topology's min degree) fails THAT plan's requests
with a structured error and leaves the queue, other cohorts, and the
scheduler loop alive — tests/test_serving.py submits exactly such a poison
request next to a healthy cohort.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Any, Optional

from distributed_optimization_tpu.config import ExperimentConfig
from distributed_optimization_tpu.log import get_logger
from distributed_optimization_tpu.observability.metrics_registry import (
    metrics_registry,
)
from distributed_optimization_tpu.observability.progress import (
    ProgressEvent,
    ProgressStream,
)
from distributed_optimization_tpu.observability.spans import Tracer
from distributed_optimization_tpu.serving.admission import (
    DEFAULT_PRIORITY,
    DEFAULT_TENANT,
    AdmissionError,
    ShedLoad,
    WeightedFairQueue,
    validate_priority,
    validate_tenant,
)
from distributed_optimization_tpu.serving.cache import (
    ExecutableCache,
    process_cache_enabled,
    process_executable_cache,
)
from distributed_optimization_tpu.serving.coalescer import (
    REPLICAS_UNSUPPORTED_REASON,
    execute_plan,
    plan_cohorts,
)

_log = get_logger("serving")

_CONFIG_FIELDS = frozenset(
    f.name for f in dataclasses.fields(ExperimentConfig)
)

QUEUED = "queued"
RUNNING = "running"
DONE = "done"
FAILED = "failed"


class ServingError(ValueError):
    """A rejected request — malformed JSON shape, unknown fields, or a
    config the validation layer refuses. The daemon maps it to a
    structured 400 response; it never kills in-flight work."""


class QueueFullError(ServingError):
    """Backpressure, not a bad request: the bounded queue is full and the
    submission should be RETRIED after in-flight work drains. The daemon
    maps it to 429 so clients can tell it apart from a permanently
    invalid config. Shed-load rejections (per-tenant or global caps,
    ISSUE-15) carry the admission controller's reason and tenant."""

    def __init__(self, detail, *, reason="global_cap", tenant=DEFAULT_TENANT):
        super().__init__(detail)
        self.reason = reason
        self.tenant = tenant


class DrainingError(ServingError):
    """The service is draining toward shutdown: in-flight work finishes,
    NEW submissions are refused. The daemon maps it to 503 — retryable by
    the client contract, because a drain usually precedes a restart that
    will accept the retry."""


@dataclasses.dataclass
class ServingOptions:
    """Scheduler knobs (the daemon exposes them as flags).

    ``window_s``: how long the scheduler waits after work arrives before
    cutting cohorts — the latency it trades for coalescing opportunity.
    ``max_cohort``: replica-axis cap per ``run_batch`` call. ``max_pending``
    bounds the queue (submits beyond it are rejected, not buffered without
    limit); in-flight work is additionally bounded by the scheduler being
    single-threaded — one cohort executes at a time on the one chip.
    ``max_done`` bounds the FINISHED-request history: a long-lived daemon
    must not retain every served result forever, so once more than
    ``max_done`` requests have completed, the oldest finished records (and
    their result payloads/manifests) are dropped — a later result poll for
    an evicted id gets "unknown request", the serving analogue of a log
    rotation. Pending/running requests are never evicted.
    ``progress_every`` is the heartbeat cadence (in eval-chunks) of the
    live progress streams (``/v1/progress/<id>``): every executed plan
    runs with progress on, in segments of this many eval-chunks — the
    continuation machinery, bitwise the one-shot program.
    ``monitors`` (ISSUE-13) attaches one anomaly ``MonitorBank`` per
    request to those heartbeats: detector firings surface as structured
    incidents in ``/v1/status`` and as ``kind='anomaly'`` events on the
    request's progress stream, and land in the response manifest's
    health block. Observation only — the serving plane never halts a
    paying request (``halt_on='never'``); it costs one Python callback
    per heartbeat.

    Admission/fairness (ISSUE-15): ``max_pending_per_tenant`` caps one
    tenant's queued depth (None = only the global bound), and
    ``tenant_weights`` biases the weighted-fair scheduler (unlisted
    tenants weigh 1.0). ``cut_budget`` bounds how many requests one
    scheduler cut dequeues (None = everything pending — the PR-7
    behavior); a bounded cut is what keeps a backlogged tenant from
    monopolizing execution order between cuts. ``workers`` > 0 runs
    cohorts on that many spawned worker processes (``serving/
    workers.py``) instead of the scheduler thread — the persistent store
    (``DOPT_EXEC_STORE``) is their shared warm tier.
    """

    window_s: float = 0.05
    max_cohort: int = 32
    max_pending: int = 1024
    max_done: int = 512
    # Heartbeats every 5 eval-chunks: the measured sweet spot on the
    # bench container (docs/perf/observatory.json — per-eval heartbeats
    # cost ~14% there, every-5 ~4%, and a served cohort's wall time is
    # dominated by its compile anyway).
    progress_every: int = 5
    monitors: bool = True
    max_pending_per_tenant: Optional[int] = None
    tenant_weights: Optional[dict] = None
    cut_budget: Optional[int] = None
    workers: int = 0
    # Autoscaling headroom (ISSUE-16): the dispatch executor is sized to
    # this many threads (None = ``workers``), so a fleet the autoscaler
    # grows past the initial ``workers`` can actually receive that many
    # concurrent plans — thread pools cannot be resized after the fact.
    max_workers: Optional[int] = None

    def __post_init__(self) -> None:
        if self.progress_every < 1:
            raise ValueError(
                f"progress_every must be >= 1, got {self.progress_every}"
            )
        if self.window_s < 0:
            raise ValueError(f"window_s must be >= 0, got {self.window_s}")
        if self.max_cohort < 1:
            raise ValueError(
                f"max_cohort must be >= 1, got {self.max_cohort}"
            )
        if self.max_pending < 1:
            raise ValueError(
                f"max_pending must be >= 1, got {self.max_pending}"
            )
        if self.max_done < 1:
            raise ValueError(
                f"max_done must be >= 1, got {self.max_done}"
            )
        if self.cut_budget is not None and self.cut_budget < 1:
            raise ValueError(
                f"cut_budget must be >= 1, got {self.cut_budget}"
            )
        if self.workers < 0:
            raise ValueError(
                f"workers must be >= 0, got {self.workers}"
            )
        if self.max_workers is not None and self.max_workers < max(
            self.workers, 1
        ):
            raise ValueError(
                f"max_workers ({self.max_workers}) must be >= "
                f"workers ({self.workers}) and >= 1"
            )


@dataclasses.dataclass
class Request:
    """One submitted simulation request and its lifecycle record."""

    id: str
    config: ExperimentConfig
    submitted_at: float
    # Admission facts (ISSUE-15): which tenant submitted it and at what
    # priority class — what the weighted-fair scheduler ordered on.
    tenant: str = DEFAULT_TENANT
    priority: str = DEFAULT_PRIORITY
    # Which worker process executed it (multi-worker plane); None when
    # the scheduler thread ran it in-process.
    worker: Optional[int] = None
    done: threading.Event = dataclasses.field(
        default_factory=threading.Event, repr=False
    )
    # Live heartbeat channel (ISSUE-10): lifecycle events (queued →
    # running → done/failed) plus the backend's per-chunk progress while
    # the request executes — what the daemon's ``/v1/progress/<id>``
    # streams. Closed when the request finishes.
    progress: ProgressStream = dataclasses.field(
        default_factory=ProgressStream, repr=False
    )
    status: str = QUEUED
    result: Any = None  # BackendRunResult when DONE
    manifest: Optional[dict] = None  # RunTrace dict when DONE
    error: Optional[str] = None  # message when FAILED
    cohort_size: int = 0
    coalesced: bool = False
    sequential_reason: Optional[str] = None
    cache_hit: Optional[bool] = None
    queue_wait_s: Optional[float] = None
    run_wall_s: Optional[float] = None
    # Anomaly-sentinel firings observed on this request's heartbeats
    # (ISSUE-13): compact anomaly dicts, appended live as detectors fire.
    incidents: list = dataclasses.field(default_factory=list)
    # Fleet remediation (ISSUE-16): what the policy engine did about this
    # request (halt/requeue attribution), and how many times remediation
    # requeued it (bounded — one clean re-run per sibling).
    remediation: Optional[dict] = None
    requeues: int = 0

    def status_dict(self) -> dict:
        """The JSON-safe view the daemon returns for status polls."""
        out = {
            "id": self.id,
            "status": self.status,
            "config_hash": self.config.structural_hash(),
            "tenant": self.tenant,
            "priority": self.priority,
        }
        if self.error is not None:
            out["error"] = self.error
        if self.incidents:
            out["incidents"] = [
                {
                    "detector": i["detector"],
                    "severity": i["severity"],
                    "onset_iteration": i["onset_iteration"],
                }
                for i in self.incidents
            ]
        if self.remediation is not None:
            out["remediation"] = self.remediation
        if self.status in (DONE, FAILED):
            out["serving"] = self.serving_block()
        return out

    def serving_block(self) -> dict:
        """The per-request serving facts recorded into the manifest's
        health block (telemetry satellite)."""
        return {
            "cache_hit": self.cache_hit,
            "cohort_size": self.cohort_size,
            "coalesced": self.coalesced,
            "sequential_reason": self.sequential_reason,
            "queue_wait_s": self.queue_wait_s,
            "run_wall_s": self.run_wall_s,
            "tenant": self.tenant,
            "priority": self.priority,
            "worker": self.worker,
        }


def parse_config(payload) -> ExperimentConfig:
    """Strict config parsing for the serving surface.

    Unlike ``ExperimentConfig.from_dict`` (which silently drops unknown
    keys — fine for reading old manifests, wrong for a request API where a
    typoed field would silently run the default), unknown keys are
    rejected, and every validation error surfaces with the config's own
    message.
    """
    if isinstance(payload, ExperimentConfig):
        return payload
    if not isinstance(payload, dict):
        raise ServingError(
            f"config must be a JSON object of ExperimentConfig fields, "
            f"got {type(payload).__name__}"
        )
    unknown = set(payload) - _CONFIG_FIELDS
    if unknown:
        raise ServingError(
            f"unknown config fields {sorted(unknown)}; valid fields are "
            f"the ExperimentConfig schema (docs/SERVING.md)"
        )
    try:
        return ExperimentConfig(**payload)
    except (TypeError, ValueError) as e:
        raise ServingError(f"invalid config: {e}") from e


class SimulationService:
    """Request-driven simulation with an executable cache and a request
    coalescer (see the module docstring)."""

    def __init__(
        self,
        options: Optional[ServingOptions] = None,
        *,
        cache: Optional[ExecutableCache] = None,
        max_datasets: int = 16,
    ):
        self.options = options or ServingOptions()
        # The service's compile amortization rides the process cache by
        # default so CLI/Simulator warm-up carries over; pass an explicit
        # instance to scope it (tests do). When the operator disabled the
        # process cache (DOPT_EXEC_CACHE=0) and no explicit cache was
        # given, the service honors the kill switch: it runs fully
        # uncached (``self.cache is None`` → ``executable_cache=False``
        # downstream) instead of silently substituting a private cache.
        self.cache = (
            cache if cache is not None else process_executable_cache()
        )
        self._max_datasets = max_datasets
        self._datasets: dict[tuple, tuple] = {}  # key -> (ds, f_opt)
        self._lock = threading.RLock()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # The admission-controlled queue (ISSUE-15): per-(tenant,
        # priority) sub-queues under a deficit-round-robin scheduler.
        # Pushes and cuts happen under the SERVICE lock (the WFQ's own
        # lock is a leaf) so the QUEUED-before-RUNNING lifecycle ordering
        # survives: a cut can never interleave between a push and its
        # QUEUED publish.
        self._queue = WeightedFairQueue(
            max_pending=self.options.max_pending,
            max_pending_per_tenant=self.options.max_pending_per_tenant,
            tenant_weights=self.options.tenant_weights,
        )
        # Requests cut from the queue but not yet finished — what a
        # graceful drain waits out alongside the queue itself.
        self._inflight = 0
        self._draining = False
        # Multi-worker plane (options.workers > 0): created on demand so
        # a plain in-process service never spawns anything.
        self._pool = None
        self._executor = None
        # Fleet reflexes (ISSUE-16): the remediation engine consulted at
        # admission (quarantine) and cohort completion (review), and the
        # autoscaler that registered against this service — both None on
        # a plain service, and both attach from serving/fleet.py.
        self._fleet = None
        self._autoscaler = None
        self._gauge_lock = threading.Lock()
        self._gauge_tenants: set[str] = set()
        self._requests: dict[str, Request] = {}
        # Finished-request ids in completion order — the bounded history
        # (ServingOptions.max_done) a long-lived daemon rotates through.
        self._done_order: "deque[str]" = deque()
        self._counter = 0
        # Coalescing/queue statistics (telemetry satellite). Bounded like
        # every other long-lived buffer here: stats() reports over the
        # most recent window, counters cover the lifetime.
        self.cohort_sizes: "deque[int]" = deque(maxlen=4096)
        self.queue_waits: "deque[float]" = deque(maxlen=4096)
        self.n_done = 0
        self.n_failed = 0
        self.n_sequential = 0
        self.n_cohorts = 0
        # Anomaly-sentinel firings across all served requests (ISSUE-13).
        self.n_incidents = 0
        self.data_gen_seconds = 0.0
        self.oracle_seconds = 0.0
        # Span tracing (ISSUE-10): request → cohort → compile/run spans,
        # exportable as a Chrome trace; per-request subtrees land in the
        # response manifests.
        self.tracer = Tracer()
        # Metrics registry instrumentation: the process-wide families a
        # /metrics scrape reads. Counters accumulate across service
        # instances; the queue-depth gauge polls the NEWEST service
        # (gauge_fn re-registration replaces the callback).
        reg = metrics_registry()
        self._m_requests = reg.counter(
            "dopt_serving_requests_total",
            "Serving requests by terminal status",
        )
        self._m_cohort_size = reg.histogram(
            "dopt_serving_cohort_size",
            "Coalesced cohort sizes (requests per executed plan)",
            buckets=(1, 2, 4, 8, 16, 32, 64),
        )
        self._m_queue_wait = reg.histogram(
            "dopt_serving_queue_wait_seconds",
            "Submit-to-execution-start wait per request",
        )
        reg.gauge_fn(
            "dopt_serving_queue_depth",
            "Requests pending in the serving queue",
            self.queue_depth,
        )
        # Admission metrics (ISSUE-15 satellite): the shed counter is a
        # labeled family so dashboards split rejections by cause and
        # actor; registered here so a cold daemon renders it as a valid
        # zero series before any shed happens.
        self._m_shed = reg.counter(
            "dopt_serving_shed_total",
            "Submissions refused by admission control, by reason "
            "(tenant_cap/global_cap) and tenant",
        )
        self._m_tenant_depth = reg.gauge(
            "dopt_serving_tenant_queue_depth",
            "Requests pending in the serving queue, per tenant",
        )

    # -------------------------------------------------------------- fleet
    def attach_fleet(self, engine) -> None:
        """Bind a ``RemediationEngine`` (serving/fleet.py): submissions
        check its quarantine table, live anomalies feed it, completed
        plans pass through its policy review, and a lazily-built worker
        pool inherits its death hook. Callers use ``engine.attach(
        service)``, which also wires the store listener."""
        self._fleet = engine
        pool = self._pool
        if pool is not None:
            pool.set_death_hook(engine.on_worker_death)

    # ---------------------------------------------------------- submission
    def submit(self, config, *, tenant=None, priority=None) -> str:
        """Validate and enqueue one request; returns its id.

        Raises ``ServingError`` for malformed/invalid configs (including
        malformed tenant/priority fields), ``QueueFullError`` when
        admission sheds the request (per-tenant or global cap), and
        ``DrainingError`` while a graceful drain is in progress —
        rejected requests never enter the queue.
        """
        cfg = parse_config(config)
        if cfg.replicas > 1:
            raise ServingError(REPLICAS_UNSUPPORTED_REASON)
        try:
            tenant = validate_tenant(tenant)
            priority = validate_priority(priority)
        except AdmissionError as e:
            # Re-raise as the structured 400 the daemon already maps —
            # a malformed tenant field is a bad request, not a 500.
            raise ServingError(str(e)) from e
        fleet = self._fleet
        if fleet is not None:
            # Quarantine check (ISSUE-16): a (tenant, structural class)
            # pair under an active divergence quarantine sheds with a
            # machine-readable reason before touching the queue — the
            # same 429 + Retry-After contract the caps speak.
            qreason = fleet.quarantine_reason(cfg, tenant)
            if qreason is not None:
                self._m_shed.inc(reason="quarantined", tenant=tenant)
                raise QueueFullError(
                    qreason, reason="quarantined", tenant=tenant,
                )
        shed: Optional[ShedLoad] = None
        with self._lock:
            if self._draining:
                raise DrainingError(
                    "service is draining toward shutdown; new submissions "
                    "are refused (retry against the restarted instance)"
                )
            req = Request(
                id=f"req-{self._counter + 1:06d}",
                config=cfg,
                submitted_at=time.perf_counter(),
                tenant=tenant,
                priority=priority,
            )
            try:
                self._queue.push(req, tenant=tenant, priority=priority)
            except ShedLoad as e:
                shed = e
            else:
                self._counter += 1
                # QUEUED must hit the stream BEFORE the request becomes
                # visible to a scheduler cut: published after the lock
                # released, a scheduler thread already past its wait
                # could cut the request and publish RUNNING first,
                # handing subscribers an out-of-order lifecycle. (The
                # push above IS visibility, but cuts also take this
                # lock, so no cut can interleave before the publish.)
                # The stream lock is a leaf (publish never calls back
                # into the service), so publishing under the service
                # lock cannot invert an order.
                req.progress.publish(ProgressEvent(
                    kind="lifecycle", iteration=0,
                    n_iterations=cfg.n_iterations, wall_seconds=0.0,
                    status=QUEUED,
                ))
                self._requests[req.id] = req
        if shed is not None:
            # Registry counters outside the service lock (the gauge
            # callbacks re-enter the service under the registry lock —
            # the ABBA convention every instrumented path here follows).
            self._m_shed.inc(reason=shed.reason, tenant=shed.tenant)
            raise QueueFullError(
                f"shed ({shed.reason}): {shed}; retry with backoff",
                reason=shed.reason, tenant=shed.tenant,
            ) from shed
        self._publish_tenant_depths()
        self._wake.set()
        return req.id

    def _publish_tenant_depths(self) -> None:
        """Refresh the per-tenant depth gauge family from the queue's
        current state; tenants that drained to zero keep an explicit 0
        series (a vanished series reads as 'scrape lost it', a 0 reads
        as 'empty'). Never called under the service lock."""
        depths = self._queue.depths()
        with self._gauge_lock:
            for t in self._gauge_tenants - set(depths):
                self._m_tenant_depth.set(0, tenant=t)
            for t, d in depths.items():
                self._m_tenant_depth.set(d, tenant=t)
            self._gauge_tenants |= set(depths)

    # ------------------------------------------------------------- lookup
    def get(self, request_id: str) -> Request:
        with self._lock:
            req = self._requests.get(request_id)
        if req is None:
            raise KeyError(f"unknown request id {request_id!r}")
        return req

    def result(self, request_id: str, timeout: Optional[float] = None):
        """Block until the request finishes; returns the Request record
        (status DONE or FAILED), or raises TimeoutError."""
        req = self.get(request_id)
        if not req.done.wait(timeout):
            raise TimeoutError(
                f"request {request_id} still {req.status} after {timeout}s"
            )
        return req

    # ---------------------------------------------------------- scheduling
    def queue_depth(self) -> int:
        return len(self._queue)

    def process_once(self) -> int:
        """Cut a weighted-fair batch from the queue and execute it;
        returns the number of requests resolved. The scheduler loop calls
        this after the wait window; tests call it directly for
        determinism. The cut takes everything pending unless
        ``options.cut_budget`` bounds it (then a backlogged tenant's
        excess stays queued for later rounds — the fairness lever).

        With workers configured, the cut's plans run CONCURRENTLY across
        the worker processes (one executor thread per in-flight plan);
        in-process mode executes them serially on the calling thread,
        exactly the PR-7 behavior.
        """
        with self._lock:  # cut under the service lock — see submit()
            batch = self._queue.cut(self.options.cut_budget)
            self._inflight += len(batch)
        if not batch:
            return 0
        self._publish_tenant_depths()
        plans = plan_cohorts(batch, self.options.max_cohort)
        executor = self._ensure_workers()
        if executor is not None and len(plans) > 1:
            futures = [
                executor.submit(self._execute_tracked, p) for p in plans
            ]
            for f in futures:
                f.result()
        else:
            for plan in plans:
                self._execute_tracked(plan)
        return len(batch)

    def _execute_tracked(self, plan) -> None:
        try:
            self._execute(plan)
        finally:
            with self._lock:
                self._inflight -= plan.size

    def _ensure_workers(self):
        """Spawn the worker pool + dispatch executor on first use (when
        ``options.workers`` > 0); returns the executor or None."""
        if self.options.workers <= 0:
            return None
        with self._lock:
            if self._pool is None:
                from concurrent.futures import ThreadPoolExecutor

                from distributed_optimization_tpu.serving.workers import (
                    WorkerPool,
                )

                fleet = self._fleet
                self._pool = WorkerPool(
                    self.options.workers,
                    on_worker_death=(
                        fleet.on_worker_death if fleet is not None else None
                    ),
                )
                self._pool.start()
                self._executor = ThreadPoolExecutor(
                    # Autoscaling headroom: size the dispatch width to the
                    # fleet ceiling, not the initial fleet (ISSUE-16).
                    max_workers=(
                        self.options.max_workers or self.options.workers
                    ),
                    thread_name_prefix="serving-dispatch",
                )
            return self._executor

    def drain(self) -> int:
        """Process until the queue is empty (synchronous callers/tests)."""
        total = 0
        while self.queue_depth() > 0:
            total += self.process_once()
        return total

    # ------------------------------------------------------ graceful drain
    @property
    def draining(self) -> bool:
        with self._lock:
            return self._draining

    def begin_drain(self) -> None:
        """Refuse new submissions from now on; in-flight and queued work
        keeps executing. ``/v1/shutdown?drain=1`` calls this, then
        ``wait_drained`` — requests already accepted survive the drain
        (tested with an in-flight cohort)."""
        with self._lock:
            self._draining = True
        self._wake.set()

    def wait_drained(self, timeout: float = 30.0) -> bool:
        """Block until queued + in-flight work is fully finished or
        ``timeout`` elapses; returns whether the service is empty. The
        scheduler loop (or explicit ``process_once`` calls) must be
        running for the queue to make progress."""
        deadline = time.perf_counter() + timeout
        while time.perf_counter() < deadline:
            with self._lock:
                empty = len(self._queue) == 0 and self._inflight == 0
            if empty:
                return True
            self._wake.set()
            time.sleep(0.02)
        with self._lock:
            return len(self._queue) == 0 and self._inflight == 0

    def dataset_for(self, cfg: ExperimentConfig):
        """Public dataset access sharing the service memo: the scenario
        engine's direct backend runs (final-state and checkpoint
        invariants) must consume the SAME dataset instance its served
        cells ran on, or cross-run bitwise comparisons would compare
        different problems."""
        return self._dataset_for(cfg)

    def _dataset_for(self, cfg: ExperimentConfig):
        """Dataset + reference optimum for a request, memoized on the
        fields that determine them (bounded FIFO — datasets are cheap to
        regenerate, the memo just keeps cohort cuts snappy)."""
        from distributed_optimization_tpu.utils.data import (
            generate_synthetic_dataset,
        )
        from distributed_optimization_tpu.utils.oracle import (
            compute_reference_optimum,
        )

        key = (
            cfg.problem_type, cfg.n_samples, cfg.n_features,
            cfg.n_informative_features, cfg.classification_sep,
            cfg.n_classes, cfg.partition, cfg.n_workers,
            cfg.resolved_data_seed(), cfg.reg_param, cfg.huber_delta,
        )
        with self._lock:
            hit = self._datasets.get(key)
        if hit is not None:
            return hit
        t0 = time.perf_counter()
        ds = generate_synthetic_dataset(cfg)
        t1 = time.perf_counter()
        _, f_opt = compute_reference_optimum(
            ds, cfg.reg_param, huber_delta=cfg.huber_delta,
            n_classes=cfg.n_classes,
        )
        t2 = time.perf_counter()
        with self._lock:
            self.data_gen_seconds += t1 - t0
            self.oracle_seconds += t2 - t1
            while len(self._datasets) >= self._max_datasets:
                self._datasets.pop(next(iter(self._datasets)))
            self._datasets[key] = (ds, float(f_opt))
        return ds, float(f_opt)

    def _plan_progress(self, plan):
        """Heartbeat plumbing for one executed plan (ISSUE-10): sequential
        requests get their own backend callback; a batched cohort's
        heartbeats fan out to every member with ITS replica's gap swapped
        in (the cohort-level mean stays in ``extra``).

        Anomaly sentinel (ISSUE-13): with ``options.monitors`` on, every
        request gets its own ``MonitorBank`` watching exactly the
        heartbeats its stream carries; a firing is appended to
        ``req.incidents`` (surfaced by ``/v1/status``) and published as a
        ``kind='anomaly'`` event on the stream, so a follower sees the
        diagnosis inline with the progress it rode in on."""
        banks: dict[str, Any] = {}
        if self.options.monitors:
            from distributed_optimization_tpu.observability.monitors import (
                MonitorBank,
            )

            for req in plan.requests:
                # Observation only: the serving plane records and
                # surfaces, it never halts a request mid-flight.
                banks[req.id] = MonitorBank(
                    req.config, halt_on="never", label=req.id,
                )

        def deliver(req, ev):
            req.progress.publish(ev)
            bank = banks.get(req.id)
            if bank is None:
                return
            for anomaly in bank.observe(ev):
                req.incidents.append(anomaly.to_dict())
                with self._lock:
                    self.n_incidents += 1
                fleet = self._fleet
                if fleet is not None:
                    try:
                        # Live remediation hook (ISSUE-16): e.g. a fatal
                        # divergence quarantines its structural class
                        # MID-FLIGHT, before the cohort finishes.
                        fleet.on_anomaly(req, anomaly)
                    except Exception:
                        _log.exception("fleet anomaly hook failed")
                req.progress.publish(ProgressEvent(
                    kind="anomaly",
                    iteration=int(anomaly.onset_iteration),
                    n_iterations=req.config.n_iterations,
                    wall_seconds=ev.wall_seconds,
                    status=f"anomaly:{anomaly.detector}",
                    extra={
                        "detector": anomaly.detector,
                        "severity": anomaly.severity,
                        "message": anomaly.message,
                    },
                ))

        def progress_factory(req):
            return lambda ev: deliver(req, ev)

        def cohort_cb(ev):
            per_replica = ev.gap_per_replica
            for idx, req in enumerate(plan.requests):
                if per_replica is not None and idx < len(per_replica):
                    ev_r = dataclasses.replace(
                        ev, gap=per_replica[idx], gap_per_replica=None,
                        extra={"cohort_gap_mean": ev.gap,
                               "cohort_size": plan.size},
                    )
                else:
                    ev_r = ev
                deliver(req, ev_r)

        return progress_factory, cohort_cb, banks

    def _execute(self, plan) -> None:
        t_start = time.perf_counter()
        for req in plan.requests:
            req.status = RUNNING
            req.queue_wait_s = t_start - req.submitted_at
            req.cohort_size = plan.size
            req.coalesced = plan.coalesced
            req.sequential_reason = plan.sequential_reason
            req.progress.publish(ProgressEvent(
                kind="lifecycle", iteration=0,
                n_iterations=req.config.n_iterations, wall_seconds=0.0,
                status=RUNNING,
                extra={"cohort_size": plan.size,
                       "coalesced": plan.coalesced},
            ))
        progress_factory, cohort_cb, banks = self._plan_progress(plan)
        # Per-plan span tree (request → cohort → the backend's
        # ``dopt.run.*`` spans, which an in-process plan records here
        # because the tracer is activated): embedded in each member's
        # manifest; its flat phases are folded into the service tracer's.
        plan_tracer = Tracer()
        try:
            with plan_tracer.activate(), plan_tracer.span(
                "cohort", aggregate=False, size=plan.size,
                coalesced=plan.coalesced,
                structural_hash=plan.base.structural_hash(),
            ):
                if self._pool is not None:
                    # Multi-worker plane: ship the plan to a worker
                    # process; its heartbeats route back into the same
                    # per-request streams the in-process path feeds.
                    deliverers = [
                        progress_factory(r) for r in plan.requests
                    ]

                    def on_progress(idx, ev_dict):
                        ev = ProgressEvent(**ev_dict)
                        if idx is None:
                            cohort_cb(ev)
                        else:
                            deliverers[idx](ev)

                    results, worker_id = self._pool.run_plan(
                        plan, on_progress,
                        progress_every=self.options.progress_every,
                    )
                    for req in plan.requests:
                        req.worker = worker_id
                else:
                    ds, f_opt = self._dataset_for(plan.base)
                    results = execute_plan(
                        plan, ds, f_opt,
                        # Honor the kill switch: no cache means COLD
                        # compiles, not a silently substituted private
                        # cache.
                        executable_cache=(
                            self.cache if self.cache is not None else False
                        ),
                        progress_factory=progress_factory,
                        cohort_progress_cb=cohort_cb,
                        progress_every=self.options.progress_every,
                    )
                wall = time.perf_counter() - t_start
            # The flat ``compile`` and ``run`` rows are the backend's own
            # clocks of the programs this plan ran (a coalesced plan runs
            # one, a sequential plan one a request), not the wall.
            ran = (
                results if plan.sequential_reason is not None
                else results[:1]
            )
            plan_tracer.phases.update(
                compile=sum(r.history.compile_seconds for r in ran),
                run=sum(r.history.run_seconds for r in ran),
            )
        except Exception as e:  # isolate the poison plan, keep serving
            msg = f"{type(e).__name__}: {e}"
            _log.warning("plan of %d request(s) failed: %s", plan.size, msg)
            with self._lock:
                self.n_failed += plan.size
            self._m_requests.inc(plan.size, status="failed")
            for req in plan.requests:
                req.status = FAILED
                req.error = msg
                self._finish(req)
            return
        with self._lock:
            self.n_cohorts += 1
            self.cohort_sizes.append(plan.size)
            self.queue_waits.extend(
                r.queue_wait_s for r in plan.requests
            )
            if plan.sequential_reason is not None:
                self.n_sequential += plan.size
            for name, secs in plan_tracer.phases.items():
                self.tracer.phases[name] = (
                    self.tracer.phases.get(name, 0.0) + secs
                )
        self._m_cohort_size.observe(plan.size)
        self._m_queue_wait.observe_many(
            [r.queue_wait_s for r in plan.requests]
        )
        jax_cached_path = (
            plan.base.backend == "jax" and plan.base.tp_degree == 1
            and (
                # Worker mode: each worker runs its own process cache,
                # governed by the same kill switch it inherited.
                process_cache_enabled() if self._pool is not None
                else self.cache is not None
            )
        )
        for req, res in zip(plan.requests, results):
            req.result = res
            bank = banks.get(req.id)
            if bank is not None and res.history.trace is not None:
                # Trace-derived detectors (screening saturation, the
                # non-finite state sentinel) see the flight recorder
                # buffers the request opted into.
                new = bank.scan_trace(
                    res.history.trace, res.history.eval_iterations
                )
                if new:
                    with self._lock:
                        self.n_incidents += len(new)
                req.incidents = [a.to_dict() for a in bank.anomalies]
            # Race-free per-request cache fact: the service always
            # measures compile, so zero compile seconds on a cached jax
            # path means this request's executable came from the cache —
            # no shared-counter delta that concurrent cache users could
            # skew. None when caching is off or the path has no reusable
            # jax compile (numpy/cpp/TP).
            req.cache_hit = (
                res.history.compile_seconds == 0.0
                if jax_cached_path else None
            )
            req.run_wall_s = wall
        # Fleet policy review (ISSUE-16): with an engine attached, a
        # fatal incident can override the default "everything completed
        # is DONE" — the offender fails with a policy-attributed error,
        # its innocent cohort siblings requeue for one clean re-run.
        verdicts: dict = {}
        fleet = self._fleet
        if fleet is not None:
            try:
                verdicts = fleet.review_plan(plan, banks)
            except Exception:
                _log.exception("fleet plan review failed; serving as-is")
                verdicts = {}
        n_done_now = n_failed_now = 0
        for req, res in zip(plan.requests, results):
            verdict = verdicts.get(req.id)
            if verdict is not None:
                req.remediation = verdict.get("remediation")
                if verdict["action"] == "requeue" and (
                    self._requeue_for_remediation(req)
                ):
                    continue  # back in the queue; not finished
                # "fail", or a requeue the admission layer shed:
                req.result = None
                req.status = FAILED
                req.error = verdict.get("error") or (
                    "failed by fleet remediation policy"
                )
                n_failed_now += 1
                self._finish(req)
                continue
            req.manifest = self._manifest(
                req, res, spans=plan_tracer.chrome_events(),
                bank=banks.get(req.id),
            )
            req.status = DONE
            n_done_now += 1
            self._finish(req)
        with self._lock:
            self.n_done += n_done_now
            self.n_failed += n_failed_now
        if n_done_now:
            self._m_requests.inc(n_done_now, status="done")
        if n_failed_now:
            self._m_requests.inc(n_failed_now, status="failed")

    def _requeue_for_remediation(self, req: Request) -> bool:
        """Push a cohort sibling back into the queue for a clean re-run
        (fleet policy action). Returns False when admission sheds the
        requeue — the caller then fails the request structurally instead
        of leaving it stuck."""
        shed = None
        with self._lock:
            req.requeues += 1
            req.status = QUEUED
            req.worker = None
            req.result = None
            req.cache_hit = None
            try:
                self._queue.push(
                    req, tenant=req.tenant, priority=req.priority,
                )
            except ShedLoad as e:
                shed = e
            else:
                req.progress.publish(ProgressEvent(
                    kind="lifecycle", iteration=0,
                    n_iterations=req.config.n_iterations,
                    wall_seconds=req.run_wall_s or 0.0,
                    status=QUEUED,
                    extra={"requeued_by": "fleet", "attempt":
                           req.requeues + 1},
                ))
        if shed is not None:
            self._m_shed.inc(reason=shed.reason, tenant=shed.tenant)
            return False
        self._publish_tenant_depths()
        self._wake.set()
        return True

    def _finish(self, req: Request) -> None:
        """Mark a request finished and rotate the bounded history: beyond
        ``max_done`` completed records, the oldest finished request (and
        its result payload) is dropped — later polls for its id get
        "unknown request". Pending/running requests are never evicted.
        The request's progress stream gets its terminal lifecycle event
        and closes — a ``/v1/progress`` follower unblocks here."""
        req.progress.publish(ProgressEvent(
            kind="lifecycle",
            iteration=(
                req.config.n_iterations if req.status == DONE else 0
            ),
            n_iterations=req.config.n_iterations,
            wall_seconds=req.run_wall_s or 0.0,
            status=req.status,
            extra={"error": req.error} if req.error else None,
        ))
        req.progress.close()
        req.done.set()
        with self._lock:
            self._done_order.append(req.id)
            while len(self._done_order) > self.options.max_done:
                self._requests.pop(self._done_order.popleft(), None)

    def _manifest(self, req: Request, res, spans=None, bank=None) -> dict:
        """The request's RunTrace manifest (the daemon's response body):
        config + hash, phases, trace buffers when the request asked for
        telemetry, the health block extended with the serving facts and
        any anomaly-sentinel incidents (ISSUE-13), and (schema v2) the
        plan's span tree."""
        from distributed_optimization_tpu import telemetry

        health = telemetry.health_summary(
            req.config, res.history, serving=req.serving_block(),
        )
        if bank is not None and bank.anomalies:
            health["incidents"] = bank.summary()
        return telemetry.build_run_trace(
            req.id, req.config, res.history,
            phases={
                "queue_wait": req.queue_wait_s or 0.0,
                "run": req.run_wall_s or 0.0,
            },
            health=health,
            spans=spans,
        ).to_dict()

    # ----------------------------------------------------- background loop
    def start(self) -> None:
        """Start the scheduler thread (the daemon's mode). Idempotent."""
        with self._lock:
            if self._thread is not None:
                return
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, name="simulation-service", daemon=True
            )
            self._thread.start()

    def _loop(self) -> None:
        while not self._stop.is_set():
            if not self._wake.wait(timeout=0.2):
                continue
            # The coalescing window: give concurrent submitters a beat to
            # land in the same cut before cohorts are formed.
            if self.options.window_s > 0:
                time.sleep(self.options.window_s)
            self._wake.clear()
            try:
                self.process_once()
            except Exception:  # pragma: no cover - belt and braces
                _log.exception("scheduler iteration failed; continuing")
            # A bounded cut (options.cut_budget) can leave work queued
            # with no further submit to wake us — re-arm so the backlog
            # drains round by round instead of stalling until the next
            # submission.
            if self.queue_depth() > 0:
                self._wake.set()

    def close(self) -> None:
        """Stop the scheduler loop (pending work stays queued) and tear
        down the worker plane when one was spawned."""
        autoscaler = self._autoscaler
        if autoscaler is not None:
            # The autoscaler must stop BEFORE the pool it scales dies.
            autoscaler.stop()
        self._stop.set()
        self._wake.set()
        thread = self._thread
        if thread is not None:
            thread.join(timeout=5.0)
            self._thread = None
        executor, pool = self._executor, self._pool
        self._executor = self._pool = None
        if executor is not None:
            executor.shutdown(wait=False)
        if pool is not None:
            pool.close()

    # ------------------------------------------------------------ telemetry
    def stats(self) -> dict:
        """Service-level counters: queue, cohorts, cache (JSON-safe).

        Shape contract (ISSUE-10 satellite, docs/SERVING.md): the
        ``cache`` and ``cohorts``/``queue_wait_s`` blocks are ALWAYS
        present with every counter key — zeros before any work, and the
        full counter set even when the executable cache is disabled
        (``disabled: true`` rides alongside) — so dashboards and the
        ``/metrics`` bridge never have to special-case a cold daemon.
        ``history`` documents the bounded (last-``max_done``) finished-
        request retention and lists the most recent completions.
        """
        import numpy as np

        if self.cache is not None:
            cache_stats = self.cache.stats()
        else:
            # The kill switch still answers with the full counter shape —
            # derived from the cache class itself so it cannot drift as
            # counters are added.
            from distributed_optimization_tpu.serving.cache import (
                ExecutableCache,
            )

            cache_stats = {"disabled": True, **ExecutableCache.empty_stats()}
        # Queue/pool stats outside the service lock (each has its own
        # leaf lock) — and the admission block is ALWAYS present with
        # every key, zeros cold, like the cache block.
        admission = {
            **self._queue.stats(),
            "depths": self._queue.depths(),
        }
        pool = self._pool
        workers_stats = pool.stats() if pool is not None else None
        # Fleet block (ISSUE-16): remediation-policy state + autoscaler
        # summary when attached, None on a plain service — computed
        # outside the service lock (both have their own leaf locks).
        fleet_block = None
        if self._fleet is not None or self._autoscaler is not None:
            fleet_block = {
                "remediation": (
                    self._fleet.status() if self._fleet is not None
                    else None
                ),
                "autoscaler": (
                    self._autoscaler.status()
                    if self._autoscaler is not None else None
                ),
            }
        with self._lock:
            admission["inflight"] = self._inflight
            draining = self._draining
            sizes = list(self.cohort_sizes)
            waits = list(self.queue_waits)
            recent = [
                self._requests[rid].status_dict()
                for rid in list(self._done_order)[-16:]
                if rid in self._requests
            ]
            out = {
                "queue_depth": len(self._queue),
                "draining": draining,
                "admission": admission,
                "workers": workers_stats,
                "fleet": fleet_block,
                "requests_total": self._counter,
                "requests_done": self.n_done,
                "requests_failed": self.n_failed,
                "requests_sequential_fallback": self.n_sequential,
                # Anomaly-sentinel firings over all served requests
                # (ISSUE-13); per-request details ride each request's
                # status_dict/manifest, this is the fleet-level count.
                "incidents_total": self.n_incidents,
                # count is lifetime; mean/max summarize the most recent
                # window (the deques are bounded — see __init__).
                "cohorts": {
                    "count": self.n_cohorts,
                    "mean_size": float(np.mean(sizes)) if sizes else None,
                    "max_size": int(max(sizes)) if sizes else None,
                },
                "queue_wait_s": {
                    "mean": float(np.mean(waits)) if waits else None,
                    "max": float(max(waits)) if waits else None,
                },
                "data_gen_seconds": self.data_gen_seconds,
                "oracle_seconds": self.oracle_seconds,
                "phases": {
                    k: float(v) for k, v in self.tracer.phases.items()
                },
                "cache": cache_stats,
                # Bounded per-request history: only the last ``bound``
                # finished requests are retained (older ids answer
                # "unknown request"); ``recent`` lists the newest 16.
                "history": {
                    "bound": self.options.max_done,
                    "retained": len(self._done_order),
                    "recent": recent,
                },
            }
        return out
