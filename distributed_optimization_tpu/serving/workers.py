"""Multi-worker execution plane (ISSUE-15 tentpole part c).

PR 7's daemon executes every cohort on the scheduler thread of ONE
process — correct, but the GIL plus one-compile-at-a-time means distinct
structural classes serialize behind each other even on a many-core host.
This module adds N worker **processes** (stdlib ``multiprocessing``,
spawn context) behind the service:

- the parent ships a planned cohort to a worker as plain data (config
  dicts + the plan facts); the worker rebuilds the plan with the SAME
  coalescer code path (``plan_cohorts``/``execute_plan``) the in-process
  mode uses, so multi-worker execution cannot drift semantically from
  single-process execution — tests pin served-vs-direct parity at
  ≤ 1e-12 through this plane;
- the **persistent executable store** (``serving/store.py``) is the
  shared warm state: each worker keeps its own in-memory process cache,
  and the ``DOPT_EXEC_STORE`` env var (inherited through spawn) points
  them all at one store directory, so a program compiled by any worker —
  or by a previous daemon incarnation — is a disk hit for every other;
- progress heartbeats stream back over the result queue as
  ``ProgressEvent.to_dict()`` payloads and are re-published into each
  request's live stream — ``/v1/progress`` behaves identically in both
  modes;
- a health monitor detects a died worker (crash, OOM-kill), **requeues**
  its in-flight tasks onto surviving workers with a bounded attempt
  budget (then fails them structurally — the daemon's 500, which the
  RetryingClient contract treats as a terminal answer, while the shed/
  restart paths stay retryable), respawns the worker, and counts it all
  in the ``dopt_serving_worker_*`` metric families.

Spawn (not fork): jax runtimes do not survive forking, and spawn gives
each worker a clean interpreter whose env (platform pins, store path) is
applied before jax initializes. Module-level imports here stay stdlib-
only so the spawned child can bootstrap without dragging jax in before
``_worker_main`` sets its environment.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from typing import Any, Optional

# Absolute cap on one task's wall time before the parent gives up on it.
# Generous: a cold whole-run compile is 4-6 s; minutes-long simulations
# ride serving only in benches. The health monitor usually fails tasks
# much sooner (dead-worker detection), this bounds the lost-message case.
DEFAULT_TASK_TIMEOUT_S = 900.0
# A task killed by a dying worker is retried on another worker this many
# times in total before it fails structurally.
MAX_TASK_ATTEMPTS = 2


class WorkerPlanError(RuntimeError):
    """A plan failed in (or with) its worker — carries the worker-side
    message; the service maps it to the same structured request failure
    an in-process execution error produces."""


# --------------------------------------------------------------- wire format


def _npify(obj):
    """Convert jax arrays (and any array-likes) to host numpy, leaving
    scalars/containers alone — the worker must never ship device arrays
    across the process boundary."""
    import numpy as np

    if obj is None or isinstance(obj, (bool, int, float, str, bytes)):
        return obj
    if isinstance(obj, dict):
        return {k: _npify(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        t = type(obj)
        return t(_npify(v) for v in obj)
    if isinstance(obj, np.ndarray):
        return obj
    if hasattr(obj, "__array__"):
        return np.asarray(obj)
    return obj


def encode_result(res) -> dict:
    """One ``BackendRunResult`` as a picklable payload (numpy + plain)."""
    return {
        "history": res.history,  # RunHistory is host-numpy by contract
        "final_models": _npify(res.final_models),
        "final_avg_model": _npify(res.final_avg_model),
        "final_state": _npify(res.final_state),
    }


def decode_result(payload: dict):
    from distributed_optimization_tpu.backends.base import BackendRunResult

    return BackendRunResult(
        history=payload["history"],
        final_models=payload["final_models"],
        final_avg_model=payload["final_avg_model"],
        final_state=payload["final_state"],
    )


def encode_plan(plan, *, progress_every: int) -> dict:
    """A ``CohortPlan`` as plain data the worker can rebuild exactly.

    Only the member configs travel: the worker re-derives grouping,
    sweep axes and the sequential fallback from them with the shared
    coalescer code, so there is exactly one source of plan semantics.
    """
    return {
        "configs": [r.config.to_dict() for r in plan.requests],
        "progress_every": int(progress_every),
    }


# ------------------------------------------------------------- worker child


@dataclasses.dataclass(eq=False)  # identity semantics — two requests may
class _Shim:                      # carry byte-identical configs
    """The coalescer's request duck type (it only reads ``.config``)."""

    config: Any


def _worker_run_plan(task: dict, datasets: dict, emit_progress) -> list:
    """Execute one shipped plan inside the worker; returns encoded
    results in request order."""
    from distributed_optimization_tpu.config import ExperimentConfig
    from distributed_optimization_tpu.serving.coalescer import (
        execute_plan,
        plan_cohorts,
    )
    from distributed_optimization_tpu.utils.data import (
        generate_synthetic_dataset,
    )
    from distributed_optimization_tpu.utils.oracle import (
        compute_reference_optimum,
    )

    configs = [ExperimentConfig.from_dict(d) for d in task["configs"]]
    plans = plan_cohorts(
        [_Shim(c) for c in configs], max_cohort=max(len(configs), 1)
    )
    if len(plans) != 1:  # the parent ships one plan's members — see encode
        raise WorkerPlanError(
            f"shipped cohort re-planned into {len(plans)} plans; "
            "parent/worker coalescer disagree"
        )
    plan = plans[0]
    cfg = plan.base
    key = (
        cfg.problem_type, cfg.n_samples, cfg.n_features,
        cfg.n_informative_features, cfg.classification_sep,
        cfg.n_classes, cfg.partition, cfg.n_workers,
        cfg.resolved_data_seed(), cfg.reg_param, cfg.huber_delta,
    )
    hit = datasets.get(key)
    if hit is None:
        ds = generate_synthetic_dataset(cfg)
        _, f_opt = compute_reference_optimum(
            ds, cfg.reg_param, huber_delta=cfg.huber_delta,
            n_classes=cfg.n_classes,
        )
        hit = (ds, float(f_opt))
        if len(datasets) >= 16:  # same bound the service memo uses
            datasets.pop(next(iter(datasets)))
        datasets[key] = hit
    ds, f_opt = hit

    idx_of = {id(s): i for i, s in enumerate(plan.requests)}

    def progress_factory(shim):
        idx = idx_of[id(shim)]
        return lambda ev: emit_progress(idx, ev.to_dict())

    def cohort_cb(ev):
        emit_progress(None, ev.to_dict())

    results = execute_plan(
        plan, ds, f_opt,
        executable_cache=None,  # the worker's process cache (+ env store)
        progress_factory=progress_factory,
        cohort_progress_cb=cohort_cb,
        progress_every=task["progress_every"],
    )
    return [encode_result(r) for r in results]


def _worker_main(worker_id: int, task_q, result_q, env: dict) -> None:
    """Worker process entry point. Applies env overrides BEFORE any jax
    import (platform pins and the store path must precede backend init),
    then serves tasks until the ``None`` sentinel."""
    os.environ.update(env)
    from distributed_optimization_tpu.runtime import configure_compile_cache

    configure_compile_cache()
    result_q.put(("ready", worker_id, os.getpid()))
    datasets: dict = {}
    while True:
        task = task_q.get()
        if task is None:
            break
        if task.get("__retire__"):
            # Autoscaler scale-down (ISSUE-16): the retire sentinel is
            # only ever picked up BETWEEN tasks, so a retiring worker has
            # by construction finished its in-flight cohort — the drain
            # contract, *per worker*. Exactly one worker consumes each
            # sentinel; it announces and exits.
            result_q.put(("retired", worker_id))
            break
        task_id = task["task_id"]
        result_q.put(("start", task_id, worker_id))

        def emit(idx, ev_dict, _tid=task_id):
            result_q.put(("progress", _tid, idx, ev_dict))

        try:
            encoded = _worker_run_plan(task, datasets, emit)
        except BaseException as e:  # ship the failure, stay alive
            result_q.put((
                "error", task_id, worker_id,
                f"{type(e).__name__}: {e}",
            ))
        else:
            result_q.put(("done", task_id, worker_id, encoded))


# ------------------------------------------------------------- parent pool


@dataclasses.dataclass
class _Task:
    """Parent-side record of one in-flight plan."""

    task_id: int
    payload: dict
    progress_handler: Any  # callable(idx_or_None, ev_dict)
    done: threading.Event = dataclasses.field(
        default_factory=threading.Event
    )
    results: Optional[list] = None
    error: Optional[str] = None
    worker_id: Optional[int] = None
    attempts: int = 1


class WorkerPool:
    """N spawn-context worker processes + router/health threads.

    ``run_plan`` is thread-safe and blocking — the service calls it from
    its per-plan executor threads, so N plans execute truly concurrently
    across N processes while the parent keeps the bookkeeping.
    """

    def __init__(
        self,
        n_workers: int,
        *,
        env: Optional[dict] = None,
        max_task_attempts: int = MAX_TASK_ATTEMPTS,
        on_worker_death=None,
    ):
        if n_workers < 1:
            raise ValueError(f"n_workers must be >= 1, got {n_workers}")
        self.n_workers = n_workers  # current TARGET size (scale ops move it)
        self.env = dict(env or {})
        self.max_task_attempts = max_task_attempts
        # Fleet hook (ISSUE-16): called as fn(worker_id, requeued, lost)
        # when a worker dies unexpectedly; returns whether to respawn.
        # None keeps the PR-15 behavior: always respawn.
        self._on_death = on_worker_death
        self._ctx = None
        self._task_q = None
        self._result_q = None
        self._procs: dict[int, Any] = {}
        self._tasks: dict[int, _Task] = {}
        self._lock = threading.Lock()
        self._counter = 0
        self._next_wid = n_workers  # fresh ids for scale-up spawns
        self._pending_retires = 0
        self._stop = threading.Event()
        self._router: Optional[threading.Thread] = None
        self._monitor: Optional[threading.Thread] = None
        self.n_restarts = 0
        self.n_requeues = 0
        self.n_retired = 0
        from distributed_optimization_tpu.observability.metrics_registry import (  # noqa: E501
            metrics_registry,
        )

        reg = metrics_registry()
        self._m_tasks = reg.counter(
            "dopt_serving_worker_tasks_total",
            "Plans executed by the worker plane, by worker and result "
            "(done/error/requeued/lost)",
        )
        self._m_restarts = reg.counter(
            "dopt_serving_worker_restarts_total",
            "Worker processes respawned after dying with tasks in flight",
        )
        reg.gauge_fn(
            "dopt_serving_workers_alive",
            "Live worker processes in the execution plane",
            self.alive_count,
        )

    # -------------------------------------------------------------- lifecycle
    def start(self) -> None:
        import multiprocessing as mp

        if self._router is not None:
            return
        self._ctx = mp.get_context("spawn")
        self._task_q = self._ctx.Queue()
        self._result_q = self._ctx.Queue()
        for wid in range(self.n_workers):
            self._spawn(wid)
        self._stop.clear()
        self._router = threading.Thread(
            target=self._route, name="worker-pool-router", daemon=True
        )
        self._router.start()
        self._monitor = threading.Thread(
            target=self._watch, name="worker-pool-health", daemon=True
        )
        self._monitor.start()

    def _spawn(self, worker_id: int) -> None:
        proc = self._ctx.Process(
            target=_worker_main,
            args=(worker_id, self._task_q, self._result_q, self.env),
            name=f"serving-worker-{worker_id}",
            daemon=True,
        )
        proc.start()
        self._procs[worker_id] = proc

    def close(self) -> None:
        self._stop.set()
        if self._task_q is not None:
            for _ in self._procs:
                try:
                    self._task_q.put(None)
                except Exception:
                    pass
        for proc in self._procs.values():
            proc.join(timeout=5.0)
            if proc.is_alive():
                proc.terminate()
                proc.join(timeout=2.0)
        for t in (self._router, self._monitor):
            if t is not None:
                t.join(timeout=2.0)
        self._router = self._monitor = None
        self._procs.clear()

    def alive_count(self) -> int:
        return sum(1 for p in self._procs.values() if p.is_alive())

    def worker_ids(self) -> list[int]:
        """Ids of the workers currently in the fleet (retired ones are
        gone) — the autoscaler's per-worker gauge label universe."""
        with self._lock:
            return sorted(self._procs)

    # --------------------------------------------------------------- scaling
    def scale_up(self, k: int = 1) -> list[int]:
        """Spawn ``k`` additional workers (fresh ids, never reusing a
        retired id — label series stay unambiguous); returns the new ids.
        Requires a started pool."""
        if self._router is None:
            raise RuntimeError("scale_up on a pool that was never started")
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        new_ids = []
        with self._lock:
            for _ in range(k):
                wid = self._next_wid
                self._next_wid += 1
                new_ids.append(wid)
            self.n_workers += k
        for wid in new_ids:
            self._spawn(wid)
        return new_ids

    def scale_down(self, k: int = 1) -> None:
        """Retire ``k`` workers gracefully: a retire sentinel is posted
        on the shared task queue per retirement, and whichever worker
        picks one up finishes its in-flight cohort first (the sentinel is
        only read between tasks), announces, and exits. Never drops the
        target below 1 — a zero-worker pool cannot serve."""
        if self._router is None:
            raise RuntimeError("scale_down on a pool that was never started")
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        with self._lock:
            if self.n_workers - k < 1:
                raise ValueError(
                    f"scale_down({k}) would leave {self.n_workers - k} "
                    "workers; the pool floor is 1"
                )
            self.n_workers -= k
            self._pending_retires += k
        for _ in range(k):
            self._task_q.put({"__retire__": True})

    def _finish_retirement(self, worker_id: int) -> None:
        """Idempotent bookkeeping for a retired worker — reached from the
        router (the announced path) or the health monitor (announcement
        lost); whichever pops the proc record wins."""
        with self._lock:
            proc = self._procs.pop(worker_id, None)
            if proc is None:
                return
            self._pending_retires = max(0, self._pending_retires - 1)
            self.n_retired += 1
        proc.join(timeout=5.0)
        if proc.is_alive():
            proc.terminate()

    # ------------------------------------------------------------ dispatching
    def run_plan(
        self, plan, progress_handler, *, progress_every: int = 1,
        timeout: float = DEFAULT_TASK_TIMEOUT_S,
    ):
        """Execute one plan on some worker; returns (results, worker_id).

        Blocks until the task finishes, is requeued-to-death, or times
        out; raises ``WorkerPlanError`` on failure. ``progress_handler``
        receives ``(replica_idx_or_None, event_dict)`` live.
        """
        with self._lock:
            self._counter += 1
            task = _Task(
                task_id=self._counter,
                payload={
                    "task_id": self._counter,
                    **encode_plan(plan, progress_every=progress_every),
                },
                progress_handler=progress_handler,
            )
            self._tasks[task.task_id] = task
        self._task_q.put(task.payload)
        try:
            if not task.done.wait(timeout):
                raise WorkerPlanError(
                    f"worker task {task.task_id} timed out after {timeout}s"
                )
        finally:
            with self._lock:
                self._tasks.pop(task.task_id, None)
        if task.error is not None:
            raise WorkerPlanError(task.error)
        return [decode_result(p) for p in task.results], task.worker_id

    # ---------------------------------------------------------------- router
    def _route(self) -> None:
        import queue as queue_mod

        while not self._stop.is_set():
            try:
                msg = self._result_q.get(timeout=0.2)
            except (queue_mod.Empty, OSError, EOFError):
                continue
            kind = msg[0]
            if kind == "ready":
                continue
            if kind == "retired":
                self._finish_retirement(msg[1])
                continue
            if kind == "start":
                _, task_id, worker_id = msg
                with self._lock:
                    task = self._tasks.get(task_id)
                    if task is not None:
                        task.worker_id = worker_id
                continue
            if kind == "progress":
                _, task_id, idx, ev_dict = msg
                with self._lock:
                    task = self._tasks.get(task_id)
                if task is not None:
                    try:
                        task.progress_handler(idx, ev_dict)
                    except Exception:
                        pass  # a progress consumer must never kill routing
                continue
            if kind in ("done", "error"):
                _, task_id, worker_id, payload = msg
                with self._lock:
                    task = self._tasks.get(task_id)
                if task is None:
                    continue
                task.worker_id = worker_id
                if kind == "done":
                    task.results = payload
                else:
                    task.error = str(payload)
                self._m_tasks.inc(
                    worker=str(worker_id),
                    result="done" if kind == "done" else "error",
                )
                task.done.set()

    # ---------------------------------------------------------------- health
    def _watch(self) -> None:
        """Detect died workers: requeue their in-flight tasks (bounded
        attempts), respawn the process, count everything."""
        while not self._stop.is_set():
            time.sleep(0.3)
            for wid, proc in list(self._procs.items()):
                if proc.is_alive() or self._stop.is_set():
                    continue
                # Tasks assigned to the dead worker and not finished:
                with self._lock:
                    orphans = [
                        t for t in self._tasks.values()
                        if t.worker_id == wid and not t.done.is_set()
                    ]
                    pending_retire = self._pending_retires > 0
                if not orphans and pending_retire:
                    # A clean exit with retirements outstanding is almost
                    # certainly a retiring worker whose announcement the
                    # router has not drained yet — fold it into the
                    # retirement path (idempotent) instead of respawning
                    # a worker the autoscaler just asked to go away.
                    self._finish_retirement(wid)
                    continue
                n_requeued = n_lost = 0
                for task in orphans:
                    if task.attempts >= self.max_task_attempts:
                        task.error = (
                            f"worker {wid} died executing task "
                            f"{task.task_id} (attempt {task.attempts}/"
                            f"{self.max_task_attempts}); giving up"
                        )
                        self._m_tasks.inc(worker=str(wid), result="lost")
                        n_lost += 1
                        task.done.set()
                    else:
                        task.attempts += 1
                        task.worker_id = None
                        self.n_requeues += 1
                        n_requeued += 1
                        self._m_tasks.inc(
                            worker=str(wid), result="requeued")
                        self._task_q.put(task.payload)
                respawn = True
                if self._on_death is not None:
                    try:
                        respawn = bool(self._on_death(wid, n_requeued,
                                                      n_lost))
                    except Exception:
                        respawn = True  # a broken policy must not strand
                if respawn:
                    self.n_restarts += 1
                    self._m_restarts.inc(worker=str(wid))
                    self._spawn(wid)
                else:
                    # Policy vetoed the respawn (dead_worker rule
                    # disabled): drop the record so the monitor does not
                    # re-detect the same corpse every poll, and shrink
                    # the target to match reality.
                    with self._lock:
                        self._procs.pop(wid, None)
                        self.n_workers = max(1, self.n_workers - 1)

    # ------------------------------------------------------------- telemetry
    def stats(self) -> dict:
        with self._lock:
            in_flight = len(self._tasks)
        return {
            "workers": self.n_workers,
            "alive": self.alive_count(),
            "in_flight": in_flight,
            "restarts": int(self.n_restarts),
            "requeues": int(self.n_requeues),
            "retired": int(self.n_retired),
        }

    def set_death_hook(self, fn) -> None:
        """(Re)attach the dead-worker policy hook after construction —
        how a fleet engine binds to a pool the service built lazily."""
        self._on_death = fn
