"""Experiment orchestration: data → oracle → run matrix → report/plots.

Capability parity with the reference's ``Simulator`` (reference
``simulator.py:12-201``): generate the dataset once, compute the sklearn
reference optimum, run the experiment matrix (centralized SGD + D-SGD over
ring / toroidal grid / fully-connected, the grid skipped with an N/A record
when N is not a perfect square — reference ``simulator.py:113-125``), record
numerical results after each run, and emit the text report and the 2-panel
log-scale figure.

Differences by design (TPU-first):

- trainers are replaced by pure-step-rule algorithms dispatched through the
  backend layer (``backends.run_algorithm``), so the same matrix runs on the
  JAX/TPU path or the numpy fidelity oracle via ``config.backend``;
- the run matrix is open: any (algorithm, topology) pair the framework
  implements can be added via ``run_one`` / ``run_suite``, not just the
  reference's four rows;
- workers are not stateful objects, so there is no ``_reset_workers`` trap
  (reference ``simulator.py:29-30``) — every run starts from fresh zero
  models by construction;
- plots are saved to a file (headless TPU hosts) instead of ``plt.show()``.
"""

from __future__ import annotations

import dataclasses
import math
from pathlib import Path
from typing import Optional

import numpy as np

from distributed_optimization_tpu.backends.base import (
    BackendRunResult,
    run_algorithm,
    run_algorithm_batch,
)
from distributed_optimization_tpu.config import ExperimentConfig
from distributed_optimization_tpu.log import get_logger
from distributed_optimization_tpu.metrics import (
    NumericalResult,
    ReplicateStats,
    summarize_replicates,
    summarize_run,
)
from distributed_optimization_tpu.utils.data import (
    HostDataset,
    generate_synthetic_dataset,
)
from distributed_optimization_tpu.utils.oracle import compute_reference_optimum
from distributed_optimization_tpu.utils.profiling import PhaseTimer

_log = get_logger("simulator")

# The reference's experiment matrix (simulator.py:99-132): algorithm,
# topology (None = centralized), display label.
REFERENCE_MATRIX = (
    ("centralized", None, "Centralized SGD"),
    ("dsgd", "ring", "D-SGD (ring)"),
    ("dsgd", "grid", "D-SGD (grid)"),
    ("dsgd", "fully_connected", "D-SGD (fully connected)"),
)


@dataclasses.dataclass
class ExperimentRecord:
    """One completed (or skipped) run of the matrix.

    Replica-batched runs (``config.replicas > 1``) additionally carry the
    full ``BatchRunResult`` and the seed-variance ``ReplicateStats``;
    ``result``/``summary`` then hold replica 0's trajectory as the
    representative curve (plots need ONE line per row), while the report
    and JSON layers quote the mean ± std columns from ``replicate_stats``.
    """

    label: str
    config: Optional[ExperimentConfig]  # None for skipped rows
    result: Optional[BackendRunResult]
    summary: Optional[NumericalResult]
    skipped_reason: Optional[str] = None
    batch: Optional[object] = None  # jax_backend.BatchRunResult
    replicate_stats: Optional[ReplicateStats] = None
    # Derived run-health block (telemetry.health_summary) — populated when
    # the run recorded flight-recorder trace buffers (config.telemetry);
    # read by format_report's run-health section and the RunTrace manifest.
    health: Optional[dict] = None
    # The run's anomaly MonitorBank (ISSUE-13) when one watched it —
    # carries the fired anomalies/halt facts; ``write_incidents`` drains
    # the forensic bundles.
    monitors: Optional[object] = None


class Simulator:
    """Runs experiments against one shared dataset + reference optimum.

    ``base_config`` fixes the problem, data, and solver hyperparameters;
    per-run calls may override algorithm/topology/backend. Data and f(x*)
    are computed once so every run is measured against the same ground truth
    (reference ``simulator.py:15-18``).
    """

    def __init__(
        self, base_config: ExperimentConfig, dataset: Optional[HostDataset] = None
    ):
        self.config = base_config
        # Phase accounting (ISSUE-5 satellite), now the hierarchical span
        # tracer (ISSUE-10: ``observability/spans.Tracer``; ``PhaseTimer``
        # is an alias): data-gen, oracle, compile, and run wall-clock
        # collected across the simulator's lifetime — surfaced in the text
        # report, the JSON dump, the RunTrace manifests, and exportable as
        # a Chrome trace (``write_chrome_trace``).
        self.phase_timer = PhaseTimer()
        with self.phase_timer.phase("data_gen"):
            self.dataset = (
                dataset if dataset is not None
                else generate_synthetic_dataset(base_config)
            )
        with self.phase_timer.phase("oracle"):
            self.w_opt, self.f_opt = compute_reference_optimum(
                self.dataset, base_config.reg_param,
                huber_delta=base_config.huber_delta,
                n_classes=base_config.n_classes,
            )
        from distributed_optimization_tpu.observability.metrics_registry import (
            observe_phases,
        )

        observe_phases({
            "data_gen": self.phase_timer.phases.get("data_gen", 0.0),
            "oracle": self.phase_timer.phases.get("oracle", 0.0),
        })
        self.records: list[ExperimentRecord] = []

    # ------------------------------------------------------------------ runs
    def run_one(
        self,
        label: Optional[str] = None,
        *,
        verbose: bool = True,
        run_kwargs: Optional[dict] = None,
        **overrides,
    ) -> ExperimentRecord:
        """Run one experiment; ``overrides`` replace base-config fields.

        ``run_kwargs`` pass through to the backend (mesh=..., checkpoint=...).
        """
        cfg = self.config.replace(**overrides) if overrides else self.config
        if label is None:
            label = (
                "Centralized SGD"
                if cfg.algorithm == "centralized"
                else f"{cfg.algorithm} ({cfg.topology})"
            )
        kwargs = dict(run_kwargs or {})
        # Anomaly monitors (ISSUE-13): a MonitorBank is per-run state
        # (latched detectors), so suite/matrix callers pass a FACTORY
        # (config -> bank) and each run gets a fresh one; a bank instance
        # passes through untouched for single runs.
        monitors = kwargs.get("monitors")
        if monitors is not None and not hasattr(monitors, "observe"):
            monitors = kwargs["monitors"] = monitors(cfg)
        replicated = cfg.replicas > 1 or "seeds" in kwargs or "sweep" in kwargs
        if verbose:
            rep = (
                f", replicas={len(kwargs['seeds']) if 'seeds' in kwargs else cfg.replicas}"
                if replicated else ""
            )
            _log.info(
                "running %r (algorithm=%s, topology=%s, backend=%s, T=%s%s)",
                label, cfg.algorithm, cfg.topology, cfg.backend,
                cfg.n_iterations, rep,
            )
        batch = None
        stats = None
        # The labeled span groups this run in the Chrome trace. Activated,
        # the tracer is where the backend records its own ``dopt.run.*``
        # spans, under this span and outside the flat table.
        with self.phase_timer.activate(), self.phase_timer.span(
            f"run_one:{label}", aggregate=False
        ):
            if replicated:
                # One vmapped program runs every replica (ISSUE-4): the
                # record keeps replica 0 as the representative trajectory
                # and the mean ± std statistics alongside.
                batch = run_algorithm_batch(
                    cfg, self.dataset, self.f_opt, **kwargs
                )
                result = batch.results[0]
                stats = summarize_replicates(
                    batch.objective,
                    batch.consensus_error,
                    result.history.eval_iterations,
                    cfg.suboptimality_threshold,
                    batch.seeds,
                    batch.aggregate_iters_per_second,
                )
            else:
                result = run_algorithm(cfg, self.dataset, self.f_opt, **kwargs)
        # The flat table's ``compile`` and ``run`` rows are the backend's
        # own two clocks (on the sequential jax path the ``dopt.run.compile``
        # and ``dopt.run.scan`` spans' intervals), not the wall around the
        # call: stacking, upload and harvest are spans of the tree only.
        phases = {
            "compile": result.history.compile_seconds,
            "run": result.history.run_seconds,
        }
        for name, seconds in phases.items():
            self.phase_timer.phases[name] = (
                self.phase_timer.phases.get(name, 0.0) + seconds
            )
        from distributed_optimization_tpu.observability.metrics_registry import (
            observe_phases,
        )

        observe_phases(phases)
        summary = summarize_run(
            label,
            result.history,
            cfg.suboptimality_threshold,
            cfg.n_workers,
            spectral_gap=result.history.spectral_gap,
        )
        health = None
        if (
            cfg.telemetry or cfg.execution == "async"
            or cfg.worker_mesh >= 2
            or (monitors is not None and monitors.anomalies)
        ):
            # Async health (staleness histogram, virtual-clock skew,
            # floats per virtual second, the event-fault block under
            # churn/thinning) derives from the presampled event timeline
            # — always available even without the opt-in in-scan trace,
            # so always surfaced (docs/ASYNC.md).
            # Sharded worker-mesh runs likewise: the bytes-over-ICI block
            # derives from the static halo plan (docs/PERF.md §16).
            from distributed_optimization_tpu.telemetry import health_summary

            health = health_summary(
                cfg, result.history, d_features=self.dataset.n_features
            )
        if monitors is not None and monitors.anomalies:
            # The sentinel's verdict rides the health block (the report
            # prints it; the RunTrace manifest records it).
            health["incidents"] = monitors.summary()
            for a in monitors.anomalies:
                _log.warning(
                    "%r: anomaly %s (%s) at iteration %d: %s",
                    label, a.detector, a.severity, a.onset_iteration,
                    a.message,
                )
            if monitors.halted_at is not None:
                _log.warning(
                    "%r: run HALTED at iteration %d of %d "
                    "(halt_on=fatal) — histories cover the executed "
                    "prefix only", label, monitors.halted_at,
                    cfg.n_iterations,
                )
        record = ExperimentRecord(
            label, cfg, result, summary, batch=batch, replicate_stats=stats,
            health=health, monitors=monitors,
        )
        self.records.append(record)
        if verbose:
            if stats is not None:
                _log.info(
                    "%r: final gap %.5f ± %.5f over %d replicas, "
                    "%.1f aggregate iters/sec",
                    label, stats.final_gap_mean, stats.final_gap_std,
                    stats.n_replicas, stats.aggregate_iters_per_second,
                )
            else:
                _log.info(
                    "%r: final gap %.5f, iters-to-threshold %s, "
                    "%.1f iters/sec",
                    label, result.history.objective[-1],
                    summary.iterations_to_threshold,
                    result.history.iters_per_second,
                )
        return record

    def skip(self, label: str, reason: str) -> ExperimentRecord:
        record = ExperimentRecord(label, None, None, None, skipped_reason=reason)
        self.records.append(record)
        return record

    def run_all(
        self, *, verbose: bool = True, run_kwargs: Optional[dict] = None
    ) -> list[ExperimentRecord]:
        """Run the reference's four-row experiment matrix.

        Grid is skipped with an N/A record when N is not a perfect square
        (reference ``simulator.py:113-125``).
        """
        n = self.config.n_workers
        side = math.isqrt(n)
        for algorithm, topology, label in REFERENCE_MATRIX:
            if topology == "grid" and side * side != n:
                self.skip(label, f"N={n} is not a perfect square")
                continue
            overrides = {"algorithm": algorithm}
            if topology is not None:
                overrides["topology"] = topology
            self.run_one(
                label, verbose=verbose, run_kwargs=run_kwargs, **overrides
            )
        return self.records

    def run_suite(
        self,
        specs: list[tuple[str, Optional[str]]],
        *,
        verbose: bool = True,
        run_kwargs: Optional[dict] = None,
    ) -> list[ExperimentRecord]:
        """Run an arbitrary list of (algorithm, topology-or-None) pairs."""
        for algorithm, topology in specs:
            overrides = {"algorithm": algorithm}
            if topology is not None:
                overrides["topology"] = topology
            self.run_one(verbose=verbose, run_kwargs=run_kwargs, **overrides)
        return self.records

    # -------------------------------------------------------------- reporting
    def report_numerical_results(self) -> str:
        """Text report (reference ``simulator.py:139-159``); also returned.

        The report itself is the product (stdout), not a diagnostic —
        it stays a print, unlike the progress logging above.
        """
        from distributed_optimization_tpu.reporting import format_report
        from distributed_optimization_tpu.serving.cache import (
            process_executable_cache,
        )

        # One-line serving summary (docs/SERVING.md): the process-wide
        # executable cache amortizes AOT compiles across run_one calls in
        # this process; surfaced once it has actually saved a compile.
        cache = process_executable_cache()
        serving = (
            cache.stats() if cache is not None and cache.hits > 0 else None
        )
        text = format_report(
            self.records, self.config, self.f_opt,
            phases=dict(self.phase_timer.phases),
            serving=serving,
            device=self._jax_device(),
        )
        print(text)
        return text

    def _jax_device(self) -> Optional[dict]:
        """The process's jax device if any recorded run used the jax
        backend (numpy/cpp-only tables never touch jax), else None."""
        if not any(
            rec.config is not None and rec.config.backend == "jax"
            for rec in self.records
        ):
            return None
        from distributed_optimization_tpu.runtime import device_summary

        return device_summary()

    # ------------------------------------------------------------- telemetry
    def run_traces(self) -> list:
        """One ``telemetry.RunTrace`` manifest per completed record —
        config + hash, phase timings, cost analysis, trace buffers, and the
        derived health block (skipped rows emit nothing)."""
        from distributed_optimization_tpu.telemetry import build_run_trace

        traces = []
        for rec in self.records:
            if rec.skipped_reason is not None or rec.result is None:
                continue
            traces.append(build_run_trace(
                rec.label, rec.config, rec.result.history,
                # The Tracer carries both the flat phase dict and the
                # span tree; build_run_trace records both (schema v2).
                phases=self.phase_timer,
                health=rec.health,
            ))
        return traces

    def write_telemetry(self, path) -> None:
        """Serialize the run manifests as JSONL (one manifest per line)."""
        from distributed_optimization_tpu.telemetry import write_jsonl

        write_jsonl(path, self.run_traces())
        _log.info("telemetry manifests saved to %s", path)

    def write_incidents(self, path) -> Path:
        """Serialize every monitored record's anomaly bundles as incident
        JSONL (ISSUE-13; ``observability/monitors.py``) — the file
        ``observatory incidents`` indexes. Returns the path; writes an
        empty file when nothing fired (an empty incident log is a
        statement, not an omission)."""
        from distributed_optimization_tpu.observability.monitors import (
            write_incidents,
        )

        bundles = []
        for rec in self.records:
            bank = rec.monitors
            if bank is None or not bank.anomalies:
                continue
            bundles.extend(bank.incidents(label=rec.label))
        out = write_incidents(path, bundles)
        _log.info(
            "%d incident bundle(s) saved to %s", len(bundles), out
        )
        return out

    def write_chrome_trace(self, path) -> None:
        """Export the simulator's span tree (data_gen/oracle + per-run
        compile/run spans) as Chrome trace-event JSON — open in
        chrome://tracing or https://ui.perfetto.dev (ISSUE-10)."""
        self.phase_timer.write_chrome_trace(path)
        _log.info("chrome trace saved to %s", path)

    def metrics_text(self) -> str:
        """The process metrics registry in Prometheus text format — the
        same exposition the serving daemon's ``/metrics`` endpoint
        scrapes, dumpable from scripts and the CLI (ISSUE-10)."""
        from distributed_optimization_tpu.observability.metrics_registry import (
            metrics_registry,
        )

        return metrics_registry().render()

    def plot_results(self, path: Optional[str] = None, show: bool = False):
        """Two-panel log-scale figure (reference ``simulator.py:161-201``)."""
        from distributed_optimization_tpu.reporting import plot_histories

        return plot_histories(
            self.records,
            self.config,
            path=path,
            show=show,
        )

    def results_dict(self) -> dict:
        """JSON-serializable summary of all runs (new capability)."""
        out = {
            "config": self.config.to_dict(),
            "f_opt": float(self.f_opt),
            "phases": {
                k: float(v) for k, v in self.phase_timer.phases.items()
            },
            "device": self._jax_device(),
            "runs": [],
        }
        for rec in self.records:
            row: dict = {"label": rec.label}
            if rec.skipped_reason is not None:
                row["skipped"] = rec.skipped_reason
            else:
                assert rec.summary is not None and rec.result is not None
                secs = rec.summary.seconds_to_threshold
                row.update(
                    iterations_to_threshold=rec.summary.iterations_to_threshold,
                    # None (not NaN) when never reached: strict-JSON friendly.
                    seconds_to_threshold=None if np.isnan(secs) else secs,
                    total_transmission_floats=rec.summary.total_transmission_floats,
                    avg_worker_transmission_floats=(
                        rec.summary.avg_worker_transmission_floats
                    ),
                    spectral_gap=rec.summary.spectral_gap,
                    iters_per_second=rec.summary.iters_per_second,
                    mesh_devices=rec.result.history.mesh_devices,
                    final_objective_gap=float(rec.result.history.objective[-1]),
                    history=rec.result.history.as_dict(),
                )
                if rec.health is not None:
                    row["health"] = rec.health
                if rec.replicate_stats is not None:
                    s = rec.replicate_stats
                    it_mean = s.iterations_to_threshold_mean
                    it_std = s.iterations_to_threshold_std
                    row["replicates"] = {
                        "n": s.n_replicas,
                        "seeds": s.seeds,
                        "final_gap_mean": s.final_gap_mean,
                        "final_gap_std": s.final_gap_std,
                        "consensus_mean": s.consensus_mean,
                        "consensus_std": s.consensus_std,
                        # None (not NaN) when no replica reached ε.
                        "iterations_to_threshold_mean": (
                            None if np.isnan(it_mean) else it_mean
                        ),
                        "iterations_to_threshold_std": (
                            None if np.isnan(it_std) else it_std
                        ),
                        "n_reached": s.n_reached,
                        "per_replica_iterations": s.per_replica_iterations,
                        "aggregate_iters_per_second": (
                            s.aggregate_iters_per_second
                        ),
                        "objective_mean": np.mean(
                            rec.batch.objective, axis=0
                        ).tolist(),
                        "objective_std": np.std(
                            rec.batch.objective, axis=0
                        ).tolist(),
                    }
            out["runs"].append(row)
        return out
