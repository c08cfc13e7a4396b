"""Metrics: suboptimality, consensus error, comms cost, iterations-to-threshold.

These four metrics ARE the product of the reference study (SURVEY.md §5.5) and
are reproduced bit-comparably in definition:

- suboptimality gap  f(x̄_t) − f(x*)  on the FULL dataset every recorded
  iteration (reference ``trainer.py:66-69,188-191``);
- consensus error  (1/N) Σ_i ‖x_i − x̄‖²  (reference ``trainer.py:184-186``);
- total floats transmitted — an *analytic* cost model, kept even though the
  TPU backend performs real collectives, so numbers stay comparable with the
  reference's Tables I/II (closed forms below);
- iterations to reach a suboptimality threshold (reference
  ``simulator.py:73-79``).

On the TPU path the per-iteration values accumulate on device inside the
``lax.scan`` carry/ys and are fetched once per run — no per-iteration host
syncs (the reference pays a full-dataset numpy objective evaluation on the
host every iteration, ``trainer.py:67``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from distributed_optimization_tpu.parallel.topology import Topology



@dataclasses.dataclass
class RunHistory:
    """Per-iteration history of one training run (host numpy arrays)."""

    objective: np.ndarray  # suboptimality gap f(x̄_t) − f(x*), [T_recorded]
    consensus_error: Optional[np.ndarray]  # [T_recorded] or None (centralized)
    time: np.ndarray  # wall-clock seconds since run start, [T_recorded]
    eval_iterations: np.ndarray  # iteration numbers (1-based) the rows refer to
    total_floats_transmitted: float
    iters_per_second: float = float("nan")
    compile_seconds: float = 0.0  # AOT compile time (jax backend; 0 for numpy)
    spectral_gap: Optional[float] = None  # 1 − ρ of the run's mixing matrix
    # True when ``time`` holds real per-eval perf_counter samples (the
    # reference's trainer.py:63,181 measurement); False when it is a linspace
    # interpolation of the total run wall-clock (fully fused scan) — the
    # report marks derived sec→ε values accordingly.
    time_measured: bool = False
    # Flight-recorder buffers (config.telemetry; telemetry.TRACE_FIELDS):
    # dict of per-eval-row health series — [n_evals] scalars and
    # [n_evals, N] per-worker rows, float32 — or None when telemetry is off
    # or the backend records none (cpp).
    trace: Optional[dict] = None
    # XLA cost analysis of the compiled program (telemetry.cost_from_lowered:
    # flops, bytes_accessed, ...); None off the jax path or when telemetry
    # is off.
    cost: Optional[dict] = None
    # How many devices each held their own block of the model stack's
    # worker rows, read off the final state's sharding (1 = unsharded or
    # replicated): a one-chip number and a four-chip number must never be
    # confused, and nothing in the config says what the auto-mesh chose.
    mesh_devices: int = 1

    @property
    def run_seconds(self) -> float:
        """The backend's own clock around the run, the last entry of
        ``time`` (on the sequential jax path the ``dopt.run.scan`` span's
        interval): compile, stacking, upload and harvest are not in it."""
        return float(self.time[-1]) if len(self.time) else 0.0

    def as_dict(self) -> dict:
        out = {
            "objective": self.objective.tolist(),
            "time": self.time.tolist(),
            "time_measured": self.time_measured,
        }
        if self.consensus_error is not None:
            out["consensus_error"] = self.consensus_error.tolist()
        return out


def consensus_error(models: np.ndarray) -> float:
    """(1/N) Σ_i ‖x_i − x̄‖² for an [N, d] model stack."""
    mean = models.mean(axis=0)
    return float(np.mean(np.sum((models - mean) ** 2, axis=1)))


def honest_mean(models: np.ndarray, byzantine: np.ndarray) -> np.ndarray:
    """Average model over the honest rows only.

    Under Byzantine injection (docs/BYZANTINE.md) the network-wide mean is
    meaningless — the adversary controls its own rows outright — so every
    reported metric conditions on the honest set: suboptimality becomes
    f(x̄_honest) − f(x*) and consensus becomes the honest spread around
    x̄_honest. ``byzantine`` is the static [N] bool mask from
    ``parallel.adversary.byzantine_mask`` (all-False reduces both to the
    standard definitions).
    """
    return models[~np.asarray(byzantine, dtype=bool)].mean(axis=0)


def honest_consensus_error(models: np.ndarray, byzantine: np.ndarray) -> float:
    """(1/H) Σ_{honest i} ‖x_i − x̄_honest‖² — Byzantine rows excluded."""
    return consensus_error(models[~np.asarray(byzantine, dtype=bool)])


def iterations_to_threshold(objective_history: np.ndarray, threshold: float,
                            eval_iterations: Optional[np.ndarray] = None) -> int:
    """First (1-based) iteration whose suboptimality gap <= threshold, or -1.

    Parity: reference ``simulator.py:73-79``. ``eval_iterations`` maps row
    index -> iteration number when eval_every > 1.
    """
    if objective_history.size == 0:
        return -1
    below = np.nonzero(objective_history <= threshold)[0]
    if below.size == 0:
        return -1
    first = int(below[0])
    if eval_iterations is not None:
        return int(eval_iterations[first])
    return first + 1


def centralized_floats_per_iteration(n_workers: int, n_features: int) -> float:
    """2·N·d floats/iter: N gradient uploads + N model broadcasts.

    Parity: reference ``trainer.py:44-61``. Closed form over T iterations is
    2NdT = 4.05e7 for the report config (BASELINE.md).
    """
    return 2.0 * n_workers * n_features


def decentralized_floats_per_iteration(
    topo: Topology, n_features: int, gossip_rounds: int = 1
) -> float:
    """Σ_i deg_i · d floats per gossip round, times the algorithm's rounds
    (``Algorithm.gossip_rounds``: 2 for gradient tracking, which mixes both
    the model and tracker arrays; 1 otherwise).

    Parity: reference ``trainer.py:169-170``. Closed form ΣdegᵢdT gives
    4.05e7 (ring) / 8.1e7 (grid) / 4.86e8 (fc) for the report config.
    """
    return topo.floats_per_iteration * n_features * gossip_rounds


@dataclasses.dataclass
class ReplicateStats:
    """Seed-variance summary of a replica-batched run (ISSUE-4).

    Every scalar the single-run report quotes becomes a (mean, std) pair
    over the R replicas — the statistical statement a single seed's
    trajectory cannot make. ``iterations_to_threshold_*`` aggregate over
    the replicas that REACHED the threshold (``n_reached`` of
    ``n_replicas``); both are NaN when none did. Stds are population
    (ddof=0) over the replicas actually aggregated.
    """

    n_replicas: int
    seeds: list
    final_gap_mean: float
    final_gap_std: float
    consensus_mean: Optional[float]  # None when consensus was not tracked
    consensus_std: Optional[float]
    iterations_to_threshold_mean: float
    iterations_to_threshold_std: float
    n_reached: int
    per_replica_iterations: list  # -1 = that replica never reached ε
    aggregate_iters_per_second: float


def summarize_replicates(
    objective: np.ndarray,  # [R, n_evals] per-replica suboptimality gaps
    consensus: Optional[np.ndarray],  # [R, n_evals] or None
    eval_iterations: np.ndarray,
    threshold: float,
    seeds: list,
    aggregate_iters_per_second: float,
) -> ReplicateStats:
    """Reduce a batch's [R, n_evals] histories to mean ± std statistics."""
    R = objective.shape[0]
    finals = objective[:, -1]
    per_rep = [
        iterations_to_threshold(objective[r], threshold, eval_iterations)
        for r in range(R)
    ]
    reached = np.asarray([it for it in per_rep if it > 0], dtype=np.float64)
    return ReplicateStats(
        n_replicas=R,
        seeds=list(seeds),
        final_gap_mean=float(np.mean(finals)),
        final_gap_std=float(np.std(finals)),
        consensus_mean=(
            float(np.mean(consensus[:, -1])) if consensus is not None else None
        ),
        consensus_std=(
            float(np.std(consensus[:, -1])) if consensus is not None else None
        ),
        iterations_to_threshold_mean=(
            float(reached.mean()) if reached.size else float("nan")
        ),
        iterations_to_threshold_std=(
            float(reached.std()) if reached.size else float("nan")
        ),
        n_reached=int(reached.size),
        per_replica_iterations=per_rep,
        aggregate_iters_per_second=aggregate_iters_per_second,
    )


@dataclasses.dataclass
class NumericalResult:
    """One row of the experiment report (reference ``simulator.py:88-92``)."""

    label: str
    iterations_to_threshold: int  # -1 = never reached
    total_transmission_floats: float
    avg_worker_transmission_floats: float
    spectral_gap: Optional[float] = None
    iters_per_second: float = float("nan")
    seconds_to_threshold: float = float("nan")  # wall clock; nan = never
    time_measured: bool = False  # sec→ε from real timestamps vs interpolation


def summarize_run(
    label: str,
    history: RunHistory,
    threshold: float,
    n_workers: int,
    spectral_gap: Optional[float] = None,
) -> NumericalResult:
    # One derivation of the threshold-crossing row serves both metrics.
    below = (
        np.nonzero(history.objective <= threshold)[0]
        if history.objective.size else np.empty(0, dtype=int)
    )
    if below.size:
        row = int(below[0])
        iters = int(history.eval_iterations[row])
        seconds = (
            float(history.time[row]) if row < history.time.size else float("nan")
        )
    else:
        iters, seconds = -1, float("nan")
    total = history.total_floats_transmitted
    return NumericalResult(
        label=label,
        iterations_to_threshold=iters,
        total_transmission_floats=total,
        avg_worker_transmission_floats=total / n_workers if n_workers else 0.0,
        spectral_gap=spectral_gap,
        iters_per_second=history.iters_per_second,
        seconds_to_threshold=seconds,
        time_measured=history.time_measured,
    )
