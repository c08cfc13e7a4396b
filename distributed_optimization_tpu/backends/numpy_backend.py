"""The numpy fidelity-oracle backend: reference-semantics simulator.

Mirrors the reference's single-process execution model (SURVEY.md §0) —
host-side float64 numpy, per-iteration Python loop, dense ``W @ models``
gossip, full-dataset objective evaluated on the host every iteration — so it

1. anchors metric/convergence parity with the reference's published numbers,
2. provides the CPU iters/sec baseline the north-star speedup is measured
   against (BASELINE.json), and
3. serves as the equivalence oracle for the JAX backend (identical injected
   batches must produce matching trajectories — SURVEY.md §4c).

Covers the two algorithms the reference implements (centralized SGD,
D-SGD) via the same shared step rules the JAX backend uses, plus
INDEPENDENT matrix-form host implementations of every extension written
directly from the published recursions rather than through the shared
``Algorithm.step`` rules — gradient tracking (Nedić-Olshevsky-Shi 2017,
DIGing), EXTRA (Shi-Ling-Wu-Yin 2015 eq. 2.13), decentralized linearized
ADMM (Ling-Shi-Wu-Ribeiro 2015, DLM; half-Laplacian matrix form), CHOCO-SGD
(Koloskova-Stich-Jaggi 2019, Algorithm 2 matrix form), and push-sum SGP
(Nedić-Olshevsky 2016; Assran et al. 2019, Algorithm 1) — so all seven
algorithms have a long-horizon fixed-point / trajectory oracle for the
JAX backend (SURVEY.md §4c backend-equivalence strategy). The only CHOCO
restriction: randomized compressors (random_k, qsgd) draw from the JAX
counter-based PRNG inside the step, which a host oracle cannot reproduce
without importing the very code under test — the deterministic compressors
(none, top_k) are supported and are the measured configurations.
"""

from __future__ import annotations

import functools
import time
from typing import Optional

import numpy as np

from distributed_optimization_tpu.algorithms import get_algorithm
from distributed_optimization_tpu.algorithms.base import StepContext
from distributed_optimization_tpu.backends.base import BackendRunResult
from distributed_optimization_tpu.metrics import (
    RunHistory,
    centralized_floats_per_iteration,
    consensus_error,
    decentralized_floats_per_iteration,
    honest_consensus_error,
    honest_mean,
)
from distributed_optimization_tpu.ops import losses_np
from distributed_optimization_tpu.ops.robust_aggregation import (
    robust_activity_np,
    robust_aggregate_np,
    validate_budget,
)
from distributed_optimization_tpu.parallel import build_topology
from distributed_optimization_tpu.parallel.adversary import byzantine_set
from distributed_optimization_tpu.utils.data import HostDataset

_SUPPORTED = (
    "centralized", "dsgd", "gradient_tracking", "extra", "admm", "choco",
    "push_sum",
)

# Algorithms with a dedicated matrix-form host implementation below,
# independent of the shared ``Algorithm.step`` rules the JAX backend runs.
_MATRIX_FORM = ("gradient_tracking", "extra", "admm", "choco", "push_sum")


def run_async(
    config,
    dataset: HostDataset,
    f_opt: float,
    *,
    batch_schedule: Optional[np.ndarray] = None,
    collect_metrics: bool = True,
    state0: Optional[dict] = None,
    start_event: int = 0,
    n_events: Optional[int] = None,
    return_state: bool = False,
    checkpoint=None,
    _fault_timeline=None,
) -> BackendRunResult:
    """Per-event float64 twin of the jax scan-over-events path.

    The event SCHEDULE and the event-axis fault realization come from the
    shared host-side builders (``parallel/events.py`` — the
    fault-timeline convention: both backends agree on who fires when,
    with whom, at what staleness, and which events are lost to crashes or
    thinning), while the per-event update math — pairwise average,
    stale-read gradient step, the DIGing tracker telescoping, τ fused
    local descents, rejoin warm restarts, the read-snapshot bookkeeping —
    is an independent float64 implementation written from the published
    recursions. Batch draws: ``batch_schedule`` injects per-event indices
    into the firing worker's shard (``[E, b]``, or ``[E, τ, b]`` with
    local_steps τ > 1 — the oracle-equivalence convention; standalone
    runs draw from a host Generator, which the jax counter-based stream
    cannot and need not reproduce). ``state0``/``start_event``/
    ``n_events`` continue a previous slice exactly like the jax twin;
    ``checkpoint`` runs the same event-indexed ``RunCheckpointer``
    contract (one chunk per eval row, bitwise resume).
    """
    from distributed_optimization_tpu.backends.async_scan import (
        _async_trace,
        _validate_slice,
        event_faults_for,
        timeline_for,
    )

    n = config.n_workers
    reg = config.reg_param
    d, objective, gradient, shards, shard_sizes = _problem_setup(
        config, dataset
    )

    topo, timeline = timeline_for(config)
    E = timeline.n_events
    n_events, events_per_eval = _validate_slice(
        config, E, start_event, n_events
    )
    algo_gt = config.algorithm == "gradient_tracking"
    tau = int(config.local_steps)
    telemetry_on = bool(config.telemetry)
    if checkpoint is not None:
        if telemetry_on:
            raise ValueError(
                "telemetry trace buffers are not checkpointed: a resumed "
                "run would report a hole — run telemetry without "
                "checkpointing, or checkpoint without telemetry"
            )
        if state0 is not None or start_event != 0:
            raise ValueError(
                "checkpointed async runs manage their own continuation "
                "cursor (the RunCheckpointer chunk); don't combine "
                "checkpoint= with state0/start_event"
            )
    if batch_schedule is not None:
        batch_schedule = np.asarray(batch_schedule)
        if len(batch_schedule) != E:
            # Same contract (and message shape) as the jax twin: the
            # schedule is indexed by ABSOLUTE event id, so a
            # window-length schedule on a continued slice is the caller
            # bug this catches.
            raise ValueError(
                f"async batch_schedule carries {len(batch_schedule)} "
                f"event rows; the schedule has {E} events (one index "
                "row per event into the firing worker's shard)"
            )
        if tau == 1:
            if batch_schedule.ndim != 2:
                raise ValueError(
                    f"async batch_schedule must be [E, b] at local_steps="
                    f"1; got shape {batch_schedule.shape}"
                )
        elif batch_schedule.ndim != 3 or batch_schedule.shape[1] != tau:
            raise ValueError(
                f"async batch_schedule must be [E, {tau}, b] at "
                f"local_steps={tau} (one [b] row per local descent); got "
                f"shape {batch_schedule.shape}"
            )
    n_evals = n_events // events_per_eval
    rounds_slice = n_events // n
    start_round = start_event // n

    _, fault_real, restart_rows = event_faults_for(
        config, topo, timeline, _fault_timeline
    )
    faults_on = fault_real is not None
    restart_on = restart_rows is not None
    partner_src = fault_real.partner if faults_on else timeline.partner

    carry_leaves = ("x", "x_read") + (("y", "g_prev") if algo_gt else ())
    if state0 is None:
        if start_event != 0:
            raise ValueError(
                "continuing from start_event > 0 needs the previous "
                f"slice's final_state ({list(carry_leaves)}) as state0"
            )
        state = {k: np.zeros((n, d)) for k in carry_leaves}
    else:
        if set(state0) != set(carry_leaves):
            raise ValueError(
                f"async state0 leaves {sorted(state0)} do not match the "
                f"event-path carry {list(carry_leaves)}"
            )
        state = {
            k: np.array(v, dtype=np.float64, copy=True)
            for k, v in state0.items()
        }

    # Standalone batch draws are COUNTER-BASED in (seed, worker, local
    # step[, local descent]) — one fresh Generator per event, like the
    # jax twin's folded keys (independent stream, same contract): a draw
    # never depends on the event interleaving or on how the run is
    # split, which is what makes the continuation path bitwise without
    # an injected schedule. τ = 1 keeps the original 4-word counter so
    # healthy runs replay the PR 9 stream exactly.
    def event_batch(i: int, k: int, m: Optional[int]) -> np.ndarray:
        b = min(config.local_batch_size, shard_sizes[i])
        if b <= 0:
            return np.empty(0, dtype=np.int64)
        words = [config.seed & 0xFFFFFFFF, 0xA57E, i, k]
        if m is not None:
            words.append(m)
        erng = np.random.default_rng(words)
        return erng.choice(shard_sizes[i], size=b, replace=False)

    eta0 = config.learning_rate_eta0
    sqrt_decay = config.resolved_lr_schedule() == "sqrt_decay"
    track_consensus = collect_metrics and config.record_consensus
    gap_hist = np.full(n_evals, np.nan)
    cons_hist = np.full(n_evals, np.nan)
    time_hist = np.empty(n_evals)

    # Event-indexed checkpointing (ISSUE-17): one chunk per eval row,
    # shared RunCheckpointer contract with the jax twin (truncated-chunk
    # fallback, config sidecar, bitwise resume — all RNG is
    # counter-based, so the replayed tail is the uninterrupted run's).
    ckptr = None
    start_chunk = 0
    if checkpoint is not None:
        from distributed_optimization_tpu.utils.checkpoint import (
            RunCheckpointer,
        )

        ckptr = RunCheckpointer(checkpoint)
        restored = None
        # Horizon-global event schedule: n_iterations is NOT resumable on
        # the event clock (async_scan's sidecar convention).
        if checkpoint.resume:
            ckptr.validate_or_record_config(
                config, resumable_keys=frozenset(),
            )
            restored = ckptr.restore()
        else:
            ckptr.reset(config, resumable_keys=frozenset())
        if restored is not None:
            state_np, gaps_r, conss_r, _fl, times_r, start_chunk = restored
            if start_chunk > n_evals:
                raise ValueError(
                    f"checkpoint at chunk {start_chunk} exceeds this "
                    f"run's horizon ({n_evals} eval chunks); raise "
                    "n_iterations to extend the checkpointed progress"
                )
            if set(state_np) != set(carry_leaves):
                raise ValueError(
                    f"checkpointed state leaves {sorted(state_np)} do "
                    f"not match the event-path carry {list(carry_leaves)}"
                )
            state = {
                k: np.array(v, dtype=np.float64, copy=True)
                for k, v in state_np.items()
            }
            gap_hist[:start_chunk] = np.asarray(gaps_r)[:start_chunk]
            if len(conss_r):
                cons_hist[:start_chunk] = np.asarray(conss_r)[:start_chunk]
            time_hist[:start_chunk] = np.asarray(times_r)[:start_chunk]

    x, x_read = state["x"], state["x_read"]
    if algo_gt:
        y, g_prev = state["y"], state["g_prev"]
    g_norm = np.zeros(n) if telemetry_on else None
    tele_rows: dict[str, list] = {
        "param_norm": [], "grad_norm": [], "nonfinite": [],
    }

    def local_chain(x_start, corr, eta, e, i):
        """τ local descents fused into one event (the jax twin's
        ``local_chain``): z_{m+1} = z_m − η(corr + g(z_m))."""
        Xi, yi = shards[i]
        z = x_start.copy()
        gsum = np.zeros_like(x_start)
        k = int(timeline.local_step[e])
        for m in range(tau):
            if batch_schedule is not None:
                idx = np.asarray(batch_schedule[e][m])
            else:
                idx = event_batch(i, k, m)
            gm = gradient(z, Xi[idx], yi[idx], reg)
            gsum += gm
            z = z - eta * (corr + gm)
        return z - x_start, gsum / tau

    t_base = float(time_hist[start_chunk - 1]) if start_chunk else 0.0
    save_seconds = 0.0
    start = time.perf_counter()
    for off in range(start_chunk * events_per_eval, n_events):
        e = start_event + off
        i = int(timeline.worker[e])
        # Mid-flight crash / thinned firing: the event is a no-op — but
        # the eval-row bookkeeping below still runs (a window whose
        # CLOSING event is a no-op must still emit its row).
        fired = not (faults_on and not fault_real.fire[e])
        if fired:
            j = int(partner_src[e])
            k = int(timeline.local_step[e])
            eta = eta0 / np.sqrt(k + 1.0) if sqrt_decay else eta0
            xi, read_i = x[i], x_read[i]
            if restart_on and fault_real.rejoin[e]:
                # neighbor_restart rejoin: warm-start from the realized
                # alive neighborhood average (x only; GT tracker rows
                # untouched).
                warm = restart_rows[e] @ x
                xi = warm
                read_i = warm
            matched = j != i
            avg = 0.5 * (xi + x[j]) if matched else None
            base_i = avg if matched else xi
            if algo_gt:
                # DIGing tracker telescoping at the stale read: the
                # network sum of y tracks the sum of g_prev EXACTLY at
                # every event.
                avg_y = 0.5 * (y[i] + y[j]) if matched else None
                base_y = avg_y if matched else y[i]
                if tau == 1:
                    Xi, yi_s = shards[i]
                    if batch_schedule is not None:
                        idx = np.asarray(batch_schedule[e])
                    else:
                        idx = event_batch(i, k, None)
                    g = gradient(read_i, Xi[idx], yi_s[idx], reg)
                    new_y_i = base_y + g - g_prev[i]
                    new_i = base_i - eta * new_y_i
                else:
                    delta, g = local_chain(
                        read_i, base_y - g_prev[i], eta, e, i
                    )
                    new_y_i = base_y + g - g_prev[i]
                    new_i = base_i + delta
                if matched:
                    y[j] = avg_y
                y[i] = new_y_i
                g_prev[i] = g
            else:
                if tau == 1:
                    Xi, yi_s = shards[i]
                    if batch_schedule is not None:
                        idx = np.asarray(batch_schedule[e])
                    else:
                        idx = event_batch(i, k, None)
                    g = gradient(read_i, Xi[idx], yi_s[idx], reg)
                    # D-PSGD ordering: average the live pair, then the
                    # firing worker descends along its stale-read
                    # gradient.
                    new_i = base_i - eta * g
                else:
                    delta, g = local_chain(read_i, 0.0, eta, e, i)
                    new_i = base_i + delta
            if matched:
                x[j] = avg
            x[i] = new_i
            x_read[i] = x[i].copy()
            if telemetry_on:
                g_norm[i] = float(np.linalg.norm(g))
        if (off + 1) % events_per_eval == 0:
            row = (off + 1) // events_per_eval - 1
            if collect_metrics:
                xbar = x.mean(axis=0)
                gap_hist[row] = (
                    objective(xbar, dataset.X_full, dataset.y_full, reg)
                    - f_opt
                )
                if track_consensus:
                    cons_hist[row] = consensus_error(x)
            if telemetry_on:
                tele_rows["param_norm"].append(
                    np.linalg.norm(x, axis=1).astype(np.float32)
                )
                tele_rows["grad_norm"].append(
                    g_norm.astype(np.float32).copy()
                )
                tele_rows["nonfinite"].append(
                    np.float32((~np.isfinite(x)).sum())
                )
            time_hist[row] = (
                t_base + time.perf_counter() - start - save_seconds
            )
            if ckptr is not None and (
                (row + 1) % checkpoint.every_evals == 0
                or row + 1 == n_evals
            ):
                t_save = time.perf_counter()
                ckptr.save(
                    row + 1,
                    {k: v.copy() for k, v in state.items()},
                    gap_hist[:row + 1], cons_hist[:row + 1],
                    (), time_hist[:row + 1],
                )
                save_seconds += time.perf_counter() - t_save
    run_seconds = time.perf_counter() - start - save_seconds

    # Comms accounting: only FIRED live exchanges move data — 2·d floats
    # for the model pair, 4·d for gradient tracking (tracker rows ride
    # alongside). Solo, degraded, and non-firing events move nothing.
    matched_eff = (
        fault_real.matched_fired if faults_on else timeline.matched()
    )
    matched_slice = int(
        np.sum(matched_eff[start_event:start_event + n_events])
    )
    per_exchange = (4.0 if algo_gt else 2.0) * d

    trace = None
    if telemetry_on:
        trace = _async_trace(
            config, timeline, fault_real, matched_eff, tele_rows,
            start_event, n_evals, events_per_eval,
        )

    history = RunHistory(
        objective=gap_hist,
        consensus_error=cons_hist if track_consensus else None,
        time=time_hist,
        time_measured=True,
        eval_iterations=np.arange(
            start_round + config.eval_every,
            start_round + rounds_slice + 1,
            config.eval_every,
        ),
        total_floats_transmitted=per_exchange * matched_slice,
        iters_per_second=(
            rounds_slice / run_seconds if run_seconds > 0 else float("inf")
        ),
        spectral_gap=topo.spectral_gap,
        trace=trace,
    )
    return BackendRunResult(
        history=history,
        final_models=x,
        final_avg_model=x.mean(axis=0),
        final_state=(
            dict(state) if return_state else None
        ),
    )


def _problem_setup(config, dataset: HostDataset):
    """Shared host problem prelude for the sync and async oracle paths:
    (d, objective, gradient, shards, shard_sizes). ``d`` is the TRAINED
    dimension — the softmax family's flat [d·K] matrix, ``n_features``
    for the scalar GLMs (mirrors jax_backend's ``problem.param_dim``
    without importing the jax problem registry)."""
    d = dataset.n_features
    if config.problem_type == "softmax":
        d = dataset.n_features * config.n_classes
    objective = losses_np.OBJECTIVES[config.problem_type]
    gradient = losses_np.GRADIENTS[config.problem_type]
    if config.problem_type == "huber":
        objective = functools.partial(objective, delta=config.huber_delta)
        gradient = functools.partial(gradient, delta=config.huber_delta)
    shards = [dataset.shard(i) for i in range(config.n_workers)]
    shard_sizes = [Xi.shape[0] for Xi, _ in shards]
    return d, objective, gradient, shards, shard_sizes


def _topk_rows(v: np.ndarray, k: int) -> np.ndarray:
    """Per-row top-k-by-magnitude compressor (Koloskova et al. '19 §2, the
    deterministic contraction): keep the k largest |v| entries per row, zero
    the rest. Ties break toward the lower index (a stable descending sort),
    matching ``lax.top_k`` so the two backends select identical supports."""
    out = np.zeros_like(v)
    for r in range(v.shape[0]):
        keep = np.argsort(-np.abs(v[r]), kind="stable")[:k]
        out[r, keep] = v[r, keep]
    return out


def run(
    config,
    dataset: HostDataset,
    f_opt: float,
    *,
    batch_schedule: Optional[np.ndarray] = None,
    collect_metrics: bool = True,
) -> BackendRunResult:
    if config.execution == "async":
        # Event-driven asynchronous gossip (docs/ASYNC.md): per-event
        # float64 twin of the jax scan-over-events path.
        return run_async(
            config, dataset, f_opt, batch_schedule=batch_schedule,
            collect_metrics=collect_metrics,
        )
    if config.algorithm not in _SUPPORTED:
        raise ValueError(
            f"numpy backend implements {_SUPPORTED} (the reference's "
            "algorithms plus matrix-form oracles for the exact first-order "
            f"extensions); {config.algorithm!r} is a jax-backend capability"
        )
    if config.gossip_schedule != "synchronous":
        raise ValueError(
            "matching-based gossip (one_peer/round_robin) is a jax-backend "
            "capability; the numpy oracle covers the synchronous schedule "
            "(fault-free or with synchronous failure injection)"
        )
    algo = get_algorithm(config.algorithm)
    # Synchronous failure injection IS oracle-supported (iid edge drops,
    # bursty Gilbert-Elliott links, iid stragglers, crash-recovery churn):
    # the fault SCHEDULE comes from the shared host-side timeline builder —
    # the same convention as the Byzantine set below, so both backends
    # agree on which edges/nodes fail — while every piece of mask/weight
    # MATH (realized MH / column-stochastic weights, the freeze, the
    # rejoin restart, the realized-floats accounting) is an independent
    # float64 twin of the jax path.
    faults_active = (
        config.edge_drop_prob > 0.0
        or config.straggler_prob > 0.0
        or config.mttf > 0.0
        or config.participation_rate < 1.0
    )
    if faults_active:
        if not algo.is_decentralized:
            raise ValueError(
                "fault injection models peer exchanges and applies only to "
                "decentralized algorithms; the centralized pattern has no "
                "peer edges"
            )
        if not algo.supports_edge_faults:
            raise ValueError(
                f"time-varying gossip is unsupported for "
                f"{config.algorithm!r} (see jax_backend for the rationale "
                "per algorithm)"
            )
        if config.mttf > 0.0 and not algo.supports_churn:
            raise ValueError(
                f"crash-recovery churn is unsupported for "
                f"{config.algorithm!r}; use 'dsgd' or 'gradient_tracking' "
                "(see jax_backend for the rationale per algorithm)"
            )
    byz_active = config.attack != "none" or (
        config.aggregation != "gossip" and config.robust_b > 0
    )
    if byz_active:
        if not algo.supports_byzantine:
            raise ValueError(
                f"Byzantine injection / robust aggregation is unsupported "
                f"for {config.algorithm!r}; use 'dsgd' or "
                "'gradient_tracking' (see jax_backend for the rationale "
                "per algorithm)"
            )
        if config.attack == "large_noise":
            raise ValueError(
                "the numpy oracle supports the deterministic attacks "
                "(sign_flip, alie); large_noise draws from the jax "
                "counter-based PRNG inside the step, which an independent "
                "host implementation cannot reproduce without importing "
                "the code under test"
            )
    T = config.n_iterations
    n = config.n_workers
    reg = config.reg_param
    d, objective, gradient, shards, shard_sizes = _problem_setup(
        config, dataset
    )

    if config.compression in ("random_k", "qsgd"):
        raise ValueError(
            "the numpy error-feedback oracle supports the deterministic "
            "compressors (none, top_k); random_k/qsgd draw from the jax "
            "counter-based PRNG inside the step, which an independent host "
            "implementation cannot reproduce without importing the code "
            "under test"
        )
    # Compressed dsgd shares CHOCO's matrix recursion (it IS the CHOCO
    # update registered under dsgd — see algorithms/dsgd.py); compressed
    # gradient tracking extends the GT matrix form with per-leaf
    # error-feedback estimates. Both therefore take the matrix-form
    # branch below instead of the shared Algorithm.step rules.
    compressed = config.compression != "none"
    if algo.is_decentralized:
        topo = build_topology(
            config.topology, n, erdos_renyi_p=config.erdos_renyi_p,
            seed=config.resolved_topology_seed(),
        )
        W = topo.mixing_matrix
        A = topo.adjacency
        degrees = topo.degrees[:, None]
        if algo.comm_payload is not None:
            # Compressed gossip transmits the compressor's payload per edge
            # (same accounting as the jax backend).
            floats_per_iter = topo.floats_per_iteration * algo.comm_payload(
                config, d
            )
        else:
            floats_per_iter = decentralized_floats_per_iteration(
                topo, d, algo.gossip_rounds
            )
        spectral_gap = topo.spectral_gap
    else:
        topo, W, A = None, None, None
        degrees = np.zeros((n, 1))
        floats_per_iter = centralized_floats_per_iteration(n, d)
        spectral_gap = None

    # --- failure injection (mirrors jax_backend; docs/CHURN.md). `live`
    # holds the CURRENT iteration's realized (W_t, A_t); the gossip
    # closures below read through it so one definition serves the static
    # and the time-varying case. The weight recomputation rules are
    # independent numpy twins of parallel/faults.py's jax forms.
    timeline = None
    live = {"W": W, "A": A}
    realized_degree_total = 0.0
    if faults_active:
        from distributed_optimization_tpu.parallel.faults import (
            timeline_for_config,
        )

        # The realization the jax backend reads, its leaves on the host.
        timeline = timeline_for_config(config, topo, T)

        def _up_row(t: int) -> Optional[np.ndarray]:
            """Composed [N] bool availability at round t: churn/straggler-up
            AND sampled-in (participation) — the independent float64 twin
            of the jax path's composed ``active(t)``. None when no node
            process is active."""
            up = None
            if timeline.node_up is not None:
                up = timeline.node_up[t]
            if timeline.part_up is not None:
                up = (
                    timeline.part_up[t] if up is None
                    else up & timeline.part_up[t]
                )
            return up

        def _realized_A(t: int) -> np.ndarray:
            if timeline.edge_up is not None:
                A_t = np.zeros((n, n))
                ei = timeline.edge_index[:, 0]
                ej = timeline.edge_index[:, 1]
                vals = timeline.edge_up[t].astype(np.float64)
                A_t[ei, ej] = vals
                if not topo.directed:
                    A_t[ej, ei] = vals
            else:
                A_t = np.asarray(A, dtype=np.float64).copy()
            up = _up_row(t)
            if up is not None:
                m = up.astype(np.float64)
                A_t *= m[:, None] * m[None, :]  # down node exchanges nothing
            return A_t

        def _mh_weights(A_t: np.ndarray) -> np.ndarray:
            # Metropolis-Hastings on realized degrees: symmetric + doubly
            # stochastic for every draw; an isolated row collapses to I.
            deg = A_t.sum(axis=1)
            pair = 1.0 / (1.0 + np.maximum(deg[:, None], deg[None, :]))
            W_t = A_t * pair
            return W_t + np.diag(1.0 - W_t.sum(axis=1))

        def _column_stochastic(A_t: np.ndarray) -> np.ndarray:
            # Surviving-out-link renormalization (directed / push-sum fault
            # model): columns sum to 1 for every realization.
            out_deg = A_t.sum(axis=0)
            W_t = A_t / (1.0 + out_deg)[None, :]
            return W_t + np.diag(1.0 - W_t.sum(axis=0))

        _realized_weights = (
            _column_stochastic if topo.directed else _mh_weights
        )

    # --- Byzantine machinery (mirrors jax_backend; docs/BYZANTINE.md).
    # The Byzantine SET comes from the shared host-side sampler so both
    # backends agree on who lies; the corruption and the robust rules are
    # independent numpy twins. Byzantine rows keep the benign W-mix of the
    # TRUE stack (attackers run honest dynamics internally and lie only on
    # the wire — same convention as parallel/adversary.py).
    byz = None
    if byz_active:
        byz = byzantine_set(config, topo)
        robust_name = (
            config.aggregation
            if config.aggregation != "gossip" and config.robust_b > 0
            else None
        )
        if robust_name is not None:
            validate_budget(
                int(topo.degrees.min()), config.robust_b, config.aggregation
            )
        scale = config.attack_scale

        def corrupt_np(v: np.ndarray) -> np.ndarray:
            if config.attack == "none":
                return v
            out = np.array(v, dtype=np.float64, copy=True)
            if config.attack == "sign_flip":
                out[byz] = -scale * v[byz]
            else:  # alie: shared honest_mean − scale·honest_std payload
                mu = v[~byz].mean(axis=0)
                sd = v[~byz].std(axis=0)
                out[byz] = mu - scale * sd
            return out

        def byz_mix(v: np.ndarray) -> np.ndarray:
            # Reads the realized (W_t, A_t) through `live`, so attacks and
            # screening run over the same per-iteration graph as the
            # mixing (the realized_adjacency composition of the jax path).
            va = corrupt_np(v)
            if robust_name is not None:
                honest_agg = robust_aggregate_np(
                    robust_name, live["A"], va, config.robust_b,
                    config.clip_tau,
                )
            else:
                honest_agg = live["W"] @ va
            if not byz.any():  # pure-defense run: no benign branch needed
                return honest_agg
            return np.where(byz[:, None], live["W"] @ v, honest_agg)

    rng = np.random.default_rng(config.seed)
    eta0 = config.learning_rate_eta0
    sqrt_decay = config.resolved_lr_schedule() == "sqrt_decay"

    def sample_indices(t: int, i: int) -> np.ndarray:
        if batch_schedule is not None:
            return batch_schedule[t, i]
        ni = shard_sizes[i]
        b = min(config.local_batch_size, ni)
        if b <= 0:
            return np.empty(0, dtype=np.int64)
        return rng.choice(ni, size=b, replace=False)

    # Last-drawn batch indices per worker — the flight recorder's gradient
    # probe reuses them, so it measures the SAME batch realization the eval
    # iteration's step consumed (jax_backend parity: its probe re-derives
    # that batch from the counter-based (key, t)) WITHOUT consuming any
    # extra host-RNG draws — telemetry must not perturb the trajectory.
    last_idx: dict[int, np.ndarray] = {}

    def make_grad(t: int):
        def grad(params: np.ndarray, slot: int) -> np.ndarray:
            out = np.zeros((n, d))
            for i in range(n):
                Xi, yi = shards[i]
                idx = sample_indices(t, i)
                last_idx[i] = idx
                out[i] = gradient(params[i], Xi[idx], yi[idx], reg)
            return out

        return grad

    if config.algorithm in _MATRIX_FORM or (
        config.algorithm == "dsgd" and compressed
    ):
        # Independent matrix recursions (NOT algo.init/algo.step): state
        # leaves written out explicitly from the published update equations.
        zeros = np.zeros((n, d))
        if config.algorithm == "gradient_tracking" and compressed:
            # Compressed DIGing (the jax rule's independent float64 twin,
            # algorithms/gradient_tracking.py): BOTH gossip rounds replace
            # W v with the error-feedback exchange v + γ(W X̂⁺ − X̂⁺) over
            # per-leaf estimate memories; Q = identity or per-row top-k
            # (randomized compressors rejected above). Compression
            # excludes faults/Byzantine by config, so W is static here.
            gamma = config.choco_gamma
            k_comp = config.compression_k
            compress = (
                (lambda v: v) if config.compression == "none"
                else (lambda v: _topk_rows(v, k_comp))
            )
            state = {"x": zeros.copy(), "y": zeros.copy(),
                     "g": zeros.copy(), "xhat": zeros.copy(),
                     "yhat": zeros.copy()}

            def matrix_step(state, t, eta, grad_at):
                xhat_new = state["xhat"] + compress(
                    state["x"] - state["xhat"]
                )
                x_new = (
                    state["x"] + gamma * (W @ xhat_new - xhat_new)
                    - eta * state["y"]
                )
                g_new = grad_at(x_new)
                yhat_new = state["yhat"] + compress(
                    state["y"] - state["yhat"]
                )
                y_new = (
                    state["y"] + gamma * (W @ yhat_new - yhat_new)
                    + g_new - state["g"]
                )
                return {"x": x_new, "y": y_new, "g": g_new,
                        "xhat": xhat_new, "yhat": yhat_new}

        elif config.algorithm == "gradient_tracking":
            # DIGing: x_{t+1} = W x_t − η y_t;  y_{t+1} = W y_t + g_{t+1} − g_t
            # with y_0 = g_prev = 0 (first step is a pure gossip step).
            # Under Byzantine injection both gossip rounds go through the
            # corrupt/screen composition, exactly like the jax rule; under
            # faults the realized W_t is read through `live`.
            gossip = byz_mix if byz is not None else (lambda v: live["W"] @ v)
            state = {"x": zeros.copy(), "y": zeros.copy(), "g": zeros.copy()}
            tau_gt = config.local_steps

            def matrix_step(state, t, eta, grad_at):
                x_new = gossip(state["x"]) - eta * state["y"]
                g_new = grad_at(x_new)
                y_new = gossip(state["y"]) + g_new - state["g"]
                # Federated local updates (config.local_steps = τ): τ−1
                # extra LOCAL descents along the tracker-corrected
                # direction y_new + (g(v) − g_new) — the independent
                # float64 twin of the jax rule's K-GT-style recursion
                # (algorithms/gradient_tracking.py). τ = 1 adds no ops.
                for _ in range(1, tau_gt):
                    x_new = x_new - eta * (y_new + grad_at(x_new) - g_new)
                return {"x": x_new, "y": y_new, "g": g_new}

        elif config.algorithm == "extra":
            # EXTRA (Shi et al. 2015):
            #   x_1     = W x_0 − η g(x_0)
            #   x_{t+1} = (I+W) x_t − (I+W)/2 x_{t−1} − η (g(x_t) − g(x_{t−1}))
            # ``Wx_prev`` carries the previous iteration's W @ x, so each
            # step performs exactly one dense mix (same comms accounting as
            # the jax rule, which also reuses the carried mix).
            state = {"x": zeros.copy(), "x_prev": zeros.copy(),
                     "Wx_prev": zeros.copy(), "g": zeros.copy(),
                     "started": False}

            def matrix_step(state, t, eta, grad_at):
                x = state["x"]
                g = grad_at(x)
                Wx = W @ x
                if not state["started"]:
                    x_new = Wx - eta * g
                else:
                    x_new = (
                        x + Wx
                        - 0.5 * (state["x_prev"] + state["Wx_prev"])
                        - eta * (g - state["g"])
                    )
                return {"x": x_new, "x_prev": x, "Wx_prev": Wx, "g": g,
                        "started": True}

        elif config.algorithm == "admm":
            # DLM (Ling-Shi-Wu-Ribeiro 2015), half-Laplacian matrix form.
            # Edge-consensus ADMM (x_i = z_e = x_j per edge) with
            # zero-initialized duals eliminates z to the edge midpoint; the
            # aggregated node dual Φ (rows φ_i = Σ_{e∋i} λ_{e,i}) and a
            # linearized f with proximal weight ρ give, with D = deg diag,
            # A = adjacency, L⁺ = (D+A)/2 (signless half-Laplacian),
            # L⁻ = (D−A)/2 (half-Laplacian):
            #   X_{k+1} = (ρI + cD)⁻¹ (ρ X_k + c L⁺ X_k − ∇F(X_k) − Φ_k)
            #   Φ_{k+1} = Φ_k + c L⁻ X_{k+1}
            # The diagonal system solves row-wise; step size is the penalty
            # pair (c, ρ), not η (constant by construction — the lr schedule
            # is irrelevant here, as in the jax rule).
            c_pen, rho = config.admm_c, config.admm_rho
            D = np.diag(topo.degrees.astype(np.float64))
            L_plus = 0.5 * (D + A)
            L_minus = 0.5 * (D - A)
            diag_inv = 1.0 / (rho + c_pen * topo.degrees)[:, None]
            state = {"x": zeros.copy(), "phi": zeros.copy()}

            def matrix_step(state, t, eta, grad_at):
                x, phi = state["x"], state["phi"]
                g = grad_at(x)
                x_new = diag_inv * (
                    rho * x + c_pen * (L_plus @ x) - g - phi
                )
                return {"x": x_new, "phi": phi + c_pen * (L_minus @ x_new)}

        elif config.algorithm == "push_sum":
            # Push-sum SGP (Nedić-Olshevsky 2016; Assran et al. 2019 Alg. 1)
            # with COLUMN-stochastic A (directed graphs; a doubly stochastic
            # W is the degenerate case with mass ≡ 1):
            #   num_{t+1} = A (num_t − η ∇F(z_t))
            #   w_{t+1}   = A w_t,  w_0 = 1
            #   z_{t+1}   = num_{t+1} / w_{t+1}
            # Gradients at the de-biased z. The 'x' leaf holds z so metrics
            # and final_models see the estimates (same layout as the jax
            # rule). Columns of A summing to 1 conserve Σ num and Σ w = N.
            state = {"x": zeros.copy(), "num": zeros.copy(),
                     "w": np.ones((n, 1))}

            def matrix_step(state, t, eta, grad_at):
                g = grad_at(state["x"])
                num_new = live["W"] @ (state["num"] - eta * g)
                w_new = live["W"] @ state["w"]
                return {"x": num_new / w_new, "num": num_new, "w": w_new}

        else:  # choco, and compressed dsgd (the identical recursion)
            # CHOCO-SGD (Koloskova et al. 2019, Algorithm 2 matrix form):
            #   X_{t+½} = X_t − η ∇F(X_t)
            #   X̂_{t+1} = X̂_t + Q(X_{t+½} − X̂_t)      ← the transmitted bits
            #   X_{t+1} = X_{t+½} + γ (W − I) X̂_{t+1}
            # Q = identity ('none') or per-row top-k; randomized compressors
            # are rejected above. Compressed dsgd routes here too: the
            # error-feedback D-SGD step IS this update (only the lr
            # schedule differs, and eta arrives resolved from the config).
            gamma = config.choco_gamma
            k_comp = config.compression_k
            compress = (
                (lambda v: v) if config.compression == "none"
                else (lambda v: _topk_rows(v, k_comp))
            )
            state = {"x": zeros.copy(), "xhat": zeros.copy()}

            def matrix_step(state, t, eta, grad_at):
                x, xhat = state["x"], state["xhat"]
                x_half = x - eta * grad_at(x)
                xhat_new = xhat + compress(x_half - xhat)
                return {
                    "x": x_half + gamma * (W @ xhat_new - xhat_new),
                    "xhat": xhat_new,
                }

    else:
        matrix_step = None
        state = {k: np.asarray(v, dtype=np.float64) for k, v in
                 algo.init(
                     np.zeros((n, d)), config,
                     neighbor_sum=(lambda v: A @ v) if A is not None else None,
                 ).items()}

    eval_every = config.eval_every
    n_evals = T // eval_every
    track_consensus = (
        collect_metrics and algo.is_decentralized and config.record_consensus
    )
    gap_hist = np.full(n_evals, np.nan)
    cons_hist = np.full(n_evals, np.nan)
    time_hist = np.empty(n_evals)
    trace_lists: Optional[dict[str, list]] = (
        {k: [] for k in ("grad_norm", "param_norm", "nodes_up",
                         "nonfinite", "live_edges", "clip_frac")}
        if config.telemetry else None
    )

    def trace_row(x: np.ndarray, t: int) -> None:
        """One flight-recorder row (telemetry.TRACE_FIELDS) — independent
        float64 twin of the jax backend's in-scan probe, same keys/shapes/
        float32 rows, recorded from the post-step state at the eval
        boundary."""
        gnorm = np.zeros(n)
        for i in range(n):
            Xi, yi = shards[i]
            idx = last_idx.get(i)
            if idx is None:  # no step ran yet (T == 0 edge)
                idx = np.arange(shard_sizes[i])
            gnorm[i] = np.linalg.norm(gradient(x[i], Xi[idx], yi[idx], reg))
        nonf = 0
        for v in state.values():
            if isinstance(v, np.ndarray) and np.issubdtype(
                v.dtype, np.floating
            ):
                nonf += int(np.sum(~np.isfinite(v)))
        if algo.is_decentralized:
            live_edges = float(np.asarray(live["A"]).sum())
        else:
            live_edges = 0.0
        up_row = _up_row(t) if timeline is not None else None
        nodes = (
            up_row.astype(np.float32)
            if up_row is not None
            else np.ones(n, dtype=np.float32)
        )
        cf = 0.0
        if byz is not None and robust_name is not None:
            cf = robust_activity_np(
                robust_name, live["A"], corrupt_np(x), config.robust_b,
                config.clip_tau,
            )
        trace_lists["grad_norm"].append(gnorm.astype(np.float32))
        trace_lists["param_norm"].append(
            np.linalg.norm(x, axis=1).astype(np.float32)
        )
        trace_lists["nodes_up"].append(nodes)
        trace_lists["nonfinite"].append(np.float32(nonf))
        trace_lists["live_edges"].append(np.float32(live_edges))
        trace_lists["clip_frac"].append(np.float32(cf))

    start = time.perf_counter()

    for t in range(T):
        eta = eta0 / np.sqrt(t + 1.0) if sqrt_decay else eta0
        if faults_active:
            A_t = _realized_A(t)
            live["A"] = A_t
            live["W"] = _realized_weights(A_t)
            realized_degree_total += A_t.sum()
            if (
                config.rejoin == "neighbor_restart"
                and timeline.rejoin is not None
                and timeline.rejoin[t].any()
            ):
                # Warm restart BEFORE the step (mirrors jax_backend): a
                # rejoining node's model row becomes its realized-
                # neighborhood average; isolated rejoiners stay stale.
                deg = A_t.sum(axis=1)
                take = timeline.rejoin[t] & (deg > 0)
                if take.any():
                    x_r = state["x"].copy()
                    nbr = (A_t @ state["x"]) / np.maximum(deg, 1.0)[:, None]
                    x_r[take] = nbr[take]
                    state = {**state, "x": x_r}
        prev_state = state
        if matrix_step is not None:
            grad_fn = make_grad(t)
            state = matrix_step(state, t, eta, lambda p: grad_fn(p, 0))
        else:
            ctx = StepContext(
                grad=make_grad(t),
                mix=(
                    byz_mix
                    if byz is not None
                    else (lambda v: live["W"] @ v)
                    if W is not None
                    else (lambda v: v)
                ),
                neighbor_sum=(
                    (lambda v: live["A"] @ v)
                    if A is not None
                    else (lambda v: v * 0)
                ),
                eta=eta,
                t=t,
                degrees=degrees,
                config=config,
            )
            state = algo.step(state, ctx)
        if timeline is not None and (
            timeline.node_up is not None or timeline.part_up is not None
        ):
            # A down/sampled-out node takes no step at all: freeze its
            # rows across every state leaf — for churn, across the WHOLE
            # outage, so a 'frozen' rejoin resumes the stale pre-crash
            # state for free.
            up = _up_row(t)
            state = {
                k: np.where(
                    up.reshape((-1,) + (1,) * (v.ndim - 1)), v, prev_state[k]
                )
                for k, v in state.items()
            }
        if (t + 1) % eval_every == 0:
            k = (t + 1) // eval_every - 1
            x = state["x"]
            if collect_metrics:
                # Honest-only metrics under attack (docs/BYZANTINE.md).
                xbar = honest_mean(x, byz) if byz is not None else x.mean(axis=0)
                gap_hist[k] = (
                    objective(xbar, dataset.X_full, dataset.y_full, reg) - f_opt
                )
                if track_consensus:
                    cons_hist[k] = (
                        honest_consensus_error(x, byz)
                        if byz is not None
                        else consensus_error(x)
                    )
            if trace_lists is not None:
                trace_row(x, t)
            time_hist[k] = time.perf_counter() - start

    run_seconds = time.perf_counter() - start

    trace = None
    if trace_lists is not None:
        trace = {
            k: np.asarray(v, dtype=np.float32)
            for k, v in trace_lists.items()
        }

    history = RunHistory(
        objective=gap_hist,
        consensus_error=cons_hist if track_consensus else None,
        time=time_hist,
        time_measured=True,  # real per-eval perf_counter samples
        eval_iterations=np.arange(eval_every, T + 1, eval_every),
        # Honest comms accounting under faults: floats actually exchanged
        # over realized edges (same edge payload convention as the jax
        # backend's realized_degree_sum path).
        total_floats_transmitted=(
            realized_degree_total * d * algo.gossip_rounds
            if faults_active
            else floats_per_iter * T
        ),
        iters_per_second=T / run_seconds if run_seconds > 0 else float("inf"),
        spectral_gap=spectral_gap,
        trace=trace,
    )
    final = state["x"]
    return BackendRunResult(
        history=history,
        final_models=final,
        final_avg_model=(
            honest_mean(final, byz) if byz is not None else final.mean(axis=0)
        ),
    )
