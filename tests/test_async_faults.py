"""Event-clock fault substrate tests (ISSUE 17 tentpole).

The composition-closure contracts: fault processes realized on the EVENT
axis (``parallel/events.py::realize_event_faults``) with the crash-free
degenerate gate pinned BITWISE against the PR 9 program, constant-latency
event churn collapsing onto the round-clock chains, churn ≡ participation
thinning at the chain level, async gradient tracking's per-event tracker
telescoping (the DIGing identity exact at any staleness, faults included),
τ local steps fused per event, event-chunked checkpoint/resume through a
mid-outage restore, and the telemetry trace riding the scan. The
wall-clock-to-ε and degradation-envelope measurements live in
``examples/bench_async_faults.py`` (docs/perf/async_faults.json).
"""

import os
import shutil

import numpy as np
import pytest
from conftest import assert_ulps_of_scale

from distributed_optimization_tpu.backends import jax_backend, numpy_backend
from distributed_optimization_tpu.backends.async_scan import (
    event_faults_for,
    run_async,
    timeline_for,
)
from distributed_optimization_tpu.config import ExperimentConfig
from distributed_optimization_tpu.parallel import build_topology
from distributed_optimization_tpu.parallel.events import (
    all_up_realization,
    realize_event_faults,
)
from distributed_optimization_tpu.parallel.faults import (
    FaultTimeline,
    _edge_list,
    timeline_for_config,
)
from distributed_optimization_tpu.utils.checkpoint import (
    CheckpointOptions,
    RunCheckpointer,
)
from distributed_optimization_tpu.utils.data import generate_synthetic_dataset
from distributed_optimization_tpu.utils.oracle import compute_reference_optimum

N = 8
T = 40


def cfg(**kw):
    base = dict(
        execution="async", n_workers=N, n_iterations=T, eval_every=10,
        n_samples=400, n_features=12, n_informative_features=8,
        local_batch_size=8, dtype="float64", problem_type="quadratic",
        algorithm="dsgd", topology="ring", latency_model="lognormal",
        latency_mean=1.0, latency_tail=0.5, seed=3,
    )
    base.update(kw)
    return ExperimentConfig(**base)


CFG = cfg()
CHURN = cfg(mttf=6.0, mttr=3.0, participation_rate=0.7, seed=9)


@pytest.fixture(scope="module")
def setup():
    ds = generate_synthetic_dataset(CFG)
    _, f_opt = compute_reference_optimum(ds, CFG.reg_param)
    return ds, f_opt


def event_schedule(config, ds, seed=0):
    """Fixed per-event batch indices shared across backends — [E, b] at
    τ=1, [E, τ, b] otherwise (the test_async.event_schedule twin)."""
    _, tl = timeline_for(config)
    sizes = [ds.shard(i)[0].shape[0] for i in range(config.n_workers)]
    rng = np.random.default_rng(seed)
    tau = config.local_steps
    shape = (config.local_batch_size,) if tau == 1 else (
        tau, config.local_batch_size,
    )
    return np.stack([
        rng.integers(0, sizes[int(w)], size=shape) for w in tl.worker
    ])


def _topo(config):
    return build_topology(
        config.topology, config.n_workers,
        erdos_renyi_p=config.erdos_renyi_p,
        seed=config.resolved_topology_seed(),
    )


def _all_up_ft(config):
    """An injected FaultTimeline whose every chain is up — the crash-free
    degenerate gate's forcing input."""
    topo = _topo(config)
    edges = _edge_list(topo)
    n, t = config.n_workers, config.n_iterations
    return FaultTimeline(
        horizon=t, directed=False, edge_index=edges,
        edge_up=np.ones((t, len(edges)), bool),
        node_up=np.ones((t, n), bool),
        rejoin=np.zeros((t, n), bool),
        part_up=np.ones((t, n), bool),
    )


# --- degenerate gates -------------------------------------------------------


def test_crash_free_injection_is_bitwise_pr9(setup):
    """All-up fault masks thread the fault-aware program, yet realize the
    IDENTICAL trajectory: the crash-free event-fault timeline is the PR 9
    async scan, bitwise on the numpy backend (one arithmetic replayed) and,
    on the jax backend, to a few float64 units of the models' scale: the
    fault-aware scan is ANOTHER executable of the same per-row arithmetic,
    whose products XLA contracts as its own fusions fall
    (``conftest.assert_ulps_of_scale``; read 0.39 of a unit). float32
    rounding is 2**29 units off."""
    ds, f_opt = setup
    plain = run_async(CFG, ds, f_opt)
    forced = run_async(CFG, ds, f_opt, _fault_timeline=_all_up_ft(CFG))
    for got, want in (
        (forced.final_models, plain.final_models),
        (forced.history.objective, plain.history.objective),
    ):
        want = np.asarray(want)
        assert want.dtype == np.float64
        assert_ulps_of_scale(got, want, 4)
        with pytest.raises(AssertionError):
            assert_ulps_of_scale(want.astype(np.float32), want, 4)
    pn = numpy_backend.run_async(CFG, ds, f_opt)
    fn = numpy_backend.run_async(
        CFG, ds, f_opt, _fault_timeline=_all_up_ft(CFG)
    )
    assert np.array_equal(pn.final_models, fn.final_models)


def test_constant_latency_churn_is_round_clock_bitwise():
    """With constant latency every worker's k-th event IS round k, so the
    event realization must reproduce the round-clock churn chains
    bitwise (the ISSUE-17 degenerate gate)."""
    c = cfg(latency_model="constant", latency_mean=1.0, latency_tail=0.0,
            mttf=6.0, mttr=3.0, seed=5)
    _, tl = timeline_for(c)
    ft = timeline_for_config(c, _topo(c), tl.n_rounds)
    real = realize_event_faults(tl, ft)
    k = tl.local_step.astype(int)
    w = tl.worker.astype(int)
    assert np.array_equal(k, np.repeat(np.arange(tl.n_rounds), N))
    nu = ft.node_up if ft.node_up is not None else np.ones((T, N), bool)
    pu = ft.part_up if ft.part_up is not None else np.ones((T, N), bool)
    assert np.array_equal(real.fire, nu[k, w] & pu[k, w])
    assert np.array_equal(real.rejoin, ft.rejoin[k, w] & real.fire)


def test_event_churn_equals_participation_thinning(setup):
    """Node-outage masks and participation-thinning masks realize the
    same event program when the masks coincide: churn at mttf=1/q is
    event thinning at rate q (the iid-equivalence gate, stated on
    injected chains so it is exact, not statistical)."""
    ds, f_opt = setup
    topo = _topo(CFG)
    edges = _edge_list(topo)
    rng = np.random.default_rng(0)
    mask = rng.random((T, N)) < 0.75

    def ft(node, part):
        return FaultTimeline(
            horizon=T, directed=False, edge_index=edges,
            edge_up=np.ones((T, len(edges)), bool), node_up=node,
            rejoin=np.zeros((T, N), bool), part_up=part,
        )

    ones = np.ones((T, N), bool)
    a = run_async(CFG, ds, f_opt, _fault_timeline=ft(mask, ones))
    b = run_async(CFG, ds, f_opt, _fault_timeline=ft(ones, mask))
    assert np.array_equal(np.array(a.final_models), np.array(b.final_models))


# --- realization structure --------------------------------------------------


def test_realization_shapes_and_accounting():
    _, tl = timeline_for(CHURN)
    ft = timeline_for_config(CHURN, _topo(CHURN), tl.n_rounds)
    real = realize_event_faults(tl, ft)
    E = len(tl.worker)
    assert real.fire.shape == (E,)
    assert real.partner.shape == (E,)
    assert real.matched_fired.shape == (E,)
    # A fired event's realized partner is itself when the exchange was
    # degraded; matched_fired counts only live pairwise exchanges.
    assert not real.matched_fired[~real.fire].any()
    assert 0.0 < real.availability < 1.0
    # Every non-fired event is EITHER a crash loss or a thinning skip.
    assert real.n_inflight_lost + real.n_thinned == int((~real.fire).sum())
    up = all_up_realization(tl)
    assert up.fire.all() and up.availability == 1.0
    assert up.n_inflight_lost == 0


def test_comms_billed_only_for_fired_live_exchanges(setup):
    ds, f_opt = setup
    _, tl = timeline_for(CHURN)
    _, real, _ = event_faults_for(CHURN, _topo(CHURN), tl)
    d = ds.shard(0)[0].shape[1]  # bias column included
    r = run_async(CHURN, ds, f_opt)
    assert r.history.total_floats_transmitted == pytest.approx(
        2.0 * d * int(real.matched_fired.sum())
    )
    # Gradient tracking ships its tracker rows too: 4·d per exchange.
    gt = CHURN.replace(algorithm="gradient_tracking")
    rg = run_async(gt, ds, f_opt)
    assert rg.history.total_floats_transmitted == pytest.approx(
        4.0 * d * int(real.matched_fired.sum())
    )


# --- cross-backend parity under composed faults -----------------------------


@pytest.mark.parametrize("algorithm", ["dsgd", "gradient_tracking"])
def test_composed_faults_jax_numpy_parity(setup, algorithm):
    """Crash churn × participation thinning × rejoin, same injected batch
    schedule: ≤ 1e-12 f64 parity between the fused jax scan and the
    numpy per-event oracle."""
    ds, f_opt = setup
    c = CHURN.replace(algorithm=algorithm)
    sched = event_schedule(c, ds)
    rj = run_async(c, ds, f_opt, batch_schedule=sched)
    rn = numpy_backend.run_async(c, ds, f_opt, batch_schedule=sched)
    assert np.max(np.abs(np.array(rj.final_models) - rn.final_models)) < 1e-12
    assert np.max(
        np.abs(np.array(rj.history.objective) - rn.history.objective)
    ) < 1e-9
    assert rj.history.total_floats_transmitted == pytest.approx(
        rn.history.total_floats_transmitted
    )


def test_local_steps_fused_per_event_parity(setup):
    ds, f_opt = setup
    c = cfg(local_steps=2, algorithm="gradient_tracking",
            mttf=6.0, mttr=3.0, seed=9)
    sched = event_schedule(c, ds)
    rj = run_async(c, ds, f_opt, batch_schedule=sched)
    rn = numpy_backend.run_async(c, ds, f_opt, batch_schedule=sched)
    assert np.max(np.abs(np.array(rj.final_models) - rn.final_models)) < 1e-12


def test_neighbor_restart_rejoin_parity(setup):
    ds, f_opt = setup
    c = cfg(mttf=6.0, mttr=3.0, rejoin="neighbor_restart", seed=9)
    sched = event_schedule(c, ds)
    rj = run_async(c, ds, f_opt, batch_schedule=sched)
    rn = numpy_backend.run_async(c, ds, f_opt, batch_schedule=sched)
    assert np.max(np.abs(np.array(rj.final_models) - rn.final_models)) < 1e-12
    frozen = run_async(c.replace(rejoin="frozen"), ds, f_opt,
                       batch_schedule=sched)
    assert not np.array_equal(
        np.array(rj.final_models), np.array(frozen.final_models)
    )


# --- gradient tracking on the event clock -----------------------------------


def _tracking_residual(result):
    state = result.final_state
    return float(np.max(np.abs(
        np.asarray(state["y"]).mean(axis=0)
        - np.asarray(state["g_prev"]).mean(axis=0)
    )))


def test_gt_tracking_invariant_staleness_zero(setup):
    """At constant latency every read is fresh (staleness 0): the async
    tracker must satisfy the DIGing identity mean(y) == mean(g_prev)
    exactly — the correction is applied at the stale read, which here IS
    the current state."""
    ds, f_opt = setup
    c = cfg(algorithm="gradient_tracking", latency_model="constant",
            latency_mean=1.0, latency_tail=0.0)
    r = run_async(c, ds, f_opt, return_state=True)
    assert _tracking_residual(r) < 1e-12


def test_gt_tracking_invariant_under_composed_faults(setup):
    """The telescoping is mean-preserving through no-op crashes, degraded
    self-exchanges, and thinning — the identity holds at ANY staleness
    under the full fault composition, on both backends."""
    ds, f_opt = setup
    c = CHURN.replace(algorithm="gradient_tracking")
    r = run_async(c, ds, f_opt, return_state=True)
    assert _tracking_residual(r) < 1e-12
    rn = numpy_backend.run_async(c, ds, f_opt, return_state=True)
    assert _tracking_residual(rn) < 1e-12


# --- checkpoint / resume ----------------------------------------------------


@pytest.mark.parametrize("backend", ["jax", "numpy"])
def test_resume_mid_outage_bitwise(setup, tmp_path, backend):
    """Event-chunked checkpointing: drop every chunk after the earliest
    surviving one (the PR 3 truncated-chunk fallback) and resume INSIDE
    the churn realization — the replayed suffix must be bitwise the
    uninterrupted run, outages included."""
    ds, f_opt = setup
    c = cfg(mttf=6.0, mttr=3.0, seed=13)
    runner = run_async if backend == "jax" else numpy_backend.run_async
    ref = runner(c, ds, f_opt)
    opts = CheckpointOptions(str(tmp_path), every_evals=1, resume=False)
    runner(c, ds, f_opt, checkpoint=opts)
    ck = RunCheckpointer(opts)
    chunks = ck.completed_chunks()
    assert len(chunks) > 1
    for chunk in chunks[1:]:
        shutil.rmtree(ck._step_dir(chunk), ignore_errors=True)
    # The resumed suffix really does contain outage events.
    _, tl = timeline_for(c)
    _, real, _ = event_faults_for(c, _topo(c), tl)
    start_event = chunks[0] * c.eval_every * N
    assert not real.fire[start_event:].all()
    resumed = runner(c, ds, f_opt, checkpoint=CheckpointOptions(
        str(tmp_path), every_evals=1, resume=True,
    ))
    assert np.array_equal(
        np.array(ref.final_models), np.array(resumed.final_models)
    )
    assert np.array_equal(
        np.array(ref.history.objective), np.array(resumed.history.objective)
    )


def test_resume_rejects_changed_horizon(setup, tmp_path):
    """The event schedule is horizon-global (events interleave across
    rounds by completion time), so n_iterations is NOT resumable on the
    event clock — unlike the round-clock checkpoint sidecar."""
    ds, f_opt = setup
    run_async(CFG.replace(n_iterations=20), ds, f_opt,
              checkpoint=CheckpointOptions(str(tmp_path), every_evals=1,
                                           resume=False))
    with pytest.raises(ValueError, match="n_iterations"):
        run_async(CFG, ds, f_opt, checkpoint=CheckpointOptions(
            str(tmp_path), every_evals=1, resume=True,
        ))


def test_checkpoint_excludes_telemetry_and_cursor(setup, tmp_path):
    ds, f_opt = setup
    with pytest.raises(ValueError, match="not checkpointed"):
        run_async(CFG.replace(telemetry=True), ds, f_opt,
                  checkpoint=CheckpointOptions(str(tmp_path)))
    with pytest.raises(ValueError, match="continuation cursor"):
        run_async(CFG, ds, f_opt, start_event=8,
                  checkpoint=CheckpointOptions(str(tmp_path)))


# --- telemetry on the event clock -------------------------------------------


def test_telemetry_trace_rides_scan_bitwise(setup):
    """telemetry=True must not perturb the trajectory (the trace rides
    the scan's per-eval outputs), and the trace carries the event-axis
    health facts: per-worker fire fractions and live-edge rates."""
    ds, f_opt = setup
    off = run_async(CHURN, ds, f_opt)
    on = run_async(CHURN.replace(telemetry=True), ds, f_opt)
    assert np.array_equal(
        np.array(off.final_models), np.array(on.final_models)
    )
    tr = on.history.trace
    n_rows = T // CHURN.eval_every
    assert np.asarray(tr["param_norm"]).shape == (n_rows, N)
    assert np.asarray(tr["grad_norm"]).shape == (n_rows, N)
    assert np.asarray(tr["nodes_up"]).shape == (n_rows, N)
    assert np.asarray(tr["live_edges"]).shape == (n_rows,)
    # Availability under churn+thinning: fire fractions strictly < 1
    # somewhere, and live-edge rates reflect only fired live exchanges.
    assert tr["nodes_up"].min() < 1.0
    _, tl = timeline_for(CHURN)
    _, real, _ = event_faults_for(CHURN, _topo(CHURN), tl)
    fired = real.matched_fired.reshape(n_rows, CHURN.eval_every * N)
    assert np.allclose(
        np.asarray(tr["live_edges"]),
        2.0 * fired.sum(axis=1) / CHURN.eval_every,
    )
    # Backend parity of the trace itself.
    tn = numpy_backend.run_async(
        CHURN.replace(telemetry=True), ds, f_opt,
        batch_schedule=event_schedule(CHURN, ds),
    ).history.trace
    for key in ("nodes_up", "live_edges", "clip_frac"):
        assert np.array_equal(np.asarray(tr[key]), np.asarray(tn[key])), key


def test_async_summary_fault_block():
    from distributed_optimization_tpu.telemetry import async_summary

    s = async_summary(CHURN)
    fb = s["faults"]
    assert 0.0 < fb["availability"] < 1.0
    assert fb["n_inflight_lost"] > 0
    assert fb["matched_fired"] <= s["matched_events"]
    assert async_summary(CFG)["faults"] is None


def test_incident_context_event_forensics():
    from distributed_optimization_tpu.observability.monitors import (
        fault_context,
    )

    ctx = fault_context(CHURN, 20)["async"]
    assert ctx["onset_event"] == 20 * N
    assert ctx["n_inflight_lost_window"] > 0
    assert 0.0 < ctx["window_availability"] < 1.0
    assert isinstance(ctx["crashed_workers_at_onset"], list)
    healthy = fault_context(CFG, 20)["async"]
    assert "n_inflight_lost_window" not in healthy


# --- validity lockstep ------------------------------------------------------


def test_validity_cross_check_async_cells_zero_divergence():
    """Every deleted rejection rule updated scenarios/validity.py in
    lockstep: the table and ExperimentConfig construction agree on the
    full async fault × schedule × τ × telemetry cross."""
    import itertools

    from distributed_optimization_tpu.scenarios.validity import cross_check

    for algo, sched, tau, tele, mttf, rate in itertools.product(
        ["dsgd", "gradient_tracking", "extra"],
        ["synchronous", "one_peer", "round_robin"],
        [1, 2], [False, True], [0.0, 6.0], [0.7, 1.0],
    ):
        cell = dict(
            execution="async", latency_model="lognormal",
            latency_mean=1.0, latency_tail=0.5, algorithm=algo,
            gossip_schedule=sched, local_steps=tau, telemetry=tele,
            mttf=mttf, mttr=3.0 if mttf else 0.0,
            participation_rate=rate,
        )
        assert cross_check(cell) is None, cell
