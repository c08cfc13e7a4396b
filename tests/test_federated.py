"""Federated-scale execution (ISSUE 8): local steps, client sampling, and
the matrix-free neighbor-table path.

Four contracts are pinned here:

1. **Reductions** — ``local_steps=1`` and ``participation_rate=1.0`` are
   BITWISE the historical programs (no extra ops, no fault machinery), and
   the in-test hand-rolled recursions confirm the τ>1 semantics.
2. **Oracle parity** — sampled participation (composed with churn and the
   Byzantine layer) and τ>1 local steps agree between the jax backend and
   the independent numpy twins ≤ 1e-12 in float64 under injected batch
   schedules.
3. **Matrix-free equivalence** — neighbor-table topologies realize the
   bit-identical graph as their dense twins (the ER sampler consumes the
   same Generator stream), the gather mixing/fault forms match the dense
   trajectories ≤ 1e-12, and the k_max blow-up guards reject quadratic
   tables loudly.
4. **Serving-cache semantics** — the new fields are structural: configs
   differing in them hash apart (deliberate cache MISS, never a cohort
   collision).
"""

import numpy as np
import pytest

from distributed_optimization_tpu.config import (
    MATRIX_FREE_AUTO_N,
    NEIGHBOR_TOPOLOGIES,
    SWEEPABLE_FIELDS,
    ExperimentConfig,
)
from distributed_optimization_tpu.backends import jax_backend, numpy_backend
from distributed_optimization_tpu.utils.data import generate_synthetic_dataset
from distributed_optimization_tpu.utils.oracle import compute_reference_optimum

N = 8
T = 40
BASE = dict(
    n_workers=N, n_samples=200, n_features=10, n_informative_features=6,
    problem_type="quadratic", n_iterations=T, topology="ring",
    algorithm="dsgd", local_batch_size=8, dtype="float64", eval_every=10,
)


def make_cfg(**kw):
    return ExperimentConfig(**{**BASE, **kw})


@pytest.fixture(scope="module")
def problem():
    cfg = make_cfg()
    ds = generate_synthetic_dataset(cfg)
    _, f_opt = compute_reference_optimum(ds, cfg.reg_param)
    rng = np.random.default_rng(0)
    sizes = [len(i) for i in ds.shard_indices]
    sched = np.stack([
        [rng.choice(sizes[i], size=BASE["local_batch_size"], replace=False)
         for i in range(N)]
        for _ in range(T)
    ])
    return ds, f_opt, sched


def run_jax(cfg, problem, **kw):
    ds, f_opt, sched = problem
    return jax_backend.run(
        cfg, ds, f_opt, batch_schedule=sched, use_mesh=False, **kw
    )


def run_np(cfg, problem):
    ds, f_opt, sched = problem
    return numpy_backend.run(cfg, ds, f_opt, batch_schedule=sched)


# ------------------------------------------------------------- reductions


@pytest.mark.parametrize("algorithm", ["dsgd", "gradient_tracking"])
@pytest.mark.parametrize("backend", ["jax", "numpy"])
def test_tau1_bitwise_reduces_to_current(problem, algorithm, backend):
    """local_steps=1 is the historical trajectory, bitwise, both backends."""
    cfg0 = make_cfg(algorithm=algorithm, backend=backend)
    cfg1 = cfg0.replace(local_steps=1)
    run = run_jax if backend == "jax" else run_np
    r0, r1 = run(cfg0, problem), run(cfg1, problem)
    np.testing.assert_array_equal(r0.final_models, r1.final_models)
    np.testing.assert_array_equal(r0.history.objective, r1.history.objective)


def test_participation_one_bitwise_and_no_fault_machinery(problem):
    """participation_rate=1.0 traces the identical no-sampling program."""
    from distributed_optimization_tpu.algorithms import get_algorithm
    from distributed_optimization_tpu.parallel import build_topology

    cfg = make_cfg(participation_rate=1.0)
    r0, r1 = run_jax(make_cfg(), problem), run_jax(cfg, problem)
    np.testing.assert_array_equal(r0.final_models, r1.final_models)
    topo = build_topology("ring", N)
    assert jax_backend._build_faulty(
        cfg, get_algorithm("dsgd"), topo, T
    ) is None


def test_dsgd_local_steps_manual_recursion(problem):
    """τ=2 D-SGD IS: x ← W x − η g(x); x ← x − η g(x) — checked against a
    hand-rolled float64 recursion (independent of both backends)."""
    ds, f_opt, sched = problem
    from distributed_optimization_tpu.parallel import build_topology
    from distributed_optimization_tpu.ops import losses_np

    cfg = make_cfg(local_steps=2, backend="numpy")
    W = build_topology("ring", N).mixing_matrix
    shards = [ds.shard(i) for i in range(N)]
    grad = losses_np.GRADIENTS["quadratic"]

    def g(params, t):
        out = np.zeros((N, 10 + 1))
        for i in range(N):
            Xi, yi = shards[i]
            idx = sched[t, i]
            out[i] = grad(params[i], Xi[idx], yi[idx], cfg.reg_param)
        return out

    x = np.zeros((N, 10 + 1))
    for t in range(T):
        eta = cfg.learning_rate_eta0 / np.sqrt(t + 1.0)
        x = W @ x - eta * g(x, t)   # round's gossip-fused step 0
        x = x - eta * g(x, t)       # local step 1 (same injected batch)
    r = run_np(cfg, problem)
    np.testing.assert_allclose(r.final_models, x, atol=1e-13, rtol=0)


def test_gt_local_steps_preserve_tracking_invariant(problem):
    """mean(y_t) == mean(g_prev_t) for every τ — the local descents touch
    only the model, never the tracker recursion."""
    cfg = make_cfg(algorithm="gradient_tracking", local_steps=3)
    r = run_jax(cfg, problem, return_state=True)
    y, g_prev = r.final_state["y"], r.final_state["g_prev"]
    np.testing.assert_allclose(
        y.mean(axis=0), g_prev.mean(axis=0), atol=1e-12, rtol=0
    )


# ----------------------------------------------------------- oracle parity


@pytest.mark.parametrize("algorithm", ["dsgd", "gradient_tracking"])
@pytest.mark.parametrize("tau", [2, 4])
def test_local_steps_jax_vs_numpy(problem, algorithm, tau):
    cj = make_cfg(algorithm=algorithm, local_steps=tau, backend="jax")
    cn = cj.replace(backend="numpy")
    rj, rn = run_jax(cj, problem), run_np(cn, problem)
    np.testing.assert_allclose(
        rj.final_models, rn.final_models, atol=1e-12, rtol=0
    )
    # Early-iteration gaps are O(10^3), so the history check is relative
    # (the 1e-12 f64 convention, scale-honest).
    np.testing.assert_allclose(
        rj.history.objective, rn.history.objective, rtol=1e-12, atol=1e-12
    )


def test_local_steps_fori_loop_path(problem):
    """τ−1 > LOCAL_UNROLL_MAX routes the jax body through lax.fori_loop;
    the numpy twin always Python-loops — same trajectory either way."""
    from distributed_optimization_tpu.algorithms.base import LOCAL_UNROLL_MAX

    tau = LOCAL_UNROLL_MAX + 3
    cfg = make_cfg(local_steps=tau, n_iterations=10, eval_every=10)
    rj = run_jax(cfg, problem)
    rn = run_np(cfg.replace(backend="numpy"), problem)
    dev = np.max(np.abs(rj.final_models[:, : T] - rn.final_models[:, : T]))
    assert dev < 1e-11, dev


@pytest.mark.parametrize("algorithm", ["dsgd", "gradient_tracking"])
def test_participation_jax_vs_numpy_under_churn(problem, algorithm):
    """Sampled participation composed with crash-recovery churn: ≤ 1e-12
    f64 parity against the independent numpy fault twins."""
    cj = make_cfg(
        algorithm=algorithm, participation_rate=0.6, mttf=8.0, mttr=3.0,
        backend="jax",
    )
    rj, rn = run_jax(cj, problem), run_np(cj.replace(backend="numpy"), problem)
    np.testing.assert_allclose(
        rj.final_models, rn.final_models, atol=1e-12, rtol=0
    )
    # Realized comms accounting agrees exactly (same realized edge count).
    assert rj.history.total_floats_transmitted == pytest.approx(
        rn.history.total_floats_transmitted
    )


def test_participation_composes_with_byzantine(problem):
    """Client sampling under attack: the screening rule runs over the
    sampled subgraph (realized_adjacency composition), matching the numpy
    twin ≤ 1e-12."""
    cj = make_cfg(
        participation_rate=0.7, attack="sign_flip", n_byzantine=1,
        aggregation="trimmed_mean", robust_b=1, partition="shuffled",
        backend="jax",
    )
    rj, rn = run_jax(cj, problem), run_np(cj.replace(backend="numpy"), problem)
    np.testing.assert_allclose(
        rj.final_models, rn.final_models, atol=1e-12, rtol=0
    )


def test_batch_replicas_match_sequential(problem):
    """run_batch with participation + local steps: replica r ==
    run(seed=seeds[r]) (the ISSUE-4 contract extended to the new regime)."""
    ds, f_opt, _ = problem
    cfg = make_cfg(
        participation_rate=0.5, local_steps=2, mttf=8.0, mttr=3.0,
        replicas=3,
    )
    br = jax_backend.run_batch(cfg, ds, f_opt)
    for r, s in enumerate(br.seeds):
        seq = jax_backend.run(
            cfg.replace(seed=s, replicas=1), ds, f_opt, use_mesh=False
        )
        np.testing.assert_allclose(
            br.results[r].final_models, seq.final_models,
            atol=1e-12, rtol=0,
        )


def test_batch_continuation_with_participation(problem):
    """The participation timeline is prefix-stable in the horizon: a batch
    split in two at t0 reproduces the one-shot run exactly."""
    ds, f_opt, _ = problem
    cfg = make_cfg(participation_rate=0.5, replicas=2)
    full = jax_backend.run_batch(cfg, ds, f_opt)
    half = cfg.replace(n_iterations=T // 2)
    first = jax_backend.run_batch(half, ds, f_opt)
    second = jax_backend.run_batch(
        half, ds, f_opt, state0=first.final_states, t0=T // 2
    )
    np.testing.assert_array_equal(
        full.final_states["x"], second.final_states["x"]
    )


# --------------------------------------------------------- matrix-free path


@pytest.mark.parametrize("name", NEIGHBOR_TOPOLOGIES)
def test_neighbor_tables_match_dense(name):
    """Matrix-free builds carry the bit-identical table ``neighbor_table``
    derives from the dense adjacency — ER included (same Generator
    stream)."""
    from distributed_optimization_tpu.parallel.topology import (
        build_topology, neighbor_table,
    )

    n = 16
    kw = dict(erdos_renyi_p=0.3, seed=7) if name == "erdos_renyi" else {}
    d = build_topology(name, n, **kw)
    m = build_topology(name, n, impl="neighbor", **kw)
    di, dm = neighbor_table(d.adjacency)
    np.testing.assert_array_equal(di, m.nbr_idx)
    np.testing.assert_array_equal(dm, m.nbr_mask)
    np.testing.assert_array_equal(d.degrees, m.degrees)
    assert m.is_matrix_free and m.adjacency is None and m.mixing_matrix is None
    assert abs(d.spectral_gap - m.spectral_gap) < 1e-6
    assert d.floats_per_iteration == m.floats_per_iteration


def test_gather_mixing_matches_dense():
    from distributed_optimization_tpu.parallel.topology import build_topology
    from distributed_optimization_tpu.ops.mixing import make_mixing_op
    import jax.numpy as jnp

    topo = build_topology("erdos_renyi", 12, erdos_renyi_p=0.4, seed=3)
    dense = make_mixing_op(topo, impl="dense", dtype=jnp.float32)
    gather = make_mixing_op(topo, impl="gather", dtype=jnp.float32)
    x = jnp.asarray(
        np.random.default_rng(1).standard_normal((12, 5)), dtype=jnp.float32
    )
    np.testing.assert_allclose(
        np.asarray(dense.apply(x)), np.asarray(gather.apply(x)), atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(dense.neighbor_sum(x)), np.asarray(gather.neighbor_sum(x)),
        atol=1e-6,
    )


def test_mixing_auto_routes_gather():
    """auto → gather for matrix-free graphs and above the measured dense
    threshold; stencil still wins where the graph embeds as shifts."""
    from distributed_optimization_tpu.parallel.topology import build_topology
    from distributed_optimization_tpu.ops.mixing import make_mixing_op

    er_free = build_topology("erdos_renyi", 16, seed=1, impl="neighbor")
    assert make_mixing_op(er_free).impl == "gather"
    ring_free = build_topology("ring", 16, impl="neighbor")
    assert make_mixing_op(ring_free).impl == "stencil"
    er_small = build_topology("erdos_renyi", 16, seed=1)
    assert make_mixing_op(er_small).impl == "dense"
    chain_big = build_topology("chain", MATRIX_FREE_AUTO_N)
    assert make_mixing_op(chain_big).impl == "gather"


def test_dense_mixing_rejected_on_matrix_free():
    from distributed_optimization_tpu.parallel.topology import build_topology
    from distributed_optimization_tpu.ops.mixing import make_mixing_op

    topo = build_topology("erdos_renyi", 16, seed=1, impl="neighbor")
    with pytest.raises(ValueError, match="matrix-free"):
        make_mixing_op(topo, impl="dense")


@pytest.mark.parametrize("topology", ["erdos_renyi", "chain", "ring"])
def test_neighbor_trajectory_matches_dense(problem, topology):
    cd = make_cfg(topology=topology, topology_impl="dense")
    cn = make_cfg(topology=topology, topology_impl="neighbor")
    rd, rn = run_jax(cd, problem), run_jax(cn, problem)
    np.testing.assert_allclose(
        rd.final_models, rn.final_models, atol=1e-12, rtol=0
    )


def test_neighbor_faulty_trajectory_matches_dense(problem):
    """Gather-form node-process faults (participation + churn +
    neighbor_restart) realize the identical graphs and trajectories as the
    dense fault machinery."""
    kw = dict(
        topology="erdos_renyi", participation_rate=0.5, mttf=8.0, mttr=3.0,
        rejoin="neighbor_restart",
    )
    rd = run_jax(make_cfg(topology_impl="dense", **kw), problem)
    rn = run_jax(make_cfg(topology_impl="neighbor", **kw), problem)
    np.testing.assert_allclose(
        rd.final_models, rn.final_models, atol=1e-12, rtol=0
    )
    assert rd.history.total_floats_transmitted == pytest.approx(
        rn.history.total_floats_transmitted
    )


def test_neighbor_batch_replicas(problem):
    """Matrix-free topologies batch: replica r == sequential run."""
    ds, f_opt, _ = problem
    cfg = make_cfg(
        topology="erdos_renyi", topology_impl="neighbor",
        participation_rate=0.6, replicas=2,
    )
    br = jax_backend.run_batch(cfg, ds, f_opt)
    for r, s in enumerate(br.seeds):
        # The batch contract pins the random graph to the BASE config's
        # resolved topology seed (the graph is structural).
        seq = jax_backend.run(
            cfg.replace(
                seed=s, replicas=1,
                topology_seed=cfg.resolved_topology_seed(),
            ),
            ds, f_opt, use_mesh=False,
        )
        np.testing.assert_allclose(
            br.results[r].final_models, seq.final_models, atol=1e-12, rtol=0
        )


def test_kmax_blowup_guards():
    from distributed_optimization_tpu.parallel.topology import (
        build_neighbor_topology,
    )

    with pytest.raises(ValueError, match="dense"):
        build_neighbor_topology("fully_connected", 64)
    with pytest.raises(ValueError, match="dense"):
        build_neighbor_topology("star", 64)
    # A dense ER draw whose k_max reaches N−1 is routed back too.
    with pytest.raises(ValueError, match="degree bound"):
        build_neighbor_topology("erdos_renyi", 8, erdos_renyi_p=0.999, seed=0)


# ----------------------------------------------- config / serving semantics


def test_rejections():
    with pytest.raises(ValueError, match="local_steps"):
        make_cfg(algorithm="extra", local_steps=2)
    with pytest.raises(ValueError, match="local_steps"):
        make_cfg(local_steps=0)
    with pytest.raises(ValueError, match="compressed"):
        make_cfg(local_steps=2, compression="top_k", compression_k=3)
    with pytest.raises(ValueError, match="cpp"):
        make_cfg(local_steps=2, backend="cpp")
    with pytest.raises(ValueError, match="participation_rate"):
        make_cfg(participation_rate=0.0)
    with pytest.raises(ValueError, match="centralized|peer"):
        make_cfg(algorithm="centralized", participation_rate=0.5)
    with pytest.raises(ValueError, match="synchronous"):
        make_cfg(participation_rate=0.5, gossip_schedule="one_peer")
    with pytest.raises(ValueError, match="fully_connected|quadratic"):
        make_cfg(topology="fully_connected", topology_impl="neighbor")
    with pytest.raises(ValueError, match="jax"):
        make_cfg(topology_impl="neighbor", backend="numpy")
    # ISSUE-9 satellites: Byzantine screening and per-edge fault processes
    # are ACCEPTED on the matrix-free path now (gather form / [horizon, E]
    # chains through the slot table — tests/test_matrix_free_faults.py);
    # only the [N, N]-materializing robust execution forms stay rejected.
    make_cfg(
        topology_impl="neighbor", attack="sign_flip", n_byzantine=1,
        aggregation="trimmed_mean", robust_b=1,
    )
    make_cfg(topology_impl="neighbor", edge_drop_prob=0.1)
    with pytest.raises(ValueError, match="gather form"):
        make_cfg(
            topology_impl="neighbor", aggregation="trimmed_mean",
            robust_b=1, robust_impl="dense",
        )
    with pytest.raises(ValueError, match="matrices|mixing"):
        make_cfg(topology_impl="neighbor", mixing_impl="dense")


def test_federated_fields_are_structural():
    """The satellite contract: local_steps / participation_rate /
    topology_impl are structural — never sweepable, always hashed — so
    serving cohorts MISS across them instead of colliding."""
    c0 = make_cfg()
    assert "local_steps" not in SWEEPABLE_FIELDS
    assert "participation_rate" not in SWEEPABLE_FIELDS
    h0 = c0.structural_hash()
    assert h0 != c0.replace(local_steps=2).structural_hash()
    assert h0 != c0.replace(participation_rate=0.5).structural_hash()
    assert h0 != c0.replace(participation_rate=0.999).structural_hash()
    # Sweepable/seed variation still coheres into one cohort.
    assert h0 == c0.replace(seed=999, learning_rate_eta0=0.5).structural_hash()
    # The RESOLVED representation is hashed: explicit 'neighbor' and
    # auto-above-threshold name the same compiled program.
    big = dict(BASE, n_workers=MATRIX_FREE_AUTO_N)
    assert (
        ExperimentConfig(**big).resolved_topology_impl() == "neighbor"
    )
    assert (
        ExperimentConfig(**big).structural_hash()
        == ExperimentConfig(**big, topology_impl="neighbor").structural_hash()
    )
    # ... and below the threshold dense vs neighbor are distinct programs.
    assert c0.structural_hash() != c0.replace(
        topology_impl="neighbor"
    ).structural_hash()


def test_realized_bhat_matrix_free_with_node_faults():
    """health_summary's B̂ rebuild must not touch the dense adjacency on a
    matrix-free run (regression: windowed_connectivity dereferenced
    topo.adjacency.shape)."""
    from distributed_optimization_tpu.telemetry import realized_bhat

    cfg = make_cfg(
        topology_impl="neighbor", participation_rate=0.5, mttf=8.0, mttr=3.0,
    )
    out = realized_bhat(cfg)
    assert out is not None and out["horizon"] == T
    # At rate 0.5 over a ring some window is needed; B̂ is either a finite
    # int or None (disconnected union) — both are valid outputs, crashing
    # is not.
    assert out["bhat"] is None or out["bhat"] >= 1


def test_mixing_auto_keeps_dense_for_high_degree_graphs():
    """The large-N auto-gather rule applies the neighbor-table degree
    bound: star (k_max = N−1) and dense ER keep the dense contraction
    instead of allocating a near-quadratic gather (regression)."""
    from distributed_optimization_tpu.parallel.topology import build_topology
    from distributed_optimization_tpu.ops.mixing import make_mixing_op

    star = build_topology("star", MATRIX_FREE_AUTO_N)
    assert make_mixing_op(star).impl == "dense"


def test_batch_edge_sweep_resolution_is_consistent():
    """The per-replica configs of a swept edge_drop axis resolve to the
    SAME representation the base config resolves to — since ISSUE-9 the
    neighbor path carries per-edge fault processes, so the edge sweep no
    longer forks replicas onto a different program than their sequential
    twins (the invariant _run_batch's resolution consult protects)."""
    big = dict(BASE, n_workers=MATRIX_FREE_AUTO_N, topology="erdos_renyi")
    base_cfg = ExperimentConfig(**big)
    assert base_cfg.resolved_topology_impl() == "neighbor"
    rep = base_cfg.replace(edge_drop_prob=0.05)  # what each replica runs
    assert rep.resolved_topology_impl() == "neighbor"


def test_auto_stays_dense_for_dense_only_features():
    big = dict(BASE, n_workers=MATRIX_FREE_AUTO_N)
    # Edge-fault processes are matrix-free-capable since ISSUE-9: auto
    # keeps the neighbor route (the N >= 10k bursty-link headroom).
    assert ExperimentConfig(
        **big, edge_drop_prob=0.1
    ).resolved_topology_impl() == "neighbor"
    # Byzantine screening runs matrix-free but stays an explicit opt-in.
    assert ExperimentConfig(
        **big, aggregation="trimmed_mean", robust_b=1,
    ).resolved_topology_impl() == "dense"
    assert ExperimentConfig(
        **big, backend="numpy"
    ).resolved_topology_impl() == "dense"
    assert ExperimentConfig(
        **dict(big, topology="fully_connected")
    ).resolved_topology_impl() == "dense"


# ------------------------------------------------------------- observability


def test_participation_telemetry_and_report(problem):
    from distributed_optimization_tpu.telemetry import health_summary
    from distributed_optimization_tpu.reporting import format_report
    from distributed_optimization_tpu.metrics import summarize_run

    cfg = make_cfg(participation_rate=0.5, telemetry=True)
    r = run_jax(cfg, problem)
    nodes = np.asarray(r.history.trace["nodes_up"])
    assert 0.25 < nodes.mean() < 0.75  # realized fraction tracks the rate
    h = health_summary(cfg, r.history)
    assert h["participation"]["rate"] == 0.5
    assert h["participation"]["realized_frac_mean"] == pytest.approx(
        nodes.mean()
    )

    class Rec:
        label = "federated"
        skipped_reason = None
        replicate_stats = None
        health = h
        summary = summarize_run("federated", r.history, 0.08, N)

    report = format_report([Rec()], cfg, 0.0)
    assert "participation" in report
    assert "target 50%" in report


def test_local_steps_comms_accounting(problem):
    from distributed_optimization_tpu.telemetry import comms_summary

    cfg = make_cfg(local_steps=4)
    r = run_jax(cfg, problem)
    comms = comms_summary(cfg, r.history)
    assert comms["local_steps"] == 4
    assert comms["floats_per_gradient_step"] == pytest.approx(
        comms["floats_per_iteration_mean"] / 4
    )
    # Per-round analytic floats are UNCHANGED by τ (the whole point):
    r1 = run_jax(make_cfg(), problem)
    assert r.history.total_floats_transmitted == pytest.approx(
        r1.history.total_floats_transmitted
    )
