"""Failure-injection tests (SURVEY.md §5.3 build target).

Properties: every realized W_t stays symmetric + doubly stochastic (average
preservation under faults); drop_prob=0 reduces exactly to the static MH
matrix; realizations are reproducible from (seed, t); D-SGD still converges
under moderate edge loss; the realized comms accounting is < the fault-free
closed form.
"""

import jax
import jax.numpy as jnp
from jax import enable_x64
import numpy as np
import pytest

from distributed_optimization_tpu.backends import jax_backend, numpy_backend
from distributed_optimization_tpu.config import ExperimentConfig
from distributed_optimization_tpu.parallel import build_topology
from distributed_optimization_tpu.parallel.faults import (
    make_faulty_mixing,
    metropolis_hastings_weights,
    sample_surviving_adjacency,
)
from distributed_optimization_tpu.utils.data import generate_synthetic_dataset
from distributed_optimization_tpu.utils.oracle import compute_reference_optimum


@pytest.mark.parametrize("topology", ["ring", "grid", "fully_connected",
                                      "erdos_renyi"])
def test_realized_W_is_symmetric_doubly_stochastic(topology):
    topo = build_topology(topology, 9, erdos_renyi_p=0.5, seed=1)
    A = jnp.asarray(topo.adjacency, dtype=jnp.float32)
    for t in range(5):
        key = jax.random.fold_in(jax.random.key(7), t)
        At = sample_surviving_adjacency(key, A, 0.4)
        W = np.asarray(metropolis_hastings_weights(At))
        np.testing.assert_allclose(W, W.T, atol=1e-6)
        np.testing.assert_allclose(W.sum(axis=1), 1.0, atol=1e-5)
        assert np.all(W >= -1e-6)
        # Surviving edges are a subset of the base adjacency.
        assert np.all(np.asarray(At) <= np.asarray(A))


def test_zero_drop_prob_matches_static_matrix():
    topo = build_topology("ring", 8)
    fm = make_faulty_mixing(topo, 0.0, seed=3)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((8, 4)),
                    dtype=jnp.float32)
    got = np.asarray(fm.mix(jnp.asarray(0), x))
    want = topo.mixing_matrix @ np.asarray(x, dtype=np.float64)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert float(fm.realized_degree_sum(jnp.asarray(0))) == topo.degrees.sum()


def test_fault_realizations_reproducible_and_time_varying():
    topo = build_topology("fully_connected", 10)
    fm = make_faulty_mixing(topo, 0.5, seed=11)
    x = jnp.ones((10, 3), dtype=jnp.float32)
    a = np.asarray(fm.mix(jnp.asarray(4), x))
    b = np.asarray(fm.mix(jnp.asarray(4), x))
    np.testing.assert_array_equal(a, b)  # same t -> same realization
    sums = {float(fm.realized_degree_sum(jnp.asarray(t))) for t in range(8)}
    assert len(sums) > 1  # realizations vary over time


def test_mean_preserved_under_faults():
    # W_t doubly stochastic => the network average is invariant through mixing.
    topo = build_topology("grid", 9)
    fm = make_faulty_mixing(topo, 0.3, seed=5)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((9, 6)),
                    dtype=jnp.float32)
    for t in range(4):
        mixed = fm.mix(jnp.asarray(t), x)
        np.testing.assert_allclose(
            np.asarray(jnp.mean(mixed, axis=0)),
            np.asarray(jnp.mean(x, axis=0)),
            atol=1e-5,
        )


CFG = ExperimentConfig(
    n_workers=9, n_samples=360, n_features=10, n_informative_features=6,
    n_iterations=600, local_batch_size=8, problem_type="quadratic",
    algorithm="dsgd", topology="ring", eval_every=50,
)


def test_dsgd_converges_under_faults_and_floats_accounting():
    ds = generate_synthetic_dataset(CFG)
    _, f_opt = compute_reference_optimum(ds, CFG.reg_param)
    clean = jax_backend.run(CFG, ds, f_opt)
    faulty = jax_backend.run(CFG.replace(edge_drop_prob=0.3), ds, f_opt)
    # Still optimizing (gap shrinks substantially from its start).
    assert faulty.history.objective[-1] < 0.2 * faulty.history.objective[0]
    # Realized communication < fault-free closed form, > half at p=0.3.
    clean_floats = clean.history.total_floats_transmitted
    assert faulty.history.total_floats_transmitted < clean_floats
    assert faulty.history.total_floats_transmitted > 0.5 * clean_floats


def test_numpy_backend_runs_synchronous_faults():
    # Synchronous failure injection became oracle-supported with the
    # fault-timeline refactor; matching schedules stay jax-only.
    ds = generate_synthetic_dataset(CFG)
    _, f_opt = compute_reference_optimum(ds, CFG.reg_param)
    r = numpy_backend.run(CFG.replace(edge_drop_prob=0.3,
                                      backend="numpy"), ds, f_opt)
    assert r.history.objective[-1] < 0.2 * r.history.objective[0]
    with pytest.raises(ValueError, match="jax-backend capability"):
        numpy_backend.run(CFG.replace(gossip_schedule="one_peer"), ds, 0.0)


def test_straggler_adjacency_and_mean_preservation():
    topo = build_topology("fully_connected", 10)
    fm = make_faulty_mixing(topo, 0.0, seed=4, straggler_prob=0.4)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((10, 3)),
                    dtype=jnp.float32)
    for t in range(4):
        m = np.asarray(fm.active(jnp.asarray(t)))
        assert set(np.unique(m)).issubset({0.0, 1.0})
        # Straggler exchanges nothing: its mixing row is identity.
        mixed = np.asarray(fm.mix(jnp.asarray(t), x))
        frozen = m == 0.0
        np.testing.assert_allclose(
            mixed[frozen], np.asarray(x)[frozen], atol=1e-6
        )
        # Doubly stochastic every realization: average preserved.
        np.testing.assert_allclose(mixed.mean(0), np.asarray(x).mean(0),
                                   atol=1e-5)


def test_straggler_rows_frozen_in_backend():
    from distributed_optimization_tpu.parallel.faults import make_faulty_mixing

    cfg = CFG.replace(straggler_prob=0.5, n_iterations=1, eval_every=1)
    ds = generate_synthetic_dataset(cfg)
    r = jax_backend.run(cfg, ds, 0.0)
    topo = build_topology("ring", cfg.n_workers)
    fm = make_faulty_mixing(topo, 0.0, seed=cfg.seed, straggler_prob=0.5)
    m = np.asarray(fm.active(jnp.asarray(0)))
    # x0 = 0: stragglers must still be exactly zero, active rows moved.
    assert np.all(r.final_models[m == 0.0] == 0.0)
    if (m == 1.0).any():
        assert np.all(np.abs(r.final_models[m == 1.0]).sum(axis=1) > 0)


def test_dsgd_converges_under_stragglers():
    ds = generate_synthetic_dataset(CFG)
    _, f_opt = compute_reference_optimum(ds, CFG.reg_param)
    clean = jax_backend.run(CFG, ds, f_opt)
    lazy = jax_backend.run(CFG.replace(straggler_prob=0.3), ds, f_opt)
    assert lazy.history.objective[-1] < 0.2 * lazy.history.objective[0]
    # Stragglers reduce realized communication: (1-q)^2 per edge ≈ 0.49.
    assert (
        lazy.history.total_floats_transmitted
        < 0.7 * clean.history.total_floats_transmitted
    )


def test_straggler_rejected_for_centralized():
    ds = generate_synthetic_dataset(CFG)
    with pytest.raises(ValueError, match="decentralized"):
        jax_backend.run(
            CFG.replace(algorithm="centralized", straggler_prob=0.2), ds, 0.0
        )
    with pytest.raises(ValueError, match="decentralized"):
        numpy_backend.run(
            CFG.replace(algorithm="centralized", straggler_prob=0.2), ds, 0.0
        )
    with pytest.raises(ValueError):
        ExperimentConfig(straggler_prob=1.0)


def test_jax_numpy_fault_parity_iid():
    """Shared fault schedule + independent mask/weight math twins must
    agree on float64 trajectories to ~1e-12 (ISSUE 2 acceptance)."""
    cfg = CFG.replace(
        n_iterations=40, eval_every=4, dtype="float64",
        edge_drop_prob=0.3, straggler_prob=0.2,
    )
    ds = generate_synthetic_dataset(cfg)
    _, f_opt = compute_reference_optimum(ds, cfg.reg_param)
    sched = _fault_batch_schedule(ds, cfg)
    rj = jax_backend.run(cfg, ds, f_opt, batch_schedule=sched)
    rn = numpy_backend.run(cfg, ds, f_opt, batch_schedule=sched)
    assert np.abs(rj.final_models - rn.final_models).max() < 1e-12
    assert rj.history.total_floats_transmitted == pytest.approx(
        rn.history.total_floats_transmitted
    )


def _fault_batch_schedule(ds, cfg, seed=0):
    """Fixed [T, N, b] injected batches so backend trajectories are
    comparable (same convention as tests/conftest.batch_schedule)."""
    rng = np.random.default_rng(seed)
    sizes = [ds.shard(i)[0].shape[0] for i in range(cfg.n_workers)]
    return np.stack([
        np.stack([
            rng.choice(sizes[i], size=cfg.local_batch_size, replace=False)
            for i in range(cfg.n_workers)
        ])
        for _ in range(cfg.n_iterations)
    ])


def test_one_peer_matching_properties():
    from distributed_optimization_tpu.parallel.faults import (
        sample_one_peer_matching,
    )

    topo = build_topology("grid", 16)
    A = jnp.asarray(topo.adjacency, dtype=jnp.float32)
    idx = np.arange(16)
    for t in range(6):
        p = np.asarray(sample_one_peer_matching(jax.random.key(t), A))
        np.testing.assert_array_equal(p[p], idx)  # involution
        matched = p != idx
        # Matched pairs are real edges of the base graph.
        assert np.all(np.asarray(A)[idx[matched], p[matched]] == 1.0)


def test_one_peer_mix_is_pairwise_average_and_mean_preserving():
    topo = build_topology("ring", 12)
    fm = make_faulty_mixing(topo, 0.0, seed=8, one_peer=True)
    x = jnp.asarray(np.random.default_rng(3).standard_normal((12, 4)),
                    dtype=jnp.float32)
    for t in range(4):
        mixed = np.asarray(fm.mix(jnp.asarray(t), x))
        np.testing.assert_allclose(mixed.mean(0), np.asarray(x).mean(0),
                                   atol=1e-5)
        # Every row is either itself (unmatched) or a pairwise average.
        xs = np.asarray(x)
        for i in range(12):
            is_self = np.allclose(mixed[i], xs[i], atol=1e-6)
            is_avg = np.any([
                np.allclose(mixed[i], 0.5 * (xs[i] + xs[j]), atol=1e-6)
                for j in range(12) if j != i
            ])
            assert is_self or is_avg
        # Floats: one model per matched node, at most N.
        assert float(fm.realized_degree_sum(jnp.asarray(t))) <= 12


def test_one_peer_dsgd_converges_with_fraction_of_comm():
    ds = generate_synthetic_dataset(CFG)
    _, f_opt = compute_reference_optimum(ds, CFG.reg_param)
    sync = jax_backend.run(CFG, ds, f_opt)
    op = jax_backend.run(CFG.replace(gossip_schedule="one_peer"), ds, f_opt)
    assert op.history.objective[-1] < 0.2 * op.history.objective[0]
    # <= N/sum(deg) = half the synchronous-ring traffic, strictly less.
    assert (
        op.history.total_floats_transmitted
        < 0.55 * sync.history.total_floats_transmitted
    )


def test_one_peer_rejections():
    ds = generate_synthetic_dataset(CFG)
    with pytest.raises(ValueError, match="decentralized"):
        jax_backend.run(
            CFG.replace(algorithm="centralized", gossip_schedule="one_peer"),
            ds, 0.0,
        )
    with pytest.raises(ValueError, match="time-varying"):
        jax_backend.run(
            CFG.replace(algorithm="admm", gossip_schedule="one_peer",
                        lr_schedule="constant"),
            ds, 0.0,
        )
    with pytest.raises(ValueError, match="jax-backend capability"):
        numpy_backend.run(CFG.replace(gossip_schedule="one_peer"), ds, 0.0)
    with pytest.raises(ValueError, match="Unknown gossip"):
        ExperimentConfig(gossip_schedule="async")


@pytest.mark.parametrize("topology,n", [
    ("ring", 8), ("ring", 9), ("chain", 7), ("chain", 8), ("grid", 16),
    ("grid", 36),
])
def test_round_robin_phases_cover_edges(topology, n):
    from distributed_optimization_tpu.parallel.matchings import (
        round_robin_partners,
        validate_partners,
    )

    topo = build_topology(topology, n)
    partners = round_robin_partners(topo)
    validate_partners(partners, topo)  # involutions, edges, exact coverage
    # Odd rings need the extra wrap phase.
    expected_phases = {("ring", 9): 3, ("grid", 16): 4, ("grid", 36): 4}
    assert partners.shape[0] == expected_phases.get((topology, n), 2)


def test_round_robin_rejects_unsupported():
    from distributed_optimization_tpu.parallel.matchings import (
        round_robin_partners,
    )

    with pytest.raises(ValueError, match="ring/chain/grid"):
        round_robin_partners(build_topology("fully_connected", 6))
    with pytest.raises(ValueError, match="even side"):
        round_robin_partners(build_topology("grid", 9))
    with pytest.raises(ValueError, match="deterministic"):
        ExperimentConfig(gossip_schedule="round_robin", edge_drop_prob=0.1)


def test_round_robin_dsgd_converges_with_third_of_traffic():
    ds = generate_synthetic_dataset(CFG)
    _, f_opt = compute_reference_optimum(ds, CFG.reg_param)
    sync = jax_backend.run(CFG, ds, f_opt)
    rr = jax_backend.run(CFG.replace(gossip_schedule="round_robin"), ds, f_opt)
    assert rr.history.objective[-1] < 0.2 * rr.history.objective[0]
    # 9-ring: the 3 phases match 4+4+1 pairs -> 2*(4+4+1)/3 = 6 transmitting
    # nodes per iteration on average vs sum(deg) = 18 synchronous: exactly
    # one third — exact only when T divides evenly into whole phase cycles.
    assert CFG.n_iterations % 3 == 0, "ratio below assumes whole 3-phase cycles"
    assert rr.history.total_floats_transmitted == pytest.approx(
        sync.history.total_floats_transmitted / 3.0
    )


def test_admm_rejects_faults():
    ds = generate_synthetic_dataset(CFG)
    with pytest.raises(ValueError, match="static degree"):
        jax_backend.run(
            CFG.replace(algorithm="admm", edge_drop_prob=0.1,
                        lr_schedule="constant"),
            ds, 0.0,
        )


def test_centralized_rejects_faults():
    ds = generate_synthetic_dataset(CFG)
    with pytest.raises(ValueError, match="decentralized"):
        jax_backend.run(
            CFG.replace(algorithm="centralized", edge_drop_prob=0.1), ds, 0.0
        )


def test_invalid_drop_prob():
    with pytest.raises(ValueError):
        ExperimentConfig(edge_drop_prob=1.0)
    with pytest.raises(ValueError):
        ExperimentConfig(edge_drop_prob=-0.1)


def test_extra_rejects_faults():
    # EXTRA carries the previous iteration's mix (W_{t-1} x_{t-1}); its
    # exactness argument needs a static W, so time-varying gossip is refused.
    ds = generate_synthetic_dataset(CFG)
    with pytest.raises(ValueError, match="static W"):
        jax_backend.run(CFG.replace(algorithm="extra", edge_drop_prob=0.1),
                        ds, 0.0)
    with pytest.raises(ValueError, match="static W"):
        jax_backend.run(
            CFG.replace(algorithm="extra", gossip_schedule="one_peer"),
            ds, 0.0,
        )


def test_fault_accounting_is_float32_regardless_of_model_dtype():
    # Degree sums above 256 quantize in bfloat16 (8 mantissa bits); the
    # accounting must stay exact while mixed MODEL values keep the run dtype.
    topo = build_topology("fully_connected", 40)  # degree sum 40*39 = 1560
    fm = make_faulty_mixing(topo, 0.0, seed=2)
    ds0 = fm.realized_degree_sum(jnp.asarray(0))
    assert ds0.dtype == jnp.float32
    assert float(ds0) == 40 * 39  # exactly; bf16 would round to 1552/1568

    x16 = jnp.ones((40, 3), dtype=jnp.bfloat16)
    assert fm.mix(jnp.asarray(0), x16).dtype == jnp.bfloat16
    assert fm.neighbor_sum(jnp.asarray(0), x16).dtype == jnp.bfloat16
    assert fm.active(jnp.asarray(0)).dtype == jnp.float32

    one_peer = make_faulty_mixing(topo, 0.0, seed=2, one_peer=True)
    assert one_peer.realized_degree_sum(jnp.asarray(1)).dtype == jnp.float32
    assert one_peer.mix(jnp.asarray(1), x16).dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# Gradient tracking under faults: the claim at parallel/faults.py (GT remains
# convergent under time-varying gossip) is backed by exercising its tracking
# invariant through the REAL backend fault paths, not just by the DIGing
# citation. The invariant mean(y_t) = mean(g_prev_t) is an algebraic identity
# of the recursion whenever (a) every realized W_t is doubly stochastic
# (edge drops, one-peer matchings) and (b) a straggler's freeze covers ALL
# state leaves with its mixing row collapsed to identity — sum(y') =
# sum(W y) - sum_frozen y + sum_active(g_new - g_prev) + sum_frozen y =
# sum_frozen g_prev + sum_active g_new = sum(g_prev'). A partial freeze
# (e.g. freezing x but gossiping y) would break it; these tests pin the
# backend's freeze at jax_backend (straggler state-freeze) to the identity.
# ---------------------------------------------------------------------------

GT_CFG = CFG.replace(
    algorithm="gradient_tracking", lr_schedule="constant",
    learning_rate_eta0=0.02, dtype="float64", n_iterations=400,
    eval_every=50,
)


def _gt_invariant_residual(result):
    y_mean = result.final_state["y"].mean(axis=0)
    g_mean = result.final_state["g_prev"].mean(axis=0)
    assert np.linalg.norm(g_mean) > 1e-8  # nontrivial state
    return float(np.abs(y_mean - g_mean).max())


@pytest.mark.parametrize(
    "faults",
    [
        dict(edge_drop_prob=0.3),
        dict(straggler_prob=0.3),
        dict(edge_drop_prob=0.2, straggler_prob=0.2),
        dict(gossip_schedule="one_peer"),
        dict(gossip_schedule="one_peer", edge_drop_prob=0.2,
             straggler_prob=0.2),
    ],
    ids=["drops", "stragglers", "both", "one_peer", "one_peer_both"],
)
def test_gt_tracking_invariant_survives_faults(faults):
    ds = generate_synthetic_dataset(GT_CFG)
    _, f_opt = compute_reference_optimum(ds, GT_CFG.reg_param)
    r = jax_backend.run(GT_CFG.replace(**faults), ds, f_opt,
                        return_state=True)
    # float64 run, T=400: the identity holds to accumulation roundoff.
    assert _gt_invariant_residual(r) < 1e-10


def test_gt_converges_under_faults_with_honest_accounting():
    ds = generate_synthetic_dataset(GT_CFG)
    _, f_opt = compute_reference_optimum(ds, GT_CFG.reg_param)
    clean = jax_backend.run(GT_CFG, ds, f_opt)
    faulty = jax_backend.run(
        GT_CFG.replace(edge_drop_prob=0.3, straggler_prob=0.2), ds, f_opt
    )
    # Still optimizing under combined faults...
    assert faulty.history.objective[-1] < 0.2 * faulty.history.objective[0]
    # ...and the realized two-round (x and y) accounting shrinks with the
    # surviving edges: E[realized] = (1-p)(1-q)^2 * clean ≈ 0.448.
    ratio = (
        faulty.history.total_floats_transmitted
        / clean.history.total_floats_transmitted
    )
    assert 0.3 < ratio < 0.6


def test_gt_straggler_freeze_covers_all_state_leaves():
    """One straggler-heavy iteration from zero init: a frozen worker's x, y,
    AND g_prev must all remain at init (the invariant's proof needs the
    freeze to cover every leaf; freezing x alone would desynchronize y)."""
    from distributed_optimization_tpu.parallel.faults import (
        make_faulty_mixing,
    )

    cfg = GT_CFG.replace(straggler_prob=0.5, n_iterations=1, eval_every=1)
    ds = generate_synthetic_dataset(cfg)
    r = jax_backend.run(cfg, ds, 0.0, return_state=True)
    topo = build_topology("ring", cfg.n_workers)
    # Fault draws are explicit float32 since the timeline refactor, so the
    # mask no longer depends on x64 mode; the scope stays to pin exactly
    # the float64 run's context.
    with enable_x64():
        fm = make_faulty_mixing(topo, 0.0, seed=cfg.seed, straggler_prob=0.5)
        m = np.asarray(fm.active(jnp.asarray(0)))
    frozen = m == 0.0
    assert frozen.any() and (~frozen).any()
    # y_0 = 0, g_prev_0 = 0; after one GT step an ACTIVE worker's y equals
    # its first gradient (nonzero), a frozen worker's stays exactly 0.
    assert np.all(r.final_state["y"][frozen] == 0.0)
    assert np.all(r.final_state["g_prev"][frozen] == 0.0)
    assert np.all(
        np.abs(r.final_state["y"][~frozen]).sum(axis=1) > 0
    )


# ---------------------------------------------------------------------------
# Bitwise reductions of the persistent fault processes (ISSUE 2): the
# Gilbert-Elliott edge chain at burst_len=1 and crash-recovery churn at the
# iid-equivalent (mttf, mttr) point consume the SAME counter-based draws as
# the memoryless samplers against the SAME thresholds — different code path
# (precomputed timeline vs on-the-fly masks), identical realizations, so the
# reductions are asserted as exact array equality through the REAL backend
# trajectories, not just at the mask level.
# ---------------------------------------------------------------------------


def test_burst_len1_masks_bitwise_match_iid():
    from distributed_optimization_tpu.parallel.faults import (
        build_fault_timeline,
    )

    topo = build_topology("erdos_renyi", 10, erdos_renyi_p=0.5, seed=2)
    fm_iid = make_faulty_mixing(topo, 0.4, seed=11)
    tl = build_fault_timeline(topo, 60, 11, edge_drop_prob=0.4, burst_len=1.0)
    fm_tl = make_faulty_mixing(topo, 0.4, seed=11, burst_len=1.0, horizon=60)
    x = jnp.asarray(np.random.default_rng(4).standard_normal((10, 3)),
                    dtype=jnp.float32)
    for t in range(60):
        np.testing.assert_array_equal(
            np.asarray(fm_iid.realized_adjacency(jnp.asarray(t))),
            np.asarray(fm_tl.realized_adjacency(jnp.asarray(t))),
        )
        np.testing.assert_array_equal(
            np.asarray(fm_iid.mix(jnp.asarray(t), x)),
            np.asarray(fm_tl.mix(jnp.asarray(t), x)),
        )
    # The timeline's marginal drop rate matches the iid sampler's target.
    assert abs((1.0 - tl.edge_up.mean()) - 0.4) < 0.05


def test_burst_len1_backend_trajectory_bitwise():
    ds = generate_synthetic_dataset(CFG)
    _, f_opt = compute_reference_optimum(ds, CFG.reg_param)
    iid = jax_backend.run(CFG.replace(edge_drop_prob=0.3), ds, f_opt)
    b1 = jax_backend.run(
        CFG.replace(edge_drop_prob=0.3, burst_len=1.0), ds, f_opt
    )
    np.testing.assert_array_equal(b1.final_models, iid.final_models)
    np.testing.assert_array_equal(b1.history.objective, iid.history.objective)
    assert (
        b1.history.total_floats_transmitted
        == iid.history.total_floats_transmitted
    )


def test_churn_iid_point_backend_trajectory_bitwise():
    from distributed_optimization_tpu.parallel.faults import (
        iid_equivalent_churn,
    )

    q = 0.25
    mttf, mttr = iid_equivalent_churn(q)
    ds = generate_synthetic_dataset(CFG)
    _, f_opt = compute_reference_optimum(ds, CFG.reg_param)
    iid = jax_backend.run(CFG.replace(straggler_prob=q), ds, f_opt)
    churn = jax_backend.run(CFG.replace(mttf=mttf, mttr=mttr), ds, f_opt)
    np.testing.assert_array_equal(churn.final_models, iid.final_models)
    np.testing.assert_array_equal(
        churn.history.objective, iid.history.objective
    )


def test_churn_iid_point_bitwise_on_numpy_backend():
    """Same reduction through the numpy oracle's independent fault twins:
    the straggler timeline and the churn chain at mttf=1/q, mttr=1/(1-q)
    drive different branches of the builder but identical realizations."""
    from distributed_optimization_tpu.parallel.faults import (
        iid_equivalent_churn,
    )

    q = 0.3
    mttf, mttr = iid_equivalent_churn(q)
    cfg = CFG.replace(n_iterations=60, eval_every=10)
    ds = generate_synthetic_dataset(cfg)
    _, f_opt = compute_reference_optimum(ds, cfg.reg_param)
    iid = numpy_backend.run(cfg.replace(straggler_prob=q), ds, f_opt)
    churn = numpy_backend.run(cfg.replace(mttf=mttf, mttr=mttr), ds, f_opt)
    np.testing.assert_array_equal(churn.final_models, iid.final_models)


def test_jax_numpy_fault_parity_bursty_and_churn():
    """ISSUE 2 acceptance: jax-vs-numpy oracle trajectory parity (~1e-12)
    for bursty + churn fault schedules, both rejoin policies."""
    for rejoin in ("frozen", "neighbor_restart"):
        cfg = CFG.replace(
            n_iterations=40, eval_every=4, dtype="float64",
            edge_drop_prob=0.3, burst_len=4.0, mttf=10.0, mttr=5.0,
            rejoin=rejoin,
        )
        ds = generate_synthetic_dataset(cfg)
        _, f_opt = compute_reference_optimum(ds, cfg.reg_param)
        sched = _fault_batch_schedule(ds, cfg)
        rj = jax_backend.run(cfg, ds, f_opt, batch_schedule=sched)
        rn = numpy_backend.run(cfg, ds, f_opt, batch_schedule=sched)
        assert np.abs(rj.final_models - rn.final_models).max() < 1e-12, rejoin
        assert rj.history.total_floats_transmitted == pytest.approx(
            rn.history.total_floats_transmitted
        )
