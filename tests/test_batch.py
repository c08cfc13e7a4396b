"""Replica-batched execution (ISSUE-4 tentpole): run_batch parity & wiring.

The contract under test: replica r of ``run_batch(config, seeds=S,
sweep=V)`` is trajectory-equivalent to the sequential
``run(config.replace(seed=S[r], topology_seed=<base graph>, **{f:
V[f][r]}))`` — through the benign path, the composed bursty+churn+
Byzantine fault stack, the gather robust path, and every swept axis — at
≤ 1e-12 in float64 through REAL backend runs. Plus: per-replica
continuation exactness (state0/t0), rejection of unsupported sweep axes
and unbatchable configs, and the suite-level mean ± std reporting.
"""

import numpy as np
import pytest

from distributed_optimization_tpu.backends import jax_backend
from distributed_optimization_tpu.config import ExperimentConfig
from distributed_optimization_tpu.utils.data import generate_synthetic_dataset
from distributed_optimization_tpu.utils.oracle import compute_reference_optimum

TOL = dict(rtol=1e-12, atol=1e-12)


def _cfg(**kw):
    defaults = dict(
        n_workers=8, n_samples=400, n_features=10, n_informative_features=6,
        problem_type="logistic", n_iterations=40, topology="ring",
        algorithm="dsgd", backend="jax", local_batch_size=8, eval_every=10,
        dtype="float64",
    )
    defaults.update(kw)
    return ExperimentConfig(**defaults)


def _setup(cfg):
    ds = generate_synthetic_dataset(cfg)
    _, f_opt = compute_reference_optimum(
        ds, cfg.reg_param, huber_delta=cfg.huber_delta,
        n_classes=cfg.n_classes,
    )
    return ds, f_opt


def _assert_replica_matches_sequential(cfg, ds, f_opt, batch, r, seed, **ov):
    seq = jax_backend.run(
        cfg.replace(seed=seed, topology_seed=cfg.resolved_topology_seed(),
                    **ov),
        ds, f_opt,
    )
    np.testing.assert_allclose(
        batch.objective[r], seq.history.objective, **TOL
    )
    np.testing.assert_allclose(
        batch.results[r].final_models, seq.final_models, **TOL
    )
    if batch.consensus_error is not None:
        np.testing.assert_allclose(
            batch.consensus_error[r], seq.history.consensus_error, **TOL
        )
    assert batch.results[r].history.total_floats_transmitted == pytest.approx(
        seq.history.total_floats_transmitted, rel=1e-12
    )


def test_benign_parity_every_replica():
    cfg = _cfg()
    ds, f_opt = _setup(cfg)
    seeds = [203, 404, 777]
    batch = jax_backend.run_batch(cfg, ds, f_opt, seeds=seeds)
    assert batch.objective.shape == (3, 4)
    for r, s in enumerate(seeds):
        _assert_replica_matches_sequential(cfg, ds, f_opt, batch, r, s)


def test_gradient_tracking_parity():
    cfg = _cfg(algorithm="gradient_tracking", problem_type="quadratic")
    ds, f_opt = _setup(cfg)
    seeds = [203, 509]
    batch = jax_backend.run_batch(cfg, ds, f_opt, seeds=seeds)
    for r, s in enumerate(seeds):
        _assert_replica_matches_sequential(cfg, ds, f_opt, batch, r, s)


def test_composed_faults_byzantine_gather_parity():
    """The hard cell: bursty links + crash-recovery churn + sign-flip
    Byzantine + gather-form trimmed mean, on a seed-dependent ER graph —
    every layer's per-replica randomness must land bit-compatibly."""
    cfg = _cfg(
        n_workers=12, n_samples=480, topology="erdos_renyi",
        erdos_renyi_p=0.7, partition="shuffled",
        edge_drop_prob=0.2, burst_len=3.0, mttf=20.0, mttr=4.0,
        attack="sign_flip", n_byzantine=1, aggregation="trimmed_mean",
        robust_b=1, robust_impl="gather",
    )
    ds, f_opt = _setup(cfg)
    seeds = [203, 500]
    batch = jax_backend.run_batch(cfg, ds, f_opt, seeds=seeds)
    for r, s in enumerate(seeds):
        _assert_replica_matches_sequential(cfg, ds, f_opt, batch, r, s)


def test_one_peer_matching_parity():
    cfg = _cfg(gossip_schedule="one_peer", edge_drop_prob=0.1)
    ds, f_opt = _setup(cfg)
    seeds = [203, 811]
    batch = jax_backend.run_batch(cfg, ds, f_opt, seeds=seeds)
    for r, s in enumerate(seeds):
        _assert_replica_matches_sequential(cfg, ds, f_opt, batch, r, s)


def test_eta0_sweep_parity():
    cfg = _cfg(algorithm="gradient_tracking", problem_type="quadratic",
               n_iterations=30)
    ds, f_opt = _setup(cfg)
    etas = [0.02, 0.05, 0.1]
    batch = jax_backend.run_batch(
        cfg, ds, f_opt, seeds=[203] * 3,
        sweep={"learning_rate_eta0": etas},
    )
    for r, e in enumerate(etas):
        _assert_replica_matches_sequential(
            cfg, ds, f_opt, batch, r, 203, learning_rate_eta0=e
        )


def test_clip_tau_and_edge_drop_sweep_parity():
    cfg = _cfg(
        n_workers=12, n_samples=480, topology="erdos_renyi",
        erdos_renyi_p=0.7, partition="shuffled", edge_drop_prob=0.15,
        attack="alie", n_byzantine=1, attack_scale=1.5,
        aggregation="clipped_gossip", robust_b=1, clip_tau=0.5,
    )
    ds, f_opt = _setup(cfg)
    taus, drops = [0.3, 0.6], [0.1, 0.25]
    batch = jax_backend.run_batch(
        cfg, ds, f_opt, seeds=[203, 404],
        sweep={"clip_tau": taus, "edge_drop_prob": drops},
    )
    for r, s in enumerate([203, 404]):
        _assert_replica_matches_sequential(
            cfg, ds, f_opt, batch, r, s, clip_tau=taus[r],
            edge_drop_prob=drops[r],
        )


def test_continuation_is_exact_per_replica():
    """Splitting a batch at t0 and resuming from final_states is the
    one-shot program split in two: bitwise-identical final state (the
    counter-based draws depend only on (seed, t), never on carried RNG)."""
    cfg = _cfg(algorithm="gradient_tracking", problem_type="quadratic",
               n_iterations=30, edge_drop_prob=0.2, burst_len=2.0)
    ds, f_opt = _setup(cfg)
    seeds = [203, 207]
    one = jax_backend.run_batch(cfg, ds, f_opt, seeds=seeds)
    h1 = jax_backend.run_batch(
        cfg.replace(n_iterations=10), ds, f_opt, seeds=seeds
    )
    h2 = jax_backend.run_batch(
        cfg.replace(n_iterations=20), ds, f_opt, seeds=seeds,
        state0=h1.final_states, t0=10,
    )
    for k in one.final_states:
        np.testing.assert_array_equal(one.final_states[k], h2.final_states[k])
    # Eval iterations carry the offset (rows continue the same history).
    np.testing.assert_array_equal(
        h2.results[0].history.eval_iterations, [20, 30]
    )
    # And the concatenated histories equal the one-shot run's.
    np.testing.assert_allclose(
        np.concatenate([h1.objective, h2.objective], axis=1),
        one.objective, **TOL,
    )


def test_default_seeds_follow_replicas_field():
    cfg = _cfg(replicas=3, n_iterations=20)
    ds, f_opt = _setup(cfg)
    batch = jax_backend.run_batch(cfg, ds, f_opt)
    assert batch.seeds == [203, 204, 205]
    assert batch.objective.shape[0] == 3


# ------------------------------------------------------------------ rejects
def test_rejects_structural_sweep_axis():
    cfg = _cfg()
    ds, f_opt = _setup(cfg)
    with pytest.raises(ValueError, match="structural"):
        jax_backend.run_batch(
            cfg, ds, f_opt, seeds=[1, 2], sweep={"n_workers": [8, 16]}
        )


def test_rejects_sweep_length_mismatch():
    cfg = _cfg()
    ds, f_opt = _setup(cfg)
    with pytest.raises(ValueError, match="length"):
        jax_backend.run_batch(
            cfg, ds, f_opt, seeds=[1, 2],
            sweep={"learning_rate_eta0": [0.1]},
        )


def test_rejects_choco():
    ds, f_opt = _setup(_cfg())
    with pytest.raises(ValueError, match="choco"):
        jax_backend.run_batch(
            _cfg(algorithm="choco", lr_schedule="constant"), ds, f_opt,
            seeds=[1, 2],
        )


def test_rejects_bad_sweep_values():
    cfg = _cfg()
    ds, f_opt = _setup(cfg)
    with pytest.raises(ValueError, match="edge_drop_prob"):
        jax_backend.run_batch(
            cfg, ds, f_opt, seeds=[1, 2],
            sweep={"edge_drop_prob": [0.0, 0.5]},
        )
    with pytest.raises(ValueError, match="clipped_gossip"):
        jax_backend.run_batch(
            cfg, ds, f_opt, seeds=[1, 2], sweep={"clip_tau": [0.1, 0.2]}
        )


def test_rejects_centralized_with_faults_or_attack():
    """The sequential path rejects faults/attacks for centralized runs;
    run_batch must too, not silently run a benign program (review fix)."""
    cfg = _cfg(algorithm="centralized")
    ds, f_opt = _setup(cfg)
    # Bypass config cross-validation by replacing after construction is
    # impossible (frozen + validated), so build the invalid combination
    # the way a caller could actually reach it: centralized + sweep.
    with pytest.raises(ValueError, match="peer edges"):
        jax_backend.run_batch(
            cfg, ds, f_opt, seeds=[1, 2],
            sweep={"edge_drop_prob": [0.1, 0.2]},
        )


def test_rejects_bad_state0():
    cfg = _cfg(n_iterations=10)
    ds, f_opt = _setup(cfg)
    h1 = jax_backend.run_batch(cfg, ds, f_opt, seeds=[1, 2])
    with pytest.raises(ValueError, match="replicas"):
        jax_backend.run_batch(
            cfg, ds, f_opt, seeds=[1, 2, 3], state0=h1.final_states, t0=10
        )


def test_config_rejects_unbatchable_combinations():
    with pytest.raises(ValueError, match="backend"):
        _cfg(replicas=2, backend="numpy")
    with pytest.raises(ValueError, match="choco"):
        _cfg(replicas=2, algorithm="choco", lr_schedule="constant")
    with pytest.raises(ValueError, match=">= 1"):
        _cfg(replicas=0)
    with pytest.raises(ValueError, match="mutually exclusive"):
        _cfg(replicas=2, tp_degree=2, problem_type="softmax",
             n_classes=4, local_batch_size=10_000)
    with pytest.raises(ValueError, match="replica-batched"):
        from distributed_optimization_tpu.backends.base import (
            run_algorithm_batch,
        )

        run_algorithm_batch(_cfg(backend="numpy"), None, 0.0)


# --------------------------------------------------------------- suite level
def test_simulator_reports_mean_std_over_replicas():
    from distributed_optimization_tpu.simulator import Simulator

    cfg = _cfg(replicas=3, n_iterations=20, dtype="float32")
    sim = Simulator(cfg)
    rec = sim.run_one(verbose=False)
    stats = rec.replicate_stats
    assert stats is not None and stats.n_replicas == 3
    assert stats.seeds == [203, 204, 205]
    # Mean/std consistent with the raw batch histories.
    assert stats.final_gap_mean == pytest.approx(
        float(np.mean(rec.batch.objective[:, -1]))
    )
    assert stats.final_gap_std == pytest.approx(
        float(np.std(rec.batch.objective[:, -1]))
    )
    row = sim.results_dict()["runs"][0]
    rep = row["replicates"]
    assert rep["n"] == 3 and len(rep["objective_mean"]) == 2
    assert rep["final_gap_std"] == pytest.approx(stats.final_gap_std)
    # The report renders the mean ± std row.
    text = sim.report_numerical_results()
    assert "[R=3]" in text and "±" in text


def test_explicit_seeds_via_run_kwargs():
    from distributed_optimization_tpu.simulator import Simulator

    cfg = _cfg(n_iterations=20, dtype="float32")
    sim = Simulator(cfg)
    rec = sim.run_one(verbose=False, run_kwargs={"seeds": [11, 99]})
    assert rec.batch.seeds == [11, 99]
    assert rec.replicate_stats.n_replicas == 2
