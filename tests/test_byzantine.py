"""Byzantine-robustness tests (docs/BYZANTINE.md build target).

Properties: robust aggregation with zero budget IS plain gossip (bitwise
through the backend, and mathematically for clipping with τ = ∞); under
f ≤ b attackers the screened aggregate stays inside the honest envelope
(the breakdown-point containment that makes the rules robust); adversary
payloads are pure functions of (seed, t) — reproducible and
checkpoint/resume-safe like fault masks; unsupported algorithms and
invalid budgets are rejected loudly; and the vectorized jax rules match
the independent per-node numpy oracles through real backend runs.
"""

import jax.numpy as jnp
from jax import enable_x64
import numpy as np
import pytest

from distributed_optimization_tpu.backends import jax_backend, numpy_backend
from distributed_optimization_tpu.config import ExperimentConfig
from distributed_optimization_tpu.metrics import (
    honest_consensus_error,
    honest_mean,
)
from distributed_optimization_tpu.ops.robust_aggregation import (
    make_robust_aggregator,
    robust_aggregate_np,
    validate_budget,
)
from distributed_optimization_tpu.parallel import build_topology
from distributed_optimization_tpu.parallel.adversary import (
    byzantine_mask,
    make_adversary,
)
from distributed_optimization_tpu.utils.data import generate_synthetic_dataset
from distributed_optimization_tpu.utils.oracle import compute_reference_optimum

CFG = ExperimentConfig(
    n_workers=16, n_samples=480, n_features=10, n_informative_features=6,
    n_iterations=600, local_batch_size=10, problem_type="logistic",
    algorithm="dsgd", topology="fully_connected", eval_every=100,
    partition="shuffled",
)

ATTACKED = CFG.replace(attack="sign_flip", n_byzantine=5, attack_scale=5.0)


@pytest.fixture(scope="module")
def data():
    ds = generate_synthetic_dataset(CFG)
    _, f_opt = compute_reference_optimum(ds, CFG.reg_param)
    return ds, f_opt


# ---------------------------------------------------------------- reduction

def test_zero_budget_robust_run_is_bitwise_plain_gossip(data):
    """robust_b=0 means "assume no attackers": every robust rule degrades
    to exactly the plain-gossip path (same compiled program)."""
    ds, f_opt = data
    plain = jax_backend.run(CFG, ds, f_opt)
    for agg in ("trimmed_mean", "median", "clipped_gossip"):
        robust = jax_backend.run(
            CFG.replace(aggregation=agg, robust_b=0), ds, f_opt
        )
        np.testing.assert_array_equal(
            robust.history.objective, plain.history.objective
        )
        np.testing.assert_array_equal(robust.final_models, plain.final_models)


def test_clipping_with_infinite_radius_is_mh_gossip():
    """τ = ∞ clips nothing: the ACTIVE clipped-gossip path reduces to the
    MH matrix product (the mathematical reduction, not the short-circuit)."""
    topo = build_topology("erdos_renyi", 12, erdos_renyi_p=0.5, seed=3)
    agg = make_robust_aggregator("clipped_gossip", budget=1, clip_tau=1e30)
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal((12, 6)), dtype=jnp.float32
    )
    got = np.asarray(agg(jnp.asarray(topo.adjacency, jnp.float32), x))
    want = topo.mixing_matrix @ np.asarray(x, dtype=np.float64)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ----------------------------------------------------- breakdown containment

@pytest.mark.parametrize("rule", ["trimmed_mean", "median"])
def test_screened_aggregate_stays_in_honest_envelope(rule):
    """f ≤ b wild attackers cannot pull a coordinate outside the honest
    range — the containment property behind the breakdown point."""
    topo = build_topology("fully_connected", 12)
    A = jnp.asarray(topo.adjacency, dtype=jnp.float32)
    rng = np.random.default_rng(1)
    x = rng.standard_normal((12, 5))
    byz = np.zeros(12, dtype=bool)
    byz[[2, 7, 9]] = True  # f = 3 attackers, wild payloads
    x[byz] = 1e6 * rng.standard_normal((3, 5))
    agg = make_robust_aggregator(rule, budget=3)
    out = np.asarray(agg(A, jnp.asarray(x, jnp.float32)))
    lo = x[~byz].min(axis=0) - 1e-4
    hi = x[~byz].max(axis=0) + 1e-4
    for i in np.nonzero(~byz)[0]:
        assert np.all(out[i] >= lo) and np.all(out[i] <= hi)


def test_clipped_gossip_bounds_adversarial_displacement():
    """Self-centered clipping: no matter the payload, a worker moves at
    most Σ_j W_ij·τ with τ ≤ its largest honest-neighbor distance."""
    topo = build_topology("fully_connected", 12)
    A = jnp.asarray(topo.adjacency, dtype=jnp.float32)
    rng = np.random.default_rng(2)
    x = rng.standard_normal((12, 5))
    byz = np.zeros(12, dtype=bool)
    byz[[0, 5, 11]] = True
    x[byz] = 1e8 * rng.standard_normal((3, 5))
    agg = make_robust_aggregator("clipped_gossip", budget=3)
    out = np.asarray(agg(A, jnp.asarray(x, jnp.float32)))
    for i in np.nonzero(~byz)[0]:
        honest_dists = np.linalg.norm(
            x[~byz] - x[i], axis=1
        )
        assert np.linalg.norm(out[i] - x[i]) <= honest_dists.max() + 1e-4


def test_breakdown_point_end_to_end(data):
    """The bench acceptance, small: under a sign-flip attack within the
    budget, plain gossip diverges or stalls far above the attack-free gap
    while trimmed-mean/median/clipping keep optimizing near it."""
    ds, f_opt = data
    clean = float(jax_backend.run(CFG, ds, f_opt).history.objective[-1])
    plain = float(jax_backend.run(ATTACKED, ds, f_opt).history.objective[-1])
    assert np.isnan(plain) or plain > 4.0 * clean
    for agg in ("trimmed_mean", "median", "clipped_gossip"):
        robust = float(
            jax_backend.run(
                ATTACKED.replace(aggregation=agg, robust_b=5), ds, f_opt
            ).history.objective[-1]
        )
        assert robust < 2.0 * clean, (agg, robust, clean)


def test_attack_composes_with_edge_faults(data):
    """Attacks run over failing links: the robust rule screens on the
    REALIZED per-iteration graph and the run still optimizes."""
    ds, f_opt = data
    r = jax_backend.run(
        ATTACKED.replace(
            aggregation="trimmed_mean", robust_b=5, edge_drop_prob=0.2
        ),
        ds, f_opt,
    )
    # Still optimizing (dropped edges shrink every screened neighborhood,
    # so progress is slower than the fault-free robust run) and well below
    # the level the undefended attack stalls at (~0.37 for this config).
    assert r.history.objective[-1] < 0.8 * r.history.objective[0]
    assert r.history.objective[-1] < 0.25
    # Realized comms accounting still active alongside the attack.
    clean = jax_backend.run(CFG, ds, f_opt)
    assert (
        r.history.total_floats_transmitted
        < clean.history.total_floats_transmitted
    )


# ------------------------------------------------------------ reproducibility

def test_payloads_reproducible_from_seed_and_t():
    x = jnp.asarray(
        np.random.default_rng(3).standard_normal((10, 4)), dtype=jnp.float32
    )
    for attack in ("sign_flip", "large_noise", "alie"):
        a1 = make_adversary(10, attack, 3, 2.0, seed=7)
        a2 = make_adversary(10, attack, 3, 2.0, seed=7)
        np.testing.assert_array_equal(a1.byzantine, a2.byzantine)
        np.testing.assert_array_equal(
            np.asarray(a1.corrupt(jnp.asarray(5), x)),
            np.asarray(a2.corrupt(jnp.asarray(5), x)),
        )
    # The noise attack varies over t but is identical at equal t.
    adv = make_adversary(10, "large_noise", 3, 2.0, seed=7)
    at4 = np.asarray(adv.corrupt(jnp.asarray(4), x))
    at5 = np.asarray(adv.corrupt(jnp.asarray(5), x))
    assert not np.array_equal(at4, at5)
    # Honest rows always pass through untouched.
    np.testing.assert_array_equal(at4[adv.honest], np.asarray(x)[adv.honest])


def test_alie_payload_is_shared_honest_stat():
    adv = make_adversary(10, "alie", 3, 1.5, seed=11)
    x = np.random.default_rng(4).standard_normal((10, 4)).astype(np.float32)
    out = np.asarray(adv.corrupt(jnp.asarray(0), jnp.asarray(x)))
    h = x[adv.honest].astype(np.float64)
    want = h.mean(axis=0) - 1.5 * h.std(axis=0)
    for i in np.nonzero(adv.byzantine)[0]:
        np.testing.assert_allclose(out[i], want, rtol=1e-5, atol=1e-6)


def test_byzantine_runs_are_checkpoint_resume_safe(data, tmp_path):
    """Killed-and-resumed attacked run == uninterrupted run, exactly the
    fault-mask property: payloads derive from (seed, t), no carried RNG."""
    from distributed_optimization_tpu.utils.checkpoint import CheckpointOptions

    ds, f_opt = data
    cfg = ATTACKED.replace(
        aggregation="trimmed_mean", robust_b=5, attack="large_noise",
        attack_scale=10.0, n_iterations=200, eval_every=20,
    )
    full = jax_backend.run(cfg, ds, f_opt)
    ckdir = str(tmp_path / "byz_ck")
    half = cfg.replace(n_iterations=100)
    jax_backend.run(
        half, ds, f_opt,
        checkpoint=CheckpointOptions(ckdir, every_evals=5),
    )
    resumed = jax_backend.run(
        cfg, ds, f_opt,
        checkpoint=CheckpointOptions(ckdir, every_evals=5),
    )
    np.testing.assert_allclose(
        resumed.final_models, full.final_models, rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        resumed.history.objective, full.history.objective,
        rtol=1e-5, atol=1e-7,
    )


# -------------------------------------------------------------- honest metrics

def test_metrics_and_final_average_exclude_byzantine_rows(data):
    ds, f_opt = data
    r = jax_backend.run(ATTACKED, ds, f_opt)
    byz = byzantine_mask(CFG.n_workers, 5, CFG.seed)
    assert byz.sum() == 5
    np.testing.assert_allclose(
        r.final_avg_model, r.final_models[~byz].mean(axis=0), rtol=1e-12
    )
    # Helper definitions match direct numpy.
    np.testing.assert_allclose(
        honest_mean(r.final_models, byz), r.final_models[~byz].mean(axis=0)
    )
    h = r.final_models[~byz]
    want = float(
        np.mean(np.sum((h - h.mean(axis=0)) ** 2, axis=1))
    )
    assert honest_consensus_error(r.final_models, byz) == pytest.approx(want)


# ------------------------------------------------------------------ rejections

def test_unsupported_algorithms_raise(data):
    ds, _ = data
    for algorithm in ("extra", "admm", "choco", "push_sum"):
        cfg = ATTACKED.replace(
            algorithm=algorithm, lr_schedule="constant",
            topology=(
                "directed_ring" if algorithm == "push_sum"
                else "fully_connected"
            ),
        )
        with pytest.raises(ValueError, match="unsupported"):
            jax_backend.run(cfg, ds, 0.0)
    with pytest.raises(ValueError, match="no peer edges"):
        jax_backend.run(ATTACKED.replace(algorithm="centralized"), ds, 0.0)


def test_budget_exceeding_min_degree_raises(data):
    ds, _ = data
    # Ring degree 2: b=2 would trim a node's whole neighborhood.
    with pytest.raises(ValueError, match="min degree"):
        jax_backend.run(
            ATTACKED.replace(
                topology="ring", aggregation="trimmed_mean", robust_b=2
            ),
            ds, 0.0,
        )
    with pytest.raises(ValueError, match="min degree"):
        validate_budget(2, 2, "median")
    validate_budget(2, 1, "median")  # 2b <= deg is fine


def test_config_level_rejections():
    with pytest.raises(ValueError, match="Unknown attack"):
        ExperimentConfig(attack="bitflip", n_byzantine=1)
    with pytest.raises(ValueError, match="Unknown aggregation"):
        ExperimentConfig(aggregation="krum")
    with pytest.raises(ValueError, match="set together"):
        ExperimentConfig(attack="sign_flip")  # attackers missing
    with pytest.raises(ValueError, match="set together"):
        ExperimentConfig(n_byzantine=2)  # payload missing
    with pytest.raises(ValueError, match="honest worker"):
        ExperimentConfig(attack="sign_flip", n_byzantine=25)
    with pytest.raises(ValueError, match="robust aggregation rule"):
        ExperimentConfig(robust_b=1)
    with pytest.raises(ValueError, match="clip_tau"):
        ExperimentConfig(aggregation="trimmed_mean", robust_b=1, clip_tau=0.5)
    with pytest.raises(ValueError, match="synchronous"):
        ExperimentConfig(
            aggregation="median", robust_b=1, gossip_schedule="one_peer"
        )


def test_numpy_backend_rejects_randomized_attack(data):
    ds, _ = data
    with pytest.raises(ValueError, match="counter-based PRNG"):
        numpy_backend.run(
            ATTACKED.replace(attack="large_noise", backend="numpy"), ds, 0.0
        )
    with pytest.raises(ValueError, match="unsupported"):
        numpy_backend.run(
            ATTACKED.replace(algorithm="extra", lr_schedule="constant"),
            ds, 0.0,
        )


def test_cpp_backend_rejects_byzantine(data):
    from distributed_optimization_tpu.backends import cpp_backend

    ds, _ = data
    with pytest.raises(ValueError, match="not the native core"):
        cpp_backend.run(ATTACKED.replace(backend="cpp"), ds, 0.0)
    with pytest.raises(ValueError, match="not the native core"):
        cpp_backend.run(
            CFG.replace(
                backend="cpp", aggregation="median", robust_b=1
            ),
            ds, 0.0,
        )


# ------------------------------------------------------- jax vs numpy oracle

ORACLE_CFG = ExperimentConfig(
    n_workers=10, n_samples=400, n_features=8, n_informative_features=5,
    n_iterations=60, local_batch_size=8, problem_type="quadratic",
    algorithm="dsgd", topology="erdos_renyi", eval_every=20,
    dtype="float64", partition="shuffled",
    attack="sign_flip", n_byzantine=2, attack_scale=2.0,
)


def _schedule(ds, T, batch, seed=0):
    rng = np.random.default_rng(seed)
    sizes = [ds.shard(i)[0].shape[0] for i in range(ds.n_workers)]
    return np.stack([
        np.stack([
            rng.choice(sizes[i], size=batch, replace=False)
            for i in range(ds.n_workers)
        ])
        for _ in range(T)
    ])


@pytest.mark.parametrize(
    "overrides",
    [
        dict(aggregation="trimmed_mean", robust_b=1),
        dict(aggregation="median", robust_b=1),
        dict(aggregation="clipped_gossip", robust_b=1),
        dict(aggregation="trimmed_mean", robust_b=1, attack="alie"),
        dict(),  # plain gossip under attack (the vulnerable baseline)
        dict(algorithm="gradient_tracking", lr_schedule="constant",
             learning_rate_eta0=0.01, aggregation="trimmed_mean",
             robust_b=1),
    ],
    ids=["tm", "median", "clip", "alie_tm", "plain_attack", "gt_tm"],
)
def test_jax_matches_numpy_oracle_under_attack(overrides):
    cfg = ORACLE_CFG.replace(**overrides)
    ds = generate_synthetic_dataset(cfg)
    _, f_opt = compute_reference_optimum(ds, cfg.reg_param)
    sched = _schedule(ds, cfg.n_iterations, cfg.local_batch_size)
    rj = jax_backend.run(cfg, ds, f_opt, batch_schedule=sched)
    rn = numpy_backend.run(cfg, ds, f_opt, batch_schedule=sched)
    np.testing.assert_allclose(
        rj.final_models, rn.final_models, rtol=1e-9, atol=1e-10
    )
    np.testing.assert_allclose(
        rj.history.objective, rn.history.objective, rtol=1e-8, atol=1e-10
    )


def test_robust_rules_match_numpy_oracle_directly():
    """Unit-level: the vectorized jax rules against the per-node loops,
    over an irregular realized graph with missing edges."""
    topo = build_topology("erdos_renyi", 14, erdos_renyi_p=0.6, seed=5)
    rng = np.random.default_rng(6)
    A_np = np.array(topo.adjacency, copy=True)
    # Drop a few directed-symmetric edges to emulate a fault realization.
    for (i, j) in [(0, 1), (3, 8), (5, 9)]:
        if A_np[i, j]:
            A_np[i, j] = A_np[j, i] = 0.0
    x = rng.standard_normal((14, 6))
    x[[2, 11]] *= 50.0  # wild rows
    with enable_x64():
        for rule in ("trimmed_mean", "median", "clipped_gossip"):
            agg = make_robust_aggregator(rule, budget=2)
            got = np.asarray(
                agg(
                    jnp.asarray(A_np, jnp.float64),
                    jnp.asarray(x, jnp.float64),
                )
            )
            want = robust_aggregate_np(rule, A_np, x, budget=2)
            np.testing.assert_allclose(
                got, want, rtol=1e-9, atol=1e-10, err_msg=rule
            )
