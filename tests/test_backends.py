"""Backend tests: jax↔numpy equivalence, algorithm correctness vs hand-rolled
matrix-form recursions, convergence oracles, comms accounting."""

import numpy as np
import pytest

from conftest import batch_schedule as _schedule, small_backend_config as small_config
from distributed_optimization_tpu.backends import run_algorithm
from distributed_optimization_tpu.ops import losses_np
from distributed_optimization_tpu.parallel import build_topology
from distributed_optimization_tpu.utils import (
    compute_reference_optimum,
    generate_synthetic_dataset,
)


@pytest.mark.parametrize("algorithm", ["centralized", "dsgd"])
def test_jax_numpy_equivalence_injected_batches(quad_setup, algorithm):
    """Identical batches ⇒ identical trajectories across backends (§4c)."""
    cfg, ds, f_opt = quad_setup
    T = 40
    sched = _schedule(ds, T, 8)
    rj = run_algorithm(
        cfg.replace(algorithm=algorithm, n_iterations=T), ds, f_opt, batch_schedule=sched
    )
    rn = run_algorithm(
        cfg.replace(algorithm=algorithm, n_iterations=T, backend="numpy"),
        ds,
        f_opt,
        batch_schedule=sched,
    )
    np.testing.assert_allclose(rj.final_models, rn.final_models, rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(
        rj.history.objective, rn.history.objective, rtol=2e-3, atol=5e-3
    )
    assert rj.total_floats_transmitted == rn.total_floats_transmitted


def test_centralized_rows_stay_identical(quad_setup):
    cfg, ds, f_opt = quad_setup
    r = run_algorithm(cfg.replace(algorithm="centralized"), ds, f_opt)
    spread = np.abs(r.final_models - r.final_models[0]).max()
    assert spread == 0.0
    assert r.history.consensus_error is None


def _hand_rolled(algorithm, ds, cfg, T, sched):
    """Matrix-form float64 recursions straight from the papers, as an oracle
    for the backend implementations (full-state, dense W)."""
    topo = build_topology(cfg.topology, cfg.n_workers)
    W = topo.mixing_matrix
    A = topo.adjacency
    deg = topo.degrees[:, None]
    n, d = cfg.n_workers, ds.n_features
    grad_f = losses_np.GRADIENTS[cfg.problem_type]
    reg = cfg.reg_param
    eta = cfg.learning_rate_eta0

    def grads(params, t):
        out = np.zeros((n, d))
        for i in range(n):
            Xi, yi = ds.shard(i)
            idx = sched[t, i]
            out[i] = grad_f(params[i], Xi[idx], yi[idx], reg)
        return out

    x = np.zeros((n, d))
    if algorithm == "gradient_tracking":
        y = np.zeros((n, d))
        g_prev = np.zeros((n, d))
        for t in range(T):
            x_new = W @ x - eta * y
            g_new = grads(x_new, t)
            y = W @ y + g_new - g_prev
            g_prev = g_new
            x = x_new
    elif algorithm == "extra":
        x_prev = x.copy()
        mix_prev = np.zeros((n, d))
        g_prev = np.zeros((n, d))
        for t in range(T):
            g = grads(x, t)
            mix_x = W @ x
            if t == 0:
                x_new = mix_x - eta * g
            else:
                x_new = x + mix_x - 0.5 * (x_prev + mix_prev) - eta * (g - g_prev)
            x_prev, mix_prev, g_prev, x = x, mix_x, g, x_new
    elif algorithm == "admm":
        c, rho = cfg.admm_c, cfg.admm_rho
        alpha = np.zeros((n, d))
        nbr = np.zeros((n, d))
        for t in range(T):
            g = grads(x, t)
            x = (rho * x + 0.5 * c * (deg * x + nbr) - g - alpha) / (rho + c * deg)
            nbr = A @ x
            alpha = alpha + 0.5 * c * (deg * x - nbr)
    else:
        raise ValueError(algorithm)
    return x


@pytest.mark.parametrize("algorithm", ["gradient_tracking", "extra", "admm"])
def test_extended_algorithms_match_matrix_form(quad_setup, algorithm):
    """Backend step rules ≡ the papers' matrix recursions on fixed batches."""
    cfg, ds, f_opt = quad_setup
    T = 12
    cfg = cfg.replace(algorithm=algorithm, n_iterations=T, learning_rate_eta0=0.01)
    sched = _schedule(ds, T, 8, seed=3)
    r = run_algorithm(cfg, ds, f_opt, batch_schedule=sched)
    expected = _hand_rolled(algorithm, ds, cfg, T, sched)
    np.testing.assert_allclose(r.final_models, expected, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("algorithm", ["gradient_tracking", "extra", "admm"])
def test_exact_methods_converge_where_dsgd_stalls(quad_setup, algorithm):
    """Constant-step GT/EXTRA/ADMM reach the exact optimum on non-IID data;
    constant-step D-SGD stalls at a bias floor — the study's core phenomenon."""
    cfg, ds, f_opt = quad_setup
    T = 600
    kw = dict(n_iterations=T, local_batch_size=50, lr_schedule="constant")
    exact = run_algorithm(
        cfg.replace(algorithm=algorithm, learning_rate_eta0=0.02, **kw), ds, f_opt
    )
    dsgd = run_algorithm(
        cfg.replace(algorithm="dsgd", learning_rate_eta0=0.02, **kw), ds, f_opt
    )
    assert exact.history.objective[-1] < 1.0
    assert exact.history.objective[-1] < 0.2 * dsgd.history.objective[-1]
    assert exact.history.consensus_error[-1] < 1e-2


def test_admm_on_erdos_renyi_logistic():
    """BASELINE.json config #3: decentralized ADMM, logistic, 16-worker ER."""
    cfg = small_config(
        problem_type="logistic",
        algorithm="admm",
        topology="erdos_renyi",
        n_workers=16,
        n_iterations=400,
        local_batch_size=25,
        admm_rho=2.0,
        admm_c=0.5,
    )
    ds = generate_synthetic_dataset(cfg)
    _, f_opt = compute_reference_optimum(ds, cfg.reg_param)
    r = run_algorithm(cfg, ds, f_opt)
    assert r.history.objective[-1] < 0.01
    assert r.history.consensus_error[-1] < 1e-4


def test_gradient_tracking_on_torus():
    """BASELINE.json config #4 (scaled down): GT, quadratic, 2D torus."""
    cfg = small_config(
        algorithm="gradient_tracking",
        topology="grid",
        n_workers=16,
        n_iterations=500,
        local_batch_size=25,
        learning_rate_eta0=0.02,
    )
    ds = generate_synthetic_dataset(cfg)
    _, f_opt = compute_reference_optimum(ds, cfg.reg_param)
    r = run_algorithm(cfg, ds, f_opt)
    assert r.history.objective[-1] < 0.5
    assert r.total_floats_transmitted == pytest.approx(2 * 4 * 16 * 11 * 500)


def test_worker_mesh_backend_path(quad_setup):
    """End-to-end run with explicit ppermute collectives on the 8-dev mesh
    (the worker mesh's shifts on the ring's neighbor table) against the
    dense matmul on one device."""
    cfg, ds, f_opt = quad_setup
    r_halo = run_algorithm(
        cfg.replace(worker_mesh=8, topology_impl="neighbor", n_iterations=50), ds, f_opt
    )
    r_dense = run_algorithm(
        cfg.replace(mixing_impl="dense", n_iterations=50), ds, f_opt, use_mesh=False
    )
    np.testing.assert_allclose(
        r_halo.final_models, r_dense.final_models, rtol=5e-4, atol=5e-4
    )


@pytest.mark.parametrize("backend", ["jax", "numpy"])
def test_eval_every_subsamples_history(quad_setup, backend):
    """eval_every=k records metrics at iterations k, 2k, ... matching the
    k=1 history at those points (same trajectory, sparser evaluation)."""
    cfg, ds, f_opt = quad_setup
    T = 40
    sched = _schedule(ds, T, 8)
    dense = run_algorithm(
        cfg.replace(n_iterations=T, backend=backend), ds, f_opt, batch_schedule=sched
    )
    sparse = run_algorithm(
        cfg.replace(n_iterations=T, eval_every=10, backend=backend),
        ds,
        f_opt,
        batch_schedule=sched,
    )
    assert sparse.history.objective.shape == (4,)
    np.testing.assert_array_equal(sparse.history.eval_iterations, [10, 20, 30, 40])
    np.testing.assert_allclose(
        sparse.history.objective, dense.history.objective[9::10], rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(sparse.final_models, dense.final_models, rtol=1e-6)


def test_record_consensus_off(quad_setup):
    cfg, ds, f_opt = quad_setup
    r = run_algorithm(cfg.replace(record_consensus=False), ds, f_opt)
    assert r.history.consensus_error is None
    assert np.isfinite(r.history.objective[-1])


def test_numpy_backend_rejects_randomized_choco_compressors(quad_setup):
    """All six algorithms run on the numpy oracle; the only carve-out is
    CHOCO with a randomized compressor, whose draws live in the jax
    counter-based PRNG stream an independent host oracle cannot reproduce."""
    cfg, ds, f_opt = quad_setup
    with pytest.raises(ValueError, match="deterministic compressors"):
        run_algorithm(
            cfg.replace(algorithm="choco", backend="numpy",
                        compression="qsgd", compression_k=4),
            ds, f_opt,
        )


def test_sqrt_decay_matches_reference_schedule(quad_setup):
    """eta_t = eta0/sqrt(t+1) (reference trainer.py:17-19): one-step check."""
    cfg, ds, f_opt = quad_setup
    T = 1
    sched = _schedule(ds, T, 8)
    r = run_algorithm(cfg.replace(n_iterations=T), ds, f_opt, batch_schedule=sched)
    # After one step from x0 = 0: x1 = -eta0 * g0 (mix(0) = 0).
    grad_f = losses_np.GRADIENTS[cfg.problem_type]
    g0 = np.stack(
        [
            grad_f(np.zeros(ds.n_features), *[a[sched[0, i]] for a in ds.shard(i)], cfg.reg_param)
            for i in range(cfg.n_workers)
        ]
    )
    np.testing.assert_allclose(
        r.final_models, -cfg.learning_rate_eta0 * g0, rtol=1e-4, atol=1e-5
    )
