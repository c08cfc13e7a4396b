"""Million-worker mesh round (ISSUE 18, docs/PERF.md §17).

Three layers:

1. **Sparse sampler**: the O(N·k_max) Erdős–Rényi constructor is
   seed-pure, realizes the same G(n, p) law as the dense-stream
   reference (degree distribution), and `sampler='auto'` resolves to the
   bitwise dense reference below ``SPARSE_SAMPLER_AUTO_N`` — small-N
   graphs are never silently re-realized.
2. **Compressed halo exchange**: sharded CHOCO-style gossip ships only
   the compressed increment's boundary rows; trajectories match the
   unsharded reference bitwise for deterministic compressors (top_k) and
   to ~1e-12 for qsgd (stochastic-rounding thresholds sit on a reduction
   XLA may fuse differently across the two programs), while
   compression='none' stays bitwise-identical to the PR 11 exchange.
3. **Scale** (slow-marked): N=1,000,000 ring/torus tables + halo plans
   build dense-free under a memory ceiling.

Plus the sequential-mesh replica dispatch satellite (run_batch).
"""

import os
import tracemalloc

import numpy as np
import pytest
from conftest import assert_ulps_of_scale

from distributed_optimization_tpu.config import (
    SPARSE_SAMPLER_AUTO_N,
    ExperimentConfig,
)
from distributed_optimization_tpu.parallel.topology import (
    _chain_neighbor_lists,
    _chain_neighbor_tables,
    _erdos_renyi_forward_edges_sparse,
    _pad_neighbor_lists,
    _ring_neighbor_lists,
    _ring_neighbor_tables,
    _torus_neighbor_lists,
    _torus_neighbor_tables,
    build_halo_plan,
    build_neighbor_topology,
    build_topology,
    neighbor_tables_for,
)

N = 16
# Units of the models' scale between the sharded and the unsharded program
# (tests/test_worker_mesh.py's yardstick; read: 2 to 4).
MODEL_ULPS = 32
BASE = dict(
    n_workers=N, n_samples=320, n_features=10, n_informative_features=6,
    problem_type="quadratic", n_iterations=24, topology="ring",
    algorithm="dsgd", local_batch_size=8, dtype="float64", eval_every=8,
    topology_impl="neighbor", mixing_impl="gather",
)


def make_cfg(**kw):
    return ExperimentConfig(**{**BASE, **kw})


@pytest.fixture(scope="module")
def problem():
    from distributed_optimization_tpu.utils.data import (
        generate_synthetic_dataset,
    )
    from distributed_optimization_tpu.utils.oracle import (
        compute_reference_optimum,
    )

    cfg = make_cfg()
    ds = generate_synthetic_dataset(cfg)
    _, f_opt = compute_reference_optimum(ds, cfg.reg_param)
    return ds, f_opt


# ------------------------------------------------------- sparse sampler


def test_vectorized_builders_match_list_builders():
    """The vectorized ring/chain/torus table constructors are bitwise the
    per-node list builders they replaced."""
    for n in (3, 5, 16, 97):
        np.testing.assert_array_equal(
            _ring_neighbor_tables(n)[0],
            _pad_neighbor_lists(_ring_neighbor_lists(n), n)[0],
        )
        np.testing.assert_array_equal(
            _chain_neighbor_tables(n)[0],
            _pad_neighbor_lists(_chain_neighbor_lists(n), n)[0],
        )
    for side in (3, 4, 7):
        np.testing.assert_array_equal(
            _torus_neighbor_tables(side)[0],
            _pad_neighbor_lists(
                _torus_neighbor_lists(side, side), side * side
            )[0],
        )


def test_sparse_er_seed_pure_and_valid():
    n, p = 600, 0.02
    s1, d1 = _erdos_renyi_forward_edges_sparse(n, p, seed=11)
    s2, d2 = _erdos_renyi_forward_edges_sparse(n, p, seed=11)
    np.testing.assert_array_equal(s1, s2)
    np.testing.assert_array_equal(d1, d2)
    assert (s1 < d1).all()  # forward (upper-triangle) edges, unique
    assert np.unique(s1 * n + d1).size == s1.size
    s3, _ = _erdos_renyi_forward_edges_sparse(n, p, seed=12)
    assert s3.size != s1.size or not np.array_equal(s1, s3)


def test_sparse_er_matches_dense_law():
    """Same G(n, p) law: mean degree within 5 sigma of n·(n−1)·p/ n, and
    both realizations are connected/symmetric topologies."""
    n, p = 1500, 0.01
    sparse = build_neighbor_topology(
        "erdos_renyi", n, erdos_renyi_p=p, seed=5, sampler="sparse"
    )
    dense = build_neighbor_topology(
        "erdos_renyi", n, erdos_renyi_p=p, seed=5, sampler="dense"
    )
    assert sparse.sampler == "sparse" and dense.sampler == "dense"
    mean_expected = (n - 1) * p
    # Var(degree) = (n−1)·p·(1−p); the mean over n (dependent) degrees
    # has variance ≤ 2·(n−1)p(1−p)/n — 5 sigma of the safe bound.
    sigma = np.sqrt(2 * (n - 1) * p * (1 - p) / n)
    for topo in (sparse, dense):
        assert abs(topo.degrees.mean() - mean_expected) < 5 * sigma
        nbr, mask = neighbor_tables_for(topo)
        # symmetry: every (i → j) slot has a (j → i) slot
        rows = np.repeat(np.arange(n), nbr.shape[1])[mask.ravel() > 0]
        cols = nbr.ravel()[mask.ravel() > 0]
        fwd = set(zip(rows.tolist(), cols.tolist()))
        assert all((j, i) in fwd for i, j in fwd)


def test_auto_sampler_resolution_and_small_n_bitwise():
    """'auto' keeps the bitwise dense reference below the cutoff and the
    explicit dense build matches the historical default exactly."""
    er = dict(topology="erdos_renyi", erdos_renyi_p=0.5, topology_seed=7)
    cfg = make_cfg(**er)
    assert cfg.topology_sampler == "auto"
    assert cfg.resolved_topology_sampler() == "dense"
    assert make_cfg().resolved_topology_sampler() == "dense"  # ring: dense
    big = make_cfg(
        n_workers=SPARSE_SAMPLER_AUTO_N * 2, n_samples=SPARSE_SAMPLER_AUTO_N * 4,
        erdos_renyi_p=16.0 / (SPARSE_SAMPLER_AUTO_N * 2), **{
            k: v for k, v in er.items() if k != "erdos_renyi_p"
        })
    assert big.resolved_topology_sampler() == "sparse"
    t_default = build_neighbor_topology("erdos_renyi", N, erdos_renyi_p=0.5,
                                        seed=7)
    t_dense = build_neighbor_topology("erdos_renyi", N, erdos_renyi_p=0.5,
                                      seed=7, sampler="dense")
    np.testing.assert_array_equal(t_default.nbr_idx, t_dense.nbr_idx)
    np.testing.assert_array_equal(t_default.nbr_mask, t_dense.nbr_mask)


def test_sampler_identity_is_structural():
    er = dict(topology="erdos_renyi", erdos_renyi_p=0.5, topology_seed=7)
    h_dense = make_cfg(**er).structural_hash()
    h_sparse = make_cfg(topology_sampler="sparse", **er).structural_hash()
    assert h_dense != h_sparse
    # deterministic topologies carry no sampler identity
    assert (make_cfg().structural_dict()["topology_sampler"] is None)


def test_sampler_rejections():
    with pytest.raises(ValueError, match="dense' or 'sparse"):
        build_neighbor_topology("erdos_renyi", 8, sampler="fast")
    # the dense [N, N] path cannot honor a sparse-sampler request
    with pytest.raises(ValueError, match="sampler"):
        build_topology("erdos_renyi", 8, impl="dense", sampler="sparse")
    # ring has a unique realization: explicit non-auto sampler is noise
    with pytest.raises(ValueError, match="one realization"):
        make_cfg(topology_sampler="sparse")


def test_halo_plan_cache_key_includes_sampler():
    t_dense = build_neighbor_topology("erdos_renyi", N, erdos_renyi_p=0.5,
                                      seed=7, sampler="dense")
    t_sparse = build_neighbor_topology("erdos_renyi", N, erdos_renyi_p=0.5,
                                       seed=7, sampler="sparse")
    p1 = build_halo_plan(*neighbor_tables_for(t_dense), 4, sampler="dense")
    p2 = build_halo_plan(*neighbor_tables_for(t_dense), 4, sampler="dense")
    assert p1 is p2  # cache hit
    p3 = build_halo_plan(*neighbor_tables_for(t_sparse), 4, sampler="sparse")
    assert p3 is not p1


# ------------------------------------------- compressed halo exchange


def run_pair(problem, **kw):
    from distributed_optimization_tpu.backends import jax_backend

    ds, f_opt = problem
    cfg_u = make_cfg(**kw)
    cfg_s = cfg_u.replace(worker_mesh=4)
    r_u = jax_backend.run(cfg_u, ds, f_opt, use_mesh=False, return_state=True)
    r_s = jax_backend.run(cfg_s, ds, f_opt, return_state=True)
    return r_u, r_s


@pytest.mark.parametrize("algo", ["dsgd", "choco", "gradient_tracking"])
def test_compressed_mesh_topk_bitwise(problem, algo):
    r_u, r_s = run_pair(problem, algorithm=algo, compression="top_k",
                        compression_k=4, choco_gamma=0.5)
    np.testing.assert_array_equal(
        np.asarray(r_u.final_models), np.asarray(r_s.final_models)
    )
    assert "xhat_halo" in r_s.final_state
    if algo == "gradient_tracking":
        assert "yhat_halo" in r_s.final_state
    # the halo leaf never leaks into the unsharded program
    assert "xhat_halo" not in r_u.final_state


def test_compressed_mesh_qsgd_close(problem):
    """qsgd: reproducible per program, ~1e-12 across programs (its
    stochastic-rounding threshold sits on a row-norm reduction XLA may
    fuse differently in the sharded vs unsharded executable)."""
    r_u, r_s = run_pair(problem, compression="qsgd", compression_k=4,
                        choco_gamma=0.5)
    np.testing.assert_allclose(
        np.asarray(r_u.final_models), np.asarray(r_s.final_models),
        rtol=0, atol=1e-12,
    )


def test_uncompressed_mesh_stays_bitwise(problem):
    """The PR 11 gate: compression='none' runs the unchanged exchange. The
    sharded and the unsharded run are two programs: a few units of the
    models' scale apart (``conftest.assert_ulps_of_scale``), where a run
    in float32 would be 2**29."""
    r_u, r_s = run_pair(problem)
    assert_ulps_of_scale(r_s.final_models, r_u.final_models, MODEL_ULPS)
    assert "xhat_halo" not in r_s.final_state


def test_ici_summary_prices_compressed_wire_rows():
    from distributed_optimization_tpu.telemetry import ici_summary

    plain = ici_summary(make_cfg(worker_mesh=4))
    comp = ici_summary(make_cfg(worker_mesh=4, compression="top_k",
                                compression_k=2, choco_gamma=0.5))
    assert comp["compression"] == "top_k"
    assert (comp["bytes_per_device_per_round_max"]
            < plain["bytes_per_device_per_round_max"])
    # top_k ships k (value, index) pairs per row instead of d+1 floats
    assert comp["payload_floats_per_row"] == pytest.approx(2 * 2)


def test_two_mesh_runs_of_one_config_are_bitwise(problem):
    from distributed_optimization_tpu.backends import jax_backend

    ds, f_opt = problem
    r_u = jax_backend.run(make_cfg(), ds, f_opt, use_mesh=False)
    r_mesh = jax_backend.run(make_cfg(worker_mesh=4), ds, f_opt)
    # Against the unsharded program the mesh run is another executable
    # (MODEL_ULPS); a replay of itself is bitwise:
    assert_ulps_of_scale(r_mesh.final_models, r_u.final_models, MODEL_ULPS)
    r_again = jax_backend.run(make_cfg(worker_mesh=4), ds, f_opt)
    np.testing.assert_array_equal(
        np.asarray(r_mesh.final_models), np.asarray(r_again.final_models)
    )


# ------------------------------------------------- sequential-mesh batch


def test_mesh_replicas_dispatch_sequentially(problem):
    from distributed_optimization_tpu.backends import jax_backend

    ds, f_opt = problem
    cfg = make_cfg(worker_mesh=4, replicas=2)
    br = jax_backend.run_batch(cfg, ds, f_opt)
    assert br.objective.shape[0] == 2
    # replica 0 is bitwise the sequential run at the same seeds
    seq = jax_backend.run(
        cfg.replace(replicas=1, seed=cfg.replica_seeds()[0],
                    topology_seed=cfg.resolved_topology_seed()),
        ds, f_opt,
    )
    np.testing.assert_array_equal(
        np.asarray(br.results[0].history.objective),
        np.asarray(seq.history.objective),
    )
    # the serving coalescer still routes mesh configs off the vmap path
    assert "worker_mesh" in jax_backend.batch_unsupported_reason(cfg)


def test_mesh_batch_rejects_resume():
    from distributed_optimization_tpu.backends import jax_backend

    with pytest.raises(ValueError, match="resume"):
        jax_backend.run_batch(
            make_cfg(worker_mesh=4, replicas=2), None, 0.0,
            state0={"x": np.zeros((N, 11))}, t0=8,
        )


# --------------------------------------------------------- 1M scale


@pytest.mark.slow
def test_million_worker_tables_and_plan_under_memory_ceiling():
    """N=1,000,000 ring + torus tables and a 16-shard halo plan build
    dense-free: peak traced allocation stays far below the ~4 TB dense
    [N, N] object (ceiling 2 GB), and per-device halo rows are O(1)."""
    n = 1_000_000
    tracemalloc.start()
    try:
        ring = build_neighbor_topology("ring", n)
        plan = build_halo_plan(*neighbor_tables_for(ring), 16)
        torus = build_neighbor_topology("grid", n)
        plan_t = build_halo_plan(*neighbor_tables_for(torus), 16)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert peak < 2 * 1024**3, f"peak {peak / 1e9:.2f} GB"
    assert ring.nbr_idx.shape == (n, 2)
    assert torus.nbr_idx.shape == (n, 4)
    # boundary exchange is O(1) rows/device regardless of N
    assert plan.h_max == 2
    assert int(max(plan.sent_rows)) == 2
    assert int(max(plan_t.sent_rows)) <= 2 * 1000 + 2


@pytest.mark.slow
def test_million_worker_sparse_er_plan():
    # mean degree 20 — safely above the G(n, p) connectivity threshold
    # ln(n) ≈ 13.8, so the connected draw lands in O(1) tries.
    n = 1_000_000
    p = 20.0 / n
    topo = build_neighbor_topology("erdos_renyi", n, erdos_renyi_p=p,
                                   seed=3, sampler="sparse")
    assert topo.sampler == "sparse"
    assert abs(topo.degrees.mean() - (n - 1) * p) < 0.5
    plan = build_halo_plan(*neighbor_tables_for(topo), 16, sampler="sparse")
    assert plan.n_shards == 16


if __name__ == "__main__":  # pragma: no cover
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8"
    )
    raise SystemExit(pytest.main([__file__, "-v"]))
