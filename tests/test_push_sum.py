"""Push-sum / SGP over directed graphs (VERDICT r3 item 2).

The directed continuation of the reference's MH-gossip family (reference
``trainer.py:118-126`` builds the symmetric case; Nedić-Olshevsky 2016 and
Assran et al. 2019 define the asymmetric one). Pinned here:

- directed topology invariants (column-stochastic weights = mass
  conservation, strong connectivity, the directed ring's closed-form gap),
- compiled-form agreement (stencil ≡ dense, row-sharded too) and the ICI claim
  that a directed-ring round is ONE boundary CollectivePermute of d floats
  (half the undirected ring's traffic), enforced against compiled HLO,
- the push-sum state invariants through the real jax backend (Σw = N, w > 0,
  x ≡ num/w; w ≡ 1 exactly when W is doubly stochastic),
- three-tier agreement (jax step rule, numpy matrix oracle, C++ recursion)
  on deterministic full-batch runs,
- convergence on a directed graph where MH gossip is undefined, and the
  config gates that keep plain gossip off directed topologies.
"""

import re

import jax
import jax.numpy as jnp
from jax import enable_x64
import numpy as np
import pytest

from conftest import batch_schedule as _schedule, small_backend_config
from distributed_optimization_tpu.backends import run_algorithm
from distributed_optimization_tpu.backends import jax_backend, numpy_backend
from distributed_optimization_tpu.config import ExperimentConfig
from distributed_optimization_tpu.ops.mixing import make_mixing_op
from distributed_optimization_tpu.parallel.mesh import (
    make_worker_mesh,
    shard_over_workers,
)
from distributed_optimization_tpu.parallel.topology import (
    build_topology,
    directed_ring_spectral_gap_closed_form,
)
from distributed_optimization_tpu.utils.data import generate_synthetic_dataset
from distributed_optimization_tpu.utils.oracle import compute_reference_optimum


# ------------------------------------------------------------- topologies


@pytest.mark.parametrize("name", ["directed_ring", "directed_erdos_renyi"])
def test_directed_topology_invariants(name):
    topo = build_topology(name, 12, erdos_renyi_p=0.3, seed=3)
    A = topo.mixing_matrix
    assert topo.directed
    # Column-stochastic (mass conservation), nonnegative, zero-diagonal adj.
    np.testing.assert_allclose(A.sum(axis=0), 1.0, atol=1e-12)
    assert np.all(A >= 0)
    assert np.all(np.diag(topo.adjacency) == 0)
    # degrees are OUT-degrees (column sums of the receive-convention adj),
    # and the analytic comms count is the number of directed edges.
    np.testing.assert_array_equal(topo.degrees, topo.adjacency.sum(axis=0))
    assert topo.floats_per_iteration == topo.adjacency.sum()
    # Primitive chain: a positive spectral gap.
    assert 0.0 < topo.spectral_gap <= 1.0


def test_directed_er_strongly_connected():
    """Every sampled directed ER graph must be strongly connected — both
    orientations reachable from node 0 (the resample-until guarantee)."""
    for seed in range(5):
        topo = build_topology("directed_erdos_renyi", 10, erdos_renyi_p=0.25,
                              seed=seed)
        for adj in (topo.adjacency, topo.adjacency.T):
            reached = {0}
            frontier = [0]
            while frontier:
                j = frontier.pop()
                for i in np.nonzero(adj[:, j])[0]:
                    if int(i) not in reached:
                        reached.add(int(i))
                        frontier.append(int(i))
            assert len(reached) == topo.n


def test_directed_er_is_genuinely_asymmetric():
    topo = build_topology("directed_erdos_renyi", 12, erdos_renyi_p=0.3, seed=3)
    assert not np.allclose(topo.adjacency, topo.adjacency.T)
    # In-degrees differ from out-degrees somewhere — the mass imbalance
    # push-sum exists to correct.
    assert not np.array_equal(
        topo.adjacency.sum(axis=1), topo.adjacency.sum(axis=0)
    )


@pytest.mark.parametrize("n", [5, 25, 64])
def test_directed_ring_gap_matches_closed_form(n):
    topo = build_topology("directed_ring", n)
    assert topo.spectral_gap == pytest.approx(
        directed_ring_spectral_gap_closed_form(n), abs=1e-9
    )


# ------------------------------------------------- compiled mixing forms


def test_mass_conservation_all_impls(rng):
    """Σ_i (Ax)_i = Σ_i x_i — the invariant the weight debiasing rests on —
    for the dense matrix AND the directed-ring stencil (float64 scope)."""
    x = rng.standard_normal((16, 7)).astype(np.float64)
    with enable_x64():
        for name in ("directed_ring", "directed_erdos_renyi"):
            topo = build_topology(name, 16, erdos_renyi_p=0.3, seed=1)
            op = make_mixing_op(topo, impl="dense", dtype=jnp.float64)
            np.testing.assert_allclose(
                np.asarray(op.apply(jnp.asarray(x))).sum(axis=0),
                x.sum(axis=0), rtol=1e-12,
            )
        topo = build_topology("directed_ring", 16)
        op = make_mixing_op(topo, impl="stencil", dtype=jnp.float64)
        np.testing.assert_allclose(
            np.asarray(op.apply(jnp.asarray(x))).sum(axis=0),
            x.sum(axis=0), rtol=1e-12,
        )


def test_directed_ring_stencil_matches_dense(rng):
    topo = build_topology("directed_ring", 16)
    x = jnp.asarray(rng.standard_normal((16, 5)), dtype=jnp.float32)
    dense = make_mixing_op(topo, impl="dense")
    sten = make_mixing_op(topo, impl="stencil")
    np.testing.assert_allclose(sten.apply(x), dense.apply(x), atol=1e-6)
    np.testing.assert_allclose(
        sten.neighbor_sum(x), dense.neighbor_sum(x), atol=1e-6
    )


def test_directed_ring_stencil_on_sharded_input_matches_dense(rng):
    """A directed graph has no halo form (MH weights per slot are
    undirected-only): sharded, it runs the GSPMD stencil."""
    topo = build_topology("directed_ring", 16)
    mesh = make_worker_mesh(16)
    x_host = rng.standard_normal((16, 5)).astype(np.float32)
    x = shard_over_workers(mesh, jnp.asarray(x_host))
    sten = make_mixing_op(topo, impl="stencil")
    np.testing.assert_allclose(
        jax.jit(sten.apply)(x), topo.mixing_matrix @ x_host, atol=1e-6
    )
    np.testing.assert_allclose(
        jax.jit(sten.neighbor_sum)(x), topo.adjacency @ x_host, atol=1e-6
    )


def _permute_payload_floats(hlo: str) -> list[int]:
    out = []
    for line in hlo.splitlines():
        if re.search(r"collective-permute(-start)?\(", line):
            m = re.search(r"= (?:f32|bf16|f64|u32|s32)\[([\d,]*)\]", line)
            assert m, f"unparseable collective-permute line: {line.strip()}"
            dims = [int(v) for v in m.group(1).split(",") if v]
            out.append(int(np.prod(dims)) if dims else 1)
    return out


@pytest.mark.parametrize("impl", ["stencil"])
def test_directed_ring_lowers_to_one_forward_permute(impl):
    """A directed-ring round on D devices ships exactly ONE boundary row
    forward — d floats per device per round, HALF the undirected ring's
    2·d (tests/test_collectives.py) — and never gathers the full state."""
    n, d = 16, 7
    topo = build_topology("directed_ring", n)
    mesh = make_worker_mesh(n)
    op = make_mixing_op(topo, impl=impl)
    x = shard_over_workers(mesh, jnp.zeros((n, d), jnp.float32))
    hlo = jax.jit(op.apply).lower(x).compile().as_text()
    payloads = _permute_payload_floats(hlo)
    assert len(payloads) == 1, f"expected 1 boundary permute, got {payloads}"
    assert sum(payloads) == d
    assert "all-gather" not in hlo
    assert "all-reduce" not in hlo


# ----------------------------------------------------------- config gates


@pytest.mark.parametrize("algorithm", ["dsgd", "gradient_tracking", "extra",
                                       "admm", "centralized"])
def test_directed_topologies_reject_plain_gossip(algorithm):
    with pytest.raises(ValueError, match="column-stochastic"):
        ExperimentConfig(algorithm=algorithm, topology="directed_ring")


def test_one_peer_rejected_on_directed_topologies():
    """Matching-based schedules are undirected constructions; directed
    graphs must reject them at config time."""
    for schedule in ("one_peer", "round_robin"):
        with pytest.raises(ValueError, match="one-way links"):
            ExperimentConfig(
                algorithm="push_sum", topology="directed_ring",
                gossip_schedule=schedule,
            )


# ------------------------------------------------- directed fault model


def test_directed_realized_weights_column_stochastic_and_time_varying():
    """Every realized directed-fault matrix is column-stochastic (mass
    conservation — the invariant push-sum's debiasing needs), supported on
    the surviving edges + diagonal, and genuinely time-varying."""
    from distributed_optimization_tpu.parallel.faults import (
        make_faulty_mixing,
    )

    n = 12
    topo = build_topology("directed_erdos_renyi", n, erdos_renyi_p=0.35,
                          seed=7)
    faulty = make_faulty_mixing(topo, drop_prob=0.3, seed=11)
    eye = jnp.eye(n, dtype=jnp.float32)
    mats = [np.asarray(faulty.mix(jnp.asarray(t), eye)) for t in range(4)]
    base_support = topo.adjacency + np.eye(n)
    for W in mats:
        np.testing.assert_allclose(W.sum(axis=0), 1.0, atol=1e-6)
        assert np.all(W >= 0)
        assert np.all(W[base_support == 0] == 0)  # only real edges survive
    # Time-varying: realizations differ across iterations ...
    assert any(not np.allclose(mats[0], W) for W in mats[1:])
    # ... and reproducible: same (seed, t) gives the same realization.
    again = np.asarray(faulty.mix(jnp.asarray(0), eye))
    np.testing.assert_array_equal(mats[0], again)


def test_directed_static_weights_match_topology_builder():
    """drop-free renormalization reproduces the static column-stochastic
    matrix exactly — the fault machinery is the same rule, re-realized."""
    from distributed_optimization_tpu.parallel.faults import (
        column_stochastic_weights,
    )

    topo = build_topology("directed_erdos_renyi", 10, erdos_renyi_p=0.4,
                          seed=3)
    with enable_x64():
        W = np.asarray(
            column_stochastic_weights(
                jnp.asarray(topo.adjacency, dtype=jnp.float64)
            )
        )
    np.testing.assert_allclose(W, topo.mixing_matrix, atol=1e-12)


@pytest.mark.parametrize(
    "faults",
    [dict(edge_drop_prob=0.3), dict(straggler_prob=0.2),
     dict(edge_drop_prob=0.2, straggler_prob=0.1)],
    ids=["edge_drop", "stragglers", "both"],
)
def test_push_sum_mass_conserved_under_directed_faults(faults):
    """Through the REAL backend fault paths on a directed graph: total
    push-sum mass Σw = N survives every fault mode to fp roundoff, w stays
    positive, x stays the de-biased num/w, and the realized comms
    accounting honestly undercounts the fault-free analytic."""
    cfg = small_backend_config(
        algorithm="push_sum", topology="directed_erdos_renyi",
        erdos_renyi_p=0.35, dtype="float64", n_iterations=300,
        eval_every=50, **faults,
    )
    ds = generate_synthetic_dataset(cfg)
    _, f_opt = compute_reference_optimum(ds, cfg.reg_param)
    r = jax_backend.run(cfg, ds, f_opt, return_state=True)
    w = r.final_state["w"]
    assert np.all(w > 0)
    assert w.sum() == pytest.approx(cfg.n_workers, abs=1e-9)
    np.testing.assert_allclose(
        r.final_state["x"], r.final_state["num"] / w, rtol=1e-12
    )
    gaps = r.history.objective
    assert np.all(np.isfinite(gaps))
    assert gaps[-1] < gaps[0]  # still optimizing through the faults
    topo = build_topology(cfg.topology, cfg.n_workers,
                          erdos_renyi_p=cfg.erdos_renyi_p, seed=cfg.seed)
    analytic = topo.adjacency.sum() * (ds.n_features + 1) * cfg.n_iterations
    assert r.history.total_floats_transmitted < analytic


def test_push_sum_mass_stays_one_under_undirected_faults(quad_setup):
    """On an undirected topology the realized MH matrices stay doubly
    stochastic under faults, so faulty push-sum's mass never moves — the
    degenerate case survives failure injection too."""
    cfg, ds, f_opt = quad_setup
    r = jax_backend.run(
        cfg.replace(algorithm="push_sum", dtype="float64", n_iterations=80,
                    edge_drop_prob=0.25),
        ds, f_opt, return_state=True,
    )
    np.testing.assert_allclose(r.final_state["w"], 1.0, atol=1e-12)


# ------------------------------------------------------- state invariants


@pytest.fixture(scope="module")
def der_setup():
    """(config, dataset, f_opt) on the directed-ER graph, float64."""
    cfg = small_backend_config(
        algorithm="push_sum", topology="directed_erdos_renyi",
        erdos_renyi_p=0.35, dtype="float64", n_iterations=200,
    )
    ds = generate_synthetic_dataset(cfg)
    _, f_opt = compute_reference_optimum(ds, cfg.reg_param)
    return cfg, ds, f_opt


def test_push_sum_invariants_through_backend(der_setup):
    """Through the real jax backend: Σw = N conserved to fp, w stays
    positive, and the 'x' leaf is exactly the de-biased num/w."""
    cfg, ds, f_opt = der_setup
    r = jax_backend.run(cfg, ds, f_opt, return_state=True)
    w = r.final_state["w"]
    assert w.shape == (cfg.n_workers, 1)
    assert np.all(w > 0)
    assert w.sum() == pytest.approx(cfg.n_workers, abs=1e-9)
    np.testing.assert_allclose(
        r.final_state["x"], r.final_state["num"] / w, rtol=1e-12
    )
    # The mass genuinely left 1 (directed ER is irregular) — the debiasing
    # is doing real work, not passing through.
    assert np.abs(w - 1.0).max() > 1e-3


def test_push_sum_mass_stays_one_on_doubly_stochastic_gossip(quad_setup):
    """Degenerate case: on an undirected (MH, doubly stochastic) topology
    the push-sum mass never moves and z ≡ num."""
    cfg, ds, f_opt = quad_setup
    r = jax_backend.run(
        cfg.replace(algorithm="push_sum", dtype="float64", n_iterations=100),
        ds, f_opt, return_state=True,
    )
    np.testing.assert_allclose(r.final_state["w"], 1.0, atol=1e-12)
    np.testing.assert_allclose(
        r.final_state["x"], r.final_state["num"], rtol=1e-12
    )


# ----------------------------------------------- cross-tier verification


def test_jax_matches_numpy_oracle_full_batch(der_setup):
    """Deterministic full-batch trajectories: the jax step rule and the
    independent numpy matrix recursion must agree to fp tolerance."""
    cfg, ds, f_opt = der_setup
    full = cfg.replace(local_batch_size=10_000)  # clamped to the shard size
    rj = jax_backend.run(full, ds, f_opt)
    rn = numpy_backend.run(full, ds, f_opt)
    np.testing.assert_allclose(rj.final_models, rn.final_models, atol=1e-8)
    np.testing.assert_allclose(
        rj.history.objective, rn.history.objective, atol=1e-7
    )
    assert (
        rj.history.total_floats_transmitted
        == rn.history.total_floats_transmitted
    )


def test_cpp_matches_numpy_oracle_full_batch(der_setup):
    cpp_backend = pytest.importorskip(
        "distributed_optimization_tpu.backends.cpp_backend"
    )
    try:
        cpp_backend.load_library()
    except cpp_backend.NativeBuildError:  # pragma: no cover
        pytest.skip("native toolchain unavailable")
    cfg, ds, f_opt = der_setup
    full = cfg.replace(local_batch_size=10_000)
    rc = cpp_backend.run(full, ds, f_opt)
    rn = numpy_backend.run(full, ds, f_opt)
    np.testing.assert_allclose(rc.final_models, rn.final_models, atol=1e-9)
    np.testing.assert_allclose(
        rc.history.objective, rn.history.objective, atol=1e-9
    )
    assert (
        rc.history.total_floats_transmitted
        == rn.history.total_floats_transmitted
    )


def test_comm_payload_counts_mass_scalar(der_setup):
    """One round transmits d+1 floats per directed edge (model + mass)."""
    cfg, ds, f_opt = der_setup
    topo = build_topology(cfg.topology, cfg.n_workers,
                          erdos_renyi_p=cfg.erdos_renyi_p, seed=cfg.seed)
    r = numpy_backend.run(cfg, ds, f_opt)
    d = ds.n_features
    assert r.history.total_floats_transmitted == pytest.approx(
        topo.adjacency.sum() * (d + 1) * cfg.n_iterations
    )


# ------------------------------------------------------------ convergence


def test_converges_where_mh_gossip_is_undefined(der_setup):
    """On the directed ER graph — where no MH/doubly-stochastic weight
    assignment exists — push-sum drives the suboptimality gap down and
    contracts consensus of the de-biased estimates."""
    cfg, ds, f_opt = der_setup
    long = cfg.replace(n_iterations=3000, eval_every=100)
    r = numpy_backend.run(long, ds, f_opt)
    gaps = r.history.objective
    assert np.all(np.isfinite(gaps))
    assert gaps[-1] < 0.4 * gaps[0]
    cons = r.history.consensus_error
    assert cons[-1] < cons[0]
    # Late-phase monotone-ish decrease (no divergence/oscillation blowup).
    assert gaps[-1] <= gaps[len(gaps) // 2]


def test_injected_batches_match_oracle_step_for_step(quad_setup):
    """Same injected batches ⇒ same trajectory, jax vs numpy, on BOTH a
    directed graph and the undirected degenerate case (T=40)."""
    cfg, ds, f_opt = quad_setup
    T = 40
    sched = _schedule(ds, T, 8, seed=13)
    for topology in ("directed_erdos_renyi", "ring"):
        kw = dict(algorithm="push_sum", topology=topology, n_iterations=T,
                  learning_rate_eta0=0.02)
        rj = run_algorithm(cfg.replace(**kw), ds, f_opt, batch_schedule=sched)
        rn = run_algorithm(
            cfg.replace(backend="numpy", dtype="float64", **kw), ds, f_opt,
            batch_schedule=sched,
        )
        np.testing.assert_allclose(
            rj.final_models, rn.final_models, rtol=5e-4, atol=5e-4
        )
        np.testing.assert_allclose(
            rj.history.objective, rn.history.objective, rtol=2e-3, atol=5e-3
        )
