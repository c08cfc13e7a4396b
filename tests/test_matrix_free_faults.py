"""Matrix-free edge-fault processes + Byzantine gather screening (ISSUE 9
satellites — the PR 8 matrix-free path's remaining headroom).

PR 8 shipped node-process faults only on ``topology_impl='neighbor'``;
here the ``[horizon, E]`` per-edge Gilbert-Elliott chains index through
the static (node, slot) → edge-id table (``incident_edge_slots``) so
bursty-link studies run with no dense [N, N] object anywhere, and robust
aggregation (``robust_impl='gather'``) composes on the matrix-free path
the same way it composes on the dense one.

Draw-stream contract: the matrix-free edge chains draw ONE uniform per
edge per round (the dense path's (n, n) matrix draw is the quadratic
object the representation avoids), so matrix-free and dense builds of the
same config realize DIFFERENT (equally seed-pure) fault samples —
dense-vs-matrix-free parity is therefore tested by injecting one shared
timeline into both forms, and through the replica-batched path, whose
replicas must reproduce sequential runs of the same stream bitwise.
"""

import numpy as np
import pytest
from jax import enable_x64

from distributed_optimization_tpu.backends import jax_backend
from distributed_optimization_tpu.config import ExperimentConfig
from distributed_optimization_tpu.parallel import build_topology
from distributed_optimization_tpu.parallel.faults import (
    build_fault_timeline,
    make_faulty_mixing,
)
from distributed_optimization_tpu.parallel.topology import (
    incident_edge_slots,
    neighbor_tables_for,
)
from distributed_optimization_tpu.utils.data import generate_synthetic_dataset
from distributed_optimization_tpu.utils.oracle import compute_reference_optimum

N = 16
BASE = dict(
    n_workers=N, n_iterations=24, eval_every=8, n_samples=480,
    n_features=10, n_informative_features=6, dtype="float64",
    local_batch_size=6, problem_type="quadratic", algorithm="dsgd",
    topology="ring",
)


@pytest.fixture(scope="module")
def setup():
    cfg = ExperimentConfig(**BASE)
    ds = generate_synthetic_dataset(cfg)
    _, f_opt = compute_reference_optimum(ds, cfg.reg_param)
    return ds, f_opt


# --- timeline: matrix-free edge chains -------------------------------------


def test_matrix_free_edge_chains_shape_and_marginal():
    topo = build_topology("ring", N, impl="neighbor")
    p, T = 0.3, 20_000
    tl = build_fault_timeline(topo, T, 3, edge_drop_prob=p, burst_len=4.0)
    assert tl.edge_up.shape == (T, N)  # a ring has E == N edges
    assert tl.edge_index.shape == (N, 2)
    # Matched marginal at every burst level (the Gilbert-Elliott
    # construction), realized from the per-edge stream.
    assert abs((1.0 - tl.edge_up.mean()) - p) < 0.03
    # Pure in (seed, horizon): identical rebuild.
    tl2 = build_fault_timeline(topo, T, 3, edge_drop_prob=p, burst_len=4.0)
    assert np.array_equal(tl.edge_up, tl2.edge_up)
    # Mean burst length scales ~B/(1-p), like the dense chains.
    lengths = []
    for e in range(tl.edge_index.shape[0]):
        run = 0
        for up in tl.edge_up[:, e]:
            if not up:
                run += 1
            elif run:
                lengths.append(run)
                run = 0
    assert np.mean(lengths) == pytest.approx(4.0 / 0.7, rel=0.15)


def test_gather_mixing_matches_dense_on_shared_timeline():
    """One injected timeline, both execution forms: the gather-form mixing,
    availability, liveness, degree accounting and rejoin restart realize
    the identical per-round graph as the dense scatter."""
    with enable_x64():
        import jax.numpy as jnp

        H = 12
        topo_d = build_topology("ring", N)
        topo_m = build_topology("ring", N, impl="neighbor")
        tl = build_fault_timeline(
            topo_m, H, 11, edge_drop_prob=0.3, burst_len=3.0,
            mttf=6.0, mttr=3.0,
        )
        kw = dict(burst_len=3.0, mttf=6.0, mttr=3.0, horizon=H,
                  timeline=tl, rejoin="neighbor_restart")
        fm_m = make_faulty_mixing(topo_m, 0.3, 11, **kw)
        fm_d = make_faulty_mixing(topo_d, 0.3, 11, **kw)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((N, 5)))
        ni, nm = neighbor_tables_for(topo_d)
        for t in range(H):
            assert np.max(np.abs(
                np.asarray(fm_m.mix(t, x)) - np.asarray(fm_d.mix(t, x))
            )) < 1e-12, t
            assert np.max(np.abs(
                np.asarray(fm_m.neighbor_sum(t, x))
                - np.asarray(fm_d.neighbor_sum(t, x))
            )) < 1e-12, t
            assert np.array_equal(
                np.asarray(fm_m.active(t)), np.asarray(fm_d.active(t))
            )
            assert float(fm_m.realized_degree_sum(t)) == float(
                fm_d.realized_degree_sum(t)
            )
            # Gather liveness == dense realized adjacency read per slot,
            # bitwise (the incident_edge_slots composition).
            lv = np.asarray(fm_m.make_neighbor_liveness(ni, nm)(t))
            A_t = np.asarray(fm_d.realized_adjacency(t))
            ref = np.where(nm, A_t[np.arange(N)[:, None], ni], 0.0)
            assert np.array_equal(lv, ref), t
            assert np.max(np.abs(
                np.asarray(fm_m.rejoin_restart(t, x))
                - np.asarray(fm_d.rejoin_restart(t, x))
            )) < 1e-12, t


def test_incident_slots_cover_matrix_free_edge_list():
    topo = build_topology("erdos_renyi", 24, erdos_renyi_p=0.3, seed=5,
                          impl="neighbor")
    from distributed_optimization_tpu.parallel.faults import _edge_list

    edges = _edge_list(topo)
    slots = incident_edge_slots(topo.nbr_idx, topo.nbr_mask, edges)
    # Every live (node, slot) maps to the edge joining the pair — both
    # endpoints land on the SAME edge id (the symmetric composition).
    for i in range(topo.n):
        for s in range(topo.nbr_idx.shape[1]):
            if topo.nbr_mask[i, s]:
                j = int(topo.nbr_idx[i, s])
                e = int(slots[i, s])
                assert {int(edges[e, 0]), int(edges[e, 1])} == {i, j}


# --- backend paths ----------------------------------------------------------


def test_bursty_edges_batch_matches_sequential(setup):
    """Real-backend parity for matrix-free edge chains: every replica of a
    batched neighbor-path run with bursty links reproduces its sequential
    twin (both consume the same per-edge stream) ≤ 1e-12 f64."""
    ds, f_opt = setup
    cfg = ExperimentConfig(
        topology_impl="neighbor", edge_drop_prob=0.3, burst_len=3.0,
        **BASE,
    )
    batch = jax_backend.run_batch(cfg, ds, f_opt, seeds=[203, 204])
    for r, s in enumerate([203, 204]):
        seq = jax_backend.run(cfg.replace(seed=s), ds, f_opt)
        assert np.max(
            np.abs(batch.results[r].final_models - seq.final_models)
        ) < 1e-12, s
        assert np.allclose(
            batch.objective[r], seq.history.objective,
            rtol=1e-12, atol=1e-10,
        )
        # Realized comms accounting agrees between the paths.
        assert batch.results[r].history.total_floats_transmitted == (
            pytest.approx(seq.history.total_floats_transmitted, rel=1e-12)
        )


def test_matrix_free_edge_faults_health_and_bhat(setup):
    from distributed_optimization_tpu.telemetry import realized_bhat

    cfg = ExperimentConfig(
        topology_impl="neighbor", edge_drop_prob=0.4, burst_len=4.0,
        **BASE,
    )
    wc = realized_bhat(cfg)
    assert wc is not None and wc["bhat"] is not None and wc["bhat"] > 1


def test_auto_topology_impl_allows_edge_faults():
    """The auto gate no longer treats edge-drop processes as dense-only:
    at matrix-free scale a bursty-link config routes to the neighbor
    representation (the satellite's N >= 10k headroom)."""
    cfg = ExperimentConfig(
        n_workers=8192, topology="ring", edge_drop_prob=0.2, burst_len=3.0,
        local_batch_size=4, n_samples=16384,
    )
    assert cfg.resolved_topology_impl() == "neighbor"
    # Byzantine screening stays an explicit opt-in for auto.
    cfg_b = ExperimentConfig(
        n_workers=8192, topology="ring", aggregation="trimmed_mean",
        robust_b=1, local_batch_size=4, n_samples=16384,
    )
    assert cfg_b.resolved_topology_impl() == "dense"


# --- Byzantine screening on the matrix-free path ----------------------------


def test_byzantine_gather_matrix_free_matches_dense(setup):
    """Satellite: robust_impl='gather' ACCEPTED on the neighbor path —
    attack + screening trajectories match the dense-representation gather
    run ≤ 1e-12 f64 (the tables are bit-identical; only the benign mixing
    op's accumulation order differs)."""
    ds, f_opt = setup
    for extra in (
        dict(attack="sign_flip", n_byzantine=2, attack_scale=1.0),
        dict(),  # pure defense: screening with no attacker
    ):
        cfg_m = ExperimentConfig(
            topology_impl="neighbor", aggregation="trimmed_mean",
            robust_b=1, partition="shuffled", **extra, **BASE,
        )
        cfg_d = cfg_m.replace(topology_impl="dense", robust_impl="gather")
        r_m = jax_backend.run(cfg_m, ds, f_opt)
        r_d = jax_backend.run(cfg_d, ds, f_opt)
        assert np.max(np.abs(r_m.final_models - r_d.final_models)) < 1e-12
        assert np.allclose(
            r_m.history.objective, r_d.history.objective,
            rtol=1e-12, atol=1e-10,
        )


def test_byzantine_gather_composes_with_matrix_free_faults(setup):
    """Screening over the realized matrix-free graph: participation
    sampling (shared node stream ⇒ dense twin comparable) composed with
    the attack, both representations ≤ 1e-12."""
    ds, f_opt = setup
    cfg_m = ExperimentConfig(
        topology_impl="neighbor", aggregation="clipped_gossip",
        robust_b=1, clip_tau=5.0, attack="sign_flip", n_byzantine=2,
        participation_rate=0.8, partition="shuffled", **BASE,
    )
    cfg_d = cfg_m.replace(topology_impl="dense", robust_impl="gather")
    r_m = jax_backend.run(cfg_m, ds, f_opt)
    r_d = jax_backend.run(cfg_d, ds, f_opt)
    assert np.max(np.abs(r_m.final_models - r_d.final_models)) < 1e-12


def test_matrix_free_byzantine_rejections():
    with pytest.raises(ValueError, match="gather form"):
        ExperimentConfig(
            topology_impl="neighbor", aggregation="trimmed_mean",
            robust_b=1, robust_impl="dense", **BASE,
        )
    # Matching schedules still need the dense adjacency.
    with pytest.raises(ValueError, match="synchronous"):
        ExperimentConfig(
            topology_impl="neighbor", gossip_schedule="one_peer", **BASE,
        )
