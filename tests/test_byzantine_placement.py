"""Where the attackers sit (ISSUE 43, docs/BYZANTINE.md "Placement"):
``byzantine_placement='within_budget'`` draws the Byzantine set on the
graph's neighbor table so that every honest worker keeps at most
``robust_b`` attacking neighbours; ``uniform`` is the draw the package always
made, to the bit; and every layer that needs the set (the jax backend, its
replica-batched path, the numpy oracle, the incident forensics) asks ONE
resolver, so all name the same workers.
"""

import numpy as np
import pytest

from distributed_optimization_tpu.backends import jax_backend, numpy_backend
from distributed_optimization_tpu.config import ExperimentConfig
from distributed_optimization_tpu.observability import monitors
from distributed_optimization_tpu.parallel import build_topology
from distributed_optimization_tpu.parallel.adversary import (
    attackers_per_honest_neighbourhood,
    byzantine_mask,
    byzantine_set,
    place_within_budget,
)
from distributed_optimization_tpu.parallel.topology import neighbor_tables_for
from distributed_optimization_tpu.scenarios import validity
from distributed_optimization_tpu.utils.data import generate_synthetic_dataset

GRAPHS = {
    "ring": lambda: build_topology("ring", 4096, impl="neighbor"),
    "torus": lambda: build_topology("grid", 64 * 64, impl="neighbor"),
    "erdos_renyi": lambda: build_topology(
        "erdos_renyi", 2048, erdos_renyi_p=12 / 2048, seed=7, impl="neighbor",
        sampler="sparse"),
    "dense_ring": lambda: build_topology("ring", 64),  # tables off the [N, N] matrix
}

CFG = ExperimentConfig(
    n_workers=16, n_samples=480, n_features=10, n_informative_features=6,
    n_iterations=40, local_batch_size=10, problem_type="quadratic",
    algorithm="dsgd", topology="ring", eval_every=10, dtype="float64",
    partition="shuffled", attack="sign_flip", n_byzantine=3, attack_scale=2.0,
    aggregation="trimmed_mean", robust_b=1, byzantine_placement="within_budget",
)


@pytest.mark.parametrize("budget", [1, 2])
@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_every_honest_worker_keeps_at_most_b_attacking_neighbours(graph, budget):
    topo = GRAPHS[graph]()
    nbr, mask = neighbor_tables_for(topo)
    f = topo.n // (40 if graph == "erdos_renyi" else 16)  # a denser graph holds fewer
    for seed in (0, 1, 2147483000):
        byz = place_within_budget(nbr, mask, f, budget, seed)
        assert byz.dtype == bool and byz.sum() == f
        assert attackers_per_honest_neighbourhood(byz, nbr, mask) <= budget
        # the invariant, from the definition: a loop over honest rows
        for i in np.flatnonzero(~byz)[:512]:
            assert byz[nbr[i][mask[i]]].sum() <= budget


def test_a_uniform_draw_at_the_same_share_breaks_the_budget():
    """f²/N honest workers of a ring sit between two attackers in
    expectation: 24 at 6 in 64 of 4,096, none under ``within_budget``."""
    topo = GRAPHS["ring"]()
    nbr, mask = neighbor_tables_for(topo)
    counts = [attackers_per_honest_neighbourhood(byzantine_mask(4096, 384, s), nbr, mask)
              for s in range(5)]
    assert counts == [2] * 5


def test_the_set_is_a_function_of_seed_graph_count_and_budget():
    topo = GRAPHS["erdos_renyi"]()
    nbr, mask = neighbor_tables_for(topo)
    base = place_within_budget(nbr, mask, 50, 1, 11)
    np.testing.assert_array_equal(base, place_within_budget(nbr, mask, 50, 1, 11))
    assert not np.array_equal(base, place_within_budget(nbr, mask, 50, 1, 12))
    assert not np.array_equal(base, place_within_budget(nbr, mask, 50, 2, 11))
    # a larger count extends the smaller one's set: the order is the seed's
    more = place_within_budget(nbr, mask, 60, 1, 11)
    assert np.all(more[base])
    other, other_mask = neighbor_tables_for(GRAPHS["ring"]())
    assert not np.array_equal(
        place_within_budget(other, other_mask, 50, 1, 11)[:2048], base)
    # a padded slot points at the worker itself and constrains nothing
    assert not mask.all()


def test_uniform_is_the_draw_the_package_always_made():
    for n, f, seed in [(64, 6, 203), (16, 5, 0), (4096, 384, 2147483000)]:
        cfg = ExperimentConfig(
            n_workers=n, n_samples=8 * n, attack="sign_flip", n_byzantine=f, seed=seed)
        assert cfg.byzantine_placement == "uniform"
        want = np.zeros(n, dtype=bool)
        want[np.random.default_rng([seed, 0xB12A]).choice(n, size=f, replace=False)] = True
        np.testing.assert_array_equal(byzantine_set(cfg), want)
        np.testing.assert_array_equal(byzantine_mask(n, f, seed), want)
        np.testing.assert_array_equal(byzantine_set(cfg, seed=seed + 1),
                                      byzantine_mask(n, f, seed + 1))


def test_an_infeasible_share_raises_with_the_graphs_limit():
    topo = GRAPHS["erdos_renyi"]()
    nbr, mask = neighbor_tables_for(topo)
    with pytest.raises(ValueError, match=r"placed (\d+) of the 2000 .* limit under this order is \1"):
        place_within_budget(nbr, mask, 2000, 1, 3)
    with pytest.raises(ValueError, match="robust_b >= 1"):
        place_within_budget(nbr, mask, 10, 0, 3)
    with pytest.raises(ValueError, match="n_byzantine must be in"):
        place_within_budget(nbr, mask, 2048, 1, 3)
    # through the program: the run is refused before anything is compiled
    cfg = CFG.replace(n_byzantine=15)
    with pytest.raises(ValueError, match="limit under this order"):
        jax_backend.run(cfg, generate_synthetic_dataset(cfg), 0.0)


@pytest.mark.parametrize("fields,match", [
    (dict(byzantine_placement="nearest"), "byzantine placement"),
    (dict(byzantine_placement="within_budget", aggregation="gossip", robust_b=0), "needs attackers"),
    (dict(byzantine_placement="within_budget", robust_b=0), "needs attackers"),
    (dict(byzantine_placement="within_budget", attack="none", n_byzantine=0, attack_scale=1.0),
     "needs attackers"),
])
def test_a_placement_without_its_budget_is_refused_at_construction(fields, match):
    with pytest.raises(ValueError, match=match):
        CFG.replace(**fields)
    verdict = validity.explain({**CFG.to_dict(), **fields})
    assert not verdict.valid and "byzantine" in verdict.axes


def test_the_cli_takes_the_field():
    from distributed_optimization_tpu.cli import build_parser, config_from_args

    args = build_parser().parse_args([
        "--topology", "ring", "--n-workers", "64", "--attack", "sign_flip",
        "--n-byzantine", "6", "--attack-scale", "5", "--aggregation", "trimmed_mean",
        "--robust-b", "1", "--byzantine-placement", "within_budget"])
    assert config_from_args(args).byzantine_placement == "within_budget"
    with pytest.raises(SystemExit):
        build_parser().parse_args(["--byzantine-placement", "nearest"])


def test_jax_numpy_and_the_forensics_name_the_same_set():
    ds = generate_synthetic_dataset(CFG)
    want = np.flatnonzero(byzantine_set(CFG))
    assert len(want) == 3
    assert not np.array_equal(want, np.flatnonzero(byzantine_mask(16, 3, CFG.seed)))
    block = monitors.fault_context(CFG, onset=20)["attack"]
    assert block["byzantine_nodes"] == want.tolist()
    rng = np.random.default_rng(0)
    sched = np.stack([np.stack([rng.choice(30, size=10, replace=False) for _ in range(16)])
                      for _ in range(CFG.n_iterations)])
    rj = jax_backend.run(CFG, ds, 0.0, batch_schedule=sched)
    rn = numpy_backend.run(CFG, ds, 0.0, batch_schedule=sched)
    np.testing.assert_allclose(rj.final_models, rn.final_models, rtol=1e-9, atol=1e-10)
    np.testing.assert_allclose(rj.history.objective, rn.history.objective, rtol=1e-8)
    # honest-only metrics: both left the same rows out
    np.testing.assert_allclose(
        rj.history.consensus_error, rn.history.consensus_error, rtol=1e-8, atol=1e-12)


def test_run_batch_places_each_replicas_set_within_the_budget():
    """Replica r is the sequential run of ``seed=s``: its attackers are that
    seed's placement on the one graph the batch shares."""
    ds = generate_synthetic_dataset(CFG)
    seeds = [203, 500]
    assert not np.array_equal(byzantine_set(CFG, seed=203), byzantine_set(CFG, seed=500))
    batch = jax_backend.run_batch(CFG, ds, 0.0, seeds=seeds)
    for r, s in enumerate(seeds):
        seq = jax_backend.run(CFG.replace(seed=s), ds, 0.0)
        np.testing.assert_allclose(
            batch.results[r].final_models, seq.final_models, rtol=1e-12, atol=1e-12)
        np.testing.assert_allclose(
            batch.results[r].history.objective, seq.history.objective, rtol=1e-12)
