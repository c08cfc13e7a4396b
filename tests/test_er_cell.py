"""The first graph that is not a shift (ISSUE 36) at a size a test run can
hold: the program on a connected Erdős–Rényi graph through its normal path
(the sparse draw, the neighbor table, gather mixing slot by slot over tables
the scan is HANDED), against the benchmark's plain reference
(``benchmark/reference/dsgd_er.py``: the documented sampler restated, the
mixing on the edge list, nothing of the package), by the limits of the
cell's own configuration file; the gather form against the dense matrix; the
scan's program with no table among its constants; one graph a structural
identity a process. CPU, N = 64, T = 40: numbers against limits, never a
time.
"""

import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, datasets, program  # noqa: E402
from benchmark import run as harness  # noqa: E402
from benchmark.reference import dsgd_er  # noqa: E402

from distributed_optimization_tpu.observability.spans import Tracer  # noqa: E402
from distributed_optimization_tpu.ops.mixing import make_mixing_op  # noqa: E402
from distributed_optimization_tpu.parallel import topology  # noqa: E402

NAME, MIX = "glm81_er262k_deg12", "steady300"
SEEDS = [3, 4, 2147483999]


@pytest.fixture(scope="module")
def cell():
    """(config, traffic) at the files' rehearsal sizes: 64 workers of 24
    rows, p = 0.2 by the sparse sampler on the neighbor table, 40
    iterations, the check following 12."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    _, config, traffic = harness.load_cell(bench, f"{NAME}.{MIX}", rehearse=True)
    exp = config["experiment"]
    assert (exp["n_workers"], exp["erdos_renyi_p"]) == (64, 0.2)
    assert (exp["topology_impl"], exp["topology_sampler"]) == ("neighbor", "sparse")
    assert (traffic["n_iterations"], traffic["check_iterations"]) == (40, 12)
    return config, traffic


@pytest.fixture()
def no_graphs_kept():
    """The process's graph cache, empty before and after."""
    topology._TOPOLOGY_CACHE.clear()
    yield topology._TOPOLOGY_CACHE
    topology._TOPOLOGY_CACHE.clear()


def run_program(config, traffic, seed, **replace):
    X, y, L = datasets.make(config, seed)
    cfg, dataset = program.build(config, traffic, X, y, L, program.seed_for(seed))
    if replace:
        cfg = cfg.replace(**replace)
    tracer = Tracer()
    with tracer.activate():
        result = program.run_experiment(cfg, dataset)
    events = tracer.spans()
    (root,) = [e for e in events if e["name"] == "dopt.run"]
    (drawn,) = [e for e in events if e["name"] == "dopt.run.topology"]
    return result, root["args"], drawn, (X, y, program.seed_for(seed))


def judged(produced, ref, config):
    said = []
    ok = compare.judge(compare.numbers(produced, ref), config["limits"][MIX], said.append)
    return ok, said


@pytest.mark.parametrize("seed", SEEDS)
def test_the_program_is_within_the_cells_limits(cell, seed):
    """Objective and consensus at every evaluation the reference follows, by
    the cell's limits, and the root says what mixed: the gather, with the
    table's own counters."""
    config, traffic = cell
    result, args, _, (X, y, pseed) = run_program(config, traffic, seed)
    src, dst, _ = dsgd_er.draw_edges(64, 0.2, config["experiment"]["topology_seed"])
    k_max = int(np.bincount(np.concatenate([src, dst])).max())
    assert args["mixing"] == "gather"
    assert (args["k_max"], args["edges"]) == (k_max, src.size)
    assert args["live_slot_share"] == pytest.approx(2 * src.size / (64 * k_max))
    # nbr s32 and w_nbr f32, each [k_max, 64], and w_self f32[64]
    assert args["table_bytes"] == (2 * k_max + 1) * 64 * 4
    ref = dsgd_er.run(config, traffic, X, y, pseed)
    ok, said = judged(harness.produced_of(result), ref, config)
    assert ok, said
    assert not harness.gate_failures(result, traffic)


@pytest.mark.parametrize("control", ["bfloat16", "max_degree_weights"])
@pytest.mark.parametrize("seed", SEEDS[:2])
def test_each_control_is_not_correct(cell, control, seed):
    """The reference computed another way, in the program's place, against
    the cell's own limits: the precision below the stated one, and another
    doubly stochastic matrix on the same graph, are each over at least one."""
    config, traffic = cell
    assert control == config["precision"]["control"] or control in config["mixing_controls"]
    X, y, _ = datasets.make(config, seed)
    ref = dsgd_er.run(config, traffic, X, y, seed)
    how = dict(precision=control) if control == "bfloat16" else dict(weights=control)
    ok, said = judged(dsgd_er.run(config, traffic, X, y, seed, **how), ref, config)
    assert not ok, said


# (n, p, seed): the last two need more than one try to come out connected.
DRAWS = [(64, 0.2, 7), (200, 0.05, 11), (500, 0.02, 2147483999),
         (64, 0.06, 5), (128, 0.035, 3)]


@pytest.mark.parametrize("n,p,seed", DRAWS)
def test_the_reference_restates_the_sampler(n, p, seed):
    """Edge for edge the package's sparse draw, retries included, and the
    weights on it are the matrix's."""
    want_src, want_dst = topology._erdos_renyi_forward_edges_sparse(n, p, seed)
    src, dst, tries = dsgd_er.draw_edges(n, p, seed)
    np.testing.assert_array_equal(src, want_src)
    np.testing.assert_array_equal(dst, want_dst)
    assert tries >= 1 and np.all(src < dst)
    adjacency = np.zeros((n, n))
    adjacency[src, dst] = adjacency[dst, src] = 1.0
    W = topology.metropolis_hastings_weights(adjacency)
    np.testing.assert_allclose(
        dsgd_er.edge_weights(src, dst, n), W[src, dst].astype(np.float32), rtol=1e-7)


def test_some_draw_above_took_a_retry():
    assert max(dsgd_er.draw_edges(n, p, seed)[2] for n, p, seed in DRAWS) > 1


@pytest.mark.parametrize("weights", dsgd_er.WEIGHTS)
def test_the_references_mixing_is_its_matrix(weights):
    """x + both directions of w_e (x_j - x_i) is W x for the W it states;
    both matrices are doubly stochastic, and they differ."""
    n = 96
    src, dst, _ = dsgd_er.draw_edges(n, 0.08, 21)
    w = dsgd_er.edge_weights(src, dst, n, weights)
    W = np.zeros((n, n))
    W[src, dst] = W[dst, src] = w
    W[np.arange(n), np.arange(n)] = 1.0 - W.sum(axis=1)
    np.testing.assert_allclose(W.sum(axis=0), 1.0, atol=1e-12)
    x = np.random.default_rng(0).standard_normal((n, 9)).astype(np.float32)
    blocks = dsgd_er.edge_blocks(src, dst, w, block=64)
    assert blocks[0].shape[1] == 64 and blocks[0].shape[0] > 1
    got = np.asarray(dsgd_er.mix(jnp.asarray(x), *blocks))
    np.testing.assert_allclose(got, W @ x.astype(np.float64), atol=2e-6)
    other = dsgd_er.edge_weights(src, dst, n, [v for v in dsgd_er.WEIGHTS if v != weights][0])
    assert np.abs(other - w).max() > 1e-3


@pytest.mark.parametrize("rank", [2, 3])
def test_the_gather_form_is_the_dense_matrix(rank):
    """On a drawn graph with padded slots and a node of degree 1: slot by
    slot over the slot-major tables is ``W @ x`` of
    ``metropolis_hastings_weights``, ``neighbor_sum`` is ``A @ x``, and a
    rebinding over other leaves reads those."""
    n, p, seed = 128, 0.035, 3
    topo = topology.build_neighbor_topology(
        "erdos_renyi", n, erdos_renyi_p=p, seed=seed, sampler="sparse")
    assert topo.degrees.min() == 1 and not topo.nbr_mask.all()
    k_max = topo.nbr_idx.shape[1]
    assert k_max > 4  # wider than a lattice: the loop over slots
    adjacency = np.zeros((n, n))
    adjacency[np.repeat(np.arange(n), k_max)[topo.nbr_mask.ravel()],
              topo.nbr_idx[topo.nbr_mask]] = 1.0
    W = topology.metropolis_hastings_weights(adjacency)
    op = make_mixing_op(topo, impl="gather")
    assert set(op.tables) == {"nbr", "w_nbr", "w_self"}
    assert op.tables["nbr"].shape == op.tables["w_nbr"].shape == (k_max, n)
    x = np.random.default_rng(1).standard_normal((n, 7, 3)[:rank]).astype(np.float32)
    flat = x.reshape(n, -1).astype(np.float64)
    for fn, matrix in ((op.apply, W), (op.neighbor_sum, adjacency)):
        got = np.asarray(jax.jit(fn)(jnp.asarray(x)))
        assert got.dtype == np.float32 and got.shape == x.shape
        np.testing.assert_allclose(got.reshape(n, -1), matrix @ flat, atol=3e-6)
    # bound over other leaves (here: no neighbour weighs anything), as a
    # program's arguments are
    silent = dict(op.tables, w_nbr=jnp.zeros_like(op.tables["w_nbr"]),
                  w_self=jnp.ones_like(op.tables["w_self"]))
    np.testing.assert_array_equal(np.asarray(op.bind(silent).apply(jnp.asarray(x))), x)


def test_a_round_is_one_loop_over_the_slots_whatever_k_max():
    """The first slot's term and a loop over the rest, one gather in its
    body, for a chain's two slots as for a drawn graph's twenty and more:
    one program shape, whose temporaries do not grow with k_max."""
    x = jnp.zeros((64, 5), jnp.float32)
    chain = make_mixing_op(topology.build_neighbor_topology("chain", 64), impl="gather")
    drawn = make_mixing_op(topology.build_neighbor_topology(
        "erdos_renyi", 64, erdos_renyi_p=0.2, seed=7, sampler="sparse"), impl="gather")
    assert chain.tables["nbr"].shape[0] == 2 and drawn.tables["nbr"].shape[0] > 20
    gathers = re.compile(r"= \"stablehlo\.gather\"\(")
    for op in (chain, drawn):
        text = jax.jit(op.apply).lower(x).as_text()
        assert "stablehlo.while" in text and len(gathers.findall(text)) == 2


def test_the_scans_program_holds_no_table(cell, no_graphs_kept):
    """The compiled scan names the tables among its arguments and holds no
    constant over 1 MB: at a size where each table is 1.7 MB, through the
    normal path."""
    from distributed_optimization_tpu.observability import device_scopes

    config, traffic = cell
    n = 16384  # a [k_max, n] table is over 1 MB: a constant of it would show
    config = dict(config, experiment=dict(
        config["experiment"], n_workers=n, erdos_renyi_p=12 / n),
        dataset=dict(config["dataset"], rows_per_worker=4))
    traffic = dict(traffic, n_iterations=2, check_iterations=2)
    result, args, _, _ = run_program(config, traffic, 5)
    assert args["mixing"] == "gather" and args["table_bytes"] > 2**21
    text = device_scopes._programs[args["program"]]["executable"]().as_text()
    entry = text[text.index("ENTRY"):].split("\n", 1)[0]
    assert f"s32[{args['k_max']},{n}]" in entry and f"f32[{args['k_max']},{n}]" in entry
    constants = [
        device_scopes._shape_bytes(ins[1])
        for ins in map(device_scopes._instruction, text.splitlines())
        if ins is not None and ins[2] == "constant"
    ]
    assert constants and max(constants) < 2**20, max(constants)


def test_one_graph_a_structural_identity(cell, no_graphs_kept):
    """Two calls of one identity draw the graph once (``miss`` then ``hit``)
    and give bitwise the same rows; another topology seed misses; the data's
    seed is no part of the identity."""
    config, traffic = cell
    first, _, drawn, _ = run_program(config, traffic, 3)
    assert drawn["args"]["cache"] == "miss" and len(no_graphs_kept) == 1
    again, _, drawn, _ = run_program(config, traffic, 3)
    assert drawn["args"]["cache"] == "hit" and len(no_graphs_kept) == 1
    for a, b in zip(harness.produced_of(first).values(), harness.produced_of(again).values()):
        np.testing.assert_array_equal(a, b)
    _, _, drawn, _ = run_program(config, traffic, 4)
    assert drawn["args"]["cache"] == "hit"
    other, _, drawn, _ = run_program(config, traffic, 3, topology_seed=8)
    assert drawn["args"]["cache"] == "miss" and len(no_graphs_kept) == 2
    assert not np.array_equal(other.history.consensus_error, first.history.consensus_error)


def test_a_kept_graph_is_the_one_a_fresh_build_gives(no_graphs_kept):
    """``cached_topology`` is ``build_topology``: the same tables, read-only
    once kept, the few most recent kept, a ring one graph whatever seed or p
    it is asked with, a graph that holds its matrices never kept."""
    kw = dict(erdos_renyi_p=0.2, impl="neighbor", sampler="sparse")
    kept, hit = topology.cached_topology("erdos_renyi", 64, seed=7, **kw)
    fresh = topology.build_topology("erdos_renyi", 64, seed=7, **kw)
    assert not hit and topology.cached_topology("erdos_renyi", 64, seed=7, **kw) == (kept, True)
    np.testing.assert_array_equal(kept.nbr_idx, fresh.nbr_idx)
    np.testing.assert_array_equal(kept.nbr_mask, fresh.nbr_mask)
    assert kept.spectral_gap == fresh.spectral_gap
    with pytest.raises(ValueError):
        kept.nbr_idx[0, 0] = 1
    assert not topology.cached_topology("erdos_renyi", 64, seed=7, **dict(kw, sampler="dense"))[1]
    ring, _ = topology.cached_topology("ring", 64, seed=1, erdos_renyi_p=0.1, impl="neighbor")
    assert topology.cached_topology("ring", 64, seed=2, erdos_renyi_p=0.3, impl="neighbor") == (ring, True)
    for seed in range(topology._TOPOLOGY_CACHE_MAX + 2):
        topology.cached_topology("erdos_renyi", 32, seed=seed, erdos_renyi_p=0.3, impl="neighbor")
    assert len(no_graphs_kept) == topology._TOPOLOGY_CACHE_MAX
    # a graph with its [N, N] matrices is never kept: made anew, its caller's to write
    for _ in range(2):
        dense, hit = topology.cached_topology("erdos_renyi", 32, seed=0, erdos_renyi_p=0.3)
        assert not hit and not dense.is_matrix_free and dense.mixing_matrix.flags.writeable
    assert all(t.is_matrix_free for t in no_graphs_kept.values())


def test_the_file_states_what_the_cell_runs():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as fh:
        whole = json.load(fh)
    with open(os.path.join(ROOT, "benchmark", "configs", "glm81_ring262k.json")) as fh:
        sibling = json.load(fh)
    # the sibling's experiment but for the graph, at the sibling's size
    n = sibling["experiment"]["n_workers"]
    assert whole["experiment"] == dict(
        sibling["experiment"], topology="erdos_renyi", erdos_renyi_p=12 / n,
        topology_seed=whole["graph"]["topology_seed"])
    assert whole["dataset"] == sibling["dataset"] and whole["precision"] == sibling["precision"]
    assert whole["architecture"] is None and whole["chips"] == 1
    assert set(whole["reduced"]) == set(whole["reduced_why"]) and len(whole["guarantees"]) >= 5
    graph = whole["graph"]
    assert 2 * graph["edges"] / n == pytest.approx(12, rel=0.01)
    assert graph["k_max"] * n <= topology.NEIGHBOR_TABLE_MAX_CELLS
    text = open(dsgd_er.__file__).read().replace("from ", "import ")
    assert "import distributed_optimization_tpu" not in text
