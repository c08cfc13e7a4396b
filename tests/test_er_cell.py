"""The first graph that is not a shift (ISSUE 36) at a size a test run can
hold: the program on a connected Erdős–Rényi graph through its normal path
(the sparse draw, the neighbor table, gather mixing over the live slots'
chunk list, tables the scan is HANDED), against the benchmark's plain reference
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
from distributed_optimization_tpu.ops import mixing  # noqa: E402
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
    # a small graph: one chunk a slot, the chunk the 64 rows; and the sums'
    # way back from the degree order
    assert args["gathered_rows"] == k_max * 64 + 64
    # nbr s32 and w_nbr f32, each [k_max, 64], row0 s32[k_max], w_self
    # f32[64] and inverse s32[64]
    assert args["table_bytes"] == (2 * k_max * 64 + k_max + 2 * 64) * 4
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


def padded_table(topo):
    """The table as PR 36's round walked it, padded slots and all, with its
    weights: node-major host arrays ``nbr``, ``mask``, ``w_nbr`` ``[n,
    k_max]`` and ``w_self`` ``[n]``."""
    nbr, mask = topology.neighbor_tables_for(topo)
    return (nbr, mask, *topology.gather_mixing_weights(nbr, mask, topo.degrees))


@pytest.mark.parametrize("rank", [2, 3])
def test_the_gather_form_is_the_dense_matrix(rank, monkeypatch):
    """On a drawn graph with padded slots and a node of degree 1, its slots'
    runs cut into several chunks: the loop over the live list is ``W @ x``
    of ``metropolis_hastings_weights``, ``neighbor_sum`` is ``A @ x``, both
    are EQUAL to the loop over the padded slot-major tables (every row's
    terms added in the table's slot order), and a rebinding over other
    leaves reads those."""
    n, p, seed = 128, 0.035, 3
    monkeypatch.setattr(topology, "GATHER_CHUNK_ROWS", 32)
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
    assert set(op.tables) == {"nbr", "w_nbr", "row0", "w_self", "inverse"}
    chunks = sum(-(-int((topo.degrees > s).sum()) // 32) for s in range(k_max))
    assert k_max < chunks < 4 * k_max
    assert op.tables["nbr"].shape == op.tables["w_nbr"].shape == (chunks, 32)
    assert op.tables["row0"].shape == (chunks,)
    assert op.tables["w_self"].shape == op.tables["inverse"].shape == (n,)
    x = np.random.default_rng(1).standard_normal((n, 7, 3)[:rank]).astype(np.float32)
    flat = x.reshape(n, -1).astype(np.float64)
    # slot-major, as ``ops.mixing.slot_sum`` reads them
    nbr, _, w_nbr, w_self = padded_table(topo)
    nbr, w_nbr, w_self = (jnp.asarray(nbr.T), jnp.asarray(w_nbr.T, jnp.float32),
                          jnp.asarray(w_self, jnp.float32))
    padded = {
        "apply": lambda x: mixing._col(w_self, x) * x + mixing.slot_sum(x, nbr, w_nbr),
        "neighbor_sum": lambda x: mixing.slot_sum(
            x, nbr, w_nbr, lambda w: (w > 0).astype(x.dtype)),
    }
    for form, matrix in (("apply", W), ("neighbor_sum", adjacency)):
        got = np.asarray(jax.jit(getattr(op, form))(jnp.asarray(x)))
        assert got.dtype == np.float32 and got.shape == x.shape
        np.testing.assert_allclose(got.reshape(n, -1), matrix @ flat, atol=3e-6)
        np.testing.assert_array_equal(got, np.asarray(jax.jit(padded[form])(jnp.asarray(x))))
    # bound over other leaves (here: no neighbour weighs anything), as a
    # program's arguments are
    silent = dict(op.tables, w_nbr=jnp.zeros_like(op.tables["w_nbr"]),
                  w_self=jnp.ones_like(op.tables["w_self"]))
    np.testing.assert_array_equal(np.asarray(op.bind(silent).apply(jnp.asarray(x))), x)


def test_a_round_is_one_loop_over_the_slots_whatever_k_max():
    """The first chunk's term, then ONE loop over the rest of the live
    list, one gather in its body, and one gather that puts the sums back in
    the workers' order, for a chain's two slots as for a drawn graph's
    twenty and more: one program shape, whose temporaries do not grow with
    k_max. A regular graph's degree order is the workers' own: no gather
    after the loop."""
    x = jnp.zeros((64, 5), jnp.float32)
    chain = make_mixing_op(topology.build_neighbor_topology("chain", 64), impl="gather")
    drawn = make_mixing_op(topology.build_neighbor_topology(
        "erdos_renyi", 64, erdos_renyi_p=0.2, seed=7, sampler="sparse"), impl="gather")
    torus = make_mixing_op(topology.build_neighbor_topology("grid", 64), impl="gather")
    assert chain.tables["nbr"].shape[0] == 2 and drawn.tables["nbr"].shape[0] > 20
    assert "inverse" not in torus.tables and torus.tables["nbr"].shape == (4, 64)
    gathers = re.compile(r"= \"stablehlo\.gather\"\(")
    for op, after_the_loop in ((chain, 1), (drawn, 1), (torus, 0)):
        text = jax.jit(op.apply).lower(x).as_text()
        assert text.count("stablehlo.while") == 1
        # the first chunk's, the loop's, and the way back
        assert len(gathers.findall(text)) == 2 + after_the_loop


def live_pairs(topo):
    nbr, mask, w_nbr, _ = padded_table(topo)
    rows, slots = np.nonzero(mask)
    return sorted(zip(slots.tolist(), rows.tolist(), nbr[mask].tolist(), w_nbr[mask].tolist()))


@pytest.mark.parametrize("n,p,seed,chunk", [
    (128, 0.035, 3, 32), (500, 0.02, 2147483999, 64), (200, 0.05, 11, 16384), (301, 0.04, 5, 100)])
def test_the_chunk_list_is_the_live_slots_once(n, p, seed, chunk, monkeypatch):
    """Every live (slot, row) pair of the table stands in the chunk list
    once, with its neighbour and its weight, in its own row of the degree
    order, slot after slot; every other entry weighs 0; a slot takes the
    chunks its live rows need and no more."""
    monkeypatch.setattr(topology, "GATHER_CHUNK_ROWS", chunk)
    topo = topology.build_neighbor_topology(
        "erdos_renyi", n, erdos_renyi_p=p, seed=seed, sampler="sparse")
    tb = topo.gather_chunks
    C = topology.gather_chunk_rows(n)
    assert C <= chunk and -(-n // C) == -(-n // min(n, chunk))
    assert tb["nbr"].shape == tb["w_nbr"].shape == (tb["row0"].size, C)
    assert tb["nbr"].dtype == tb["row0"].dtype == tb["inverse"].dtype == np.int32
    order = np.argsort(tb["inverse"])
    degrees = np.asarray(topo.degrees)[order]
    assert np.all(np.diff(degrees) <= 0) and sorted(order) == list(range(n))
    # a chunk's slot: the runs come slot after slot, each from row 0 on
    slot = np.cumsum(tb["row0"] == 0) - 1
    per_slot = np.bincount(slot)
    assert per_slot.tolist() == [-(-int((topo.degrees > s).sum()) // C) for s in range(len(per_slot))]
    assert np.all(tb["row0"] % C == 0) and np.all(tb["row0"] + C <= -(-n // C) * C)
    place = tb["row0"][:, None] + np.arange(C)
    live = tb["w_nbr"] > 0
    assert place[live].max() < n and tb["nbr"].min() >= 0 and tb["nbr"].max() < n
    got = sorted(zip(np.broadcast_to(slot[:, None], live.shape)[live].tolist(),
                     order[place[live]].tolist(), tb["nbr"][live].tolist(),
                     tb["w_nbr"][live].tolist()))
    assert got == live_pairs(topo)
    assert not tb["w_nbr"][~live].any()
    with pytest.raises(ValueError):
        tb["nbr"][0, 0] = 1


def test_a_table_with_a_hole_in_a_row_is_refused():
    """The prefix rule rests on every row listing its live slots first."""
    topo = topology.build_neighbor_topology("chain", 8)
    mask = topo.nbr_mask.copy()
    mask[3] = [False, True]
    with pytest.raises(ValueError, match="live slots first"):
        topology.live_slot_chunks(topo.nbr_idx, mask, mask.sum(axis=1))


def test_the_pinned_graphs_chunks():
    """The cell's own graph, drawn as its configuration file pins it (2 s):
    the edges and width the file states, the live list in 209 chunks of
    16,384 rows, so a round gathers 3.42 M rows where the padded table is
    7.86 M, and 2^18 more on the sums' way back."""
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as fh:
        whole = json.load(fh)
    graph, n = whole["graph"], whole["experiment"]["n_workers"]
    topo = topology.build_neighbor_topology(
        "erdos_renyi", n, erdos_renyi_p=whole["experiment"]["erdos_renyi_p"],
        seed=graph["topology_seed"], sampler=graph["sampler"])
    assert int(topo.degrees.sum()) == 2 * graph["edges"]
    assert topo.nbr_idx.shape == (n, graph["k_max"])
    tb = topo.gather_chunks
    assert tb["nbr"].shape == (209, 16384) and tb["inverse"].shape == (n,)
    assert int((tb["w_nbr"] > 0).sum()) == 2 * graph["edges"]
    assert tb["nbr"].size == 3_424_256 < 0.44 * n * graph["k_max"]


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
    # one chunk a slot at this size, and the one gather beside the loop's
    assert args["gathered_rows"] == (args["k_max"] + 1) * n
    assert f"s32[{args['k_max']},{n}]" in entry and f"f32[{args['k_max']},{n}]" in entry
    assert f"s32[{args['k_max']}]" in entry and f"s32[{n}]" in entry
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
