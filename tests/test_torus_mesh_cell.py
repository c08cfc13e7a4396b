"""The study's toroidal grid across four chips (ISSUE 52) at sizes a test run
can hold, on the forced host devices: the program at ``worker_mesh=4`` on
8 x 8 and 16 x 16 tori blocked by grid rows, whose gossip is the halo GATHER
(planned ``ppermute`` rotations of whole grid rows, the per-shard neighbor
table over the halo-extended block), against the benchmark's plain reference
(``benchmark/reference/dsgd_torus_blocks.py``: the state whole on one device,
the torus four ``jnp.roll``s; no ``shard_map``, no collective, no table), by
the limits of the cell's own configuration file. CPU: what is checked is
numbers against limits, never a time.
"""

import json
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, datasets, program  # noqa: E402
from benchmark import run as harness  # noqa: E402
from benchmark.reference import dsgd_torus_blocks  # noqa: E402
from conftest import assert_ulps_of_scale  # noqa: E402

from distributed_optimization_tpu.observability.spans import Tracer  # noqa: E402
from distributed_optimization_tpu.parallel import topology  # noqa: E402

NAME, MIX = "glm81_torus1m_mesh4", "grid1k"
SEEDS = [3, 4, 2147483999]
SIDES = [8, 16]


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as fh:
        return json.load(fh)


def cell_at(side):
    """(config, traffic) at the files' rehearsal sizes, the torus ``side``
    square: 24 rows a worker over four devices, 40 iterations, the check
    following 12."""
    bench = load("..", "BENCHMARK.json")
    _, config, traffic = harness.load_cell(bench, f"{NAME}.{MIX}", rehearse=True)
    assert config["experiment"]["n_workers"] == 64
    assert config["experiment"]["worker_mesh"] == 4
    config["experiment"]["n_workers"] = side * side
    return config, traffic


def run_program(config, traffic, seed, **replace):
    X, y, L = datasets.make(config, seed)
    cfg, dataset = program.build(config, traffic, X, y, L, program.seed_for(seed))
    tracer = Tracer()
    with tracer.activate():
        result = program.run_experiment(cfg.replace(**replace), dataset)
    (root,) = [e for e in tracer.spans() if e["name"] == "dopt.run"]
    return result, root["args"], tracer, (X, y)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("side", SIDES)
def test_the_sharded_program_is_within_the_cells_limits(side, seed):
    config, traffic = cell_at(side)
    result, args, _, (X, y) = run_program(config, traffic, seed)
    ref = dsgd_torus_blocks.run(config, traffic, X, y, program.seed_for(seed))
    assert result.history.mesh_devices == 4
    assert (args["mesh"], args["mixing"]) == (f"4x{side * side // 4}", "halo_gather")
    assert args["grid_shape"] == f"{side}x{side}"
    said = []
    nums = compare.numbers(harness.produced_of(result), ref)
    assert compare.judge(nums, config["limits"][MIX], said.append), said
    assert not harness.gate_failures(result, traffic)


@pytest.mark.parametrize("mixing", dsgd_torus_blocks.MIXINGS)
@pytest.mark.parametrize("precision", ["reference", "bfloat16"])
def test_the_blocked_reference_is_the_unblocked_one(precision, mixing):
    """Cutting the shards into blocks changes where the numbers lie, not
    one of them: every row the same reference gives with its shards in ONE
    block, bit for bit."""
    config, traffic = cell_at(8)
    assert 64 % config["reference_blocks"] == 0 < config["reference_blocks"] != 1
    X, y, _ = datasets.make(config, 3)
    got = dsgd_torus_blocks.run(config, traffic, X, y, 3, precision=precision, mixing=mixing)
    want = dsgd_torus_blocks.run(
        dict(config, reference_blocks=1), traffic, X, y, 3, precision=precision, mixing=mixing)
    assert want["objective"].shape == (traffic["check_iterations"],)
    np.testing.assert_array_equal(got["objective"], want["objective"])
    np.testing.assert_array_equal(got["consensus"], want["consensus"])


def test_the_references_mixing_is_the_toruss_matrix():
    """Four rolls of the state viewed [R, C, D] are W x for the dense
    Metropolis-Hastings matrix of networkx's periodic grid (the study's
    ``grid_2d_graph(periodic=True)``), and the two graph controls are other
    matrices: symmetric and doubly stochastic both."""
    import networkx as nx

    side, d = 5, 3
    n = side * side
    x = np.random.default_rng(0).standard_normal((n, d)).astype(np.float32)

    def matrix(graph):
        A = nx.to_numpy_array(graph, nodelist=sorted(graph.nodes()))
        deg = A.sum(1)
        W = A / (1.0 + np.maximum(deg[:, None], deg[None, :]))
        return W + np.diag(1.0 - W.sum(1))

    torus = matrix(nx.grid_2d_graph(side, side, periodic=True))
    got = np.asarray(dsgd_torus_blocks.mix(jax.numpy.asarray(x), (side, side)))
    np.testing.assert_allclose(got, torus @ x, rtol=0, atol=1e-6)
    # the wrap's edges cut, each end keeping the 1/5 (not the cylinder's own
    # Metropolis-Hastings matrix, whose open rows would weigh 1/4)
    cylinder = torus.copy()
    for c in range(side):
        i, j = c, (side - 1) * side + c
        cylinder[i, j] = cylinder[j, i] = 0.0
        cylinder[i, i] += 0.2
        cylinder[j, j] += 0.2
    got = np.asarray(dsgd_torus_blocks.mix(jax.numpy.asarray(x), (side, side), "no_wrap"))
    np.testing.assert_allclose(got, cylinder @ x, rtol=0, atol=1e-6)
    rings = np.kron(np.eye(side), matrix(nx.cycle_graph(side)))
    got = np.asarray(dsgd_torus_blocks.mix(jax.numpy.asarray(x), (side, side), "ring_mix"))
    np.testing.assert_allclose(got, rings @ x, rtol=0, atol=1e-6)
    for W in (cylinder, rings):
        np.testing.assert_allclose(W, W.T)
        np.testing.assert_allclose(W.sum(0), 1.0)
        assert np.abs(W - torus).max() > 0.1


@pytest.mark.parametrize("side", SIDES)
def test_sharded_against_unsharded_at_matched_n(side):
    """``worker_mesh`` 4 against none, both through neighbor tables and the
    gather (per row the same terms in the same order), to the tolerance
    ``tests/test_worker_mesh.py`` states for two executables of one
    arithmetic: a few units of the models' scale a round."""
    config, traffic = cell_at(side)
    sharded, args_s, _, _ = run_program(config, traffic, 6)
    plain, args_u, _, _ = run_program(
        config, traffic, 6, worker_mesh=0, topology_impl="neighbor", mixing_impl="gather")
    assert (args_s["mixing"], args_u["mixing"]) == ("halo_gather", "gather")
    assert "mesh" not in args_u and plain.history.mesh_devices in (None, 0, 1)
    assert_ulps_of_scale(sharded.final_models, plain.final_models, 32)
    np.testing.assert_allclose(
        sharded.history.objective, plain.history.objective, rtol=2e-6)
    np.testing.assert_allclose(
        sharded.history.consensus_error, plain.history.consensus_error, rtol=2e-5)


@pytest.mark.parametrize("seed", SEEDS[:2])
@pytest.mark.parametrize("control", [
    {"precision": "bfloat16"}, {"mixing": "ring_mix"}, {"mixing": "no_wrap"}],
    ids=["bfloat16", "ring_mix", "no_wrap"])
def test_a_control_is_not_correct(control, seed):
    """The reference in the control's form, in the program's place, against
    the cell's own limits: over at least one."""
    config, traffic = cell_at(8)
    assert config["precision"]["control"] == "bfloat16"
    assert config["mixing_controls"] == ["ring_mix", "no_wrap"]
    X, y, _ = datasets.make(config, seed)
    ref = dsgd_torus_blocks.run(config, traffic, X, y, seed)
    ctl = dsgd_torus_blocks.run(config, traffic, X, y, seed, **control)
    said = []
    assert not compare.judge(
        compare.numbers(ctl, ref), config["limits"][MIX], said.append), said


def test_the_program_is_not_a_control():
    """The sharded program against each graph control in the reference's
    place: over the limits, so the limits tell the torus from a cylinder
    and from rings."""
    config, traffic = cell_at(8)
    result, _, _, (X, y) = run_program(config, traffic, 7)
    for mixing in ("ring_mix", "no_wrap"):
        ctl = dsgd_torus_blocks.run(
            config, traffic, X, y, program.seed_for(7), mixing=mixing)
        said = []
        assert not compare.judge(
            compare.numbers(harness.produced_of(result), ctl),
            config["limits"][MIX], said.append), said


def test_boundary_rows_not_exchanged_are_not_correct(monkeypatch):
    """A halo exchange that delivers nothing (every ``ppermute`` hands back
    zeros): each block's first and last grid rows mix with 0 where the
    neighbouring chip's grid row belongs, and the cell's limits say so."""
    config, traffic = cell_at(8)
    real = jax.lax.ppermute
    monkeypatch.setattr(
        jax.lax, "ppermute",
        lambda x, axis_name, perm: real(x, axis_name, perm) * 0)
    # a seed of its own: the process's executable cache holds the sound
    # programs of the seeds above
    result, _, _, (X, y) = run_program(config, traffic, 5)
    ref = dsgd_torus_blocks.run(config, traffic, X, y, program.seed_for(5))
    said = []
    nums = compare.numbers(harness.produced_of(result), ref)
    assert not compare.judge(nums, config["limits"][MIX], said.append), said
    assert nums["consensus_max_rel"] > 100 * config["limits"][MIX]["consensus_max_rel"]


def test_the_plan_at_the_cells_size():
    """Host only, no device array: the 1024 x 1024 torus over four shards
    plans TWO rotations of 1,024 rows, every chip sending its first grid row
    back and its last forward (chip 3's last to chip 0: the wrap), 2,048
    halo rows a chip, and a table that addresses the block and its halo."""
    side, P = 1024, 4
    n, S = side * side, side * side // P
    topo = topology.build_topology("grid", n, impl="neighbor")
    assert topo.grid_shape == (side, side)
    nbr_idx, nbr_mask = topology.neighbor_tables_for(topo)
    assert nbr_idx.shape == (n, 4) and bool(np.all(nbr_mask))
    plan = topology.build_halo_plan(nbr_idx, nbr_mask, P, sampler=topo.sampler)
    assert (plan.n_shards, plan.shard_rows, plan.h_max) == (P, S, 2 * side)
    assert len(plan.steps) == 2
    assert sorted(st.rotation % P for st in plan.steps) == [1, P - 1]
    first, last = np.arange(side), np.arange(S - side, S)
    for st in plan.steps:
        assert st.send_idx.shape == (P, side)
        rows = last if st.rotation % P == 1 else first  # forward / back
        for p in range(P):
            np.testing.assert_array_equal(np.sort(st.send_idx[p]), rows)
    np.testing.assert_array_equal(plan.sent_rows, [2 * side] * P)
    np.testing.assert_array_equal(plan.recv_rows, [2 * side] * P)
    for p in range(P):
        lo = ((p * S - side) % n) + np.arange(side)   # the grid row before the block
        hi = (((p + 1) * S) % n) + np.arange(side)    # the one after it
        np.testing.assert_array_equal(
            np.sort(plan.halo_idx[p]), np.sort(np.concatenate([lo, hi])))
    local = plan.local_nbr
    assert local.min() == 0 and local.max() == S + 2 * side - 1  # never the dump row
    inner = local.reshape(P, S, 4)[:, side:S - side]  # rows with no neighbour off the chip
    assert inner.max() < S


def test_the_root_says_what_the_halo_gather_holds():
    config, traffic = cell_at(16)
    _, args, tracer, _ = run_program(config, traffic, 8)
    S, side, d = 64, 16, 81
    assert args["mixing"] == "halo_gather"
    assert (args["k_max"], args["gathered_rows"], args["halo_steps"]) == (4, 4 * S, 2)
    assert args["halo_rows"] == 2 * side
    assert args["ici_bytes_per_round"] == 2 * side * d * 4
    assert args["halo_tables"] == "constant"
    # nbr s32 and w_nbr f32 [4, 4, S], w_self f32 [N], two rotations' send
    # and receive lists s32 [4, side] each (the CPU pads nothing)
    assert args["halo_table_bytes"] == 2 * 4 * 4 * S * 4 + 4 * S * 4 + 4 * (4 * side * 4)
    (row,) = tracer.calls_table(format="json")
    assert row["seconds"]["halo_plan"] > 0
    plans = [e for e in tracer.spans() if e["name"] == "dopt.run.halo_plan"]
    (root,) = [e for e in tracer.spans() if e["name"] == "dopt.run"]
    assert [(e["parent"], e["args"]["form"]) for e in plans] == [(root["id"], "halo_gather")]


def test_a_rings_root_says_nothing_of_a_gather():
    """``halo_shift`` holds no table: the root carries none of the gather's
    arguments, and the plan's span says which form it made."""
    config, traffic = cell_at(8)
    config["experiment"]["topology"] = "ring"
    _, args, tracer, _ = run_program(config, traffic, 9)
    assert args["mixing"] == "halo_shift"
    assert not {"k_max", "gathered_rows", "halo_steps", "halo_table_bytes",
                "halo_tables"} & set(args)
    (plan,) = [e for e in tracer.spans() if e["name"] == "dopt.run.halo_plan"]
    assert plan["args"]["form"] == "halo_shift"


def test_an_unsharded_call_plans_no_halo():
    config, traffic = cell_at(8)
    _, args, tracer, _ = run_program(config, traffic, 9, worker_mesh=0)
    assert "halo_steps" not in args
    assert not [e for e in tracer.spans() if e["name"] == "dopt.run.halo_plan"]


def test_the_file_states_what_the_cell_runs():
    config = load("configs", NAME + ".json")
    sibling = load("configs", "glm81_ring1m_mesh4.json")
    exp = config["experiment"]
    # everything but the graph is the four-chip ring's; every selector auto
    assert {**exp, "topology": "ring"} == sibling["experiment"]
    assert exp["topology"] == "grid"
    assert not {k for k in exp if k.endswith("_impl") or k == "topology_sampler"}
    assert config["dataset"] == sibling["dataset"]
    assert config["precision"] == sibling["precision"]
    assert config["architecture"] is None
    assert exp["n_workers"] == 1024 * 1024 and exp["worker_mesh"] == config["chips"] == 4
    assert dsgd_torus_blocks.torus_shape(exp["n_workers"]) == (1024, 1024)
    bench = load("..", "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["reduced"] == config["reduced"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert config["reference"] == "dsgd_torus_blocks"
    for key in ("source", "assumed", "layout", "sizing", "guarantees", "limits_set_from"):
        assert config[key], key
    assert "blocking" in config["assumed"]
    (cell,) = [w for w in bench["workloads"] if w["config"] == NAME]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (f"{NAME}.{MIX}", MIX, 4)
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) == 2 <= len(bench["workloads"]) // 4
    traffic = load("traffic", MIX + ".json")
    assert (traffic["n_iterations"], traffic["eval_every"],
            traffic["check_iterations"], traffic["trace_calls"]) == (1000, 1, 100, 1)
    assert traffic["gates_set_from"] and traffic["who"]
    assert set(config["limits"][MIX]) == {"objective_max_rel", "consensus_max_rel"}
    # one block of the reference goes up as one copy under the runtime's cliff
    from distributed_optimization_tpu.parallel.mesh import (
        FLAT_MIN_TILED_BYTES,
        tiled_bytes,
    )

    block = exp["n_workers"] // config["reference_blocks"]
    L = config["dataset"]["rows_per_worker"]
    assert tiled_bytes((block, L, exp["n_features"] + 1), 4) < FLAT_MIN_TILED_BYTES


def test_the_reference_imports_nothing_of_the_package():
    path = os.path.join(ROOT, "benchmark", "reference", "dsgd_torus_blocks.py")
    with open(path) as fh:
        imports = [ln.strip() for ln in fh if ln.startswith(("import ", "from "))]
    assert imports == [
        "import importlib", "import math", "import jax", "import jax.numpy as jnp",
        "import numpy as np",
        "from benchmark.reference.dsgd_ring import PRECISIONS, _make_mm, batch_weights",
    ]
