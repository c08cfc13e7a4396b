"""The first attacked and screened deployment (ISSUE 43) at a size a test run
can hold: the README's Byzantine ring (6 sign-flipping workers of 64, placed
within the per-neighbourhood budget, screened by the trimmed mean), through
the program's normal path, against the benchmark's plain reference
(``benchmark/reference/dsgd_ring_byzantine.py``: the placement restated, the
payload a ``where``, the screening a three-way sort of rolled stacks; no
neighbor table, no gather, nothing of the package), by the limits of the
cell's own configuration file. CPU, N = 64, T = 40: what is checked is
numbers against limits, never a time.
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, datasets, program  # noqa: E402
from benchmark import run as harness  # noqa: E402
from benchmark.reference import dsgd_ring_byzantine  # noqa: E402

from distributed_optimization_tpu.backends import jax_backend  # noqa: E402
from distributed_optimization_tpu.observability.spans import Tracer  # noqa: E402
from distributed_optimization_tpu.parallel import adversary, build_topology  # noqa: E402
from distributed_optimization_tpu.parallel.topology import neighbor_tables_for  # noqa: E402

NAME, MIX = "glm81_ring262k_signflip_tm1", "screen1k"
SEEDS = [3, 4, 2147483999]


@pytest.fixture(scope="module")
def cell():
    """(config, traffic) at the files' rehearsal sizes: 64 workers of 24
    rows, 6 attackers, 40 iterations, the check following 12."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    _, config, traffic = harness.load_cell(bench, f"{NAME}.{MIX}", rehearse=True)
    exp = config["experiment"]
    assert (exp["n_workers"], exp["n_byzantine"], exp["topology_impl"]) == (64, 6, "neighbor")
    assert (exp["attack"], exp["attack_scale"]) == ("sign_flip", 5.0)
    assert (exp["aggregation"], exp["robust_b"]) == ("trimmed_mean", 1)
    assert exp["byzantine_placement"] == "within_budget"
    assert (traffic["n_iterations"], traffic["check_iterations"]) == (40, 12)
    return config, traffic


def run_program(config, traffic, seed, **replace):
    X, y, L = datasets.make(config, seed)
    cfg, dataset = program.build(config, traffic, X, y, L, program.seed_for(seed))
    if replace:
        cfg = cfg.replace(**replace)
    tracer = Tracer()
    with tracer.activate():
        result = program.run_experiment(cfg, dataset)
    (root,) = [e for e in tracer.spans() if e["name"] == "dopt.run"]
    children = [e["name"] for e in tracer.spans() if e["parent"] == root["id"]]
    return result, root["args"], children, (X, y, program.seed_for(seed))


def benign(config):
    """The cell's configuration with the attack and the rule taken out."""
    exp = {k: v for k, v in config["experiment"].items() if k not in (
        "attack", "n_byzantine", "attack_scale", "byzantine_placement", "aggregation",
        "robust_b")}
    return dict(config, experiment=exp)


def judged(produced, ref, config, scale=1.0):
    said = []
    limits = {k: v * scale for k, v in config["limits"][MIX].items()}
    ok = compare.judge(compare.numbers(produced, ref), limits, said.append)
    return ok, said


# ``forward``: what the CPU's auto takes (the gather sampler: recomputed), the
# dense sampler the chip takes (the margins carried from the eval), and the
# chip's own form, the shard visit, interpreted.
@pytest.mark.parametrize("seed,forward", [
    (SEEDS[0], "recomputed"), (SEEDS[1], "recomputed"), (SEEDS[2], "recomputed"),
    (SEEDS[0], "carried"), (SEEDS[1], "carried"), (SEEDS[0], "fused"),
])
def test_the_program_is_within_the_cells_limits(cell, seed, forward, monkeypatch):
    """Every row of the first 12 iterations by the cell's own limits, in each
    form of the forward product: the eval's x-bar is the HONEST mean in all
    three, and a carry that forgot ``honest_w`` would show here."""
    config, traffic = cell
    replace = {}
    if forward != "recomputed":
        replace["sampling_impl"] = "dense"
    if forward == "fused":
        monkeypatch.setattr(jax_backend, "_visit_is_fused", lambda carried, X: carried)
        monkeypatch.setenv("DOPT_EXEC_CACHE", "0")
    result, args, children, (X, y, pseed) = run_program(config, traffic, seed, **replace)
    assert args["forward"] == forward
    ref = dsgd_ring_byzantine.run(config, traffic, X, y, pseed)
    ok, said = judged(harness.produced_of(result), ref, config)
    assert ok, said
    assert not harness.gate_failures(result, traffic)


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_the_float64_program_follows_the_float32_reference(cell, seed):
    """The same rule a precision above, on the whole shard a step (a draw
    from float64 uniforms is another batch): the reference's float32 rows
    lie within ten times the cell's limits of the program's float64 ones."""
    config, traffic = cell
    rows = config["dataset"]["rows_per_worker"]
    config = dict(config, experiment=dict(config["experiment"], local_batch_size=rows))
    result, args, _, (X, y, pseed) = run_program(config, traffic, seed, dtype="float64")
    assert result.final_models.dtype == np.float64
    ref = dsgd_ring_byzantine.run(config, traffic, X, y, pseed)
    ok, said = judged(harness.produced_of(result), ref, config, scale=10.0)
    assert ok, said


@pytest.mark.parametrize("control", ["bfloat16", "no_screening", "all_rows_metrics"])
@pytest.mark.parametrize("seed", SEEDS[:2])
def test_each_control_is_not_correct(cell, control, seed):
    """The reference computed another way, in the program's place, against
    the cell's own limits: the precision below the stated one, honest workers
    that average what they receive, and metrics that count the attackers'
    rows are each over at least one."""
    config, traffic = cell
    assert control == config["precision"]["control"] or control in config["byzantine_controls"]
    X, y, _ = datasets.make(config, seed)
    ref = dsgd_ring_byzantine.run(config, traffic, X, y, seed)
    how = dict(precision=control) if control == "bfloat16" else dict(variant=control)
    ctl = dsgd_ring_byzantine.run(config, traffic, X, y, seed, **how)
    ok, said = judged(ctl, ref, config)
    assert not ok, said


def test_the_root_says_who_lied_and_how_it_was_screened(cell):
    config, traffic = cell
    result, args, children, _ = run_program(config, traffic, SEEDS[0])
    assert args["attack"] == "sign_flip:6/64"
    assert args["byzantine_placement"] == "within_budget" and args["budget_max"] == 1
    assert args["aggregation"] == "trimmed_mean:b=1" and args["robust_impl"] == "gather"
    assert args["screen_order"] == "network:3"  # three slots: no sort (PR 44)
    assert args["screen_fetch"] == "shift"  # a ring's table: no gather (PR 45)
    assert args["screened_rows"] == 64 * 3 and args["robust_bytes"] == 0.0
    assert args["mixing"] == "stencil"  # the attackers' benign rows
    assert children.count("dopt.run.adversary") == 1
    assert children.index("dopt.run.adversary") > children.index("dopt.run.topology")
    # a benign call says none of it and opens no such span
    _, args, children, _ = run_program(benign(config), traffic, SEEDS[0])
    assert not {"attack", "byzantine_placement", "budget_max", "aggregation", "robust_impl",
                "screen_order", "screen_fetch", "screened_rows", "robust_bytes"} & set(args)
    assert "dopt.run.adversary" not in children


@pytest.mark.parametrize("graph,fetch,order", [("ring", "shift", "network:3"), ("grid", "gather", "network:5")])
def test_the_root_says_how_the_received_rows_were_fetched(cell, graph, fetch, order):
    """``screen_fetch`` is the predicate the rule asked, read off the neighbor
    table (ISSUE 45): ``shift`` on the cell's ring, whose compiled scan holds
    no gather under ``dopt.robust``; ``gather`` on an 8 x 8 torus of the same
    workers (what its round compiles to is ``tests/test_tpu_compile.py``'s)."""
    from distributed_optimization_tpu.observability import device_scopes

    config, traffic = cell
    result, args, _, _ = run_program(config, traffic, SEEDS[0], topology=graph)
    assert (args["screen_fetch"], args["screen_order"]) == (fetch, order)
    assert args["robust_impl"] == "gather" and args["budget_max"] == 1
    assert not harness.gate_failures(result, traffic)
    if fetch == "shift":
        rows = device_scopes.table_for(args["program"])["rows"]
        robust = [r["head"] for r in rows if r["scope"] == "robust" or "robust" in r["also"]]
        assert robust and not [h for h in robust if "gather" in h.split("(")[0]], robust


def test_the_compiled_scan_bills_the_round_to_its_own_scope(cell):
    """``dopt.robust`` is on the payload and the screening rule's
    instructions, nested in ``dopt.gossip`` (the innermost bills); the
    attackers' stencil stays ``gossip``; a benign program carries none."""
    from distributed_optimization_tpu.observability import device_scopes

    config, traffic = cell
    _, args, _, _ = run_program(config, traffic, SEEDS[0])
    rows = device_scopes.table_for(args["program"])["rows"]
    # three slots are ordered by the compare-exchange network (PR 44): no
    # sort anywhere, and the rule's selects ride in a fusion that says so
    assert not [r for r in rows if r["head"].startswith("%sort")]
    robust = [r for r in rows if r["scope"] == "robust" or "robust" in r["also"]]
    assert any("fusion" in r["head"].split(" = ")[0] for r in robust), [r["head"][:60] for r in robust]
    assert any(r["scope"] == "gossip" for r in rows)
    _, args, _, _ = run_program(benign(config), traffic, SEEDS[0])
    rows = device_scopes.table_for(args["program"])["rows"]
    assert not [r for r in rows if r["scope"] == "robust" or "robust" in r["also"]]


@pytest.mark.parametrize("n,f", [(64, 6), (64, 20), (1024, 96), (4096, 384)])
@pytest.mark.parametrize("seed", [0, 1234567, 2147483000])
def test_the_reference_restates_the_placement(n, f, seed):
    topo = build_topology("ring", n, impl="neighbor")
    want = adversary.place_within_budget(*neighbor_tables_for(topo), f, 1, seed)
    got = dsgd_ring_byzantine.attackers_on_a_ring(seed, n, f, 1)
    np.testing.assert_array_equal(got, want)
    assert got.sum() == f


def test_the_file_states_what_the_cell_runs(cell):
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as fh:
        whole = json.load(fh)
    with open(os.path.join(ROOT, "benchmark", "configs", "glm81_ring262k.json")) as fh:
        sibling = json.load(fh)
    # the sibling's experiment plus the README entry's flags at its share
    assert whole["experiment"] == dict(
        sibling["experiment"], partition="shuffled", attack="sign_flip", n_byzantine=24576,
        attack_scale=5.0, byzantine_placement="within_budget", aggregation="trimmed_mean",
        robust_b=1, topology_impl="neighbor")
    assert whole["experiment"]["n_byzantine"] * 64 == 6 * whole["experiment"]["n_workers"]
    assert whole["dataset"] == dict(sibling["dataset"], generator="gaussian_two_class_iid")
    assert whole["reduced"] == ["n_workers", "n_byzantine", "rows_per_worker", "n_iterations"]
    assert whole["architecture"] is None and len(whole["guarantees"]) >= 5
    assert {"byzantine_placement", "dataset", "f_opt"} <= set(whole["assumed"])
    assert "import distributed_optimization_tpu" not in open(
        dsgd_ring_byzantine.__file__).read().replace("from ", "import ")
    # contiguous shards of the generator are IID: every shard holds both classes
    X, y, L = datasets.make(dict(whole, experiment=dict(
        whole["experiment"], n_workers=64), dataset=dict(whole["dataset"])), 7)
    shards = y.reshape(64, L)
    assert np.all((shards > 0).any(axis=1) & (shards < 0).any(axis=1))
