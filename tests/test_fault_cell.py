"""The first deployment that fails (ISSUE 32) at a size a test run can hold:
the program under the README's 30% link loss and 10% stragglers on a ring,
through its normal path, against the benchmark's plain reference
(``benchmark/reference/dsgd_ring_faulty.py``: the documented draws restated,
the realized Metropolis-Hastings weights as three rolled terms, the freeze as
a ``where``; no neighbor table, no gather, nothing of the package), by the
limits of the cell's own configuration file. CPU, N = 64, T = 40: what is
checked is numbers against limits, never a time.
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
from benchmark.reference import dsgd_ring, dsgd_ring_faulty  # noqa: E402

from distributed_optimization_tpu.observability.spans import Tracer  # noqa: E402

NAME, MIX = "glm81_ring262k_drop30strag10", "steady1k"
SEEDS = [3, 4, 2147483999]


@pytest.fixture(scope="module")
def cell():
    """(config, traffic) at the files' rehearsal sizes: 64 workers of 24
    rows on the neighbor table, 40 iterations, the check following 12."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    _, config, traffic = harness.load_cell(bench, f"{NAME}.{MIX}", rehearse=True)
    exp = config["experiment"]
    assert (exp["n_workers"], exp["topology_impl"]) == (64, "neighbor")
    assert (exp["edge_drop_prob"], exp["straggler_prob"]) == (0.3, 0.1)
    assert (traffic["n_iterations"], traffic["check_iterations"]) == (40, 12)
    return config, traffic


def run_program(config, traffic, seed):
    X, y, L = datasets.make(config, seed)
    cfg, dataset = program.build(config, traffic, X, y, L, program.seed_for(seed))
    tracer = Tracer()
    with tracer.activate():
        result = program.run_experiment(cfg, dataset)
    (root,) = [e for e in tracer.spans() if e["name"] == "dopt.run"]
    return result, root["args"], (X, y, program.seed_for(seed))


def judged(produced, ref, config):
    said = []
    ok = compare.judge(compare.numbers(produced, ref), config["limits"][MIX], said.append)
    return ok, said


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("mixing", ["shift", "gather"])
def test_the_program_is_within_the_cells_limits(cell, seed, mixing, monkeypatch):
    """The cell's ring is mixed by shifts (ISSUE 33: read off its neighbor
    table, no option), with no table to hand the scan; the index-table form
    that every other graph keeps is held to the same limits on the same
    ring, by telling the rule that the table is no ring's."""
    from distributed_optimization_tpu.parallel import faults

    if mixing == "gather":
        monkeypatch.setattr(faults, "_table_is_a_ring", lambda topo: False)
        # the executable cache keys a program by its configuration, which
        # decides the form everywhere but under this patch
        monkeypatch.setenv("DOPT_EXEC_CACHE", "0")
    config, traffic = cell
    result, args, (X, y, pseed) = run_program(config, traffic, seed)
    assert args["faults"] == "edge_drop:0.3,straggler:0.1"
    assert args["fault_form"] == "drawn" and args["fault_mixing"] == mixing
    # gather: nbr s32, mask f32, slot s32, each [64, 2]
    assert args["fault_bytes"] == (0 if mixing == "shift" else 3 * 64 * 2 * 4)
    assert 0.45 < args["live_edge_share"] < 0.68  # 0.567 over 40 rounds of 64 links
    ref = dsgd_ring_faulty.run(config, traffic, X, y, pseed)
    ok, said = judged(harness.produced_of(result), ref, config)
    assert ok, said
    assert not harness.gate_failures(result, traffic)


@pytest.mark.parametrize("control", ["bfloat16", "no_freeze", "static"])
@pytest.mark.parametrize("seed", SEEDS[:2])
def test_each_control_is_not_correct(cell, control, seed):
    """The reference computed another way, in the program's place, against
    the cell's own limits: the precision below the stated one, stragglers
    that step, and no faults at all are each over at least one."""
    config, traffic = cell
    assert control == config["precision"]["control"] or control in config["fault_controls"]
    X, y, _ = datasets.make(config, seed)
    ref = dsgd_ring_faulty.run(config, traffic, X, y, seed)
    how = (dict(precision=control) if control == "bfloat16" else dict(faults=control))
    ctl = dsgd_ring_faulty.run(config, traffic, X, y, seed, **how)
    ok, said = judged(ctl, ref, config)
    assert not ok, said


@pytest.mark.parametrize("precision", ["reference", "bfloat16"])
def test_without_faults_the_reference_is_the_fault_free_one(cell, precision):
    """``static`` is the plain ring, weights 1/3: ``dsgd_ring``'s rows to the
    last places (the weighted sum and the mean of three round differently)."""
    config, traffic = cell
    X, y, _ = datasets.make(config, 5)
    want = dsgd_ring.run(config, traffic, X, y, 5, precision=precision)
    got = dsgd_ring_faulty.run(
        config, traffic, X, y, 5, precision=precision, faults="static")
    tol = 1e-6 if precision == "reference" else 2e-2
    np.testing.assert_allclose(got["objective"], want["objective"], rtol=tol)
    np.testing.assert_allclose(got["consensus"], want["consensus"], rtol=20 * tol)


def test_the_reference_restates_the_draws():
    """Edge e of the documented list is up iff its uniform is at least p; the
    reference reads the same bits at the ring's positions."""
    from distributed_optimization_tpu.parallel import build_topology, faults

    n, seed = 64, 1234567
    topo = build_topology("ring", n, impl="neighbor")
    tl = faults.build_fault_timeline(
        topo, 9, seed, edge_drop_prob=0.3, straggler_prob=0.1)
    for t in range(9):
        right, m = dsgd_ring_faulty.ring_liveness(seed, t, n, 0.3, 0.1)
        np.testing.assert_array_equal(np.asarray(m), tl.node_up[t].astype(np.float32))
        up = {tuple(e): bool(u) for e, u in zip(tl.edge_index, tl.edge_up[t])}
        want = [up[(min(i, (i + 1) % n), max(i, (i + 1) % n))]
                and tl.node_up[t, i] and tl.node_up[t, (i + 1) % n] for i in range(n)]
        np.testing.assert_array_equal(np.asarray(right) > 0, want)


def test_the_file_states_what_the_cell_runs(cell):
    config, _ = cell
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as fh:
        whole = json.load(fh)
    with open(os.path.join(ROOT, "benchmark", "configs", "glm81_ring262k.json")) as fh:
        sibling = json.load(fh)
    # the sibling's experiment but for the two rates, at the sibling's size
    assert whole["experiment"] == dict(
        sibling["experiment"], edge_drop_prob=0.3, straggler_prob=0.1)
    assert whole["dataset"] == sibling["dataset"]
    assert whole["reduced"] == sibling["reduced"] and whole["architecture"] is None
    assert "checkpoint_every" in whole["left_out"] and len(whole["guarantees"]) >= 5
    assert "import distributed_optimization_tpu" not in open(
        dsgd_ring_faulty.__file__).read().replace("from ", "import ")
