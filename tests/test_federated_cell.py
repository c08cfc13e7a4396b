"""The first deployment whose gossip round holds more than one gradient step
(ISSUE 50) at a size a test run can hold: tau local SGD steps a round with
the workers sampled anew each round, through the program's normal path,
against the benchmark's plain reference
(``benchmark/reference/dsgd_ring_local.py``: the round written out over the
documented draws, the local descents a Python loop, the freeze a ``where``;
no timeline, no neighbor table, nothing of the package), by the limits of
the cell's own configuration file; at the cell's tau = 4 / rate 0.5 and at
tau = 10 / rate 0.8, where the local descents run as a ``fori_loop`` whose
slot is traced (the issue's tau = 9 is the last that is still unrolled:
``LOCAL_UNROLL_MAX`` = 8 descents). And what the program says of such a
round: the root's five arguments, and the tenth device scope ``dopt.local``
on the later descents' gradients, metadata and nothing else. CPU, N = 64,
T = 40: what is checked is numbers against limits, never a time.
"""

import json
import os
import sys

import numpy as np
import pytest
from test_device_scopes import _NoScope, lowered_scan, stripped

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, datasets, program  # noqa: E402
from benchmark import run as harness  # noqa: E402
from benchmark.flops import glm_local_steps, glm_step  # noqa: E402
from benchmark.reference import dsgd_ring_local  # noqa: E402

from distributed_optimization_tpu.algorithms.base import LOCAL_UNROLL_MAX  # noqa: E402
from distributed_optimization_tpu.backends import jax_backend  # noqa: E402
from distributed_optimization_tpu.observability import device_scopes  # noqa: E402
from distributed_optimization_tpu.observability.spans import CALL_ARGS, Tracer  # noqa: E402
from distributed_optimization_tpu.parallel import build_topology, faults  # noqa: E402

NAME, MIX = "glm81_ring262k_local4_part50", "rounds250"
SEEDS = [3, 4, 2147483999]
# the cell's own round, and one whose local descents are a ``fori_loop``
ROUND = {"cell": dict(local_steps=4, participation_rate=0.5),
         "loop": dict(local_steps=10, participation_rate=0.8)}
N, T = 64, 40
FIRST_READS = {"fused": 1, "carried": 2, "recomputed": 3}
NEW_ARGS = {"local_steps", "local_forward", "shard_reads", "sampled_out_share",
            "timeline_placement"}


def load(workload):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    _, config, traffic = harness.load_cell(bench, workload, rehearse=True)
    return config, traffic


@pytest.fixture(scope="module")
def cell():
    """(config, traffic) at the files' rehearsal sizes: 64 workers of 24
    rows on the neighbor table, 40 rounds, the check following all."""
    config, traffic = load(f"{NAME}.{MIX}")
    exp = config["experiment"]
    assert (exp["n_workers"], exp["topology_impl"]) == (N, "neighbor")
    assert (exp["local_steps"], exp["participation_rate"]) == (4, 0.5)
    assert (traffic["n_iterations"], traffic["check_iterations"]) == (T, T)
    assert ROUND["loop"]["local_steps"] - 1 > LOCAL_UNROLL_MAX
    return config, traffic


def at(config, rounds):
    return dict(config, experiment=dict(config["experiment"], **ROUND[rounds]))


def run_program(config, traffic, seed, **replace):
    X, y, L = datasets.make(config, seed)
    cfg, dataset = program.build(config, traffic, X, y, L, program.seed_for(seed))
    cfg = cfg.replace(**replace)
    tracer = Tracer()
    with tracer.activate():
        result = program.run_experiment(cfg, dataset)
    (root,) = [e for e in tracer.spans() if e["name"] == "dopt.run"]
    return result, root["args"], tracer, cfg, (X, y, program.seed_for(seed))


def judged(produced, ref, config):
    said = []
    ok = compare.judge(compare.numbers(produced, ref), config["limits"][MIX], said.append)
    return ok, said


def host_leaf(cfg):
    """The ``part_up`` leaf the program read, rebuilt on the host."""
    topo = build_topology("ring", cfg.n_workers, impl="neighbor")
    tl = faults.timeline_for_config(cfg, topo, cfg.n_iterations)
    assert isinstance(tl.part_up, np.ndarray) and tl.part_up.shape == (T, N)
    assert tl.edge_up is None and tl.node_up is None and tl.rejoin is None
    return tl.part_up


# ``forward``: the dense sampler the chip takes (the margins carried from the
# eval to a round's FIRST gradient), what the CPU's auto takes (the gather
# sampler: recomputed), and the chip's own form, the shard visit, interpreted.
@pytest.mark.parametrize("rounds,seed,forward", [
    (rounds, seed, "carried") for rounds in sorted(ROUND) for seed in SEEDS
] + [("cell", SEEDS[0], "recomputed"), ("loop", SEEDS[1], "recomputed"),
     ("cell", SEEDS[1], "fused"), ("loop", SEEDS[0], "fused")])
def test_the_program_is_within_the_cells_limits(cell, rounds, seed, forward, monkeypatch):
    config, traffic = at(cell[0], rounds), cell[1]
    replace = {}
    if forward != "recomputed":
        replace["sampling_impl"] = "dense"
    if forward == "fused":
        monkeypatch.setattr(jax_backend, "_visit_is_fused", lambda carried, X: carried)
        monkeypatch.setenv("DOPT_EXEC_CACHE", "0")
    result, args, tracer, cfg, (X, y, pseed) = run_program(config, traffic, seed, **replace)
    tau, rate = ROUND[rounds]["local_steps"], ROUND[rounds]["participation_rate"]
    assert args["faults"] == f"participation:{rate:g}"
    assert (args["fault_form"], args["fault_mixing"]) == ("timeline", "shift")
    assert "fault_chains" not in args and "down_share" not in args
    # the five arguments of a round that holds more than one gradient and
    # samples its workers
    # under ``fused`` each later gradient is one visit of the shards (ISSUE
    # 51: ``glm_shard_gradient``, interpreted here), everywhere else two reads
    visited = forward == "fused"
    assert (args["local_steps"], args["forward"], args["local_forward"]) == (
        tau, forward, "visited" if visited else "recomputed")
    assert args["shard_reads"] == FIRST_READS[forward] + (1 if visited else 2) * (tau - 1)
    assert args["timeline_placement"] == "device"
    assert args["fault_bytes"] == T * N  # part_up alone: a byte a bit
    leaf = host_leaf(cfg)
    assert args["sampled_out_share"] == 1.0 - int(leaf.sum()) / leaf.size
    assert abs(args["sampled_out_share"] - (1.0 - rate)) < 0.02
    assert args["sampled_out_share"] == pytest.approx(
        dsgd_ring_local.sampled_out_share(pseed, N, T, rate), abs=1e-12)
    (row,) = tracer.calls_table(format="json")
    assert row["said"] == {a: args[a] for a in CALL_ARGS} and set(CALL_ARGS) == NEW_ARGS
    assert tracer.calls_table().splitlines()[0].split()[-len(CALL_ARGS):] == list(CALL_ARGS)
    ref = dsgd_ring_local.run(config, traffic, X, y, pseed)
    np.testing.assert_array_equal(ref["sampled_out"], (~leaf).sum(axis=1))
    ok, said = judged(harness.produced_of(result), ref, config)
    assert ok, said
    assert not harness.gate_failures(result, traffic)


STATED = {}


@pytest.mark.parametrize("control", ["bfloat16", *dsgd_ring_local.ROUNDS[1:]])
@pytest.mark.parametrize("rounds", sorted(ROUND))
@pytest.mark.parametrize("seed", SEEDS[:2])
def test_each_control_is_not_correct(cell, control, rounds, seed):
    """The reference computed another way, in the program's place, against
    the cell's own limits: the precision below the stated one, a round
    without its local descents, local descents on one batch, sampled-out
    workers that step, and local descents at a global counter's step size
    are each over at least one."""
    config, traffic = at(cell[0], rounds), cell[1]
    assert control == config["precision"]["control"] or control in config["round_controls"]
    if (rounds, seed) not in STATED:  # one stated run for its five controls
        X, y, _ = datasets.make(config, seed)
        STATED[rounds, seed] = X, y, dsgd_ring_local.run(config, traffic, X, y, seed)
    X, y, ref = STATED[rounds, seed]
    how = dict(precision=control) if control == "bfloat16" else dict(rounds=control)
    ctl = dsgd_ring_local.run(config, traffic, X, y, seed, **how)
    ok, said = judged(ctl, ref, config)
    assert not ok, said


@pytest.mark.parametrize("which,says", [
    ("control", set()),
    ("sampled", {"sampled_out_share", "timeline_placement"}),
    ("local", {"local_steps", "local_forward", "shard_reads"}),
])
def test_a_call_says_of_its_round_what_it_has_and_no_more(cell, which, says):
    """tau = 1 with everyone taking part (the control's root is as it was),
    tau = 1 under sampling, tau = 4 with everyone: each argument on the calls
    it belongs to and on no other."""
    if which == "control":
        config, traffic = load("glm81_ring262k.steady2k")
    else:
        drop = "local_steps" if which == "sampled" else "participation_rate"
        exp = {k: v for k, v in cell[0]["experiment"].items() if k != drop}
        config, traffic = dict(cell[0], experiment=exp), cell[1]
    _, args, tracer, cfg, _ = run_program(config, traffic, 3)
    assert NEW_ARGS & set(args) == says
    (row,) = tracer.calls_table(format="json")
    assert set(row["said"]) == says
    if which == "local":
        # a CPU's auto is the gather sampler: recomputed, two reads a descent
        assert (args["local_steps"], args["local_forward"], args["shard_reads"]) == (
            4, "recomputed", 3 + 2 * 3)
        assert "faults" not in args
    if which == "sampled":
        assert args["faults"] == "participation:0.5" and cfg.local_steps == 1


# ---- the tenth device scope: the later descents' gradients ----

def scan_of(cell, monkeypatch, **replace):
    """The cell's one device program at the rehearsal's size, lowered; the
    dense sampler, as on the chip."""
    config, traffic = cell
    X, y, L = datasets.make(config, 3)
    cfg, dataset = program.build(config, traffic, X, y, L, 3)
    cfg = cfg.replace(sampling_impl="dense", n_iterations=8, **replace)
    return lowered_scan(cfg, dataset, monkeypatch)


def scoped_instructions(text, scope):
    """Instructions of a compiled text whose innermost scope is ``scope``."""
    return sum(device_scopes._scope_of(line) == scope for line in text.splitlines())


@pytest.mark.parametrize("rounds", sorted(ROUND))
def test_the_later_gradients_carry_local_and_the_first_gradient(cell, rounds, monkeypatch):
    lowered = scan_of(cell, monkeypatch, **ROUND[rounds])
    assert "dopt." not in lowered.as_text()
    compiled = lowered.compile()
    text = compiled.as_text()
    table = device_scopes.scope_table(compiled)
    found = {row["scope"] for row in table["rows"]} | {
        s for row in table["rows"] for s in row["also"]}
    assert found - {None} == {
        "sampling", "gradient", "local", "gossip", "faults", "update", "eval"}
    first, later = (scoped_instructions(text, s) for s in ("gradient", "local"))
    assert first > 0 and later > 0
    if rounds == "cell":  # three unrolled whole gradients beside one half of one
        assert later > 2 * first
    # the draws of the later descents stay ``sampling``, under no ``local``
    assert "dopt.local/dopt.sampling" not in text and "dopt.sampling/dopt.local" not in text
    # the same scan with every scope a no-op: the same instructions
    monkeypatch.setattr(device_scopes, "scope", lambda name: _NoScope())
    bare = scan_of(cell, monkeypatch, **ROUND[rounds]).compile().as_text()
    assert "dopt." not in bare
    assert stripped(bare) == stripped(text)


def test_a_round_of_one_gradient_holds_no_local(cell, monkeypatch):
    text = scan_of(cell, monkeypatch, local_steps=1).compile().as_text()
    assert "dopt.gradient" in text and "dopt.local" not in text
    assert "local" in device_scopes.SCOPES and len(device_scopes.SCOPES) == 10


# ---- the files ----

def test_the_reference_restates_the_sampling(cell):
    """The reference's participation bits over a whole horizon are the
    timeline's, to the bit, and slot 0's batches are the sibling's."""
    from benchmark.reference import dsgd_ring

    seed, horizon = 424242, 120
    topo = build_topology("ring", N, impl="neighbor")
    for rate in (0.5, 0.8):
        tl = faults.build_fault_timeline(topo, horizon, seed, participation_rate=rate)
        counted = faults.timeline_counters(tl)
        assert sorted(counted) == ["sampled_out_share", "timeline_placement"]
        assert counted["sampled_out_share"] == pytest.approx(
            dsgd_ring_local.sampled_out_share(seed, N, horizon, rate), abs=1e-12)
        for t in (0, 1, horizon - 1):
            np.testing.assert_array_equal(
                np.asarray(tl.part_up[t]),
                np.asarray(dsgd_ring_local.taking_part(seed, t, N, rate)))
    slots = [np.asarray(dsgd_ring_local.slot_batch_weights(seed, s, 5, N, 24, 16))
             for s in range(3)]
    np.testing.assert_array_equal(
        slots[0], np.asarray(dsgd_ring.batch_weights(seed, 5, N, 24, 16)))
    assert all((w > 0).sum(axis=1).tolist() == [16] * N for w in slots)
    assert not np.array_equal(slots[1], slots[0]) and not np.array_equal(slots[1], slots[2])


def test_the_file_states_what_the_cell_runs():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as fh:
        whole = json.load(fh)
    with open(os.path.join(ROOT, "benchmark", "configs", "glm81_ring262k.json")) as fh:
        sibling = json.load(fh)
    with open(os.path.join(ROOT, "benchmark", "traffic", MIX + ".json")) as fh:
        mix = json.load(fh)
    # the sibling's experiment but for the round, the rate and the partition
    assert whole["experiment"] == dict(
        sibling["experiment"], local_steps=4, participation_rate=0.5, partition="shuffled")
    assert whole["dataset"] == dict(sibling["dataset"], generator="gaussian_two_class_iid")
    assert whole["reduced"] == sibling["reduced"] and whole["architecture"] is None
    assert set(whole["reduced"]) == set(whole["reduced_why"])
    assert len(whole["guarantees"]) >= 6 and {"objective", "dataset"} <= set(whole["assumed"])
    assert whole["round_controls"] == list(dsgd_ring_local.ROUNDS[1:])
    assert (mix["eval_every"], mix["check_iterations"]) == (1, 100)
    assert mix["n_iterations"] in (250, 150)
    # tau reads of the shards and one gossip round, from the file alone
    one_read = glm_step.compulsory_bytes(sibling) - 2 * 262144 * 81 * 4
    assert glm_local_steps.compulsory_bytes(whole) == 4 * one_read + 2 * 262144 * 81 * 4
    assert glm_local_steps.compulsory_bytes(sibling) == glm_step.compulsory_bytes(sibling)
    assert "import distributed_optimization_tpu" not in open(
        dsgd_ring_local.__file__).read().replace("from ", "import ")
