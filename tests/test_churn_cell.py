"""The first deployment whose failures have memory (ISSUE 46) at a size a test
run can hold: Gilbert-Elliott link bursts and crash-recovery churn on a ring
under the ``neighbor_restart`` rejoin, through the program's normal path,
against the benchmark's plain reference
(``benchmark/reference/dsgd_ring_churn.py``: the two chains written out over
the documented draws, their state carried in a Python loop, the restart a
``where`` before the step; no timeline, no neighbor table, nothing of the
package), by the limits of the cell's own configuration file; at the cell's
rates (mttf 400 / mttr 150) and at mttf 6 / mttr 3, where most rounds restart
several rows and every branch of the restart runs. And where the timeline's
leaves live: over a neighbor table the device arrays the chains' scans
returned, bit for bit the host-built chains. CPU, N = 64, T = 40: what is
checked is numbers against limits, never a time.
"""

import json
import os
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
from benchmark.reference import dsgd_ring_churn  # noqa: E402

from distributed_optimization_tpu.backends import jax_backend  # noqa: E402
from distributed_optimization_tpu.observability.spans import Tracer  # noqa: E402
from distributed_optimization_tpu.parallel import build_topology, faults  # noqa: E402
from distributed_optimization_tpu.parallel.mesh import replicate  # noqa: E402

NAME, MIX = "glm81_ring262k_burst4_churn400", "outage1k"
SEEDS = [3, 4, 2147483999]
# the cell's own churn, and one at which several rows come back most rounds
CHURN = {"cell": dict(mttf=400.0, mttr=150.0), "fast": dict(mttf=6.0, mttr=3.0)}
N, T = 64, 40


@pytest.fixture(scope="module")
def cell():
    """(config, traffic) at the files' rehearsal sizes: 64 workers of 24
    rows on the neighbor table, 40 iterations, the check following all."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    _, config, traffic = harness.load_cell(bench, f"{NAME}.{MIX}", rehearse=True)
    exp = config["experiment"]
    assert (exp["n_workers"], exp["topology_impl"]) == (N, "neighbor")
    assert (exp["edge_drop_prob"], exp["burst_len"]) == (0.3, 4.0)
    assert (exp["mttf"], exp["mttr"], exp["rejoin"]) == (400.0, 150.0, "neighbor_restart")
    assert (traffic["n_iterations"], traffic["check_iterations"]) == (T, T)
    return config, traffic


def at(config, churn):
    return dict(config, experiment=dict(config["experiment"], **CHURN[churn]))


def run_program(config, traffic, seed, **replace):
    X, y, L = datasets.make(config, seed)
    cfg, dataset = program.build(config, traffic, X, y, L, program.seed_for(seed))
    cfg = cfg.replace(**replace)
    tracer = Tracer()
    with tracer.activate():
        result = program.run_experiment(cfg, dataset)
    (root,) = [e for e in tracer.spans() if e["name"] == "dopt.run"]
    children = [e["name"] for e in tracer.spans() if e["parent"] == root["id"]]
    return result, root["args"], children, cfg, (X, y, program.seed_for(seed))


def judged(produced, ref, config):
    said = []
    ok = compare.judge(compare.numbers(produced, ref), config["limits"][MIX], said.append)
    return ok, said


def host_rounds(cfg):
    """The rounds the program read, counted in numpy from a timeline rebuilt
    on the host: per round the rows that came back, of them the rows with a
    live link (restarted), and three kinds of rejoin."""
    topo = build_topology("ring", cfg.n_workers, impl="neighbor")
    tl = faults.timeline_for_config(cfg, topo, cfg.n_iterations)
    assert all(isinstance(getattr(tl, k), np.ndarray) for k in faults.TIMELINE_LEAVES[:3])
    n = cfg.n_workers
    up, m, back = tl.edge_up, tl.node_up, tl.rejoin
    link = np.concatenate([up[:, 0:1], up[:, 2:], up[:, 1:2]], axis=1)  # {i, i+1}
    right = link & m & np.roll(m, -1, axis=1)
    left = np.roll(right, 1, axis=1)
    deg = left.astype(int) + right
    beside_down = back & (~np.roll(m, 1, axis=1) | ~np.roll(m, -1, axis=1))
    over_dropped = back & (~link | ~np.roll(link, 1, axis=1))
    return {
        "rejoin_rows": int(back.sum()), "down_share": 1.0 - m.mean(),
        "restarts": (back & (deg > 0)).sum(axis=1),
        "isolated": int((back & (deg == 0)).sum()),
        "beside_down": int(beside_down.sum()), "over_dropped": int(over_dropped.sum()),
        "n": n,
    }


# ``forward``: the dense sampler the chip takes (since ISSUE 47 the margins
# carried from the eval, taken at the models the NEXT round's restart leaves),
# what the CPU's auto takes (the gather sampler: recomputed), and the chip's
# own form, the shard visit, interpreted.
@pytest.mark.parametrize("churn,seed,forward", [
    (churn, seed, "carried") for churn in sorted(CHURN) for seed in SEEDS
] + [("cell", SEEDS[0], "recomputed"), ("fast", SEEDS[1], "recomputed"),
     ("cell", SEEDS[1], "fused"), ("fast", SEEDS[0], "fused")])
def test_the_program_is_within_the_cells_limits(cell, churn, seed, forward, monkeypatch):
    config, traffic = at(cell[0], churn), cell[1]
    replace = {}
    if forward != "recomputed":
        replace["sampling_impl"] = "dense"
    if forward == "fused":
        monkeypatch.setattr(jax_backend, "_visit_is_fused", lambda carried, X: carried)
        monkeypatch.setenv("DOPT_EXEC_CACHE", "0")
    result, args, children, cfg, (X, y, pseed) = run_program(
        config, traffic, seed, **replace)
    want = CHURN[churn]
    assert args["faults"] == f"edge_drop:0.3,burst:4,mttf:{want['mttf']:g},mttr:{want['mttr']:g}"
    assert args["fault_chains"] == f"burst:0.3x4,churn:{want['mttf']:g}/{want['mttr']:g}"
    assert (args["fault_form"], args["fault_mixing"]) == ("timeline", "shift")
    assert (args["rejoin"], args["forward"]) == ("neighbor_restart", forward)
    assert args.get("forward_of") == (None if forward == "recomputed" else "restarted")
    assert args["timeline_placement"] == "device"
    assert args["fault_bytes"] == 3 * T * N  # edge_up, node_up, rejoin: a byte a bit
    assert "dopt.run.faults" in children
    rounds = host_rounds(cfg)
    assert args["rejoin_rows"] == rounds["rejoin_rows"] > 0
    assert args["down_share"] == pytest.approx(rounds["down_share"], abs=1e-12)
    stationary = want["mttr"] / (want["mttf"] + want["mttr"])
    assert abs(args["down_share"] - stationary) < 0.12  # 64 chains of 40 rounds
    ref = dsgd_ring_churn.run(config, traffic, X, y, pseed)
    np.testing.assert_array_equal(ref["restarts"], rounds["restarts"])
    ok, said = judged(harness.produced_of(result), ref, config)
    assert ok, said
    assert not harness.gate_failures(result, traffic)
    if churn == "fast":
        # every branch of the restart ran: a rejoiner with no live link, one
        # beside a down neighbour, one over a dropped link; and most rounds
        # restart a row
        assert min(rounds["isolated"], rounds["beside_down"], rounds["over_dropped"]) > 0
        assert (rounds["restarts"] > 0).sum() > T // 2 and rounds["rejoin_rows"] > 2 * T


STATED = {}


@pytest.mark.parametrize("control", ["bfloat16", "frozen_rejoin", "memoryless", "no_freeze"])
@pytest.mark.parametrize("churn", sorted(CHURN))
@pytest.mark.parametrize("seed", SEEDS[:2])
def test_each_control_is_not_correct(cell, control, churn, seed):
    """The reference computed another way, in the program's place, against
    the cell's own limits: the precision below the stated one, no restart,
    chains without their memory and down workers that step are each over at
    least one."""
    config, traffic = at(cell[0], churn), cell[1]
    assert control == config["precision"]["control"] or control in config["fault_controls"]
    if (churn, seed) not in STATED:  # one stated run for its four controls
        X, y, _ = datasets.make(config, seed)
        STATED[churn, seed] = X, y, dsgd_ring_churn.run(config, traffic, X, y, seed)
    X, y, ref = STATED[churn, seed]
    how = dict(precision=control) if control == "bfloat16" else dict(faults=control)
    ctl = dsgd_ring_churn.run(config, traffic, X, y, seed, **how)
    ok, said = judged(ctl, ref, config)
    assert not ok, said


# ---- where the timeline's leaves live, and that they are the same bits ----

PROCESSES = {
    "burst4": dict(edge_drop_prob=0.3, burst_len=4.0),
    "burst1": dict(edge_drop_prob=0.3, burst_len=1.0),
    "churn_cell": dict(mttf=400.0, mttr=150.0),
    "churn_fast": dict(mttf=6.0, mttr=3.0),
    "churn_iid": dict(zip(("mttf", "mttr"), faults.iid_equivalent_churn(0.1))),
    "stragglers": dict(straggler_prob=0.1),
    "participation": dict(participation_rate=0.8),
    "all": dict(edge_drop_prob=0.3, burst_len=4.0, mttf=6.0, mttr=3.0,
                participation_rate=0.8),
}


def host_chain(key, first, after_up, after_down, size, horizon):
    """A two-state chain a column unrolled on the host, a round at a time,
    in float32: docs/CHURN.md's rule, over the documented draw."""
    first, after_up, after_down = (np.float32(v) for v in (first, after_up, after_down))
    up, rows = np.ones(size, bool), []
    for t in range(horizon):
        u = np.asarray(jax.random.uniform(
            jax.random.fold_in(key, t), (size,), dtype=jnp.float32))
        up = u >= (first if t == 0 else np.where(up, after_up, after_down))
        rows.append(up)
    return np.stack(rows)


def host_built(seed, n, horizon, edge_drop_prob=0.0, burst_len=1.0, straggler_prob=0.0,
               mttf=0.0, mttr=0.0, participation_rate=1.0):
    base = jax.random.key(seed)
    out = dict.fromkeys(faults.TIMELINE_LEAVES)
    if edge_drop_prob:
        p, B = edge_drop_prob, burst_len
        out["edge_up"] = host_chain(
            jax.random.fold_in(base, 0x0FA17), p, p / B, 1.0 - (1.0 - p) / B, n, horizon)
    if mttf or straggler_prob:
        q = straggler_prob
        th = (mttr / (mttf + mttr), 1.0 / mttf, 1.0 - 1.0 / mttr) if mttf else (q, q, q)
        up = host_chain(jax.random.fold_in(base, 0x57A66), *th, n, horizon)
        out["node_up"] = up
        out["rejoin"] = up & ~np.concatenate([np.ones((1, n), bool), up[:-1]])
    if participation_rate < 1.0:
        out["part_up"] = host_chain(
            jax.random.fold_in(base, 0x9AC70), *(1.0 - participation_rate,) * 3, n, horizon)
    return out


@pytest.mark.parametrize("process", sorted(PROCESSES))
def test_the_device_kept_timeline_is_the_host_built_one(process):
    """Over a neighbor table every leaf is the device array the chains' scan
    returned; bit for bit the chain unrolled on the host, and ``host()``'s
    copy of it."""
    seed, horizon = 1234567, 60
    topo = build_topology("ring", N, impl="neighbor")
    tl = faults.build_fault_timeline(topo, horizon, seed, **PROCESSES[process])
    want = host_built(seed, N, horizon, **PROCESSES[process])
    fetched = tl.host()
    assert fetched.host() is fetched
    for name in faults.TIMELINE_LEAVES:
        leaf = getattr(tl, name)
        if want[name] is None:
            assert leaf is None
            continue
        assert isinstance(leaf, jax.Array) and leaf.dtype == bool
        assert isinstance(getattr(fetched, name), np.ndarray)
        np.testing.assert_array_equal(np.asarray(leaf), want[name])
        np.testing.assert_array_equal(getattr(fetched, name), want[name])
    counted = faults.timeline_counters(tl)
    assert counted["timeline_placement"] == "device"
    assert faults.timeline_counters(fetched)["timeline_placement"] == "host"
    if want["node_up"] is not None:
        assert counted["rejoin_rows"] == int(want["rejoin"].sum())
        assert counted["down_share"] == pytest.approx(1.0 - want["node_up"].mean(), abs=1e-12)
        assert faults.timeline_counters(fetched) == dict(counted, timeline_placement="host")


@pytest.mark.parametrize("process,which,rate", [
    ("burst1", "edge_up", 0.3), ("churn_iid", "node_up", 0.1), ("stragglers", "node_up", 0.1)])
def test_a_chain_without_memory_is_the_memoryless_draw(process, which, rate):
    """``burst_len = 1`` and the iid-equivalent churn point hold every round's
    uniform against one threshold: the bits the memoryless fault layer draws
    inside the step."""
    seed, horizon = 99, 30
    topo = build_topology("ring", N, impl="neighbor")
    tl = faults.build_fault_timeline(topo, horizon, seed, **PROCESSES[process])
    tag = 0x0FA17 if which == "edge_up" else 0x57A66
    key = jax.random.fold_in(jax.random.key(seed), tag)
    for t in range(horizon):
        u = jax.random.uniform(jax.random.fold_in(key, t), (N,), dtype=jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(getattr(tl, which)[t]), np.asarray(u >= np.float32(rate)))


def test_the_scan_is_handed_the_arrays_the_chains_scans_returned():
    """Between the chains' scans and the experiment's scan no ``[T, .]`` leaf
    crosses the host: the fault layer's tables ARE the timeline's device
    arrays, and placing them for one device hands the same arrays on."""
    topo = build_topology("ring", N, impl="neighbor")
    fm = faults.make_faulty_mixing(
        topo, 0.3, 5, burst_len=4.0, mttf=6.0, mttr=3.0, rejoin="neighbor_restart",
        horizon=T)
    assert fm.addressing == "shift" and sorted(fm.tables) == ["edge_up", "node_up", "rejoin"]
    placed = replicate(None, fm.tables)
    for name, leaf in fm.tables.items():
        assert isinstance(leaf, jax.Array)
        assert leaf is getattr(fm.timeline, name) and placed[name] is leaf
    # a dense adjacency, a host consumer's rebuild and an injected timeline
    # keep host arrays, as ever
    dense = faults.build_fault_timeline(
        build_topology("ring", 16), 20, 5, edge_drop_prob=0.3, burst_len=4.0, mttf=6.0, mttr=3.0)
    assert all(isinstance(getattr(dense, k), np.ndarray) for k in ("edge_up", "node_up", "rejoin"))
    injected = faults.make_faulty_mixing(
        topo, 0.3, 5, burst_len=4.0, mttf=6.0, mttr=3.0, horizon=T,
        timeline=fm.timeline.host())
    assert faults.timeline_counters(injected.timeline)["timeline_placement"] == "host"
    np.testing.assert_array_equal(
        np.asarray(injected.tables["edge_up"]), np.asarray(fm.tables["edge_up"]))


def test_a_calls_leaves_end_with_the_call(cell):
    """The fault layer's closures hold no reference cycle, so the timeline's
    device arrays (0.79 GB at the cell's size) are freed when the call
    returns, not at the garbage collector's next full pass."""
    import gc

    def alive():
        return sum(1 for a in jax.live_arrays() if a.dtype == bool and a.shape == (T, N))

    config, traffic = cell
    run_program(config, traffic, 3)  # every cache warm
    gc.collect()
    before = alive()
    gc.disable()
    try:
        for _ in range(2):
            run_program(config, traffic, 3)
            assert alive() == before
    finally:
        gc.enable()


def test_the_reference_restates_the_chains(cell):
    """The reference's count of the rounds a worker came back, and its share
    of rounds down, over a whole horizon: the timeline's, to the bit."""
    config, _ = cell
    seed, horizon = 424242, 200
    for churn in sorted(CHURN):
        exp = at(config, churn)["experiment"]
        topo = build_topology("ring", N, impl="neighbor")
        tl = faults.build_fault_timeline(topo, horizon, seed, **CHURN[churn])
        back, down = dsgd_ring_churn.chain_counts(seed, N, horizon, exp)
        counted = faults.timeline_counters(tl)
        assert back == counted["rejoin_rows"] > 0
        assert down == pytest.approx(counted["down_share"], abs=1e-12)


def test_a_call_without_chains_says_nothing_of_them(cell):
    """The root's five arguments are a call's whose faults have memory, and
    no other's: the memoryless sibling's root is as it was."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    _, config, traffic = harness.load_cell(
        bench, "glm81_ring262k_drop30strag10.steady1k", rehearse=True)
    _, args, _, _, _ = run_program(config, traffic, 3)
    assert args["fault_form"] == "drawn"
    assert not {"fault_chains", "rejoin", "rejoin_rows", "down_share",
                "timeline_placement"} & set(args)
    # churn that resumes stale rows: the policy and the share, no restarted rows
    frozen = dict(cell[0], experiment=dict(cell[0]["experiment"], rejoin="frozen"))
    _, args, _, _, _ = run_program(frozen, cell[1], 3)
    assert args["rejoin"] == "frozen" and args["fault_chains"] == "burst:0.3x4,churn:400/150"
    assert "rejoin_rows" not in args and 0.0 < args["down_share"] < 1.0


def test_the_file_states_what_the_cell_runs():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as fh:
        whole = json.load(fh)
    with open(os.path.join(ROOT, "benchmark", "configs", "glm81_ring262k.json")) as fh:
        sibling = json.load(fh)
    # the sibling's experiment but for the four rates and the policy, at its size
    assert whole["experiment"] == dict(
        sibling["experiment"], edge_drop_prob=0.3, burst_len=4.0, mttf=400.0, mttr=150.0,
        rejoin="neighbor_restart")
    assert whole["dataset"] == sibling["dataset"]
    assert whole["reduced"] == sibling["reduced"] and whole["architecture"] is None
    assert set(whole["reduced"]) == set(whole["reduced_why"])
    assert len(whole["guarantees"]) >= 6 and "composition" in whole["assumed"]
    assert whole["fault_controls"] == ["frozen_rejoin", "memoryless", "no_freeze"]
    assert "import distributed_optimization_tpu" not in open(
        dsgd_ring_churn.__file__).read().replace("from ", "import ")
