"""Memoryless faults on a matrix-free topology are DRAWN inside the step
(ISSUE 32): round t makes its link and node bits from ``(seed, t)`` by the
rule ``parallel/faults.py`` documents ("Matrix-free draws"), where every
fault mode used to unroll a ``[horizon, E]`` + ``[horizon, N]`` timeline on
the device, fetch it, and close it into the executable. The realization is
the timeline's bit for bit, so a drawn run is bitwise the run handed that
timeline; nothing ``[horizon, ·]`` is in the drawn program; a persistent
process's timeline reaches its program as arguments, not constants; the
(node, slot) → edge map is array code equal to the loop it replaced.
CPU, N = 64: values, structure and counts, never a time.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import assert_ulps_of_scale, small_backend_config

from distributed_optimization_tpu.backends import jax_backend
from distributed_optimization_tpu.observability.spans import Tracer
from distributed_optimization_tpu.parallel import build_topology, faults
from distributed_optimization_tpu.parallel.topology import incident_edge_slots
from distributed_optimization_tpu.utils.data import generate_synthetic_dataset

N, T, P_DROP, Q_STRAG = 64, 40, 0.3, 0.1
GRAPHS = {
    "ring": dict(topology="ring"),
    "torus": dict(topology="grid"),
    "sparse_er": dict(topology="erdos_renyi", erdos_renyi_p=0.1),
}


def topo_of(graph):
    kw = dict(GRAPHS[graph])
    name = kw.pop("topology")
    return build_topology(name, N, impl="neighbor", seed=3, **kw)


def cfg_of(graph, **kw):
    kw.setdefault("edge_drop_prob", P_DROP)
    kw.setdefault("straggler_prob", Q_STRAG)
    kw.setdefault("n_iterations", T)
    kw.setdefault("n_features", 20)
    kw.setdefault("n_informative_features", 10)
    return small_backend_config(
        n_workers=N, n_samples=N * 24, problem_type="logistic",
        topology_impl="neighbor", topology_seed=3, **GRAPHS[graph], **kw)


def run_rooted(cfg, ds, **kw):
    tracer = Tracer()
    with tracer.activate():
        result = jax_backend.run(cfg, ds, 0.0, executable_cache=False, **kw)
    events = tracer.spans()
    (root,) = [e for e in events if e["name"] == "dopt.run"]
    children = [e["name"] for e in events if e["parent"] == root["id"]]
    return result, root["args"], children


def handed_the_timeline(monkeypatch):
    """Every later ``make_faulty_mixing`` of this test gets the timeline
    ``build_fault_timeline`` unrolls for its arguments: the form every
    matrix-free fault mode took before ISSUE 32."""
    real = faults.make_faulty_mixing

    def with_timeline(topo, drop_prob, seed, **kw):
        kw["timeline"] = faults.build_fault_timeline(
            topo, kw["horizon"], seed, edge_drop_prob=drop_prob,
            straggler_prob=kw.get("straggler_prob", 0.0))
        return real(topo, drop_prob, seed, **kw)

    monkeypatch.setattr(jax_backend, "make_faulty_mixing", with_timeline)


@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_the_drawn_bits_are_the_timelines(graph):
    topo = topo_of(graph)
    tl = faults.build_fault_timeline(
        topo, T, 11, edge_drop_prob=P_DROP, straggler_prob=Q_STRAG)
    drawn = faults.make_faulty_mixing(topo, P_DROP, 11, straggler_prob=Q_STRAG)
    timed = faults.make_faulty_mixing(
        topo, P_DROP, 11, straggler_prob=Q_STRAG, timeline=tl)
    assert drawn.timeline is None and timed.timeline is tl
    assert sorted(drawn.tables) == ["mask", "nbr", "slot"]
    assert sorted(timed.tables) == ["edge_up", "mask", "nbr", "node_up", "slot"]
    slot = np.asarray(drawn.tables["slot"]).T  # kept slot-major, [k_max, N]
    live = drawn.make_neighbor_liveness(topo.nbr_idx, topo.nbr_mask)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((N, 7)), jnp.float32)
    for t in range(T):
        m = tl.node_up[t].astype(np.float32)
        np.testing.assert_array_equal(np.asarray(drawn.active(t)), m)
        want = (topo.nbr_mask * tl.edge_up[t][slot]
                * m[:, None] * m[topo.nbr_idx]).astype(np.float32)
        np.testing.assert_array_equal(np.asarray(live(t)), want)
        np.testing.assert_array_equal(
            np.asarray(drawn.mix(t, x)), np.asarray(timed.mix(t, x)))
        assert float(drawn.realized_degree_sum(t)) == want.sum()


@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_a_drawn_run_is_bitwise_the_run_handed_the_timeline(graph, monkeypatch):
    cfg = cfg_of(graph)
    ds = generate_synthetic_dataset(cfg)
    got, root, children = run_rooted(cfg, ds)
    assert root["fault_form"] == "drawn" and "dopt.run.faults" in children
    assert root["faults"] == "edge_drop:0.3,straggler:0.1"
    handed_the_timeline(monkeypatch)
    want, root, _ = run_rooted(cfg, ds)
    assert root["fault_form"] == "timeline"
    np.testing.assert_array_equal(got.history.objective, want.history.objective)
    np.testing.assert_array_equal(
        got.history.consensus_error, want.history.consensus_error)
    np.testing.assert_array_equal(got.final_models, want.final_models)
    assert (got.history.total_floats_transmitted
            == want.history.total_floats_transmitted)


def loop_slots(nbr_idx, nbr_mask, edge_index):
    """``incident_edge_slots`` as it was: a dict of edges and a loop over
    every (node, slot). The oracle."""
    edge_id = {(int(i), int(j)): e for e, (i, j) in enumerate(edge_index)}
    slots = np.zeros(nbr_idx.shape, dtype=np.int32)
    for i in range(nbr_idx.shape[0]):
        for s in range(nbr_idx.shape[1]):
            if nbr_mask[i, s]:
                j = int(nbr_idx[i, s])
                slots[i, s] = edge_id[(min(i, j), max(i, j))]
    return slots


@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_the_array_slot_map_is_the_loops(graph):
    topo = topo_of(graph)
    edges = faults._edge_list(topo)
    got = incident_edge_slots(topo.nbr_idx, topo.nbr_mask, edges)
    assert got.dtype == np.int32
    np.testing.assert_array_equal(
        got, loop_slots(topo.nbr_idx, topo.nbr_mask, edges))
    # whatever order the edge list comes in, the ids follow it
    shuffled = edges[np.random.default_rng(1).permutation(len(edges))]
    np.testing.assert_array_equal(
        incident_edge_slots(topo.nbr_idx, topo.nbr_mask, shuffled),
        loop_slots(topo.nbr_idx, topo.nbr_mask, shuffled))
    with pytest.raises(KeyError):  # an edge of the table the list lacks
        incident_edge_slots(topo.nbr_idx, topo.nbr_mask, edges[1:])


def test_the_ring_edges_are_numbered_as_documented():
    """(0, 1), (0, N-1), (1, 2), ..., (N-2, N-1): the order the module's
    docstring states and the benchmark's plain reference restates."""
    edges = faults._edge_list(topo_of("ring"))
    want = [(0, 1), (0, N - 1)] + [(i, i + 1) for i in range(1, N - 1)]
    assert [tuple(e) for e in edges] == want


class _Traced(Exception):
    pass


def lowered_scan(cfg, ds, monkeypatch):
    """(StableHLO text, the data arguments) of the call's one device
    program, taken where ``_run`` hands it to the driver."""
    def grab(make_seg_scan, trips_per_eval, state0, data_args, mesh, config,
             n_evals, spans, **kw):
        raise _Traced(make_seg_scan(n_evals), (state0, jnp.int32(0), data_args))

    monkeypatch.setattr(jax_backend, "_drive_segments", grab)
    with pytest.raises(_Traced) as caught:
        jax_backend.run(cfg, ds, 0.0, use_mesh=False)
    seg_scan, args = caught.value.args
    return jax.jit(seg_scan).lower(*args).as_text(), args[2]


def horizon_shapes(text, horizon):
    """Every tensor type in ``text`` whose leading dimension is the
    horizon and that has a second one (``tensor<37x64xi1>``), but for the
    scan's own iteration numbers, ``ts`` ``[trips, micro]`` int32."""
    found = set(re.findall(rf"tensor<{horizon}x\d+[x\d]*x\w+>", text))
    return sorted(found - {f"tensor<{horizon}x1xi32>"})


def test_a_memoryless_matrix_free_program_holds_nothing_by_the_horizon(monkeypatch):
    # a horizon that is no other dimension of the program
    cfg = cfg_of("ring", n_iterations=37)
    ds = generate_synthetic_dataset(cfg)
    text, data = lowered_scan(cfg, ds, monkeypatch)
    assert sorted(data["faults"]) == ["mask", "nbr", "slot"]
    assert horizon_shapes(text, 37) == []
    # ... and the tables are arguments: no [N, k] integer constant either
    assert not re.search(rf"stablehlo\.constant dense<.*tensor<{N}x2xi32>", text)
    # the form it replaced held the bits as constants of the program
    handed_the_timeline(monkeypatch)
    text, data = lowered_scan(cfg, ds, monkeypatch)
    assert sorted(data["faults"]) == ["edge_up", "mask", "nbr", "node_up", "slot"]
    assert horizon_shapes(text, 37) == ["tensor<37x64xi1>"]
    assert "stablehlo.constant" not in "".join(
        line for line in text.splitlines() if "tensor<37x64xi1>" in line)


@pytest.mark.parametrize("process", [
    dict(edge_drop_prob=0.3, burst_len=3.0, straggler_prob=0.0),
    dict(edge_drop_prob=0.0, straggler_prob=0.0, mttf=12.0, mttr=4.0),
    dict(edge_drop_prob=0.0, straggler_prob=0.0, participation_rate=0.7),
], ids=["bursts", "churn", "participation"])
def test_a_persistent_process_keeps_its_timeline_as_arguments(process, monkeypatch):
    cfg = cfg_of("ring", n_iterations=37, **process)
    ds = generate_synthetic_dataset(cfg)
    _, root, _ = run_rooted(cfg, ds)
    assert root["fault_form"] == "timeline"
    text, data = lowered_scan(cfg, ds, monkeypatch)
    leaves = {k: v for k, v in data["faults"].items() if v.shape[0] == 37}
    assert leaves and all(v.dtype == bool for v in leaves.values())
    # each [horizon, ·] leaf is a parameter of the program and no constant
    assert horizon_shapes(text, 37) == ["tensor<37x64xi1>"]
    assert root["fault_bytes"] >= sum(v.nbytes for v in data["faults"].values())
    for line in text.splitlines():
        assert not ("stablehlo.constant" in line and "tensor<37x64xi1>" in line)


def test_the_root_counts_what_the_fault_layer_holds_and_what_got_through():
    cfg = cfg_of("ring", n_iterations=400, eval_every=100)
    ds = generate_synthetic_dataset(cfg)
    result, root, _ = run_rooted(cfg, ds)
    # nbr s32, mask f32, slot s32, each [64, 2]
    assert root["fault_bytes"] == 3 * N * 2 * 4
    # a link carries a model iff it is up and both its ends are
    want = (1 - P_DROP) * (1 - Q_STRAG) ** 2
    assert root["live_edge_share"] == pytest.approx(want, abs=0.01)
    assert root["live_edge_share"] == pytest.approx(
        result.history.total_floats_transmitted / (400 * 2 * N * 21))
    # a fault-free call says nothing of faults
    _, root, children = run_rooted(
        cfg_of("ring", edge_drop_prob=0.0, straggler_prob=0.0), ds)
    assert not {"faults", "fault_form", "fault_bytes", "live_edge_share"} & set(root)


def test_forward_is_carried_under_the_cells_faults(monkeypatch):
    """p = 0.3, q = 0.1 on the neighbor table, the dense sampler: the margins
    ride the carry (a frozen row's are its frozen model's) and the run is the
    recomputed one to the last places."""
    cfg = cfg_of("ring", sampling_impl="dense", n_features=80,
                 n_informative_features=40)
    ds = generate_synthetic_dataset(cfg)
    got, root, _ = run_rooted(cfg, ds)
    assert root["forward"] == "carried" and root["fault_form"] == "drawn"
    monkeypatch.setattr(jax_backend, "_forward_is_carried", lambda *a, **k: False)
    want, root, _ = run_rooted(cfg, ds)
    assert root["forward"] == "recomputed"
    want32 = lambda a: np.asarray(a, dtype=np.float32)  # noqa: E731
    assert_ulps_of_scale(got.history.objective, want32(want.history.objective), 16)
    assert_ulps_of_scale(
        got.history.consensus_error, want32(want.history.consensus_error), 16)
    assert_ulps_of_scale(got.final_models, want32(want.final_models), 16)
