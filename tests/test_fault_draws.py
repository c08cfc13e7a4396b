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

import dataclasses
import hashlib
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
    "chain": dict(topology="chain"),
}
# How the fault layer addresses a neighbour (ISSUE 33), read off the
# neighbor table: a ring's is shifts, every other graph's index tables.
FORM = {"ring": "shift", "torus": "gather", "sparse_er": "gather", "chain": "gather"}


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
    # a ring's table is read by shifts and needs no table; the others keep
    # theirs, slot-major [k_max, N]
    held = [] if graph == "ring" else ["mask", "nbr", "slot"]
    assert drawn.addressing == timed.addressing == FORM[graph]
    assert sorted(drawn.tables) == held
    assert sorted(timed.tables) == sorted(held + ["edge_up", "node_up"])
    slot = incident_edge_slots(topo.nbr_idx, topo.nbr_mask, tl.edge_index)
    if held:
        np.testing.assert_array_equal(np.asarray(drawn.tables["slot"]).T, slot)
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
    assert root["fault_mixing"] == FORM[graph]
    handed_the_timeline(monkeypatch)
    want, root, _ = run_rooted(cfg, ds)
    assert root["fault_form"] == "timeline" and root["fault_mixing"] == FORM[graph]
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


@pytest.mark.parametrize("graph", ["ring", "torus"])
def test_a_memoryless_matrix_free_program_holds_nothing_by_the_horizon(graph, monkeypatch):
    # a horizon that is no other dimension of the program
    cfg = cfg_of(graph, n_iterations=37)
    ds = generate_synthetic_dataset(cfg)
    assert run_rooted(cfg, ds)[1]["fault_mixing"] == FORM[graph]
    held = [] if graph == "ring" else ["mask", "nbr", "slot"]
    text, data = lowered_scan(cfg, ds, monkeypatch)
    assert sorted(data["faults"]) == held
    assert horizon_shapes(text, 37) == []
    # ... and the tables are arguments (the ring has none to hand over): no
    # [N, k] integer constant either, and no gather of a ring's neighbours
    assert not re.search(rf"stablehlo\.constant dense<.*tensor<{N}x\dxi32>", text)
    k = 2 if graph == "ring" else 4
    assert (f"tensor<{N}x{k}xi32>" in text) == (graph != "ring")
    # the form it replaced held the bits as constants of the program
    handed_the_timeline(monkeypatch)
    text, data = lowered_scan(cfg, ds, monkeypatch)
    assert sorted(data["faults"]) == sorted(held + ["edge_up", "node_up"])
    n_edges = N * k // 2
    assert horizon_shapes(text, 37) == sorted(
        {"tensor<37x64xi1>", f"tensor<37x{n_edges}xi1>"})
    assert "stablehlo.constant" not in "".join(
        line for line in text.splitlines() if "tensor<37x" in line and "xi1>" in line)


@pytest.mark.parametrize("process", [
    dict(edge_drop_prob=0.3, burst_len=3.0, straggler_prob=0.0),
    dict(edge_drop_prob=0.0, straggler_prob=0.0, mttf=12.0, mttr=4.0),
    dict(edge_drop_prob=0.0, straggler_prob=0.0, participation_rate=0.7),
], ids=["bursts", "churn", "participation"])
@pytest.mark.parametrize("graph", ["ring", "torus"])
def test_a_persistent_process_keeps_its_timeline_as_arguments(graph, process, monkeypatch):
    cfg = cfg_of(graph, n_iterations=37, **process)
    ds = generate_synthetic_dataset(cfg)
    _, root, _ = run_rooted(cfg, ds)
    assert root["fault_form"] == "timeline" and root["fault_mixing"] == FORM[graph]
    text, data = lowered_scan(cfg, ds, monkeypatch)
    leaves = {k: v for k, v in data["faults"].items() if v.shape[0] == 37}
    assert leaves and all(v.dtype == bool for v in leaves.values())
    # the shift form is handed the timeline's leaves and nothing else
    assert (set(data["faults"]) == set(leaves)) == (graph == "ring")
    # each [horizon, ·] leaf is a parameter of the program and no constant
    shapes = sorted({f"tensor<37x{v.shape[1]}xi1>" for v in leaves.values()})
    assert horizon_shapes(text, 37) == shapes
    assert root["fault_bytes"] >= sum(v.nbytes for v in data["faults"].values())
    for line in text.splitlines():
        assert not ("stablehlo.constant" in line and any(s in line for s in shapes))


@pytest.mark.parametrize("graph, degree", [("ring", 2), ("chain", 2), ("torus", 4)])
def test_the_root_counts_what_the_fault_layer_holds_and_what_got_through(graph, degree):
    cfg = cfg_of(graph, n_iterations=400, eval_every=100)
    ds = generate_synthetic_dataset(cfg)
    result, root, _ = run_rooted(cfg, ds)
    assert root["fault_mixing"] == FORM[graph]
    # gather: nbr s32, mask f32, slot s32, each [64, k_max]; shift: no table
    assert root["fault_bytes"] == (0 if graph == "ring" else 3 * N * degree * 4)
    # a link carries a model iff it is up and both its ends are
    want = (1 - P_DROP) * (1 - Q_STRAG) ** 2
    assert root["live_edge_share"] == pytest.approx(want, abs=0.01)
    links = 2 * (N - 1) if graph == "chain" else degree * N  # Σ deg_i
    assert root["live_edge_share"] == pytest.approx(
        result.history.total_floats_transmitted / (400 * links * 21))
    # a fault-free call says nothing of faults
    _, root, children = run_rooted(
        cfg_of(graph, edge_drop_prob=0.0, straggler_prob=0.0), ds)
    assert not {"faults", "fault_form", "fault_bytes", "fault_mixing",
                "live_edge_share"} & set(root)


def test_forward_is_carried_under_the_cells_faults(monkeypatch):
    """p = 0.3, q = 0.1 on the neighbor table, the dense sampler: the margins
    ride the carry (a frozen row's are its frozen model's) and the run is the
    recomputed one to the last places."""
    cfg = cfg_of("ring", sampling_impl="dense", n_features=80,
                 n_informative_features=40)
    ds = generate_synthetic_dataset(cfg)
    got, root, _ = run_rooted(cfg, ds)
    assert root["forward"] == "carried" and root["fault_form"] == "drawn"
    assert root["fault_mixing"] == "shift"
    monkeypatch.setattr(jax_backend, "_forward_is_carried", lambda *a, **k: False)
    want, root, _ = run_rooted(cfg, ds)
    assert root["forward"] == "recomputed"
    want32 = lambda a: np.asarray(a, dtype=np.float32)  # noqa: E731
    assert_ulps_of_scale(got.history.objective, want32(want.history.objective), 16)
    assert_ulps_of_scale(
        got.history.consensus_error, want32(want.history.consensus_error), 16)
    assert_ulps_of_scale(got.final_models, want32(want.final_models), 16)


# --- ISSUE 33: on a ring the fault layer reads its neighbours by shifts ------
#
# One round, two ways to address a neighbour. No option asks for either, so
# the two builders are called directly, on the same ring, keys and t.

# jax 0.9's lowering of the two graphs below on the parent commit (fd89778),
# taken by ``lowered_scan`` on ``cfg_of(graph, n_iterations=37,
# sampling_impl="dense")``: what "a graph that is not a shift runs the
# parent's program" is held to. Another jax spells the text its own way and
# is held to the builder's name alone. Under the dense sampler since ISSUE
# 40, which changed the gather sampler these runs took on the CPU: the pins
# were taken again on ISSUE 40's parent (62d21c7), where the gather form
# still hashed to fd89778's ("63277440065029ae", "18283ae3dc7839e0").
PARENT_SCANS = {"jax": "0.9", "chain": "67058614145c344e", "sparse_er": "77c45438f0fd285b"}


def ring_of(n):
    """A ring's neighbor-table topology, N = 3 included (``build_topology``
    refuses a table as wide as the dense adjacency: 3 workers of degree 2)."""
    from distributed_optimization_tpu.parallel.topology import (
        Topology, _ring_neighbor_tables)

    nbr, mask = _ring_neighbor_tables(n)
    return Topology(name="ring", n=n, adjacency=None, mixing_matrix=None,
                    degrees=mask.sum(axis=1).astype(np.float64),
                    nbr_idx=nbr, nbr_mask=mask)


def keys_of(seed):
    key = jax.random.key(seed)
    return dict(fault_key=jax.random.fold_in(key, 0x0FA17),
                node_key=jax.random.fold_in(key, 0x57A66))


def both_forms(topo, timeline=None, *, seed=11, **kw):
    kw = dict(dict(drop_prob=0.0, straggler_prob=0.0, churn_active=False,
                   participation_active=False, rejoin="frozen"), **kw)
    return tuple(
        build(topo, timeline, **kw, **keys_of(seed))
        for build in (faults._make_gather_faulty_mixing,
                      faults._make_shift_faulty_mixing))


FAULTS = {
    "drop_and_stragglers": dict(drop_prob=P_DROP, straggler_prob=Q_STRAG),
    "drop": dict(drop_prob=P_DROP),
    "stragglers": dict(straggler_prob=Q_STRAG),
    "bursts": dict(drop_prob=P_DROP, timeline=dict(
        edge_drop_prob=P_DROP, burst_len=3.0)),
    "churn_restart": dict(churn_active=True, rejoin="neighbor_restart",
                          timeline=dict(mttf=6.0, mttr=3.0)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("n", [3, 4, 64])
def test_shift_and_gather_realize_one_round(n, fault):
    """The same ``live`` bits, participation and degree sums exactly; ``mix``,
    ``neighbor_sum`` and ``rejoin_restart`` bit for bit operation by operation
    (every sum of the round has two terms, and two terms commute), and to 2
    units in the last place as two compiled programs, where XLA:CPU contracts
    ``w_self·x + (w_l·x_l + w_r·x_r)`` into fused multiply-adds as each
    program's fusions fall (``conftest.assert_ulps_of_scale``)."""
    topo, rounds = ring_of(n), 12
    assert faults._table_is_a_ring(topo)
    kw = dict(FAULTS[fault])
    tl = kw.pop("timeline", None)
    if tl is not None:
        tl = faults.build_fault_timeline(topo, rounds, 11, **tl)
    gather, shift = both_forms(topo, tl, **kw)
    assert (gather.addressing, shift.addressing) == ("gather", "shift")
    assert sorted(shift.tables) == sorted(
        k for k in ("edge_up", "node_up", "rejoin")
        if tl is not None and getattr(tl, k) is not None
        and (k != "rejoin" or fault == "churn_restart"))
    assert {"nbr", "mask"} <= set(gather.tables)
    tables = (topo.nbr_idx, topo.nbr_mask)
    live_g, live_s = (f.make_neighbor_liveness(*tables) for f in (gather, shift))
    x = jnp.asarray(
        np.random.default_rng(n).standard_normal((n, 7)) + 3.0, jnp.float32)
    ops = ["mix", "neighbor_sum"] + ["rejoin_restart"] * (fault == "churn_restart")
    compiled = {(f.addressing, op): jax.jit(getattr(f, op))
                for f in (gather, shift) for op in ops}
    links = 0.0
    for t in range(rounds):
        m = np.asarray(gather.active(t))
        np.testing.assert_array_equal(np.asarray(shift.active(t)), m)
        lv = np.asarray(live_g(t))
        np.testing.assert_array_equal(np.asarray(live_s(t)), lv)
        assert float(shift.realized_degree_sum(t)) == float(
            gather.realized_degree_sum(t)) == lv.sum()
        links += lv.sum()
        for op in ops:
            want = np.asarray(getattr(gather, op)(t, x))
            np.testing.assert_array_equal(np.asarray(getattr(shift, op)(t, x)), want)
            assert_ulps_of_scale(
                compiled["shift", op](t, x), compiled["gather", op](t, x), 2)
        mixed = np.asarray(shift.mix(t, x))
        # W_t is doubly stochastic: the mean stays; a node that sat out has
        # lost every link, so its row of W_t is the identity's
        np.testing.assert_allclose(mixed.mean(axis=0), np.asarray(x).mean(axis=0), rtol=2e-6)
        np.testing.assert_array_equal(mixed[m == 0], np.asarray(x)[m == 0])
    assert 0 < links < rounds * 2 * n  # some links were down, some up


def test_shift_liveness_is_in_the_callers_order():
    """``make_neighbor_liveness`` answers in the order of the table it is
    handed: the topology's own is ascending (row 0 is [1, N-1], not
    left/right), and a caller may list a row's neighbours the other way."""
    topo = ring_of(8)
    gather, shift = both_forms(topo, drop_prob=P_DROP, straggler_prob=Q_STRAG)
    assert topo.nbr_idx[0].tolist() == [1, 7] and topo.nbr_idx[7].tolist() == [0, 6]
    flipped = topo.nbr_idx[:, ::-1].copy()
    for nbr in (topo.nbr_idx, flipped):
        got, want = (f.make_neighbor_liveness(nbr, topo.nbr_mask) for f in (shift, gather))
        for t in range(8):
            np.testing.assert_array_equal(np.asarray(got(t)), np.asarray(want(t)))
    with pytest.raises(ValueError, match="not this ring's"):
        shift.make_neighbor_liveness((topo.nbr_idx + 2) % 8, topo.nbr_mask)


def test_shift_under_vmap_with_traced_keys():
    """As ``run_batch`` builds it: inside ``vmap``, the keys and the drop
    probability tracers. Each replica is its own sequential round."""
    topo, seeds = ring_of(16), [3, 5, 2147483999]
    x = jnp.asarray(np.random.default_rng(0).standard_normal((16, 5)), jnp.float32)
    stacked = {k: jnp.stack([keys_of(s)[k] for s in seeds]) for k in keys_of(0)}
    drops = jnp.asarray([0.3, 0.3, 0.5], jnp.float32)

    def replica(keys, p, t):
        fm = faults._make_shift_faulty_mixing(
            topo, None, drop_prob=p, straggler_prob=Q_STRAG, churn_active=False,
            participation_active=False, rejoin="frozen", **keys)
        return fm.mix(t, x), fm.realized_degree_sum(t), fm.active(t)

    for t in (0, 7):
        mixed, links, up = jax.vmap(replica, in_axes=(0, 0, None))(stacked, drops, t)
        for r, seed in enumerate(seeds):
            (one,) = both_forms(topo, seed=seed, drop_prob=float(drops[r]),
                                straggler_prob=Q_STRAG)[1:]
            assert_ulps_of_scale(mixed[r], one.mix(t, x), 2)
            assert float(links[r]) == float(one.realized_degree_sum(t))
            np.testing.assert_array_equal(np.asarray(up[r]), np.asarray(one.active(t)))


@pytest.mark.parametrize("graph, is_ring", [
    ("ring", True), ("chain", False), ("torus", False), ("sparse_er", False)])
def test_the_form_is_read_off_the_table(graph, is_ring):
    topo = topo_of(graph)
    assert faults._table_is_a_ring(topo) is is_ring
    fm = faults.make_faulty_mixing(topo, P_DROP, 11, straggler_prob=Q_STRAG)
    assert fm.addressing == FORM[graph]
    if is_ring:
        # the table, not the name: the same ring listed descending is a gather
        other = dataclasses.replace(topo, nbr_idx=topo.nbr_idx[:, ::-1].copy())
        assert not faults._table_is_a_ring(other)
        # ... and an injected timeline whose edges are numbered another way
        tl = faults.build_fault_timeline(topo, 5, 11, edge_drop_prob=P_DROP)
        order = np.random.default_rng(0).permutation(N)
        moved = dataclasses.replace(
            tl, edge_index=tl.edge_index[order], edge_up=tl.edge_up[:, order])
        same = [faults.make_faulty_mixing(topo, P_DROP, 11, timeline=t) for t in (tl, moved)]
        assert [f.addressing for f in same] == ["shift", "gather"]
        live = [f.make_neighbor_liveness(topo.nbr_idx, topo.nbr_mask) for f in same]
        for t in range(5):
            np.testing.assert_array_equal(np.asarray(live[0](t)), np.asarray(live[1](t)))


@pytest.mark.parametrize("graph", ["chain", "sparse_er"])
def test_a_graph_that_is_not_a_shift_runs_the_parents_program(graph, monkeypatch):
    cfg = cfg_of(graph, n_iterations=37, sampling_impl="dense")
    ds = generate_synthetic_dataset(cfg)
    assert run_rooted(cfg, ds)[1]["fault_mixing"] == "gather"
    text, data = lowered_scan(cfg, ds, monkeypatch)
    assert sorted(data["faults"]) == ["mask", "nbr", "slot"]
    # the builder it goes through is the gather's, called directly ...
    direct = faults._make_gather_faulty_mixing
    monkeypatch.setattr(faults, "_make_shift_faulty_mixing", None)
    monkeypatch.setattr(faults, "_table_is_a_ring", lambda topo: False)
    assert lowered_scan(cfg, ds, monkeypatch)[0] == text
    assert faults._make_gather_faulty_mixing is direct
    # ... and the program is the parent's, byte for byte
    if jax.__version__.startswith(PARENT_SCANS["jax"]):
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENT_SCANS[graph]
