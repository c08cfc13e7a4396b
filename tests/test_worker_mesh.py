"""Sharded worker mesh (ISSUE 11, docs/PERF.md §16) on the 8-device CPU mesh.

Three layers, mirroring the tentpole's contract:

1. **Halo plan** (host-side, no devices): the send/recv schedule built by
   ``topology.build_halo_plan`` is emulated in numpy and checked against
   the global gather — ``ext[local_nbr]`` must reproduce ``x[nbr_idx]``
   row for row — and the shard-local index map is checked against the
   dense realized adjacency.
2. **Halo collectives**: ``make_halo_mixing_op`` is the single-device
   gather operator under jit (to a few units of the rows' scale), by
   shifts where the neighbor table is a ring's or a torus's cut by whole
   grid rows and through the per-shard tables on every other (ISSUEs 35
   and 53: the two forms against each other, and which table takes
   which), and the compiled HLO of a ring round ships
   exactly the boundary rows per device (2·d floats, independent of N)
   with no gather, no index table and no all-gather of the [N, d] state.
3. **End-to-end parity**: sharded-vs-unsharded trajectories through the
   real backend at matched N — plain ring/ER, gradient tracking, churn,
   participation, Byzantine screening, checkpoint/resume — bitwise on the
   final models (the one exception, trimmed-mean at wide-k ER, sits at
   the repo's documented ≤1e-12 f64 cross-program-shape convention).

Plus the composition-validation satellites: every not-yet-sharded feature
is rejected with the missing piece named, and auto/explicit mesh sizing
agrees (the ``make_worker_mesh`` grid-rows satellite).
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import assert_ulps_of_scale

from distributed_optimization_tpu.config import ExperimentConfig
from distributed_optimization_tpu.parallel.topology import (
    build_halo_plan,
    build_topology,
    neighbor_tables_for,
)

N = 16
T = 30
BASE = dict(
    n_workers=N, n_samples=320, n_features=10, n_informative_features=6,
    problem_type="quadratic", n_iterations=T, topology="ring",
    algorithm="dsgd", local_batch_size=8, dtype="float64", eval_every=10,
    topology_impl="neighbor", mixing_impl="gather",
)
ER = dict(topology="erdos_renyi", erdos_renyi_p=0.5, topology_seed=7)


def make_cfg(**kw):
    return ExperimentConfig(**{**BASE, **kw})


@pytest.fixture(scope="module")
def problem():
    from distributed_optimization_tpu.utils.data import (
        generate_synthetic_dataset,
    )
    from distributed_optimization_tpu.utils.oracle import (
        compute_reference_optimum,
    )

    cfg = make_cfg()
    ds = generate_synthetic_dataset(cfg)
    _, f_opt = compute_reference_optimum(ds, cfg.reg_param)
    return ds, f_opt


def run_pair(problem, **kw):
    """(unsharded, sharded) backend results for the same config."""
    from distributed_optimization_tpu.backends import jax_backend

    ds, f_opt = problem
    cfg_u = make_cfg(**kw)
    cfg_s = cfg_u.replace(worker_mesh=4)
    r_u = jax_backend.run(cfg_u, ds, f_opt, use_mesh=False, return_state=True)
    r_s = jax_backend.run(cfg_s, ds, f_opt, return_state=True)
    return r_u, r_s


# Thirty rounds of two programs that each round a row's products their own
# way (conftest.assert_ulps_of_scale): read 2 to 6 units of the models'
# scale; a float32 run is 2**29 such units off.
MODEL_ULPS = 32


def assert_parity(r_u, r_s, *, models_bitwise=True, models_ulps=None,
                  obj_rtol=1e-12):
    mu, ms = np.asarray(r_u.final_models), np.asarray(r_s.final_models)
    if models_ulps is not None:
        assert_ulps_of_scale(ms, mu, models_ulps)
    elif models_bitwise:
        np.testing.assert_array_equal(mu, ms)
    else:
        # The documented f64 cross-program-shape convention (XLA reduce
        # order differs between the sharded and unsharded programs for
        # wide-k sorts; see docs/PERF.md §16).
        np.testing.assert_allclose(mu, ms, rtol=obj_rtol, atol=1e-12)
    ou = np.asarray(r_u.history.objective, dtype=np.float64)
    os_ = np.asarray(r_s.history.objective, dtype=np.float64)
    # The objective eval reduces over the worker axis, whose GSPMD
    # reduction tree differs from the single-device linear order — 1-ulp
    # class, never trajectory divergence.
    np.testing.assert_allclose(ou, os_, rtol=obj_rtol, atol=1e-12)


# ------------------------------------------------------------- halo plan


def _emulated_ext(plan, x, p):
    """Run shard p's planned exchange in numpy: block + filled halo."""
    S = plan.shard_rows
    blocks = x.reshape(plan.n_shards, S, -1)
    halo = np.zeros((plan.h_max + 1, blocks.shape[-1]), x.dtype)
    for st in plan.steps:
        src = (p - st.rotation) % plan.n_shards
        halo[st.recv_pos[p]] = blocks[src][st.send_idx[src]]
    halo[plan.h_max] = 0.0  # the dump row padded traffic lands in
    return np.concatenate([blocks[p], halo], axis=0)


@pytest.mark.parametrize("name,n,shards", [
    ("ring", 16, 4), ("ring", 24, 8), ("chain", 16, 2),
    ("erdos_renyi", 16, 4), ("erdos_renyi", 32, 8), ("grid", 64, 4),
])
def test_halo_plan_gather_matches_global(rng, name, n, shards):
    """ext[local_nbr] == x[nbr_idx]: the bitwise-parity contract, emulated
    host-side from the plan's own send/recv schedule."""
    topo = build_topology(name, n, seed=3, impl="neighbor")
    nbr_idx, nbr_mask = neighbor_tables_for(topo)
    plan = build_halo_plan(nbr_idx, nbr_mask, shards)
    x = rng.normal(size=(n, 5))
    S = plan.shard_rows
    for p in range(shards):
        ext = _emulated_ext(plan, x, p)
        local = plan.local_nbr[p * S:(p + 1) * S]
        mask = nbr_mask[p * S:(p + 1) * S]
        got = ext[local]                      # [S, k_max, 5]
        want = x[nbr_idx[p * S:(p + 1) * S]]  # [S, k_max, 5]
        np.testing.assert_array_equal(got[mask], want[mask])


def test_halo_index_map_matches_dense_adjacency():
    """Shard-local indices map back to exactly the dense adjacency's
    neighbor sets (the ISSUE satellite's correctness cross-check)."""
    n, shards = 16, 4
    topo_d = build_topology("erdos_renyi", n, seed=7, impl="dense")
    topo_n = build_topology("erdos_renyi", n, seed=7, impl="neighbor")
    nbr_idx, nbr_mask = neighbor_tables_for(topo_n)
    plan = build_halo_plan(nbr_idx, nbr_mask, shards)
    S = plan.shard_rows
    adj = np.asarray(topo_d.adjacency) > 0
    for p in range(shards):
        halo = plan.halo_idx[p]
        for i in range(S):
            g = p * S + i
            mapped = set()
            for s in range(nbr_idx.shape[1]):
                if not nbr_mask[g, s]:
                    continue
                loc = plan.local_nbr[g, s]
                mapped.add(p * S + loc if loc < S else int(halo[loc - S]))
            assert mapped == set(np.flatnonzero(adj[g])), (p, i)


def test_halo_plan_counts_are_the_boundary():
    """Ring blocks: every shard ships exactly its 2 boundary rows (one per
    rotation), so the per-device ICI accounting is 2 rows/round flat."""
    topo = build_topology("ring", 32, impl="neighbor")
    plan = build_halo_plan(*neighbor_tables_for(topo), 4)
    assert plan.h_max == 2
    assert [st.rotation for st in plan.steps] == [1, 3]
    np.testing.assert_array_equal(plan.sent_rows, [2, 2, 2, 2])
    np.testing.assert_array_equal(plan.recv_rows, [2, 2, 2, 2])


def test_halo_plan_rejections():
    topo = build_topology("ring", 16, impl="neighbor")
    tables = neighbor_tables_for(topo)
    with pytest.raises(ValueError, match="divide"):
        build_halo_plan(*tables, 3)
    with pytest.raises(ValueError, match=">= 2"):
        build_halo_plan(*tables, 1)


# ------------------------------------------------------- halo collectives


def _mesh(p):
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:p]), ("workers",))


@pytest.mark.parametrize("name,n,shards", [
    ("ring", 16, 4), ("ring", 16, 8), ("erdos_renyi", 16, 4),
])
def test_halo_mixing_bitwise_vs_gather(rng, name, n, shards):
    """The halo op under jit is the single-device gather op under jit: the
    same per-row op sequence, boundary rows just arrive over ICI. Two
    executables, so to a few float32 units of the rows' scale (read: 1;
    ``conftest.assert_ulps_of_scale`` says why not bitwise); bfloat16
    arithmetic is far outside."""
    from distributed_optimization_tpu.ops.mixing import make_mixing_op
    from distributed_optimization_tpu.parallel.collectives import (
        make_halo_mixing_op,
    )

    topo = build_topology(name, n, seed=3, impl="neighbor")
    halo_op = make_halo_mixing_op(topo, _mesh(shards), dtype=jnp.float32)
    gather_op = make_mixing_op(topo, impl="gather")
    x = jnp.asarray(rng.normal(size=(n, 7)).astype(np.float32))
    for form in ("apply", "neighbor_sum"):
        want = np.asarray(jax.jit(getattr(gather_op, form))(x))
        got = np.asarray(jax.jit(getattr(halo_op, form))(x))
        assert got.dtype == want.dtype == np.float32
        assert_ulps_of_scale(got, want, 4)
    low = make_halo_mixing_op(topo, _mesh(shards), dtype=jnp.bfloat16)
    rounded = jax.jit(low.apply)(x.astype(jnp.bfloat16))
    with pytest.raises(AssertionError):
        assert_ulps_of_scale(
            np.asarray(rounded.astype(jnp.float32)),
            np.asarray(jax.jit(gather_op.apply)(x)), 4)


@pytest.mark.parametrize("form", ["apply", "neighbor_sum"])
def test_halo_gather_round_sums_slot_by_slot(form):
    """A block's round is the one-device round's ``slot_sum`` on the block
    with its halo behind it (ISSUE 36): a loop over the table's slots, one
    row gather in its body, and the ``[S, k_max, d]`` stack of every
    neighbour's row in no instruction."""
    from distributed_optimization_tpu.parallel.collectives import (
        make_halo_mixing_op,
    )

    n, d, shards = 64, 7, 4
    topo = build_topology("erdos_renyi", n, erdos_renyi_p=0.2, seed=7, impl="neighbor")
    k_max = topo.nbr_idx.shape[1]
    assert k_max > 8
    op = make_halo_mixing_op(topo, _mesh(shards))
    assert op.impl == "halo_gather"
    text = jax.jit(getattr(op, form)).lower(jnp.zeros((n, d), jnp.float32)).as_text()
    assert "stablehlo.while" in text
    assert f"tensor<{n // shards}x{k_max}x{d}xf32>" not in text
    assert f"tensor<{k_max}x{n // shards}x{d}xf32>" not in text


def _permute_payload_floats(hlo: str) -> list[int]:
    out = []
    for line in hlo.splitlines():
        if re.search(r"collective-permute(-start)?\(", line):
            m = re.search(r"= (?:f32|bf16|f64|u32|s32)\[([\d,]*)\]", line)
            assert m, f"unparseable collective-permute line: {line.strip()}"
            dims = [int(v) for v in m.group(1).split(",") if v]
            out.append(int(np.prod(dims)) if dims else 1)
    return out


def test_halo_ring_round_ships_boundary_rows_only():
    """Compiled HLO of one halo ring round: two boundary CollectivePermutes
    of [1, d] each — 2·d floats per device, independent of N — and no
    all-gather of the [N, d] state (PAPER.md's real-collective claim)."""
    from distributed_optimization_tpu.parallel.collectives import (
        _make_halo_gather_mixing_op,
        make_halo_mixing_op,
    )
    from distributed_optimization_tpu.parallel.mesh import shard_over_workers

    n, d, shards = 32, 7, 8
    topo = build_topology("ring", n, impl="neighbor")
    mesh = _mesh(shards)
    op = make_halo_mixing_op(topo, mesh, dtype=jnp.float32)
    x = shard_over_workers(mesh, jnp.zeros((n, d), jnp.float32))
    hlo = jax.jit(op.apply).lower(x).compile().as_text()
    payloads = _permute_payload_floats(hlo)
    assert len(payloads) == 2, f"expected 2 boundary permutes, got {payloads}"
    assert sum(payloads) == 2 * d
    assert len(re.findall(rf"f32\[1,{d}\]\S* collective-permute", hlo)) == 2
    assert "all-gather" not in hlo
    # A ring's table is read by shifts (ISSUE 35): no row gather, and no
    # per-shard neighbor table baked into the executable.
    assert op.impl == "halo_shift"
    assert not re.search(r"\bgather\(", hlo)
    assert not re.search(r"s32\[[\d,]*,2\]", hlo)
    # ... both of which the gather form of the same round has (its table
    # slot-major since ISSUE 36: a block's two slots by its four rows).
    slot_major = rf"s32\[(\d+,)?2,{n // shards}\]"
    assert not re.search(slot_major, hlo)
    table_op = _make_halo_gather_mixing_op(topo, mesh, dtype=jnp.float32)
    table_hlo = jax.jit(table_op.apply).lower(x).compile().as_text()
    assert re.search(r"\bgather\(", table_hlo)
    assert re.search(slot_major, table_hlo)


def _swapped_ring(n):
    """A ring whose every row lists its two neighbours the other way
    round: the same graph, not a ring's table."""
    topo = build_topology("ring", n, impl="neighbor")
    return dataclasses.replace(topo, nbr_idx=topo.nbr_idx[:, ::-1].copy())


def _swapped_torus(n):
    """A torus whose every row lists its four neighbours descending: the
    same graph, not a torus's table."""
    topo = build_topology("grid", n, impl="neighbor")
    return dataclasses.replace(topo, nbr_idx=topo.nbr_idx[:, ::-1].copy())


def _neighbor(name, **kw):
    return lambda n: build_topology(name, n, impl="neighbor", **kw)


# graph -> (its topology at n workers, n, the form it takes over FOUR blocks)
HALO_FORMS = {
    "ring": (_neighbor("ring"), 16, "halo_shift"),
    "chain": (_neighbor("chain"), 16, "halo_gather"),
    # 8 x 8: two whole grid rows a block
    "grid": (_neighbor("grid"), 64, "halo_shift"),
    # 4 x 4: ONE grid row a block, ``up`` and ``down`` both wholly halo
    "grid_one_row_a_block": (_neighbor("grid"), 16, "halo_shift"),
    # 6 x 6: a block of nine workers ends inside a grid row
    "grid_cut_across_rows": (_neighbor("grid"), 36, "halo_gather"),
    "grid_slots_swapped": (_swapped_torus, 64, "halo_gather"),
    "erdos_renyi": (_neighbor("erdos_renyi", seed=3), 16, "halo_gather"),
    "ring_slots_swapped": (_swapped_ring, 16, "halo_gather"),
}


@pytest.mark.parametrize("graph", sorted(HALO_FORMS))
def test_halo_form_is_read_off_the_neighbor_table(rng, graph):
    """Shifts where the table IS a ring's, or a torus's that the mesh cuts
    by whole grid rows (ISSUE 53); the per-shard tables on every other (a
    ring or a torus listed another way, a torus cut through a grid row,
    among them): by the table and the mesh's size, by no option and not by
    the topology's name. Either way the round is the single-device gather
    operator's, the dense ``W @ x`` and, on a torus, the one-chip stencil's
    (two executables of one arithmetic: a few float32 units of the rows'
    scale)."""
    from distributed_optimization_tpu.ops.mixing import make_mixing_op
    from distributed_optimization_tpu.parallel.collectives import (
        make_halo_mixing_op,
    )

    build, n, form = HALO_FORMS[graph]
    topo = build(n)
    op = make_halo_mixing_op(topo, _mesh(4), dtype=jnp.float32)
    assert op.impl == form
    assert (op.tables is None) == (form == "halo_shift")
    x = jnp.asarray(rng.normal(size=(n, 7)).astype(np.float32))
    got = jax.jit(op.apply)(x)
    assert_ulps_of_scale(
        got, jax.jit(make_mixing_op(topo, impl="gather").apply)(x), 4)
    if topo.name != "erdos_renyi":  # whose dense build is another draw
        dense = build_topology(topo.name, n, impl="dense")
        assert_ulps_of_scale(
            got, np.asarray(dense.mixing_matrix, np.float32) @ np.asarray(x),
            4)
    if topo.name == "grid":
        stencil = make_mixing_op(topo, impl="stencil")
        for f in ("apply", "neighbor_sum"):
            assert_ulps_of_scale(
                jax.jit(getattr(op, f))(x), jax.jit(getattr(stencil, f))(x),
                4)


@pytest.mark.parametrize("side,blocks,is_one", [
    (8, 4, True), (8, 8, True), (4, 4, True), (6, 2, True), (3, 1, True),
    (6, 4, False),    # nine workers a block: a block ends inside a grid row
    (8, 3, False),    # the blocks do not even divide the workers
])
def test_the_torus_rule_reads_host_tables(side, blocks, is_one):
    """``table_is_a_torus_in_row_blocks`` beside ``table_is_a_ring``: both
    compare host arrays against the graph's own vectorized tables, so the
    name is never asked and a table listed another way answers no."""
    from distributed_optimization_tpu.parallel.topology import (
        table_is_a_ring,
        table_is_a_torus_in_row_blocks,
    )

    n = side * side
    topo = build_topology("grid", n, impl="neighbor")
    nbr, mask = neighbor_tables_for(topo)
    assert table_is_a_torus_in_row_blocks(nbr, mask, blocks) is is_one
    assert not table_is_a_ring(nbr, mask)
    # the dense build's derived tables are the same layout
    dense = build_topology("grid", n, impl="dense")
    assert table_is_a_torus_in_row_blocks(
        *neighbor_tables_for(dense), blocks) is is_one
    if not is_one:
        return
    # the same graph listed descending; one dead slot; another graph's table
    assert not table_is_a_torus_in_row_blocks(nbr[:, ::-1].copy(), mask, blocks)
    dead = mask.copy()
    dead[0, 0] = False
    assert not table_is_a_torus_in_row_blocks(nbr, dead, blocks)
    moved = nbr.copy()
    moved[[0, 1]] = moved[[1, 0]]
    assert not table_is_a_torus_in_row_blocks(moved, mask, blocks)
    for other in ("ring", "chain", "erdos_renyi"):
        t = build_topology(other, n, seed=3, impl="neighbor")
        assert not table_is_a_torus_in_row_blocks(
            *neighbor_tables_for(t), blocks)
        assert table_is_a_ring(*neighbor_tables_for(t)) is (other == "ring")


@pytest.mark.parametrize("stack", [(7,), (7, 3)], ids=["Nx7", "Nx7x3"])
@pytest.mark.parametrize("shards", [2, 4, 8])
@pytest.mark.parametrize("form", ["apply", "neighbor_sum"])
@pytest.mark.parametrize("graph,n", [("ring", 16), ("grid", 64)])
def test_halo_shift_is_the_halo_gather_round(rng, graph, n, form, shards, stack):
    """One round of the two forms built on the same ring, or the same torus
    in whole grid rows (the gather forced through its private builder: no
    option reaches it), on the stack in the rank the scan carries, the
    model-shaped ``[N, d, K]`` too (the torus's body works on axis 0
    alone, with no reshape to the grid): two programs of one arithmetic,
    to a few float32 units of the rows' scale. bfloat16 arithmetic is far
    outside."""
    from distributed_optimization_tpu.parallel.collectives import (
        _make_halo_gather_mixing_op,
        make_halo_mixing_op,
    )

    topo = build_topology(graph, n, impl="neighbor")
    mesh = _mesh(shards)
    shift = make_halo_mixing_op(topo, mesh, dtype=jnp.float32)
    gather = _make_halo_gather_mixing_op(topo, mesh, dtype=jnp.float32)
    assert (shift.impl, gather.impl) == ("halo_shift", "halo_gather")
    x = jnp.asarray(rng.normal(size=(n, *stack)).astype(np.float32))
    want = np.asarray(jax.jit(getattr(gather, form))(x))
    got = np.asarray(jax.jit(getattr(shift, form))(x))
    assert got.shape == want.shape == (n, *stack) and got.dtype == np.float32
    assert_ulps_of_scale(got, want, 4)
    rounded = jax.jit(getattr(shift, form))(x.astype(jnp.bfloat16))
    assert rounded.dtype == jnp.bfloat16
    with pytest.raises(AssertionError):
        assert_ulps_of_scale(
            np.asarray(rounded.astype(jnp.float32)), want, 4)


def test_halo_mixing_rejects_directed():
    from distributed_optimization_tpu.parallel.collectives import (
        make_halo_mixing_op,
    )

    topo = build_topology("directed_ring", 16)
    with pytest.raises(ValueError, match="undirected"):
        make_halo_mixing_op(topo, _mesh(4))


# --------------------------------------------------------- backend parity


def test_e2e_ring_bitwise(problem):
    """Name kept; the two programs agree to ``MODEL_ULPS`` units of the
    models' scale, and the sharded run in the next precision down does
    not."""
    from distributed_optimization_tpu.backends import jax_backend

    r_u, r_s = run_pair(problem)
    assert_parity(r_u, r_s, models_ulps=MODEL_ULPS)
    ds, f_opt = problem
    r_32 = jax_backend.run(
        make_cfg(worker_mesh=4, dtype="float32"), ds, f_opt)
    with pytest.raises(AssertionError):
        assert_ulps_of_scale(r_32.final_models, r_u.final_models, MODEL_ULPS)


def test_e2e_erdos_renyi_bitwise(problem):
    r_u, r_s = run_pair(problem, **ER)
    assert_parity(r_u, r_s)


def test_e2e_gradient_tracking_bitwise(problem):
    r_u, r_s = run_pair(problem, algorithm="gradient_tracking")
    assert_parity(r_u, r_s, models_ulps=MODEL_ULPS)


# Node faults through the halo against the unsharded fault layer. On an
# Erdős–Rényi graph both address their neighbours through index tables and
# the models are bitwise; on the ring the unsharded layer reads its
# neighbours by shifts (ISSUE 33), another program of the same arithmetic,
# as the fault-free ring's stencil already is: ``MODEL_ULPS``.
FAULT_GRAPHS = {"ring": ({}, MODEL_ULPS), "erdos_renyi": (ER, None)}


@pytest.mark.parametrize("graph", sorted(FAULT_GRAPHS))
def test_e2e_churn_bitwise(problem, graph):
    """Crash-recovery churn composes through the halo: per-shard timeline
    slices realize the same masks as the unsharded path."""
    kw, ulps = FAULT_GRAPHS[graph]
    r_u, r_s = run_pair(problem, mttf=20.0, mttr=3.0, rejoin="frozen", **kw)
    assert_parity(r_u, r_s, models_ulps=ulps)


@pytest.mark.parametrize("graph", sorted(FAULT_GRAPHS))
def test_e2e_participation_bitwise(problem, graph):
    kw, ulps = FAULT_GRAPHS[graph]
    r_u, r_s = run_pair(problem, participation_rate=0.75, **kw)
    assert_parity(r_u, r_s, models_ulps=ulps)


@pytest.mark.parametrize("graph", sorted(FAULT_GRAPHS))
def test_e2e_stragglers_bitwise(problem, graph):
    kw, ulps = FAULT_GRAPHS[graph]
    r_u, r_s = run_pair(problem, straggler_prob=0.2, **kw)
    assert_parity(r_u, r_s, models_ulps=ulps)


@pytest.mark.parametrize("rule", ["trimmed_mean", "median", "clipped_gossip"])
def test_e2e_byzantine_ring_bitwise(problem, rule):
    """All three robust rules screen through the halo on the ring as they
    do on one device (corrupted boundary rows arrive over ppermute like
    benign traffic). The screened mix's other branch is the base mix,
    which on the mesh reads a ring by shifts (ISSUE 35) and on one device
    here through the neighbor table: two programs of one arithmetic
    (``MODEL_ULPS``), as the fault-free ring is."""
    r_u, r_s = run_pair(
        problem, attack="sign_flip", n_byzantine=1, aggregation=rule,
        robust_b=1, robust_impl="gather",
    )
    assert_parity(r_u, r_s, models_ulps=MODEL_ULPS)


def test_e2e_byzantine_trimmed_mean_er_within_convention(problem):
    """Wide-k trimmed mean is the ONE cell where XLA's reduce order differs
    across program shapes — pinned at the repo's ≤1e-12 f64 convention
    (same class as the fused-kernel and gather-vs-dense notes)."""
    r_u, r_s = run_pair(
        problem, attack="sign_flip", n_byzantine=2,
        aggregation="trimmed_mean", robust_b=2, robust_impl="gather", **ER,
    )
    assert_parity(r_u, r_s, models_bitwise=False)


@pytest.mark.parametrize("graph", sorted(FAULT_GRAPHS))
def test_e2e_byzantine_churn_composed_bitwise(problem, graph):
    kw, ulps = FAULT_GRAPHS[graph]
    r_u, r_s = run_pair(
        problem, attack="sign_flip", n_byzantine=1,
        aggregation="median", robust_b=1, robust_impl="gather",
        mttf=20.0, mttr=3.0, rejoin="frozen", **kw,
    )
    assert_parity(r_u, r_s, models_ulps=ulps)


def test_checkpoint_resume_bitwise_with_mesh(problem, tmp_path):
    """Kill-and-resume mid-run with the mesh active: the resumed tail is
    bitwise the uninterrupted sharded run (and both match unsharded)."""
    from distributed_optimization_tpu.backends import jax_backend
    from distributed_optimization_tpu.utils.checkpoint import (
        CheckpointOptions,
    )

    ds, f_opt = problem
    cfg = make_cfg(worker_mesh=4)
    full = jax_backend.run(cfg, ds, f_opt, return_state=True)
    ckdir = str(tmp_path / "ck")
    jax_backend.run(
        cfg.replace(n_iterations=20), ds, f_opt,
        checkpoint=CheckpointOptions(ckdir, every_evals=1),
    )
    resumed = jax_backend.run(
        cfg, ds, f_opt, checkpoint=CheckpointOptions(ckdir, every_evals=1),
        return_state=True,
    )
    np.testing.assert_array_equal(
        np.asarray(full.final_models), np.asarray(resumed.final_models)
    )
    np.testing.assert_array_equal(
        np.asarray(full.history.objective),
        np.asarray(resumed.history.objective),
    )


# ------------------------------------------------- composition validation


def test_worker_mesh_one_rejected():
    with pytest.raises(ValueError, match="worker_mesh must be 0"):
        make_cfg(worker_mesh=1)


@pytest.mark.parametrize("kw,needle", [
    (dict(n_workers=18, worker_mesh=4), "divide"),
    (dict(backend="numpy"), "backend='jax'"),
    (dict(topology="fully_connected"), "matrix-free"),
    (dict(topology_impl="dense"), "neighbor"),
    (dict(mixing_impl="dense"), "no sharded form"),
    (dict(execution="async", latency_model="exponential"), "async"),
    (dict(edge_drop_prob=0.1), "per-shard slicing"),
    (dict(attack="alie", n_byzantine=2, aggregation="median", robust_b=2),
     "sign_flip or large_noise"),
    (dict(mttf=20.0, mttr=3.0, rejoin="neighbor_restart"),
     "halo-averaged warm restart"),
    (dict(robust_impl="dense", attack="sign_flip", n_byzantine=1,
          aggregation="median", robust_b=1), "halo-gather"),
    (dict(algorithm="centralized"), "no peer graph"),
])
def test_unsupported_composition_rejected_naming_missing_piece(kw, needle):
    # Impls stay 'auto' so the worker_mesh composition block (not an
    # earlier explicit-impl validation) is what fires.
    base = {k: v for k, v in BASE.items()
            if k not in ("topology_impl", "mixing_impl")}
    base["worker_mesh"] = 2
    base.update(kw)
    with pytest.raises(ValueError, match=needle):
        ExperimentConfig(**base)


def test_neighbor_mixing_rejection_names_sharded_gather_path():
    """Satellite: the topology_impl='neighbor' × mixing_impl rejection now
    points at worker_mesh for the real-collectives route, not at dense."""
    with pytest.raises(ValueError, match="worker_mesh >= 2"):
        make_cfg(mixing_impl="dense", worker_mesh=0)


def test_batch_unsupported_reason_names_mesh():
    from distributed_optimization_tpu.backends.jax_backend import (
        batch_unsupported_reason,
    )

    reason = batch_unsupported_reason(make_cfg(worker_mesh=4))
    assert reason is not None and "worker_mesh" in reason


def test_resolved_topology_impl_is_neighbor_under_mesh():
    assert make_cfg(worker_mesh=4, topology_impl="auto"
                    ).resolved_topology_impl() == "neighbor"


def test_mesh_needs_enough_devices(problem):
    from distributed_optimization_tpu.backends import jax_backend

    ds, f_opt = problem
    with pytest.raises(ValueError, match="devices"):
        jax_backend.run(make_cfg(worker_mesh=16), ds, f_opt)


def test_cli_worker_mesh_flag():
    from distributed_optimization_tpu.cli import (
        build_parser, config_from_args,
    )

    args = build_parser().parse_args([
        "--n-workers", "16", "--worker-mesh", "4",
        "--topology-impl", "neighbor", "--mixing-impl", "gather",
    ])
    assert config_from_args(args).worker_mesh == 4


def test_auto_and_explicit_grid_mesh_agree(problem, monkeypatch):
    """Satellite: the auto mixing path applies the same grid-row
    divisibility rule as the explicit stencil, so both size the mesh off
    grid ROWS (6 for a 6×6 torus on 8 devices), not off N=36 (which
    would land on 4 — a count the row reshape cannot split)."""
    from distributed_optimization_tpu.backends import jax_backend
    from distributed_optimization_tpu.parallel import mesh as mesh_mod
    from distributed_optimization_tpu.utils.data import (
        generate_synthetic_dataset,
    )
    from distributed_optimization_tpu.utils.oracle import (
        compute_reference_optimum,
    )

    sizes = {}
    real = mesh_mod.make_worker_mesh

    def spy(n_workers, devices=None):
        sizes.setdefault("calls", []).append(n_workers)
        return real(n_workers, devices)

    monkeypatch.setattr(jax_backend, "make_worker_mesh", spy)
    cfg = ExperimentConfig(**{
        **{k: v for k, v in BASE.items()
           if k not in ("topology_impl", "mixing_impl", "n_workers")},
        "n_workers": 36, "topology": "grid", "n_iterations": 4,
        "eval_every": 4,
    })
    ds = generate_synthetic_dataset(cfg)
    _, f_opt = compute_reference_optimum(ds, cfg.reg_param)
    for impl in ("auto", "stencil"):
        jax_backend.run(cfg.replace(mixing_impl=impl), ds, f_opt)
    assert sizes["calls"] == [6, 6], sizes


# --------------------------------------------------------- ici accounting


def test_ici_summary_matches_plan():
    from distributed_optimization_tpu.telemetry import ici_summary

    assert ici_summary(make_cfg()) is None
    cfg = make_cfg(worker_mesh=4)
    ici = ici_summary(cfg)
    topo = build_topology("ring", N, impl="neighbor")
    plan = build_halo_plan(*neighbor_tables_for(topo), 4)
    itemsize = np.dtype(cfg.dtype).itemsize
    d_payload = cfg.n_features + 1  # bias column
    assert ici["worker_mesh"] == 4
    assert ici["halo_rows_max"] == plan.h_max
    assert ici["halo_rows_per_device"] == [len(h) for h in plan.halo_idx]
    # Wire pricing: every rotation pads to its max per-device count, so
    # each device ships the same wire_rows per round. On a ring the
    # blocks are contiguous (1 row each way), so wire == useful.
    wire = sum(st.send_idx.shape[1] for st in plan.steps)
    assert ici["wire_rows_per_device"] == wire
    assert ici["useful_rows_per_device"] == [int(r) for r in plan.sent_rows]
    assert wire == int(plan.sent_rows[0])  # ring: no pad rows
    assert ici["bytes_per_device_per_round"] == (
        [wire * d_payload * itemsize] * 4
    )
    assert ici["bytes_total_per_round"] == 4 * wire * d_payload * itemsize
    # Fault/robust side-channel floats are priced per config: node
    # processes add the availability bit + the realized-degree column;
    # robust screening the availability bit (+ degree for clipping).
    assert ici_summary(
        make_cfg(worker_mesh=4, straggler_prob=0.2)
    )["payload_floats_per_row"] == d_payload + 2
    byz = dict(attack="sign_flip", n_byzantine=1, robust_b=1,
               robust_impl="gather", worker_mesh=4)
    assert ici_summary(
        make_cfg(aggregation="median", **byz)
    )["payload_floats_per_row"] == d_payload + 1
    assert ici_summary(
        make_cfg(aggregation="clipped_gossip", **byz)
    )["payload_floats_per_row"] == d_payload + 2
    # The availability bit ships as its own f32 exchange (4 B/row even in
    # f64 runs — fault masks are explicit float32); the degree column
    # rides the model buffer at the accumulation itemsize (== state
    # itemsize for f32/f64).
    faulty = ici_summary(make_cfg(worker_mesh=4, straggler_prob=0.2))
    assert faulty["bytes_per_device_per_round_max"] == wire * (
        (d_payload + 1) * itemsize + 4
    )
    # bfloat16 states still exchange fault/robust buffers in the promoted
    # f32 accumulation dtype (4 B floats); the plain mixing op ships the
    # state dtype itself (2 B).
    bf = dict(worker_mesh=4, dtype="bfloat16")
    assert ici_summary(make_cfg(straggler_prob=0.2, **bf))[
        "bytes_per_device_per_round_max"
    ] == wire * (4 + (d_payload + 1) * 4)
    assert ici_summary(make_cfg(**bf))[
        "bytes_per_device_per_round_max"
    ] == wire * d_payload * 2
    # An adversary executes BOTH branches of the screened mix's
    # jnp.where: attack + defense prices base + robust exchange forms;
    # attack without a defense prices the base form twice.
    med = ici_summary(make_cfg(aggregation="median", **byz))
    assert med["bytes_per_device_per_round_max"] == wire * (
        d_payload * itemsize + (4 + d_payload * itemsize)
    )
    undefended = ici_summary(
        make_cfg(worker_mesh=4, attack="sign_flip", n_byzantine=1)
    )
    assert undefended["bytes_per_device_per_round_max"] == (
        wire * 2 * d_payload * itemsize
    )
    # The payload width follows the DATASET's realized column count when
    # the caller provides it (the digits dataset ignores n_features:
    # 64 pixels + bias = 65 trained columns) — Simulator/backend thread
    # ``d_features`` through so ICI bytes never follow a config guess.
    digits = ici_summary(make_cfg(worker_mesh=4), d_features=65)
    assert digits["payload_floats_per_row"] == 65


def test_ici_summary_er_prices_padded_wire_rows():
    """Irregular graphs: per-device wire bytes are uniform (the padded
    collective) and never undercount any device's useful rows."""
    from distributed_optimization_tpu.telemetry import ici_summary

    cfg = make_cfg(worker_mesh=4, **ER)
    ici = ici_summary(cfg)
    wire = ici["wire_rows_per_device"]
    useful = ici["useful_rows_per_device"]
    assert wire >= max(useful)
    assert len(set(ici["bytes_per_device_per_round"])) == 1
    row_bytes = (cfg.n_features + 1) * np.dtype(cfg.dtype).itemsize
    assert ici["bytes_per_device_per_round_max"] == wire * row_bytes
    # Dense-P2 ragged check via the plan itself: the padded width of
    # every rotation is the max of that rotation's realized counts.
    topo = build_topology(
        "erdos_renyi", N, erdos_renyi_p=ER["erdos_renyi_p"],
        seed=ER["topology_seed"], impl="neighbor",
    )
    plan = build_halo_plan(*neighbor_tables_for(topo), 4)
    for st in plan.steps:
        assert st.send_idx.shape[1] == int(st.counts.max())


def test_report_and_metrics_carry_ici_line(problem):
    """The run report prints the bytes-over-ICI line next to the analytic
    floats, and the PR-10 registry exports the per-device gauges."""
    from distributed_optimization_tpu.backends import jax_backend
    from distributed_optimization_tpu.metrics import summarize_run
    from distributed_optimization_tpu.observability.metrics_registry import (
        metrics_registry,
    )
    from distributed_optimization_tpu.reporting import format_report
    from distributed_optimization_tpu.simulator import ExperimentRecord
    from distributed_optimization_tpu.telemetry import health_summary

    ds, f_opt = problem
    cfg = make_cfg(worker_mesh=4)
    r = jax_backend.run(cfg, ds, f_opt)
    health = health_summary(cfg, r.history)
    assert "ici" in health["comms"]
    rec = ExperimentRecord(
        label="mesh", config=cfg, result=r,
        summary=summarize_run("mesh", r.history, 1.0, cfg.n_workers),
        health=health,
    )
    text = format_report([rec], cfg, f_opt)
    assert "ICI" in text and "B/dev/round" in text
    rendered = metrics_registry().render()
    assert "dopt_worker_mesh_ici_bytes_per_round" in rendered
    assert 'device="3"' in rendered
    # A later, smaller mesh replaces the per-device series wholesale —
    # devices 2/3 must not keep exporting the P=4 run's bytes.
    r2 = jax_backend.run(make_cfg(worker_mesh=2), ds, f_opt)
    assert r2 is not None
    rendered = metrics_registry().render()
    ici_lines = [
        ln for ln in rendered.splitlines()
        if ln.startswith("dopt_worker_mesh_ici_bytes_per_round{")
    ]
    assert len(ici_lines) == 2
    assert not any('device="3"' in ln for ln in ici_lines)
