"""Degree-bounded gather robust aggregation (docs/BYZANTINE.md §gather).

The gather form (``make_gather_robust_aggregator`` + the static neighbor
table + per-incident-edge liveness bits) must be an EXECUTION change only:
same screened aggregate as the dense [N, N, d] form and the per-node numpy
oracle at f64 parity ≤ 1e-12, under arbitrary realized graphs, composed
fault processes (bursty links + crash-recovery churn + Byzantine
injection), checkpoint/resume, and the faulted-down identity-row
degradation at the k_max boundary. Plus the routing contract: the 'auto'
gate picks gather exactly when the measured crossover says it wins
(k_max + 1 < N, i.e. everywhere but fully connected) and the knob is
rejected where it would be silently ignored.
"""

import jax.numpy as jnp
from jax import enable_x64
import numpy as np
import pytest
from conftest import assert_ulps_of_scale

from distributed_optimization_tpu.backends import jax_backend, numpy_backend
from distributed_optimization_tpu.config import ExperimentConfig
from distributed_optimization_tpu.ops.robust_aggregation import (
    make_gather_robust_aggregator,
    make_robust_aggregator,
    robust_aggregate_np,
)
from distributed_optimization_tpu.parallel import build_topology
from distributed_optimization_tpu.parallel.faults import make_faulty_mixing
from distributed_optimization_tpu.parallel.topology import (
    incident_edge_slots,
    neighbor_table,
)

RULES = ("trimmed_mean", "median", "clipped_gossip")


def _gather_live(A, nbr_idx, nbr_mask):
    """Host-side reference liveness: the realized adjacency gathered per
    neighbor slot (what ``FaultyMixing.make_neighbor_liveness`` produces
    on-device)."""
    return np.take_along_axis(np.asarray(A), nbr_idx, axis=1) * nbr_mask


# ------------------------------------------------------------- table builder

def test_neighbor_table_shape_order_and_padding():
    topo = build_topology("erdos_renyi", 12, erdos_renyi_p=0.5, seed=7)
    nbr_idx, nbr_mask = neighbor_table(topo.adjacency)
    k_max = int(topo.degrees.max())
    assert nbr_idx.shape == nbr_mask.shape == (12, k_max)
    for i in range(12):
        nbrs = np.nonzero(topo.adjacency[i])[0]
        # Ascending neighbor order (dense axis-1 visit order), self-padded.
        np.testing.assert_array_equal(nbr_idx[i, : len(nbrs)], nbrs)
        assert np.all(nbr_idx[i, len(nbrs):] == i)
        assert nbr_mask[i].sum() == len(nbrs)


def test_neighbor_table_rejects_directed():
    topo = build_topology("directed_ring", 8)
    with pytest.raises(ValueError, match="undirected"):
        neighbor_table(topo.adjacency)


def test_incident_edge_slots_are_symmetric():
    """Edge {i, j}'s timeline bit must land in BOTH endpoints' rows — the
    gather twin of the dense A[ei, ej] = A[ej, ei] scatter."""
    from distributed_optimization_tpu.parallel.faults import _edge_list

    topo = build_topology("grid", 16)
    nbr_idx, nbr_mask = neighbor_table(topo.adjacency)
    edges = _edge_list(topo)
    slots = incident_edge_slots(nbr_idx, nbr_mask, edges)
    for e, (i, j) in enumerate(edges):
        si = np.nonzero(nbr_idx[i] == j)[0][0]
        sj = np.nonzero(nbr_idx[j] == i)[0][0]
        assert slots[i, si] == e and slots[j, sj] == e


# ----------------------------------------------- unit parity (f64 <= 1e-12)

def _faulted_instance(topo_name, n):
    """An irregular fault-realized graph with wild (attack-like) rows: the
    realized adjacency, the stack, the static table and its liveness."""
    topo = build_topology(topo_name, n, erdos_renyi_p=0.5, seed=3)
    rng = np.random.default_rng(11)
    A = np.array(topo.adjacency, copy=True)
    ei, ej = np.nonzero(np.triu(A, 1))
    drop = rng.random(len(ei)) < 0.3
    A[ei[drop], ej[drop]] = A[ej[drop], ei[drop]] = 0.0
    x = rng.standard_normal((n, 7))
    x[[1, 5]] *= 1e4  # wild rows the screening must contain
    nbr_idx, nbr_mask = neighbor_table(topo.adjacency)
    return A, x, nbr_idx, _gather_live(A, nbr_idx, nbr_mask)



@pytest.mark.parametrize("rule", RULES)
@pytest.mark.parametrize(
    "topo_name,n", [("ring", 16), ("erdos_renyi", 14), ("grid", 16)]
)
def test_gather_matches_dense_and_oracle_f64(rule, topo_name, n):
    """The acceptance parity: gather vs dense vs the per-node numpy oracle
    at ≤ 1e-12 in float64, over an irregular fault-realized graph with
    wild (attack-like) rows."""
    A, x, nbr_idx, live = _faulted_instance(topo_name, n)
    with enable_x64():
        dense = make_robust_aggregator(rule, budget=1)
        gather = make_gather_robust_aggregator(rule, 1, nbr_idx)
        d_out = np.asarray(
            dense(jnp.asarray(A, jnp.float64), jnp.asarray(x, jnp.float64))
        )
        g_out = np.asarray(
            gather(
                jnp.asarray(live, jnp.float64), jnp.asarray(x, jnp.float64)
            )
        )
    o_out = robust_aggregate_np(rule, A, x, budget=1)
    # ≤ 1e-12 in BOTH senses (the wild rows sit at 1e4, where a pure atol
    # would demand better-than-ulp agreement).
    np.testing.assert_allclose(g_out, d_out, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(g_out, o_out, rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("rule", ("trimmed_mean", "median"))
def test_gather_f32_matches_dense_f32(rule):
    """Both forms keep float32 inputs in float32 and pick the same sorted
    values, so the count rules agree to float32's own units of the stack's
    scale (the kept values are summed over axes of different length);
    bfloat16 arithmetic is far outside."""
    A, x, nbr_idx, live = _faulted_instance("erdos_renyi", 14)
    dense = make_robust_aggregator(rule, budget=1)
    gather = make_gather_robust_aggregator(rule, 1, nbr_idx)
    xv = jnp.asarray(x, jnp.float32)
    want = dense(jnp.asarray(A, jnp.float32), xv)
    got = gather(jnp.asarray(live, jnp.float32), xv)
    assert got.dtype == want.dtype == jnp.float32
    assert_ulps_of_scale(got, want, 4)
    rounded = gather(
        jnp.asarray(live, jnp.bfloat16), xv.astype(jnp.bfloat16)
    ).astype(jnp.float32)
    with pytest.raises(AssertionError):
        assert_ulps_of_scale(rounded, want, 4)


def test_gather_fixed_clip_tau_matches_dense():
    topo = build_topology("erdos_renyi", 12, erdos_renyi_p=0.6, seed=9)
    rng = np.random.default_rng(4)
    x = rng.standard_normal((12, 5))
    nbr_idx, nbr_mask = neighbor_table(topo.adjacency)
    live = _gather_live(topo.adjacency, nbr_idx, nbr_mask)
    with enable_x64():
        dense = make_robust_aggregator("clipped_gossip", 1, clip_tau=0.7)
        gather = make_gather_robust_aggregator(
            "clipped_gossip", 1, nbr_idx, clip_tau=0.7
        )
        d_out = np.asarray(
            dense(
                jnp.asarray(topo.adjacency, jnp.float64),
                jnp.asarray(x, jnp.float64),
            )
        )
        g_out = np.asarray(
            gather(
                jnp.asarray(live, jnp.float64), jnp.asarray(x, jnp.float64)
            )
        )
    np.testing.assert_allclose(g_out, d_out, rtol=0, atol=1e-12)
    o_out = robust_aggregate_np(
        "clipped_gossip", np.asarray(topo.adjacency), x, 1, clip_tau=0.7
    )
    np.testing.assert_allclose(g_out, o_out, rtol=0, atol=1e-12)


# ------------------------------------ liveness == realized adjacency, per t

@pytest.mark.parametrize(
    "fault_kw",
    [
        dict(drop_prob=0.3),
        dict(drop_prob=0.0, straggler_prob=0.25),
        dict(drop_prob=0.3, straggler_prob=0.2),
        dict(drop_prob=0.3, burst_len=4.0, horizon=12),
        dict(drop_prob=0.25, burst_len=3.0, mttf=4.0, mttr=3.0, horizon=12),
    ],
    ids=["iid_edges", "stragglers", "edges+stragglers", "bursty", "composed"],
)
def test_neighbor_liveness_is_gathered_realized_adjacency(fault_kw):
    """The gather-form fault realization consumes the SAME draws/chains as
    the dense one: live(t) must equal realized_adjacency(t) gathered per
    slot, bit for bit, at every iteration — memoryless and timeline paths."""
    topo = build_topology("erdos_renyi", 10, erdos_renyi_p=0.5, seed=2)
    faulty = make_faulty_mixing(topo, seed=5, **fault_kw)
    nbr_idx, nbr_mask = neighbor_table(topo.adjacency)
    live_fn = faulty.make_neighbor_liveness(nbr_idx, nbr_mask)
    for t in range(fault_kw.get("horizon", 8)):
        A_t = np.asarray(faulty.realized_adjacency(jnp.asarray(t)))
        want = _gather_live(A_t, nbr_idx, nbr_mask)
        got = np.asarray(live_fn(jnp.asarray(t)))
        np.testing.assert_array_equal(got, want)


# --------------------------------- identity-row degradation at the boundary

@pytest.mark.parametrize("rule", RULES)
def test_faulted_down_neighborhood_degrades_to_identity_row(rule):
    """When faults shrink a realized closed neighborhood to ≤ 2b (or
    deg ≤ b for adaptive clipping), that node keeps its own model — the
    FaultyMixing isolated-node convention — in the gather form, the dense
    form, and the oracle alike; full-degree rows still screen normally."""
    topo = build_topology("ring", 10)  # k_max = 2, budget 1
    rng = np.random.default_rng(8)
    x = rng.standard_normal((10, 4))
    A = np.array(topo.adjacency, copy=True)
    A[0, :] = A[:, 0] = 0.0           # node 0 fully isolated
    A[3, 4] = A[4, 3] = 0.0           # nodes 3/4 at degree 1 (= b)
    nbr_idx, nbr_mask = neighbor_table(topo.adjacency)
    live = _gather_live(A, nbr_idx, nbr_mask)
    with enable_x64():
        gather = make_gather_robust_aggregator(rule, 1, nbr_idx)
        g_out = np.asarray(
            gather(
                jnp.asarray(live, jnp.float64), jnp.asarray(x, jnp.float64)
            )
        )
        dense = make_robust_aggregator(rule, budget=1)
        d_out = np.asarray(
            dense(jnp.asarray(A, jnp.float64), jnp.asarray(x, jnp.float64))
        )
    o_out = robust_aggregate_np(rule, A, x, budget=1)
    # Isolated node: identity row in every implementation.
    for out in (g_out, d_out, o_out):
        np.testing.assert_array_equal(out[0], x[0])
    if rule == "trimmed_mean":
        # degree 1 ⇒ closed count 2 ≤ 2b: identity row too.
        for out in (g_out, d_out, o_out):
            np.testing.assert_array_equal(out[3], x[3])
    if rule == "clipped_gossip":
        # degree 1 = b ⇒ adaptive τ = 0: the node does not move.
        for out in (g_out, d_out, o_out):
            np.testing.assert_allclose(out[3], x[3], rtol=0, atol=1e-15)
    # A full-degree node still screens (not frozen by the degradation).
    np.testing.assert_allclose(g_out, d_out, rtol=0, atol=1e-12)
    np.testing.assert_allclose(g_out, o_out, rtol=0, atol=1e-12)


# ------------------------ slot planes and the compare-exchange network (PR 44)

from distributed_optimization_tpu.ops import robust_aggregation as ra  # noqa: E402

THRESHOLD = ra.NETWORK_MAX_SLOTS
FLOATS = {"f32": jnp.float32, "f64": jnp.float64}


def _hostile_instance(slots, dtype, n=48, d=7, seed=5):
    """A table of ``slots - 1`` neighbours a worker (a circulant graph's, an
    odd width's last column the antipode), 30% of the slots dead, three rows
    with every slot dead, and a stack that holds what an attack and a fault
    put there: payloads at -5·x, ONE row of NaN, zeros of both signs, ties."""
    k = slots - 1
    rng = np.random.default_rng([seed, slots])
    i = np.arange(n)[:, None]
    cols = [(i + o) % n for o in range(1, k // 2 + 1)]
    cols += [(i - o) % n for o in range(1, k // 2 + 1)]
    if k % 2:
        cols.append((i + n // 2) % n)
    nbr = np.sort(np.concatenate(cols, axis=1), axis=1).astype(np.int32)
    live = (rng.random((n, k)) >= 0.3).astype(np.float64)
    live[[2, 11, 30]] = 0.0                       # count 1: identity rows
    live[7] = 1.0                                 # the NaN row screens in full
    x = rng.standard_normal((n, d))
    x[[4, 19, 33]] *= -5.0                        # sign-flip payloads
    x[7] = np.nan                                 # one NaN payload
    x[:, 0] = np.where(rng.random(n) < 0.5, 0.0, -0.0)  # tied zeros, both signs
    x[:, 1] = np.round(x[:, 1])                   # ties among small integers
    return nbr, jnp.asarray(live, dtype), jnp.asarray(x, dtype)


def _sorted_reference(rule, budget, nbr, live, x):
    """The count rules as they were written before PR 44: the closed
    neighbourhood stacked [N, k_max + 1, d], ``jnp.sort`` along the slot
    axis, a masked ``jnp.sum`` / ``take_along_axis``."""
    vals = jnp.where(live[:, :, None] > 0, x[nbr], jnp.inf)
    s = jnp.sort(jnp.concatenate([x[:, None, :], vals], axis=1), axis=1)
    counts = jnp.sum(live, axis=1) + 1.0
    if rule == "median":
        c = counts.astype(jnp.int32)
        lo = jnp.maximum((c - 1) // 2, 0)[:, None, None]
        hi = jnp.maximum(c // 2, 0)[:, None, None]
        return 0.5 * (jnp.take_along_axis(s, lo, axis=1)
                      + jnp.take_along_axis(s, hi, axis=1))[:, 0, :], counts
    pos = jnp.arange(nbr.shape[1] + 1, dtype=x.dtype)
    keep = (pos[None, :] >= budget) & (pos[None, :] < (counts - budget)[:, None])
    kept = jnp.maximum(counts - 2 * budget, 0.0)
    total = jnp.sum(jnp.where(keep[:, :, None], s, 0.0), axis=1)
    mean = total / jnp.maximum(kept, 1.0)[:, None]
    return jnp.where((kept >= 1.0)[:, None], mean, x), counts


# every (width, rule, budget) the table can support: 2b <= k_max
HOSTILE_CASES = [
    (slots, rule, budget)
    for slots in (3, 5, THRESHOLD, THRESHOLD + 1)
    for rule, budget in (("trimmed_mean", 1), ("trimmed_mean", 2), ("median", 1))
    if 2 * budget <= slots - 1
]


@pytest.mark.parametrize("floats", sorted(FLOATS))
@pytest.mark.parametrize("slots,rule,budget", HOSTILE_CASES)
def test_the_network_is_the_sort_on_hostile_stacks(slots, rule, budget, floats, monkeypatch):
    """Planes and a compare-exchange network against ``jnp.sort`` and a
    masked sum, on dead slots, faulted-down rows, a NaN payload, ±0 ties and
    payloads at -5·x: the VALUES kept are the sort's, so the aggregate is
    the sort's wherever one plane is kept (a row of the trimmed mean with
    c - 2b = 1; the median everywhere: 0.5·(a + b) is one arithmetic) and
    within the rounding of another order of additions elsewhere: 1e-12 of
    the stack's scale in float64, 2·slots units in the last place of it in
    float32. Both orderings are run at every width (the constant patched),
    and the module's own choice is one of them."""
    with enable_x64(floats == "f64"):
        nbr, live, x = _hostile_instance(slots, FLOATS[floats])
        want, counts = _sorted_reference(rule, budget, nbr, live, x)
        want, counts = np.asarray(want), np.asarray(counts)
        got = {}
        for path, widest in (("network", 10**6), ("sort", 0), ("own", THRESHOLD)):
            monkeypatch.setattr(ra, "NETWORK_MAX_SLOTS", widest)
            out = ra.make_gather_robust_aggregator(rule, budget, nbr)(live, x)
            assert out.dtype == FLOATS[floats]
            got[path] = np.asarray(out)
    np.testing.assert_array_equal(got["sort"], want)
    np.testing.assert_array_equal(
        got["own"], got["network" if slots <= THRESHOLD else "sort"])
    one_kept = (
        np.ones(len(counts), bool) if rule == "median"
        else counts - 2 * budget <= 1  # one plane, or the identity row
    )
    assert one_kept.any()
    np.testing.assert_array_equal(got["network"][one_kept], want[one_kept])
    scale = float(np.nanmax(np.abs(np.where(np.isfinite(want), want, np.nan))))
    atol = (1e-12 if floats == "f64" else 2 * slots * float(np.finfo(np.float32).eps)) * scale
    np.testing.assert_allclose(got["network"], want, rtol=0, atol=atol)
    # the NaN payload is trimmed exactly as the sort trims it: under the
    # trimmed mean its row's neighbours hold numbers (under the median a
    # neighbour with a dead slot beside it reads +inf, as the sort's does);
    # an identity row keeps its own
    if rule == "trimmed_mean":
        assert np.isfinite(got["network"][[6, 8]]).all()
    np.testing.assert_array_equal(got["network"][[2, 11, 30]], np.asarray(x)[[2, 11, 30]])


@pytest.mark.parametrize("floats", sorted(FLOATS))
@pytest.mark.parametrize("slots", [2, 3, 5, THRESHOLD])
def test_the_network_orders_planes_as_the_stable_sort_does(slots, floats):
    """Plane j is BIT FOR BIT position j of ``jnp.sort`` along the slot axis:
    +inf after every number, a NaN (of either sign) after +inf, and tied
    values, -0 and +0 among them, in slot order, because the comparators sit
    between adjacent planes and leave a tie alone."""
    rng = np.random.default_rng([9, slots])
    with enable_x64(floats == "f64"):
        stack = rng.standard_normal((slots, 64, 5))
        stack[:, :, 0] = np.round(stack[:, :, 0])  # ties
        special = np.array([0.0, -0.0, np.inf, -np.inf, np.nan, -np.nan, 1.0, -5.0])
        stack[:, :, 1:3] = rng.choice(special, size=(slots, 64, 2))
        stack = jnp.asarray(stack, FLOATS[floats])
        planes = ra._ordered_planes([stack[j] for j in range(slots)])
        got = np.stack([np.asarray(p) for p in planes])
        want = np.asarray(jnp.sort(stack, axis=0))
    bits = np.uint64 if floats == "f64" else np.uint32
    np.testing.assert_array_equal(got.view(bits), want.view(bits))
    assert np.isnan(want).any() and np.signbit(want[want == 0]).any()


def test_one_kept_plane_keeps_its_own_zero():
    """Where ONE plane is kept the aggregate is that plane to the bit, the
    sign of a zero included: the median of (-0, -1, 1) is -0. (The masked
    ``jnp.sum`` of the sort path adds it to a +0 and returns +0: equal as
    numbers, the one bit the two orderings may differ in.)"""
    nbr = np.array([[1, 2], [0, 2], [0, 1]], dtype=np.int32)
    x = jnp.asarray([[-0.0], [-1.0], [1.0]], jnp.float32)
    live = jnp.ones((3, 2), jnp.float32)
    for rule in ("trimmed_mean", "median"):
        out = np.asarray(ra.make_gather_robust_aggregator(rule, 1, nbr)(live, x))
        np.testing.assert_array_equal(out, np.zeros((3, 1), np.float32))
        assert np.signbit(out).all(), out


@pytest.mark.parametrize("slots,order", [(THRESHOLD, "network"), (THRESHOLD + 1, "sort")])
def test_the_tables_width_alone_decides_the_ordering(slots, order):
    """No option, no name: ``k_max + 1`` up to ``NETWORK_MAX_SLOTS`` traces
    no sort, one slot more traces one; the root's ``screen_order`` is the
    same rule read aloud."""
    import jax

    nbr, live, x = _hostile_instance(slots, jnp.float32)
    for rule in ("trimmed_mean", "median"):
        traced = str(jax.make_jaxpr(ra.make_gather_robust_aggregator(rule, 1, nbr))(live, x))
        assert ("sort[" in traced) == (order == "sort"), traced[:400]
        assert ("concatenate" in traced) == (order == "sort")
        assert ra.screen_order(rule, "gather", 48, slots - 1) == f"{order}:{slots}"
        assert ra.screen_order(rule, "halo_gather", 48, slots - 1) == f"{order}:{slots}"
        assert ra.screen_order(rule, "dense", 48, slots - 1) == "sort:48"
    assert ra.screen_order("clipped_gossip", "gather", 48, slots - 1) == "none"


# ------------------------------------------- a ring's rows come by shifts

def _end_row_stack(n, shape, seed):
    """Numbers with ties of -0 and +0, ±inf and NaN payloads of either sign
    strewn over two columns, and on BOTH slots of the end rows (rows 0 and
    N - 1 receive rows 1, N - 1 and 0, N - 2) one of each, so that a plane
    holding the wrong slot's row there shows in the bits."""
    rng = np.random.default_rng([45, n, seed])
    x = rng.standard_normal((n,) + shape)
    flat = x.reshape(n, -1)  # a view
    flat[:, 0] = np.round(flat[:, 0])
    special = np.array([0.0, -0.0, np.inf, -np.inf, np.nan, -np.nan, 1.0, -5.0])
    flat[:, 1:3] = rng.choice(special, size=(n, 2))
    # row 0 receives rows 1 (slot 0) and N - 1 (slot 1), row N - 1 rows 0
    # (slot 0) and N - 2 (slot 1): a tie of -0 then +0, and of NaN then
    # -NaN, above the row's own value leaves the network in slot order, so
    # the middle plane is the FIRST slot's, and another's under a swap
    for first, second, own, col in ((1, n - 1, 0, 3), (0, n - 2, n - 1, 6)):
        flat[[first, second, own], col] = [-0.0, 0.0, -1.0]
        flat[[first, second, own], col + 1] = [np.nan, -np.nan, 0.0]
        flat[[first, second], col + 2] = [np.inf, -np.inf]
    return jnp.asarray(x, jnp.float32)


def _liveness(n, drawn, seed):
    if not drawn:
        return jnp.ones((n, 2), jnp.float32)
    live = (np.random.default_rng([46, n, seed]).random((n, 2)) > 0.3).astype(np.float32)
    live[0], live[n - 1] = (0.0, 1.0), (1.0, 0.0)  # a dead slot on each end row, not the same one
    return jnp.asarray(live)


def _through_a_traced_table(rule, budget, nbr):
    """The gather form: the same ``closed_neighbourhood_rule`` handed the
    table as a traced array, which no predicate can read."""
    import jax

    count_rule = ra.closed_neighbourhood_rule(rule, budget)

    @jax.jit
    def aggregate(table, live, x):
        rows = x.reshape(x.shape[0], -1)
        return count_rule(rows, rows, table, live).reshape(x.shape)

    return lambda live, x: aggregate(jnp.asarray(nbr), live, x)


SHIFT_CASES = [
    pytest.param(rule, n, drawn, shape, id=f"{rule}-ring{n}-{'drawn' if drawn else 'live'}-{'NdK' if len(shape) == 2 else 'Nd'}")
    for rule in ("trimmed_mean", "median") for n in (3, 4, 64)
    for drawn in (False, True) for shape in ((9,), (5, 3))
] + [
    pytest.param(rule, table, True, (9,), id=f"{rule}-{table}")
    for rule, table in (("trimmed_mean", "ring_descending"), ("median", "chain"), ("trimmed_mean", "torus"))
]


@pytest.mark.parametrize("rule,table,drawn,shape", SHIFT_CASES)
def test_a_rings_rows_come_by_shifts_bitwise_the_gather(rule, table, drawn, shape):
    """ISSUE 45: handed a ring's table as a HOST array the count rules read
    the two received planes by two shifts of the transmitted stack (no
    gather traced), and the aggregate is BIT FOR BIT the gather form's: the
    slot order is the table's on the end rows too (rows 0 and N - 1 list
    i + 1 first), so a slot's liveness bit masks that slot's row and ties
    leave the network in slot order. A ring listed descending, a chain and a
    torus keep the gather and their values."""
    import jax

    by_shifts = isinstance(table, int)
    if by_shifts:
        nbr, mask = neighbor_table(build_topology("ring", table).adjacency)
    elif table == "ring_descending":
        nbr, mask = neighbor_table(build_topology("ring", 16).adjacency)
        nbr = nbr[:, ::-1].copy()
    else:
        nbr, mask = neighbor_table(build_topology({"torus": "grid"}.get(table, table), 16).adjacency)
    n, k_max = nbr.shape
    x = _end_row_stack(n, shape, seed=len(rule))
    if k_max == 2:
        live = _liveness(n, drawn, seed=len(rule))
    else:
        live = jnp.asarray(np.random.default_rng(47).random((n, k_max)) > 0.3, jnp.float32)
    live = live * jnp.asarray(mask, jnp.float32)  # a chain's end rows hold one neighbour
    own = ra.make_gather_robust_aggregator(rule, 1, nbr)
    got = np.asarray(own(live, x))
    want = np.asarray(_through_a_traced_table(rule, 1, nbr)(live, x))
    assert got.shape == x.shape and got.dtype == np.float32
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
    if by_shifts and not drawn:
        # the end rows' ties came out in slot order: the first slot's -0 and NaN
        ends = want.reshape(n, -1)[[0, n - 1], [3, 6]], want.reshape(n, -1)[[0, n - 1], [4, 7]]
        assert (ends[0] == 0).all() and np.signbit(ends[0]).all(), ends
        assert np.isnan(ends[1]).all() and not np.signbit(ends[1]).any(), ends
    traced = str(jax.make_jaxpr(own)(live, x))
    assert ("gather" in traced) == (not by_shifts), traced[:300]
    assert ra.screen_fetch(rule, "gather", nbr) == ("shift" if by_shifts else "gather")
    assert ra.screen_fetch(rule, "halo_gather", nbr) == "gather"
    assert ra.screen_fetch("clipped_gossip", "gather", nbr) == "gather"


# --------------------------------------------- end-to-end impl equivalence

E2E_CFG = ExperimentConfig(
    n_workers=12, n_samples=360, n_features=8, n_informative_features=5,
    n_iterations=80, local_batch_size=8, problem_type="quadratic",
    algorithm="dsgd", topology="erdos_renyi", erdos_renyi_p=0.6,
    eval_every=20, dtype="float64", partition="shuffled",
    attack="sign_flip", n_byzantine=2, attack_scale=2.0,
    aggregation="trimmed_mean", robust_b=1,
)


@pytest.fixture(scope="module")
def e2e_data():
    from distributed_optimization_tpu.utils.data import (
        generate_synthetic_dataset,
    )
    from distributed_optimization_tpu.utils.oracle import (
        compute_reference_optimum,
    )

    ds = generate_synthetic_dataset(E2E_CFG)
    _, f_opt = compute_reference_optimum(ds, E2E_CFG.reg_param)
    return ds, f_opt


@pytest.mark.parametrize("rule", RULES)
def test_e2e_gather_matches_dense_under_composed_faults(e2e_data, rule):
    """The full composition — bursty links + crash-recovery churn +
    Byzantine sign-flip — through real backend runs: robust_impl is an
    execution knob, so gather and dense must produce the same f64
    trajectory (≤ 1e-12), and both must track the numpy oracle."""
    ds, f_opt = e2e_data
    cfg = E2E_CFG.replace(
        aggregation=rule, edge_drop_prob=0.2, burst_len=3.0,
        mttf=8.0, mttr=3.0,
    )
    from conftest import batch_schedule

    sched = batch_schedule(ds, cfg.n_iterations, cfg.local_batch_size)
    rd = jax_backend.run(
        cfg.replace(robust_impl="dense"), ds, f_opt, batch_schedule=sched
    )
    rg = jax_backend.run(
        cfg.replace(robust_impl="gather"), ds, f_opt, batch_schedule=sched
    )
    np.testing.assert_allclose(
        rg.final_models, rd.final_models, rtol=0, atol=1e-12
    )
    np.testing.assert_allclose(
        rg.history.objective, rd.history.objective, rtol=1e-12
    )
    rn = numpy_backend.run(cfg, ds, f_opt, batch_schedule=sched)
    np.testing.assert_allclose(
        rg.final_models, rn.final_models, rtol=1e-9, atol=1e-10
    )


def test_e2e_auto_routes_like_explicit_on_sparse_graph(e2e_data):
    """On a ring (k_max=2 ≪ N) 'auto' must take the gather path — same
    compiled trajectory as forcing it."""
    ds, f_opt = e2e_data
    cfg = E2E_CFG.replace(topology="ring")
    ra = jax_backend.run(cfg, ds, f_opt)
    rg = jax_backend.run(cfg.replace(robust_impl="gather"), ds, f_opt)
    np.testing.assert_array_equal(ra.final_models, rg.final_models)


def test_auto_stays_gather_under_faults_and_telemetry(e2e_data):
    """Time-varying graphs and an active telemetry activity probe run the
    measured gather routing, like every other 'auto' configuration."""
    ds, f_opt = e2e_data
    faulty = E2E_CFG.replace(edge_drop_prob=0.2)
    ra = jax_backend.run(faulty, ds, f_opt, use_mesh=False)
    rg = jax_backend.run(
        faulty.replace(robust_impl="gather"), ds, f_opt, use_mesh=False
    )
    np.testing.assert_array_equal(ra.final_models, rg.final_models)
    tele = E2E_CFG.replace(telemetry=True)
    rt = jax_backend.run(tele, ds, f_opt, use_mesh=False)
    rtg = jax_backend.run(
        tele.replace(robust_impl="gather"), ds, f_opt, use_mesh=False
    )
    np.testing.assert_array_equal(rt.final_models, rtg.final_models)


def test_e2e_gradient_tracking_gather_matches_dense(e2e_data):
    """A second step rule through the screened mix (the tracker's two
    gossip rounds an iteration both screen), with the flight recorder's
    activity probe on: gather and dense are one f64 trajectory and one
    screened fraction."""
    ds, f_opt = e2e_data
    cfg = E2E_CFG.replace(algorithm="gradient_tracking", telemetry=True)
    rd, rg = (
        jax_backend.run(cfg.replace(robust_impl=impl), ds, f_opt, use_mesh=False)
        for impl in ("dense", "gather")
    )
    np.testing.assert_allclose(
        rg.final_models, rd.final_models, rtol=1e-12, atol=1e-12
    )
    np.testing.assert_allclose(
        rg.history.trace["clip_frac"], rd.history.trace["clip_frac"],
        rtol=1e-12, atol=1e-12,
    )
    assert np.max(rg.history.trace["clip_frac"]) > 0.0


def test_gather_resume_exactness(e2e_data, tmp_path):
    """Killed-and-resumed gather run == uninterrupted run: the neighbor
    table is static and the liveness derives from (seed, t), so resume
    rebuilds the identical screened trajectory."""
    from distributed_optimization_tpu.utils.checkpoint import (
        CheckpointOptions,
    )

    ds, f_opt = e2e_data
    cfg = E2E_CFG.replace(
        robust_impl="gather", edge_drop_prob=0.2, burst_len=2.0,
        n_iterations=120, eval_every=20,
    )
    full = jax_backend.run(cfg, ds, f_opt)
    ckdir = str(tmp_path / "gather_ck")
    jax_backend.run(
        cfg.replace(n_iterations=60), ds, f_opt,
        checkpoint=CheckpointOptions(ckdir, every_evals=3),
    )
    resumed = jax_backend.run(
        cfg, ds, f_opt, checkpoint=CheckpointOptions(ckdir, every_evals=3)
    )
    np.testing.assert_allclose(
        resumed.final_models, full.final_models, rtol=1e-12
    )
    np.testing.assert_allclose(
        resumed.history.objective, full.history.objective, rtol=1e-12
    )


# ------------------------------------------------------- config / routing

def test_config_rejects_bad_robust_impl():
    with pytest.raises(ValueError, match="Unknown robust impl"):
        ExperimentConfig(robust_impl="csr")
    # An impl choice with no robust rule active would be silently ignored.
    with pytest.raises(ValueError, match="silently ignored"):
        ExperimentConfig(robust_impl="gather")
    with pytest.raises(ValueError, match="silently ignored"):
        ExperimentConfig(
            robust_impl="dense", aggregation="median", robust_b=0
        )


def test_resolved_robust_impl_crossover():
    cfg = ExperimentConfig(
        n_workers=256, topology="ring", aggregation="trimmed_mean",
        robust_b=1,
    )
    assert cfg.resolved_robust_impl(k_max=2) == "gather"
    # Fully connected: k_max = N − 1, gather measured a tie at best —
    # dense keeps the simpler form.
    assert cfg.resolved_robust_impl(k_max=255) == "dense"
    assert cfg.resolved_robust_impl(k_max=254) == "gather"
    # Explicit choices pass through.
    assert cfg.replace(robust_impl="dense").resolved_robust_impl(2) == "dense"
    assert (
        cfg.replace(robust_impl="gather").resolved_robust_impl(255)
        == "gather"
    )


def test_cli_robust_impl_flag():
    from distributed_optimization_tpu.cli import (
        build_parser,
        config_from_args,
    )

    args = build_parser().parse_args(
        ["--aggregation", "median", "--robust-b", "1",
         "--robust-impl", "gather"]
    )
    assert config_from_args(args).robust_impl == "gather"
