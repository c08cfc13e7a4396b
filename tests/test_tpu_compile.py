"""Compiled for a described v5e, at real sizes, without the chip (the TPU's
compiler is installed here; nothing runs, so no time and no result comes out
of this file). The one file that describes a topology: only the worker that
is given it loads the TPU's library, inside a fixture, never at import."""

import collections
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_optimization_tpu.observability import device_scopes
from distributed_optimization_tpu.ops.mixing import make_mixing_op
from distributed_optimization_tpu.parallel import topology


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as err:
        pytest.skip(f"no v5e:2x2 topology can be described here: {err}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def drawn_graph_round(one_chip):
    """One gossip round of the gather form at the drawn-graph cell's size
    (the pinned graph's live list, 209 chunks of 16,384 rows, over the
    cell's 2^18 rows of 81 floats; the graph itself a small one, its
    tables' SHAPES the cell's), tables as arguments."""
    n, chunks, d = 1 << 18, 209, 81
    rows = topology.gather_chunk_rows(n)
    small = topology.build_neighbor_topology(
        "erdos_renyi", 256, erdos_renyi_p=0.05, seed=7, sampler="sparse")
    op = make_mixing_op(small, impl="gather")

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    tables = {"nbr": shape((chunks, rows), jnp.int32), "w_nbr": shape((chunks, rows), jnp.float32),
              "row0": shape((chunks,), jnp.int32), "w_self": shape((n,), jnp.float32),
              "inverse": shape((n,), jnp.int32)}
    assert {k: v.dtype for k, v in tables.items()} == {
        k: v.dtype for k, v in op.tables.items()}
    return jax.jit(lambda x, tb: op.bind(tb).apply(x)).lower(
        shape((n, d), jnp.float32), tables).compile()


def test_a_gather_round_holds_one_chunk_not_thirty_slots(drawn_graph_round):
    """The round's temporaries are the models row-major and the accumulator,
    whatever k_max is: 0.27 GB (PR 36's loop over whole slots held 0.40)
    where the [N, 30, 81] stack and its product are 8.3 and thirty gathers
    hoisted ahead of their sum 4.2."""
    memory = drawn_graph_round.memory_analysis()
    assert memory.temp_size_in_bytes < 450_000_000, memory.temp_size_in_bytes


def test_a_gather_round_has_no_table_among_its_constants(drawn_graph_round):
    text = drawn_graph_round.as_text()
    constants = [
        device_scopes._shape_bytes(ins[1])
        for ins in map(device_scopes._instruction, text.splitlines())
        if ins is not None and ins[2] == "constant"
    ]
    assert max(constants) < 2**20
    entry = text[text.index("ENTRY"):].split("\n", 1)[0]
    assert "s32[209,16384]" in entry and "f32[209,16384]" in entry
    assert "s32[209]" in entry and "s32[262144]" in entry


def test_a_gather_round_adds_a_chunk_in_place(drawn_graph_round):
    """One loop; in its body one gather of a chunk's rows (the first chunk's
    stands before it) and ONE fusion that sums onto the accumulator's block
    and writes it where it lies: the ``dynamic-update-slice`` is that
    fusion's root, not a copy of its own after the sum, and no copy of the
    accumulator's 134 MB is made a trip; the one gather of 2^18 rows puts
    the sums back in the workers' order."""
    text = drawn_graph_round.as_text()
    ops = [ins for ins in map(device_scopes._instruction, text.splitlines()) if ins is not None]
    assert sum(ins[2] == "while" for ins in ops) == 1
    assert sorted(ins[1].split("{")[0] for ins in ops if ins[2] == "gather") == [
        "f32[16384,81]", "f32[16384,81]", "f32[262144,81]"]
    # the accumulator as 16 blocks of 16,384 rows, a chunk indexing its block
    (update,) = [line for line in text.splitlines() if " dynamic-update-slice(" in line]
    assert update.lstrip().startswith("ROOT ") and "f32[16,16384,81]" in update
    fused = [ins for ins in ops if ins[2] == "fusion" and ins[1].startswith("f32[16,16384,81]")]
    assert len(fused) == 1 and "kind=kLoop" in fused[0][4]
    copies = [ins for ins in ops if ins[2] == "copy" and ins[1].startswith("f32[262144,81]")]
    # x to row-major for the first chunk and for the loop, the result back
    # to the carried layout
    assert len(copies) <= 3, copies


@pytest.fixture(scope="module")
def gather_sampler_loop(one_chip):
    """The gather sampler at the tracker cell's size (16,384 shards of 800
    rows of 81 floats, 16 drawn a worker) as the scan runs it: its table made
    before the loop, a draw a trip, both under ``dopt.sampling``."""
    from distributed_optimization_tpu.ops.sampling import batch_table, sample_table_batches

    n, rows, d, batch = 16_384, 800, 81, 16

    def loop(X, y, n_valid):
        key = jax.random.key(7)
        with device_scopes.scope("sampling"):
            table = batch_table(X, y)

        def trip(acc, t):
            with device_scopes.scope("sampling"):
                Xb, yb, w = sample_table_batches(key, t, table, n_valid, batch)
            return acc + jnp.einsum("nbd,nb->nd", Xb, yb * w), None

        return jax.lax.scan(trip, jnp.zeros((n, d), X.dtype), jnp.arange(8, dtype=jnp.int32))[0]

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return jax.jit(loop).lower(
        shape((n, rows, d), jnp.float32), shape((n, rows), jnp.float32),
        shape((n,), jnp.int32)).compile()


def test_the_gather_sampler_sorts_and_scatters_nothing(gather_sampler_loop):
    """A batch is selected by a counted threshold and fetched by ONE gather
    of whole rows, the targets riding in them."""
    ops = [ins for ins in map(device_scopes._instruction, gather_sampler_loop.as_text().splitlines())
           if ins is not None]
    assert not [ins for ins in ops if ins[2] in ("sort", "scatter")]
    assert "TopK" not in gather_sampler_loop.as_text()
    gathers = [ins for ins in ops if ins[2] == "gather"]
    # 16,384 x 16 rows of 82 floats, whichever way the batch axes are merged
    assert len(gathers) == 1 and device_scopes._shape_bytes(gathers[0][1]) == 262_144 * 82 * 4, gathers
    assert "dopt.sampling" in gathers[0][4]


def test_the_gather_samplers_table_is_its_one_large_temporary(gather_sampler_loop):
    """The table, 82 numbers a row padded to 128 lanes (6.71 GB: what XLA's
    own row-major copy of the shards took at PR 39), and no second array of
    its size beside it while it is filled or read."""
    memory = gather_sampler_loop.memory_analysis()
    assert 6_710_886_400 <= memory.temp_size_in_bytes < 7_400_000_000, memory.temp_size_in_bytes


@pytest.fixture(scope="module")
def shard_visit_at_the_cell(one_chip):
    """``glm_shard_visit`` at the GLM cells' size (2^18 shards of 53 rows of
    81 floats, the logistic pair), compiled by Mosaic and XLA together."""
    from distributed_optimization_tpu.ops import pallas_kernels as pk
    from distributed_optimization_tpu.ops.losses import LOGISTIC

    n, rows, d = 1 << 18, 53, 81

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return jax.jit(
        lambda *a: pk.glm_shard_visit(LOGISTIC, *a, interpret=False)
    ).lower(
        shape(n, rows, d), shape(n, rows), shape(n, d), shape(d),
        shape(n, rows), shape(n, dtype=jnp.int32)).compile()


def test_the_shard_visit_reads_the_stack_where_it_lies(shard_visit_at_the_cell):
    """The runtime keeps the stack with the worker axis minor, so the kernel's
    ``[d, L, N]`` view of it, and ``[d, N]`` of the models, are bitcasts: ONE
    custom call, no copy, transpose or fusion anywhere, no temporary."""
    text = shard_visit_at_the_cell.as_text()
    entry = text[text.index("ENTRY"):].split("\n", 1)[0]
    assert "f32[262144,53,81]" in entry
    ops = [ins for ins in map(device_scopes._instruction, text.splitlines()) if ins is not None]
    assert sorted({ins[2] for ins in ops} - {"parameter", "tuple", "get-tuple-element"}) == [
        "bitcast", "custom-call"]
    (call,) = [ins for ins in ops if ins[2] == "custom-call"]
    assert call[0].startswith("%glm_shard_visit") and "f32[81,262144]" in call[1]
    assert "f32[81,53,262144]{2,1,0" in text
    assert shard_visit_at_the_cell.memory_analysis().temp_size_in_bytes == 0


def test_the_shard_gradient_reads_the_stack_where_it_lies(one_chip):
    """The visit without its objective half (ISSUE 51) at the same size: the
    same bitcast views, ONE custom call under its own name with ONE result,
    no temporary."""
    from distributed_optimization_tpu.ops import pallas_kernels as pk
    from distributed_optimization_tpu.ops.losses import LOGISTIC

    n, rows, d = 1 << 18, 53, 81

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    compiled = jax.jit(
        lambda *a: pk.glm_shard_gradient(LOGISTIC, *a, interpret=False)
    ).lower(shape(n, rows, d), shape(n, rows), shape(n, d), shape(n, rows)).compile()
    text = compiled.as_text()
    ops = [ins for ins in map(device_scopes._instruction, text.splitlines()) if ins is not None]
    assert sorted({ins[2] for ins in ops} - {"parameter", "tuple", "get-tuple-element"}) == [
        "bitcast", "custom-call"]
    (call,) = [ins for ins in ops if ins[2] == "custom-call"]
    assert call[0].startswith("%glm_shard_gradient") and call[1].startswith("f32[81,262144]")
    assert "f32[81,53,262144]{2,1,0" in text
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.fixture(scope="module")
def byzantine_round(one_chip):
    """One screened gossip round at the Byzantine cell's size (ISSUE 43: a
    ring of 2^18 workers, 24,576 sign-flippers placed within the budget, the
    trimmed mean at b = 1 over the neighbor table, k_max = 2), as
    ``_bind_byzantine`` composes it: the payload, the gather form's rule
    (three slot planes and a compare-exchange network since ISSUE 44, the
    two received planes two shifts of the transmitted stack since ISSUE 45:
    the table is a ring's), the attackers' stencil."""
    from distributed_optimization_tpu.ops.robust_aggregation import (
        make_gather_robust_aggregator,
    )
    from distributed_optimization_tpu.parallel.adversary import (
        make_adversary,
        make_byzantine_mixing,
        place_within_budget,
    )

    n, d = 1 << 18, 81
    ring = topology.build_topology("ring", n, impl="neighbor")
    nbr_idx, nbr_mask = topology.neighbor_tables_for(ring)
    mix_op = make_mixing_op(ring, impl="auto", dtype=jnp.float32)
    rule = make_gather_robust_aggregator("trimmed_mean", 1, nbr_idx)
    live = jnp.asarray(nbr_mask, dtype=jnp.float32)
    attackers = make_adversary(
        n, "sign_flip", 24_576, 5.0, 7,
        byz=place_within_budget(nbr_idx, nbr_mask, 24_576, 1, 7))
    mix = make_byzantine_mixing(
        attackers, lambda t, v: mix_op.apply(v), aggregate_t=lambda t, v: rule(live, v))
    return jax.jit(lambda x: mix(jnp.int32(0), x)).lower(
        jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=one_chip)).compile()


def test_the_byzantine_round_orders_three_slots_without_a_lane_wide_stack(byzantine_round):
    """Three slots are three planes ``[N, 81]`` ordered by compare-and-select
    (ISSUE 44): NO ``sort``, no ``iota s32[262144,3,81]`` beside it, no
    operand ``[262144,3,81]`` at all (the concatenate and the masked sum went
    with it); the network, the attackers' stencil and the outer select are
    ONE elementwise fusion over the models. No operand is the stack's size
    times 128, and the round's temporaries are the payload and the shifted
    planes' row slices: 0.37 GB (ISSUE 45) where the gathered planes and the
    row-major copies held 0.67 and the sort's round 1.21."""
    text = byzantine_round.as_text()
    ops = [ins for ins in map(device_scopes._instruction, text.splitlines()) if ins is not None]
    stack = (1 << 18) * 81 * 4
    assert max(device_scopes._shape_bytes(ins[1]) for ins in ops) < 16 * stack
    # (the one iota is the row index ``s32[262144]`` of the end rows' select)
    assert not [ins for ins in ops if ins[2] == "sort" or (ins[2] == "iota" and "81]" in ins[1])], "a sort came back"
    assert "[262144,3,81]" not in text and "[262144,2,81]" not in text
    assert byzantine_round.memory_analysis().temp_size_in_bytes < 500_000_000


def _holds_no_table_and_nothing_row_major(text):
    """A ring's round by shifts (ISSUE 45): no gather, no gathered
    ``f32[524288,81]``, no index constant ``s32[524288]`` and no array
    ``f32[262144,81]`` laid row-major (the carried models lie ``{0,1}``, the
    worker axis minor; the gather wanted them ``{1,0}`` and the aggregate
    copied back)."""
    ops = [ins for ins in map(device_scopes._instruction, text.splitlines()) if ins is not None]
    assert not [ins for ins in ops if ins[2] == "gather"], "a gather came back"
    assert "[524288,81]" not in text and "s32[524288]" not in text
    assert not [ins for ins in ops if "f32[262144,81]{1,0" in ins[1]], "a row-major copy came back"
    return ops


def test_the_byzantine_round_reads_its_neighbours_by_shifts(byzantine_round):
    """Closed into the executable, the all-ones liveness is folded away and
    the attackers' mask is a ``pred[262144]``; the ring's neighbor table is
    not in the program at all (ISSUE 45; one flat ``s32[524288]`` before):
    no ``[262144, 2]`` constant, which lies in 134 MB of tiles, survives.
    Why the masks stay constants (ROADMAP W2 d). The transmitted stack's row
    slices are ``dopt.robust``'s, the stencil's ``dopt.gossip``'s."""
    text = byzantine_round.as_text()
    ops = _holds_no_table_and_nothing_row_major(text)
    constants = [ins for ins in ops if ins[2] == "constant"]
    assert max(device_scopes._shape_bytes(ins[1]) for ins in constants) <= 262_144
    assert not [ins for ins in constants if "[262144,2]" in ins[1]]
    assert byzantine_round.memory_analysis().generated_code_size_in_bytes < 4 * 2**20
    slices = [ins for ins in ops if ins[2] == "fusion" and "f32[262143,81]" in ins[1]]
    assert len(slices) == 2 and sum("dopt.robust" in ins[4] for ins in slices) == 1


def test_the_faulted_byzantine_round_reads_its_neighbours_by_shifts(one_chip):
    """The same rule over the liveness bits the ring's fault layer draws at
    t (a bit a slot, 30% link loss, 10% stragglers) at the cell's size:
    shifts too, and the fault layer's own reads are shifts since ISSUE 33,
    so the whole round holds no gather and no table."""
    from distributed_optimization_tpu.ops.robust_aggregation import (
        make_gather_robust_aggregator,
    )
    from distributed_optimization_tpu.parallel.faults import make_faulty_mixing

    n, d = 1 << 18, 81
    ring = topology.build_topology("ring", n, impl="neighbor")
    nbr_idx, nbr_mask = topology.neighbor_tables_for(ring)
    rule = make_gather_robust_aggregator("trimmed_mean", 1, nbr_idx)
    live_fn = make_faulty_mixing(
        ring, 0.3, seed=5, straggler_prob=0.1).make_neighbor_liveness(nbr_idx, nbr_mask)
    text = jax.jit(lambda t, x: rule(live_fn(t), x)).lower(
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=one_chip)).compile().as_text()
    _holds_no_table_and_nothing_row_major(text)


def test_a_toruss_round_still_holds_its_one_gather(one_chip):
    """Any table that is not a ring's keeps the gather as it was: a 512 x 512
    torus at the cell's N and d, its four slots' planes from ONE gather of
    ``[4·N, d]`` under ``dopt.robust``."""
    from distributed_optimization_tpu.ops.robust_aggregation import (
        make_gather_robust_aggregator,
    )

    n, d = 1 << 18, 81
    torus = topology.build_topology("grid", n, impl="neighbor")
    nbr_idx, nbr_mask = topology.neighbor_tables_for(torus)
    assert nbr_idx.shape == (n, 4)
    rule = make_gather_robust_aggregator("trimmed_mean", 1, nbr_idx)
    live = jnp.asarray(nbr_mask, dtype=jnp.float32)
    text = jax.jit(lambda x: rule(live, x)).lower(
        jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=one_chip)).compile().as_text()
    (gather,) = [ins for ins in map(device_scopes._instruction, text.splitlines())
                 if ins is not None and ins[2] == "gather"]
    assert "dopt.robust" not in gather[4]  # the rule alone: the scope is the mixing's
    assert "f32[1048576,81]" in text and "s32[1048576]" in text


def test_one_slot_over_the_networks_width_still_sorts(one_chip):
    """The table's width decides and nothing else: the same rule over a table
    of ``NETWORK_MAX_SLOTS`` neighbours a worker (one slot more than the
    network takes) at the cell's N and d compiles the stack and its sort."""
    from distributed_optimization_tpu.ops import robust_aggregation as ra

    n, d, k = 1 << 18, 81, ra.NETWORK_MAX_SLOTS
    i = np.arange(n)[:, None]
    nbr_idx = np.sort(np.concatenate(
        [(i + o) % n for o in range(1, k // 2 + 1)]
        + [(i - o) % n for o in range(1, k - k // 2 + 1)], axis=1), axis=1)
    assert nbr_idx.shape == (n, k)
    rule = ra.make_gather_robust_aggregator("trimmed_mean", 1, nbr_idx)
    live = jnp.ones((n, k), jnp.float32)
    text = jax.jit(lambda x: rule(live, x)).lower(
        jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=one_chip)).compile().as_text()
    (sort,) = [ins for ins in map(device_scopes._instruction, text.splitlines())
               if ins is not None and ins[2] == "sort"]
    assert f"f32[262144,{k + 1},81]" in sort[1], sort[1]


def test_a_timelines_chain_is_one_scan_and_its_leaf_a_byte_a_bit(one_chip):
    """The churn cell's chains at its size (ISSUE 46): one scan of 1,000
    rounds over 2^18 columns leaves ``pred[1000, 262144]``, 262,144,000 B in
    the chip's tiles (a byte a bit, no padding: what ``faults.state_bytes``
    counts three of), with no second ``[T, N]`` array among its temporaries;
    and ``rejoin`` is one pass over ``node_up`` with no temporary at all."""
    from distributed_optimization_tpu.parallel import faults

    n, horizon = 1 << 18, 1000
    key = jax.eval_shape(lambda: jax.random.fold_in(jax.random.key(0), 1))
    key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip)
    f32 = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    chain = faults._unroll_chain.lower(key, f32, f32, f32, size=n, horizon=horizon).compile()
    memory = chain.memory_analysis()
    assert memory.output_size_in_bytes == horizon * n
    assert memory.temp_size_in_bytes < 4 << 20
    assert "pred[1000,262144]" in chain.as_text()
    up = jax.ShapeDtypeStruct((horizon, n), jnp.bool_, sharding=one_chip)
    back = faults._rejoin_rounds.lower(up).compile().memory_analysis()
    assert (back.output_size_in_bytes, back.temp_size_in_bytes) == (horizon * n, 0)


def _cell_scan(one_chip, config_name, horizon, **replace):
    """A GLM ring cell's whole scan as ``_run`` builds it (the dense sampler,
    the visit fused as on the chip) at N = 2^18, L = 53, d = 81, unroll 8,
    compiled for ``horizon`` trips with the timeline's ``pred[horizon, N]``
    leaves (where the cell has a fault layer) as arguments; ``replace``
    overrides fields of the file's experiment. No shard, timeline or model
    of that size is made:
    the call is cut where it hands its program to the driver, the program
    lowered from shapes."""
    from distributed_optimization_tpu.backends import jax_backend
    from distributed_optimization_tpu.config import ExperimentConfig
    from distributed_optimization_tpu.ops import pallas_kernels as pk
    from distributed_optimization_tpu.utils.data import DeviceDataset, HostDataset

    n, rows, d = 1 << 18, 53, 81
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", config_name + ".json")) as fh:
        experiment = json.load(fh)["experiment"]
    assert (experiment["n_workers"], experiment["n_features"]) == (n, d - 1)
    # what ``auto`` takes on the chip (a CPU's is the gather sampler, unroll 1)
    cfg = ExperimentConfig(
        **{**experiment, **replace}, n_samples=n * rows, sampling_impl="dense",
        scan_unroll=8, n_iterations=2, eval_every=1)

    class Handed(Exception):
        pass

    def hand_over(make_seg_scan, trips_per_eval, state0, data_args, *a, **kw):
        raise Handed(make_seg_scan, state0, data_args)

    def zeros(*dims, dtype=np.float32):  # no memory behind them
        return np.broadcast_to(np.zeros((), dtype), dims)

    def shaped(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax_backend, "_drive_segments", hand_over)
        patch.setattr(jax_backend, "stack_shards", lambda ds, dtype: DeviceDataset(
            X=zeros(n, rows, d), y=zeros(n, rows), n_valid=np.full(n, rows, np.int32),
            stacked_by="view"))
        patch.setattr(jax_backend, "place_shards", lambda mesh, X: (shaped(X), "direct", {}))
        patch.setattr(jax_backend, "shard_over_workers", lambda mesh, a: shaped(a))
        patch.setattr(jax_backend, "_visit_is_fused", lambda carried, X: bool(carried))
        patch.setattr(pk, "resolve_interpret", lambda *a, **kw: False)
        nothing = HostDataset(X_full=zeros(1, d - 1), y_full=zeros(1), shard_indices=[],
                              problem_type="logistic")
        with pytest.raises(Handed) as handed:
            jax_backend.run(cfg, nothing, 0.0, use_mesh=False, measure_compile=False)
        make_seg_scan, state0, data = handed.value.args
        data = jax.tree.map(shaped, data)
        if "faults" in data:  # the fault-free control has no timeline
            data["faults"] = {
                k: jax.ShapeDtypeStruct((horizon, n), v.dtype, sharding=one_chip)
                for k, v in data["faults"].items()}
        return jax.jit(make_seg_scan(horizon)).lower(
            jax.tree.map(shaped, state0),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip), data).compile()


@pytest.fixture(scope="module")
def churn_cell_scan(one_chip):
    """The churn cell's whole scan (ISSUE 47: bursts, churn and
    ``neighbor_restart`` on the ring's shift form), 1,000 trips, the three
    ``pred[1000, N]`` leaves as arguments."""
    return _cell_scan(one_chip, "glm81_ring262k_burst4_churn400", 1000)


def test_the_churn_cells_trip_visits_the_shards_once(churn_cell_scan):
    """Under ``neighbor_restart`` the trip is the fused one (ISSUE 47): ONE
    ``glm_shard_visit`` a trip (eight in the unrolled body, one in front of
    the loop), no fusion, reduce or copy that reads or writes the
    ``f32[262144,53,81]`` stack besides, and the restart's two row slices
    made ONCE a trip (the restarted models ride in the carry: the step does
    not make them again)."""
    text = churn_cell_scan.as_text()
    ops = [ins for ins in map(device_scopes._instruction, text.splitlines()) if ins is not None]
    visits = [ins for ins in ops if ins[2] == "custom-call" and ins[0].startswith("%glm_shard_visit")]
    assert len(visits) == 9 and all("f32[81,262144]" in ins[1] for ins in visits)
    # Whatever holds the stack (a parameter, the loop's element, a bitcast
    # of either) is read by the visit and by nothing else: no reduce, fusion
    # or copy of it. Names are a computation's own, so one at a time.
    stack = re.compile(r"^f32\[(262144,53,81|81,53,262144)\]")
    comps = device_scopes._computations(text)[0]
    for lines in comps.values():
        body = [ins for ins in map(device_scopes._instruction, lines) if ins is not None]
        held = {ins[0] for ins in body if stack.match(ins[1])}
        readers = {ins[2] for ins in body if held & set(ins[3])}
        assert readers <= {"bitcast", "custom-call", "tuple", "while"}, readers
    assert not [ins for ins in ops if stack.match(ins[1])
                and ins[2] not in ("parameter", "get-tuple-element", "bitcast")]
    slices = [ins for ins in ops if ins[2] == "fusion" and ins[1].startswith("(f32[262143,81]")]
    restarts = [ins for ins in slices if "dopt.faults" in ins[4]]
    assert len(restarts) == 9, len(restarts)  # a trip's ONE, and the first trip's in front of the loop
    assert churn_cell_scan.memory_analysis().temp_size_in_bytes < 760_000_000


def test_the_churn_cells_leaves_stay_arguments(churn_cell_scan):
    """The three ``pred[1000, 262144]`` timeline leaves are parameters of the
    entry computation; no constant of their size (262 MB each) is closed
    into the executable."""
    text = churn_cell_scan.as_text()
    entry = text[text.index("ENTRY"):].split("\n", 1)[0]
    assert entry.count("pred[1000,262144]") == 3
    constants = [
        device_scopes._shape_bytes(ins[1])
        for ins in map(device_scopes._instruction, text.splitlines())
        if ins is not None and ins[2] == "constant"]
    assert max(constants) < 2**21, max(constants)
    assert churn_cell_scan.memory_analysis().generated_code_size_in_bytes < 32 * 2**20


@pytest.fixture(scope="module")
def federated_cell_scan(one_chip):
    """The federated cell's whole scan (ISSUE 50: four gradient steps a
    round, half the workers sampled out), 250 trips, the one
    ``pred[250, N]`` leaf as an argument."""
    return _cell_scan(one_chip, "glm81_ring262k_local4_part50", 250)


def _stack_readers(text):
    """(scope, opcode, ``name = shape``) of every instruction that reads the
    ``f32[262144,53,81]`` stack, through whatever holds it in its
    computation (a parameter, the loop's element, a bitcast of either);
    bitcasts, tuples and loops themselves left out, and the insides of a
    fusion (the fusion is the reader)."""
    stack = re.compile(r"^f32\[(262144,53,81|81,53,262144)\]")
    parsed = {}  # computation -> [(instruction, its scope)]
    for name, lines in device_scopes._computations(text)[0].items():
        pairs = ((device_scopes._instruction(line), device_scopes._scope_of(line))
                 for line in lines)
        parsed[name] = [(ins, scope) for ins, scope in pairs if ins is not None]
    fused = {called for body in parsed.values() for ins, _ in body if ins[2] == "fusion"
             for called in device_scopes._CALLED_RE.findall(ins[4])}
    readers = []
    for name, body in parsed.items():
        if name in fused:
            continue
        held = {ins[0] for ins, _ in body if stack.match(ins[1])}
        readers += [(scope, ins[2], f"{ins[0]} = {ins[1]}") for ins, scope in body
                    if held & set(ins[3]) and ins[2] not in ("bitcast", "tuple", "while")]
    return readers


def test_the_federated_cells_round_reads_the_shards_four_times(federated_cell_scan):
    """A round of tau = 4 as the root's ``shard_reads`` plans it (ISSUE 51):
    ONE ``glm_shard_visit`` (the first gradient and the objective,
    ``gradient``) and, for each of the three later descents, ONE
    ``glm_shard_gradient`` (the visit without its objective half, ``local``);
    no fusion reads the stack: 4 reads a trip, where the later descents' X.x
    and X^T.c fusions made it 7. 250 trips are 31 bodies of eight and a
    remainder of two, so ten trips are written out: ten visits and one in
    front of the loops, thirty gradients."""
    readers = collections.Counter(
        (scope, opcode, name.split(".")[0])
        for scope, opcode, name in _stack_readers(federated_cell_scan.as_text()))
    assert readers == {("gradient", "custom-call", "%glm_shard_visit"): 10 + 1,
                       ("local", "custom-call", "%glm_shard_gradient"): 10 * 3}, readers


def test_the_federated_cells_kernel_rows_carry_one_scope_each(federated_cell_scan):
    """Every instruction of the later descents' kernel says ``local`` and
    every ``glm_shard_visit`` ``gradient``: two kinds of row by the
    benchmark's own rule (``trace_reduce.op_kind``: another stem, another
    output type), and no kind of row that reads the stack holds two scopes,
    so the reduction bills each row whole and neither reads 0."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import scope_reduce, trace_reduce

    kinds = scope_reduce.kinds_of([device_scopes.scope_table(federated_cell_scan)])
    assert {k: v["scopes"] for k, v in kinds.items() if k.startswith("glm_shard_")} == {
        "glm_shard_gradient f32[81,262144]": {"local"},
        "glm_shard_visit (f32[81,262144]": {"gradient"}}
    reading = {trace_reduce.op_kind(name)
               for _, _, name in _stack_readers(federated_cell_scan.as_text())}
    assert len(reading) == 2 and all(len(kinds[k]["scopes"]) == 1 for k in reading), reading
    # the plain later gradients' two large rows went with them
    assert not [k for k, v in kinds.items()
                if k.startswith("multiply_reduce_fusion") and "local" in v["scopes"]]


def test_the_federated_cells_leaf_stays_an_argument(federated_cell_scan):
    text = federated_cell_scan.as_text()
    entry = text[text.index("ENTRY"):].split("\n", 1)[0]
    assert entry.count("pred[250,262144]") == 1
    memory = federated_cell_scan.memory_analysis()
    # the later gradients' [N, 53] margins went with their fusions: 2.2 GB
    # at PR 50 (PERF.md section 5)
    assert memory.temp_size_in_bytes < 1_300_000_000, memory.temp_size_in_bytes
    assert memory.generated_code_size_in_bytes < 32 * 2**20


def test_a_round_of_one_gradient_holds_no_shard_gradient(one_chip):
    """The tau = 1 control (``glm81_ring262k``, everyone taking part): its
    compiled scan visits the shards once a trip and holds no instruction of
    the later descents' kernel, nor anything under ``local``."""
    text = _cell_scan(one_chip, "glm81_ring262k", 250).as_text()
    assert "glm_shard_gradient" not in text and "dopt.local" not in text
    readers = collections.Counter(
        (scope, opcode, name.split(".")[0]) for scope, opcode, name in _stack_readers(text))
    assert readers == {("gradient", "custom-call", "%glm_shard_visit"): 10 + 1}, readers


def test_the_loop_form_of_the_local_descents_visits_once_a_descent(one_chip):
    """tau = 10 on the federated cell (nine descents, over ``LOCAL_UNROLL_MAX``:
    a ``fori_loop`` whose slot is traced): Mosaic and XLA compile the kernel
    inside the loop's body, ONE ``glm_shard_gradient`` a written-out trip
    under ``local`` at weights drawn from the traced slot, and nothing else
    reads the stack."""
    text = _cell_scan(one_chip, "glm81_ring262k_local4_part50", 250, local_steps=10).as_text()
    readers = collections.Counter(
        (scope, opcode, name.split(".")[0]) for scope, opcode, name in _stack_readers(text))
    assert readers == {("gradient", "custom-call", "%glm_shard_visit"): 10 + 1,
                       ("local", "custom-call", "%glm_shard_gradient"): 10}, readers
