"""Explicit-collective mixing operators: shard_map + ppermute/psum over ICI.

This is the north-star communication backend (SURVEY.md §5.8, C12): each
device holds a contiguous block of workers, and one gossip round exchanges
only the block-boundary rows with the neighboring devices via
``jax.lax.ppermute`` (ring/torus) or reduces with ``jax.lax.psum`` (fully
connected / centralized). This replaces the reference's simulated dense
``W @ models`` matmul (reference ``trainer.py:173``) with the real collective
traffic pattern: a ring of N workers on D devices moves exactly 2·d floats
per device per round over ICI, independent of N — enforced against the
compiled HLO (instruction kinds and payload element counts) by
``tests/test_collectives.py::test_ring_lowers_to_boundary_permutes_with_2d_floats``
and companions, for both this module's explicit ops and the GSPMD stencils.

The GSPMD stencils in ``ops/mixing.py`` compile to the same collectives
automatically; this module is the manually scheduled form — used when
``mixing_impl='shard_map'`` — and doubles as executable documentation of the
communication pattern. Property tests check both against the dense matrix.

Intra-block neighbor averaging is pure local compute; only the first/last
rows of each block cross device boundaries. Worker blocks are contiguous
(worker i lives at block row i % (N/D) on device i // (N/D)), matching the
``NamedSharding`` layout that ``mesh.shard_over_workers`` produces.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import dataclasses

import numpy as np

from distributed_optimization_tpu.ops.mixing import MixingOp, slot_sum
from distributed_optimization_tpu.parallel.mesh import WORKER_AXIS
from distributed_optimization_tpu.parallel.topology import (
    Topology,
    _table_is_a_ring,
    build_halo_plan,
    gather_mixing_weights,
    neighbor_tables_for,
)


def _ring_block_mix(axis: str, n_devices: int, w: float):
    """Per-block ring stencil: local shifts + edge-row ppermutes."""
    fwd = [(i, (i + 1) % n_devices) for i in range(n_devices)]
    bwd = [(i, (i - 1) % n_devices) for i in range(n_devices)]

    def exchange(block):  # block: [per, d] on each device
        # Row arriving from the previous device (their last worker) and the
        # next device (their first worker).
        from_prev = jax.lax.ppermute(block[-1:], axis, fwd)
        from_next = jax.lax.ppermute(block[:1], axis, bwd)
        left = jnp.concatenate([from_prev, block[:-1]], axis=0)  # x_{i-1}
        right = jnp.concatenate([block[1:], from_next], axis=0)  # x_{i+1}
        return left, right

    def mix(block):
        left, right = exchange(block)
        return (w * (block + left + right)).astype(block.dtype)

    def nbr(block):
        left, right = exchange(block)
        return (left + right).astype(block.dtype)

    return mix, nbr


def _directed_ring_block_mix(axis: str, n_devices: int):
    """Per-block directed-ring stencil: ONE forward ppermute per round.

    The directed ring receives only from the predecessor, so each device
    ships exactly its last worker row forward — d floats per device per
    round, HALF the undirected ring's boundary traffic (asserted against
    compiled HLO by tests/test_push_sum.py)."""
    fwd = [(i, (i + 1) % n_devices) for i in range(n_devices)]

    def exchange(block):  # block: [per, d] on each device
        from_prev = jax.lax.ppermute(block[-1:], axis, fwd)
        return jnp.concatenate([from_prev, block[:-1]], axis=0)  # x_{i-1}

    def mix(block):
        return (0.5 * (block + exchange(block))).astype(block.dtype)

    def nbr(block):
        return exchange(block).astype(block.dtype)

    return mix, nbr


def _fc_block_ops(axis: str, n_total: int):
    def mix(block):
        total = jax.lax.psum(jnp.sum(block, axis=0, keepdims=True), axis)
        return jnp.broadcast_to(total / n_total, block.shape).astype(block.dtype)

    def nbr(block):
        total = jax.lax.psum(jnp.sum(block, axis=0, keepdims=True), axis)
        return (total - block).astype(block.dtype)

    return mix, nbr


def _grid_block_ops(axis: str, n_devices: int, rows: int, cols: int, w: float):
    """Torus stencil with the row axis blocked over devices.

    Each device holds rows_per_dev full grid rows ([rows_per_dev, cols, d]);
    column rolls are local, row rolls exchange one boundary grid-row (cols·d
    floats) with each neighboring device.
    """
    fwd = [(i, (i + 1) % n_devices) for i in range(n_devices)]
    bwd = [(i, (i - 1) % n_devices) for i in range(n_devices)]

    def shifts(block):  # [r_loc, cols, d]
        from_prev = jax.lax.ppermute(block[-1:], axis, fwd)
        from_next = jax.lax.ppermute(block[:1], axis, bwd)
        up = jnp.concatenate([from_prev, block[:-1]], axis=0)
        down = jnp.concatenate([block[1:], from_next], axis=0)
        lateral = jnp.roll(block, 1, axis=1) + jnp.roll(block, -1, axis=1)
        return up + down + lateral

    def mix(block):
        return (w * (block + shifts(block))).astype(block.dtype)

    def nbr(block):
        return shifts(block).astype(block.dtype)

    return mix, nbr


def _over_row_blocks(block_fn, mesh: Mesh):
    """``block_fn`` under ``shard_map`` with the leading (worker) axis blocked
    over the mesh and every parameter axis replicated: the spec follows the
    stack's rank ([N, d] or a model-shaped [N, d, K])."""

    def fn(x):
        spec = P(WORKER_AXIS, *([None] * (x.ndim - 1)))
        return shard_map(block_fn, mesh=mesh, in_specs=spec, out_specs=spec)(x)

    return fn


def make_shard_map_mixing_op(topo: Topology, mesh: Mesh) -> MixingOp:
    """Build the explicit shard_map collective mixing op for a topology.

    Supports the mesh-embeddable graphs (ring, torus grid, fully connected).
    Irregular graphs (Erdős–Rényi, chain, star) use the dense form instead
    (SURVEY.md §7 hard part (c)).
    """
    axis = WORKER_AXIS
    n_devices = mesh.shape[axis]
    n = topo.n
    if n % n_devices != 0:
        raise ValueError(f"n_workers={n} not divisible by mesh size {n_devices}")

    if topo.name == "ring":
        if n < 3:
            raise ValueError("shard_map ring mixing needs n >= 3")
        mix_block, nbr_block = _ring_block_mix(axis, n_devices, 1.0 / 3.0)
    elif topo.name == "directed_ring":
        if n < 3:
            raise ValueError("shard_map directed_ring mixing needs n >= 3")
        mix_block, nbr_block = _directed_ring_block_mix(axis, n_devices)
    elif topo.name == "fully_connected":
        mix_block, nbr_block = _fc_block_ops(axis, n)
    elif topo.name == "grid":
        rows, cols = topo.grid_shape  # type: ignore[misc]
        if min(rows, cols) < 3:
            raise ValueError("shard_map grid mixing needs a >=3x3 torus")
        if rows % n_devices != 0:
            raise ValueError(
                f"grid rows={rows} not divisible by mesh size {n_devices}"
            )
        mix_block, nbr_block = _grid_block_ops(axis, n_devices, rows, cols, 1.0 / 5.0)
    else:
        raise ValueError(
            f"No shard_map stencil for topology {topo.name!r}; use dense mixing"
        )

    def _wrap(block_fn):
        # The worker axis (the grid's row axis) is blocked over devices.
        sharded = _over_row_blocks(block_fn, mesh)
        if topo.name != "grid":
            return sharded
        rows, cols = topo.grid_shape  # type: ignore[misc]

        def fn(x):  # grid layout -> stencil -> back
            g = x.reshape(rows, cols, *x.shape[1:])
            return sharded(g).reshape(x.shape)

        return fn

    return MixingOp(topo.name, "shard_map", _wrap(mix_block), _wrap(nbr_block))


# ---------------------------------------------------------------------------
# Sharded worker mesh (ISSUE-11 tentpole; docs/PERF.md §16): the k_max-
# bounded gather path of docs/PERF.md §14 lowered to REAL collectives.
# Each device owns a contiguous block of N/P worker rows — state [S, d],
# neighbor-table block [S, k_max] remapped to shard-local coordinates —
# and one gossip round ppermute-fetches only the boundary rows the block's
# table references (the halo), then runs the ordinary gather math locally.
# Per-row arithmetic is the EXACT op sequence of the single-device gather
# operators (same slot order, same accumulation dtype), so sharded and
# unsharded trajectories agree at matched N to the few units in the last
# place by which two executables contract the same products and sums
# differently (bitwise under the jax this was written on; one f32 ulp a
# round under jax 0.9: tests/test_worker_mesh.py states the tolerance,
# ISSUE 30); the only cross-device traffic is
# the halo rows — O(boundary · d) per device per round, independent of N
# for ring/torus/chain and O(E/P² · d) per rotation for Erdős–Rényi.
# A block whose neighbor table is a ring's needs no table at all: its
# plain mixing is two row shifts with the two boundary rows patched from
# the ppermuted halo (``make_halo_mixing_op``'s ``halo_shift``, PR 35),
# because the chip prices a gather by its indices. The fault, compressed
# and robust halo layers below still address a ring through the tables.
# Single-process multi-device (the closures capture sharded tables, which
# multi-process jax forbids); on CPU hosts simulate the mesh via
# XLA_FLAGS=--xla_force_host_platform_device_count=P.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HaloExchange:
    """A ``HaloPlan`` bound to a device mesh, ready to run under shard_map.

    ``run(body, *arrays)`` shard_maps ``body`` over row-sharded ``arrays``
    ([N, ...] leaves, axis 0 split over the mesh). The body receives
    ``(exchange, nbr_l [S, k_max], mask [S, k_max], *blocks)`` where
    ``exchange(buf [S, w]) -> ext [S + h_max + 1, w]`` performs the
    planned ppermute rotations — ``ext[nbr_l]`` then gathers exactly the
    values ``x_global[nbr_idx]`` gathers on one device. The body must
    return one ``[S, ...]`` array (row-sharded output).
    """

    mesh: Mesh
    plan: object                 # topology.HaloPlan
    nbr_l: jax.Array             # [P, S, k_max] int32 (shard-local coords)
    mask: jax.Array              # [P, S, k_max] float32 static liveness
    sends: tuple                 # per step [P, s_max] int32
    recvs: tuple                 # per step [P, s_max] int32
    perms: tuple                 # per step static ((src, dst), ...) pairs

    @property
    def n_shards(self) -> int:
        return self.plan.n_shards

    def run(self, body, *arrays):
        P_ = jax.sharding.PartitionSpec
        n_steps = len(self.perms)
        h_max = self.plan.h_max
        perms = self.perms

        def shard_body(nbr_lb, maskb, *rest):
            sends = rest[:n_steps]
            recvs = rest[n_steps:2 * n_steps]
            blocks = rest[2 * n_steps:]

            def exchange(buf):
                # buf [S, w] -> ext [S + h_max + 1, w]; the trailing halo
                # slot is the dump row padded traffic lands in.
                halo = jnp.zeros((h_max + 1, buf.shape[-1]), buf.dtype)
                for perm, s_idx, r_pos in zip(perms, sends, recvs):
                    got = jax.lax.ppermute(
                        buf[s_idx[0]], WORKER_AXIS, perm
                    )
                    halo = halo.at[r_pos[0]].set(got)
                return jnp.concatenate([buf, halo], axis=0)

            return body(exchange, nbr_lb[0], maskb[0], *blocks)

        table_spec = P_(WORKER_AXIS, None, None)
        step_spec = P_(WORKER_AXIS, None)
        arr_specs = tuple(
            P_(WORKER_AXIS, *([None] * (a.ndim - 1))) for a in arrays
        )
        return shard_map(
            shard_body,
            mesh=self.mesh,
            in_specs=(table_spec, table_spec)
            + tuple(step_spec for _ in range(2 * n_steps))
            + arr_specs,
            out_specs=P_(WORKER_AXIS, None),
        )(self.nbr_l, self.mask, *self.sends, *self.recvs, *arrays)


def make_halo_exchange(
    topo: Topology, mesh: Mesh, *, overlap: str = "off"
) -> HaloExchange:
    """Build the device-ready halo plan for a topology over a 1-D mesh.

    ``overlap`` names the exchange form the plan serves (it is part of
    the plan's memoization identity — see ``build_halo_plan``); the
    device arrays are identical across modes today.
    """
    n_devices = mesh.shape[WORKER_AXIS]
    nbr_idx, nbr_mask = neighbor_tables_for(topo)
    if topo.n % n_devices:
        raise ValueError(
            f"n_workers={topo.n} not divisible by mesh size {n_devices}"
        )
    plan = build_halo_plan(
        nbr_idx, nbr_mask, n_devices, sampler=topo.sampler, overlap=overlap
    )
    S, k_max = plan.shard_rows, nbr_idx.shape[1]
    return HaloExchange(
        mesh=mesh,
        plan=plan,
        nbr_l=jnp.asarray(
            plan.local_nbr.reshape(n_devices, S, k_max), dtype=jnp.int32
        ),
        mask=jnp.asarray(
            nbr_mask.reshape(n_devices, S, k_max), dtype=jnp.float32
        ),
        sends=tuple(
            jnp.asarray(st.send_idx, dtype=jnp.int32) for st in plan.steps
        ),
        recvs=tuple(
            jnp.asarray(st.recv_pos, dtype=jnp.int32) for st in plan.steps
        ),
        perms=tuple(
            tuple((p, (p + st.rotation) % n_devices)
                  for p in range(n_devices))
            for st in plan.steps
        ),
    )


def make_halo_mixing_op(
    topo: Topology, mesh: Mesh, dtype=jnp.float32, *, overlap: str = "off"
) -> MixingOp:
    """The worker mesh's mixing operator: how a shard reaches its
    neighbours' rows is read off the neighbor table, by no option and no
    test of the topology's name (the rule PR 33 wrote for the unsharded
    fault layer, ``topology._table_is_a_ring``). The ``dopt.run`` root's
    ``mixing`` says which form a call took.

    ``halo_shift`` — the table IS a ring's: a block's neighbours are its
    own rows one up and one down, and its two boundary rows' neighbours
    arrive by two one-row ``ppermute``s (``_ring_block_mix``, the body
    ``mixing_impl='shard_map'`` runs too), ``w · (x + left + right)`` with
    w = 1/3 as the one-chip stencil writes it, on the stack in the rank
    the scan carries. No ``HaloExchange``, no per-shard neighbor table
    and no weights table is built or closed over: at 262,144 rows a
    device the gather form's ``s32[4, 262144, 2]`` table was 0.54 GB of
    every chip's executable, and its 524,288-index row gather 5.2 of
    25.3 ms an iteration where the shifts are 0.3 on one chip (PERF.md
    sections 5 and 6, PR 35). The permutes depend on nothing local, so
    ``overlap`` has nothing to reorder: 'off' and 'double_buffer' are
    one program here.

    ``halo_gather`` — every other table (chain, torus, Erdős–Rényi, a
    ring whose slots are ordered another way): ``_make_halo_gather_mixing_op``.
    """
    if topo.directed:
        raise ValueError(
            "halo gather mixing is undirected-only (MH weights per slot); "
            f"directed topology {topo.name!r} has no gather form"
        )
    if overlap not in ("off", "double_buffer"):
        raise ValueError(f"Unknown halo overlap mode: {overlap!r}")
    n_devices = mesh.shape[WORKER_AXIS]
    if topo.n % n_devices:
        raise ValueError(
            f"n_workers={topo.n} not divisible by mesh size {n_devices}"
        )
    if not _table_is_a_ring(topo):
        return _make_halo_gather_mixing_op(topo, mesh, dtype, overlap=overlap)
    mix_block, nbr_block = _ring_block_mix(WORKER_AXIS, n_devices, 1.0 / 3.0)
    return MixingOp(
        topo.name,
        "halo_shift",
        _over_row_blocks(mix_block, mesh),
        _over_row_blocks(nbr_block, mesh),
    )


def _make_halo_gather_mixing_op(
    topo: Topology, mesh: Mesh, dtype=jnp.float32, *, overlap: str = "off"
) -> MixingOp:
    """Sharded twin of ``ops/mixing.py`` impl='gather' over real collectives.

    MH weights are the identical per-slot values ``gather_mixing_weights``
    derives (sharded per block, slot-major: ``_slot_major_blocks``); the
    apply/neighbor_sum bodies call the single-device gather operator's own
    ``ops.mixing.slot_sum`` on the halo-extended buffer (slot by slot, one
    row gather in flight, no ``[S, k_max, d]`` stack: ISSUE 36), so the
    two forms are equal to the last
    place or two (two executables: see the section comment above) — with
    boundary rows arriving over ICI as ppermute traffic instead of being
    addressed in one device's HBM. On the chip the gather is the cost
    (priced by its indices, whatever each fetches), which is why a ring's
    table never comes here (``make_halo_mixing_op``). What a round lowers
    to at k_max = 4, a 1024 x 1024 torus over four v5e (PERF.md section 5,
    PR 52's traced run): the block copied row-major (the scan carries it
    worker-minor) and back at the end, two 1,024-row gathers for the
    sends, two ``collective-permute``s of ``f32[1024,81]``, two scatters
    into the ``[2049, 81]`` halo, the concatenate as a pad-and-maximum
    ``f32[264193,81]``, then PER SLOT an index clamp, one row gather
    ``fusion f32[262144,81]`` (2.54 ms each: 10.2 of the round's 12.4 ms)
    and one ``multiply_add_fusion`` (0.44 ms), the first slot's written
    out and the other three inside ``slot_sum``'s ``while``; the permutes
    themselves lie under the ten rows a trace reader is handed.

    ``overlap='double_buffer'`` (config.halo_overlap; docs/PERF.md §17)
    restructures ``apply`` into the stencil latency-hiding form: the
    boundary-row ppermutes are issued FIRST, the self + in-block partial
    sum computes while they are in flight (XLA schedules collectives
    concurrently with independent compute on async backends), and the
    halo contributions are added last. The summation ORDER differs from
    the gather body (in-block slots before halo slots instead of slot
    order: two ``slot_sum``s over the table, the halo's slots weighing 0
    in the first and the block's in the second), so double_buffer is a
    distinct structural program — NOT bitwise vs off; 'off' is the
    one-device round's op sequence, which is the gate
    tests/test_worker_mesh.py pins.
    """
    hx = make_halo_exchange(topo, mesh, overlap=overlap)
    nbr_sm, w_nbr, w_self = _slot_major_blocks(hx, topo, dtype)
    S = hx.plan.shard_rows

    # The slot-major blocks ride ``HaloExchange.run`` as ordinary arrays
    # split on their leading (shard) axis: each body sees its
    # ``[1, k_max, S]`` block — no second copy of the shard_map/exchange
    # plumbing to keep in sync. The plan's own node-major table and mask
    # go unread here.
    def apply(x: jax.Array) -> jax.Array:
        def body(exchange, _nbr_l, _mask_f32, nb, wn, ws, xb):
            out = ws[:, None] * xb + slot_sum(exchange(xb), nb[0], wn[0])
            return out.astype(xb.dtype)

        x2 = x.reshape(x.shape[0], -1)
        return hx.run(body, nbr_sm, w_nbr, w_self, x2).reshape(x.shape)

    def apply_overlap(x: jax.Array) -> jax.Array:
        def body(exchange, _nbr_l, _mask_f32, nb, wn, ws, xb):
            nb, wn = nb[0], wn[0]
            # Issue every boundary-row send before touching the local
            # math: the in-block partial sum has no data dependence on
            # the permutes, so an async backend's scheduler runs the
            # collectives concurrently with it (CPU single-stream ties).
            halo = exchange(xb)[S:]
            in_block = nb < S
            none = jnp.zeros((), wn.dtype)
            partial = ws[:, None] * xb + slot_sum(
                xb, jnp.where(in_block, nb, 0), jnp.where(in_block, wn, none)
            )
            out = partial + slot_sum(
                halo, jnp.where(in_block, 0, nb - S),
                jnp.where(in_block, none, wn),
            )
            return out.astype(xb.dtype)

        x2 = x.reshape(x.shape[0], -1)
        return hx.run(body, nbr_sm, w_nbr, w_self, x2).reshape(x.shape)

    def neighbor_sum(x: jax.Array) -> jax.Array:
        def body(exchange, _nbr_l, _mask_f32, nb, wn, xb):
            # A live slot's weight is positive, a padded one's 0: the mask.
            return slot_sum(
                exchange(xb), nb[0], wn[0],
                lambda w: (w > 0).astype(xb.dtype),
            ).astype(xb.dtype)

        x2 = x.reshape(x.shape[0], -1)
        return hx.run(body, nbr_sm, w_nbr, x2).reshape(x.shape)

    return MixingOp(
        topo.name,
        "halo_gather",
        apply_overlap if overlap == "double_buffer" else apply,
        neighbor_sum,
        # Every device array the operators read, whole (all P shards'):
        # with no ``bind`` they are constants of whatever program closes
        # over this op, and the ``dopt.run`` root says what they take
        # (``halo_table_bytes``, ``halo_tables`` = ``constant``).
        tables={
            "nbr": nbr_sm, "w_nbr": w_nbr, "w_self": w_self,
            "send": hx.sends, "recv": hx.recvs,
        },
    )


def _slot_major_blocks(hx: HaloExchange, topo: Topology, dtype):
    """A halo plan's shard-local neighbor table and the MH weights of
    ``gather_mixing_weights`` as per-shard SLOT-MAJOR blocks, ``nbr`` s32
    and ``w_nbr`` ``[P, k_max, S]`` and ``w_self`` ``[N]``: what
    ``ops.mixing.slot_sum`` reads on one device, so that a block's round
    is the one-device round's per-row op sequence."""
    nbr_idx, nbr_mask = neighbor_tables_for(topo)
    w_nbr_np, w_self_np = gather_mixing_weights(
        nbr_idx, nbr_mask, topo.degrees
    )
    P_n, S = hx.n_shards, hx.plan.shard_rows

    def blocks(table):
        return np.asarray(table).reshape(P_n, S, -1).transpose(0, 2, 1)

    return (
        jnp.asarray(blocks(hx.plan.local_nbr), dtype=jnp.int32),
        jnp.asarray(blocks(w_nbr_np), dtype=dtype),
        jnp.asarray(w_self_np, dtype=dtype),
    )


def make_halo_compressed_mixing_op(topo: Topology, mesh: Mesh, dtype=jnp.float32):
    """Compressed halo exchange: ship only the CHOCO increment's boundary rows.

    Returns ``compressed_mix(q, xhat_new, halo) -> (mixed, halo_new)`` for
    ``ops/compression.py::ErrorFeedbackGossip.exchange_sharded``: ``q`` is
    the compressed increment (row-sharded [N, d]), ``xhat_new = x̂ + q`` the
    already-updated local estimate, and ``halo`` the persistent receiver-side
    copy of the NEIGHBORS' estimate rows ([P·(h_max+1), d] row-sharded —
    h_max+1 rows per shard, the trailing one the dump row padded traffic
    lands in). One round ppermutes only the boundary rows of ``q`` and
    scatter-ADDS them into ``halo`` — the receiver replays the owner's
    ``x̂ ← x̂ + q`` update on its copy, the wire form Koloskova et al. '19
    rely on — then gathers the MH mix from the [block | halo] extension.

    Starting from the all-zeros halo the backend seeds, the receiver copy
    equals the owner row by induction (identical float adds on identical
    values), so ``mixed`` is bitwise the gather-form mix of the exact
    owner estimates. End-to-end sharded-vs-unsharded trajectories are
    BITWISE equal for the deterministic compressors (top_k — pinned by
    tests/test_worker_mesh.py); qsgd's stochastic rounding thresholds sit
    on a row-norm reduction XLA may fuse differently across the two
    compiled programs, so its parity gate is ~1e-12, not bitwise (the
    same caveat every cross-program reduction in this repo carries). The
    dump row is re-zeroed every round so padded-slot traffic (whose
    scatter-add order XLA does not define when several padded sends land
    together) can never leak into state.

    Wire accounting: physically each ppermute still ships dense-width rows
    (the analytic convention every comms number in this repo uses);
    ``telemetry.ici_summary`` prices the rows at the compressor's
    ``floats_per_edge`` — that is the committed byte cut in
    docs/perf/mesh_scale.json.
    """
    if topo.directed:
        raise ValueError(
            "compressed halo mixing is undirected-only (MH weights per "
            f"slot); directed topology {topo.name!r} has no gather form"
        )
    hx = make_halo_exchange(topo, mesh)
    nbr_sm, w_nbr, w_self = _slot_major_blocks(hx, topo, dtype)
    h_max = hx.plan.h_max
    n_steps = len(hx.perms)
    perms = hx.perms
    halo_rows = mesh.shape[WORKER_AXIS] * (h_max + 1)

    def compressed_mix(q: jax.Array, xhat_new: jax.Array, halo: jax.Array):
        P_ = jax.sharding.PartitionSpec

        def shard_body(nbr_lb, wn, ws, qb, xb, hb, *steps):
            sends = steps[:n_steps]
            recvs = steps[n_steps:]
            hnew = hb
            for perm, s, r in zip(perms, sends, recvs):
                got = jax.lax.ppermute(qb[s[0]], WORKER_AXIS, perm)
                hnew = hnew.at[r[0]].add(got)
            # Padded steps all target the dump row; several adds landing
            # there have no defined order — zero it so nothing leaks.
            hnew = hnew.at[h_max].set(jnp.zeros((), hnew.dtype))
            ext = jnp.concatenate([xb, hnew], axis=0)
            out = ws[:, None] * xb + slot_sum(ext, nbr_lb[0], wn[0])
            return out.astype(xb.dtype), hnew

        q2 = q.reshape(q.shape[0], -1)
        x2 = xhat_new.reshape(xhat_new.shape[0], -1)
        h2 = halo.reshape(halo_rows, -1)
        table_spec = P_(WORKER_AXIS, None, None)
        step_spec = P_(WORKER_AXIS, None)
        mixed, halo_new = shard_map(
            shard_body,
            mesh=mesh,
            in_specs=(table_spec, table_spec, P_(WORKER_AXIS),
                      step_spec, step_spec, step_spec)
            + tuple(step_spec for _ in range(2 * n_steps)),
            out_specs=(P_(WORKER_AXIS, None), P_(WORKER_AXIS, None)),
        )(nbr_sm, w_nbr, w_self, q2, x2, h2, *hx.sends, *hx.recvs)
        return mixed.reshape(xhat_new.shape), halo_new.reshape(halo.shape)

    compressed_mix.halo_rows = halo_rows
    return compressed_mix


def make_halo_robust_aggregator_t(
    name: str,
    budget: int,
    topo: Topology,
    mesh: Mesh,
    clip_tau: float = 0.0,
    active_fn=None,
):
    """Sharded robust screening: ``aggregate_t(t, x) -> x_new`` over the halo.

    The degree-bounded gather rules of ``ops/robust_aggregation.py``
    (coordinate-wise trimmed mean / median, self-centered clipping) run
    shard-locally on the halo-extended buffer: corrupted boundary rows
    arrive over ppermute exactly like benign gossip traffic, each shard
    screens its own closed neighborhoods of k_max + 1 slots, and the two
    count rules ARE the unsharded gather form's
    (``closed_neighbourhood_rule``, called on the block and its halo;
    clipping mirrors its twin term for term) — sharded-vs-unsharded
    screening is BITWISE identical.
    ``active_fn(t) -> [N] float32`` composes node-process faults
    (stragglers/churn/participation) into the realized liveness through a
    1-float-per-row halo exchange; None = the static graph. The caller
    (``jax_backend._bind_byzantine``) applies the adversary's corruption
    BEFORE this aggregate, like every other robust binding.
    """
    from distributed_optimization_tpu.config import AGGREGATIONS
    from distributed_optimization_tpu.ops.robust_aggregation import (
        _adaptive_clip_tau,
        closed_neighbourhood_rule,
    )

    if name not in AGGREGATIONS or name == "gossip":
        raise ValueError(
            f"no robust aggregator named {name!r}; plain gossip is the "
            "halo mixing op itself"
        )
    if budget < 1:
        raise ValueError(f"{name} needs a positive attack budget, got {budget}")
    hx = make_halo_exchange(topo, mesh)
    nbr_idx, _ = neighbor_tables_for(topo)
    k_max = nbr_idx.shape[1]
    n = topo.n
    adaptive_tau = isinstance(clip_tau, (int, float)) and clip_tau <= 0.0

    def _live(exchange, nbr_l, mask_f32, mb):
        m_ext = exchange(mb[:, None])[:, 0]
        return mask_f32 * mb[:, None] * m_ext[nbr_l]  # [S, k_max] f32

    if name in ("trimmed_mean", "median"):
        # The unsharded gather form's own definition, on the block and its
        # halo: the terms the BITWISE sharded-vs-unsharded contract rests
        # on live in one place.
        rule = closed_neighbourhood_rule(name, budget)

        def body(exchange, nbr_l, mask_f32, xb, mb):
            acc = jnp.promote_types(jnp.float32, xb.dtype)
            xa = xb.astype(acc)
            lv = _live(exchange, nbr_l, mask_f32, mb).astype(acc)
            return rule(xa, exchange(xa), nbr_l, lv).astype(xb.dtype)

    else:  # clipped_gossip

        def body(exchange, nbr_l, mask_f32, xb, mb):
            acc = jnp.promote_types(jnp.float32, xb.dtype)
            xa = xb.astype(acc)
            lv = _live(exchange, nbr_l, mask_f32, mb).astype(acc)
            deg = jnp.sum(lv, axis=1)
            d2 = xa.shape[-1]
            ext = exchange(jnp.concatenate([xa, deg[:, None]], axis=1))
            gathered = ext[nbr_l]
            diffs = gathered[:, :, :d2] - xa[:, None, :]
            norms = jnp.sqrt(jnp.sum(diffs * diffs, axis=-1))
            if not adaptive_tau:
                tau = jnp.full(xb.shape[0], clip_tau, dtype=acc)
            else:
                tau = _adaptive_clip_tau(lv, norms, budget, k_max)
            w = lv / (1.0 + jnp.maximum(deg[:, None], gathered[:, :, d2]))
            factor = jnp.minimum(
                1.0, tau[:, None] / jnp.maximum(norms, jnp.finfo(acc).tiny)
            )
            moved = jnp.sum(
                w[:, :, None] * diffs * factor[:, :, None], axis=1
            )
            return (xa + moved).astype(xb.dtype)

    def aggregate_t(t, x):
        m = (
            active_fn(t) if active_fn is not None
            else jnp.ones(n, dtype=jnp.float32)
        )
        # The body screens over one parameter axis: flatten at the boundary.
        return hx.run(body, x.reshape(n, -1), m).reshape(x.shape)

    return aggregate_t
