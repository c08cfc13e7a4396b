"""Compiled gossip/mixing operators: x -> W x and neighbor sums x -> A x.

The reference realizes gossip as a dense ``W @ models`` matmul in numpy
(reference ``trainer.py:173``) — a *simulation* of communication. Here the
same linear operator has three interchangeable compiled forms on one
device or an auto (GSPMD) mesh:

- ``dense``: an on-device matmul with the [N, N] mixing matrix. Works for any
  graph (Erdős–Rényi et al.). Under GSPMD sharding this becomes an
  all-gather + local contraction — fine for irregular graphs.
- ``stencil``: for ring / torus / fully-connected graphs, where MH weights are
  uniform by symmetry, W x is a weighted sum of circular shifts of x along the
  worker axis (ring: ±1; torus: ±1 along each grid axis; fc: the global mean).
  When x is sharded over the mesh, XLA compiles ``jnp.roll`` on the sharded
  axis into ``CollectivePermute`` over ICI and the fc mean into an
  ``AllReduce`` — the communication graph maps onto the pod topology, which is
  the north-star design (SURVEY.md §5.8).
- ``gather`` (round 9): the matrix-free k_max-bounded form over padded
  neighbor tables — O(E·d), no [N, N] object anywhere; the route that
  lifts the worker axis to N ≥ 10k, and the one every graph that is not
  a shift takes there (Erdős–Rényi, chain). A round sums over the LIVE
  slots only (PR 38): a padded slot points at the row itself, weighs 0
  and would be fetched and multiplied like any other (six rows in ten on
  a drawn graph of mean degree 12 in a table 30 wide), so the host lays
  the table's live (slot, row) pairs out once a graph
  (``topology.live_slot_chunks``, kept with the kept graph as
  ``Topology.gather_chunks``): rows in order of falling degree, where
  slot s is live on a PREFIX of the rows and nowhere else, slot after
  slot, each slot's run cut into chunks of one length C
  (``topology.gather_chunk_rows``: derived from N, a small graph's chunk
  the whole row axis), a run's last chunk filled with entries of weight
  0 as padding is. The tables are ONE pytree, ``MixingOp.tables``:
  ``nbr`` s32 and ``w_nbr`` ``[n_chunks, C]`` (the neighbours in the
  workers' own numbering: x is gathered as it is carried), ``row0`` s32
  ``[n_chunks]`` (each chunk's first row in the degree order, read by
  trip number), ``w_self`` ``[N]`` in the workers' order and ``inverse``
  s32 ``[N]`` (each worker's place in the degree order; left out where
  that order is the workers' own, a regular graph). The operators are
  built over a copy of the pytree by ``MixingOp.bind``:
  ``jax_backend._run`` hands the tables to its scan as ARGUMENTS
  (``data['mixing']``) and rebinds, so they are never constants of the
  executable (a node-major ``s32[262144, 30]`` constant is 134 MB in the
  TPU's (8, 128) tiles; PERF.md section 6, PR 36). A round is
  ``w_self·x + acc[inverse]`` (``live_slot_sum``): the first chunk's
  term starts the accumulator, then ONE ``lax.scan`` over the other
  chunks, its body one row gather of C rows whose weighted rows are
  added into rows ``[row0, row0 + C)`` of the accumulator in place (the
  accumulator kept ``[N/C, C, ...]``, a chunk indexing its block: one
  fused pass on the TPU), then one gather of N rows that puts the sums
  back in the workers' order (skipped without ``inverse``). Never the
  ``[N, k_max, ...]`` stack (at N = 2^18, k_max = 30, d = 81 that stack
  and its product are 8.3 GB of the chip's tiles), one loop whatever
  k_max and whatever the degrees, and per row the terms are added in the
  table's slot order: the sums are those of ``slot_sum``, the loop over
  the padded slot-major ``[k_max, n]`` tables that the sharded twin's
  blocks run, so sharded and unsharded agree to the bit.
  ``neighbor_sum`` reads the mask off the weights (a live slot's is
  positive), so no mask table exists. A caller that binds nothing gets
  the operators over ``tables`` as they are (constants of whatever it
  traces). Its
  SHARDED twin is ``parallel/collectives.make_halo_mixing_op`` (impl tag
  ``'halo_gather'``, the ``worker_mesh`` axis, docs/PERF.md §16): the
  same weights over per-shard tables with the worker rows split over a
  device mesh and boundary rows ppermute-fetched at shard edges —
  selected by the backend (not here) because it needs the device mesh
  (a ring's table, and a torus's cut by whole grid rows, takes that
  builder's ``'halo_shift'`` instead: shifts, no table).

All forms agree to floating-point tolerance; property tests check the
stencil, gather and halo forms against the dense matrix.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from distributed_optimization_tpu.config import MATRIX_FREE_AUTO_N
from distributed_optimization_tpu.parallel.topology import (
    NEIGHBOR_TABLE_MAX_CELLS,
    Topology,
)

MixFn = Callable[[jax.Array], jax.Array]


def _col(v: jax.Array, x: jax.Array) -> jax.Array:
    return v.reshape((-1,) + (1,) * (x.ndim - 1))


def slot_sum(src: jax.Array, nbr: jax.Array, w_nbr: jax.Array,
             weight=None) -> jax.Array:
    """Σ_s weight(w_nbr[s])·src[nbr[s]] over slot-major ``[k_max, n]``
    tables, slot by slot in the table's order, padded slots included: ONE
    row gather of ``[n, ...]`` in flight, the ``[n, k_max, ...]`` stack of
    every neighbour's row never made. The first slot's term, then a loop
    over the rest, whose body XLA cannot reorder into k_max gathers held at
    once. The halo gather's per-shard blocks run it
    (``parallel/collectives.py``); ``src`` may hold more rows than ``n`` (a
    shard's block with its halo behind it); ``weight`` maps a slot's
    weights (``neighbor_sum``'s mask), the identity where None."""

    def term(idx, w):
        return _col(w if weight is None else weight(w), src) * src[idx]

    def add_slot(acc, slot):
        return acc + term(*slot), None

    total, _ = jax.lax.scan(
        add_slot, term(nbr[0], w_nbr[0]), (nbr[1:], w_nbr[1:])
    )
    return total


def live_slot_sum(x: jax.Array, tb: dict, weight=None) -> jax.Array:
    """Σ_s weight(w_nbr)·x[nbr] over the LIVE slots alone, read from the
    chunk list ``topology.live_slot_chunks`` lays out (module docstring):
    the first chunk's term, then one loop over the rest, one row gather of
    ``C`` rows in its body, the weighted rows added in place into the
    accumulator's rows ``[row0, row0 + C)`` (rows in the degree order),
    then the sums put back in the workers' order. Per row the terms are
    added in the table's slot order: ``slot_sum``'s result. ``row0`` is read
    by trip number, so a list cut short runs the trips it has.

    The accumulator is kept ``[N/C, C, ...]`` and a chunk indexes its BLOCK
    of rows, ``row0 // C``: an index on the leading axis is aligned to the
    device's tiles whatever its value, so XLA:TPU fuses the sum and the
    update into one pass over the block in place; as an offset into the
    rows of ``[N, ...]`` it is not known to be, and the update was a copy
    of its own after the sum (5.8 of 39.9 ms a round at the drawn-graph
    cell's size, PERF.md section 6, PR 38)."""
    nbr, row0 = tb["nbr"], tb["row0"]
    w_nbr = tb["w_nbr"] if weight is None else weight(tb["w_nbr"])
    n, chunk = x.shape[0], nbr.shape[1]
    blocks = -(-n // chunk)

    def term(idx, w):
        return _col(w, x) * x[idx]

    def add_chunk(acc, trip):
        t, idx, w = trip
        block = row0[t] // chunk
        rows = jax.lax.dynamic_index_in_dim(acc, block, 0, keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(
            acc, rows + term(idx, w), block, 0
        ), None

    # The list's first chunk is slot 0's first, block 0: its term starts the
    # accumulator, as ``slot_sum``'s first slot starts its sum (where a
    # chunk is the whole row axis the two loops are one program).
    first = term(nbr[0], w_nbr[0])
    acc = jnp.zeros((blocks,) + first.shape, first.dtype)
    acc, _ = jax.lax.scan(
        add_chunk, jax.lax.dynamic_update_index_in_dim(acc, first, 0, 0),
        (jnp.arange(1, nbr.shape[0]), nbr[1:], w_nbr[1:]),
    )
    acc = acc.reshape((blocks * chunk,) + first.shape[1:])
    return acc[tb["inverse"]] if "inverse" in tb else acc[:n]


@dataclasses.dataclass(frozen=True)
class MixingOp:
    """Jittable linear operators attached to one topology.

    ``apply``: x [N, ...] -> W x (the gossip averaging step).
    ``neighbor_sum``: x [N, ...] -> A x (sum over graph neighbors; used by
    ADMM-family algorithms whose updates need Σ_{j∈N(i)} x_j rather than the
    doubly-stochastic average).
    """

    topology_name: str
    impl: str
    apply: MixFn
    neighbor_sum: MixFn
    # The gather form only, else None: ``tables`` is the pytree of every
    # device array its operators read (the live slots' chunk list, module
    # docstring), and
    # ``bind(tables_like)`` rebuilds this op over a same-structured pytree
    # — the leaves a compiled program receives ``tables`` as.
    # ``jax_backend._run`` hands the tables to its scan as arguments and
    # rebinds inside it; ``FaultyMixing`` has the same pair.
    tables: Optional[dict] = None
    bind: Optional[Callable[[dict], "MixingOp"]] = None


def _supports_stencil(topo: Topology) -> bool:
    if topo.name == "fully_connected":
        return True
    if topo.name in ("ring", "directed_ring"):
        return topo.n >= 3
    if topo.name == "grid":
        return topo.grid_shape is not None and min(topo.grid_shape) >= 3
    return False


def make_mixing_op(topo: Topology, impl: str = "auto", dtype=jnp.float32) -> MixingOp:
    """Build the compiled mixing operator for a topology.

    ``impl``: 'auto' picks 'stencil' where the graph embeds into the mesh as
    shifts (ring/grid/fc), 'gather' for a matrix-free graph that is not one
    and for a large degree-bounded irregular one, else 'dense' (for a small
    irregular graph the [N, N] matmul beat an edge-list contraction at every
    size one chip holds: docs/PERF.md, "Pre-ledger history"). The worker
    mesh's forms are built in ``parallel/collectives.py``: they need a Mesh.
    """
    if impl == "auto":
        if _supports_stencil(topo):
            # Stencils are already matrix-free (rolls/means of the whole
            # block) and the measured winner where they apply.
            impl = "stencil"
        elif topo.is_matrix_free:
            impl = "gather"
        elif not topo.directed and topo.n >= MATRIX_FREE_AUTO_N:
            # The k_max-bounded gather route (docs/PERF.md §14): default
            # for matrix-backed irregular graphs above the measured
            # threshold — the [N, N] contraction's O(N²·d) work and the
            # matrix itself stop fitting where docs/perf/federated.json's
            # scale cells take over (docs/PERF.md §14). Gate on
            # the SAME degree bound build_neighbor_topology enforces:
            # gather's [N, k_max, d] transient beats dense only while
            # k_max ≪ N, so high-degree graphs (star, dense ER) keep the
            # dense contraction instead of allocating a near-quadratic
            # gather inside the scan.
            k_max = int(np.asarray(topo.degrees).max())
            degree_bounded = (
                k_max + 1 < topo.n
                and max(k_max, 1) * topo.n <= NEIGHBOR_TABLE_MAX_CELLS
            )
            impl = "gather" if degree_bounded else "dense"
        else:
            impl = "dense"
    if impl not in ("dense", "stencil", "gather"):
        raise ValueError(f"Unknown mixing impl: {impl!r}")
    if impl == "stencil" and not _supports_stencil(topo):
        raise ValueError(f"stencil mixing unsupported for {topo.name} (n={topo.n})")
    if topo.is_matrix_free and impl not in ("stencil", "gather"):
        raise ValueError(
            f"mixing_impl={impl!r} consumes the dense [N, N] matrices a "
            f"matrix-free topology ({topo.name}, n={topo.n}) never "
            "materializes — use 'gather' (or 'stencil' where the graph "
            "embeds as shifts)"
        )

    if impl == "gather":
        if topo.directed:
            raise ValueError(
                "gather mixing is undirected-only (MH weights per slot); "
                f"directed topology {topo.name!r} has no gather form"
            )
        # int32 indices as they are; the float64 weights in ``dtype``
        tables = {
            key: jnp.asarray(leaf, dtype=None if leaf.dtype == np.int32 else dtype)
            for key, leaf in topo.gather_chunks.items()
        }
        name = topo.name

        def bind(tb) -> MixingOp:
            w_self = tb["w_self"]

            def apply(x: jax.Array) -> jax.Array:
                out = _col(w_self, x) * x + live_slot_sum(x, tb)
                return out.astype(x.dtype)

            def neighbor_sum(x: jax.Array) -> jax.Array:
                # A live slot's MH weight is 1/(1 + max degree) > 0 and a
                # padded one's is 0: the mask, without a table of its own.
                return live_slot_sum(
                    x, tb, lambda w: (w > 0).astype(x.dtype)
                ).astype(x.dtype)

            return MixingOp(
                name, "gather", apply, neighbor_sum, tables=tables, bind=bind
            )

        return bind(tables)

    if impl == "dense":
        W = jnp.asarray(topo.mixing_matrix, dtype=dtype)
        A = jnp.asarray(topo.adjacency, dtype=dtype)

        def apply(x: jax.Array) -> jax.Array:
            return jnp.tensordot(W, x, axes=1).astype(x.dtype)

        def neighbor_sum(x: jax.Array) -> jax.Array:
            return jnp.tensordot(A, x, axes=1).astype(x.dtype)

        return MixingOp(topo.name, "dense", apply, neighbor_sum)

    if topo.name == "fully_connected":
        # Degree N-1 everywhere ⇒ every MH weight (incl. diagonal) is 1/N:
        # mixing is exactly the global mean. Compiles to AllReduce when sharded.
        def apply(x: jax.Array) -> jax.Array:
            return jnp.broadcast_to(jnp.mean(x, axis=0, keepdims=True), x.shape).astype(
                x.dtype
            )

        def neighbor_sum(x: jax.Array) -> jax.Array:
            return (jnp.sum(x, axis=0, keepdims=True) - x).astype(x.dtype)

        return MixingOp(topo.name, "stencil", apply, neighbor_sum)

    if topo.name == "ring":
        # Degree 2 everywhere ⇒ all weights (self and both neighbors) are 1/3.
        w = 1.0 / 3.0

        def apply(x: jax.Array) -> jax.Array:
            return (w * (x + jnp.roll(x, 1, axis=0) + jnp.roll(x, -1, axis=0))).astype(
                x.dtype
            )

        def neighbor_sum(x: jax.Array) -> jax.Array:
            return (jnp.roll(x, 1, axis=0) + jnp.roll(x, -1, axis=0)).astype(x.dtype)

        return MixingOp(topo.name, "stencil", apply, neighbor_sum)

    if topo.name == "directed_ring":
        # Out-degree 1 everywhere ⇒ column-stochastic weights are 1/2 on the
        # self-loop and the forward edge: (Ax)_i = (x_i + x_{i-1})/2. ONE
        # roll — when sharded this is a single forward CollectivePermute per
        # round, half the undirected ring's boundary traffic.
        def apply(x: jax.Array) -> jax.Array:
            return (0.5 * (x + jnp.roll(x, 1, axis=0))).astype(x.dtype)

        def neighbor_sum(x: jax.Array) -> jax.Array:
            return jnp.roll(x, 1, axis=0).astype(x.dtype)

        return MixingOp(topo.name, "stencil", apply, neighbor_sum)

    if topo.name == "grid":
        rows, cols = topo.grid_shape  # type: ignore[misc]
        # Degree 4 everywhere ⇒ all five weights are 1/5. Worker i lives at
        # grid position (i // cols, i % cols) — row-major, matching the
        # reference's node indexing (trainer.py:104).
        w = 1.0 / 5.0

        def _shifts(x: jax.Array) -> jax.Array:
            g = x.reshape(rows, cols, *x.shape[1:])
            s = (
                jnp.roll(g, 1, axis=0)
                + jnp.roll(g, -1, axis=0)
                + jnp.roll(g, 1, axis=1)
                + jnp.roll(g, -1, axis=1)
            )
            return s.reshape(x.shape)

        def apply(x: jax.Array) -> jax.Array:
            return (w * (x + _shifts(x))).astype(x.dtype)

        def neighbor_sum(x: jax.Array) -> jax.Array:
            return _shifts(x).astype(x.dtype)

        return MixingOp(topo.name, "stencil", apply, neighbor_sum)

    raise ValueError(f"No stencil form for topology {topo.name!r}")
