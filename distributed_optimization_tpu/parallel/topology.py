"""Communication-graph topologies and Metropolis-Hastings mixing matrices.

Capability parity with the reference's topology + mixing-matrix builder
(reference ``trainer.py:91-136``): ring, periodic 2-D grid (torus), and
fully-connected graphs with Metropolis-Hastings gossip weights
``W_ij = 1/(1 + max(deg_i, deg_j))`` and self-weight = row remainder, plus the
same invariants (row-stochastic, symmetric) and the spectral gap ``1 - ρ``
from the second-largest absolute eigenvalue.

Extensions beyond the reference: Erdős–Rényi random graphs (the BASELINE.json
decentralized-ADMM config), chain (path), and star topologies; and a
*stencil* description (shift offsets + weights) for the topologies whose
mixing step maps onto TPU ICI as `ppermute` neighbor shifts instead of a dense
``W @ models`` matmul — ring/chain/torus are the cases where the communication
graph embeds directly into the pod mesh.

Round 4 adds DIRECTED graphs (``directed_ring``, ``directed_erdos_renyi``)
with column-stochastic uniform-out-weight mixing — the push-sum/SGP setting
(Nedić-Olshevsky 2016; Assran et al. 2019), where Metropolis-Hastings gossip
is undefined because asymmetric links admit no symmetric doubly stochastic
weight assignment. Convention: ``adjacency[i, j] = 1`` iff j sends to i
(row i = who i RECEIVES from), so ``mixing_matrix @ x`` aggregates received
messages for both directed and undirected graphs. The directed ring is the
ICI-friendly case: one gossip round is a single forward ``ppermute`` — half
the undirected ring's boundary traffic.

This module is host-side (numpy): topologies are built once per run, outside
``jit``. The compiled mixing operators that consume them live in
``ops/mixing.py`` and ``parallel/collectives.py``.

Round 8 adds the MATRIX-FREE representation (``build_topology(...,
impl='neighbor')``): ring/torus/chain/Erdős–Rényi built directly as a
static padded ``[N, k_max]`` neighbor table — the dense ``[N, N]``
adjacency and mixing matrix are never materialized (``adjacency`` /
``mixing_matrix`` are None; at N = 10k the dense float64 pair alone is
~1.6 GB; the pre-ledger dense-mixing measurements stop around N≈4k,
docs/PERF.md "Pre-ledger history").
Everything downstream that needs the graph reads the table: gather-form
MH mixing (``gather_mixing_weights`` + ``ops/mixing.py`` impl='gather',
O(N·k_max·d) per round), node-process fault composition
(``parallel/faults.py``), and the spectral gap via closed forms or
matrix-free power iteration. The ER constructor consumes the numpy
Generator stream row-by-row in exactly the order the dense sampler's one
``random((n, n))`` call does, so both representations of G(n, p, seed)
realize the IDENTICAL graph.

The million-worker round adds the SPARSE sampler
(``build_neighbor_topology(..., sampler='sparse')``): the bit-identical
ER constructor above replays the dense [N, N] uniform stream and is
therefore O(N²) draws — the recorded reason ER-at-100k was skipped in
docs/perf/worker_mesh.json. The sparse sampler draws O(N·k_max):
per-node forward-degree Binomial(n−1−i, p) counts, tail-sampled
partners, global dedupe + bounded top-up, and vectorized min-label
connectivity — the SAME G(n, p) law, a DIFFERENT realization per
(seed, p), so the sampler's identity is structural
(``config.structural_dict()['topology_sampler']``). Ring/torus/chain
tables are built by vectorized twins of the per-row list builders
(bitwise-identical tables, pinned by tests) so a 1M-node mesh builds
without any per-row Python loop or dense object.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib
import math
import threading
from typing import Optional

import numpy as np

from distributed_optimization_tpu.config import RANDOM_TOPOLOGIES

# Mirrors config.NEIGHBOR_TOPOLOGIES (the single source of the AUTO policy
# is config.py — this module only needs to know which names have a
# constructor). ``config`` itself imports nothing of the package, so the
# one name taken from it above, RANDOM_TOPOLOGIES (which graphs a seed, p
# and sampler tell apart: ``cached_topology``'s key), closes no cycle.
MATRIX_FREE_TOPOLOGIES = ("ring", "grid", "chain", "erdos_renyi")

# Power-iteration budget for the matrix-free spectral-gap estimate: the
# norm ratio converges to ρ geometrically in the (|λ3|/|λ2|) ratio, and
# 500 applications at O(N·k_max) each is still ~10^7 flops at N = 10k —
# cheaper than one dense [N, N] eigendecomposition at N = 1k.
_POWER_ITERS = 500


@dataclasses.dataclass(frozen=True)
class Topology:
    """A communication graph plus its gossip structure.

    Undirected graphs (``directed=False``) carry a Metropolis-Hastings
    mixing matrix (row-stochastic, symmetric — hence doubly stochastic);
    directed graphs carry a column-stochastic uniform-out-weight matrix
    (each node splits its mass equally over its out-neighbors and itself),
    the push-sum setting. ``adjacency[i, j] = 1`` iff j sends to i.

    MATRIX-FREE topologies (``impl='neighbor'``) set ``adjacency`` and
    ``mixing_matrix`` to None and carry the padded neighbor table instead:
    ``nbr_idx [N, k_max]`` int32 (row i = i's neighbors ascending, padded
    slots pointing at i) and ``nbr_mask [N, k_max]`` bool — exactly the
    layout ``neighbor_table`` derives from a dense adjacency, so dense and
    matrix-free builds of the same graph produce bit-identical tables.
    """

    name: str
    n: int
    # [N, N] 0/1, zero diagonal; row i = i's in-edges. None when the
    # topology is matrix-free (neighbor-table-native).
    adjacency: Optional[np.ndarray]
    # Out-degrees (== in-degrees for undirected graphs): how many neighbors
    # each node TRANSMITS to per gossip round — the comms-accounting side.
    degrees: np.ndarray  # [N]
    # [N, N]; MH (undirected) or column-stochastic. None when matrix-free.
    mixing_matrix: Optional[np.ndarray]
    grid_shape: Optional[tuple[int, int]] = None  # set for 'grid'
    directed: bool = False
    # Matrix-free neighbor table (None on the dense representation).
    nbr_idx: Optional[np.ndarray] = None   # [N, k_max] int32
    nbr_mask: Optional[np.ndarray] = None  # [N, k_max] bool
    # Which random-graph sampler realized the table: 'dense' (the
    # [N, N]-stream-replaying bitwise reference) or 'sparse' (the
    # O(N·k_max)-draw constructor). Always 'dense' for deterministic
    # topologies — the value is part of the graph's structural identity
    # and keys the halo-plan cache (``build_halo_plan``).
    sampler: str = "dense"

    @property
    def is_matrix_free(self) -> bool:
        return self.adjacency is None

    @functools.cached_property
    def spectral_gap(self) -> float:
        """1 - ρ where ρ is the second-largest |eigenvalue| of W, worked out
        once a ``Topology`` (the power iteration is seconds at 2^18 nodes,
        and ``cached_topology`` hands one object to every call).

        Parity: reference trainer.py:133-135. Closed-form values for the
        report setup: ring(25) ≈ 0.0209, 5x5 torus ≈ 0.2764, fc = 1.0.
        Directed mixing matrices are non-normal with a possibly complex
        spectrum; ρ is the second-largest eigenvalue MODULUS (the
        ergodicity coefficient of the column-stochastic chain — self-loops
        make it primitive, so ρ < 1 for strongly connected graphs).

        Matrix-free topologies never materialize W: ring and torus use
        their closed forms (exact — uniform MH weights by symmetry);
        chain/ER estimate ρ by power iteration on the mean-deflated
        gather-form operator v ↦ W v − v̄ (O(N·k_max) per application,
        deterministic start vector), accurate to the iteration budget's
        geometric tail — a diagnostic, like the dense eigensolve.
        """
        if self.n < 2:
            return 1.0
        if self.is_matrix_free:
            if self.name == "ring" and self.n >= 3:
                return ring_spectral_gap_closed_form(self.n)
            if (
                self.name == "grid"
                and self.grid_shape is not None
                and self.grid_shape[0] == self.grid_shape[1]
                and min(self.grid_shape) >= 3
            ):
                return torus_spectral_gap_closed_form(self.grid_shape[0])
            return self._power_iteration_gap()
        if self.directed:
            eigs = np.sort(np.abs(np.linalg.eigvals(self.mixing_matrix)))
        else:
            eigs = np.sort(np.abs(np.linalg.eigvalsh(self.mixing_matrix)))
        return float(1.0 - eigs[-2])

    def _power_iteration_gap(self) -> float:
        """ρ ≈ lim ‖B^k v‖ / ‖B^{k−1} v‖ for B = W − (1/n)𝟙𝟙ᵀ (symmetric,
        so the normalized-iterate norm converges to the largest
        |eigenvalue| of the deflated operator — i.e. ρ — even under
        eigenvalue multiplicity, the ring's generic case)."""
        from scipy import sparse

        w_nbr, w_self = gather_mixing_weights(
            self.nbr_idx, self.nbr_mask, self.degrees
        )
        # The live slots as one CSR matrix: a product reads 2·E weights,
        # not N·k_max padded ones through a fancy index (at 2^18 nodes of
        # degree 12 in a table 30 wide: 9 ms against 110, 500 times).
        live = self.nbr_mask
        off_diag = sparse.csr_matrix(
            (w_nbr[live], (np.nonzero(live)[0], self.nbr_idx[live])),
            shape=(self.n, self.n),
        )
        v = np.random.default_rng(0).standard_normal(self.n)
        v -= v.mean()
        v /= np.linalg.norm(v)
        rho = 0.0
        for _ in range(_POWER_ITERS):
            v = w_self * v + off_diag @ v
            v -= v.mean()
            rho = np.linalg.norm(v)
            if rho < 1e-300:  # degenerate: W is exact averaging
                return 1.0
            v /= rho
        return float(1.0 - rho)

    @functools.cached_property
    def gather_chunks(self) -> dict:
        """``live_slot_chunks`` of this graph's neighbor table, worked out
        once a ``Topology`` and read-only: a function of the graph alone,
        so it is kept with the kept graph (``cached_topology``) and a later
        call's gather mixing only casts and uploads it."""
        chunks = live_slot_chunks(*neighbor_tables_for(self), self.degrees)
        for leaf in chunks.values():
            leaf.setflags(write=False)
        return chunks

    @property
    def floats_per_iteration(self) -> float:
        """Analytic gossip cost in floats per iteration per model dimension.

        One gossip round sends each worker's model to each of its neighbors:
        Σ_i deg_i values per model coordinate (reference trainer.py:169-170).
        For directed graphs deg = out-degree, so the sum counts each directed
        edge once. Multiply by d (and by rounds-per-iteration for two-mix
        algorithms).
        """
        return float(np.sum(self.degrees))

    def validate(self) -> None:
        """Invariant checks (parity: reference trainer.py:128-131 asserts).

        Directed graphs swap the row-sum + symmetry invariants for the
        column-sum one: column-stochasticity is exactly mass conservation,
        the property push-sum's debiasing relies on (Σ_i (Ax)_i = Σ_j x_j).

        Matrix-free topologies validate the TABLE invariants instead:
        in-range indices, padded slots self-pointing, degrees matching the
        mask, and symmetry (every (i → j) slot has a (j → i) twin) — the
        property that makes gather-form MH mixing doubly stochastic.
        """
        if self.is_matrix_free:
            idx, mask = self.nbr_idx, self.nbr_mask
            if idx is None or mask is None or idx.shape != mask.shape:
                raise AssertionError(
                    f"matrix-free topology needs matching nbr_idx/nbr_mask "
                    f"tables ({self.name})"
                )
            if idx.min() < 0 or idx.max() >= self.n:
                raise AssertionError(
                    f"neighbor indices out of range ({self.name})"
                )
            if not np.all(idx[~mask] == np.nonzero(~mask)[0]):
                raise AssertionError(
                    f"padded neighbor slots must self-point ({self.name})"
                )
            if not np.array_equal(mask.sum(axis=1), self.degrees):
                raise AssertionError(
                    f"degrees disagree with the neighbor mask ({self.name})"
                )
            # Symmetry as a vectorized multiset identity: the directed
            # slot keys i·n + j must equal their swapped twins j·n + i
            # after sorting — every (i → j) slot has a (j → i) twin.
            # (O(E log E) numpy; the former per-edge Python set was the
            # validation bottleneck at N = 1M.)
            ii = np.broadcast_to(
                np.arange(self.n, dtype=np.int64)[:, None], idx.shape
            )[mask]
            jj = idx[mask].astype(np.int64)
            if not np.array_equal(
                np.sort(ii * self.n + jj), np.sort(jj * self.n + ii)
            ):
                raise AssertionError(
                    f"neighbor table must be symmetric ({self.name})"
                )
            return
        W = self.mixing_matrix
        if np.any(W < -1e-12):
            raise AssertionError(f"Mixing matrix must be nonnegative ({self.name})")
        if self.directed:
            if not np.allclose(W.sum(axis=0), 1.0):
                raise AssertionError(
                    f"Directed mixing matrix columns must sum to 1 ({self.name})"
                )
            return
        if not np.allclose(W.sum(axis=1), 1.0):
            raise AssertionError(f"Mixing matrix rows must sum to 1 ({self.name})")
        if not np.allclose(W, W.T):
            raise AssertionError(f"Mixing matrix must be symmetric ({self.name})")


def _ring_adjacency(n: int) -> np.ndarray:
    adj = np.zeros((n, n))
    ids = np.arange(n)
    adj[ids, (ids + 1) % n] = 1.0
    adj[ids, (ids - 1) % n] = 1.0
    np.fill_diagonal(adj, 0.0)  # n == 1, 2 edge cases
    return adj


def _chain_adjacency(n: int) -> np.ndarray:
    adj = np.zeros((n, n))
    ids = np.arange(n - 1)
    adj[ids, ids + 1] = 1.0
    adj[ids + 1, ids] = 1.0
    return adj


def _star_adjacency(n: int) -> np.ndarray:
    adj = np.zeros((n, n))
    adj[0, 1:] = 1.0
    adj[1:, 0] = 1.0
    return adj


def _torus_adjacency(rows: int, cols: int) -> np.ndarray:
    """Periodic 2-D grid. Worker (r, c) sits at index r*cols + c (row-major),
    matching the reference's sorted-node indexing of
    ``networkx.grid_2d_graph(periodic=True)`` (reference trainer.py:103-108)."""
    n = rows * cols
    adj = np.zeros((n, n))
    for r in range(rows):
        for c in range(cols):
            i = r * cols + c
            for rr, cc in ((r - 1, c), (r + 1, c), (r, c - 1), (r, c + 1)):
                j = (rr % rows) * cols + (cc % cols)
                if j != i:  # degenerate 1- or 2-length axes collapse neighbors
                    adj[i, j] = 1.0
    return adj


def _erdos_renyi_adjacency(n: int, p: float, seed: int) -> np.ndarray:
    """Connected Erdős–Rényi G(n, p): resample until connected."""
    rng = np.random.default_rng(seed)
    for _ in range(1000):
        upper = rng.random((n, n)) < p
        adj = np.triu(upper, k=1).astype(float)
        adj = adj + adj.T
        if _is_connected(adj):
            return adj
    raise RuntimeError(f"Could not sample a connected G({n}, {p}) in 1000 tries")


def _directed_ring_adjacency(n: int) -> np.ndarray:
    """Each node receives from its predecessor: edge (i-1) → i."""
    adj = np.zeros((n, n))
    ids = np.arange(n)
    adj[ids, (ids - 1) % n] = 1.0
    np.fill_diagonal(adj, 0.0)  # n == 1 edge case
    return adj


def _directed_erdos_renyi_adjacency(n: int, p: float, seed: int) -> np.ndarray:
    """Strongly connected directed G(n, p): each ORDERED pair (j → i) draws
    independently, resampled until every node reaches every other (checked
    as reachability from node 0 along both edge orientations)."""
    rng = np.random.default_rng(seed)
    for _ in range(1000):
        adj = (rng.random((n, n)) < p).astype(float)
        np.fill_diagonal(adj, 0.0)
        # Strong connectivity ⟺ node 0 reaches all (follow in-edges of the
        # receive convention = walk adj as "i reachable from j") and all
        # reach node 0 (same walk on the transpose).
        if _is_connected_directed(adj) and _is_connected_directed(adj.T):
            return adj
    raise RuntimeError(
        f"Could not sample a strongly connected directed G({n}, {p}) in 1000 tries"
    )


def _is_connected_directed(adj: np.ndarray) -> bool:
    """All nodes reachable from node 0 following edges j → i (adj[i, j])."""
    n = adj.shape[0]
    if n == 0:
        return False
    reached = np.zeros(n, dtype=bool)
    frontier = [0]
    reached[0] = True
    while frontier:
        j = frontier.pop()
        for i in np.nonzero(adj[:, j])[0]:
            if not reached[i]:
                reached[i] = True
                frontier.append(int(i))
    return bool(reached.all())


def _is_connected(adj: np.ndarray) -> bool:
    n = adj.shape[0]
    if n == 0:
        return False
    reached = np.zeros(n, dtype=bool)
    frontier = [0]
    reached[0] = True
    while frontier:
        i = frontier.pop()
        for j in np.nonzero(adj[i])[0]:
            if not reached[j]:
                reached[j] = True
                frontier.append(int(j))
    return bool(reached.all())


def neighbor_table(adjacency: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Static padded neighbor-index table of an undirected 0/1 adjacency.

    Returns ``(nbr_idx [N, k_max] int32, nbr_mask [N, k_max] bool)``: row i
    lists i's neighbors in ascending index order (the same order a dense
    axis-1 reduction visits them, so gather-form aggregations sum in the
    identical order as their dense twins); padded slots point at i itself
    (an always-in-bounds gather target) with mask False. ``k_max`` is the
    maximum degree — the whole point of the gather path is that sorts and
    reductions then run over k_max+1 values instead of N
    (``ops/robust_aggregation.py::make_gather_robust_aggregator``).

    Host-side like everything in this module: built once per run, outside
    ``jit``. Directed graphs are rejected — the degree-bounded screening
    path is undirected-only (robust aggregation composes only with MH
    gossip; the directed/push-sum family rejects Byzantine injection).
    """
    A = np.asarray(adjacency)
    if not np.array_equal(A, A.T):
        raise ValueError(
            "neighbor_table expects an undirected (symmetric) adjacency; "
            "the degree-bounded gather path has no directed form"
        )
    n = A.shape[0]
    k_max = max(int(A.sum(axis=1).max()), 1) if n else 1
    nbr_idx = np.tile(np.arange(n, dtype=np.int32)[:, None], (1, k_max))
    nbr_mask = np.zeros((n, k_max), dtype=bool)
    for i in range(n):
        nbrs = np.nonzero(A[i])[0]
        nbr_idx[i, : len(nbrs)] = nbrs
        nbr_mask[i, : len(nbrs)] = True
    return nbr_idx, nbr_mask


def incident_edge_slots(
    nbr_idx: np.ndarray, nbr_mask: np.ndarray, edge_index: np.ndarray
) -> np.ndarray:
    """[N, k_max] int32 map from (node, neighbor-slot) to undirected edge id.

    ``edge_index`` is the [E, 2] i<j edge list a fault timeline indexes
    (``parallel/faults.py``); entry (i, s) is the id of edge
    {i, nbr_idx[i, s]} — each edge appears in BOTH endpoints' rows, so a
    per-edge liveness bit gathered through this table lands symmetrically,
    exactly like the dense scatter ``A[ei, ej] = A[ej, ei] = up[e]``.
    Padded slots map to 0 (masked out by ``nbr_mask`` downstream).
    """
    n = nbr_idx.shape[0]
    mask = np.asarray(nbr_mask, dtype=bool)
    # An edge {i, j}, i < j, as the one number i·n + j; a slot's edge is
    # found among the sorted numbers (no loop over nodes, no dict of edges:
    # at 2^18 workers those were seconds of every call).
    edges = np.asarray(edge_index, dtype=np.int64).reshape(-1, 2)
    number = edges[:, 0] * n + edges[:, 1]
    order = np.argsort(number, kind="stable")
    here = np.arange(n, dtype=np.int64)[:, None]
    there = np.asarray(nbr_idx, dtype=np.int64)
    want = np.minimum(here, there) * n + np.maximum(here, there)
    at = np.searchsorted(number[order], want)
    hit = mask & (at < len(order))
    hit[hit] = number[order[at[hit]]] == want[hit]
    if (mask & ~hit).any():
        i, s = np.argwhere(mask & ~hit)[0]
        raise KeyError((int(want[i, s] // n), int(want[i, s] % n)))
    slots = np.zeros(nbr_idx.shape, dtype=np.int32)
    slots[hit] = order[at[hit]]
    return slots


def _pad_neighbor_lists(nbrs: list[np.ndarray], n: int):
    """Pack per-node ascending neighbor lists into the padded table
    (identical layout/convention to ``neighbor_table``: padded slots point
    at the node itself, mask False)."""
    k_max = max((len(v) for v in nbrs), default=0)
    k_max = max(k_max, 1)
    nbr_idx = np.tile(np.arange(n, dtype=np.int32)[:, None], (1, k_max))
    nbr_mask = np.zeros((n, k_max), dtype=bool)
    for i, v in enumerate(nbrs):
        nbr_idx[i, : len(v)] = np.sort(v).astype(np.int32)
        nbr_mask[i, : len(v)] = True
    return nbr_idx, nbr_mask


def _ring_neighbor_lists(n: int) -> list[np.ndarray]:
    if n <= 1:
        return [np.empty(0, dtype=np.int64) for _ in range(n)]
    if n == 2:
        return [np.array([1]), np.array([0])]
    return [
        np.unique(np.array([(i - 1) % n, (i + 1) % n]))
        for i in range(n)
    ]


def _chain_neighbor_lists(n: int) -> list[np.ndarray]:
    out = []
    for i in range(n):
        v = [j for j in (i - 1, i + 1) if 0 <= j < n]
        out.append(np.asarray(v, dtype=np.int64))
    return out


def _torus_neighbor_lists(rows: int, cols: int) -> list[np.ndarray]:
    """Same node indexing and neighbor set as ``_torus_adjacency`` (row-major
    (r, c) ↦ r·cols + c; degenerate short axes collapse duplicates)."""
    out = []
    for r in range(rows):
        for c in range(cols):
            i = r * cols + c
            js = {
                (rr % rows) * cols + (cc % cols)
                for rr, cc in ((r - 1, c), (r + 1, c), (r, c - 1), (r, c + 1))
            }
            js.discard(i)
            out.append(np.asarray(sorted(js), dtype=np.int64))
    return out


def _erdos_renyi_neighbor_lists(
    n: int, p: float, seed: int
) -> list[np.ndarray]:
    """Connected G(n, p) WITHOUT the [N, N] draw matrix.

    Bit-identical to ``_erdos_renyi_adjacency``: numpy's Generator fills
    ``random((n, n))`` row-major from one sequential stream, so drawing
    ``random(n)`` per row walks the same values in the same order — the
    same (seed, try) realizes the same graph in both representations
    (pinned by tests/test_federated.py). Memory is O(n) per row plus the
    O(E) adjacency lists; connectivity is union-find over the edges as
    they are drawn.
    """
    rng = np.random.default_rng(seed)
    for _ in range(1000):
        nbrs: list[list[int]] = [[] for _ in range(n)]
        parent = list(range(n))

        def find(a: int) -> int:
            while parent[a] != a:
                parent[a] = parent[parent[a]]
                a = parent[a]
            return a

        comps = n
        for i in range(n):
            row = rng.random(n)
            for j in np.nonzero(row[i + 1:] < p)[0]:
                j = int(i + 1 + j)
                nbrs[i].append(j)
                nbrs[j].append(i)
                ri, rj = find(i), find(j)
                if ri != rj:
                    parent[ri] = rj
                    comps -= 1
        if comps == 1:
            return [np.asarray(v, dtype=np.int64) for v in nbrs]
    raise RuntimeError(f"Could not sample a connected G({n}, {p}) in 1000 tries")


def _ring_neighbor_tables(n: int) -> tuple[np.ndarray, np.ndarray]:
    """Vectorized twin of ``_ring_neighbor_lists`` + ``_pad_neighbor_lists``
    for n >= 3 (every node has the two distinct neighbors (i±1) mod n,
    listed ascending) — bitwise-identical tables without the per-row
    Python loop, the 1M-node path."""
    ids = np.arange(n, dtype=np.int64)
    left, right = (ids - 1) % n, (ids + 1) % n
    nbr_idx = np.stack(
        [np.minimum(left, right), np.maximum(left, right)], axis=1
    ).astype(np.int32)
    return nbr_idx, np.ones((n, 2), dtype=bool)


def _chain_neighbor_tables(n: int) -> tuple[np.ndarray, np.ndarray]:
    """Vectorized twin of ``_chain_neighbor_lists`` + ``_pad_neighbor_lists``
    for n >= 3 (interior rows [i−1, i+1]; endpoint rows degree 1 with the
    padded slot self-pointing)."""
    ids = np.arange(n, dtype=np.int32)
    nbr_idx = np.tile(ids[:, None], (1, 2))
    nbr_mask = np.zeros((n, 2), dtype=bool)
    nbr_idx[1:-1, 0] = ids[1:-1] - 1
    nbr_idx[1:-1, 1] = ids[1:-1] + 1
    nbr_mask[1:-1] = True
    nbr_idx[0, 0] = 1
    nbr_mask[0, 0] = True
    nbr_idx[-1, 0] = n - 2
    nbr_mask[-1, 0] = True
    return nbr_idx, nbr_mask


def _torus_neighbor_tables(side: int) -> tuple[np.ndarray, np.ndarray]:
    """Vectorized twin of ``_torus_neighbor_lists`` + ``_pad_neighbor_lists``
    for square tori with side >= 3 (all four wrap neighbors distinct,
    sorted ascending per row). In the table's own int32 throughout (ids
    fit it or the table could not hold them): the sort is a quarter of
    int64's, and ``table_is_a_torus_in_row_blocks`` pays it every call."""
    r = np.repeat(np.arange(side, dtype=np.int32), side)
    c = np.tile(np.arange(side, dtype=np.int32), side)
    stacked = np.stack(
        [
            ((r - 1) % side) * side + c,
            ((r + 1) % side) * side + c,
            r * side + (c - 1) % side,
            r * side + (c + 1) % side,
        ],
        axis=1,
    )
    return np.sort(stacked, axis=1), np.ones((side * side, 4), dtype=bool)


def _pack_neighbor_tables(
    src: np.ndarray, dst: np.ndarray, n: int
) -> tuple[np.ndarray, np.ndarray]:
    """Pack forward undirected edges (src < dst, unique) into the padded
    table — vectorized counterpart of ``_pad_neighbor_lists`` (padded
    slots self-point, per-row neighbors ascending)."""
    si = np.concatenate([src, dst])
    di = np.concatenate([dst, src])
    order = np.lexsort((di, si))
    si, di = si[order], di[order]
    deg = np.bincount(si, minlength=n)
    k_max = max(int(deg.max()) if n else 0, 1)
    nbr_idx = np.tile(np.arange(n, dtype=np.int32)[:, None], (1, k_max))
    nbr_mask = np.zeros((n, k_max), dtype=bool)
    offs = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(deg, out=offs[1:])
    col = np.arange(si.size, dtype=np.int64) - offs[si]
    nbr_idx[si, col] = di.astype(np.int32)
    nbr_mask[si, col] = True
    return nbr_idx, nbr_mask


def _edges_connected(src: np.ndarray, dst: np.ndarray, n: int) -> bool:
    """Connectivity of an undirected edge list by vectorized min-label
    propagation with pointer jumping: each round every node takes the
    minimum label over its closed neighborhood, then labels chase labels
    (``lab[lab]``). At the fixed point labels are constant per component,
    so connected ⟺ all labels equal node 0's. O((E + N) · rounds) with
    rounds ~ log(diameter) — the union-find replacement that needs no
    per-edge Python loop at N = 1M."""
    if n == 0:
        return False
    lab = np.arange(n, dtype=np.int64)
    for _ in range(10_000):
        nxt = lab.copy()
        np.minimum.at(nxt, src, lab[dst])
        np.minimum.at(nxt, dst, lab[src])
        nxt = nxt[nxt]
        if np.array_equal(nxt, lab):
            break
        lab = nxt
    return bool((lab == 0).all())


# Bounded dedupe/top-up rounds for the sparse ER sampler. Each round
# redraws only the deficit (forward edges lost to duplicate tail draws);
# with k_max ≪ tail the per-draw collision probability is ~k_max/tail,
# so deficits shrink geometrically and the bound is never approached in
# practice — it exists so a pathological (n, p) fails loudly.
_SPARSE_TOPUP_ROUNDS = 200


def _erdos_renyi_forward_edges_sparse(
    n: int, p: float, seed: int
) -> tuple[np.ndarray, np.ndarray]:
    """Connected G(n, p) in O(N·k_max) draws: the million-node sampler.

    Decomposes the undirected upper-triangle draw by FORWARD tails: node
    i's edges into {i+1, …, n−1} are Binomial(n−1−i, p) in number and
    uniform without replacement in position. One vectorized
    ``rng.binomial`` draws every forward degree, one vectorized uniform
    draw proposes that many tail partners WITH replacement, and bounded
    top-up rounds redraw exactly the rows that lost proposals to
    duplicates — total work O(E) instead of the dense sampler's O(N²)
    stream replay. Connectivity is vectorized min-label propagation
    (``_edges_connected``); like every sampler here the generator stream
    is seed-pure (draws depend only on (n, p, seed) and the retry
    index), so a given seed realizes the same graph everywhere.

    Same G(n, p) law as ``_erdos_renyi_neighbor_lists``, a DIFFERENT
    realization per (seed, p) — which is why the sampler choice is part
    of a config's structural identity rather than a transparent
    implementation detail (``config.resolved_topology_sampler()``).

    Returns the forward edge list ``(src, dst)`` with src < dst, unique,
    for ``_pack_neighbor_tables``.
    """
    rng = np.random.default_rng(seed)
    ids = np.arange(n, dtype=np.int64)
    tail = (n - 1) - ids
    for _ in range(1000):
        counts = rng.binomial(tail, p)
        src = np.repeat(ids, counts)
        dst = src + 1 + np.floor(
            rng.random(src.size) * tail[src]
        ).astype(np.int64)
        keys = np.unique(src * n + dst)
        for _ in range(_SPARSE_TOPUP_ROUNDS):
            deficit = counts - np.bincount(keys // n, minlength=n)
            if not (deficit > 0).any():
                break
            src2 = np.repeat(ids, np.maximum(deficit, 0))
            dst2 = src2 + 1 + np.floor(
                rng.random(src2.size) * tail[src2]
            ).astype(np.int64)
            keys = np.unique(np.concatenate([keys, src2 * n + dst2]))
        else:
            raise RuntimeError(
                f"sparse G({n}, {p}) top-up did not converge in "
                f"{_SPARSE_TOPUP_ROUNDS} rounds"
            )
        src_f, dst_f = keys // n, keys % n
        if _edges_connected(src_f, dst_f, n):
            return src_f, dst_f
    raise RuntimeError(f"Could not sample a connected G({n}, {p}) in 1000 tries")


# Ceiling on the padded neighbor-table cell count (satellite guard): a
# topology whose k_max approaches N has no degree-bounded structure to
# exploit, and "matrix-free" would just reallocate the quadratic object
# under a different name. fully_connected/star are rejected by name with
# the specific message; this catches dense Erdős–Rényi draws.
NEIGHBOR_TABLE_MAX_CELLS = 64_000_000


def _guard_table_size(k_max: int, n: int) -> None:
    """The two degree guards of the matrix-free path, shared by every
    constructor branch: a k_max approaching N has no degree bound to
    exploit, and the padded table's cell count is capped so 'matrix-free'
    can never silently reallocate the quadratic object."""
    if n > 2 and k_max >= n - 1:
        raise ValueError(
            f"realized max degree {k_max} at N={n} leaves no degree bound "
            "to exploit — the neighbor table would match the dense "
            "adjacency's footprint; use the dense representation"
        )
    if max(k_max, 1) * n > NEIGHBOR_TABLE_MAX_CELLS:
        raise ValueError(
            f"neighbor table would hold {max(k_max, 1) * n:,} cells "
            f"(k_max={k_max}, N={n}) > NEIGHBOR_TABLE_MAX_CELLS "
            f"({NEIGHBOR_TABLE_MAX_CELLS:,}) — this graph is too dense "
            "for the degree-bounded path; use the dense representation "
            "or a sparser graph"
        )


def build_neighbor_topology(
    name: str,
    n: int,
    *,
    erdos_renyi_p: float = 0.4,
    seed: int = 0,
    sampler: str = "dense",
) -> Topology:
    """Matrix-free constructor: the [N, k_max] neighbor table IS the graph.

    Supports ``MATRIX_FREE_TOPOLOGIES`` (undirected, degree-bounded).
    fully_connected and star are rejected loudly — k_max = N−1 makes the
    padded table the very [N, N] allocation this path exists to avoid —
    and any draw whose table would exceed ``NEIGHBOR_TABLE_MAX_CELLS``
    (or whose k_max reaches N−1) routes the caller back to dense with the
    reason.

    ``sampler`` selects the Erdős–Rényi constructor: 'dense' replays the
    [N, N] uniform stream bit-for-bit (O(N²) draws — the historical
    reference), 'sparse' draws O(N·k_max)
    (``_erdos_renyi_forward_edges_sparse`` — the million-node path, a
    different realization of the same law). Deterministic topologies
    ignore it (their tables are unique); callers resolve 'auto' policy
    via ``config.resolved_topology_sampler()`` before calling.
    """
    if name in ("fully_connected", "star"):
        raise ValueError(
            f"topology {name!r} has k_max = N-1: its neighbor table IS the "
            "dense [N, N] object the matrix-free path avoids — use the "
            "dense representation (impl='dense')"
        )
    if sampler not in ("dense", "sparse"):
        raise ValueError(
            f"unknown topology sampler {sampler!r} (expected 'dense' or "
            "'sparse')"
        )
    grid_shape: Optional[tuple[int, int]] = None
    sampler_used = "dense"
    if name == "ring":
        tables = (
            _ring_neighbor_tables(n)
            if n > 2
            else _pad_neighbor_lists(_ring_neighbor_lists(n), n)
        )
    elif name == "chain":
        tables = (
            _chain_neighbor_tables(n)
            if n > 2
            else _pad_neighbor_lists(_chain_neighbor_lists(n), n)
        )
    elif name == "grid":
        side = int(math.isqrt(n))
        if side * side != n:
            raise ValueError(f"grid topology requires a perfect square, got {n}")
        tables = (
            _torus_neighbor_tables(side)
            if side >= 3
            else _pad_neighbor_lists(_torus_neighbor_lists(side, side), n)
        )
        grid_shape = (side, side)
    elif name == "erdos_renyi":
        sampler_used = sampler
        if sampler == "sparse":
            src, dst = _erdos_renyi_forward_edges_sparse(
                n, erdos_renyi_p, seed
            )
            # Guard on the realized degrees BEFORE allocating the padded
            # table — at this scale the table is the dominant allocation.
            deg = np.bincount(
                np.concatenate([src, dst]), minlength=max(n, 1)
            )
            _guard_table_size(int(deg.max()) if n else 0, n)
            tables = _pack_neighbor_tables(src, dst, n)
        else:
            nbrs = _erdos_renyi_neighbor_lists(n, erdos_renyi_p, seed)
            _guard_table_size(max((len(v) for v in nbrs), default=0), n)
            tables = _pad_neighbor_lists(nbrs, n)
    else:
        raise ValueError(
            f"no matrix-free constructor for topology {name!r} "
            f"(supported: {MATRIX_FREE_TOPOLOGIES})"
        )
    nbr_idx, nbr_mask = tables
    _guard_table_size(int(nbr_mask.sum(axis=1).max()) if n else 0, n)
    topo = Topology(
        name=name,
        n=n,
        adjacency=None,
        degrees=nbr_mask.sum(axis=1).astype(np.float64),
        mixing_matrix=None,
        grid_shape=grid_shape,
        nbr_idx=nbr_idx,
        nbr_mask=nbr_mask,
        sampler=sampler_used,
    )
    topo.validate()
    return topo


def neighbor_tables_for(topo: Topology) -> tuple[np.ndarray, np.ndarray]:
    """The (nbr_idx, nbr_mask) tables of any undirected topology: native
    for matrix-free builds, derived via ``neighbor_table`` from the dense
    adjacency otherwise (both produce the identical layout)."""
    if topo.nbr_idx is not None:
        return topo.nbr_idx, topo.nbr_mask
    return neighbor_table(topo.adjacency)


def table_is_a_ring(nbr_idx: np.ndarray, nbr_mask=None) -> bool:
    """Whether a neighbor table IS a ring's: n >= 3 rows, every row the two
    neighbours (i ± 1) mod n in ascending order and, where the static mask
    is given, every slot live in it. Read off the table, not a topology's
    name: whatever graph has this table has its neighbours read by shifts —
    the unsharded fault layer (``faults._make_shift_faulty_mixing``), the
    worker mesh's halo mixing (``collectives.make_halo_mixing_op``) and the
    screened round's count rules
    (``ops.robust_aggregation.closed_neighbourhood_rule``, which is handed
    each round's liveness and so asks without the mask) ask this ONE rule —
    every other one by gathers (but a torus in whole grid rows under the
    worker mesh: ``table_is_a_torus_in_row_blocks``, which the halo mixing
    asks next). Host arrays, a few ms at 2^18 workers."""
    n = nbr_idx.shape[0]
    if n < 3 or nbr_idx.shape != (n, 2):
        return False
    nbr, mask = _ring_neighbor_tables(n)
    return bool(
        (nbr_mask is None or np.array_equal(nbr_mask, mask))
        and np.array_equal(nbr_idx, nbr)
    )


def _table_is_a_ring(topo: Topology) -> bool:
    """``table_is_a_ring`` of a topology's own tables, the static mask
    included: what the fault layer and the halo mixing ask."""
    return table_is_a_ring(*neighbor_tables_for(topo))


def table_is_a_torus_in_row_blocks(
    nbr_idx: np.ndarray, nbr_mask: np.ndarray, n_blocks: int
) -> bool:
    """Whether a neighbor table IS a square torus's, cut by whole grid rows
    into ``n_blocks`` contiguous blocks: n = side², side >= 3 (the four wrap
    neighbours distinct), ``side % n_blocks == 0`` so that a block is
    ``side / n_blocks`` whole grid rows, and table and static mask equal
    ``_torus_neighbor_tables(side)`` (worker i at (i // side, i % side), a
    row's four neighbours ascending, every slot live). Read off the table
    and the number of blocks, not a topology's name, in ``table_is_a_ring``'s
    manner: whatever graph answers yes has every neighbour at a fixed
    offset, ±1 inside a grid row with the column wrap and ±side for the grid
    row above and below, and only a block's first and last grid row reach
    another block, so the worker mesh's halo mixing reads it by shifts
    (``collectives.make_halo_mixing_op``); a torus whose slots are listed
    another way, one that the blocks cut through a grid row (6 x 6 over 4),
    chain and Erdős–Rényi answer no and are gathered. Host arrays, the ring
    rule's cost in proportion: the refusals cost nothing, a torus's yes its
    tables made once more and compared, 0.05 s at 2^20 workers."""
    n = nbr_idx.shape[0]
    side = math.isqrt(n)
    if (
        side < 3 or side * side != n or nbr_idx.shape != (n, 4)
        or n_blocks < 1 or side % n_blocks
    ):
        return False
    nbr, mask = _torus_neighbor_tables(side)
    return bool(
        np.array_equal(nbr_mask, mask) and np.array_equal(nbr_idx, nbr)
    )


@dataclasses.dataclass(frozen=True)
class HaloStep:
    """One ppermute rotation of the halo exchange (devices p → (p+r) mod P).

    At rotation ``r`` every shard p ships to shard (p+r) mod P exactly the
    block rows that destination's neighbor table references, padded to the
    rotation's max count so the collective is shape-uniform. ``send_idx``
    [P, s_max] holds SENDER-local row indices (pad 0 — a harmless real
    row); ``recv_pos`` [P, s_max] the receiver's halo-buffer positions
    (pad = h_max, the dump slot past the real halo). ``counts`` [P] are
    the realized (unpadded) row counts — the per-device ICI accounting.
    """

    rotation: int
    send_idx: np.ndarray  # [P, s_max] int32
    recv_pos: np.ndarray  # [P, s_max] int32
    counts: np.ndarray    # [P] int64


@dataclasses.dataclass(frozen=True)
class HaloPlan:
    """Static sharding plan of a padded neighbor table over P row blocks.

    Shard p owns the contiguous global rows [p·S, (p+1)·S). ``local_nbr``
    is the whole table remapped to SHARD-LOCAL coordinates: entry (i, s)
    of shard p's block indexes into that shard's extended buffer
    ``ext = concat([block [S], halo [h_max + 1]])`` — in-block neighbors
    map to their block row, boundary neighbors to S + (position in the
    shard's sorted halo list), so ``ext[local_nbr]`` gathers exactly the
    values ``x[nbr_idx]`` gathers globally (the bitwise-parity contract
    of the sharded gather path). The extra halo slot (index S + h_max)
    is the dump row padded exchange traffic lands in — never referenced
    by ``local_nbr``. ``sent_rows``/``recv_rows`` [P] count the realized
    boundary rows each device ships/receives per exchange: the
    bytes-over-ICI accounting is ``sent_rows · payload_width · itemsize``.
    """

    n_shards: int
    shard_rows: int
    h_max: int
    local_nbr: np.ndarray     # [N, k_max] int32, values in [0, S + h_max)
    halo_idx: list            # per-shard sorted GLOBAL boundary rows
    steps: tuple              # tuple[HaloStep, ...] — empty rotations dropped
    sent_rows: np.ndarray     # [P] int64
    recv_rows: np.ndarray     # [P] int64


# One sharded faulty+robust run consults the identical plan up to five
# times (mixing op, fault layer, robust aggregator, /metrics gauges,
# health_summary) and each build is an O(N·k_max) host pass with
# per-shard Python loops — memoize by content digest so the plan is
# built once per (table, P). Plans are treated read-only by every
# consumer (they are lowered straight into device arrays).
_HALO_PLAN_CACHE: "collections.OrderedDict[tuple, HaloPlan]" = (
    collections.OrderedDict()
)
_HALO_PLAN_CACHE_MAX = 8


def build_halo_plan(
    nbr_idx: np.ndarray,
    nbr_mask: np.ndarray,
    n_shards: int,
    *,
    sampler: str = "dense",
) -> HaloPlan:
    """Shard a padded neighbor table into P contiguous row blocks + halo maps.

    Host-side like every builder in this module: runs once per run
    (memoized by table digest — see ``_HALO_PLAN_CACHE``). The
    exchange schedule enumerates rotations r = 1..P−1 and keeps only the
    ones some shard actually needs (a ring's contiguous blocks keep r ∈
    {1, P−1} with one row each — the classic boundary exchange; an
    Erdős–Rényi graph keeps every rotation with ~E/P² rows). Both sides
    of a rotation enumerate the shared rows in ascending global order, so
    the sender's packing and the receiver's halo positions agree by
    construction (asserted against the realized adjacency in
    tests/test_worker_mesh.py).

    ``sampler`` names the topology's sampler identity. Today's plan layout
    is identical across samplers, but it is part of the memoization key so
    a cache hit can never serve a plan built for the other if the layouts
    ever diverge.
    """
    n, k_max = nbr_idx.shape
    if n_shards < 2:
        raise ValueError(f"halo plans need >= 2 shards, got {n_shards}")
    if n % n_shards:
        raise ValueError(
            f"n_shards={n_shards} must divide the worker count ({n})"
        )
    digest = hashlib.sha1()
    digest.update(np.ascontiguousarray(nbr_idx).tobytes())
    digest.update(np.ascontiguousarray(nbr_mask).tobytes())
    cache_key = (
        digest.hexdigest(), nbr_idx.shape, int(n_shards), str(sampler)
    )
    cached = _HALO_PLAN_CACHE.get(cache_key)
    if cached is not None:
        _HALO_PLAN_CACHE.move_to_end(cache_key)
        return cached
    S = n // n_shards
    halo_idx: list[np.ndarray] = []
    for p in range(n_shards):
        rows = nbr_idx[p * S:(p + 1) * S]
        mask = nbr_mask[p * S:(p + 1) * S]
        ref = np.unique(rows[mask])
        halo_idx.append(ref[(ref < p * S) | (ref >= (p + 1) * S)])
    h_max = max((len(h) for h in halo_idx), default=0)

    local_nbr = np.empty_like(nbr_idx, dtype=np.int32)
    for p in range(n_shards):
        block = nbr_idx[p * S:(p + 1) * S].astype(np.int64)
        in_block = (block >= p * S) & (block < (p + 1) * S)
        pos = np.searchsorted(halo_idx[p], block)
        local_nbr[p * S:(p + 1) * S] = np.where(
            in_block, block - p * S, S + pos
        ).astype(np.int32)
        # Padded slots self-point globally, hence in-block locally — the
        # searchsorted values on them are never selected.
        if (~in_block).any():
            h = halo_idx[p]
            clipped = np.minimum(pos, len(h) - 1)
            bad = ~in_block & (
                (pos >= len(h)) | (np.take(h, clipped) != block)
            )
            if bad.any():
                raise AssertionError(
                    f"shard {p}: neighbor rows missing from the halo list"
                )

    steps = []
    sent = np.zeros(n_shards, dtype=np.int64)
    recv = np.zeros(n_shards, dtype=np.int64)
    for r in range(1, n_shards):
        # Receiver view: shard p receives from src = (p - r) mod P the
        # subset of its halo that lives in src's block.
        needed = []
        for p in range(n_shards):
            src = (p - r) % n_shards
            h = halo_idx[p]
            needed.append(h[(h >= src * S) & (h < (src + 1) * S)])
        counts = np.array([len(v) for v in needed], dtype=np.int64)
        if not counts.any():
            continue
        s_max = int(counts.max())
        send_idx = np.zeros((n_shards, s_max), dtype=np.int32)
        recv_pos = np.full((n_shards, s_max), h_max, dtype=np.int32)
        for p in range(n_shards):
            dest = (p + r) % n_shards
            ship = needed[dest]  # global rows dest needs from p
            send_idx[p, : len(ship)] = (ship - p * S).astype(np.int32)
            mine = needed[p]     # global rows p receives this rotation
            recv_pos[p, : len(mine)] = np.searchsorted(
                halo_idx[p], mine
            ).astype(np.int32)
            sent[p] += len(ship)
            recv[p] += len(mine)
        steps.append(
            HaloStep(rotation=r, send_idx=send_idx, recv_pos=recv_pos,
                     counts=counts)
        )
    plan = HaloPlan(
        n_shards=n_shards, shard_rows=S, h_max=h_max, local_nbr=local_nbr,
        halo_idx=halo_idx, steps=tuple(steps), sent_rows=sent,
        recv_rows=recv,
    )
    _HALO_PLAN_CACHE[cache_key] = plan
    while len(_HALO_PLAN_CACHE) > _HALO_PLAN_CACHE_MAX:
        _HALO_PLAN_CACHE.popitem(last=False)
    return plan


def gather_mixing_weights(
    nbr_idx: np.ndarray, nbr_mask: np.ndarray, degrees: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Metropolis-Hastings weights in gather (per-slot) form.

    Returns ``(w_nbr [N, k_max], w_self [N])`` float64 with
    ``w_nbr[i, s] = 1/(1 + max(deg_i, deg_{nbr[i, s]}))`` on live slots
    (0 on padding) and ``w_self = 1 − Σ_s w_nbr`` — elementwise the same
    values as ``metropolis_hastings_weights`` read at (i, nbr[i, s]) and
    (i, i), never materializing the [N, N] matrix. ``W x`` is then
    ``w_self·x + Σ_s w_nbr[:, s]·x[nbr[:, s]]``: O(N·k_max·d).
    """
    deg = np.asarray(degrees, dtype=np.float64)
    pair = np.maximum(deg[:, None], deg[nbr_idx])
    w_nbr = np.where(nbr_mask, 1.0 / (1.0 + pair), 0.0)
    w_self = 1.0 - w_nbr.sum(axis=1)
    return w_nbr, w_self


# The most rows one chunk of the gather mixing's live list holds: one trip
# of its loop gathers a chunk. Shorter chunks waste fewer rows on the
# padding that ends a slot's run (half a chunk a slot on average) and make
# more trips; settled on the chip at the drawn-graph cell's size (PERF.md
# section 6, PR 38).
GATHER_CHUNK_ROWS = 16384


def gather_chunk_rows(n: int) -> int:
    """The chunk length for a graph of ``n`` nodes: the rows split evenly
    into the fewest chunks of at most ``GATHER_CHUNK_ROWS`` (a small graph:
    one chunk a slot, the chunk the whole table row)."""
    return -(-n // -(-n // GATHER_CHUNK_ROWS))


def live_slot_chunks(
    nbr_idx: np.ndarray, nbr_mask: np.ndarray, degrees: np.ndarray
) -> dict:
    """The table's LIVE (slot, row) pairs as the gather mixing walks them
    (``ops/mixing.py``), with their Metropolis-Hastings weights.

    A padded slot is fetched and multiplied like a live one, so the rows
    are taken in order of FALLING degree (stable: a regular graph keeps its
    order): every row lists its live slots first, hence slot s is live on
    the first n_s rows of that order, n_s the number of rows of degree > s,
    and on no other. Each slot's run of n_s rows is cut into chunks of
    ``C = gather_chunk_rows(n)`` rows that start at multiples of C; the
    last chunk of a run is filled from the table itself (padded slots,
    self-pointing and weighing 0) and, past the table's end, with row 0 at
    weight 0. Returns host arrays, slot after slot:

    - ``nbr`` int32 ``[n_chunks, C]``: the neighbours, in the nodes' OWN
      numbering (the models are gathered as they are carried);
    - ``w_nbr`` float64 ``[n_chunks, C]``: ``gather_mixing_weights``' per
      slot weights, 0 on every fill entry;
    - ``row0`` int32 ``[n_chunks]``: the position, in the degree order, of
      each chunk's first row;
    - ``w_self`` float64 ``[n]``, in the nodes' own numbering;
    - ``inverse`` int32 ``[n]``: each node's position in the degree order;
      left out where that order is the nodes' own (a regular graph).
    """
    n, k_max = nbr_idx.shape
    deg = np.asarray(degrees)
    slots = np.arange(k_max)
    if not np.array_equal(nbr_mask, slots < deg[:, None]):
        raise ValueError(
            "the gather mixing needs every row of the neighbor table to "
            "list its live slots first, as neighbor_table packs them"
        )
    w_nbr, w_self = gather_mixing_weights(nbr_idx, nbr_mask, deg)
    order = np.argsort(-deg, kind="stable")
    chunk = gather_chunk_rows(n)
    blocks = -(-n // chunk)

    def by_chunk(table):
        out = np.zeros((k_max, blocks * chunk), table.dtype)
        out[:, :n] = table[order].T
        return out.reshape(k_max, blocks, chunk)

    live_rows = (deg[:, None] > slots).sum(axis=0)  # n_s
    # (slot 0 keeps a chunk on a graph with no edge: the list is never empty)
    per_slot = np.maximum(-(-live_rows // chunk), slots == 0)
    slot = np.repeat(slots, per_slot)
    block = np.arange(slot.size) - np.repeat(
        np.cumsum(per_slot) - per_slot, per_slot
    )
    chunks = {
        "nbr": by_chunk(nbr_idx)[slot, block],
        "w_nbr": by_chunk(w_nbr)[slot, block],
        "row0": (block * chunk).astype(np.int32),
        "w_self": w_self,
    }
    if not np.array_equal(order, np.arange(n)):
        inverse = np.empty(n, dtype=np.int32)
        inverse[order] = np.arange(n, dtype=np.int32)
        chunks["inverse"] = inverse
    return chunks


def metropolis_hastings_weights(adjacency: np.ndarray) -> np.ndarray:
    """Metropolis-Hastings mixing matrix from an adjacency matrix.

    W_ij = 1 / (1 + max(deg_i, deg_j)) for edges, W_ii = 1 - Σ_j W_ij.
    Parity: reference trainer.py:118-126. Vectorized instead of the
    reference's per-neighbor Python loops.
    """
    degrees = adjacency.sum(axis=1)
    pairwise_max = np.maximum(degrees[:, None], degrees[None, :])
    W = adjacency / (1.0 + pairwise_max)
    np.fill_diagonal(W, 0.0)
    np.fill_diagonal(W, 1.0 - W.sum(axis=1))
    return W


def column_stochastic_weights(adjacency: np.ndarray) -> np.ndarray:
    """Uniform-out-weight column-stochastic mixing matrix (push-sum gossip).

    Node j splits its mass equally over its out-neighbors and itself:
    A_ij = 1/(1 + outdeg_j) for every edge j → i and for i = j. Columns sum
    to 1 by construction (mass conservation — the invariant push-sum's
    weight debiasing rests on, Nedić-Olshevsky 2016 §II). This is the
    standard construction when nodes know only their OUT-degree, the honest
    information model for asymmetric links.
    """
    out_degrees = adjacency.sum(axis=0)
    A = adjacency / (1.0 + out_degrees[None, :])
    np.fill_diagonal(A, 1.0 / (1.0 + out_degrees))
    return A


def build_topology(
    name: str,
    n: int,
    *,
    erdos_renyi_p: float = 0.4,
    seed: int = 0,
    impl: str = "dense",
    sampler: str = "dense",
) -> Topology:
    """Build a named topology over ``n`` workers.

    Undirected names get MH mixing weights; directed names
    (``directed_ring``, ``directed_erdos_renyi``) get column-stochastic
    uniform-out weights (the push-sum setting).

    ``impl``: 'dense' materializes the [N, N] adjacency + mixing matrix
    (the historical representation); 'neighbor' builds the matrix-free
    padded neighbor table instead (``build_neighbor_topology`` — the
    federated-scale route, docs/PERF.md §14). Callers resolve 'auto'
    policy via ``config.resolved_topology_impl()`` before calling.

    ``sampler`` (matrix-free Erdős–Rényi only) picks the 'dense'
    bitwise-reference or 'sparse' O(N·k_max) constructor; callers resolve
    'auto' via ``config.resolved_topology_sampler()``. The dense [N, N]
    representation has exactly one sampler — requesting 'sparse' with
    ``impl='dense'`` is a contradiction and raises.
    """
    if impl == "neighbor":
        return build_neighbor_topology(
            name, n, erdos_renyi_p=erdos_renyi_p, seed=seed, sampler=sampler
        )
    if impl != "dense":
        raise ValueError(f"Unknown topology impl: {impl!r}")
    if sampler != "dense":
        raise ValueError(
            "the dense [N, N] representation replays its own uniform "
            f"stream — sampler={sampler!r} only exists on the matrix-free "
            "path (impl='neighbor')"
        )
    if name in ("directed_ring", "directed_erdos_renyi"):
        adj = (
            _directed_ring_adjacency(n)
            if name == "directed_ring"
            else _directed_erdos_renyi_adjacency(n, erdos_renyi_p, seed)
        )
        topo = Topology(
            name=name,
            n=n,
            adjacency=adj,
            degrees=adj.sum(axis=0),  # out-degrees (column sums)
            mixing_matrix=column_stochastic_weights(adj),
            directed=True,
        )
        topo.validate()
        return topo

    grid_shape: Optional[tuple[int, int]] = None
    if name == "ring":
        adj = _ring_adjacency(n)
    elif name == "grid":
        side = int(math.isqrt(n))
        if side * side != n:
            # Parity: reference trainer.py:100-102 raises for non-square N.
            raise ValueError(f"grid topology requires a perfect square, got {n}")
        adj = _torus_adjacency(side, side)
        grid_shape = (side, side)
    elif name == "fully_connected":
        adj = np.ones((n, n)) - np.eye(n)
    elif name == "erdos_renyi":
        adj = _erdos_renyi_adjacency(n, erdos_renyi_p, seed)
    elif name == "chain":
        adj = _chain_adjacency(n)
    elif name == "star":
        adj = _star_adjacency(n)
    else:
        raise ValueError(f"Unknown topology: {name!r}")

    topo = Topology(
        name=name,
        n=n,
        adjacency=adj,
        degrees=adj.sum(axis=1),
        mixing_matrix=metropolis_hastings_weights(adj),
        grid_shape=grid_shape,
    )
    topo.validate()
    return topo


# One graph a structural identity a process (ISSUE 36): a drawn graph at
# 2^18 nodes is seconds of host code (the sampler, its connectivity check,
# the packing, ``validate``, the power iteration of ``spectral_gap``), and
# a sweep calls ``jax_backend.run`` on one graph many times. Keyed as
# ``config.structural_dict`` keys a graph: the name, n, the resolved
# representation and, for the drawn ones alone, p, the resolved topology
# seed and the resolved sampler (a ring is one graph whatever they say).
# MATRIX-FREE graphs only, the ones whose making is seconds and whose
# tables are O(N·k_max): a dense ``Topology`` carries ``[N, N]`` matrices
# (hundreds of MB at N near 4096), is made anew for every call as before,
# and stays its caller's to write into. The few most recently used are
# kept, their host arrays made read-only.
_TOPOLOGY_CACHE: "collections.OrderedDict[tuple, Topology]" = (
    collections.OrderedDict()
)
_TOPOLOGY_CACHE_MAX = 4
_TOPOLOGY_CACHE_LOCK = threading.Lock()  # the serving plane calls from threads


def cached_topology(
    name: str,
    n: int,
    *,
    erdos_renyi_p: float = 0.4,
    seed: int = 0,
    impl: str = "dense",
    sampler: str = "dense",
) -> tuple[Topology, bool]:
    """``build_topology`` with the same arguments, a matrix-free graph made
    once a process for one structural identity: ``(topology, hit)``,
    ``hit`` False where this call made it."""
    drawn = name in RANDOM_TOPOLOGIES
    key = (
        name, int(n), impl,
        float(erdos_renyi_p) if drawn else None,
        int(seed) if drawn else None,
        sampler if drawn else None,
    )
    with _TOPOLOGY_CACHE_LOCK:
        topo = _TOPOLOGY_CACHE.get(key)
        if topo is not None:
            _TOPOLOGY_CACHE.move_to_end(key)
            return topo, True
    topo = build_topology(
        name, n, erdos_renyi_p=erdos_renyi_p, seed=seed, impl=impl,
        sampler=sampler,
    )
    if not topo.is_matrix_free:
        return topo, False
    for field in dataclasses.fields(topo):
        # Shared by every later call: a write is a fault, loudly.
        leaf = getattr(topo, field.name)
        if isinstance(leaf, np.ndarray):
            leaf.setflags(write=False)
    with _TOPOLOGY_CACHE_LOCK:
        _TOPOLOGY_CACHE[key] = topo
        while len(_TOPOLOGY_CACHE) > _TOPOLOGY_CACHE_MAX:
            _TOPOLOGY_CACHE.popitem(last=False)
    return topo, False


def ring_spectral_gap_closed_form(n: int) -> float:
    """Closed-form spectral gap of the MH ring (all degrees 2 ⇒ W_ij = 1/3).

    Eigenvalues of W are (1 + 2cos(2πk/n))/3; ρ = max_{k≠0} |λ_k|.
    Matches the report's §III-A value 0.0209 for n = 25.
    """
    if n < 3:
        return 1.0
    lambdas = (1.0 + 2.0 * np.cos(2.0 * np.pi * np.arange(1, n) / n)) / 3.0
    return float(1.0 - np.max(np.abs(lambdas)))


def directed_ring_spectral_gap_closed_form(n: int) -> float:
    """Closed-form spectral gap of the uniform-out directed ring.

    Out-degree 1 everywhere ⇒ A = (I + P)/2 with P the cyclic shift.
    Eigenvalues are (1 + e^{2πik/n})/2 with modulus cos(πk/n), so
    ρ = cos(π/n) and the gap is 1 − cos(π/n) ≈ π²/(2n²).
    """
    if n < 2:
        return 1.0
    return float(1.0 - np.cos(np.pi / n))


def torus_spectral_gap_closed_form(side: int) -> float:
    """Closed-form spectral gap of the MH torus (degree 4 ⇒ off-diag 1/5).

    Eigenvalues are (1 + 2cos(2πj/s) + 2cos(2πk/s))/5 over j,k.
    Matches the report's §III-A value 0.2764 for s = 5.
    """
    js = np.arange(side)
    cj = 2.0 * np.cos(2.0 * np.pi * js / side)
    lam = (1.0 + cj[:, None] + cj[None, :]) / 5.0
    lam = lam.ravel()
    lam_sorted = np.sort(np.abs(lam))
    return float(1.0 - lam_sorted[-2])
