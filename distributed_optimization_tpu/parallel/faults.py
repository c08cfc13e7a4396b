"""Failure injection: time-varying gossip over dropped edges and stragglers.

The reference has no failure model — its synchronous lockstep loop cannot
lose a worker (SURVEY.md §5.3); its report only *discusses* the parameter
server as a single point of failure. Here two failure modes are first-class,
jit-compatible simulations:

- **link failure** (``drop_prob``): each iteration, every edge of the base
  topology independently drops with probability p (a symmetric draw — both
  endpoints agree the link is down);
- **stragglers / node failure** (``straggler_prob``): each iteration, every
  node independently sits the round out with probability q — it exchanges
  nothing (all incident edges drop) and, in the backend, its state is frozen
  for the iteration (no local gradient step either).

Both of those are MEMORYLESS per-iteration coin flips. Real decentralized
systems fail in *bursts*: links flap for stretches and nodes crash, stay
down for many rounds, then rejoin with stale state. Two PERSISTENT
(temporally-correlated) fault processes share the same interface:

- **bursty link failures** (``burst_len >= 1``): each edge follows an
  independent two-state Markov chain (Gilbert '60 / Elliott '63 channel
  model) parameterized by the MARGINAL drop rate ``drop_prob`` plus the
  burst-length multiplier ``burst_len``.  The transition thresholds are
  P(down_t | up_{t-1}) = p/B and P(down_t | down_{t-1}) = 1 − (1−p)/B, so
  the stationary drop rate is exactly p for EVERY B (matched-marginal by
  construction) while the mean burst length is B/(1−p) — B times the iid
  chain's.  B = 1 makes both thresholds p, i.e. next-state independent of
  current state: the chain consumes the SAME uniform draws as the iid
  sampler and compares them against the SAME threshold, so ``burst_len=1``
  reduces *bitwise* to today's iid edge drops.
- **crash–recovery churn** (``mttf``/``mttr``): each node follows a
  two-state Markov chain with geometric holding times — mean up-time
  ``mttf`` rounds (P(crash) = 1/mttf) and mean outage ``mttr`` rounds
  (P(stay down) = 1 − 1/mttr); stationary downtime = mttr/(mttf+mttr).
  Down nodes exchange nothing and take no local step (the straggler freeze,
  now spanning whole outages).  The iid straggler model is the point
  mttf = 1/q, mttr = 1/(1−q) — both thresholds collapse to q, consuming
  the same draws as the iid straggler sampler, so those values reduce
  *bitwise* to ``straggler_prob=q``.  The ``rejoin`` policy decides what a
  node resumes with after an outage: ``'frozen'`` keeps its stale
  pre-crash state (the staleness stress test — this is what plain freeze
  gives for free), ``'neighbor_restart'`` warm-restarts its model row from
  the realized-neighborhood average on the rejoin round (trading exact
  average preservation for a consensus reset after long outages).

Persistent processes are realized as PRECOMPUTED ``[horizon]``-indexed
fault timelines (``build_fault_timeline``): the per-(iteration, edge/node)
uniform draws still come purely from (seed, t) via counter-based keys —
identical to the on-the-fly samplers — but the chain state is unrolled once
at setup into ``[horizon, ·]`` arrays (over a neighbor table they stay on
the device that unrolled them; ``FaultTimeline``), so per-iteration access
is a jit-gatherable ``timeline[t]`` with NO carried RNG or chain state.  Checkpoint/resume
therefore stays exact (a resumed run rebuilds the identical timeline from
the config), and the numpy oracle backend consumes the SAME timeline while
implementing all mask/weight math independently.  Why correlated faults
matter at the same marginal rate: the time-varying-gossip analyses this
repo leans on (Koloskova et al. '20 undirected; Nedić–Olshevsky '16
directed) bound convergence by WINDOWED connectivity (every B-window's
union graph connected), not by the marginal drop rate — bursts stretch the
effective window B̂ (see ``windowed_connectivity``), so convergence
degrades with burst length even though the average number of dropped edges
is identical.  ``examples/bench_churn.py`` measures exactly that.

A third *scheduling* mode shares the machinery:

- **one-peer randomized gossip** (``one_peer=True``): instead of averaging
  with ALL surviving neighbors, each node proposes one uniformly random
  neighbor and an edge activates iff the proposal is mutual (Boyd et al.
  '06 randomized gossip, pairwise-averaging form). The realized W_t is
  0.5·(I + P_t) for the involution P_t of matched pairs — each node
  exchanges at most ONE model per iteration, the extreme
  communication-frugality point of the gossip spectrum.

Synchronous gossip runs over the surviving graph with Metropolis–Hastings
weights recomputed on realized degrees; an isolated or inactive node's row
collapses to identity. DIRECTED topologies (round 5) instead drop each
one-way link independently and renormalize each node's surviving
OUT-weights column-stochastically (``column_stochastic_weights``) — the
Nedić-Olshevsky time-varying directed setting push-sum is analyzed under;
every realization conserves total mass (columns sum to 1), which is the
invariant push-sum's debiasing needs, in place of the undirected case's
doubly stochastic average preservation. For UNDIRECTED topologies (synchronous MH recomputation and every matching
schedule) this is the time-varying-graph setting of Koloskova et al. '20
(reference report ref [13]): W_t stays symmetric and doubly stochastic for
every realization, so the network average is preserved and D-SGD and
DIGing-style gradient tracking remain convergent under their
time-varying-gossip analyses — the directed path above intentionally trades
that invariant for column-stochastic mass conservation. For gradient tracking this is not just the
citation: the tracking invariant mean(y_t) = mean(g_t) survives every fault
mode because (a) each realized W_t is doubly stochastic and (b) the
backend's straggler freeze covers ALL state leaves with the frozen node's
mixing row collapsed to identity — verified numerically to accumulation
roundoff through the real backend paths
(tests/test_faults.py::test_gt_tracking_invariant_survives_faults) and
measured on-chip (examples/bench_faults.py gt_* rows). EXTRA does NOT
compose (its fixed-point argument needs a static W — it is rejected
alongside ADMM/CHOCO, see ``Algorithm.supports_edge_faults``).

Fault masks, realized adjacencies, MH weights, and the realized-floats
accounting are always computed in float32 regardless of the run dtype:
under bfloat16 (8 mantissa bits) edge counts above ~256 quantize and MH row
sums pick up off-by-ulp mass, corrupting both the mixing invariants and the
"honest" comms metric. Only the mixed MODEL values are cast to the run
dtype.

**Matrix-free draws.** On a neighbor-table (matrix-free) topology no
[N, N] uniform matrix exists, so the stream is its own, equally seed-pure:
round t (counted from 0) draws ONE float32 uniform per edge,
``uniform(fold_in(fold_in(key(seed), 0x0FA17), t), (E,))``, and one per
node, ``uniform(fold_in(fold_in(key(seed), 0x57A66), t), (N,))``; edge e is
up iff ``u_e >= p`` and node i takes part iff ``u_i >= q``. Edge e is row e
of ``_edge_list(topo)``: the ``i < j`` entries of the ASCENDING neighbor
table read row by row — on a ring of N nodes (0, 1), (0, N−1), (1, 2),
(2, 3), …, (N−2, N−1), which is NOT {i, i+1} in turn (node 0's row holds
both its neighbors before node 1's adds (1, 2)). A link carries a model iff
it is up and both its ends take part: ``live_ij = up_ij · m_i · m_j``,
``w_ij = live_ij / (1 + max(deg_i, deg_j))`` on realized degrees, the row
remainder on the diagonal. Memoryless faults are drawn inside the step by
exactly this rule; persistent processes unroll the same draws into a
timeline (``build_fault_timeline``), so at burst_len = 1 or plain
``straggler_prob`` the two are one realization, bit for bit
(tests/test_fault_draws.py). Where the neighbor table is a ring's
(``_table_is_a_ring``) the fault layer reads that draw, and a timeline's
row, by three static slices of ``_edge_list``'s order — the bit of edge
{i, i+1 mod N} is ``concat(up[0:1], up[2:N], up[1:2])[i]`` — and its
neighbours by rolls (``_make_shift_faulty_mixing``); on every other graph
through the (node, slot) → edge-id table and the neighbor table
(``_make_gather_faulty_mixing``). One realization either way.

**Device scope.** What a faulty round costs beyond a fault-free one is
traced under ``dopt.faults`` (``observability/device_scopes.py``): the draws
or timeline reads, liveness, realized degrees and weights, a warm restart.
The weighted sum over the neighbours stays the caller's ``dopt.gossip``.

Masks are derived purely from (fault key, iteration) — like batch sampling,
fault realizations are reproducible and checkpoint/resume-safe with no
carried RNG state.  The underlying uniform draws are EXPLICIT float32
(independent of the run dtype and of x64 mode), so the same (seed, t)
yields the same fault realization on every backend and in every precision —
the property the timeline precompute and the numpy-oracle parity rely on.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from distributed_optimization_tpu.observability import device_scopes
from distributed_optimization_tpu.parallel.topology import (
    Topology,
    _table_is_a_ring,
)

# Allowed rejoin policies after a crash-recovery outage (config and CLI
# derive from this constant): 'frozen' resumes the stale pre-crash state,
# 'neighbor_restart' warm-restarts the model row from the realized-
# neighborhood average on the rejoin round.
REJOIN_POLICIES = ("frozen", "neighbor_restart")


@dataclasses.dataclass(frozen=True)
class FaultyMixing:
    """Per-iteration mixing operators over a randomly failing topology.

    ``mix(t, x)``: W_t x with W_t the MH matrix of the surviving graph.
    ``neighbor_sum(t, x)``: A_t x over surviving edges.
    ``realized_degree_sum(t)``: Σ realized deg_i at iteration t (multiply by
    the per-edge payload downstream for the floats-transmitted metric).
    ``active(t)``: [N] 0/1 node-participation mask (all-ones when
    straggler_prob == 0); the backend freezes inactive rows for the step.
    """

    mix: Callable[[jax.Array, jax.Array], jax.Array]
    neighbor_sum: Callable[[jax.Array, jax.Array], jax.Array]
    realized_degree_sum: Callable[[jax.Array], jax.Array]
    active: Callable[[jax.Array], jax.Array]
    drop_prob: float
    straggler_prob: float
    # ``realized_adjacency(t)``: the surviving [N, N] 0/1 graph at t —
    # consumed by the Byzantine robust-aggregation layer so attacks and
    # defenses run over the same per-iteration graph as the mixing. None
    # for matching schedules (one_peer/round_robin), whose single-partner
    # exchanges cannot realize a screening budget (config rejects the
    # combination).
    realized_adjacency: Optional[Callable[[jax.Array], jax.Array]] = None
    # ``make_neighbor_liveness(nbr_idx, nbr_mask)``: build the GATHER form
    # of the realized adjacency for the degree-bounded robust-aggregation
    # path — returns ``live(t) -> [N, k_max]`` float32 per-incident-edge
    # liveness bits over the topology's static padded neighbor table
    # (``parallel/topology.py::neighbor_table``). Bit-for-bit the same
    # realization as ``realized_adjacency(t)`` gathered per slot: the
    # timeline path indexes the precomputed [horizon, E] edge chains
    # through a (node, slot) → edge-id table instead of scattering a dense
    # [N, N] matrix; the memoryless path consumes the SAME counter-based
    # (seed, t) uniform draw as the dense sampler, gathered at the slot's
    # (i, j) entry. None for matching schedules (no screening budget is
    # realizable) and directed graphs (no gather screening path).
    make_neighbor_liveness: Optional[Callable[..., Callable]] = None
    # --- persistent fault processes (None/0/False when memoryless) ---
    # Crash-recovery churn is active (the backend must freeze DOWN nodes'
    # state, exactly like stragglers, for the whole outage).
    churn_active: bool = False
    # Rejoin policy in force ('frozen' needs no machinery beyond the
    # freeze; 'neighbor_restart' supplies ``rejoin_restart``).
    rejoin: str = "frozen"
    # ``rejoin_restart(t, x)``: on rejoin rounds, replace a rejoining
    # node's model row with its realized-neighborhood average (rows of
    # nodes that are not rejoining — or have no realized neighbors — pass
    # through untouched). None unless rejoin == 'neighbor_restart'.
    rejoin_restart: Optional[Callable[[jax.Array, jax.Array], jax.Array]] = None
    # Per-round partial participation (client sampling, docs/PERF.md §14)
    # is active: ``active(t)`` composes the presampled participation mask
    # into the node-availability row, and the backend must freeze
    # sampled-out nodes' state exactly like stragglers.
    participation_active: bool = False
    # The precomputed timeline backing this mixing (None on the memoryless
    # on-the-fly path; its leaves on the device or the host as
    # ``FaultTimeline`` says) — exposed for diagnostics
    # (``node_downtime``, ``windowed_connectivity``) and tests.
    timeline: Optional["FaultTimeline"] = None
    # Unsharded matrix-free forms only, else None: ``tables`` is the pytree
    # of device arrays the operators above read, placed once when the
    # mixing is built — in the gather form the neighbor table, its mask
    # and the (node, slot) → edge-id map; in either form, for a persistent
    # process, the ``[horizon, ·]`` timeline leaves; so the shift form
    # under memoryless faults holds an EMPTY dict — and ``bind(tb)`` the
    # same operators over another copy of it, such as the tracers a jitted
    # program receives ``tables`` as. ``jax_backend._run`` hands the tables
    # to the scan as ARGUMENTS; the unbound operators make them constants
    # of whatever program traces them, which at 2^18 workers is hundreds
    # of megabytes of executable (ROADMAP A9).
    tables: Optional[dict] = None
    # None too on a mixing that ``bind`` itself returned (``_rebindable``).
    bind: Optional[Callable[[dict], "FaultyMixing"]] = None
    # How those operators reach a neighbour's value and an incident edge's
    # bit: ``'shift'`` where the neighbor table is a ring's (rolls and
    # three slices of the edge draw, no table) and ``'gather'`` on every
    # other matrix-free graph (index tables); decided from the table when
    # the mixing is built, by no option (``_table_is_a_ring``). The
    # ``dopt.run`` root's ``fault_mixing``. None off the unsharded
    # neighbor table.
    addressing: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class FaultTimeline:
    """Precomputed ``[horizon]``-indexed fault realizations.

    Pure function of (topology, horizon, seed, fault params): the uniform
    draw at (t, edge/node) is the same counter-based float32 draw the
    on-the-fly samplers consume, with the Markov chain state unrolled once
    at build time — so lookups are jit-gatherable and resume-exact with no
    carried RNG.  ``edge_up[t, e]`` indexes the base topology's edge list
    ``edge_index`` ([E, 2]; i<j rows for undirected graphs, ordered (i, j)
    receiver/sender pairs for directed ones).  ``node_up[t, i]`` is node
    availability; ``rejoin[t, i]`` marks the first up-round after an
    outage.  Entries are None for fault modes that are not active.

    Where the ``[horizon, ·]`` leaves live: built over a matrix-free
    topology they are the DEVICE arrays the chains' scans returned (the
    program that reads them by ``[t]`` runs there: nothing is fetched and
    placed again); built over a dense one, injected, stacked or rebuilt by a
    host consumer (``timeline_for_config``) they are host arrays. A host
    consumer of a timeline it did not build fetches when it reads:
    ``host()``.
    """

    horizon: int
    directed: bool
    edge_index: Optional[np.ndarray] = None  # [E, 2] int32, always host
    edge_up: Optional[np.ndarray] = None     # [horizon, E] bool
    node_up: Optional[np.ndarray] = None     # [horizon, N] bool
    rejoin: Optional[np.ndarray] = None      # [horizon, N] bool
    # Per-round participation mask (client sampling, iid per (round,
    # node) at rate ``participation_rate`` from its own key stream;
    # docs/PERF.md §14). Composes with ``node_up`` by AND: a round's
    # realized availability is churn-up AND sampled-in. Sampling is NOT
    # an outage — no rejoin events — so ``rejoin`` stays a pure
    # crash-recovery record.
    part_up: Optional[np.ndarray] = None     # [horizon, N] bool

    def host(self) -> "FaultTimeline":
        """This timeline with every leaf a host array (itself where they
        already are)."""
        held = {
            name: getattr(self, name) for name in TIMELINE_LEAVES
            if isinstance(getattr(self, name), jax.Array)
        }
        if not held:
            return self
        return dataclasses.replace(
            self, **{name: np.asarray(leaf) for name, leaf in held.items()}
        )


# The ``[horizon, ·]`` leaves of a ``FaultTimeline``.
TIMELINE_LEAVES = ("edge_up", "node_up", "rejoin", "part_up")


def sample_surviving_adjacency(key, adjacency: jax.Array, drop_prob: float):
    """Symmetric iid edge-drop mask applied to a 0/1 adjacency matrix.

    Draws are explicit float32 regardless of x64 mode, so the realization
    is a function of (key, shape) alone — the timeline precompute and the
    numpy oracle reproduce it bit-for-bit under any run dtype."""
    n = adjacency.shape[0]
    u = jax.random.uniform(key, (n, n), dtype=jnp.float32)
    u = jnp.triu(u, 1)
    u = u + u.T  # symmetric: both endpoints see the same draw
    return jnp.where(u >= drop_prob, adjacency, jnp.zeros_like(adjacency))


def sample_surviving_directed_adjacency(
    key, adjacency: jax.Array, drop_prob: float
):
    """Independent iid drop per DIRECTED edge (no symmetrization).

    Unlike the undirected sampler, the j→i and i→j links (when both exist)
    fail independently — one-way links are exactly what the directed fault
    setting models (Nedić-Olshevsky 2016 time-varying directed graphs)."""
    u = jax.random.uniform(key, adjacency.shape, dtype=jnp.float32)
    return jnp.where(u >= drop_prob, adjacency, jnp.zeros_like(adjacency))


def column_stochastic_weights(adjacency: jax.Array) -> jax.Array:
    """Uniform-out-weight column-stochastic matrix for a realized directed
    graph (jit-compatible).

    Each node j re-splits its mass equally over its SURVIVING out-neighbors
    and itself: W_ij = 1/(1 + outdeg_j) on realized edges, diagonal = the
    column remainder (exactly 1/(1 + outdeg_j), so an isolated node keeps
    all its mass). Convention matches ``parallel/topology.py``:
    ``adjacency[i, j] = 1`` iff j sends to i, so out-degrees are COLUMN
    sums and ``W @ x`` aggregates received mass. This is the same rule the
    static directed topology builder uses, recomputed per realization — the
    sender-side renormalization push-sum's time-varying-directed analysis
    assumes (each node knows which of its out-links delivered). Columns sum
    to 1 for every realization, so Σ_i (Wx)_i = Σ_j x_j: the mass
    conservation push-sum's debiasing relies on survives every fault draw.
    """
    out_deg = jnp.sum(adjacency, axis=0)
    W = adjacency / (1.0 + out_deg)[None, :]
    return W + jnp.diag(1.0 - jnp.sum(W, axis=0))


def metropolis_hastings_weights(adjacency: jax.Array) -> jax.Array:
    """MH mixing matrix for an arbitrary 0/1 adjacency (jit-compatible).

    W_ij = 1/(1 + max(d_i, d_j)) on edges, diagonal = row remainder — the
    same rule the static topology builder uses (reference
    ``trainer.py:118-126``), but recomputed on-device for each realization.
    Symmetric and doubly stochastic for any undirected graph, including
    isolated nodes (row collapses to W_ii = 1).
    """
    deg = jnp.sum(adjacency, axis=1)
    pair = 1.0 / (1.0 + jnp.maximum(deg[:, None], deg[None, :]))
    W = adjacency * pair
    return W + jnp.diag(1.0 - jnp.sum(W, axis=1))


def _matching_ops(partner_fn):
    """Mixing closures for any matching schedule given partner_fn(t).

    W_t = 0.5 (I + P_t): pairwise averaging with the matched peer (identity
    row for unmatched nodes). Shared by the one-peer randomized and
    round-robin deterministic schedules.
    """

    def mix(t, x):
        return (0.5 * (x + x[partner_fn(t)])).astype(x.dtype)

    def neighbor_sum(t, x):
        p = partner_fn(t)
        matched = (p != jnp.arange(p.shape[0])).astype(x.dtype)
        return (x[p] * matched.reshape((-1,) + (1,) * (x.ndim - 1))).astype(
            x.dtype
        )

    def realized_degree_sum(t):
        # float32 regardless of run dtype: the downstream floats accounting
        # multiplies by the payload and sums over chunks, which overflows
        # int32 at scale and quantizes above ~256 in bfloat16.
        p = partner_fn(t)
        return jnp.sum((p != jnp.arange(p.shape[0])).astype(jnp.float32))

    return mix, neighbor_sum, realized_degree_sum


def make_round_robin_mixing(topo: Topology) -> FaultyMixing:
    """Deterministic matching schedule (``parallel/matchings.py`` phases) as
    time-varying mixing ops, same interface as ``make_faulty_mixing``."""
    from distributed_optimization_tpu.parallel.matchings import (
        round_robin_partners,
    )

    partners = jnp.asarray(round_robin_partners(topo), dtype=jnp.int32)
    n_phases, n = partners.shape
    mix, neighbor_sum, realized_degree_sum = _matching_ops(
        lambda t: partners[t % n_phases]
    )
    return FaultyMixing(
        mix=mix,
        neighbor_sum=neighbor_sum,
        realized_degree_sum=realized_degree_sum,
        active=lambda t: jnp.ones(n, dtype=jnp.float32),
        drop_prob=0.0,
        straggler_prob=0.0,
    )


def sample_one_peer_matching(key, adjacency: jax.Array) -> jax.Array:
    """Mutual-proposal random matching: partner[i] (an involution; self if
    unmatched). Each node proposes a uniformly random neighbor; an edge
    activates iff both endpoints proposed each other."""
    n = adjacency.shape[0]
    idx = jnp.arange(n)
    scores = (
        jax.random.uniform(key, adjacency.shape, dtype=jnp.float32)
        * adjacency
    )
    prop = jnp.argmax(scores, axis=1)
    # Isolated rows (all-zero scores) would spuriously propose node 0.
    prop = jnp.where(jnp.sum(adjacency, axis=1) > 0, prop, idx)
    mutual = prop[prop] == idx
    return jnp.where(mutual, prop, idx)


def iid_equivalent_churn(straggler_prob: float) -> tuple[float, float]:
    """The (mttf, mttr) point at which crash-recovery churn reduces bitwise
    to iid stragglers at rate q: both chain thresholds collapse to q when
    mttf = 1/q and mttr = 1/(1−q) (stationary downtime exactly q)."""
    if not 0.0 < straggler_prob < 1.0:
        raise ValueError(
            f"straggler_prob must be in (0, 1), got {straggler_prob}"
        )
    return 1.0 / straggler_prob, 1.0 / (1.0 - straggler_prob)


def _edge_list(topo: Topology) -> np.ndarray:
    """[E, 2] int32 edge list of the base topology: one row per undirected
    edge (i < j — the triu entry whose draw both endpoints share in the iid
    sampler), or per one-way link (i, j) for directed graphs.

    Matrix-free topologies enumerate the same i < j rows from the
    ascending neighbor table, read row by row, without touching a dense
    [N, N] array. That order IS the numbering of the matrix-free per-edge
    draws (module docstring, "Matrix-free draws"): on a ring of N nodes
    (0, 1), (0, N−1), (1, 2), (2, 3), …, (N−2, N−1) — not {i, i+1} in turn.
    """
    if topo.is_matrix_free:
        rows, slots = np.nonzero(topo.nbr_mask)
        js = topo.nbr_idx[rows, slots]
        keep = rows < js  # each undirected edge once, i < j
        return np.stack([rows[keep], js[keep]], axis=1).astype(np.int32)
    A = np.asarray(topo.adjacency)
    src = np.triu(A, 1) if not topo.directed else A
    ei, ej = np.nonzero(src)
    return np.stack([ei, ej], axis=1).astype(np.int32)


def config_faults_active(config) -> bool:
    """Whether this config runs ANY synchronous node/edge fault process —
    the single definition shared by every consumer that decides to
    rebuild a timeline from a config (live-B̂ heartbeats, the health
    block's realized B̂, incident forensics)."""
    return (
        config.edge_drop_prob > 0.0
        or config.straggler_prob > 0.0
        or config.mttf > 0.0
        or config.participation_rate < 1.0
    )


def timeline_for_config(config, topo: Topology, horizon: int,
                        seed=None) -> FaultTimeline:
    """The canonical config → ``build_fault_timeline`` parameter mapping.

    This mapping IS the bitwise purity contract: the timeline a consumer
    rebuilds host-side (telemetry's realized B̂, the live-B̂ heartbeat
    probe, incident forensics, the replica-batched stacker) must be the
    realization the backend executed, so the burst clamp and the
    straggler-vs-churn exclusivity rule live in exactly one place.
    ``seed`` overrides ``config.seed`` (the replica-batched path passes
    per-replica seeds). Every caller reads the leaves on the host, so they
    are fetched here, once.
    """
    return build_fault_timeline(
        topo, horizon, config.seed if seed is None else seed,
        edge_drop_prob=config.edge_drop_prob,
        burst_len=config.burst_len if config.burst_len >= 1.0 else 1.0,
        straggler_prob=(
            0.0 if config.mttf > 0.0 else config.straggler_prob
        ),
        mttf=config.mttf, mttr=config.mttr,
        participation_rate=config.participation_rate,
    ).host()


def build_fault_timeline(
    topo: Topology,
    horizon: int,
    seed: int,
    *,
    edge_drop_prob: float = 0.0,
    burst_len: float = 1.0,
    straggler_prob: float = 0.0,
    mttf: float = 0.0,
    mttr: float = 0.0,
    participation_rate: float = 1.0,
) -> FaultTimeline:
    """Unroll the per-edge / per-node fault chains into ``[horizon, ·]``
    bool arrays: over a neighbor table the device arrays the scans return,
    over a dense adjacency host arrays (``FaultTimeline``).

    The uniform draw at iteration t is the SAME counter-based float32 draw
    the on-the-fly samplers consume (same key derivation, same shape), so
    chains whose thresholds are state-independent — burst_len == 1, or
    churn at the ``iid_equivalent_churn`` point, or plain ``straggler_prob``
    — reproduce the iid samplers bit for bit.  Survival convention matches
    the samplers: alive iff u >= threshold, where

        edge thresholds:  P(down | up) = p/B,  P(down | down) = 1 − (1−p)/B
        node thresholds:  P(down | up) = 1/mttf, P(down | down) = 1 − 1/mttr
        (or both = q for iid stragglers)

    with the t = 0 state drawn from the stationary marginal (p, resp.
    mttr/(mttf+mttr)) so every burst level is matched-marginal from the
    first iteration.  Memory: one byte per (iteration, edge) plus one per
    (iteration, node) — [horizon, E] + [horizon, N] bool.
    """
    if horizon <= 0:
        raise ValueError(f"timeline horizon must be positive, got {horizon}")
    if burst_len < 1.0:
        raise ValueError(f"burst_len must be >= 1, got {burst_len}")
    if (mttf > 0.0) != (mttr > 0.0):
        raise ValueError("mttf and mttr must be set together")
    if mttf > 0.0 and (mttf < 1.0 or mttr < 1.0):
        raise ValueError(
            f"mttf/mttr are mean holding times in rounds and must be >= 1 "
            f"(got mttf={mttf}, mttr={mttr})"
        )
    if mttf > 0.0 and straggler_prob > 0.0:
        raise ValueError(
            "crash-recovery churn replaces iid stragglers; set one of "
            "(mttf, mttr) / straggler_prob, not both"
        )
    if not 0.0 < participation_rate <= 1.0:
        raise ValueError(
            f"participation_rate must be in (0, 1], got {participation_rate}"
        )
    n = topo.n
    fault_key = jax.random.fold_in(jax.random.key(seed), 0x0FA17)
    node_key = jax.random.fold_in(jax.random.key(seed), 0x57A66)
    # Over a neighbor table the leaves stay the device arrays the scans
    # return; over a dense adjacency they are fetched, as ever.
    keep = (lambda leaf: leaf) if topo.is_matrix_free else np.asarray

    edge_index = None
    edge_up = None
    if edge_drop_prob > 0.0:
        edge_index = _edge_list(topo)
        p = edge_drop_prob
        if burst_len == 1.0:
            # State-independent thresholds — EXACTLY the iid comparison
            # (u >= p), guaranteeing the bitwise reduction regardless of
            # float rounding in the general-B formulas below.
            t_enter = t_stay = t_init = np.float32(p)
        else:
            t_enter = np.float32(p / burst_len)            # P(down | up)
            t_stay = np.float32(1.0 - (1.0 - p) / burst_len)  # P(down|down)
            t_init = np.float32(p)                          # stationary
        n_edges = edge_index.shape[0]

        if topo.is_matrix_free:
            # Matrix-free edge chains (ISSUE-9 satellite): draw ONE
            # float32 uniform per edge per round — the dense path's
            # (n, n) matrix draw IS the quadratic object this
            # representation exists to avoid, so the matrix-free stream
            # is a different (equally seed-pure) realization of the same
            # chain; dense-vs-matrix-free parity tests inject one shared
            # timeline rather than relying on shared draws.
            ups = _unroll_chain(
                fault_key, t_init, t_enter, t_stay,
                size=n_edges, horizon=horizon,
            )
        else:
            ei = jnp.asarray(edge_index[:, 0])
            ej = jnp.asarray(edge_index[:, 1])

            def edge_draw(t):
                # The SAME symmetric (seed, t) matrix draw the on-the-fly
                # iid sampler consumes, read at the edge entries — what
                # makes burst_len=1 reduce bitwise to the memoryless path.
                return jax.random.uniform(
                    jax.random.fold_in(fault_key, t), (n, n),
                    dtype=jnp.float32,
                )[ei, ej]

            ups = _chain_scan(
                edge_draw, t_init, t_enter, t_stay, n_edges, horizon
            )
        edge_up = keep(ups)

    node_up = None
    rejoin = None
    if mttf > 0.0 or straggler_prob > 0.0:
        if mttf > 0.0:
            n_crash = np.float32(1.0 / mttf)           # P(down | up)
            n_stay = np.float32(1.0 - 1.0 / mttr)      # P(down | down)
            n_init = np.float32(mttr / (mttf + mttr))  # stationary downtime
        else:
            n_crash = n_stay = n_init = np.float32(straggler_prob)
        node_up = _unroll_chain(
            node_key, n_init, n_crash, n_stay, size=n, horizon=horizon
        )
        rejoin = keep(_rejoin_rounds(node_up))
        node_up = keep(node_up)

    part_up = None
    if participation_rate < 1.0:
        # Client sampling (docs/PERF.md §14): iid per (round, node) at the
        # configured rate, from its OWN counter-based stream — distinct
        # from the churn/straggler chain, so participation composes with
        # (never perturbs) every other fault realization. Survival
        # convention matches the node chain: in iff u >= 1 − rate, whatever
        # the round before (a chain whose three thresholds are one).
        part_key = jax.random.fold_in(jax.random.key(seed), 0x9AC70)
        p_out = np.float32(1.0 - participation_rate)
        part_up = keep(_unroll_chain(
            part_key, p_out, p_out, p_out, size=n, horizon=horizon
        ))

    return FaultTimeline(
        horizon=horizon,
        directed=topo.directed,
        edge_index=edge_index,
        edge_up=edge_up,
        node_up=node_up,
        rejoin=rejoin,
        part_up=part_up,
    )


def _chain_scan(draw, t_init, t_enter, t_stay, size: int, horizon: int):
    """``[horizon, size]`` bool: a two-state chain a column, unrolled.
    Column c is up in round t iff ``draw(t)[c] >= threshold``, the
    threshold ``t_init`` in round 0 and after it ``t_enter`` where the
    column was up the round before, ``t_stay`` where it was down."""

    def step(up_prev, t):
        thresh = jnp.where(
            t == 0, t_init, jnp.where(up_prev, t_enter, t_stay)
        )
        up = draw(t) >= thresh
        return up, up

    _, ups = jax.lax.scan(
        step, jnp.ones(size, dtype=bool),
        jnp.arange(horizon, dtype=jnp.int32),
    )
    return ups


@functools.partial(jax.jit, static_argnames=("size", "horizon"))
def _unroll_chain(key, t_init, t_enter, t_stay, *, size: int, horizon: int):
    """``_chain_scan`` over one float32 uniform a column a round from
    ``fold_in(key, t)``: every node process, and a neighbor table's edges.
    One executable a (size, horizon) for the process's life: the key and
    the thresholds are arguments, so a later call traces and compiles
    nothing."""
    return _chain_scan(
        lambda t: jax.random.uniform(
            jax.random.fold_in(key, t), (size,), dtype=jnp.float32
        ),
        t_init, t_enter, t_stay, size, horizon,
    )


@jax.jit
def _rejoin_rounds(node_up):
    """``[horizon, N]`` bool: up in round t and down in t − 1, round 0
    held against every node up."""
    prev_up = jnp.concatenate(
        [jnp.ones_like(node_up[:1]), node_up[:-1]], axis=0
    )
    return node_up & ~prev_up


def timeline_counters(timeline: FaultTimeline) -> dict:
    """What a call's ``dopt.run`` root says of the timeline it read:
    ``timeline_placement`` (``device`` where the leaves are the arrays the
    chains' scans returned, ``host`` where they were fetched, injected or
    stacked) and, under a node process, ``down_share`` (the mean of
    ``1 − node_up`` over the horizon) and ``rejoin_rows`` (the set bits of
    ``rejoin``: the rows a ``neighbor_restart`` run is asked to restart);
    under participation sampling ``sampled_out_share`` (the mean of
    ``1 − part_up`` over the horizon).
    Counted where the leaves live; what is fetched is a count a node."""
    leaves = [
        getattr(timeline, name) for name in TIMELINE_LEAVES
        if getattr(timeline, name) is not None
    ]
    out = {
        "timeline_placement": (
            "device" if leaves and all(
                isinstance(leaf, jax.Array) for leaf in leaves
            ) else "host"
        ),
    }
    if timeline.node_up is not None:
        up = _set_bits(timeline.node_up)
        out["down_share"] = 1.0 - up / timeline.node_up.size
        out["rejoin_rows"] = _set_bits(timeline.rejoin)
    if timeline.part_up is not None:
        taking_part = _set_bits(timeline.part_up)
        out["sampled_out_share"] = 1.0 - taking_part / timeline.part_up.size
    return out


def _set_bits(leaf) -> int:
    """The set bits of a ``[horizon, N]`` bool leaf: summed over the rounds
    where the leaf lives (an int32 a node holds any horizon), over the
    nodes on the host."""
    xp = jnp if isinstance(leaf, jax.Array) else np
    return int(np.asarray(xp.sum(leaf, axis=0, dtype=np.int32)).sum())


# --- availability / staleness diagnostics (host-side, over a timeline) ----


def node_downtime(timeline: FaultTimeline) -> np.ndarray:
    """Per-node fraction of rounds spent down over the timeline horizon."""
    if timeline.node_up is None:
        raise ValueError("timeline has no node fault process")
    return 1.0 - np.asarray(timeline.node_up).mean(axis=0)


def outage_stats(timeline: FaultTimeline) -> dict:
    """Aggregate outage statistics: count, mean and max outage length (in
    rounds) across all nodes — the staleness a ``frozen`` rejoin carries."""
    if timeline.node_up is None:
        raise ValueError("timeline has no node fault process")
    lengths: list[int] = []
    node_up = np.asarray(timeline.node_up)
    for i in range(node_up.shape[1]):
        run = 0
        for up in node_up[:, i]:
            if not up:
                run += 1
            elif run:
                lengths.append(run)
                run = 0
        if run:
            lengths.append(run)  # outage still open at the horizon
    return {
        "n_outages": len(lengths),
        "mean_outage_rounds": float(np.mean(lengths)) if lengths else 0.0,
        "max_outage_rounds": int(max(lengths)) if lengths else 0,
    }


def _realized_edge_alive(
    timeline: FaultTimeline, topo: Topology
) -> tuple[np.ndarray, np.ndarray]:
    """([T, E] bool alive-mask, [E, 2] edge list) of per-round realized
    edges: an edge is alive iff its link is up AND both endpoints are up."""
    timeline = timeline.host()
    edges = (
        timeline.edge_index
        if timeline.edge_index is not None
        else _edge_list(topo)
    )
    T = timeline.horizon
    alive = (
        timeline.edge_up.copy()
        if timeline.edge_up is not None
        else np.ones((T, edges.shape[0]), dtype=bool)
    )
    if timeline.node_up is not None:
        alive &= (
            timeline.node_up[:, edges[:, 0]]
            & timeline.node_up[:, edges[:, 1]]
        )
    if timeline.part_up is not None:
        # A sampled-out client exchanges nothing: its incident edges are
        # not realized that round, exactly like a down node's.
        alive &= (
            timeline.part_up[:, edges[:, 0]]
            & timeline.part_up[:, edges[:, 1]]
        )
    return alive, edges


def _union_connected(present: np.ndarray, edges: np.ndarray, n: int) -> bool:
    """Union-find connectivity of the graph with ``edges[present]`` (weak
    connectivity for directed edge lists)."""
    parent = list(range(n))

    def find(a: int) -> int:
        while parent[a] != a:
            parent[a] = parent[parent[a]]
            a = parent[a]
        return a

    comps = n
    for i, j in edges[present]:
        ri, rj = find(int(i)), find(int(j))
        if ri != rj:
            parent[ri] = rj
            comps -= 1
    return comps == 1


def windowed_connectivity(
    timeline: FaultTimeline, topo: Topology
) -> Optional[int]:
    """B̂: the smallest window length B such that EVERY length-B window's
    union of realized graphs is connected (Koloskova et al. '20
    B-connectivity; weak connectivity for directed graphs).

    This is the quantity the time-varying-gossip rates depend on — NOT the
    marginal drop rate — so at matched marginal, B̂ grows with burst length
    and with outage duration.  Returns None if even the full horizon's
    union graph is disconnected (no finite B exists).  Host-side
    diagnostic: O(T · E · log T) worst case via binary search over B with
    a prefix-count sliding union per candidate.
    """
    alive, edges = _realized_edge_alive(timeline, topo)
    n = topo.n
    T = timeline.horizon
    # Prefix counts: window [s, s+B) contains edge e iff counts differ.
    csum = np.concatenate(
        [np.zeros((1, edges.shape[0]), dtype=np.int64),
         np.cumsum(alive, axis=0, dtype=np.int64)],
        axis=0,
    )

    def all_windows_connected(B: int) -> bool:
        for s in range(T - B + 1):
            present = (csum[s + B] - csum[s]) > 0
            if not _union_connected(present, edges, n):
                return False
        return True

    if not all_windows_connected(T):
        return None
    lo, hi = 1, T  # predicate is monotone in B (bigger window ⊇ union)
    while lo < hi:
        mid = (lo + hi) // 2
        if all_windows_connected(mid):
            hi = mid
        else:
            lo = mid + 1
    return lo


def stack_fault_timelines(timelines: list[FaultTimeline]) -> FaultTimeline:
    """Stack per-replica timelines into one with [R, ...] leading axes.

    The replica-batched execution path (``jax_backend.run_batch``) builds
    one timeline per replica seed host-side, stacks them here, threads the
    stacked arrays through ``vmap`` (in_axes=0), and reconstitutes a
    per-replica ``FaultTimeline`` view inside the traced program — so the
    batched fault realizations are the SAME host arrays the sequential
    runs gather from.  ``edge_index`` is topology-static and shared; the
    fault-process structure (which arrays are present) must match across
    replicas (same config, different seeds).
    """
    if not timelines:
        raise ValueError("need at least one timeline to stack")
    t0 = timelines[0]
    for t in timelines[1:]:
        if (
            t.horizon != t0.horizon
            or t.directed != t0.directed
            or (t.edge_up is None) != (t0.edge_up is None)
            or (t.node_up is None) != (t0.node_up is None)
            or (t.part_up is None) != (t0.part_up is None)
        ):
            raise ValueError(
                "timelines disagree in structure (horizon / fault modes); "
                "replica stacking requires one config over many seeds"
            )

    def _stack(field):
        vals = [getattr(t, field) for t in timelines]
        if vals[0] is None:
            return None
        return np.stack([np.asarray(v) for v in vals])

    return FaultTimeline(
        horizon=t0.horizon,
        directed=t0.directed,
        edge_index=t0.edge_index,
        edge_up=_stack("edge_up"),
        node_up=_stack("node_up"),
        rejoin=_stack("rejoin"),
        part_up=_stack("part_up"),
    )


def make_faulty_mixing(
    topo: Topology,
    drop_prob: float,
    seed: int,
    straggler_prob: float = 0.0,
    one_peer: bool = False,
    burst_len: float = 0.0,
    mttf: float = 0.0,
    mttr: float = 0.0,
    rejoin: str = "frozen",
    horizon: Optional[int] = None,
    keys: Optional[tuple] = None,
    timeline: Optional[FaultTimeline] = None,
    participation_rate: float = 1.0,
    mesh=None,
) -> FaultyMixing:
    """Build time-varying mixing operators for a base topology.

    ``mesh`` (ISSUE-11, docs/PERF.md §16): a 1-D worker ``Mesh`` — the
    matrix-free node-process route then runs SHARDED: timeline columns
    are placed per-shard, and the realized-MH gossip round becomes a
    ppermute halo exchange (``make_halo_faulty_mixing``), bitwise the
    unsharded gather realization. Dense topologies reject a mesh here
    (the sharded path is neighbor-table-native).

    All internal fault machinery (masks, realized adjacency, MH weights,
    degree accounting) runs in float32; only ``mix``/``neighbor_sum`` outputs
    are cast back to the input's dtype.

    Memoryless faults (``drop_prob``/``straggler_prob`` alone) sample masks
    on the fly from (seed, t) — dense topologies from the symmetric [N, N]
    draw, unsharded matrix-free ones from one uniform an edge and one a
    node (module docstring, "Matrix-free draws").  Persistent processes —
    bursty links (``burst_len >= 1``) and crash-recovery churn
    (``mttf``/``mttr``) —
    require ``horizon`` and route through a precomputed
    ``build_fault_timeline`` (gathered per iteration; bitwise-identical to
    the on-the-fly path at burst_len=1 / the iid-equivalent churn point).

    Replica-batched callers (``jax_backend.run_batch``) override the
    seed-derived randomness per replica: ``keys`` = (fault_key, node_key,
    match_key) pre-derived typed PRNG keys (may be vmap tracers), and
    ``timeline`` = a prebuilt per-replica ``FaultTimeline`` whose arrays
    may be traced [horizon, ...] slices of a stacked replica axis.
    ``drop_prob`` may then also be a traced scalar (a swept axis); traced
    values skip the host-side range validation — the batch caller
    validates per-replica configs before tracing — and always take the
    sampling path (a draw ``u >= p`` with p = 0 keeps every edge, so the
    realization stays correct for any in-range value).
    """
    drop_concrete = isinstance(drop_prob, (int, float))
    if drop_concrete and not 0.0 <= drop_prob < 1.0:
        raise ValueError(f"drop_prob must be in [0, 1), got {drop_prob}")
    # Host-side activity flags: traced drop probabilities always run the
    # sampling math (correct for any value — see the docstring).
    drop_active = (not drop_concrete) or drop_prob > 0.0
    strag_active = straggler_prob > 0.0
    if not 0.0 <= straggler_prob < 1.0:
        raise ValueError(
            f"straggler_prob must be in [0, 1), got {straggler_prob}"
        )
    if topo.directed and one_peer:
        raise ValueError(
            "one_peer gossip is a mutual-matching (undirected) schedule; "
            f"topology {topo.name!r} has one-way links, so a pairwise "
            "exchange cannot be realized"
        )
    if burst_len != 0.0 and burst_len < 1.0:
        raise ValueError(
            f"burst_len must be 0 (iid sampler) or >= 1, got {burst_len}"
        )
    if rejoin not in REJOIN_POLICIES:
        raise ValueError(
            f"Unknown rejoin policy: {rejoin!r}; known: {REJOIN_POLICIES}"
        )
    churn_active = mttf > 0.0 or mttr > 0.0
    if churn_active and one_peer:
        raise ValueError(
            "crash-recovery churn requires the synchronous schedule: rejoin "
            "policies act on the realized neighborhood, which a one-peer "
            "matching (at most one partner per round) cannot supply"
        )
    if not 0.0 < participation_rate <= 1.0:
        raise ValueError(
            f"participation_rate must be in (0, 1], got {participation_rate}"
        )
    participation_active = participation_rate < 1.0
    if participation_active and one_peer:
        raise ValueError(
            "participation sampling requires the synchronous schedule: the "
            "sampled subgraph reweights the whole realized neighborhood, "
            "which a one-peer matching cannot supply"
        )
    use_timeline = (
        burst_len >= 1.0 or churn_active or participation_active
        or timeline is not None
        # The sharded matrix-free route keeps its per-shard [horizon, N/P]
        # timeline slices for memoryless stragglers too. Unsharded,
        # MEMORYLESS matrix-free faults are DRAWN inside the step from
        # (seed, t) — the same keys, shapes and float32 comparison
        # ``build_fault_timeline`` uses, one uniform an edge and one a
        # node, so the realization is bitwise the timeline's and nothing
        # [horizon, ·] is built, fetched or placed (no dense [N, N] draw
        # either way). Decided by what the faults are, never by an option.
        or (
            topo.is_matrix_free and mesh is not None
            and (strag_active or drop_active)
        )
    )
    if use_timeline and timeline is None:
        if horizon is None:
            raise ValueError(
                "persistent fault processes (burst_len >= 1, mttf/mttr, or "
                "participation_rate < 1) precompute a [horizon]-indexed "
                "timeline; pass horizon=n_iterations"
            )
        timeline = build_fault_timeline(
            topo, horizon, seed,
            edge_drop_prob=drop_prob,
            burst_len=burst_len if burst_len >= 1.0 else 1.0,
            straggler_prob=0.0 if churn_active else straggler_prob,
            mttf=mttf, mttr=mttr,
            participation_rate=participation_rate,
        )
    # Distinct streams from batch sampling: fold tags into the seed key
    # (or take the caller's pre-derived per-replica keys verbatim).
    if keys is None:
        fault_key = jax.random.fold_in(jax.random.key(seed), 0x0FA17)
        node_key = jax.random.fold_in(jax.random.key(seed), 0x57A66)
    else:
        fault_key, node_key, _ = keys
    if mesh is not None and not topo.is_matrix_free:
        raise ValueError(
            "sharded (worker_mesh) fault mixing is neighbor-table-native: "
            f"dense topology {topo.name!r} has no halo form — build the "
            "graph with topology_impl='neighbor'"
        )
    if topo.is_matrix_free:
        # Matrix-free (neighbor-table-native) route: node-process faults
        # (participation sampling, iid stragglers, crash-recovery churn)
        # AND per-edge drop processes (iid / bursty Gilbert-Elliott
        # chains, ISSUE-9 satellite) — all realized over the static
        # [N, k_max] table, the [horizon, E] edge chains indexed through
        # the (node, slot) → edge-id map (or, on a ring, sliced). Matching
        # schedules still need the dense adjacency (partner sampling is
        # an [N, N] argmax) and are rejected upstream and here.
        if one_peer or topo.directed:
            raise ValueError(
                "matrix-free topologies support synchronous fault "
                "processes only; matching schedules and directed graphs "
                "need the dense adjacency — use topology_impl='dense'"
            )
        if mesh is not None:
            if timeline is not None and timeline.edge_up is not None:
                raise ValueError(
                    "sharded (worker_mesh) fault mixing composes node "
                    "processes only; per-edge chains need per-shard "
                    "slicing of the [horizon, E] timeline — run edge "
                    "faults unsharded"
                )
            return make_halo_faulty_mixing(
                topo, mesh, timeline,
                drop_prob=drop_prob, straggler_prob=straggler_prob,
                churn_active=churn_active,
                participation_active=participation_active, rejoin=rejoin,
            )
        # One round, two ways to address a neighbour, chosen by the shape
        # of the input: a ring's table is shifts (an injected timeline's
        # edge bits must be numbered as the ring's draw is), anything else
        # index tables.
        shifts = _table_is_a_ring(topo) and (
            timeline is None or timeline.edge_up is None
            or np.array_equal(timeline.edge_index, _edge_list(topo))
        )
        build = (
            _make_shift_faulty_mixing if shifts
            else _make_gather_faulty_mixing
        )
        return build(
            topo, timeline, drop_prob=drop_prob,
            straggler_prob=straggler_prob, churn_active=churn_active,
            participation_active=participation_active, rejoin=rejoin,
            fault_key=fault_key, node_key=node_key,
        )
    base_A = jnp.asarray(topo.adjacency, dtype=jnp.float32)

    if use_timeline:
        node_up_dev = (
            jnp.asarray(timeline.node_up)
            if timeline.node_up is not None else None
        )
        part_up_dev = (
            jnp.asarray(timeline.part_up)
            if timeline.part_up is not None else None
        )
        edge_up_dev = (
            jnp.asarray(timeline.edge_up)
            if timeline.edge_up is not None else None
        )
        if edge_up_dev is not None:
            ei = jnp.asarray(timeline.edge_index[:, 0], dtype=jnp.int32)
            ej = jnp.asarray(timeline.edge_index[:, 1], dtype=jnp.int32)
        node_masked = node_up_dev is not None or part_up_dev is not None

        @device_scopes.scope("faults")
        def active(t) -> jax.Array:
            # Realized availability: churn/straggler-up AND sampled-in
            # (participation). Either alone is the mask verbatim.
            if not node_masked:
                return jnp.ones(base_A.shape[0], dtype=jnp.float32)
            if node_up_dev is None:
                return part_up_dev[t].astype(jnp.float32)
            m = node_up_dev[t].astype(jnp.float32)
            if part_up_dev is not None:
                m = m * part_up_dev[t].astype(jnp.float32)
            return m

        @device_scopes.scope("faults")
        def realized_adjacency(t) -> jax.Array:
            if edge_up_dev is not None:
                e = edge_up_dev[t].astype(jnp.float32)
                half = jnp.zeros_like(base_A).at[ei, ej].set(e)
                A_t = half if topo.directed else half + half.T
            else:
                A_t = base_A
            if node_masked:
                m = active(t)
                A_t = A_t * m[:, None] * m[None, :]  # down: exchanges nothing
            return A_t
    else:

        @device_scopes.scope("faults")
        def active(t) -> jax.Array:
            if not strag_active:
                return jnp.ones(base_A.shape[0], dtype=jnp.float32)
            key = jax.random.fold_in(node_key, t)
            u = jax.random.uniform(
                key, (base_A.shape[0],), dtype=jnp.float32
            )
            return (u >= straggler_prob).astype(jnp.float32)

        @device_scopes.scope("faults")
        def realized_adjacency(t) -> jax.Array:
            if not drop_active and not strag_active:
                return base_A  # no fault sampling on the fault-free fast path
            key = jax.random.fold_in(fault_key, t)
            if topo.directed:
                A_t = sample_surviving_directed_adjacency(
                    key, base_A, drop_prob
                )
            else:
                A_t = sample_surviving_adjacency(key, base_A, drop_prob)
            if strag_active:
                m = active(t)
                A_t = A_t * m[:, None] * m[None, :]  # exchanges nothing
            return A_t

    def make_neighbor_liveness(nbr_idx: np.ndarray, nbr_mask: np.ndarray):
        """Gather-form realized adjacency (see the FaultyMixing field doc).

        Host tables come from the caller (built once when the gather
        screening path is selected); the returned ``live(t)`` is
        jit-gatherable and consumes exactly the draws/chains the dense
        ``realized_adjacency`` consumes, so the two forms realize the
        identical graph at every t in every precision.
        """
        n = base_A.shape[0]
        nbr_dev = jnp.asarray(nbr_idx, dtype=jnp.int32)
        mask_dev = jnp.asarray(nbr_mask, dtype=jnp.float32)
        if use_timeline:
            slot_dev = None
            if timeline.edge_up is not None:
                from distributed_optimization_tpu.parallel.topology import (
                    incident_edge_slots,
                )

                slot_dev = jnp.asarray(
                    incident_edge_slots(
                        nbr_idx, nbr_mask, timeline.edge_index
                    ),
                    dtype=jnp.int32,
                )
                edge_up_gather = jnp.asarray(timeline.edge_up)

            @device_scopes.scope("faults")
            def live(t) -> jax.Array:
                out = mask_dev
                if slot_dev is not None:
                    out = out * edge_up_gather[t].astype(jnp.float32)[
                        slot_dev
                    ]
                if timeline.node_up is not None or timeline.part_up is not None:
                    m = active(t)
                    out = out * m[:, None] * m[nbr_dev]
                return out
        else:

            @device_scopes.scope("faults")
            def live(t) -> jax.Array:
                if not drop_active and not strag_active:
                    return mask_dev  # fault-free fast path: static table
                out = mask_dev
                if drop_active:
                    # The SAME symmetric (seed, t) draw as
                    # sample_surviving_adjacency, gathered per slot — the
                    # O(N²) uniform matrix carries no d factor, so the
                    # degree-bounded complexity claim is untouched.
                    key = jax.random.fold_in(fault_key, t)
                    u = jax.random.uniform(key, (n, n), dtype=jnp.float32)
                    u = jnp.triu(u, 1)
                    u = u + u.T
                    out = out * (
                        jnp.take_along_axis(u, nbr_dev, axis=1) >= drop_prob
                    ).astype(jnp.float32)
                if strag_active:
                    m = active(t)
                    out = out * m[:, None] * m[nbr_dev]
                return out

        return live

    rejoin_restart = None
    if churn_active and rejoin == "neighbor_restart":
        rejoin_dev = jnp.asarray(timeline.rejoin)

        def rejoin_restart(t, x) -> jax.Array:
            # Warm restart: a rejoining node replaces its (stale) model row
            # with the average of its REALIZED neighbors' current rows —
            # exactly the neighborhood it can actually hear from on the
            # rejoin round.  Isolated rejoiners (no surviving realized
            # neighbor) keep their stale state.  float32 accumulation floor
            # like all fault machinery; output cast back to the run dtype.
            acc = jnp.promote_types(jnp.float32, x.dtype)
            A_t = realized_adjacency(t).astype(acc)
            deg = jnp.sum(A_t, axis=1)
            rows = tuple(range(1, x.ndim))  # a unit axis per parameter axis
            nbr_avg = jnp.tensordot(
                A_t, x.astype(acc), axes=1
            ) / jnp.expand_dims(jnp.maximum(deg, 1.0), rows)
            take = rejoin_dev[t] & (deg > 0)
            return jnp.where(
                jnp.expand_dims(take, rows), nbr_avg, x.astype(acc)
            ).astype(x.dtype)

    match_key = (
        jax.random.fold_in(jax.random.key(seed), 0x3A7C4)
        if keys is None else keys[2]
    )

    @device_scopes.scope("faults")
    def partner(t) -> jax.Array:
        key = jax.random.fold_in(match_key, t)
        return sample_one_peer_matching(key, realized_adjacency(t))

    exposed_adjacency = None
    if one_peer:
        mix, neighbor_sum, realized_degree_sum = _matching_ops(partner)
    else:
        exposed_adjacency = realized_adjacency
        # Accumulate in at-least-float32: bf16 inputs get the f32 upcast the
        # accounting needs, while float64 fidelity runs keep full precision
        # (the 0/1 adjacency is exact in any dtype, so casting it up first
        # makes the MH weights exact in the accumulation dtype). Directed
        # graphs renormalize the surviving OUT-weights column-stochastically
        # (the push-sum fault model); undirected graphs recompute MH weights
        # on realized degrees (doubly stochastic for every draw).
        realized_weights = (
            column_stochastic_weights if topo.directed
            else metropolis_hastings_weights
        )

        def mix(t, x):
            acc = jnp.promote_types(jnp.float32, x.dtype)
            with device_scopes.scope("faults"):
                W = realized_weights(realized_adjacency(t).astype(acc))
            return jnp.tensordot(W, x.astype(acc), axes=1).astype(x.dtype)

        def neighbor_sum(t, x):
            acc = jnp.promote_types(jnp.float32, x.dtype)
            return jnp.tensordot(
                realized_adjacency(t).astype(acc), x.astype(acc), axes=1
            ).astype(x.dtype)

        def realized_degree_sum(t):
            return jnp.sum(realized_adjacency(t))

    return FaultyMixing(
        mix=mix,
        neighbor_sum=neighbor_sum,
        realized_degree_sum=realized_degree_sum,
        active=active,
        drop_prob=drop_prob,
        straggler_prob=straggler_prob,
        realized_adjacency=exposed_adjacency,
        make_neighbor_liveness=(
            make_neighbor_liveness
            if exposed_adjacency is not None and not topo.directed
            else None
        ),
        churn_active=churn_active,
        rejoin=rejoin,
        rejoin_restart=rejoin_restart,
        participation_active=participation_active,
        timeline=timeline,
    )


def _matrix_free_bits(
    topo: Topology,
    timeline: Optional[FaultTimeline],
    *,
    drop_prob,
    straggler_prob: float,
    churn_active: bool,
    rejoin: str,
    fault_key,
    node_key,
):
    """Where round t's bits come from on a neighbor table, whichever way
    the neighbours are then addressed: ``(leaves, edge_index, bits)``.

    Decided by ``timeline``: given one (persistent processes, the
    replica-batched stacker, an injected realization) round t reads its
    rows, and ``leaves`` holds them (``edge_up``, ``node_up``, ``part_up``,
    ``rejoin``: the timeline's own arrays, on the device where
    ``build_fault_timeline`` left them there, else host arrays or traced
    slices; handed to the program with the form's own tables); given none (memoryless faults) round t
    DRAWS them from ``fold_in(fault_key, t)`` / ``fold_in(node_key, t)`` —
    ``build_fault_timeline``'s own keys, shapes and float32 comparison, so
    the two realize one graph bit for bit and ``leaves`` is empty.
    ``edge_index`` is the ``[E, 2]`` list the edge bits are numbered by
    (None without an edge process); ``bits(tb)`` gives ``edge_up(t)``
    (``[E]`` float32, or None) and ``active(t)`` (``[N]`` float32) over a
    copy ``tb`` of the leaves.
    """
    n = topo.n
    drawn = timeline is None
    # The drawn form's thresholds, None where that process is off (a traced
    # drop probability always draws: see ``make_faulty_mixing``).
    drop_off = isinstance(drop_prob, (int, float)) and drop_prob == 0.0
    drop_p = (
        jnp.asarray(drop_prob, dtype=jnp.float32)
        if drawn and not drop_off else None
    )
    strag_q = (
        np.float32(straggler_prob)
        if drawn and straggler_prob > 0.0 else None
    )
    leaves = {}
    edge_index = None
    if not drawn and timeline.edge_up is not None:
        edge_index = timeline.edge_index
        leaves["edge_up"] = timeline.edge_up
    elif drop_p is not None:
        edge_index = _edge_list(topo)
    if not drawn:
        for name in ("node_up", "part_up"):
            if getattr(timeline, name) is not None:
                leaves[name] = getattr(timeline, name)
        if churn_active and rejoin == "neighbor_restart":
            leaves["rejoin"] = timeline.rejoin
    n_edges = None if edge_index is None else edge_index.shape[0]

    def bits(tb):
        @device_scopes.scope("faults")
        def edge_up(t):
            """[E] float32 link liveness at t, or None (no edge process)."""
            if "edge_up" in tb:
                return tb["edge_up"][t].astype(jnp.float32)
            if drop_p is None:
                return None
            u = jax.random.uniform(
                jax.random.fold_in(fault_key, t), (n_edges,),
                dtype=jnp.float32,
            )
            return (u >= drop_p).astype(jnp.float32)

        @device_scopes.scope("faults")
        def active(t) -> jax.Array:
            if strag_q is not None:
                u = jax.random.uniform(
                    jax.random.fold_in(node_key, t), (n,), dtype=jnp.float32
                )
                return (u >= strag_q).astype(jnp.float32)
            if "node_up" not in tb and "part_up" not in tb:
                return jnp.ones(n, dtype=jnp.float32)
            if "node_up" not in tb:
                return tb["part_up"][t].astype(jnp.float32)
            m = tb["node_up"][t].astype(jnp.float32)
            if "part_up" in tb:
                m = m * tb["part_up"][t].astype(jnp.float32)
            return m

        return edge_up, active

    return leaves, edge_index, bits


def _rebindable(bind, tables) -> FaultyMixing:
    """``bind(tables)`` with ``bind`` as its ``bind`` field. The field is
    set from outside the closure: a ``bind`` that named itself would be a
    reference cycle through its own cell, and the ``[horizon, ·]`` leaves it
    closes over (0.79 GB on the device at the churn cell's size) would
    outlive their call until the garbage collector's next full pass."""
    return dataclasses.replace(bind(tables), bind=bind)


def _make_gather_faulty_mixing(
    topo: Topology,
    timeline: Optional[FaultTimeline],
    *,
    drop_prob: float,
    straggler_prob: float,
    churn_active: bool,
    participation_active: bool,
    rejoin: str,
    fault_key,
    node_key,
) -> FaultyMixing:
    """Faults over a matrix-free (neighbor-table) topology, in gather form:
    any graph whose table is not a ring's (``_table_is_a_ring``).

    The realized graph at round t is the static table masked by the edge
    liveness bits and the composed node-availability row m_t
    (churn/straggler-up AND sampled-in):
    ``live_t[i, s] = mask[i, s] · up_t[slot[i, s]] · m_t[i] · m_t[nbr[i, s]]``.
    Realized MH weights come straight from the live slots —
    ``w = live / (1 + max(deg_i, deg_{nbr}))`` with the row remainder on
    the diagonal, the identical per-entry formula the dense
    ``metropolis_hastings_weights`` computes on the realized adjacency
    (a fully-masked row degenerates to identity the same way) — so the
    whole time-varying gossip round stays O(N·k_max·d) with no [N, N]
    object anywhere. Same float32 mask/weight convention as the dense
    path; only the mixed model values are cast back to the input dtype.

    Where the bits come from: ``_matrix_free_bits``.

    Every array the operators read lives in ONE pytree, ``tables``, placed
    on the device here, and the operators are built over a copy of it by
    ``bind`` (see the ``FaultyMixing`` fields): the caller decides whether
    the tables are arguments of its program or constants in it.
    """
    n = topo.n
    k_max = topo.nbr_idx.shape[1]
    leaves, edge_index, bits = _matrix_free_bits(
        topo, timeline, drop_prob=drop_prob, straggler_prob=straggler_prob,
        churn_active=churn_active, rejoin=rejoin,
        fault_key=fault_key, node_key=node_key,
    )
    # The [N, k_max] tables are kept SLOT-MAJOR, [k_max, N]: as an argument
    # in the TPU's (8, 128) tiles an s32[262144, 2] is 134 MB where its
    # transpose is 2; the operators read them through ``.T``, which costs a
    # program nothing (a layout).
    tables = {
        "nbr": np.asarray(topo.nbr_idx, dtype=np.int32).T,
        "mask": np.asarray(topo.nbr_mask, dtype=np.float32).T,
    }
    # Per-edge liveness in gather form (ISSUE-9 satellite): the bits land
    # on both endpoints' rows through the static (node, slot) → edge-id
    # table — the same symmetric composition the dense path realizes by
    # scattering A[ei, ej] = A[ej, ei] = up[e], with no [N, N] object.
    if edge_index is not None:
        from distributed_optimization_tpu.parallel.topology import (
            incident_edge_slots,
        )

        tables["slot"] = incident_edge_slots(
            topo.nbr_idx, topo.nbr_mask, edge_index
        ).T
    # Placed here, once (a traced timeline leaf of the replica-batched
    # path passes through as it is).
    tables = {k: jnp.asarray(v) for k, v in {**tables, **leaves}.items()}

    def bind(tb) -> FaultyMixing:
        nbr_dev, mask_dev = tb["nbr"].T, tb["mask"].T
        slot_dev = tb["slot"].T if "slot" in tb else None
        edge_up, active = bits(tb)

        @device_scopes.scope("faults")
        def live_over(t, nbr, mask, slots) -> jax.Array:
            out = mask
            up = edge_up(t)
            if up is not None:
                out = out * up[slots]
            m = active(t)
            return out * m[:, None] * m[nbr]

        def live(t) -> jax.Array:
            return live_over(t, nbr_dev, mask_dev, slot_dev)

        def _wshape(x: jax.Array):
            return (n, k_max) + (1,) * (x.ndim - 1)

        def mix(t, x):
            acc = jnp.promote_types(jnp.float32, x.dtype)
            with device_scopes.scope("faults"):
                lv = live(t).astype(acc)
                deg = jnp.sum(lv, axis=1)
                w = lv / (1.0 + jnp.maximum(deg[:, None], deg[nbr_dev]))
                w_self = 1.0 - jnp.sum(w, axis=1)
            xa = x.astype(acc)
            out = w_self.reshape((-1,) + (1,) * (x.ndim - 1)) * xa + jnp.sum(
                w.reshape(_wshape(x)) * xa[nbr_dev], axis=1
            )
            return out.astype(x.dtype)

        def neighbor_sum(t, x):
            acc = jnp.promote_types(jnp.float32, x.dtype)
            lv = live(t).astype(acc)
            return jnp.sum(
                lv.reshape(_wshape(x)) * x.astype(acc)[nbr_dev], axis=1
            ).astype(x.dtype)

        def realized_degree_sum(t):
            return jnp.sum(live(t))

        rejoin_restart = None
        if "rejoin" in tb:

            def rejoin_restart(t, x) -> jax.Array:
                # Gather twin of the dense warm restart: a rejoining
                # node's model row becomes its realized-neighborhood
                # average; isolated rejoiners keep their stale state.
                acc = jnp.promote_types(jnp.float32, x.dtype)
                lv = live(t).astype(acc)
                deg = jnp.sum(lv, axis=1)
                rows = tuple(range(1, x.ndim))  # a unit axis per param axis
                nbr_avg = jnp.sum(
                    jnp.expand_dims(lv, tuple(range(2, x.ndim + 1)))
                    * x.astype(acc)[nbr_dev], axis=1
                ) / jnp.expand_dims(jnp.maximum(deg, 1.0), rows)
                take = tb["rejoin"][t] & (deg > 0)
                return jnp.where(
                    jnp.expand_dims(take, rows), nbr_avg, x.astype(acc)
                ).astype(x.dtype)

        def make_neighbor_liveness(nbr_idx: np.ndarray, nbr_mask: np.ndarray):
            # Same contract as the dense path's: live(t) over the CALLER's
            # tables, composing the edge bits through the caller-table
            # slot map plus the node availability. For a matrix-free
            # topology the caller's tables are the topology's own
            # (neighbor_tables_for returns them verbatim): the bound ones.
            if nbr_idx is topo.nbr_idx and nbr_mask is topo.nbr_mask:
                return live
            caller_nbr = jnp.asarray(nbr_idx, dtype=jnp.int32)
            caller_mask = jnp.asarray(nbr_mask, dtype=jnp.float32)
            caller_slots = None
            if edge_index is not None:
                from distributed_optimization_tpu.parallel.topology import (
                    incident_edge_slots,
                )

                caller_slots = jnp.asarray(incident_edge_slots(
                    np.asarray(nbr_idx), np.asarray(nbr_mask), edge_index
                ))
            return lambda t: live_over(t, caller_nbr, caller_mask, caller_slots)

        return FaultyMixing(
            mix=mix,
            neighbor_sum=neighbor_sum,
            realized_degree_sum=realized_degree_sum,
            active=active,
            drop_prob=(
                drop_prob if isinstance(drop_prob, (int, float)) else 0.0
            ),
            straggler_prob=straggler_prob,
            realized_adjacency=None,
            make_neighbor_liveness=make_neighbor_liveness,
            churn_active=churn_active,
            rejoin=rejoin,
            rejoin_restart=rejoin_restart,
            participation_active=participation_active,
            timeline=timeline,
            tables=tables,
            addressing="gather",
        )

    return _rebindable(bind, tables)


def _make_shift_faulty_mixing(
    topo: Topology,
    timeline: Optional[FaultTimeline],
    *,
    drop_prob: float,
    straggler_prob: float,
    churn_active: bool,
    participation_active: bool,
    rejoin: str,
    fault_key,
    node_key,
) -> FaultyMixing:
    """``_make_gather_faulty_mixing``'s round where the neighbor table is a
    ring's (``_table_is_a_ring``): node i's neighbours are rows i − 1 and
    i + 1 mod N, so a neighbour's value is a ``jnp.roll`` and no table is
    read — an index gather costs the chip about 9 ns an index whatever it
    fetches (PERF.md §6, PR 32), a shift its bytes.

    Edge-major, the two directions kept as two ``[N]`` arrays (never one
    ``[N, 2]``): ``r[i]`` is the live bit of edge {i, i+1 mod N} =
    ``up_t[{i, i+1}] · m_t[i] · m_t[i+1]``, ``l = roll(r, 1)`` that of
    {i−1, i}, ``deg = l + r``, ``w_r = r / (1 + max(deg, roll(deg, −1)))``,
    ``w_l = roll(w_r, 1)`` (an edge's weight is one number, read from either
    end), ``w_self = 1 − (w_l + w_r)`` and
    ``out = w_self·x + (w_l·roll(x, 1) + w_r·roll(x, −1))``: the gather
    form's arithmetic term for term, its two-term sums in either order.

    The draw is the gather form's, untouched (``_matrix_free_bits``): one
    float32 uniform an edge in ``_edge_list``'s order — on a ring (0, 1),
    (0, N−1), (1, 2), …, (N−2, N−1) — so the bit of edge {i, i+1 mod N} is
    ``concat(up[0:1], up[2:N], up[1:2])[i]``: three static slices, no
    (node, slot) → edge map. A timeline's ``edge_up[t]`` row is numbered by
    the same list (``make_faulty_mixing`` checks an injected one's
    ``edge_index`` before it takes this form). ``tables`` holds the
    timeline's leaves and nothing else: nothing at all for memoryless
    faults.
    """
    n = topo.n
    leaves, _, bits = _matrix_free_bits(
        topo, timeline, drop_prob=drop_prob, straggler_prob=straggler_prob,
        churn_active=churn_active, rejoin=rejoin,
        fault_key=fault_key, node_key=node_key,
    )
    tables = {k: jnp.asarray(v) for k, v in leaves.items()}

    def bind(tb) -> FaultyMixing:
        edge_up, active = bits(tb)

        @device_scopes.scope("faults")
        def right(t) -> jax.Array:
            """[N] float32: the live bit of edge {i, i+1 mod N}."""
            m = active(t)
            r = m * jnp.roll(m, -1)
            up = edge_up(t)
            if up is not None:
                r = jnp.concatenate([up[0:1], up[2:], up[1:2]]) * r
            return r

        def _col(v, x):
            return v.reshape((-1,) + (1,) * (x.ndim - 1))

        def neighbor_terms(x, c_l, c_r):
            """Σ over the two neighbours of coefficient × row."""
            return _col(c_l, x) * jnp.roll(x, 1, axis=0) + _col(
                c_r, x
            ) * jnp.roll(x, -1, axis=0)

        def mix(t, x):
            acc = jnp.promote_types(jnp.float32, x.dtype)
            with device_scopes.scope("faults"):
                r = right(t).astype(acc)
                deg = jnp.roll(r, 1) + r
                w_r = r / (1.0 + jnp.maximum(deg, jnp.roll(deg, -1)))
                w_l = jnp.roll(w_r, 1)
                w_self = 1.0 - (w_l + w_r)
            xa = x.astype(acc)
            out = _col(w_self, x) * xa + neighbor_terms(xa, w_l, w_r)
            return out.astype(x.dtype)

        def neighbor_sum(t, x):
            acc = jnp.promote_types(jnp.float32, x.dtype)
            r = right(t).astype(acc)
            return neighbor_terms(
                x.astype(acc), jnp.roll(r, 1), r
            ).astype(x.dtype)

        def realized_degree_sum(t):
            # Σ_i deg_i: every live edge once from each end.
            return 2.0 * jnp.sum(right(t))

        rejoin_restart = None
        if "rejoin" in tb:

            def rejoin_restart(t, x) -> jax.Array:
                # The gather form's warm restart: a rejoining node's row
                # becomes its realized-neighborhood average; isolated
                # rejoiners keep their stale state.
                acc = jnp.promote_types(jnp.float32, x.dtype)
                r = right(t).astype(acc)
                l = jnp.roll(r, 1)
                deg = l + r
                xa = x.astype(acc)
                nbr_avg = neighbor_terms(xa, l, r) / _col(
                    jnp.maximum(deg, 1.0), x
                )
                take = tb["rejoin"][t] & (deg > 0)
                return jnp.where(_col(take, x), nbr_avg, xa).astype(x.dtype)

        def make_neighbor_liveness(nbr_idx: np.ndarray, nbr_mask: np.ndarray):
            # The contract is the gather form's: [N, k_max] in the CALLER's
            # table order, which on a ring is ascending, not left/right
            # (row 0 is [1, N−1], row N−1 [0, N−2]). Each slot is told on
            # the host which of the two edges it names.
            nbr_idx = np.asarray(nbr_idx)
            mask = np.asarray(nbr_mask, dtype=bool)
            ids = np.arange(n)
            is_right = nbr_idx == ((ids + 1) % n)[:, None]
            is_left = nbr_idx == ((ids - 1) % n)[:, None]
            if (mask & ~(is_right | is_left)).any():
                raise ValueError(
                    "neighbor liveness was asked over a table that is not "
                    "this ring's"
                )
            pick_right = jnp.asarray(is_right)
            caller_mask = jnp.asarray(mask, dtype=jnp.float32)

            @device_scopes.scope("faults")
            def live(t) -> jax.Array:
                r = right(t)
                return caller_mask * jnp.where(
                    pick_right, r[:, None], jnp.roll(r, 1)[:, None]
                )

            return live

        return FaultyMixing(
            mix=mix,
            neighbor_sum=neighbor_sum,
            realized_degree_sum=realized_degree_sum,
            active=active,
            drop_prob=(
                drop_prob if isinstance(drop_prob, (int, float)) else 0.0
            ),
            straggler_prob=straggler_prob,
            realized_adjacency=None,
            make_neighbor_liveness=make_neighbor_liveness,
            churn_active=churn_active,
            rejoin=rejoin,
            rejoin_restart=rejoin_restart,
            participation_active=participation_active,
            timeline=timeline,
            tables=tables,
            addressing="shift",
        )

    return _rebindable(bind, tables)


def make_halo_faulty_mixing(
    topo: Topology,
    mesh,
    timeline: Optional[FaultTimeline],
    *,
    drop_prob: float,
    straggler_prob: float,
    churn_active: bool,
    participation_active: bool,
    rejoin: str,
) -> FaultyMixing:
    """Sharded (worker-mesh) twin of ``_make_gather_faulty_mixing``.

    Node-process faults (iid stragglers, crash-recovery churn, client
    sampling) over a matrix-free topology with the worker axis split into
    contiguous blocks over ``mesh`` (docs/PERF.md §16). The [horizon, N]
    timeline masks are device-placed with their NODE axis sharded — each
    device holds only its own [horizon, N/P] timeline slice — and one
    realized-MH gossip round runs as TWO halo exchanges inside shard_map:
    first the per-node availability bit (1 float per boundary row, so
    each shard can realize its live slots and degrees locally), then the
    model rows with the realized degree riding as one extra column (the
    neighbor-degree term of the MH weight). Per-row arithmetic mirrors
    the unsharded gather form term for term — f32 liveness, accumulation
    dtype floor, identity-row degeneration — so sharded and unsharded
    realizations are BITWISE identical (tests/test_worker_mesh.py).

    Not yet sharded (rejected upstream with the missing piece named):
    per-edge chains (need per-shard [horizon, E] slicing) and the
    ``neighbor_restart`` rejoin policy (needs the halo-averaged warm
    restart).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_optimization_tpu.parallel.collectives import (
        make_halo_exchange,
    )
    from distributed_optimization_tpu.parallel.mesh import WORKER_AXIS

    if timeline is not None and timeline.edge_up is not None:
        raise ValueError(
            "sharded fault mixing composes node processes only (see "
            "make_faulty_mixing)"
        )
    if churn_active and rejoin == "neighbor_restart":
        raise ValueError(
            "rejoin='neighbor_restart' has no sharded form yet (the warm "
            "restart needs the halo-averaged neighborhood) — use 'frozen'"
        )
    n = topo.n
    hx = make_halo_exchange(topo, mesh)
    nbr_global = jnp.asarray(topo.nbr_idx, dtype=jnp.int32)

    def _col_sharded(host_arr):
        # [horizon, N] bool → device array with the NODE axis sharded:
        # the per-shard timeline slice of the tentpole contract.
        return jax.device_put(
            jnp.asarray(host_arr),
            NamedSharding(mesh, P(None, WORKER_AXIS)),
        )

    node_up_dev = (
        _col_sharded(timeline.node_up)
        if timeline is not None and timeline.node_up is not None else None
    )
    part_up_dev = (
        _col_sharded(timeline.part_up)
        if timeline is not None and timeline.part_up is not None else None
    )

    @device_scopes.scope("faults")
    def active(t) -> jax.Array:
        if node_up_dev is None and part_up_dev is None:
            return jnp.ones(n, dtype=jnp.float32)
        if node_up_dev is None:
            return part_up_dev[t].astype(jnp.float32)
        m = node_up_dev[t].astype(jnp.float32)
        if part_up_dev is not None:
            m = m * part_up_dev[t].astype(jnp.float32)
        return m

    def _mix_body(exchange, nbr_l, mask_f32, xb, mb):
        # The unsharded gather form, shard-local: live in f32, weights and
        # models in the accumulation dtype, neighbor degrees fetched
        # through the second exchange's extra column.
        acc = jnp.promote_types(jnp.float32, xb.dtype)
        with device_scopes.scope("faults"):
            m_ext = exchange(mb[:, None])[:, 0]           # [S + h + 1] f32
            lv = (mask_f32 * mb[:, None] * m_ext[nbr_l]).astype(acc)
            deg = jnp.sum(lv, axis=1)                      # [S] acc
        xa = xb.astype(acc)
        d2 = xa.shape[-1]
        ext = exchange(jnp.concatenate([xa, deg[:, None]], axis=1))
        gathered = ext[nbr_l]                              # [S, k, d2 + 1]
        with device_scopes.scope("faults"):
            w = lv / (1.0 + jnp.maximum(deg[:, None], gathered[:, :, d2]))
            w_self = 1.0 - jnp.sum(w, axis=1)
        out = w_self[:, None] * xa + jnp.sum(
            w[:, :, None] * gathered[:, :, :d2], axis=1
        )
        return out.astype(xb.dtype)

    def _nbr_body(exchange, nbr_l, mask_f32, xb, mb):
        acc = jnp.promote_types(jnp.float32, xb.dtype)
        with device_scopes.scope("faults"):
            m_ext = exchange(mb[:, None])[:, 0]
            lv = (mask_f32 * mb[:, None] * m_ext[nbr_l]).astype(acc)
        xa = xb.astype(acc)
        ext = exchange(xa)
        out = jnp.sum(lv[:, :, None] * ext[nbr_l], axis=1)
        return out.astype(xb.dtype)

    def mix(t, x):
        shape = x.shape
        x2 = x.reshape(shape[0], -1)
        out = hx.run(_mix_body, x2, active(t))
        return out.reshape(shape)

    def neighbor_sum(t, x):
        shape = x.shape
        x2 = x.reshape(shape[0], -1)
        out = hx.run(_nbr_body, x2, active(t))
        return out.reshape(shape)

    def realized_degree_sum(t):
        # Observability path (floats accounting / trace): the [N] mask
        # gathered over the global table is a cheap GSPMD gather of N
        # floats — the model-payload traffic stays on the halo path.
        m = active(t)
        lv = (
            jnp.asarray(topo.nbr_mask, dtype=jnp.float32)
            * m[:, None] * m[nbr_global]
        )
        return jnp.sum(lv)

    return FaultyMixing(
        mix=mix,
        neighbor_sum=neighbor_sum,
        realized_degree_sum=realized_degree_sum,
        active=active,
        drop_prob=drop_prob if isinstance(drop_prob, (int, float)) else 0.0,
        straggler_prob=straggler_prob,
        realized_adjacency=None,
        make_neighbor_liveness=None,
        churn_active=churn_active,
        rejoin=rejoin,
        rejoin_restart=None,
        participation_active=participation_active,
        timeline=timeline,
    )
