"""Robust neighbor aggregation: Byzantine-tolerant replacements for W @ x.

Plain gossip is a linear map — one Byzantine neighbor sending an arbitrary
vector moves an honest worker's aggregate arbitrarily far (unbounded
sensitivity). These rules bound that sensitivity by SCREENING the received
neighbor messages before combining them; all three are jit-compatible pure
functions of (realized adjacency, stacked models), so they compose with the
fault machinery's per-iteration graphs (``parallel/faults.py``) inside the
scanned training loop:

- **coordinate-wise trimmed mean** (Yin et al. 2018, per neighborhood):
  node i sorts the values of its CLOSED neighborhood {x_j : j ∈ N(i)} ∪
  {x_i} per coordinate, drops the ``b`` largest and ``b`` smallest, and
  averages the rest. Tolerates up to b Byzantine neighbors per node: the
  kept values are bracketed by honest ones in every coordinate.
- **coordinate-wise median**: the midpoint of the closed-neighborhood
  values per coordinate — maximal trimming, tolerating any minority of a
  neighborhood (< (deg+1)/2 attackers).
- **self-centered clipping** (ClippedGossip, He-Karimireddy-Jaggi 2022):
  x_i + Σ_j W_ij · clip_τᵢ(x_j − x_i) with W the MH weights recomputed on
  the realized graph. Each received model moves a worker at most W_ij·τᵢ
  from its own state regardless of the payload. τᵢ is a fixed config
  radius, or adaptive: the (degᵢ − b)-th smallest neighbor-difference
  norm, so exactly the b most-distant messages are clipped down to the
  honest envelope. τ = ∞ (no clipping) IS plain MH gossip, which is why
  this rule degrades to the benign path exactly.

Budget semantics: ``b == 0`` means "assume no attackers" — the caller
(backends) short-circuits to plain MH gossip, bitwise identical to a run
with ``aggregation='gossip'``. ``validate_budget`` enforces 2·b ≤ min
degree: beyond that a node's trimmed neighborhood can be empty. Under
edge faults a REALIZED degree may still drop below 2b+1; the rules then
degrade per-node to the worker keeping its own model for that round (the
same identity-row convention an isolated node gets in ``FaultyMixing``).

The ``*_np`` twin is an independent per-node loop implementation written
directly from the rule definitions (numpy-oracle convention, see
``backends/numpy_backend.py``): equivalence between the vectorized jax
forms and this oracle is pinned in tests/test_byzantine.py.

Two jax implementations of every rule (``robust_impl`` knob):

- **dense** (``make_robust_aggregator``): materializes the [N, N, d]
  closed-neighborhood tensor and sorts over the full node axis —
  O(N²·d·log N) work, O(N²·d) memory, regardless of how sparse the
  topology is;
- **gather** (``make_gather_robust_aggregator``): precomputes a static
  padded neighbor-index table [N, k_max] from the topology
  (``parallel/topology.py::neighbor_table``) and reads neighbor models and
  per-incident-edge liveness bits [N, k_max] through it — O(N·k_max·d)
  memory, an ~N/k_max-fold reduction on degree-bounded graphs (measured
  69-75× e2e for trimmed mean/median on an N=256 ring,
  docs/perf/robust_scale.json). Clipping reduces over the k_max axis of
  the gathered [N, k_max, d] differences. The two count rules order the
  closed neighborhood (``closed_neighbourhood_rule``, the ONE definition
  the unsharded and the halo form both call), and HOW is read off the
  table's static width, never off an option:

  - up to ``NETWORK_MAX_SLOTS`` = 17 slots (k_max + 1: a ring's 3, a
    torus's 5, an 8-regular graph's 9) the neighborhood is k_max + 1
    PLANES of [N, d] — the worker's own rows and each slot's rows, +inf
    where the slot is dead — put in order by a compare-exchange network (a
    compare and two selects a comparator, adjacent planes only, so it is
    stable), and the rules read planes: no [N, k_max + 1, d] stack, no
    sort. Each plane holds bit for bit what ``jnp.sort`` puts in that
    position: +inf after every finite value, a NaN last, tied values (±0
    among them) in slot order. HOW a slot's rows are fetched is read off
    the table too (``_received_planes``): where it is a host array and IS
    a ring's (``parallel.topology.table_is_a_ring``, the ONE rule the
    fault layer and the halo mixing ask too) the two planes are two shifts
    of the transmitted stack, ``roll(x, 1)`` and ``roll(x, -1)``, swapped
    by a select on rows 0 and N − 1, whose table lists i + 1 first — no
    table, no gather, nothing row-major in the program; on every other
    table (a ring listed another way, chain, torus, a drawn graph, the halo
    form's traced table) ONE gather through the table read slot-major.
    The same bits either way (``tests/test_robust_gather.py``);
  - above it (a drawn graph's k_max of 30) the stack, ``jnp.sort`` along
    the slot axis and a masked sum, as ever.

The forms are algebraically identical: each orders the same finite values
(+inf padding beyond the realized neighborhood, same convention),
neighbor slots are ordered ascending by index (the order a dense axis-1
reduction visits them), and f64 parity ≤ 1e-12 across dense / gather /
the numpy oracle is asserted in tests/test_robust_gather.py. Where the
kept planes are several (a torus at b = 1 keeps three) the network path
adds them in rising position, the sort path by ``jnp.sum``: the last bits
of a float32 sum may differ between the two, never the values summed.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from distributed_optimization_tpu.config import AGGREGATIONS
from distributed_optimization_tpu.parallel.faults import (
    metropolis_hastings_weights,
)
from distributed_optimization_tpu.parallel.topology import table_is_a_ring

RobustAggregator = Callable[[jax.Array, jax.Array], jax.Array]


def _on_flat_rows(rule):
    """``rule(graph, x)`` is written over ONE parameter axis (it sorts per
    coordinate and takes row norms): a model-shaped stack ([N, d, K]) is
    flattened to [N, d·K] at this boundary and a stack-shaped result
    restored. For the [N, d] stack the reshapes are the identity and trace
    no op."""

    def on_any_rank(graph, x):
        out = rule(graph, x.reshape(x.shape[0], -1))
        return out.reshape(x.shape) if out.ndim else out

    return on_any_rank


def validate_budget(min_degree: int, budget: int, aggregation: str) -> None:
    """Reject trimming budgets the topology cannot support.

    Trimmed mean keeps deg+1−2b closed-neighborhood values, so the
    weakest node needs 2b ≤ min degree for at least one kept value beyond
    its own; the same bound keeps clipping's adaptive radius (deg−b ≥ 1
    unclipped reference) and the median's implicit minority assumption
    meaningful. Faults may still shrink REALIZED degrees below the bound —
    that degrades per-node to an identity row, not an error.
    """
    if aggregation not in AGGREGATIONS:
        raise ValueError(f"Unknown aggregation: {aggregation}")
    if 2 * budget > min_degree:
        raise ValueError(
            f"robust_b={budget} exceeds what the topology supports: "
            f"trimming {budget} from each tail needs 2*b <= min degree "
            f"({min_degree}), or the weakest node's screened neighborhood "
            "is empty — lower robust_b or use a better-connected topology"
        )


def _adaptive_clip_tau(mask, norms, budget: int, k_cap: int):
    """Adaptive ClippedGossip radius over masked neighbor distances: the
    (deg−b)-th smallest realized neighbor-difference norm, so exactly the
    ``b`` most-distant messages get clipped into the honest envelope;
    deg ≤ b ⇒ τ = 0 (identity row). ``mask``: realized adjacency/liveness
    weights (> 0 = live slot); ``k_cap``: the sortable axis length (N for
    the dense form, k_max for gather). ONE definition shared by both
    aggregator forms and their telemetry activity twins — the probe must
    see exactly the radius the rule uses.
    """
    deg = jnp.sum(mask, axis=1).astype(jnp.int32)
    masked = jnp.where(mask > 0, norms, jnp.inf)
    ranked = jnp.sort(masked, axis=1)
    k = jnp.clip(deg - budget - 1, 0, k_cap - 1)
    kth = jnp.take_along_axis(ranked, k[:, None], axis=1)[:, 0]
    return jnp.where(deg - budget >= 1, kth, 0.0)


# The widest closed neighborhood (k_max + 1 slots) the compare-exchange
# network orders; a wider table takes ``jnp.sort``. Derived, no option:
# the round alone on one v5e at N = 2^18, d = 81 (PERF.md §6, PR 44), ms a
# round, network | sort: 3 slots 6.6 | 66.1, 5: 12.1 | 22.9, 7: 17.6 | 89.9,
# 9: 23.5 | 50.5, 17: 57.2 | 107.2. The network won at every width read, so
# this is the widest READ, not a crossover: at a drawn graph's 31 slots the
# sort's side does not fit the chip beside the network's to be compared.
NETWORK_MAX_SLOTS = 17


def screen_order(name: str, impl: str, n: int, k_max: int) -> str:
    """How a screened call orders a closed neighborhood, and over how many
    slots, as its ``dopt.run`` root says it: ``network:3`` / ``sort:31``
    for the count rules over a neighbor table (``gather``, ``halo_gather``),
    ``sort:<N>`` for their dense form; clipping orders no models: ``none``."""
    if name == "clipped_gossip":
        return "none"
    if impl == "dense":
        return f"sort:{n}"
    slots = k_max + 1
    return f"{'network' if slots <= NETWORK_MAX_SLOTS else 'sort'}:{slots}"


def _compare_exchange(a, b):
    """(lower, upper) elementwise by a compare and two selects, so each
    result is one of the two VALUES to the bit. A NaN counts as above
    everything, +inf included, and a tie (±0, two NaNs) keeps its order:
    ``jnp.sort``'s own order. ``minimum`` / ``maximum`` would spread a NaN
    into both results, and so let one payload through the trimming."""
    swap = (a > b) | (jnp.isnan(a) & ~jnp.isnan(b))
    return jnp.where(swap, b, a), jnp.where(swap, a, b)


def _ordered_planes(planes):
    """The planes ascending per row and coordinate, by an odd-even
    transposition network: len(planes) rounds of comparators between
    ADJACENT planes (three comparators for three planes). Adjacent
    comparators that leave a tie alone make the network stable, so plane j
    is bit for bit position j of the stable ``jnp.sort`` over the slot axis
    whatever the planes hold; what no rule reads of the last rounds the
    compiler drops."""
    planes = list(planes)
    for rnd in range(len(planes)):
        for i in range(rnd % 2, len(planes) - 1, 2):
            planes[i], planes[i + 1] = _compare_exchange(
                planes[i], planes[i + 1]
            )
    return planes


def _rows_come_by_shifts(nbr) -> bool:
    """Whether the count rules read a table's neighbours by two shifts of
    the transmitted stack: the table is a HOST array (the unsharded form's;
    the halo form's is the shard's traced table over the halo-extended
    block) and IS a ring's (``topology.table_is_a_ring``, the rule the fault
    layer and the halo mixing ask). Read off the table, by no option."""
    return isinstance(nbr, np.ndarray) and table_is_a_ring(nbr)


def screen_fetch(name: str, impl: str, nbr_idx) -> str:
    """How a screened call fetches the rows a worker received, as its
    ``dopt.run`` root says it, from the predicate the rule itself asks:
    ``shift`` = a ring's table under the unsharded count rules (its three
    slots are always planes), two shifts of the transmitted stack;
    ``gather`` = through the neighbor table (every other table, clipping,
    ``halo_gather``); ``none`` for the dense form, which reads no table
    (``nbr_idx`` is not looked at)."""
    if impl == "dense":
        return "none"
    by_shifts = (
        name != "clipped_gossip"
        and impl == "gather"
        and _rows_come_by_shifts(nbr_idx)
    )
    return "shift" if by_shifts else "gather"


def _received_planes(source, nbr, live):
    """What each slot delivered, a plane [N, d] a slot, +inf where the slot
    is dead. On a ring's table (``_rows_come_by_shifts``) two shifts of
    ``source``: the table lists neighbours ASCENDING, so slot 0 is row
    i − 1 and slot 1 row i + 1 on every row but 0 and N − 1 ([1, N − 1],
    [0, N − 2]), where a select on the row index swaps the two, and plane s
    holds what slot s delivered on EVERY row, as ``live[:, s]`` and the
    network's order of ties want it. On every other table ONE row gather
    through the table read slot-major, so that slot s is the rows
    [s·N, (s + 1)·N) of what it returns: whole tiles whatever k_max, where
    [N, k_max, d] keeps the slots on the sublanes. The same bits either
    way."""
    n, k_max = nbr.shape
    if _rows_come_by_shifts(nbr):
        below, above = jnp.roll(source, 1, axis=0), jnp.roll(source, -1, axis=0)
        row = jnp.arange(n)[:, None]
        end = (row == 0) | (row == n - 1)
        slots = [jnp.where(end, above, below), jnp.where(end, below, above)]
    else:
        rows = source[nbr.T.reshape(-1)]
        slots = [rows[s * n:(s + 1) * n] for s in range(k_max)]
    return [
        jnp.where(live[:, s:s + 1] > 0, slots[s], jnp.inf)
        for s in range(k_max)
    ]


def _pick_plane(planes, idx):
    """``planes[idx[i]]`` row by row, as a chain of selects."""
    out = planes[0]
    for j in range(1, len(planes)):
        out = jnp.where((idx == j)[:, None], planes[j], out)
    return out


def closed_neighbourhood_rule(name: str, budget: int):
    """The two count rules over a neighbor table: ``rule(own, source, nbr,
    live) -> aggregate``, all in the accumulation dtype.

    ``own``: the workers' own rows [N, d]; ``source``: the rows the table
    addresses (``own`` itself unsharded, the halo-extended block under a
    worker mesh); ``nbr``: the [N, k_max] table into ``source`` (a host
    array or a traced one); ``live``: this round's 0/1 bits [N, k_max].
    ONE definition for ``make_gather_robust_aggregator`` and the halo form
    (``parallel/collectives.py``), which are held BITWISE equal.

    The table's static width decides how the closed neighborhood is put in
    order (module docstring): planes and a compare-exchange network up to
    ``NETWORK_MAX_SLOTS`` slots, the [N, k_max + 1, d] stack and
    ``jnp.sort`` above. Position j holds the same value either way; valid
    values occupy positions [0, c_i), the +inf padding is never selected.
    The planes of a ring's host table are two shifts of ``source``, of any
    other table one gather (``_received_planes``): the same bits.
    """
    if name not in ("trimmed_mean", "median"):
        raise ValueError(f"{name!r} is no count rule")

    def rule(own, source, nbr, live):
        k_max = nbr.shape[1]
        counts = jnp.sum(live, axis=1) + 1.0
        as_planes = k_max + 1 <= NETWORK_MAX_SLOTS
        if as_planes:
            ordered = _ordered_planes(
                [own] + _received_planes(source, nbr, live)
            )
        else:
            vals = jnp.where(live[:, :, None] > 0, source[nbr], jnp.inf)
            closed = jnp.concatenate([own[:, None, :], vals], axis=1)
            ordered = jnp.sort(closed, axis=1)

        if name == "trimmed_mean":
            # Keep the positions [b, c_i − b).
            kept = jnp.maximum(counts - 2 * budget, 0.0)
            if as_planes:
                # Only the planes b … k_max − b can be kept at any count;
                # added in rising position. Where ONE plane is kept the
                # total is that plane to the bit.
                total = None
                for j in range(budget, k_max + 1 - budget):
                    term = jnp.where(
                        (counts - budget > j)[:, None], ordered[j], 0.0
                    )
                    total = term if total is None else total + term
            else:
                pos = jnp.arange(k_max + 1, dtype=own.dtype)
                keep = (pos[None, :] >= budget) & (
                    pos[None, :] < (counts - budget)[:, None]
                )
                total = jnp.sum(
                    jnp.where(keep[:, :, None], ordered, 0.0), axis=1
                )
            mean = total / jnp.maximum(kept, 1.0)[:, None]
            # Faulted-down neighborhoods (c_i ≤ 2b): identity row.
            return jnp.where((kept >= 1.0)[:, None], mean, own)

        c = counts.astype(jnp.int32)
        lo = jnp.maximum((c - 1) // 2, 0)
        hi = jnp.maximum(c // 2, 0)
        if as_planes:
            # hi ≤ (k_max + 1) // 2: the planes above are never the middle.
            middle = ordered[: (k_max + 1) // 2 + 1]
            return 0.5 * (_pick_plane(middle, lo) + _pick_plane(middle, hi))
        return 0.5 * (
            jnp.take_along_axis(ordered, lo[:, None, None], axis=1)
            + jnp.take_along_axis(ordered, hi[:, None, None], axis=1)
        )[:, 0, :]

    return rule


def make_robust_aggregator(
    name: str, budget: int, clip_tau: float = 0.0
) -> RobustAggregator:
    """Build ``aggregate(A_t, x) -> x_new`` for one rule.

    ``A_t``: realized 0/1 adjacency (zero diagonal, convention
    ``A[i, j] = 1`` iff j's message reaches i this round); ``x``: the
    [N, d] stack of models AS TRANSMITTED (the adversary's corruption is
    applied upstream — honest rows carry true models). Internal math runs
    in at-least-float32 like the fault machinery; only the output is cast
    back to the input dtype.
    """
    if name not in AGGREGATIONS or name == "gossip":
        raise ValueError(
            f"no robust aggregator named {name!r}; plain gossip is built by "
            "ops/mixing.py / parallel/faults.py"
        )
    if budget < 1:
        # b == 0 is the caller's short-circuit to plain gossip (for the
        # median, b only gates and sizes the validated assumption — the
        # rule itself is budget-free); reaching the screened path with an
        # empty budget is a wiring bug.
        raise ValueError(
            f"{name} needs a positive attack budget, got {budget}"
        )

    def _closed_sorted(A, x):
        """Ascending per-coordinate sort of the closed neighborhood.

        Returns (sorted [N, N, d] with +inf beyond each row's count,
        counts [N]): row i holds the values {x_j : A[i,j]=1} ∪ {x_i}.
        """
        n = A.shape[0]
        closed = A + jnp.eye(n, dtype=A.dtype)
        mask = closed > 0
        vals = jnp.where(mask[:, :, None], x[None, :, :], jnp.inf)
        return jnp.sort(vals, axis=1), jnp.sum(closed, axis=1)

    if name == "trimmed_mean":

        def aggregate(A, x):
            acc = jnp.promote_types(jnp.float32, x.dtype)
            xa = x.astype(acc)
            s, counts = _closed_sorted(A.astype(acc), xa)
            # Valid entries occupy sorted positions [0, c_i); keep the
            # slice [b, c_i − b) — the +inf padding is never selected.
            pos = jnp.arange(A.shape[0], dtype=acc)
            keep = (pos[None, :] >= budget) & (
                pos[None, :] < (counts - budget)[:, None]
            )
            kept = jnp.maximum(counts - 2 * budget, 0.0)
            total = jnp.sum(jnp.where(keep[:, :, None], s, 0.0), axis=1)
            mean = total / jnp.maximum(kept, 1.0)[:, None]
            # Faulted-down neighborhoods (c_i ≤ 2b): identity row.
            return jnp.where(
                (kept >= 1.0)[:, None], mean, xa
            ).astype(x.dtype)

    elif name == "median":

        def aggregate(A, x):
            acc = jnp.promote_types(jnp.float32, x.dtype)
            xa = x.astype(acc)
            s, counts = _closed_sorted(A.astype(acc), xa)
            c = counts.astype(jnp.int32)
            lo = jnp.maximum((c - 1) // 2, 0)[:, None, None]
            hi = jnp.maximum(c // 2, 0)[:, None, None]
            med = 0.5 * (
                jnp.take_along_axis(s, lo, axis=1)
                + jnp.take_along_axis(s, hi, axis=1)
            )
            return med[:, 0, :].astype(x.dtype)

    else:  # clipped_gossip
        # Adaptive vs fixed radius is a HOST decision: a traced clip_tau (a
        # replica-swept axis, run_batch-validated > 0) is always the fixed
        # form — only a concrete 0.0 selects the adaptive per-node radius.
        adaptive_tau = isinstance(clip_tau, (int, float)) and clip_tau <= 0.0

        def aggregate(A, x):
            acc = jnp.promote_types(jnp.float32, x.dtype)
            Aa = A.astype(acc)
            xa = x.astype(acc)
            W = metropolis_hastings_weights(Aa)
            diffs = xa[None, :, :] - xa[:, None, :]  # [recv i, send j, d]
            norms = jnp.sqrt(jnp.sum(diffs * diffs, axis=-1))
            if not adaptive_tau:
                tau = jnp.full(A.shape[0], clip_tau, dtype=acc)
            else:
                tau = _adaptive_clip_tau(Aa, norms, budget, A.shape[0])
            factor = jnp.minimum(
                1.0, tau[:, None] / jnp.maximum(norms, jnp.finfo(acc).tiny)
            )
            # Off-graph entries have W_ij = 0; the diagonal difference is 0.
            moved = jnp.sum(W[:, :, None] * diffs * factor[:, :, None], axis=1)
            return (xa + moved).astype(x.dtype)

    return _on_flat_rows(aggregate)


def make_gather_robust_aggregator(
    name: str,
    budget: int,
    nbr_idx: np.ndarray,
    clip_tau: float = 0.0,
) -> Callable[[jax.Array, jax.Array], jax.Array]:
    """Degree-bounded ``aggregate(live, x) -> x_new`` for one rule.

    ``nbr_idx``: the static [N, k_max] padded neighbor-index table of the
    BASE topology (``parallel/topology.py::neighbor_table``; padded slots
    point at self). ``live``: per-incident-edge 0/1 liveness bits
    [N, k_max] for this round — the gather-form realized adjacency
    (``FaultyMixing.neighbor_liveness``, or the static ``nbr_mask`` when
    fault-free); symmetric by construction, so a neighbor's realized
    degree is recoverable by gathering row sums. ``x``: the [N, d] stack
    AS TRANSMITTED, like the dense form.

    Each rule mirrors its dense twin term for term over the k_max slots —
    same +inf padding, same accumulation dtype floor, same identity-row
    degradation for faulted-down neighborhoods (realized closed
    neighborhood ≤ 2b, or deg ≤ b for adaptive clipping) — but the
    ordering, rank selection, and neighbor reduction are O(k_max), not
    O(N). The two count rules are ``closed_neighbourhood_rule``, which
    reads off the table's width whether the slots are planes put in order
    by a compare-exchange network or a stack given to ``jnp.sort``, and
    off the table itself whether a ring's planes come by two shifts.
    """
    if name not in AGGREGATIONS or name == "gossip":
        raise ValueError(
            f"no robust aggregator named {name!r}; plain gossip is built by "
            "ops/mixing.py / parallel/faults.py"
        )
    if budget < 1:
        raise ValueError(
            f"{name} needs a positive attack budget, got {budget}"
        )
    k_max = nbr_idx.shape[1]

    if name in ("trimmed_mean", "median"):
        # The table stays on the host, where the rule can read whether it
        # is a ring's (then no table reaches the program); otherwise the
        # program's constant is the flat s32[k_max·N] the gather reads
        # ([N, k_max] s32 on the device lies in tiles of 128 lanes).
        table = np.asarray(nbr_idx, dtype=np.int32)
        rule = closed_neighbourhood_rule(name, budget)

        def aggregate(live, x):
            acc = jnp.promote_types(jnp.float32, x.dtype)
            xa = x.astype(acc)
            return rule(xa, xa, table, live.astype(acc)).astype(x.dtype)

    else:  # clipped_gossip
        # Same host decision as the dense twin: traced clip_tau (a swept
        # replica axis) is the fixed form; concrete 0.0 is adaptive.
        adaptive_tau = isinstance(clip_tau, (int, float)) and clip_tau <= 0.0
        nbr = jnp.asarray(nbr_idx, dtype=jnp.int32)  # [N, k_max]

        def aggregate(live, x):
            acc = jnp.promote_types(jnp.float32, x.dtype)
            xa = x.astype(acc)
            lv = live.astype(acc)
            deg = jnp.sum(lv, axis=1)  # realized degrees [N]
            diffs = xa[nbr] - xa[:, None, :]  # [recv i, slot, d]
            norms = jnp.sqrt(jnp.sum(diffs * diffs, axis=-1))
            if not adaptive_tau:
                tau = jnp.full(nbr.shape[0], clip_tau, dtype=acc)
            else:
                tau = _adaptive_clip_tau(lv, norms, budget, k_max)
            # MH weights on realized degrees, gather form: the liveness is
            # symmetric, so a neighbor's realized degree is its row sum
            # gathered through the slot table; dead slots carry lv = 0.
            w = lv / (1.0 + jnp.maximum(deg[:, None], deg[nbr]))
            factor = jnp.minimum(
                1.0, tau[:, None] / jnp.maximum(norms, jnp.finfo(acc).tiny)
            )
            moved = jnp.sum(w[:, :, None] * diffs * factor[:, :, None], axis=1)
            return (xa + moved).astype(x.dtype)

    return _on_flat_rows(aggregate)


def _screening_fraction(name: str, budget: int, counts):
    """Fraction of received (open-neighborhood) messages a count-only rule
    screens out, given realized CLOSED-neighborhood counts ``counts``.

    trimmed_mean keeps max(c−2b, 1) values (1 = the identity-row
    degradation), the median keeps the middle one (two for even counts);
    everything else of the c−1 received messages is screened. Shared by the
    jax activity twins below; float32 like all fault-layer accounting.
    """
    c = counts.astype(jnp.float32)
    if name == "trimmed_mean":
        kept = jnp.maximum(c - 2.0 * budget, 1.0)
    else:  # median
        kept = 2.0 - jnp.mod(c, 2.0)
    return (c - kept) / jnp.maximum(c - 1.0, 1.0)


def make_robust_activity(
    name: str, budget: int, clip_tau: float = 0.0
) -> RobustAggregator:
    """Telemetry twin of ``make_robust_aggregator``: ``activity(A_t, x) ->
    scalar`` — the network-mean fraction of received neighbor messages the
    rule screened out this round (trimmed values for trimmed_mean/median;
    messages actually clipped — ‖diff‖ > τᵢ — for clipped_gossip, with τᵢ
    recomputed exactly as the aggregator computes it). Pure observability:
    nothing here feeds back into the step. float32 output.
    """
    if name not in AGGREGATIONS or name == "gossip":
        raise ValueError(
            f"no robust aggregator named {name!r}; plain gossip screens "
            "nothing (activity is identically 0)"
        )
    if budget < 1:
        raise ValueError(f"{name} needs a positive attack budget, got {budget}")

    if name in ("trimmed_mean", "median"):

        def activity(A, x):
            counts = jnp.sum(A.astype(jnp.float32), axis=1) + 1.0
            return jnp.mean(_screening_fraction(name, budget, counts))

    else:  # clipped_gossip — same adaptive/fixed τ decision as the rule
        adaptive_tau = isinstance(clip_tau, (int, float)) and clip_tau <= 0.0

        def activity(A, x):
            acc = jnp.promote_types(jnp.float32, x.dtype)
            Aa = A.astype(acc)
            xa = x.astype(acc)
            diffs = xa[None, :, :] - xa[:, None, :]
            norms = jnp.sqrt(jnp.sum(diffs * diffs, axis=-1))
            if not adaptive_tau:
                tau = jnp.full(A.shape[0], clip_tau, dtype=acc)
            else:
                tau = _adaptive_clip_tau(Aa, norms, budget, A.shape[0])
            clipped = jnp.sum(Aa * (norms > tau[:, None]))
            return (clipped / jnp.maximum(jnp.sum(Aa), 1.0)).astype(
                jnp.float32
            )

    return _on_flat_rows(activity)


def make_gather_robust_activity(
    name: str, budget: int, nbr_idx: np.ndarray, clip_tau: float = 0.0
) -> Callable[[jax.Array, jax.Array], jax.Array]:
    """Degree-bounded twin of ``make_robust_activity``: ``activity(live, x)``
    over the static [N, k_max] neighbor table + per-slot liveness bits —
    the same realization the gather aggregator screens. float32 output.
    """
    if name not in AGGREGATIONS or name == "gossip":
        raise ValueError(
            f"no robust aggregator named {name!r}; plain gossip screens "
            "nothing (activity is identically 0)"
        )
    if budget < 1:
        raise ValueError(f"{name} needs a positive attack budget, got {budget}")
    nbr = jnp.asarray(nbr_idx, dtype=jnp.int32)
    k_max = nbr.shape[1]

    if name in ("trimmed_mean", "median"):

        def activity(live, x):
            counts = jnp.sum(live.astype(jnp.float32), axis=1) + 1.0
            return jnp.mean(_screening_fraction(name, budget, counts))

    else:  # clipped_gossip

        adaptive_tau = isinstance(clip_tau, (int, float)) and clip_tau <= 0.0

        def activity(live, x):
            acc = jnp.promote_types(jnp.float32, x.dtype)
            lv = live.astype(acc)
            xa = x.astype(acc)
            diffs = xa[nbr] - xa[:, None, :]
            norms = jnp.sqrt(jnp.sum(diffs * diffs, axis=-1))
            if not adaptive_tau:
                tau = jnp.full(nbr.shape[0], clip_tau, dtype=acc)
            else:
                tau = _adaptive_clip_tau(lv, norms, budget, k_max)
            clipped = jnp.sum(lv * (norms > tau[:, None]))
            return (clipped / jnp.maximum(jnp.sum(lv), 1.0)).astype(
                jnp.float32
            )

    return _on_flat_rows(activity)


def robust_activity_np(
    name: str, A: np.ndarray, x: np.ndarray, budget: int, clip_tau: float = 0.0
) -> float:
    """Independent per-node oracle of the activity twins (float64 numpy,
    numpy-backend convention — written from the definitions, not the jax
    forms)."""
    n = x.shape[0]
    if name in ("trimmed_mean", "median"):
        fracs = []
        for i in range(n):
            c = int(A[i].sum()) + 1
            if name == "trimmed_mean":
                kept = max(c - 2 * budget, 1)
            else:
                kept = 2 - (c % 2)
            fracs.append((c - kept) / max(c - 1, 1))
        return float(np.mean(fracs))
    if name != "clipped_gossip":
        raise ValueError(f"no robust aggregator named {name!r}")
    clipped = 0.0
    total = 0.0
    for i in range(n):
        nbrs = np.nonzero(A[i])[0]
        if len(nbrs) == 0:
            continue
        norms = np.linalg.norm(x[nbrs] - x[i], axis=1)
        if clip_tau > 0.0:
            tau = clip_tau
        else:
            k = len(nbrs) - budget
            tau = float(np.sort(norms)[k - 1]) if k >= 1 else 0.0
        clipped += float(np.sum(norms > tau))
        total += float(len(nbrs))
    return clipped / total if total else 0.0


def robust_aggregate_np(
    name: str, A: np.ndarray, x: np.ndarray, budget: int, clip_tau: float = 0.0
) -> np.ndarray:
    """Independent per-node oracle of the rules above (float64 numpy).

    Written as explicit per-node loops from the definitions, not by
    transcribing the vectorized jax forms — the numpy-backend convention
    for everything the equivalence tests pin.
    """
    n = x.shape[0]
    degs = A.sum(axis=1)
    out = np.empty_like(x, dtype=np.float64)
    for i in range(n):
        nbrs = np.nonzero(A[i])[0]
        if name in ("trimmed_mean", "median"):
            vals = np.concatenate([x[nbrs], x[i : i + 1]], axis=0)
            s = np.sort(vals, axis=0)
            c = vals.shape[0]
            if name == "median":
                out[i] = 0.5 * (s[(c - 1) // 2] + s[c // 2])
            elif c - 2 * budget >= 1:
                out[i] = s[budget : c - budget].mean(axis=0)
            else:
                out[i] = x[i]
        elif name == "clipped_gossip":
            diffs = x[nbrs] - x[i]
            norms = np.linalg.norm(diffs, axis=1)
            if clip_tau > 0.0:
                tau = clip_tau
            else:
                k = len(nbrs) - budget
                tau = float(np.sort(norms)[k - 1]) if k >= 1 else 0.0
            w = 1.0 / (1.0 + np.maximum(degs[i], degs[nbrs]))
            fac = np.minimum(1.0, tau / np.maximum(norms, np.finfo(np.float64).tiny))
            out[i] = x[i] + (w[:, None] * diffs * fac[:, None]).sum(axis=0)
        else:
            raise ValueError(f"no robust aggregator named {name!r}")
    return out
