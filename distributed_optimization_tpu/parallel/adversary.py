"""Byzantine adversary injection: workers that send WRONG models.

The fault layer (``parallel/faults.py``) covers benign failures — links
and workers that go silent. This module covers the adversarial dimension
the reference's report only alludes to (its parameter-server single point
of failure): a static, seed-deterministic set of Byzantine workers that
participates in every round but replaces its OUTGOING model with an
attack payload. Three canonical payloads (Blanchard et al. 2017; Baruch
et al. 2019; He-Karimireddy-Jaggi 2022):

- **sign_flip**: send −scale·x_i — pulls every neighbor away from descent
  along the attacker's own trajectory;
- **large_noise**: send x_i + scale·N(0, I), redrawn per (seed, t) — a
  variance attack that stalls consensus without an obvious direction;
- **alie** ("a little is enough"): the colluders compute the honest
  workers' per-coordinate mean and standard deviation (omniscient
  collusion — the strongest static threat model) and ALL send
  mean − scale·std, an outlier small enough to hide inside the honest
  spread and evade norm screens.

Payloads are pure functions of (seed, iteration, transmitted stack) —
like fault masks and batch sampling there is no carried RNG state, so
attack realizations are reproducible and checkpoint/resume-safe. Within
one iteration the corruption is applied per gossip round (gradient
tracking corrupts both its x and y exchanges); ``large_noise`` reuses the
(seed, t) draw across same-iteration rounds, which keeps resume exactness
without per-call counters. All adversarial math runs in at-least-float32
(the faults convention); only the corrupted stack is cast back to the run
dtype.

The Byzantine SET is sampled host-side from the config seed and shared
verbatim by the jax backend, the numpy oracle backend, the incident
forensics and the honest-only metrics — all must agree on who is lying, so
all ask ONE resolver, ``byzantine_set(config, topo)``. Two placements
(``config.byzantine_placement``, docs/BYZANTINE.md "Placement"):
``uniform`` (``byzantine_mask``: a draw that does not know the graph) and
``within_budget`` (``place_within_budget``: a seeded greedy on the graph's
neighbor table that leaves every honest worker at most ``robust_b``
attacking neighbours — the screening rules' per-neighbourhood assumption,
which a uniform draw of f attackers on a ring of N breaks at f²/N honest
workers in expectation).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from distributed_optimization_tpu.config import ATTACKS
from distributed_optimization_tpu.observability import device_scopes
from distributed_optimization_tpu.parallel.topology import (
    cached_topology,
    neighbor_tables_for,
)

# Stream tags folded into the seed key, disjoint from the fault layer's
# (0x0FA17 edges, 0x57A66 stragglers, 0x3A7C4 matchings).
_BYZ_SET_TAG = 0xB12A
_BYZ_NOISE_TAG = 0xBAD0


def byzantine_mask(n_workers: int, n_byzantine: int, seed: int) -> np.ndarray:
    """Static Byzantine node set as a host [N] bool mask.

    Seed-deterministic (a fresh Generator keyed on (seed, tag)), so every
    layer that needs the honest/Byzantine split — backends, metrics,
    benches — reconstructs the identical set from the config alone.
    """
    if not 0 <= n_byzantine < n_workers:
        raise ValueError(
            f"n_byzantine must be in [0, n_workers), got {n_byzantine} "
            f"of {n_workers}"
        )
    mask = np.zeros(n_workers, dtype=bool)
    if n_byzantine > 0:
        rng = np.random.default_rng([seed, _BYZ_SET_TAG])
        mask[rng.choice(n_workers, size=n_byzantine, replace=False)] = True
    return mask


def place_within_budget(
    nbr_idx: np.ndarray, nbr_mask: np.ndarray, n_byzantine: int,
    budget: int, seed: int,
) -> np.ndarray:
    """Byzantine set drawn WITHIN the screening budget, as a host [N] bool
    mask: the workers in a seeded random order (``permutation(N)`` of the
    stream ``byzantine_mask`` draws from), a candidate admitted iff every
    CURRENTLY HONEST neighbour of it would still count at most ``budget``
    attackers among its neighbours; stops at ``n_byzantine``.

    On return every honest worker has at most ``budget`` attacking
    neighbours: an admission checks exactly the honest workers whose count
    it raises, and a worker that later turns attacker only drops a
    constraint. Reads the ``[N, k_max]`` neighbor table (padded slots
    masked out), never an [N, N] matrix. A function of (seed, graph,
    n_byzantine, budget) alone. Host work of a Python loop over the
    candidates tried (about f / (1 − share·k) of them; 30 ms at f = 24,576
    on a ring of 2^18), the table's rows fetched a block of candidates at a
    time so the loop never walks what it does not try.
    """
    n = nbr_idx.shape[0]
    if not 0 <= n_byzantine < n:
        raise ValueError(
            f"n_byzantine must be in [0, n_workers), got {n_byzantine} "
            f"of {n}"
        )
    if budget < 1:
        raise ValueError(
            f"a placement within the budget needs robust_b >= 1, got {budget}"
        )
    order = np.random.default_rng([seed, _BYZ_SET_TAG]).permutation(n)
    padded = not bool(np.all(nbr_mask))
    byz = bytearray(n)  # 1 = attacker
    hits = [0] * n      # attackers among a worker's neighbours
    placed = 0
    block = max(4096, 2 * n_byzantine)
    for lo in range(0, n, block):
        if placed == n_byzantine:
            break
        cand = order[lo:lo + block]
        rows = nbr_idx[cand].tolist()
        live = nbr_mask[cand].tolist() if padded else None
        for k, c in enumerate(cand.tolist()):
            nbrs = rows[k]
            if padded:
                nbrs = [j for j, m in zip(nbrs, live[k]) if m]
            if all(byz[j] or hits[j] < budget for j in nbrs):
                byz[c] = 1
                for j in nbrs:
                    hits[j] += 1
                placed += 1
                if placed == n_byzantine:
                    break
    if placed < n_byzantine:
        raise ValueError(
            f"byzantine_placement='within_budget' placed {placed} of the "
            f"{n_byzantine} attackers asked for: on this graph ({n} "
            f"workers, max degree {nbr_idx.shape[1]}) with robust_b="
            f"{budget} and seed {seed} the order ran out — every worker "
            f"left would give an honest neighbour more than {budget} "
            f"attacking neighbours. The graph's limit under this order is "
            f"{placed}: lower n_byzantine or raise robust_b"
        )
    return np.frombuffer(byz, dtype=bool).copy()


def attackers_per_honest_neighbourhood(
    byz: np.ndarray, nbr_idx: np.ndarray, nbr_mask: np.ndarray
) -> int:
    """The most attackers any HONEST worker counts among its neighbours:
    the screening rules' guarantee as a number (``budget_max`` on the
    ``dopt.run`` root; at most ``robust_b`` under ``within_budget``)."""
    counts = np.sum(byz[nbr_idx] & nbr_mask, axis=1)
    honest = ~byz
    return int(counts[honest].max()) if honest.any() else 0


def byzantine_set(config, topo=None, *, seed: Optional[int] = None) -> np.ndarray:
    """WHO lies in a run of ``config``: the one resolver every layer asks
    (``make_adversary``'s callers in the jax backend, ``run_batch``'s
    per-seed sets, the numpy oracle, ``monitors.fault_context``).
    ``seed`` overrides the config's (a replica's); ``topo`` is the run's
    graph where the caller holds it — ``within_budget`` reads its neighbor
    table and builds it from the config otherwise."""
    seed = config.seed if seed is None else seed
    if config.byzantine_placement == "uniform":
        return byzantine_mask(config.n_workers, config.n_byzantine, seed)
    if topo is None:
        topo, _ = cached_topology(
            config.topology, config.n_workers,
            erdos_renyi_p=config.erdos_renyi_p,
            seed=config.resolved_topology_seed(),
            impl=config.resolved_topology_impl(),
            sampler=config.resolved_topology_sampler(),
        )
    return place_within_budget(
        *neighbor_tables_for(topo), config.n_byzantine, config.robust_b, seed
    )


@dataclasses.dataclass(frozen=True)
class Adversary:
    """One attack bound to its static Byzantine set.

    ``corrupt(t, x)``: replace Byzantine rows of the [N, d] stack with the
    iteration-t payload (honest rows pass through untouched — a Byzantine
    worker lies to its neighbors; it cannot touch anyone else's state).
    """

    attack: str
    n_byzantine: int
    byzantine: np.ndarray  # host [N] bool, static for the whole run
    corrupt: Callable[[jax.Array, jax.Array], jax.Array]

    @property
    def honest(self) -> np.ndarray:
        return ~self.byzantine


def make_adversary(
    n_workers: int,
    attack: str,
    n_byzantine: int,
    attack_scale: float,
    seed: int,
    *,
    byz=None,
    noise_key=None,
) -> Optional[Adversary]:
    """Build the jit-compatible adversary for a config (None when benign).

    ``byz``/``noise_key`` override the seed-derived Byzantine set and
    large-noise stream — the replica-batched path
    (``jax_backend.run_batch``) derives both per replica host-side (the
    identical ``byzantine_mask``/fold-in formulas) and threads them
    through ``vmap``, so they may be tracers here.
    """
    if attack not in ATTACKS:
        raise ValueError(f"Unknown attack: {attack}")
    if attack == "none":
        return None
    if byz is None:
        byz = byzantine_mask(n_workers, n_byzantine, seed)
    byz_dev = jnp.asarray(byz, dtype=jnp.float32)
    if noise_key is None:
        noise_key = jax.random.fold_in(jax.random.key(seed), _BYZ_NOISE_TAG)

    def corrupt(t, x):
        acc = jnp.promote_types(jnp.float32, x.dtype)
        xa = x.astype(acc)
        m = byz_dev.astype(acc).reshape((-1,) + (1,) * (x.ndim - 1))
        if attack == "sign_flip":
            payload = -attack_scale * xa
        elif attack == "large_noise":
            key = jax.random.fold_in(noise_key, t)
            payload = xa + attack_scale * jax.random.normal(
                key, x.shape, dtype=acc
            )
        else:  # alie: colluders share honest_mean − scale·honest_std
            h = (1.0 - byz_dev).astype(acc)
            n_honest = jnp.sum(h)
            rows = tuple(range(1, x.ndim))  # a unit axis per parameter axis
            mu = jnp.sum(xa * jnp.expand_dims(h, rows), axis=0) / n_honest
            var = (
                jnp.sum(jnp.expand_dims(h, rows) * (xa - mu[None]) ** 2, axis=0)
                / n_honest
            )
            payload = jnp.broadcast_to(
                mu - attack_scale * jnp.sqrt(var), xa.shape
            )
        return jnp.where(m > 0, payload, xa).astype(x.dtype)

    return Adversary(
        attack=attack, n_byzantine=n_byzantine, byzantine=byz, corrupt=corrupt
    )


def make_byzantine_mixing(
    adversary: Optional[Adversary],
    base_mix: Callable[[jax.Array, jax.Array], jax.Array],
    *,
    aggregate_t=None,
) -> Callable[[jax.Array, jax.Array], jax.Array]:
    """Compose corruption and (robust) aggregation into one mix(t, x).

    ``base_mix(t, x)``: the benign time-varying gossip (static MixingOp or
    FaultyMixing) — used when no robust rule is active, i.e. the
    VULNERABLE baseline the breakdown benches measure. With
    ``aggregate_t(t, x)`` (an ``ops.robust_aggregation`` rule bound by the
    backend to its per-iteration graph source — the dense realized
    adjacency or the gather-form neighbor liveness, per ``robust_impl``)
    the mix instead screens the corrupted stack, so attacks, edge faults,
    and the defense all see the same per-iteration realization.
    ``adversary=None`` gives the pure-defense path (robust rule, no
    attackers).

    Byzantine ROWS keep the benign mix of the TRUE stack: the literature's
    threat model is an attacker that runs honest dynamics internally (so
    its transmitted lie — e.g. a flipped model — tracks a plausible
    trajectory) and lies only on the wire. Feeding attackers their own
    corrupted echo instead makes their state diverge exponentially under
    self-centered rules, overflowing to inf and poisoning the honest rows
    through NaN payloads — a simulation artifact, not an attack.

    The corruption and the screening rule are the device scope ``robust``
    (``dopt.robust``, nested in the caller's ``dopt.gossip``: the innermost
    scope bills); the benign mix — the attackers' rows, and the vulnerable
    baseline's — stays ``gossip``.
    """
    corrupt = (
        adversary.corrupt if adversary is not None else (lambda t, x: x)
    )

    def honest_view(t, x):
        with device_scopes.scope("robust"):
            xa = corrupt(t, x)
            if aggregate_t is not None:
                return aggregate_t(t, xa)
        return base_mix(t, xa)

    if adversary is None:
        return honest_view

    byz_col = jnp.asarray(adversary.byzantine, dtype=jnp.float32)

    def mix(t, x):
        m = byz_col.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)
        return jnp.where(m > 0, base_mix(t, x), honest_view(t, x))

    return mix
