"""Communication-compression operators for gossip algorithms.

Not present in the reference (its gossip always exchanges full d-vectors,
reference ``trainer.py:169-173``); this is the compressed-gossip capability
from the same literature line the reference's report builds on (Koloskova,
Stich & Jaggi '19 — report ref [13] authors — define CHOCO-SGD around exactly
these operators).

Each operator is a jittable contraction ``Q(key, v) -> v_compressed`` over
the last axis of an ``[N, d]`` stack, together with its per-edge float cost
(the analytic comms-accounting payload; index transmission is counted as one
float per index, the accounting convention of the sparsification literature):

- ``top_k``: keep the k largest-|magnitude| coordinates per row (biased,
  contraction factor delta = k/d); cost 2k (k values + k indices).
- ``random_k``: keep k uniformly random coordinates per row (unbiased after
  (d/k)-rescaling in expectation, but used UNscaled inside CHOCO, which
  requires only a contraction); cost 2k.
- ``qsgd``: stochastic uniform quantization to s = 2^b levels per row
  (Alistarh et al. '17 as used by CHOCO: ‖v‖·sign(v)·ξ(v,s) with the
  1/(1+min(d/s², √d/s)) scaling that makes it a contraction); cost counted
  as d·(b+1)/32 + 1 floats per edge (b+1 bits per coordinate + the norm).
- ``none``: identity; cost d.

All operators satisfy the contraction property
E‖v − Q(v)‖² ≤ (1 − delta)‖v‖², delta > 0 — the condition CHOCO's
convergence proof needs.
"""

from __future__ import annotations

import dataclasses
from math import sqrt as np_sqrt
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from distributed_optimization_tpu.config import COMPRESSIONS

# Counter-based stream tag for the (possibly randomized) compressor draws,
# folded into the run seed: jax.random.fold_in(fold_in(key(seed), TAG), t).
# Single source shared by CHOCO and the generalized compressed dsgd /
# gradient-tracking steps — CHOCO's pre-refactor trajectories depend on
# exactly this derivation, so it must not drift.
_COMPRESSION_TAG = 0xC0C0


@dataclasses.dataclass(frozen=True)
class Compressor:
    """A jittable row-wise compression operator with its comms payload."""

    name: str
    apply: Callable[[Optional[jax.Array], jax.Array], jax.Array]
    floats_per_edge: float  # payload replacing d in the float accounting
    delta: float  # contraction factor (k/d; 1 for identity)


def make_compressor(name: str, d: int, k: int = 0) -> Compressor:
    """Build a compressor for d-dimensional rows.

    ``k``: coordinates kept for top_k/random_k (0 < k <= d); quantization
    BITS per coordinate for qsgd (1 <= k <= 16).
    """
    if name == "none":
        return Compressor("none", lambda key, v: v, float(d), 1.0)
    if name not in COMPRESSIONS:
        raise ValueError(f"Unknown compression: {name!r}; known {COMPRESSIONS}")

    if name == "qsgd":
        if not 1 <= k <= 16:
            raise ValueError(f"qsgd bits (compression_k) must be in [1, 16], got {k}")
        s = float(2 ** k)  # quantization levels
        # QSGD variance bound omega_var = min(d/s^2, sqrt(d)/s); scaling the
        # unbiased quantizer by omega = 1/(1 + omega_var) makes it a
        # contraction with delta = omega (Koloskova et al. '19, Sec. 2):
        # E||v - omega*xi(v)||^2 <= (1 - omega)||v||^2.
        omega = 1.0 / (1.0 + min(d / (s * s), np_sqrt(d) / s))

        def apply_qsgd(key, v):
            if key is None:
                raise ValueError("qsgd compression needs a PRNG key")
            norm = jnp.linalg.norm(v, axis=-1, keepdims=True)
            scale = jnp.where(norm > 0, norm, 1.0)
            level = jnp.abs(v) / scale * s  # in [0, s]
            low = jnp.floor(level)
            p_up = level - low  # stochastic rounding
            u = jax.random.uniform(key, v.shape)
            q = (low + (u < p_up)) / s
            return omega * norm * jnp.sign(v) * q

        bits_per_coord = k + 1  # sign + k magnitude bits
        floats_cost = d * bits_per_coord / 32.0 + 1.0  # + the row norm
        return Compressor("qsgd", apply_qsgd, floats_cost, omega)

    if not 0 < k <= d:
        raise ValueError(f"compression_k must be in (0, {d}], got {k}")

    def keep_top_scored(v, scores):
        """``v`` with all but the k top-scored coordinates of each row set
        to 0: exactly k survive, and of equal scores the one at the lower
        index does (``lax.top_k``'s order; the benchmark's plain reference,
        ``benchmark/reference/choco_ring.py``, restates the rule).

        Cost at large rows. The TPU compiler lowers ``top_k`` at this k to
        a stable sort of the whole row with an iota beside it, and the
        scatter to a flat scatter with a sort of its indices in front and a
        row-by-row copy back. On ``[96, 2097664]`` rows at k = 20,977 (one
        v5e, builder's chip runs, PR 26, ``softmax4096_choco_ring96``): the
        sort 542 ms an iteration, scatter and its way back 41 ms, mask
        arithmetic 8 ms, against 36 ms for the whole D-SGD step beside it;
        at d = 81 the same lines are a small partial reduce. A selection by
        threshold (no sort, no scatter) is the open ``perf_opt`` (PERF.md
        section 7); it has to keep this tie rule."""
        _, idx = jax.lax.top_k(scores, k)
        mask = jnp.zeros_like(v).at[
            jnp.arange(v.shape[0])[:, None], idx
        ].set(1.0)
        return v * mask

    if name == "top_k":

        def apply_topk(key, v):
            # Deterministic operator; key unused.
            return keep_top_scored(v, jnp.abs(v))

        return Compressor("top_k", apply_topk, 2.0 * k, k / d)

    def apply_randk(key, v):
        if key is None:
            raise ValueError("random_k compression needs a PRNG key")
        # Uniform scores = k uniformly random coordinates per row.
        return keep_top_scored(v, jax.random.uniform(key, v.shape))

    return Compressor("random_k", apply_randk, 2.0 * k, k / d)


# ------------------------------------------------- error-feedback machinery


def compression_key(seed: int, t, round: int = 0):
    """The counter-based PRNG key for iteration ``t``'s compressor draw.

    ``round`` distinguishes multiple exchanges within one iteration
    (gradient tracking compresses both its x and y gossip rounds); round 0
    is EXACTLY the pre-refactor CHOCO derivation, so single-exchange
    algorithms (choco, compressed dsgd) keep their historical draws.
    """
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.key(seed), _COMPRESSION_TAG), t
    )
    if round:
        key = jax.random.fold_in(key, round)
    return key


@dataclasses.dataclass(frozen=True)
class ErrorFeedbackGossip:
    """CHOCO-style error-feedback compressed gossip, algorithm-agnostic.

    Generalized out of ``algorithms/choco.py`` (ISSUE-6 tentpole) so
    D-SGD and gradient tracking can route their gossip exchanges through
    the same machinery. Each worker carries a public estimate x̂_i (the
    error-accumulator memory) that every neighbor holds a copy of; one
    exchange transmits only q_i = Q(v_i − x̂_i):

        x̂⁺ = x̂ + Q(v − x̂)                ← the ONLY bits on the wire
        v⁺  = v + γ [(W − I) X̂⁺]          (gossip over the estimates)

    The compression error v − x̂⁺ stays in the carry and is re-offered to
    the compressor next round — the error-feedback property that keeps
    the scheme convergent for any contraction operator (Koloskova, Stich
    & Jaggi '19). Identity compression at γ = 1 makes one exchange exactly
    the plain W-mix (v⁺ = W v), which is why uncompressed trajectories
    are unaffected. ``floats_per_edge`` (the compressor's payload) is the
    comms-accounting hook the backends consume.

    The compressors contract over ONE parameter axis (top-k, the row norm),
    so a model-shaped stack ([N, d, K]) is flattened to [N, d·K] at this
    boundary and the results restored; for an [N, d] stack the reshapes are
    the identity and trace no op. Build the exchange for the flat row
    length (``row_dim``).
    """

    compressor: Compressor
    gamma: float

    @property
    def floats_per_edge(self) -> float:
        return self.compressor.floats_per_edge

    def init(self, x0) -> jax.Array:
        """The estimate memory starts at 0 — every copy trivially agrees."""
        return jnp.zeros_like(x0)

    def exchange(
        self, key, v, memory, mix: Callable
    ) -> Tuple[jax.Array, jax.Array]:
        """One compressed gossip exchange: ``(v⁺, x̂⁺)``.

        ``mix``: the backend's x → W x collective (the estimates gossip
        through whatever mixing implementation the run selected). Ops are
        term-for-term the pre-refactor CHOCO step — trajectories are
        bitwise-unchanged (pinned in tests/test_choco.py).
        """
        q = self.compressor.apply(
            key, (v - memory).reshape(v.shape[0], -1)
        ).reshape(v.shape)
        memory_new = memory + q
        v_new = v + self.gamma * (mix(memory_new) - memory_new)
        return v_new, memory_new

    def exchange_sharded(
        self, key, v, memory, halo, compressed_mix: Callable
    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """One compressed exchange over the worker mesh: ``(v⁺, x̂⁺, halo⁺)``.

        ``compressed_mix(q, x̂⁺, halo) -> (W x̂⁺, halo⁺)`` is the sharded
        wire form (``collectives.make_halo_compressed_mixing_op``): only
        the increment q's boundary rows cross devices, and ``halo`` is the
        persistent receiver-side copy of the neighbors' estimates that the
        q rows scatter-ADD into — the receiver replays the owner's
        ``x̂ ← x̂ + q`` update, which is what makes shipping q sufficient.
        The local algebra (q, x̂⁺, the γ-step) is term-for-term
        ``exchange``; the compressor runs OUTSIDE shard_map on the
        row-sharded stack (row-wise + shape-based draws, so sharding
        cannot change its output), keeping the historical per-row draws.
        """
        q = self.compressor.apply(
            key, (v - memory).reshape(v.shape[0], -1)
        ).reshape(v.shape)
        memory_new = memory + q
        mixed, halo_new = compressed_mix(q, memory_new, halo)
        v_new = v + self.gamma * (mixed - memory_new)
        return v_new, memory_new, halo_new


def row_dim(x) -> int:
    """Flat length of one row of an [N, ...] stack: the ``d`` a compressor
    over that stack is built for."""
    return x.size // x.shape[0]


def make_error_feedback(
    name: str, d: int, k: int, gamma: float
) -> ErrorFeedbackGossip:
    """Build the shared error-feedback exchange for d-dimensional rows."""
    return ErrorFeedbackGossip(
        compressor=make_compressor(name, d, k), gamma=float(gamma)
    )
