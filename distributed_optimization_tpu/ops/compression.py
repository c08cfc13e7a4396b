"""Communication-compression operators for gossip algorithms.

Not present in the reference (its gossip always exchanges full d-vectors,
reference ``trainer.py:169-173``); this is the compressed-gossip capability
from the same literature line the reference's report builds on (Koloskova,
Stich & Jaggi '19 — report ref [13] authors — define CHOCO-SGD around exactly
these operators).

Each operator is a jittable contraction ``Q(key, v) -> v_compressed`` over
the rows of a stack ``[N, ...]`` (a row is one worker's whole model:
everything behind the first axis, d numbers in row-major order; ``[N, d]``
or model-shaped ``[N, d, K]``), together with its per-edge float cost
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
import functools
from math import sqrt as np_sqrt
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from distributed_optimization_tpu.config import COMPRESSIONS
from distributed_optimization_tpu.observability import device_scopes

# Counter-based stream tag for the (possibly randomized) compressor draws,
# folded into the run seed: jax.random.fold_in(fold_in(key(seed), TAG), t).
# Single source shared by CHOCO and the generalized compressed dsgd /
# gradient-tracking steps — CHOCO's pre-refactor trajectories depend on
# exactly this derivation, so it must not drift.
_COMPRESSION_TAG = 0xC0C0


# Bits of a threshold that one counting pass settles: a pass counts the
# 2**bits - 1 candidate thresholds in one read of the row. Two: the widest
# pass the chip's memory still bounds (``select_top_scored`` has the
# readings), and a divisor of every key width.
_BITS_PER_PASS = 2


def _passes(n_bits: int) -> int:
    """Counting passes that settle a threshold of ``n_bits`` bits."""
    return -(-n_bits // _BITS_PER_PASS)


def _count_threshold(keys, k):
    """Per row of unsigned ``keys`` ``[N, ...]``, the k-th largest and how
    many entries are above it: ``(t [N, 1, ...], count(> t) [N])``.

    Counted, not sorted: t is built from its most significant bits down,
    ``_BITS_PER_PASS`` at a time, each pass one fused compare-and-count
    over the stack (one variadic reduce, so the stack is read once a pass
    however many candidates are counted)."""
    dtype = keys.dtype
    rows = (keys.shape[0],) + (1,) * (keys.ndim - 1)
    axes = tuple(range(1, keys.ndim))
    passes = _passes(dtype.itemsize * 8)
    digits = [jnp.asarray(j, dtype) for j in range(1, 2 ** _BITS_PER_PASS)]

    def settle(i, carry):
        t, above = carry
        shift = ((passes - 1 - i) * _BITS_PER_PASS).astype(dtype)
        reached = tuple(
            (keys >= (t | (j << shift))).astype(jnp.int32) for j in digits
        )
        counts = jax.lax.reduce(
            reached, (jnp.int32(0),) * len(reached),
            lambda a, b: tuple(x + y for x, y in zip(a, b)), axes,
        )
        # The counts fall as the digit rises, so the digits that still reach
        # k are the lowest ones: their number is the digit to keep. The
        # lowest candidate that falls short is t + 1 unless a later pass
        # finds one: what reaches it is what is above t.
        digit = jnp.zeros_like(counts[0], dtype)
        for c in reversed(counts):
            above = jnp.where(c < k, c, above)
            digit = digit + (c >= k).astype(dtype)
        return t | (digit.reshape(rows) << shift), above

    return jax.lax.fori_loop(
        0, passes, settle,
        (jnp.zeros(rows, dtype), jnp.zeros(keys.shape[:1], jnp.int32)),
    )


def _index_of_nth(marked, n):
    """Per row of a boolean stack ``[N, ..., B]``, the row-major index of
    its n-th marked entry (``n`` [N], from 1; at most the row's count): a
    prefix count in two levels, over the marked entries of every block of
    B (the last axis), then inside the one block where the count reaches
    n, so the stack is read once."""
    rows, width = marked.shape[0], marked.shape[-1]
    per_block = jnp.sum(marked, axis=-1, dtype=jnp.int32).reshape(rows, -1)
    upto = jnp.cumsum(per_block, axis=1)
    block = jnp.argmax(upto >= n[:, None], axis=1)[:, None]
    before = jnp.take_along_axis(upto - per_block, block, axis=1)
    inside = jnp.take_along_axis(
        marked.reshape(rows, -1, width), block[:, :, None], axis=1
    )[:, 0]
    within = jnp.argmax(
        jnp.cumsum(inside, axis=1, dtype=jnp.int32) >= n[:, None] - before,
        axis=1,
    )
    return block[:, 0] * width + within


@functools.partial(jax.jit, static_argnames="k")
def select_top_scored(scores, k: int):
    """Boolean mask of the ``k`` largest entries of each row of a stack
    ``[N, ...]`` of non-negative scores (a row is everything behind the
    first axis, in row-major order): exactly k a row, and of equal scores
    the one at the lower index (``lax.top_k``'s order; the benchmark's plain
    reference, ``benchmark/reference/choco_ring.py``, restates the rule).

    An exact selection by threshold. Non-negative floats order as their bit
    patterns read as unsigned integers do, so the k-th largest score of a
    row is ``_count_threshold`` over the bits. Every entry above it is
    kept, and of the entries equal to it the first ``k - count(above)`` by
    index (``_index_of_nth``). No sort and no scatter: the mask is three
    comparisons.

    Cost at large rows (one v5e, builder's chip runs, PR 27,
    ``softmax4096_choco_ring96``: ``[96, 4097, 512]`` float32, k = 20,977;
    57.3 ms of device time an iteration). 16 passes of 1.08 ms over the
    0.8 GB of keys (740 GB/s): 17.3 ms; the keys written beside the
    gradient step, 2.9; the ties' mask and its counts by blocks of 512,
    one pass, 1.4, and a copy of the mask 0.6; the product in one fusion
    with CHOCO's x̂ + q, at the price of the sum alone: about 22.5 ms,
    where ``lax.top_k`` with a scatter, a stable sort of every whole row,
    was 592 (604 with the flatten the sort needed; PR 26). Ties are the
    rule at this size, not the exception: of 2 M float32 magnitudes a row,
    two at the threshold share a bit pattern in some row of 96 in 75 of the
    cell's 80 iterations, which is why the first ties are found by a
    prefix count in one pass and not by counting passes of their own (11 of
    0.67 ms, 6.9 ms an iteration, when they were). Alone on the chip: 27.3
    ms on ``[96, 4097, 512]``, and by bits a pass, on ``[96, 2097664]``
    with the ties left out, 54 (one), 37 (two), 34 (three, which divides no
    key width), 43 (four: 15 compares a number, and the vector unit bounds
    the pass, not the memory). A flat row is one block: its prefix count
    runs over the whole row. On the TPU the compiler turns ``v * mask``
    into a select, so a dropped negative entry reads +0.0 where the
    parent's product read -0.0; no value differs."""
    keys = jax.lax.bitcast_convert_type(
        scores, jnp.dtype(f"uint{scores.dtype.itemsize * 8}")
    )
    kth, above = _count_threshold(keys, k)
    tied = keys == kth
    last_tie = _index_of_nth(tied, k - above)
    index = jnp.arange(row_dim(keys), dtype=jnp.int32).reshape(
        (1,) + keys.shape[1:]
    )
    last_tie = last_tie.reshape(kth.shape)
    return (keys > kth) | (tied & (index <= last_tie))


def selection_label(name: str, dtype) -> str:
    """How compressor ``name`` picks the entries it keeps of a stack of
    ``dtype``, for a run's root span: ``threshold:16`` is
    ``select_top_scored`` with 16 counting passes over a row (one per
    ``_BITS_PER_PASS`` bits of a score: the magnitudes for ``top_k``, in the
    stack's dtype; uniform draws for ``random_k``, in jax's default float
    dtype); ``none`` where nothing is selected."""
    if name == "top_k":
        scores = jnp.dtype(dtype)
    elif name == "random_k":
        scores = jnp.dtype(jax.dtypes.canonicalize_dtype(float))
    else:
        return "none"
    return f"threshold:{_passes(scores.itemsize * 8)}"


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
            # The row norm is taken over ONE parameter axis: a model-shaped
            # stack is flattened here and the result restored.
            shape, v = v.shape, v.reshape(v.shape[0], -1)
            norm = jnp.linalg.norm(v, axis=-1, keepdims=True)
            scale = jnp.where(norm > 0, norm, 1.0)
            level = jnp.abs(v) / scale * s  # in [0, s]
            low = jnp.floor(level)
            p_up = level - low  # stochastic rounding
            u = jax.random.uniform(key, v.shape)
            q = (low + (u < p_up)) / s
            return (omega * norm * jnp.sign(v) * q).reshape(shape)

        bits_per_coord = k + 1  # sign + k magnitude bits
        floats_cost = d * bits_per_coord / 32.0 + 1.0  # + the row norm
        return Compressor("qsgd", apply_qsgd, floats_cost, omega)

    if not 0 < k <= d:
        raise ValueError(f"compression_k must be in (0, {d}], got {k}")

    def keep_top_scored(v, scores):
        """``v`` with all but the k top-scored entries of each row set to 0
        (``select_top_scored``): exactly k survive, and of equal scores the
        one at the lower index does."""
        return v * select_top_scored(scores, k).astype(v.dtype)

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
    comms-accounting hook the backends consume. An exchange is traced under
    ``dopt.compress`` (``observability/device_scopes.py``): the compressor,
    the estimate's update and the γ step, the sharded wire form with them;
    the ``mix`` inside it stays the backend's ``dopt.gossip``.

    The stacks go to the compressor in the shape the scan carries them,
    [N, d] or model-shaped [N, d, K]: a selection by threshold compares and
    counts over every axis behind the first, so nothing is flattened here
    (on one v5e the flatten and its return were 12.9 ms of every CHOCO
    iteration on [96, 4097, 512]: PERF.md section 6, PR 27); a compressor
    that needs ONE parameter axis (qsgd's row norm) flattens at its own
    boundary. Build the exchange for the flat row length (``row_dim``).
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
        with device_scopes.scope("compress"):
            q = self.compressor.apply(key, v - memory)
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
        with device_scopes.scope("compress"):
            q = self.compressor.apply(key, v - memory)
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
