"""Per-worker mini-batch sampling with explicit JAX PRNG keys.

The reference samples each worker's batch from one *global* numpy RNG stream
(``np.random.choice`` at reference ``worker.py:27``, seeded once at
``main.py:24``), which makes batch draws order-dependent across workers. The
TPU-native design replaces that with counter-based PRNG: every (worker,
iteration) pair gets its own key via ``fold_in``, so sampling is
order-independent, reproducible, and embarrassingly parallel across the mesh.
Exact batch-sequence parity with the reference is impossible by construction
(documented in SURVEY.md §3.4); equivalence tests inject identical batches
instead.

Semantics preserved from the reference (``worker.py:15-28``):
- sampling is without replacement;
- the effective batch size is ``min(batch_size, n_valid)`` — encoded as a
  weight vector rather than a dynamic shape;
- a worker with zero valid samples yields an all-zero weight vector (its
  gradient contribution is then exactly the regularizer term, mirroring the
  empty-batch guard at ``obj_problems.py:14-15``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distributed_optimization_tpu.ops.compression import _count_threshold


def _worker_keys(key: jax.Array, step: jax.Array, n_workers: int) -> jax.Array:
    """Per-(iteration, worker) keys: fold_in(fold_in(key, step), worker_id).

    SHARED by the gather and dense sampling paths — both must derive the
    identical key stream or their sampled subsets diverge (the dense==gather
    equivalence is structural, not just tested).
    """
    step_key = jax.random.fold_in(key, step)
    return jax.vmap(lambda i: jax.random.fold_in(step_key, i))(
        jnp.arange(n_workers)
    )


def _masked_scores(worker_key: jax.Array, n_local: int, n_valid: jax.Array) -> jax.Array:
    """One worker's uniform ranking scores with padding rows pushed to -inf.
    Shared by both sampling paths (same draw => same subset)."""
    scores = jax.random.uniform(worker_key, (n_local,))
    valid = jnp.arange(n_local) < n_valid
    return jnp.where(valid, scores, -jnp.inf)


def _effective_batch(batch_size: int, n_valid: jax.Array, n_local: int) -> jax.Array:
    """min(batch_size, n_valid, n_local) — the reference's batch clamp
    (worker.py:21), shared by both sampling paths."""
    return jnp.minimum(jnp.minimum(batch_size, n_valid), n_local)


def _selection_keys(scores: jax.Array) -> jax.Array:
    """Masked scores as unsigned keys that order the same way: a valid row's
    uniform is non-negative, so its bits read as an unsigned integer order as
    it does, and the one added puts every valid row above the padding's 0
    (a uniform's bits stay far under the key's top)."""
    bits = jax.lax.bitcast_convert_type(
        scores, jnp.dtype(f"uint{scores.dtype.itemsize * 8}")
    )
    return jnp.where(scores >= 0, bits + 1, 0)


# Entries a block of ``_nth_marked``'s prefix count holds: the MXU's width.
_PREFIX_BLOCK = 128


def _nth_marked(marked: jax.Array, nth: jax.Array) -> jax.Array:
    """Per row of a boolean stack ``[N, M]``, the index of its ``nth``-th
    marked entry (``nth`` ``[N, P]`` int32, from 0), ``[N, P]`` int32; M or
    more where the row has no such entry. No sort and no scatter: that index
    is the number of entries whose inclusive prefix count is at most nth, a
    compare-and-reduce over ``[P, M, N]``. The prefix count is made in two
    levels and never laid out whole: inside blocks of ``_PREFIX_BLOCK``
    entries by one product with a triangle of ones (int8 into int32), and
    the marked entries before a block are taken off the place it is compared
    with. (A ``cumsum`` over ``[16384, 800]`` compiles for the chip to six
    materialized passes and two relayouts.)"""
    n_rows, length = marked.shape
    blocks = jnp.pad(
        marked.astype(jnp.int8), ((0, 0), (0, -length % _PREFIX_BLOCK))
    ).reshape(n_rows, -1, _PREFIX_BLOCK)
    # The counts with the ROW axis last, [blocks, block, N], and the sums
    # over the two leading axes: the rows lie along the chip's lanes, and a
    # place's count is added up lane by lane, with no reduction across them.
    within = jnp.einsum(
        "nkb,bc->kcn", blocks,
        jnp.triu(jnp.ones((_PREFIX_BLOCK,) * 2, jnp.int8)),
        preferred_element_type=jnp.int32,
    )
    totals = within[:, -1]
    before = jnp.cumsum(totals, axis=0) - totals
    # The padding behind M counts what its block holds: only an nth the row
    # does not have reaches it.
    left = nth.T[:, None] - before
    return jnp.sum(within <= left[:, :, None], axis=(1, 2), dtype=jnp.int32).T


def draw_batch_indices(
    scores: jax.Array,  # [N, L] masked scores (``_masked_scores``)
    n_valid: jax.Array,  # [N]
    batch_size: int,
) -> tuple[jax.Array, jax.Array]:
    """The rows ``sample_worker_batch_weights`` gives weight to, as indices:
    ``(indices [N, batch_size] int32, weights [N, batch_size] f32)``. Per
    worker the ``b_eff = min(batch_size, n_valid, L)`` largest scores among
    the valid rows, of equal scores the lower index.

    Selected, not sorted, by ``ops.compression.select_top_scored``'s rule
    with a k of each row's own: the b_eff-th largest key of a worker is a
    counted threshold over the keys' bits (``_count_threshold``, the
    compressor's; ``select_top_scored`` has the readings); every row above
    it is drawn, and of the rows AT it the first by index until b_eff are.
    The mask's marked entries, counted off in rising row order, are the
    batch (``_nth_marked``): the real draws come first (the order inside a
    batch is no part of the contract: every real draw weighs the same); the
    surplus places of a short shard point at its last row and weigh 0."""
    n_rows, n_local = scores.shape
    keys = _selection_keys(scores)
    effective = _effective_batch(batch_size, n_valid, n_local)
    kth, n_above = _count_threshold(keys, effective)
    tied = keys == kth
    last_tie = _nth_marked(tied, (effective - n_above - 1)[:, None])
    drawn = (keys > kth) | (tied & (jnp.arange(n_local) <= last_tie))
    place = jnp.arange(batch_size, dtype=jnp.int32)
    indices = _nth_marked(drawn, jnp.broadcast_to(place, (n_rows, batch_size)))
    weights = jnp.where(
        place < effective[:, None],
        1.0 / jnp.maximum(effective, 1)[:, None], 0.0,
    )
    return jnp.minimum(indices, n_local - 1), weights.astype(jnp.float32)


def sample_batch_indices(
    key: jax.Array, n_local: int, n_valid: jax.Array, batch_size: int
) -> tuple[jax.Array, jax.Array]:
    """Draw ``batch_size`` row indices without replacement from the valid rows.

    Returns ``(indices [batch_size] int32, weights [batch_size] f32)`` where
    weights are ``1/min(batch_size, n_valid)`` on rows that represent real
    draws and 0 on padding rows. Uses the Gumbel-top-k trick (uniform scores,
    the top k of them: ``draw_batch_indices``) so shapes stay static under
    jit. One worker's form of ``sample_worker_batches``' draw.
    """
    scores = _masked_scores(key, n_local, n_valid)
    indices, weights = draw_batch_indices(
        scores[None], jnp.asarray(n_valid)[None], batch_size
    )
    return indices[0], weights[0]


def sample_worker_batch_weights(
    key: jax.Array,
    step: jax.Array,
    n_valid: jax.Array,  # [N] true shard sizes
    n_local: int,  # L, the padded shard length
    batch_size: int,
) -> jax.Array:
    """Dense-weights formulation of per-worker batch sampling: ``[N, L]``
    weights carrying ``1/b_eff`` on sampled rows and 0 elsewhere.

    Selects the SAME row subsets as :func:`sample_worker_batches` for the
    same key (same per-worker uniform draw; membership in the top
    ``b_eff`` scores computed by rank instead of by a threshold, with ties
    broken toward the lower index on both paths — a float32 uniform has 23
    random bits, so of 16,384 workers' 800 rows some pair ties every
    iteration). The gradient over the
    full shard with these weights equals the gathered mini-batch gradient.

    Why it exists: the gather path runs a selection and a row gather
    every iteration — serial latency-bound ops on TPU. This form trades
    them for one [L, L] comparison matrix and a full-shard weighted
    gradient: ~L/b more FLOPs, but fewer/larger ops, which wins when the
    step is latency-bound (measured: docs/perf/breakdown.json — the
    full-shard objective pass costs ~4µs while the sampling+gather
    machinery dominates the 84µs iteration).
    """
    worker_keys = _worker_keys(key, step, n_valid.shape[0])
    idx = jnp.arange(n_local)

    def one(worker_key, ni):
        u = _masked_scores(worker_key, n_local, ni)
        # rank[l] = #{m : u_m > u_l, or u_m == u_l with m < l} — the position
        # l would take in a stable descending sort (the gather path's order).
        beats = (u[None, :] > u[:, None]) | (
            (u[None, :] == u[:, None]) & (idx[None, :] < idx[:, None])
        )
        rank = jnp.sum(beats, axis=1)
        effective = _effective_batch(batch_size, ni, n_local)
        sel = (rank < effective) & (idx < ni)
        return jnp.where(sel, 1.0 / jnp.maximum(effective, 1), 0.0)

    return jax.vmap(one)(worker_keys, n_valid).astype(jnp.float32)


def targets_ride(x_dtype, y_dtype) -> bool:
    """Whether a row's target can ride in the row: where it is of the
    features' dtype (every GLM's). Softmax's class labels stay int32
    whatever the run dtype (``utils.data.stack_shards``) and are fetched by
    a gather of their own."""
    return jnp.dtype(x_dtype) == jnp.dtype(y_dtype)


# Numbers a block of ``batch_table`` holds: the table is filled a block of
# rows at a time, so what the fill holds beside the table is a block (64 MiB
# in float32), not a second table.
_TABLE_BLOCK_NUMBERS = 1 << 24


def batch_table(X: jax.Array, y: jax.Array) -> tuple[jax.Array, ...]:
    """What the gather sampler fetches a draw from, one gather an array:
    ``(Xy [N, L, d + 1],)``, a row's target riding as its last number so ONE
    gather fetches both (a gather is priced by its indices, not its bytes),
    or ``(X, y)`` where the targets cannot ride (``targets_ride``). Make it
    once, before the loop.

    Filled in blocks of whole rows along L (a multiple of 8 of them, the last
    block drawn back to end at L): one ``concatenate`` of the whole stack
    holds three tables' worth on the chip, each row padded to 128 lanes."""
    if not targets_ride(X.dtype, y.dtype):
        return X, y
    n_workers, n_local, d = X.shape
    rows = _TABLE_BLOCK_NUMBERS // (n_workers * (d + 1))
    rows = min(n_local, max(8, rows - rows % 8))

    def fill(i, table):
        first = jnp.minimum(i * rows, n_local - rows)
        block = jnp.concatenate(
            [
                jax.lax.dynamic_slice_in_dim(X, first, rows, axis=1),
                jax.lax.dynamic_slice_in_dim(y, first, rows, axis=1)[..., None],
            ],
            axis=-1,
        )
        return jax.lax.dynamic_update_slice_in_dim(table, block, first, axis=1)

    return (
        jax.lax.fori_loop(
            0, -(-n_local // rows), fill,
            jnp.zeros((n_workers, n_local, d + 1), X.dtype),
        ),
    )


def sample_table_batches(
    key: jax.Array,
    step: jax.Array,
    table: tuple[jax.Array, ...],  # ``batch_table`` of the padded shards
    n_valid: jax.Array,  # [N] true shard sizes
    batch_size: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Sample one mini-batch per worker for iteration ``step``.

    Returns ``(Xb [N, b, d], yb [N, b], weights [N, b])``. Each worker's key is
    ``fold_in(fold_in(key, step), worker_id)`` — independent of every other
    worker and iteration. The b rows are drawn without a sort
    (``draw_batch_indices``) and fetched by one gather an array of the
    table: features and target together, ``[N, b, d + 1]``, where the target
    rides in the row.
    """
    n_workers, n_local = table[0].shape[:2]
    worker_keys = _worker_keys(key, step, n_workers)
    scores = jax.vmap(
        lambda worker_key, ni: _masked_scores(worker_key, n_local, ni)
    )(worker_keys, n_valid)
    indices, weights = draw_batch_indices(scores, n_valid, batch_size)
    fetched = [
        jnp.take_along_axis(
            a, indices.reshape(indices.shape + (1,) * (a.ndim - 2)), axis=1,
            mode="promise_in_bounds",
        )
        for a in table
    ]
    if len(fetched) == 1:
        (rows,) = fetched
        # The target out of its row by a select and a sum along the row
        # (exact: zeros are added to it). A slice ``rows[..., -1:]`` is an
        # array [N, b, 1], 128 times padded in the chip's tiles: cut out and
        # copied it was 0.59 ms of the tracker cell's 11.7 ms iteration,
        # this 0.18 (PERF.md section 6, PR 40).
        last = rows.shape[-1] - 1
        yb = jnp.sum(jnp.where(jnp.arange(last + 1) == last, rows, 0), axis=-1)
        return rows[..., :-1], yb, weights
    return (*fetched, weights)


def sample_worker_batches(
    key: jax.Array,
    step: jax.Array,
    X: jax.Array,  # [N, L, d] stacked per-worker shards (padded)
    y: jax.Array,  # [N, L]
    n_valid: jax.Array,  # [N] true shard sizes
    batch_size: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``sample_table_batches`` over a table made here: one draw's form. A
    loop makes ``batch_table(X, y)`` once, before it."""
    return sample_table_batches(
        key, step, batch_table(X, y), n_valid, batch_size
    )
