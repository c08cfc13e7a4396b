"""Per-worker PRNG sampling tests: without-replacement, masking, determinism."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_optimization_tpu.ops.sampling import (
    sample_batch_indices,
    sample_worker_batches,
)


def test_without_replacement_and_weights():
    key = jax.random.key(0)
    idx, wts = sample_batch_indices(key, n_local=50, n_valid=jnp.asarray(50), batch_size=16)
    idx = np.asarray(idx)
    assert idx.shape == (16,)
    assert len(np.unique(idx)) == 16  # without replacement
    assert np.all((idx >= 0) & (idx < 50))
    np.testing.assert_allclose(np.asarray(wts), 1.0 / 16)


def test_short_shard_effective_batch():
    """n_valid < batch_size: weights encode effective batch = n_valid."""
    key = jax.random.key(1)
    idx, wts = sample_batch_indices(key, n_local=50, n_valid=jnp.asarray(5), batch_size=16)
    idx, wts = np.asarray(idx), np.asarray(wts)
    # Real draws come first and all lie in the valid range.
    assert np.all(idx[:5] < 5)
    assert len(np.unique(idx[:5])) == 5
    np.testing.assert_allclose(wts[:5], 1.0 / 5)
    np.testing.assert_allclose(wts[5:], 0.0)
    np.testing.assert_allclose(wts.sum(), 1.0, rtol=1e-6)


def test_batch_size_exceeds_shard_capacity():
    """batch_size > n_local (tiny shards): clamp, don't crash (regression)."""
    key = jax.random.key(7)
    idx, wts = sample_batch_indices(key, n_local=1, n_valid=jnp.asarray(1), batch_size=4)
    idx, wts = np.asarray(idx), np.asarray(wts)
    assert idx.shape == (4,) and np.all(idx == 0)
    np.testing.assert_allclose(wts, [1.0, 0.0, 0.0, 0.0])


def test_empty_shard_zero_weights():
    key = jax.random.key(2)
    _, wts = sample_batch_indices(key, n_local=10, n_valid=jnp.asarray(0), batch_size=4)
    np.testing.assert_allclose(np.asarray(wts), 0.0)


def test_worker_batches_shapes_and_independence():
    key = jax.random.key(3)
    N, L, d, b = 6, 20, 4, 8
    X = jnp.arange(N * L * d, dtype=jnp.float32).reshape(N, L, d)
    y = jnp.arange(N * L, dtype=jnp.float32).reshape(N, L)
    n_valid = jnp.full((N,), L)
    Xb, yb, w = sample_worker_batches(key, jnp.asarray(0), X, y, n_valid, b)
    assert Xb.shape == (N, b, d) and yb.shape == (N, b) and w.shape == (N, b)
    # Batch rows must come from the right worker's shard.
    for i in range(N):
        assert np.all(np.isin(np.asarray(yb[i]), np.asarray(y[i])))
    # Different workers / steps draw differently (overwhelmingly likely).
    Xb2, _, _ = sample_worker_batches(key, jnp.asarray(1), X, y, n_valid, b)
    assert not np.array_equal(np.asarray(Xb), np.asarray(Xb2))
    # Determinism: same key + step reproduces exactly.
    Xb3, _, _ = sample_worker_batches(key, jnp.asarray(0), X, y, n_valid, b)
    np.testing.assert_array_equal(np.asarray(Xb), np.asarray(Xb3))


def test_sampling_is_jittable():
    f = jax.jit(
        lambda key, step, X, y, nv: sample_worker_batches(key, step, X, y, nv, 4)
    )
    X = jnp.ones((3, 10, 2))
    y = jnp.ones((3, 10))
    out = f(jax.random.key(0), jnp.asarray(5), X, y, jnp.full((3,), 10))
    assert out[0].shape == (3, 4, 2)


def test_dense_weight_sampling_selects_same_subsets_as_gather():
    """sample_worker_batch_weights must pick the SAME rows as the gather path
    (same key => same uniforms => same top-b subset), expressed as weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_optimization_tpu.ops.sampling import (
        sample_batch_indices,
        sample_worker_batch_weights,
    )

    key = jax.random.key(7)
    n_local, batch = 13, 5
    n_valid = jnp.array([13, 9, 3, 0, 1])
    step = 4
    w_dense = sample_worker_batch_weights(key, step, n_valid, n_local, batch)
    # Rebuild the gather path's per-worker keys the same way.
    step_key = jax.random.fold_in(key, step)
    for i in range(len(n_valid)):
        wk = jax.random.fold_in(step_key, i)
        idx, w = sample_batch_indices(wk, n_local, n_valid[i], batch)
        dense_rows = np.nonzero(np.asarray(w_dense[i]) > 0)[0]
        gather_rows = np.unique(np.asarray(idx)[np.asarray(w) > 0])
        np.testing.assert_array_equal(np.sort(dense_rows), gather_rows)
        eff = min(batch, int(n_valid[i]))
        if eff:
            np.testing.assert_allclose(
                np.asarray(w_dense[i])[dense_rows], 1.0 / eff, rtol=1e-6
            )
        else:
            assert dense_rows.size == 0


def test_dense_sampling_backend_trajectory_matches_gather():
    """Full backend runs with sampling_impl gather vs dense produce identical
    trajectories (same sampled subsets, same math, fp-tolerance)."""
    import numpy as np

    from conftest import small_backend_config
    from distributed_optimization_tpu.backends import run_algorithm
    from distributed_optimization_tpu.utils import (
        compute_reference_optimum,
        generate_synthetic_dataset,
    )

    cfg = small_backend_config(n_iterations=40)
    ds = generate_synthetic_dataset(cfg)
    _, f_opt = compute_reference_optimum(ds, cfg.reg_param)
    rg = run_algorithm(cfg.replace(sampling_impl="gather"), ds, f_opt)
    rd = run_algorithm(cfg.replace(sampling_impl="dense"), ds, f_opt)
    np.testing.assert_allclose(rd.final_models, rg.final_models, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(
        rd.history.objective, rg.history.objective, rtol=1e-3, atol=1e-5
    )


def test_sampling_auto_resolution_follows_measured_rule():
    from distributed_optimization_tpu.config import ExperimentConfig

    cfg = ExperimentConfig()
    assert cfg.resolved_sampling_impl("tpu", 49) == "dense"
    assert cfg.resolved_sampling_impl("tpu", 500) == "gather"
    assert cfg.resolved_sampling_impl("cpu", 49) == "gather"
    assert cfg.replace(sampling_impl="dense").resolved_sampling_impl(
        "cpu", 500
    ) == "dense"


def test_dense_sampling_composes_with_worker_mesh():
    """Dense sampling on the 8-virtual-device mesh partitions cleanly (the
    [N, L] weights and full-shard weighted gradients are worker-sharded) and
    matches the single-device dense trajectory."""
    import numpy as np

    from conftest import small_backend_config
    from distributed_optimization_tpu.backends import jax_backend
    from distributed_optimization_tpu.parallel.mesh import make_worker_mesh
    from distributed_optimization_tpu.utils import (
        compute_reference_optimum,
        generate_synthetic_dataset,
    )

    cfg = small_backend_config(n_iterations=40, sampling_impl="dense")
    ds = generate_synthetic_dataset(cfg)
    _, f_opt = compute_reference_optimum(ds, cfg.reg_param)
    mesh = make_worker_mesh(cfg.n_workers)
    r_mesh = jax_backend.run(cfg, ds, f_opt, mesh=mesh)
    r_single = jax_backend.run(cfg, ds, f_opt, use_mesh=False)
    np.testing.assert_allclose(
        r_mesh.final_models, r_single.final_models, rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        r_mesh.history.objective, r_single.history.objective, rtol=1e-4, atol=1e-6
    )


# (L, b, the workers' n_valid): whole shards, short ones (n_valid < b), an
# empty one, and a batch wider than the shard (b > L).
DRAW_GRID = [
    (40, 16, [40, 40, 40]),
    (40, 16, [40, 17, 16, 15, 3, 1, 0]),
    (13, 5, [13, 9, 3, 0, 1]),
    (6, 9, [6, 4, 0]),
    (1, 4, [1, 0]),
    (130, 8, [130, 129, 7, 0]),
    (300, 64, [300, 64, 63]),
]


def _id_stack(n_workers, n_local, d=5, seed=0):
    """Shards whose rows name themselves: feature 0 is the row's index, the
    target the index plus a half."""
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n_workers, n_local, d)).astype(np.float32)
    X[:, :, 0] = np.arange(n_local)
    y = (np.arange(n_local, dtype=np.float32) + 0.5)[None, :].repeat(n_workers, 0)
    return jnp.asarray(X), jnp.asarray(y)


@pytest.mark.parametrize("n_local,batch,n_valid", DRAW_GRID)
@pytest.mark.parametrize("seed,step", [(0, 0), (11, 7), (2147483646, 999)])
def test_gathered_batch_is_the_dense_weights_support(n_local, batch, n_valid, seed, step):
    """The rows ``sample_worker_batches`` fetches with weight are the rows
    ``sample_worker_batch_weights`` weighs, at the same weight; a place's
    target is its own row's (one gather fetched both)."""
    from distributed_optimization_tpu.ops.sampling import sample_worker_batch_weights

    n_valid = jnp.asarray(n_valid, jnp.int32)
    X, y = _id_stack(len(n_valid), n_local)
    key, t = jax.random.key(seed), jnp.asarray(step, jnp.int32)
    Xb, yb, w = map(np.asarray, sample_worker_batches(key, t, X, y, n_valid, batch))
    dense = np.asarray(sample_worker_batch_weights(key, t, n_valid, n_local, batch))
    assert Xb.shape == (len(n_valid), batch, 5) and w.shape == yb.shape == Xb.shape[:2]
    np.testing.assert_array_equal(yb, Xb[:, :, 0] + 0.5)
    for i, ni in enumerate(np.asarray(n_valid)):
        rows = Xb[i, w[i] > 0, 0].astype(np.int64)
        assert len(np.unique(rows)) == len(rows) == min(batch, ni, n_local)
        np.testing.assert_array_equal(np.sort(rows), np.flatnonzero(dense[i]))
        np.testing.assert_array_equal(w[i, w[i] > 0], dense[i, np.sort(rows)])
        # every place names a row of the shard, the weightless ones too
        np.testing.assert_array_equal(
            Xb[i], np.asarray(X)[i, Xb[i, :, 0].astype(np.int64)])


@pytest.mark.parametrize("problem_type", ["quadratic", "logistic"])
@pytest.mark.parametrize("n_local,batch,n_valid", DRAW_GRID)
def test_gathered_gradient_is_the_dense_weights_gradient(problem_type, n_local, batch, n_valid):
    from distributed_optimization_tpu.models import get_problem
    from distributed_optimization_tpu.ops.sampling import sample_worker_batch_weights

    problem = get_problem(problem_type)
    n_valid = jnp.asarray(n_valid, jnp.int32)
    X, y = _id_stack(len(n_valid), n_local, seed=3)
    X = X.at[:, :, 0].multiply(1.0 / n_local)
    y = jnp.sign(jnp.sin(y)) if problem_type == "logistic" else y / n_local
    key, t = jax.random.key(4), jnp.asarray(12, jnp.int32)
    params = jax.random.normal(jax.random.key(9), (len(n_valid), X.shape[-1]))
    grad = jax.vmap(problem.gradient_weighted, in_axes=(0, 0, 0, 0, None))
    gathered = grad(params, *sample_worker_batches(key, t, X, y, n_valid, batch), 1e-4)
    dense = grad(
        params, X, y, sample_worker_batch_weights(key, t, n_valid, n_local, batch), 1e-4)
    np.testing.assert_allclose(np.asarray(gathered), np.asarray(dense), rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("batch", [1, 3, 4, 6, 9])
def test_tied_scores_go_to_the_lower_index(batch):
    """Equal uniforms fed to the selection itself: of the rows at the
    threshold the first by index are drawn, as the dense ranking has it."""
    from distributed_optimization_tpu.ops.sampling import draw_batch_indices

    scores = jnp.asarray([
        [0.5, 0.25, 0.5, 0.5, 0.75, 0.5, 0.25, 0.5],
        [0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0],
        [0.5, 0.5, 0.5, 0.5, 0.5, -np.inf, -np.inf, -np.inf],
        [0.125, 0.0, 0.125, 0.0, 0.125, 0.0, -np.inf, -np.inf],
    ], jnp.float32)
    n_valid = jnp.asarray([8, 8, 5, 6], jnp.int32)
    idx, w = map(np.asarray, draw_batch_indices(scores, n_valid, batch))
    for i, ni in enumerate(np.asarray(n_valid)):
        eff = min(batch, ni)
        # a stable sort by falling score keeps equal scores in index order
        want = np.argsort(-np.asarray(scores[i]), kind="stable")[:eff]
        np.testing.assert_array_equal(np.sort(idx[i][w[i] > 0]), np.sort(want))
        np.testing.assert_allclose(w[i][w[i] > 0], 1.0 / eff)
        assert np.sum(w[i] > 0) == eff and np.all((idx[i] >= 0) & (idx[i] < 8))


def test_labels_that_cannot_ride_are_fetched_on_their_own():
    """Class labels stay int32 whatever the features' dtype: the table is
    the two arrays, a draw two gathers over the same indices."""
    from distributed_optimization_tpu.ops.sampling import batch_table, targets_ride

    X, y = _id_stack(3, 20)
    labels = jnp.asarray(np.arange(20, dtype=np.int32)[None, :].repeat(3, 0) + 300)
    assert targets_ride(X.dtype, y.dtype) and len(batch_table(X, y)) == 1
    for feats in (X, X.astype(jnp.bfloat16)):
        assert not targets_ride(feats.dtype, labels.dtype)
        assert len(batch_table(feats, labels)) == 2
        Xb, yb, _ = sample_worker_batches(
            jax.random.key(1), jnp.asarray(0), feats, labels, jnp.full((3,), 20), 6)
        assert yb.dtype == jnp.int32
        np.testing.assert_array_equal(
            np.asarray(yb) - 300, np.asarray(Xb[:, :, 0].astype(jnp.float32)))


@pytest.mark.parametrize("n_workers,n_local", [(3, 1), (5, 7), (2, 8), (4, 21), (3, 64)])
def test_the_table_filled_in_blocks_is_the_concatenation(n_workers, n_local, monkeypatch):
    from distributed_optimization_tpu.ops import sampling

    X, y = _id_stack(n_workers, n_local)
    want = np.concatenate([np.asarray(X), np.asarray(y)[..., None]], axis=-1)
    np.testing.assert_array_equal(np.asarray(sampling.batch_table(X, y)[0]), want)
    # many blocks, the last one drawn back to end at L
    monkeypatch.setattr(sampling, "_TABLE_BLOCK_NUMBERS", 8 * n_workers * 6)
    np.testing.assert_array_equal(np.asarray(sampling.batch_table(X, y)[0]), want)
