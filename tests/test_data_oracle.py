"""Data generation, non-IID partition, and sklearn-oracle tests."""

import numpy as np
import pytest

from distributed_optimization_tpu.config import ExperimentConfig
from distributed_optimization_tpu.ops import losses_np
from distributed_optimization_tpu.utils import (
    HostDataset,
    compute_reference_optimum,
    generate_synthetic_dataset,
    stack_shards,
)


def small_config(problem="quadratic", **kw):
    defaults = dict(
        n_workers=5,
        n_samples=250,
        n_features=12,
        n_informative_features=8,
        problem_type=problem,
        n_iterations=100,
    )
    defaults.update(kw)
    return ExperimentConfig(**defaults)


@pytest.mark.parametrize("problem", ["logistic", "quadratic"])
def test_dataset_shapes_and_bias_column(problem):
    cfg = small_config(problem)
    ds = generate_synthetic_dataset(cfg)
    assert ds.X_full.shape == (250, 13)  # d + bias
    np.testing.assert_allclose(ds.X_full[:, -1], 1.0)
    if problem == "logistic":
        assert set(np.unique(ds.y_full)) == {-1.0, 1.0}
    # Features standardized (before bias column).
    np.testing.assert_allclose(ds.X_full[:, :-1].mean(axis=0), 0.0, atol=1e-9)
    np.testing.assert_allclose(ds.X_full[:, :-1].std(axis=0), 1.0, atol=1e-9)


def test_partition_is_disjoint_covering_and_non_iid():
    cfg = small_config("quadratic")
    ds = generate_synthetic_dataset(cfg)
    all_idx = np.concatenate(ds.shard_indices)
    assert sorted(all_idx.tolist()) == list(range(250))
    # Sorted-by-target partition ⇒ per-worker mean targets strictly increase.
    means = [ds.y_full[idx].mean() for idx in ds.shard_indices]
    assert all(a < b for a, b in zip(means, means[1:]))
    # Worker shard target ranges don't overlap (contiguous slices of sorted y).
    maxes = [ds.y_full[idx].max() for idx in ds.shard_indices]
    mins = [ds.y_full[idx].min() for idx in ds.shard_indices]
    assert all(maxes[i] <= mins[i + 1] for i in range(len(mins) - 1))


def test_stack_shards_roundtrip():
    cfg = small_config("quadratic", n_workers=3, n_samples=100)
    ds = generate_synthetic_dataset(cfg)
    dev = stack_shards(ds)
    assert dev.X.shape[0] == 3
    assert int(dev.n_valid.sum()) == 100
    for i in range(3):
        Xi, yi = ds.shard(i)
        ni = int(dev.n_valid[i])
        np.testing.assert_allclose(dev.X[i, :ni], Xi.astype(np.float32), rtol=1e-6)
        np.testing.assert_allclose(dev.y[i, :ni], yi.astype(np.float32), rtol=1e-6)
        np.testing.assert_allclose(dev.X[i, ni:], 0.0)


def test_uneven_split_padding():
    cfg = small_config("quadratic", n_workers=7, n_samples=100)
    ds = generate_synthetic_dataset(cfg)
    dev = stack_shards(ds)
    # 100 = 7*14 + 2 → first two shards hold 15 (array_split semantics).
    assert sorted(dev.n_valid.tolist(), reverse=True) == [15, 15] + [14] * 5
    assert dev.X.shape[1] == 15


def _stack_shards_by_loop(dataset, dtype=np.float32):
    """``stack_shards`` as it was until ISSUE 29, a Python loop over the
    workers: the plain oracle the loop-free form is held to, bit for bit."""
    n = dataset.n_workers
    d = dataset.n_features
    sizes = np.array([len(idx) for idx in dataset.shard_indices], dtype=np.int32)
    L = int(sizes.max()) if n else 0
    y_dtype = np.int32 if dataset.problem_type == "softmax" else dtype
    X = np.zeros((n, L, d), dtype=dtype)
    y = np.zeros((n, L), dtype=y_dtype)
    for i in range(n):
        Xi, yi = dataset.shard(i)
        X[i, : sizes[i]] = Xi
        y[i, : sizes[i]] = yi
    return X, y, sizes


def _consecutive(
    n_workers, rows, dtype=np.float64, problem="logistic", *,
    shard_indices=None, fortran=False,
):
    """Equal shards laid worker after worker, as the benchmark's are (or
    ``shard_indices`` over the same rows)."""
    rng = np.random.default_rng(7)
    n = n_workers * rows
    X = rng.standard_normal((n, 6)).astype(dtype)
    y = (
        rng.integers(0, 512, size=n) if problem == "softmax"
        else rng.standard_normal(n)
    )
    if shard_indices is None:
        shard_indices = list(np.arange(n).reshape(n_workers, rows))
    return HostDataset(
        X_full=np.asfortranarray(X) if fortran else X, y_full=y.astype(dtype),
        shard_indices=shard_indices, problem_type=problem,
    )


def _generated(problem, **kw):
    return generate_synthetic_dataset(small_config(problem, **kw))


STACK_CASES = {
    # name: (dataset factory, run dtype, expected ``stacked_by``)
    "consecutive-run-dtype": (
        lambda: _consecutive(8, 5, np.float32), "float32", "view"),
    "consecutive-f64-to-f32": (lambda: _consecutive(8, 5), "float32", "cast"),
    "consecutive-prefix": (
        lambda: _consecutive(
            8, 5, np.float32,
            shard_indices=list(np.arange(30).reshape(6, 5))),
        "float32", "view"),
    "consecutive-fortran-order": (
        lambda: _consecutive(8, 5, np.float32, fortran=True),
        "float32", "cast"),
    "argsort-equal-sizes": (
        lambda: _generated("quadratic", n_workers=5, n_samples=250),
        "float32", "gather"),
    "argsort-equal-sizes-f64": (
        lambda: _generated("logistic", n_workers=5, n_samples=250),
        "float64", "gather"),
    "ragged": (
        lambda: _generated("quadratic", n_workers=7, n_samples=100),
        "float32", "gather"),
    "ragged-consecutive": (
        lambda: _consecutive(
            8, 5, np.float32, shard_indices=np.array_split(np.arange(38), 8)),
        "float32", "gather"),
    "more-workers-than-samples": (
        lambda: _generated(
            "quadratic", n_workers=12, n_samples=9, local_batch_size=1),
        "float32", "gather"),
    "no-rows-at-all": (
        lambda: _consecutive(
            3, 5, np.float32, shard_indices=[np.arange(0)] * 3),
        "float32", "gather"),
    "shuffled": (
        lambda: _generated(
            "logistic", n_workers=4, n_samples=100, partition="shuffled"),
        "float32", "gather"),
    "negative-indices": (
        lambda: _consecutive(
            4, 5, np.float32,
            shard_indices=list(np.arange(-20, 0).reshape(4, 5))),
        "float32", "gather"),
    "softmax-bfloat16": (
        lambda: _consecutive(4, 128, problem="softmax"), "bfloat16", "cast"),
    "softmax-bfloat16-argsort": (
        lambda: _generated(
            "softmax", n_workers=5, n_samples=203, n_classes=3),
        "bfloat16", "gather"),
}


@pytest.mark.parametrize("case", list(STACK_CASES))
def test_stack_shards_is_bitwise_the_loop(case):
    """ISSUE 29: no loop over workers, and the same bytes — X, y, n_valid
    and their dtypes — as the loop gave, whatever the partition; where the
    shards are consecutive rows in the run dtype X is the dataset's own
    memory, read-only."""
    make, dtype, stacked_by = STACK_CASES[case]
    ds = make()
    dtype = np.dtype(dtype)
    X, y, sizes = _stack_shards_by_loop(ds, dtype=dtype)
    dev = stack_shards(ds, dtype=dtype)
    assert dev.stacked_by == stacked_by
    for got, want in ((dev.X, X), (dev.y, y), (dev.n_valid, sizes)):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes()
    assert np.shares_memory(dev.X, ds.X_full) == (stacked_by == "view")
    if stacked_by == "view":
        assert not dev.X.flags.writeable and ds.X_full.flags.writeable
        with pytest.raises(ValueError, match="read-only"):
            dev.X[0, 0, 0] = 1.0


def test_stack_shards_refuses_rows_outside_the_dataset():
    ds = _consecutive(
        4, 5, shard_indices=list(np.arange(1, 21).reshape(4, 5)))
    with pytest.raises(IndexError, match="outside"):
        stack_shards(ds)


@pytest.mark.parametrize("problem", ["logistic", "quadratic"])
def test_reference_optimum_is_a_minimum(problem):
    cfg = small_config(problem)
    ds = generate_synthetic_dataset(cfg)
    reg = cfg.reg_param
    w_opt, f_opt = compute_reference_optimum(ds, reg)
    assert w_opt.shape == (13,)
    obj = losses_np.OBJECTIVES[problem]
    # f_opt beats w = 0 and random perturbations of w_opt.
    assert f_opt < obj(np.zeros(13), ds.X_full, ds.y_full, reg)
    rng = np.random.default_rng(0)
    for _ in range(5):
        w_pert = w_opt + 0.1 * rng.normal(size=13)
        assert f_opt <= obj(w_pert, ds.X_full, ds.y_full, reg) + 1e-10
    # Near-stationarity of the full gradient at the optimum. sklearn does not
    # penalize the intercept while the study's objective regularizes all of w
    # (reference obj_problems.py:10 vs simulator.py:49), so the bias coordinate
    # keeps an O(λ·intercept) residual — same slack exists in the reference.
    g = losses_np.GRADIENTS[problem](w_opt, ds.X_full, ds.y_full, reg)
    assert np.linalg.norm(g) < 5e-3


@pytest.mark.parametrize("generator", ["synthetic", "digits"])
def test_shuffled_partition_breaks_target_sorting(generator):
    """partition='shuffled' (the IID control) must be honored by BOTH data
    paths: same samples, same totals, but shards no longer slice a sorted
    target range."""
    if generator == "digits":
        from distributed_optimization_tpu.utils.data import (
            generate_digits_dataset as gen,
        )
    else:
        gen = generate_synthetic_dataset
    kw = dict(problem="logistic", n_workers=5, n_samples=250)
    srt = gen(small_config(**kw))
    shf = gen(small_config(partition="shuffled", **kw))
    np.testing.assert_array_equal(srt.X_full, shf.X_full)
    # Sorted shards have monotone per-shard target means; shuffled don't.
    def means(ds):
        return [ds.shard(i)[1].mean() for i in range(5)]
    assert means(srt) == sorted(means(srt))
    assert means(shf) != sorted(means(shf))
    # Every sample still lands in exactly one shard.
    all_idx = np.concatenate(shf.shard_indices)
    assert np.array_equal(np.sort(all_idx), np.arange(250))


def test_config_validation():
    with pytest.raises(ValueError):
        ExperimentConfig(problem_type="nope")
    with pytest.raises(ValueError):
        ExperimentConfig(topology="grid", n_workers=24)
    cfg = ExperimentConfig()
    assert ExperimentConfig.from_dict(cfg.to_dict()) == cfg


def test_partition_summary_reports_every_worker():
    """Generation-time distribution report (parity: reference utils.py:43-48):
    one line per worker with size/range/mean, plus the totals line."""
    from distributed_optimization_tpu.utils.data import partition_summary

    cfg = small_config("quadratic")
    ds = generate_synthetic_dataset(cfg)
    text = partition_summary(ds)
    lines = text.splitlines()
    assert len(lines) == cfg.n_workers + 1
    for i in range(cfg.n_workers):
        _, yi = ds.shard(i)
        assert lines[i].startswith(f"Worker {i}: {len(yi)} samples")
    assert lines[-1] == (
        f"Generated {cfg.n_samples} samples, {ds.n_features} features"
    )
    # The sorted partition is what the report makes visible: worker means
    # must be non-decreasing.
    means = [float(ds.shard(i)[1].mean()) for i in range(cfg.n_workers)]
    assert means == sorted(means)


def test_partition_summary_truncates_at_scale():
    """Above max_workers the per-worker lines collapse to head + elision +
    tail (sweep-scale runs would otherwise print thousands of stderr lines);
    at or below the threshold every worker still gets its line."""
    from distributed_optimization_tpu.utils.data import partition_summary

    cfg = small_config("quadratic").replace(n_workers=100, n_samples=400)
    ds = generate_synthetic_dataset(cfg)
    text = partition_summary(ds)
    lines = text.splitlines()
    assert len(lines) < 40
    assert lines[0].startswith("Worker 0:")
    assert any("workers elided" in ln for ln in lines)
    assert lines[-2].startswith("Worker 99:")
    assert lines[-1].startswith("Generated 400 samples")
    # Full report restored by raising the cap.
    assert len(partition_summary(ds, max_workers=100).splitlines()) == 101
