"""Synthetic data generation and non-IID partitioning.

Capability parity with the reference's data layer (reference ``utils.py:5-50``):
sklearn ``make_classification`` / ``make_regression`` with identical
hyperparameters, ``StandardScaler`` standardization, an appended all-ones bias
column (d → d+1), and the *sorted-by-target* partition across workers that
forces label/target heterogeneity (the non-IID knob, ``utils.py:34-38``).

Generation stays host-side numpy on purpose: it is the parity anchor that
makes convergence curves comparable across the numpy oracle backend, the JAX
backend, and the reference's published numbers. The device side gets the data
as *stacked, padded* arrays — ``X [N, L, d]``, ``y [N, L]``, per-worker valid
counts — because N ragged shards would defeat XLA's static-shape compilation;
padding rows carry zero weight everywhere downstream.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class HostDataset:
    """Full dataset + per-worker partition, host-side (numpy, float64)."""

    X_full: np.ndarray  # [n_samples, d] standardized, bias column appended
    y_full: np.ndarray  # [n_samples] (±1 for logistic)
    shard_indices: list[np.ndarray]  # per-worker row indices into X_full
    problem_type: str

    @property
    def n_features(self) -> int:
        return self.X_full.shape[1]

    @property
    def n_workers(self) -> int:
        return len(self.shard_indices)

    def shard(self, i: int) -> tuple[np.ndarray, np.ndarray]:
        idx = self.shard_indices[i]
        return self.X_full[idx], self.y_full[idx]


@dataclasses.dataclass(frozen=True)
class DeviceDataset:
    """Stacked, padded per-worker shards ready for device placement.

    ``X``: [N, L, d], ``y``: [N, L], ``n_valid``: [N] — L is the max shard
    size; rows at index >= n_valid[i] are zero padding.
    """

    X: np.ndarray
    y: np.ndarray
    n_valid: np.ndarray
    # How ``stack_shards`` formed ``X``: "view" (it IS the host dataset's
    # memory: read-only by contract), "cast" or "gather".
    stacked_by: str = "gather"

    @property
    def n_workers(self) -> int:
        return self.X.shape[0]

    @property
    def n_features(self) -> int:
        return self.X.shape[2]


def generate_synthetic_dataset(config) -> HostDataset:
    """Generate the study's synthetic dataset and its non-IID partition.

    Mirrors reference ``utils.py:5-50``: same sklearn generators, same
    hyperparameters (n_redundant = n_features - n_informative,
    n_clusters_per_class=1, flip_y=0.05, random_state=203 by default via
    ``config.resolved_data_seed()`` — ``seed`` unless ``data_seed`` pins the
    problem instance independently; noise=10.0 for regression), labels
    mapped to ±1,
    StandardScaler, bias column, argsort(y) + array_split partition.
    """
    from sklearn.datasets import make_classification, make_regression
    from sklearn.preprocessing import StandardScaler

    if config.problem_type == "logistic":
        X, y = make_classification(
            n_samples=config.n_samples,
            n_features=config.n_features,
            n_informative=config.n_informative_features,
            n_redundant=config.n_features - config.n_informative_features,
            n_clusters_per_class=1,
            flip_y=0.05,
            class_sep=config.classification_sep,
            random_state=config.resolved_data_seed(),
        )
        y = y.astype(np.float64) * 2.0 - 1.0
    elif config.problem_type == "softmax":
        # Same generator as logistic with K classes; labels stay 0..K−1
        # (float-stored class indices — the softmax kernels cast back).
        # The separability constraint is make_classification's, so it lives
        # here with the call, not in config: the digits path has real
        # classes and ignores n_informative_features entirely.
        if config.n_classes > 2**config.n_informative_features:
            raise ValueError(
                f"n_classes ({config.n_classes}) exceeds what "
                f"{config.n_informative_features} informative features can "
                "separate (sklearn make_classification requires n_classes "
                "<= 2^n_informative)"
            )
        X, y = make_classification(
            n_samples=config.n_samples,
            n_features=config.n_features,
            n_informative=config.n_informative_features,
            n_redundant=config.n_features - config.n_informative_features,
            n_classes=config.n_classes,
            n_clusters_per_class=1,
            flip_y=0.05,
            class_sep=config.classification_sep,
            random_state=config.resolved_data_seed(),
        )
        y = y.astype(np.float64)
    elif config.problem_type in ("quadratic", "huber"):
        # Huber shares the regression pipeline (same targets, same noise=10
        # scale its delta is calibrated to).
        X, y = make_regression(
            n_samples=config.n_samples,
            n_features=config.n_features,
            n_informative=config.n_informative_features,
            noise=10.0,
            random_state=config.resolved_data_seed(),
        )
        y = y.astype(np.float64)
    else:
        raise ValueError(f"Unknown problem type: {config.problem_type}")

    X = StandardScaler().fit_transform(X)
    X = np.hstack([X, np.ones((X.shape[0], 1))])  # bias column: d -> d+1

    # Default non-IID partition: sort by target, then split contiguously so
    # each worker sees a narrow slice of the target distribution. The
    # 'shuffled' alternative is the IID counterfactual (seed-deterministic
    # random permutation) — the bounded-heterogeneity regime the Byzantine
    # robust-aggregation analyses assume (docs/BYZANTINE.md), and a control
    # for separating non-IID effects in any experiment.
    if config.partition == "shuffled":
        order = np.random.default_rng(config.resolved_data_seed()).permutation(y.shape[0])
    else:
        order = np.argsort(y)
    shard_indices = [np.asarray(s) for s in np.array_split(order, config.n_workers)]

    return HostDataset(
        X_full=X, y_full=y, shard_indices=shard_indices, problem_type=config.problem_type
    )


def generate_digits_dataset(config) -> HostDataset:
    """Real image-feature dataset (the BASELINE.json "MNIST features" stretch
    config, offline-friendly): sklearn's bundled 8×8 digits (1,797 samples,
    64 pixel features) instead of synthetic data.

    Same preprocessing pipeline as the synthetic path: StandardScaler, bias
    column, sorted-by-target non-IID partition (or the 'shuffled' IID
    control, honoring ``config.partition``). For ``logistic`` the labels
    are binarized to ±1 (digit ≥ 5); for ``quadratic`` the digit value is the
    regression target. ``config.n_samples`` caps the sample count;
    ``n_features`` is ignored (the data has 64).
    """
    from sklearn.datasets import load_digits
    from sklearn.preprocessing import StandardScaler

    X, digit = load_digits(return_X_y=True)
    n = min(config.n_samples, X.shape[0])
    X, digit = X[:n], digit[:n]
    if config.problem_type == "logistic":
        y = np.where(digit >= 5, 1.0, -1.0)
    elif config.problem_type == "softmax":
        # The natural multiclass form of the digits task: the ten digit
        # classes ARE the labels. The config must budget all of them.
        if config.n_classes < 10:
            raise ValueError(
                "digits has 10 classes; softmax needs n_classes >= 10 "
                f"(got {config.n_classes})"
            )
        y = digit.astype(np.float64)
    else:
        y = digit.astype(np.float64)

    X = StandardScaler().fit_transform(X)
    # Constant pixels scale to 0/0; StandardScaler leaves them 0 — fine.
    X = np.hstack([X, np.ones((X.shape[0], 1))])

    if config.partition == "shuffled":
        order = np.random.default_rng(config.resolved_data_seed()).permutation(y.shape[0])
    else:
        order = np.argsort(y, kind="stable")
    shard_indices = [np.asarray(s) for s in np.array_split(order, config.n_workers)]
    return HostDataset(
        X_full=X, y_full=y, shard_indices=shard_indices,
        problem_type=config.problem_type,
    )


def random_softmax_dataset(
    n_workers: int, batch: int, d_feat: int, n_classes: int, seed: int = 0
) -> HostDataset:
    """Seeded random standardized features + uniform labels for the
    compute-bound softmax tier; each worker's shard is exactly its batch
    (full-batch local gradients).

    Generated directly rather than through sklearn: throughput does not
    depend on learnability, and ``make_classification`` at d=4096 costs
    minutes a measurement does not need. Convergence of the family is
    pinned at small shapes in tests/test_softmax.py.
    """
    rng = np.random.default_rng(seed)
    n = n_workers * batch
    X = rng.standard_normal((n, d_feat)).astype(np.float64)
    X = np.hstack([X, np.ones((n, 1))])
    y = rng.integers(0, n_classes, size=n).astype(np.float64)
    shard_indices = [
        np.arange(i * batch, (i + 1) * batch) for i in range(n_workers)
    ]
    return HostDataset(X_full=X, y_full=y, shard_indices=shard_indices,
                       problem_type="softmax")


def partition_summary(dataset: HostDataset, max_workers: int = 32) -> str:
    """Per-worker shard report, parity with the reference's generation-time
    printout (reference ``utils.py:43-48``): shard size, target range, and
    mean per worker — the lines that make the sorted-partition non-IID skew
    visible — plus the dataset totals line.

    Above ``max_workers`` workers the per-worker lines are truncated to the
    first and last few plus an elision line (the reference prints all N, but
    never runs past N=25; at this repo's sweep scales that would be thousands
    of stderr lines per run).
    """

    def worker_line(i: int) -> str:
        _, yi = dataset.shard(i)
        if len(yi) == 0:
            # n_workers > n_samples leaves trailing shards empty (array_split
            # semantics); runnable downstream, so report rather than crash.
            return f"Worker {i}: 0 samples"
        return (
            f"Worker {i}: {len(yi)} samples, Target y range: "
            f"[{yi.min():.2f}, {yi.max():.2f}], Mean y: {yi.mean():.2f}"
        )

    n = dataset.n_workers
    if n <= max_workers:
        lines = [worker_line(i) for i in range(n)]
    else:
        head, tail = max_workers - 4, 2
        sizes = np.array([len(idx) for idx in dataset.shard_indices])
        lines = [worker_line(i) for i in range(head)]
        lines.append(
            f"... ({n - head - tail} workers elided; shard sizes "
            f"{sizes.min()}-{sizes.max()}) ..."
        )
        lines.extend(worker_line(i) for i in range(n - tail, n))
    lines.append(
        f"Generated {dataset.X_full.shape[0]} samples, "
        f"{dataset.n_features} features"
    )
    return "\n".join(lines)


def stack_shards(dataset: HostDataset, dtype=np.float32) -> DeviceDataset:
    """Stack ragged shards into padded [N, L, d] arrays for the device path.

    No loop over workers. Equal shards that are consecutive rows of
    ``X_full`` ARE ``X_full.reshape(N, L, d)``: in the run dtype that view
    is returned as it is (``stacked_by`` ``view``; nothing downstream writes
    into a ``DeviceDataset``, and nothing may), in another dtype it is one
    ``astype`` pass (``cast``). Any other partition (a permutation from
    ``argsort``, ragged sizes from ``array_split``, empty trailing shards) is
    one [N, L] index matrix and one ``np.take`` (``gather``), padding rows
    zero.

    Softmax labels are CLASS INDICES and stay int32 regardless of the run
    dtype: under bfloat16 (8-bit significand) every odd index above 256
    would silently round to its even neighbor — at the compute-bound
    tier's K=512 that corrupts ~25% of the labels while throughput looks
    normal. The kernels consume them via ``y.astype(int32)`` either way
    (ops/losses.py softmax section), so only the storage changes.
    """
    n = dataset.n_workers
    d = dataset.n_features
    sizes = np.array([len(idx) for idx in dataset.shard_indices], dtype=np.int32)
    L = int(sizes.max()) if n else 0
    dtype = np.dtype(dtype)
    y_dtype = np.dtype(np.int32) if dataset.problem_type == "softmax" else dtype
    if L == 0:  # no worker holds a row
        return DeviceDataset(
            X=np.zeros((n, 0, d), dtype), y=np.zeros((n, 0), y_dtype),
            n_valid=sizes,
        )
    X_full, y_full = dataset.X_full, dataset.y_full
    rows = np.concatenate(dataset.shard_indices)
    if rows.size == n * L and np.array_equal(rows, np.arange(n * L)):
        view = X_full.dtype == dtype and X_full.flags.c_contiguous
        X = X_full[: n * L].astype(dtype, copy=not view).reshape(n, L, d)
        y = y_full[: n * L].astype(y_dtype, copy=False).reshape(n, L)
        for a, full in ((X, X_full), (y, y_full)):
            if np.may_share_memory(a, full):
                a.flags.writeable = False
        return DeviceDataset(
            X=X, y=y, n_valid=sizes, stacked_by="view" if view else "cast"
        )
    n_rows = X_full.shape[0]
    if rows.min() < -n_rows or rows.max() >= n_rows:
        raise IndexError(
            f"shard_indices reach outside the dataset's {n_rows} rows"
        )
    # Padding slots read row 0 (any valid row) and are zeroed after.
    index = np.zeros((n, L), dtype=np.intp)
    valid = np.arange(L) < sizes[:, None]
    index[valid] = rows % n_rows
    X = np.empty((n, L, d), dtype=dtype)
    y = np.empty((n, L), dtype=y_dtype)
    # Cast first: the gather then writes the output once, in its dtype.
    # ``mode="clip"`` (the indices are in range, checked above) because
    # under the default mode ``np.take`` fills ``out`` through a buffer.
    np.take(X_full.astype(dtype, copy=False), index, axis=0, out=X, mode="clip")
    np.take(y_full.astype(y_dtype, copy=False), index, out=y, mode="clip")
    padding = ~valid
    X[padding] = 0
    y[padding] = 0
    return DeviceDataset(X=X, y=y, n_valid=sizes, stacked_by="gather")
