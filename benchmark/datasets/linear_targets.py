"""Real targets of a linear model, as sklearn ``make_regression(
n_informative=k, noise=10.0)`` draws them (what the package's own generator
calls for the quadratic and huber families), restated for bulk:
standard-normal features, a ground-truth coefficient 100 * U(0, 1) on the
first ``n_informative_features`` columns and 0 on the rest (the bias among
them), y = X @ coef + ``noise`` * standard normal. Rows are sorted by target
with a stable sort before they are cut into shards (the reference study's
non-IID partition), so each shard holds a narrow slice of the targets. No
scaler: the features are unit-variance by construction."""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.datasets import N_CHUNKS, N_THREADS, features_with_bias


def _in_chunks(n, fill):
    bounds = np.linspace(0, n, N_CHUNKS + 1).astype(np.int64)
    with ThreadPoolExecutor(N_THREADS) as pool:
        list(pool.map(lambda k: fill(bounds[k], bounds[k + 1]), range(N_CHUNKS)))


def generate(spec, exp, seed_seq):
    L = int(spec["rows_per_worker"])
    n_features = int(exp["n_features"])
    n = int(exp["n_workers"]) * L
    feat_seq, coef_seq, noise_seq = seed_seq.spawn(3)
    k = int(exp["n_informative_features"])
    coef = np.zeros(n_features + 1, dtype=np.float32)
    coef[:k] = 100.0 * np.random.default_rng(coef_seq).random(k, dtype=np.float32)
    X = features_with_bias(n, n_features, feat_seq)
    y = np.random.default_rng(noise_seq).standard_normal(n, dtype=np.float32)
    y *= np.float32(spec["noise"])

    def add_signal(lo, hi):
        y[lo:hi] += X[lo:hi] @ coef

    _in_chunks(n, add_signal)
    order = np.argsort(y, kind="stable")
    X_sorted = np.empty_like(X)

    def place(lo, hi):
        np.take(X, order[lo:hi], axis=0, out=X_sorted[lo:hi])

    _in_chunks(n, place)
    return X_sorted, y[order], L
