"""``gaussian_two_class``'s data with every row's class drawn independently:
binary labels in {-1, +1}, each +1 with probability 1/2; one unit-variance
Gaussian cluster per class, ``class_sep`` apart along a seeded direction
over the informative features; ``flip_y`` of the rows carry the other
class's label. Nothing is sorted, so shards cut contiguously are IID: what
the package's ``partition='shuffled'`` gives by permuting sklearn's rows."""

import numpy as np

from benchmark.datasets import features_with_bias


def generate(spec, exp, seed_seq):
    L = int(spec["rows_per_worker"])
    n_features = int(exp["n_features"])
    n = int(exp["n_workers"]) * L
    feat_seq, dir_seq, flip_seq, class_seq = seed_seq.spawn(4)
    cluster = np.where(
        np.random.default_rng(class_seq).random(n, dtype=np.float32) < 0.5, -1.0, 1.0
    ).astype(np.float32)
    rng = np.random.default_rng(dir_seq)
    direction = np.zeros(n_features, dtype=np.float32)
    k = int(exp["n_informative_features"])
    direction[:k] = rng.standard_normal(k)
    direction *= float(spec["class_sep"]) / np.linalg.norm(direction)
    y = cluster.copy()
    flips = np.random.default_rng(flip_seq).random(n) < float(spec["flip_y"])
    y[flips] *= -1.0
    X = features_with_bias(n, n_features, feat_seq, cluster, direction)
    return X, y, L
