"""Standard-normal features, labels uniform over ``n_classes``: throughput
of the multinomial tier does not depend on learnability."""

import numpy as np

from benchmark.datasets import features_with_bias


def generate(spec, exp, seed_seq):
    L = int(spec["rows_per_worker"])
    n_features = int(exp["n_features"])
    n = int(exp["n_workers"]) * L
    feat_seq, label_seq = seed_seq.spawn(2)
    X = features_with_bias(n, n_features, feat_seq)
    y = np.random.default_rng(label_seq).integers(
        0, int(exp["n_classes"]), size=n).astype(np.float32)
    return X, y, L
