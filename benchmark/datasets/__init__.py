"""Seeded synthetic datasets, made in bulk on the host as float32, the type
they are handed to the program in. One file per family,
``benchmark/datasets/<generator>.py`` with ``generate(spec, exp, seed_seq)``,
found by the name in the configuration file's ``dataset`` block; rows are
laid out worker after worker, so worker i's shard is rows [i*L, (i+1)*L).

The package's own generators (sklearn ``make_classification``, a float64
``standard_normal``) take minutes and tens of GB at these sizes; these draw the
same kind of data (one Gaussian cluster per class, label noise, sorted-by-label
partition; or standard-normal features with uniform labels) in seconds.
"""

import importlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# Fixed, so the data depend on the seed alone and never on the core count.
N_CHUNKS = 32
N_THREADS = 8


def features_with_bias(n, n_features, seed_seq, row_sign=None, direction=None):
    """[n, n_features + 1] float32: standard normals (plus ``row_sign`` times
    ``direction``, where given) and a last column of ones, made chunk by chunk
    over a few threads."""
    X = np.empty((n, n_features + 1), dtype=np.float32)
    bounds = np.linspace(0, n, N_CHUNKS + 1).astype(np.int64)
    children = seed_seq.spawn(N_CHUNKS)

    def fill(k):
        lo, hi = bounds[k], bounds[k + 1]
        rng = np.random.default_rng(children[k])
        block = rng.standard_normal((hi - lo, n_features), dtype=np.float32)
        if row_sign is not None:
            block += row_sign[lo:hi, None] * direction[None, :]
        X[lo:hi, :-1] = block
        X[lo:hi, -1] = 1.0

    with ThreadPoolExecutor(N_THREADS) as pool:
        list(pool.map(fill, range(N_CHUNKS)))
    return X


def make(config, seed):
    """(X [n, d+1] float32, y [n], rows per worker) for this configuration and seed."""
    spec = config["dataset"]
    generator = importlib.import_module(f"benchmark.datasets.{spec['generator']}")
    seed_seq = np.random.SeedSequence([int(seed), 0x5EED])
    return generator.generate(spec, config["experiment"], seed_seq)
