"""The only file of the benchmark that touches the package under test: it
builds the program's own config and dataset objects from the benchmark's
files and calls its normal entry point, ``jax_backend.run``."""

import numpy as np


def seed_for(seed):
    """The program's PRNG seed is an int32; ``--seed`` may exceed it. Draw one
    that fits from it."""
    return int(np.random.SeedSequence([int(seed)]).generate_state(1)[0] >> 1)


def build(config, traffic, X, y, rows_per_worker, seed):
    """(ExperimentConfig, HostDataset) for one experiment."""
    from distributed_optimization_tpu.config import ExperimentConfig
    from distributed_optimization_tpu.utils.data import HostDataset

    exp = dict(config["experiment"])
    n_workers = int(exp["n_workers"])
    cfg = ExperimentConfig(
        **exp,
        n_samples=X.shape[0],
        n_iterations=int(traffic["n_iterations"]),
        eval_every=int(traffic["eval_every"]),
        seed=int(seed),
    )
    rows = np.arange(X.shape[0], dtype=np.int64).reshape(n_workers, rows_per_worker)
    dataset = HostDataset(
        X_full=X, y_full=y, shard_indices=list(rows),
        problem_type=exp["problem_type"],
    )
    return cfg, dataset


def run_experiment(cfg, dataset):
    """One experiment through the normal path. ``f_opt`` = 0, so the recorded
    gap is the full-data objective itself."""
    from distributed_optimization_tpu.backends import jax_backend

    return jax_backend.run(cfg, dataset, 0.0, measure_compile=False)
