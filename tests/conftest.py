"""Test configuration: run JAX on 8 virtual CPU devices.

Multi-device tests (sharding, shard_map/ppermute collectives) run without TPU
hardware via XLA's host-platform device-count override — the same mechanism
the driver's multi-chip dry-run uses. Must be set before jax initializes.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# cli.main / the daemon's main place the persistent compile cache in
# <checkout>/.jax_cache (runtime.configure_compile_cache). Tests assert on
# compile behaviour (compile_seconds, executable-store hits), so the cache
# itself stays off here whatever directory is configured.
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from distributed_optimization_tpu.config import ExperimentConfig  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def small_backend_config(**kw):
    """The canonical small experiment config shared by the backend-level test
    modules (test_backends, test_oracle_extensions): 8 ring workers, tiny
    quadratic problem, jax backend."""
    defaults = dict(
        n_workers=8,
        n_samples=400,
        n_features=10,
        n_informative_features=6,
        problem_type="quadratic",
        n_iterations=60,
        topology="ring",
        algorithm="dsgd",
        backend="jax",
        local_batch_size=16,
    )
    defaults.update(kw)
    return ExperimentConfig(**defaults)


def batch_schedule(ds, T, batch, seed=0):
    """Fixed [T, N, batch] batch-index schedule for backend-equivalence tests
    (identical injected batches ⇒ identical trajectories, SURVEY.md §4c)."""
    rng = np.random.default_rng(seed)
    return np.stack(
        [
            [
                rng.choice(len(ds.shard_indices[i]), batch, replace=False)
                for i in range(len(ds.shard_indices))
            ]
            for _ in range(T)
        ]
    )


@pytest.fixture(scope="module")
def quad_setup():
    """(config, dataset, f_opt) for the canonical small quadratic problem."""
    from distributed_optimization_tpu.utils import (
        compute_reference_optimum,
        generate_synthetic_dataset,
    )

    cfg = small_backend_config()
    ds = generate_synthetic_dataset(cfg)
    _, f_opt = compute_reference_optimum(ds, cfg.reg_param)
    return cfg, ds, f_opt
