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


def assert_ulps_of_scale(got, want, ulps):
    """``got`` is ``want`` to ``ulps`` units in the last place of the largest
    number in ``want``, in ``want``'s own float type.

    The yardstick for TWO DIFFERENT executables of the same per-row
    arithmetic (the sharded and the unsharded program): XLA contracts
    ``w_self * x + sum(w_nbr * gathered)`` into fused multiply-adds as each
    program's fusions fall, so a product rounds once more in one than in the
    other — one unit of the row's scale a round (jax 0.9 on the CPU; the
    same executable replayed stays bitwise and is held to that). The next
    precision down misses it by orders of magnitude: float32 arithmetic is
    2**29 float64 units off, bfloat16 2**16 float32 units."""
    want = np.asarray(want)
    atol = ulps * float(np.finfo(want.dtype).eps) * float(np.max(np.abs(want)))
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=atol)


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
