"""Device-mesh construction and worker-axis sharding.

The worker dimension N is the framework's parallel axis: models ``[N, d]``,
stacked data ``[N, L, d]``, and every algorithm-state leaf shard over a 1-D
``Mesh`` along ``'workers'``. Workers-per-device packing (N > number of chips)
is just the block size of that sharding — e.g. 256 workers on a v5e-8 puts 32
worker rows on each chip, and the per-worker math vectorizes across the block
while gossip shifts cross chip boundaries as ICI collectives (SURVEY.md §7
step 8).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

WORKER_AXIS = "workers"


def usable_device_count(n_workers: int, n_devices: int) -> int:
    """Largest device count <= n_devices that divides n_workers evenly."""
    for k in range(min(n_workers, n_devices), 0, -1):
        if n_workers % k == 0:
            return k
    return 1


def make_worker_mesh(
    n_workers: int, devices: Optional[Sequence[jax.Device]] = None
) -> Mesh:
    """1-D mesh over the devices that can evenly split ``n_workers``."""
    devices = list(devices if devices is not None else jax.devices())
    k = usable_device_count(n_workers, len(devices))
    return Mesh(devices[:k], (WORKER_AXIS,))


def make_sized_worker_mesh(n_devices: int) -> Mesh:
    """1-D worker mesh of EXACTLY ``n_devices`` devices.

    The ``worker_mesh`` config axis (docs/PERF.md §16) pins the shard
    count as a contract — the halo plan, the per-shard timeline slices
    and the bytes-over-ICI accounting are all built for that exact P —
    so unlike ``make_worker_mesh`` there is no best-effort shrink: too
    few visible devices is an error that says what is visible.
    """
    devices = jax.devices()
    if len(devices) < n_devices:
        raise ValueError(
            f"worker_mesh={n_devices} needs that many devices; JAX sees "
            f"{len(devices)} (platform {devices[0].platform}, "
            f"{devices[0].device_kind})"
        )
    return Mesh(devices[:n_devices], (WORKER_AXIS,))


def worker_sharding(mesh: Mesh, ndim: int) -> NamedSharding:
    """Sharding that splits axis 0 (workers) and replicates the rest."""
    return NamedSharding(mesh, P(WORKER_AXIS, *([None] * (ndim - 1))))


def shard_over_workers(mesh: Optional[Mesh], tree):
    """device_put every array leaf with axis 0 split over the worker axis."""
    if mesh is None:
        return jax.tree.map(jax.numpy.asarray, tree)
    return jax.tree.map(
        lambda a: jax.device_put(a, worker_sharding(mesh, a.ndim)), tree
    )


def replicate(mesh: Optional[Mesh], tree):
    """device_put array leaves fully replicated across the mesh."""
    if mesh is None:
        return jax.tree.map(jax.numpy.asarray, tree)
    return jax.tree.map(
        lambda a: jax.device_put(a, NamedSharding(mesh, P())), tree
    )
