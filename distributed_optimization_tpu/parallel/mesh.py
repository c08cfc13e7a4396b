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

import collections
import functools
import math
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
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
    """device_put every array leaf with axis 0 split over the worker axis.

    A host (numpy) leaf goes shard by shard, each slice from the host to
    its own device; a leaf that is already a device array is resharded
    from where it lies. So what is large is handed over as numpy."""
    if mesh is None:
        return jax.tree.map(jax.numpy.asarray, tree)
    return jax.tree.map(
        lambda a: jax.device_put(a, worker_sharding(mesh, a.ndim)), tree
    )


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _sharded_zeros(shape, dtype, sharding):
    return jax.lax.with_sharding_constraint(jnp.zeros(shape, dtype), sharding)


def zeros_over_workers(mesh: Optional[Mesh], shape, dtype) -> jax.Array:
    """Zeros of ``shape``, axis 0 split over the worker axis: each device
    fills its own rows. (``jnp.zeros(..., device=)`` fills the whole array
    on the first device and sends it on: my chip run, PR 30.)"""
    if mesh is None:
        return jnp.zeros(shape, dtype=dtype)
    return _sharded_zeros(
        tuple(shape), np.dtype(dtype), worker_sharding(mesh, len(shape))
    )


def replicate(mesh: Optional[Mesh], tree):
    """device_put array leaves fully replicated across the mesh."""
    if mesh is None:
        return jax.tree.map(jax.numpy.asarray, tree)
    return jax.tree.map(
        lambda a: jax.device_put(a, NamedSharding(mesh, P())), tree
    )


# --- the stacked shards' way to the device (ISSUE 29) -----------------------
#
# One host-to-device copy whose device buffer is 2**32 bytes or more takes
# a slow path in the TPU runtime, whatever its shape: on one v5e a
# [245760, 53, 81] f32 stack (4.22 GB, 4.46 GB in the device's tiles) goes
# up at 0.18 GB/s where [131072, 53, 81] goes at 7.6, and the same bytes as
# [1099008, 1024] at 0.40 where [1030320, 1024] goes at 7.4 (PERF.md
# section 6, PR 29). So a stack that large goes up in blocks of whole
# workers, each a 2-D [rows, FLAT_COLUMNS] view of its bytes, which the
# runtime copies at 9.5 GB/s whatever [L, d] is (blocks in their own
# [nb, 53, 81] shape: 7.4), and a jitted reshape writes each block into
# the [N, L, d] array on the device. Under the cliff a stack goes up as it
# is (6-9 GB/s), with no second program, transient or device time. Under a
# mesh the same rule is applied to each device's block of workers, which
# goes to that device alone (ISSUE 30): four chips' shards, 18 GB, are
# never on one.

FLAT_COLUMNS = 1024
# One block's bytes. The transient on the device is two blocks in flight
# and one being reshaped, not a second copy of the stack (which, for the
# GLM cell's 4.5 GB, does not fit: the one-block program needs 19.8 GB).
FLAT_BLOCK_BYTES = 128 << 20
# The cliff, held against ``tiled_bytes``.
FLAT_MIN_TILED_BYTES = 1 << 32


def tiled_bytes(shape: Sequence[int], itemsize: int) -> int:
    """Bytes of an array of ``shape`` with its two minor dimensions padded
    to the TPU's (8, 128) tiles (sublanes pack two 16-bit or four 8-bit
    numbers). The runtime orders the dimensions as it likes ([N, 53, 81]
    f32: N minor, 1.06 of the host's bytes where this says 1.67), so this
    is what one copy's device buffer may be at most, not what it is."""
    *major, rows, cols = (1, 1, *shape)
    sublanes = 8 * max(1, 4 // itemsize)
    return (
        math.prod(major) * math.ceil(rows / sublanes) * sublanes
        * math.ceil(cols / 128) * 128 * itemsize
    )


@functools.partial(jax.jit, static_argnums=1)
def _filled(value, shape):
    """``shape`` filled with ``value``, on the device ``value`` lies on."""
    return jnp.broadcast_to(value, shape)


@functools.partial(jax.jit, donate_argnums=0)
def _write_block(out, main, tail, first_worker):
    """``out`` with the workers from ``first_worker`` on replaced by the
    block whose numbers are ``main`` ([rows, C]) then ``tail`` (1-D, fewer
    than C); the second result is ready when the block has been written."""
    flat = jnp.concatenate([main.reshape(-1), tail])
    block = flat.reshape((-1,) + out.shape[1:])
    return (
        jax.lax.dynamic_update_slice(out, block, (first_worker, 0, 0)),
        block[0, 0, 0],
    )


def _place_flat(blocks, devices, block_workers: int, columns: int):
    """Each of ``blocks`` (equal ``[n, L, d]`` host arrays) on its device
    (``None``: the default device, uncommitted), sent as pieces of
    ``block_workers`` workers, each a 2-D ``[rows, columns]`` view of its
    bytes (and a tail); the rows sent to one device in all; and what the
    host waited: ``blocks`` (pieces sent, all devices), ``wait_s`` (seconds
    in the ``block_until_ready`` calls below), ``slowest_block_s`` and
    ``slowest_block`` (the longest of them, and which piece in the order
    sent it waited for). The pieces go round the devices in turn, so the
    copies to different devices are in flight together."""
    n = blocks[0].shape[0]
    per_worker = blocks[0].shape[1] * blocks[0].shape[2]
    flats = [X.reshape(-1) for X in blocks]  # views: each is C-contiguous
    # Each buffer is filled where it will lie: ``jnp.zeros(..., device=)``
    # fills on the first device and sends the zeros on, which put three
    # other chips' 4.76 GB on chip 0 at once (14.4 of its 15.75 GB: my chip
    # run, PR 30).
    outs = [
        _filled(jax.device_put(np.zeros((), X.dtype), dev), X.shape)
        for X, dev in zip(blocks, devices)
    ]
    written = [collections.deque() for _ in blocks]
    rows = 0
    waits = {"blocks": 0, "wait_s": 0.0, "slowest_block_s": 0.0,
             "slowest_block": 0}
    for w0 in range(0, n, block_workers):
        for p, (flat, dev) in enumerate(zip(flats, devices)):
            chunk = flat[w0 * per_worker: (w0 + block_workers) * per_worker]
            split = chunk.size - chunk.size % columns
            if p == 0:
                rows += split // columns
            outs[p], done = _write_block(
                outs[p],
                jax.device_put(chunk[:split].reshape(-1, columns), dev),
                jax.device_put(chunk[split:], dev),
                w0,
            )
            # At most two pieces on a device beside its ``out``: the copy
            # of this one runs under the write of the one before.
            written[p].append((waits["blocks"], done))
            waits["blocks"] += 1
            if len(written[p]) > 1:
                sent, done = written[p].popleft()
                t = time.perf_counter()
                done.block_until_ready()
                waited = time.perf_counter() - t
                waits["wait_s"] += waited
                if waited > waits["slowest_block_s"]:
                    waits.update(slowest_block_s=waited, slowest_block=sent)
    return outs, rows, waits


def place_shards(
    mesh: Optional[Mesh],
    X: np.ndarray,
    *,
    min_tiled_bytes: int = FLAT_MIN_TILED_BYTES,
    block_bytes: int = FLAT_BLOCK_BYTES,
    columns: int = FLAT_COLUMNS,
) -> tuple[jax.Array, str, dict]:
    """The stacked shards ``X [N, L, d]`` (host) on the device, with the
    shape, dtype and default layout ``jnp.asarray`` gives, and how they got
    there: ``direct``, or ``flat:<rows>x<C>/<blocks>``; under a mesh of P
    devices ``mesh<P>:`` and then how each device's block got to it. The
    third result is what the host waited inside a flat placement
    (``_place_flat``: ``blocks``, ``wait_s``, ``slowest_block_s``,
    ``slowest_block``), empty under ``direct``, which waits for nothing.

    Under a mesh every device's block of workers (a view of ``X``) goes
    from the host to that device and to no other, and the blocks are joined
    (``jax.make_array_from_single_device_arrays``) into the array
    ``shard_over_workers`` would give: the whole stack is never on one
    device (ISSUE 30: four chips' shards do not fit one). The choice is
    made per block, from what can be seen here: a block that one copy can
    take without reaching the runtime's cliff (``tiled_bytes`` under
    ``min_tiled_bytes``), or one that is not contiguous, goes up as it is;
    any other goes up as 2-D ``[rows, columns]`` pieces of whole workers
    (``_place_flat``). The keywords are for tests and measurements.
    """
    if mesh is None:
        sharding, devices, blocks, label = None, [None], [X], ""
    else:
        sharding = worker_sharding(mesh, X.ndim)
        where = sharding.addressable_devices_indices_map(X.shape)
        devices = list(where)
        blocks = [X[where[dev]] for dev in devices]  # views: rows p*S..(p+1)*S
        label = f"mesh{mesh.size}:"
    block = blocks[0]
    if (
        X.ndim != 3
        or tiled_bytes(block.shape, X.dtype.itemsize) < min_tiled_bytes
        or not X.flags.c_contiguous
    ):
        if mesh is None:
            return jnp.asarray(X), "direct", {}
        outs = [jax.device_put(b, dev) for b, dev in zip(blocks, devices)]
        label += "direct"
        waits = {}
    else:
        n = block.shape[0]
        per_worker = X.shape[1] * X.shape[2]
        # Pieces of about ``block_bytes``, all of one size but the last;
        # whole rows of ``columns`` a piece (no tail) where that is a few
        # workers more.
        block_workers = min(
            n, max(1, block_bytes // (per_worker * X.dtype.itemsize))
        )
        block_workers = math.ceil(n / math.ceil(n / block_workers))
        whole = columns // math.gcd(per_worker, columns)
        if whole <= block_workers:
            block_workers = math.ceil(block_workers / whole) * whole
        outs, rows, waits = _place_flat(
            blocks, devices, block_workers, columns
        )
        label += f"flat:{rows}x{columns}/{math.ceil(n / block_workers)}"
    if mesh is None:
        return outs[0], label, waits
    return (
        jax.make_array_from_single_device_arrays(X.shape, sharding, outs),
        label,
        waits,
    )
