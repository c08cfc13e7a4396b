"""The shards' way to the device (ISSUE 29): ``parallel.mesh.place_shards``
hands a stack over as 2-D ``[rows, C]`` blocks of whole workers where one
copy of it could reach 2**32 bytes on the device (such a copy takes a slow
path in the TPU runtime, whatever its shape) and forms ``[N, L, d]`` there;
the array the scan takes is the one ``jnp.asarray`` gave, and a run is
bitwise the same however its shards were stacked and placed.
CPU, small shapes: what is checked is values, shapes and labels, never a
time.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import small_backend_config

from distributed_optimization_tpu.backends import jax_backend
from distributed_optimization_tpu.observability.spans import Tracer
from distributed_optimization_tpu.parallel import mesh as mesh_mod
from distributed_optimization_tpu.parallel.mesh import (
    make_worker_mesh,
    place_shards,
    tiled_bytes,
)
from distributed_optimization_tpu.utils.data import (
    HostDataset,
    generate_synthetic_dataset,
    stack_shards,
)


def stack(shape, dtype=np.float32):
    rng = np.random.default_rng(3)
    return rng.standard_normal(shape).astype(dtype)


FLAT_CASES = {
    # name: (shape, dtype, keywords, label)
    # 40 * 63 = 2520 numbers: no multiple of 128, so every block has a tail.
    "tails-5-blocks": (
        (40, 7, 9), "float32", dict(block_bytes=8 * 63 * 4, columns=128),
        "flat:15x128/5"),
    "tail-1-block": (
        (40, 7, 9), "float32", dict(block_bytes=1 << 30, columns=128),
        "flat:19x128/1"),
    # 37 workers in blocks of 5: the last block is shorter.
    "ragged-last-block": (
        (37, 5, 3), "float32", dict(block_bytes=300, columns=128),
        "flat:0x128/8"),
    # The GLM cell's slab: blocks grow to whole rows of 1,024 (1,024
    # workers), so no block has a tail.
    "glm-slab-whole-rows": (
        (2048, 53, 81), "float32", dict(block_bytes=20 << 20),
        "flat:8586x1024/2"),
    "float64": (
        (16, 7, 9), "float64", dict(block_bytes=4 * 63 * 8, columns=128),
        "flat:4x128/4"),
    "bfloat16": (
        (16, 7, 9), "bfloat16", dict(block_bytes=1 << 30, columns=128),
        "flat:7x128/1"),
    # Slabs that fill the device's tiles go the same way.
    "tile-filling-slabs": (
        (4, 16, 256), "float32", dict(block_bytes=2 * 16 * 256 * 4),
        "flat:16x1024/2"),
}


@pytest.mark.parametrize("case", list(FLAT_CASES))
def test_flat_placement_is_the_host_array(case):
    shape, dtype, kw, label = FLAT_CASES[case]
    X = stack(shape, np.dtype(dtype))
    with jax.enable_x64(dtype == "float64"):
        got, how, waits = place_shards(None, X, min_tiled_bytes=0, **kw)
        want = jnp.asarray(X)
    assert how == label
    # What the host waited (ISSUE 48): every piece but a device's last.
    blocks = int(label.rsplit("/", 1)[1])
    assert waits["blocks"] == blocks
    assert 0 <= waits["slowest_block_s"] <= waits["wait_s"]
    assert 0 <= waits["slowest_block"] < max(blocks - 1, 1)
    assert got.shape == X.shape and got.dtype == X.dtype
    assert got.sharding == want.sharding
    assert np.asarray(got).tobytes() == X.tobytes()


@pytest.mark.parametrize("why,X,mesh_size,kw", [
    ("under-the-cliff", stack((40, 7, 9)), 0, {}),
    ("not-contiguous", stack((40, 9, 7)).transpose(0, 2, 1), 0,
     dict(min_tiled_bytes=0)),
    ("labels-rank-2", stack((40, 7)), 0, dict(min_tiled_bytes=0)),
    ("under-a-mesh-and-the-cliff", stack((40, 7, 9)), 4, {}),
    ("labels-under-a-mesh", stack((40, 7)), 4, dict(min_tiled_bytes=0)),
])
def test_direct_placement_where_flat_gains_nothing(why, X, mesh_size, kw):
    mesh = make_worker_mesh(X.shape[0], jax.devices()[:mesh_size]) if (
        mesh_size) else None
    got, how, waits = place_shards(mesh, X, **kw)
    assert how == ("direct" if mesh is None else f"mesh{mesh_size}:direct")
    assert waits == {}  # one copy a device, nothing waited for (ISSUE 48)
    assert got.shape == X.shape and got.dtype == X.dtype
    np.testing.assert_array_equal(np.asarray(got), X)
    if mesh is not None:
        assert len(got.sharding.device_set) == mesh_size


MESH_CASES = {
    # name: (shape, dtype, devices, keywords, label)
    "direct-f32": ((40, 7, 9), "float32", 4, {}, "mesh4:direct"),
    "direct-f64": ((16, 5, 3), "float64", 2, {}, "mesh2:direct"),
    "direct-rank-2": ((40, 7), "float32", 4, dict(min_tiled_bytes=0),
                      "mesh4:direct"),
    # 10 workers a device in pieces of 3: four pieces, the last of one
    # worker, every piece with a tail (63 numbers a worker, 128 columns).
    "flat-tails": (
        (40, 7, 9), "float32", 4,
        dict(min_tiled_bytes=0, block_bytes=3 * 63 * 4, columns=128),
        "mesh4:flat:3x128/4"),
    "flat-one-piece": (
        (16, 7, 9), "bfloat16", 8,
        dict(min_tiled_bytes=0, block_bytes=1 << 30, columns=128),
        "mesh8:flat:0x128/1"),
    "flat-f64": (
        (16, 7, 9), "float64", 2,
        dict(min_tiled_bytes=0, block_bytes=4 * 63 * 8, columns=128),
        "mesh2:flat:2x128/2"),
    # The cliff is held against ONE DEVICE's block: this stack is over the
    # threshold whole, each of its four blocks under it.
    "threshold-is-per-block": (
        (8, 8, 128), "float32", 4, dict(min_tiled_bytes=3 * 8 * 128 * 4),
        "mesh4:direct"),
    "threshold-reached-by-a-block": (
        (8, 8, 128), "float32", 4,
        dict(min_tiled_bytes=2 * 8 * 128 * 4, block_bytes=8 * 128 * 4),
        "mesh4:flat:2x1024/2"),
}


@pytest.mark.parametrize("case", list(MESH_CASES))
def test_under_a_mesh_each_block_goes_to_its_own_device(case, monkeypatch):
    """ISSUE 30: bit for bit the array ``shard_over_workers(mesh,
    jnp.asarray(X))`` gives, every shard on its own device, and nothing of
    the whole stack's size is ever put on, or made on, one device."""
    shape, dtype, n_dev, kw, label = MESH_CASES[case]
    X = stack(shape, np.dtype(dtype))
    mesh = make_worker_mesh(shape[0], jax.devices()[:n_dev])
    assert mesh.size == n_dev
    largest = {"put": 0, "made": 0}
    real_put, real_zeros, real_asarray = (
        jax.device_put, jnp.zeros, jnp.asarray)

    def device_put(x, device=None, **k):
        assert isinstance(device, jax.Device), "each copy names its device"
        largest["put"] = max(largest["put"], np.size(x))
        return real_put(x, device, **k)

    def zeros(shape_, dtype=None, **k):
        # ``jnp.zeros(..., device=)`` fills on the FIRST device and sends
        # the zeros on (chip_smoke.py's placement segment reads the peak
        # that leaves): a buffer is filled from a scalar put on its device.
        largest["made"] = max(largest["made"], int(np.prod(shape_)))
        return real_zeros(shape_, dtype, **k)

    def asarray(a, *args, **k):
        assert np.size(a) < X.size, "the whole stack as one device array"
        return real_asarray(a, *args, **k)

    with jax.enable_x64(dtype == "float64"):
        want = mesh_mod.shard_over_workers(mesh, jnp.asarray(X))
        with monkeypatch.context() as patch:
            patch.setattr(jax, "device_put", device_put)
            patch.setattr(jnp, "zeros", zeros)
            patch.setattr(jnp, "asarray", asarray)
            got, how, waits = place_shards(mesh, X, **kw)
    assert how == label
    # Pieces sent over all devices, where the label counts one device's.
    assert waits.get("blocks", 0) == (
        n_dev * int(label.rsplit("/", 1)[1]) if "flat" in label else 0
    )
    assert got.shape == X.shape and got.dtype == want.dtype == X.dtype
    assert got.sharding == want.sharding and got.committed
    assert np.asarray(got).tobytes() == X.tobytes()
    rows = shape[0] // n_dev
    for p, shard in enumerate(sorted(
            got.addressable_shards, key=lambda s: s.index[0].start)):
        assert shard.device == mesh.devices.flat[p]
        assert shard.index[0] == slice(p * rows, (p + 1) * rows)
        assert shard.data.shape == (rows,) + shape[1:]
    assert 0 < largest["put"] <= X.size // n_dev
    assert largest["made"] <= 1


def test_the_cliff_is_held_against_the_tiled_bytes():
    """What decides is whether ONE copy's device buffer can reach 2**32
    bytes: the GLM cell's stack can (and 15/16 of it, 4.22 GB on the host,
    does: 0.18 GB/s on the chip), half of it and the softmax cells' cannot."""
    cliff = mesh_mod.FLAT_MIN_TILED_BYTES
    assert cliff == 1 << 32
    assert tiled_bytes((53, 81), 4) == 56 * 128 * 4
    assert tiled_bytes((53, 81), 2) == 64 * 128 * 2  # (16, 128) tiles
    assert tiled_bytes((7,), 4) == 8 * 128 * 4
    assert tiled_bytes((262144, 53, 81), 4) >= cliff
    assert tiled_bytes((245760, 53, 81), 4) >= cliff
    assert tiled_bytes((131072, 53, 81), 4) < cliff
    assert tiled_bytes((96, 2048, 4097), 4) == 96 * 2048 * 4224 * 4 < cliff
    assert tiled_bytes((192, 2048, 4097), 4) >= cliff


def run_traced(cfg, ds, **kw):
    tracer = Tracer()
    with tracer.activate():
        result = jax_backend.run(cfg, ds, 0.0, **kw)
    (root,) = [e for e in tracer.spans() if e["name"] == "dopt.run"]
    return result, root["args"]


@pytest.mark.parametrize("problem,algorithm", [
    ("quadratic", "dsgd"), ("softmax", "choco"),
])
def test_a_run_is_bitwise_the_same_however_stacked_and_placed(
    monkeypatch, problem, algorithm
):
    """The same shards as an ``argsort`` partition of float64 rows
    (``gather``) and laid worker after worker in the run dtype (``view``),
    placed directly and flat: one trajectory."""
    extra = dict(n_classes=3) if problem == "softmax" else {}
    if algorithm == "choco":
        extra.update(compression="top_k", compression_k=4, choco_gamma=0.2)
    cfg = small_backend_config(
        n_iterations=30, eval_every=5, problem_type=problem,
        algorithm=algorithm, **extra,
    )
    gathered = generate_synthetic_dataset(cfg)
    dev = stack_shards(gathered, dtype=np.float32)
    n, L, d = dev.X.shape
    consecutive = HostDataset(
        X_full=dev.X.reshape(n * L, d), y_full=dev.y.reshape(n * L),
        shard_indices=list(np.arange(n * L).reshape(n, L)),
        problem_type=problem,
    )
    want, args = run_traced(cfg, gathered, use_mesh=False)
    assert (args["stack"], args["placement"]) == ("gather", "direct")
    monkeypatch.setattr(
        jax_backend, "place_shards",
        functools.partial(place_shards, min_tiled_bytes=0, block_bytes=3 * L * d * 4,
                          columns=128),
    )
    for ds, stacked_by in ((consecutive, "view"), (gathered, "gather")):
        got, args = run_traced(cfg, ds, use_mesh=False)
        assert args["stack"] == stacked_by
        assert args["placement"].startswith("flat:") and (
            args["placement"].endswith("x128/3"))
        np.testing.assert_array_equal(
            got.history.objective, want.history.objective)
        np.testing.assert_array_equal(
            got.history.consensus_error, want.history.consensus_error)
        np.testing.assert_array_equal(got.final_models, want.final_models)
