"""Explicit shard_map/ppermute collective tests on the 8-device CPU mesh."""

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
import pytest

from distributed_optimization_tpu.ops.mixing import make_mixing_op
from distributed_optimization_tpu.parallel.collectives import make_shard_map_mixing_op
from distributed_optimization_tpu.parallel.mesh import (
    make_worker_mesh,
    shard_over_workers,
    usable_device_count,
    worker_sharding,
)
from distributed_optimization_tpu.parallel.topology import build_topology


def _mesh(n_workers):
    return make_worker_mesh(n_workers)


@pytest.mark.parametrize(
    "name,n",
    [("ring", 8), ("ring", 16), ("ring", 24), ("fully_connected", 8), ("fully_connected", 16), ("grid", 64)],
)
def test_shard_map_mix_equals_dense(rng, name, n):
    """ppermute/psum stencils reproduce W @ x exactly (up to f32)."""
    topo = build_topology(name, n)
    mesh = _mesh(n)
    op = make_shard_map_mixing_op(topo, mesh)
    assert op.impl == "shard_map"
    x_host = rng.normal(size=(n, 7)).astype(np.float32)
    x = shard_over_workers(mesh, jnp.asarray(x_host))
    expected = topo.mixing_matrix @ x_host
    np.testing.assert_allclose(np.asarray(op.apply(x)), expected, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(op.neighbor_sum(x)), topo.adjacency @ x_host, rtol=1e-5, atol=1e-5
    )


def test_shard_map_mix_under_jit_preserves_sharding(rng):
    n = 16
    topo = build_topology("ring", n)
    mesh = _mesh(n)
    op = make_shard_map_mixing_op(topo, mesh)
    x = shard_over_workers(mesh, jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32)))
    out = jax.jit(op.apply)(x)
    np.testing.assert_allclose(
        np.asarray(out), topo.mixing_matrix @ np.asarray(x), rtol=1e-5, atol=1e-6
    )
    assert out.sharding.is_equivalent_to(worker_sharding(mesh, 2), 2)


def test_gspmd_stencil_on_sharded_input_matches_dense(rng):
    """The jnp.roll stencil path also works on mesh-sharded arrays (GSPMD
    inserts the collective permutes automatically)."""
    n = 24
    topo = build_topology("ring", n)
    mesh = _mesh(n)
    op = make_mixing_op(topo, impl="stencil")
    x_host = rng.normal(size=(n, 5)).astype(np.float32)
    x = shard_over_workers(mesh, jnp.asarray(x_host))
    out = jax.jit(op.apply)(x)
    np.testing.assert_allclose(np.asarray(out), topo.mixing_matrix @ x_host, rtol=1e-5, atol=1e-6)


def test_ppermute_roundtrip_identity(rng):
    """Collective-correctness invariant (SURVEY.md §5.2): shifting +1 then -1
    around the ring returns the original array bit-for-bit."""
    n = 8
    mesh = _mesh(n)
    from jax.sharding import PartitionSpec as P

    ndev = mesh.shape["workers"]
    fwd = [(i, (i + 1) % ndev) for i in range(ndev)]
    bwd = [(i, (i - 1) % ndev) for i in range(ndev)]

    def roundtrip(block):
        once = jax.lax.ppermute(block, "workers", fwd)
        return jax.lax.ppermute(once, "workers", bwd)

    f = shard_map(
        roundtrip, mesh=mesh, in_specs=P("workers", None), out_specs=P("workers", None)
    )
    x = shard_over_workers(mesh, jnp.asarray(rng.normal(size=(n, 4)).astype(np.float32)))
    np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(x))


def test_usable_device_count():
    assert usable_device_count(16, 8) == 8
    assert usable_device_count(25, 8) == 5
    assert usable_device_count(7, 8) == 7
    assert usable_device_count(9, 8) == 3
    assert usable_device_count(11, 8) == 1


def test_shard_map_rejects_irregular_topology():
    topo = build_topology("erdos_renyi", 8, seed=0)
    with pytest.raises(ValueError):
        make_shard_map_mixing_op(topo, _mesh(8))


def test_mesh_uses_multiple_devices():
    """The conftest 8-device CPU platform must actually be in effect."""
    assert len(jax.devices()) == 8
    assert make_worker_mesh(16).shape["workers"] == 8


# --------------------------------------------------------- compiled lowering
#
# The module docstrings make two hardware claims that nothing above checks:
# parallel/collectives.py:8-10 — the sharded mixing ops lower to real
# CollectivePermute/AllReduce instructions (not all-gathers of the full
# state), and a ring round moves exactly 2·d floats per device, independent
# of N. These tests enforce both against the compiled HLO on the 8-device
# mesh, for the explicit shard_map ops AND the GSPMD stencils (where XLA,
# not we, chooses the collective — the roll-stencil only embeds as boundary
# permutes if the compiler recognizes it).

import re


def _compiled_hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _permute_payload_floats(hlo: str) -> list[int]:
    """Element counts of every collective-permute instruction's operand."""
    out = []
    for line in hlo.splitlines():
        if re.search(r"collective-permute(-start)?\(", line):
            m = re.search(r"= (?:f32|bf16|f64|u32|s32)\[([\d,]*)\]", line)
            assert m, f"unparseable collective-permute line: {line.strip()}"
            dims = [int(v) for v in m.group(1).split(",") if v]
            out.append(int(np.prod(dims)) if dims else 1)
    return out


@pytest.mark.parametrize("impl", ["shard_map", "stencil"])
@pytest.mark.parametrize("n", [16, 24])
def test_ring_lowers_to_boundary_permutes_with_2d_floats(impl, n):
    """Ring mixing on D devices compiles to exactly two boundary
    CollectivePermutes of [1, d] each — 2·d floats sent per device per
    round, independent of N — and no all-gather of the [N, d] state."""
    d = 7
    topo = build_topology("ring", n)
    mesh = _mesh(n)
    if impl == "shard_map":
        op = make_shard_map_mixing_op(topo, mesh)
    else:
        op = make_mixing_op(topo, impl="stencil")
    x = shard_over_workers(mesh, jnp.zeros((n, d), jnp.float32))
    hlo = _compiled_hlo(op.apply, x)
    payloads = _permute_payload_floats(hlo)
    assert len(payloads) == 2, f"expected 2 boundary permutes, got {payloads}"
    assert sum(payloads) == 2 * d
    assert "all-gather" not in hlo
    assert "all-reduce" not in hlo


@pytest.mark.parametrize("impl", ["shard_map", "stencil"])
def test_fc_lowers_to_all_reduce(impl):
    """Fully-connected mixing is the global mean: one AllReduce spanning all
    devices, no permutes, no gather of the full state."""
    n, d = 16, 7
    topo = build_topology("fully_connected", n)
    mesh = _mesh(n)
    if impl == "shard_map":
        op = make_shard_map_mixing_op(topo, mesh)
    else:
        op = make_mixing_op(topo, impl="stencil")
    x = shard_over_workers(mesh, jnp.zeros((n, d), jnp.float32))
    hlo = _compiled_hlo(op.apply, x)
    assert re.search(r"all-reduce(-start)?\(", hlo)
    assert not _permute_payload_floats(hlo)
    assert "all-gather" not in hlo


def test_grid_shard_map_lowers_to_row_permutes():
    """Torus stencil with rows blocked over devices: two boundary grid-row
    exchanges of [1, cols, d] each — 2·cols·d floats per device per round."""
    n, d = 64, 7
    topo = build_topology("grid", n)
    rows, cols = topo.grid_shape
    mesh = make_worker_mesh(rows)
    op = make_shard_map_mixing_op(topo, mesh)
    x = shard_over_workers(mesh, jnp.zeros((n, d), jnp.float32))
    hlo = _compiled_hlo(op.apply, x)
    payloads = _permute_payload_floats(hlo)
    assert len(payloads) == 2
    assert sum(payloads) == 2 * cols * d
    assert "all-gather" not in hlo


def test_dense_mixing_on_sharded_input_gathers():
    """Contrast case: the dense [N, N] contraction cannot ride boundary
    permutes — under GSPMD it materializes the full state (all-gather or
    equivalent full-state movement), which is exactly why the stencil/
    shard_map forms exist for mesh-embeddable graphs."""
    n, d = 16, 7
    topo = build_topology("ring", n)
    mesh = _mesh(n)
    op = make_mixing_op(topo, impl="dense")
    x = shard_over_workers(mesh, jnp.zeros((n, d), jnp.float32))
    hlo = _compiled_hlo(op.apply, x)
    # XLA may choose all-gather, or dynamic-slice + all-reduce; either way
    # the boundary-permute pattern (2 permutes of d floats) must NOT appear.
    assert _permute_payload_floats(hlo) == [] or sum(
        _permute_payload_floats(hlo)
    ) > 2 * d
