"""Sharded mixing on the 8-device CPU mesh: the worker mesh's halo forms and
the GSPMD stencils against the dense matrix, and what each lowers to."""

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
import pytest

from distributed_optimization_tpu.ops.mixing import make_mixing_op
from distributed_optimization_tpu.parallel.collectives import make_halo_mixing_op
from distributed_optimization_tpu.parallel.mesh import (
    make_worker_mesh,
    shard_over_workers,
    usable_device_count,
    worker_sharding,
)
from distributed_optimization_tpu.parallel.topology import build_topology


def _mesh(n_workers):
    return make_worker_mesh(n_workers)


def _row_mesh(topo):
    """The auto mesh ``_run`` sizes: whole grid rows a block on a torus."""
    return make_worker_mesh(topo.grid_shape[0] if topo.grid_shape else topo.n)


def _assert_is_the_dense_round(op, x, x_host, dense):
    np.testing.assert_allclose(
        np.asarray(op.apply(x)), dense.mixing_matrix @ x_host, rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(op.neighbor_sum(x)), dense.adjacency @ x_host, rtol=1e-5, atol=1e-5
    )


# grid 64 over 8 devices: ONE grid row a block, ``up`` and ``down`` wholly halo
@pytest.mark.parametrize("name,n", [("ring", 8), ("ring", 16), ("ring", 24), ("grid", 64)])
def test_halo_shift_equals_dense(rng, name, n):
    """The worker mesh's ppermute shifts, built from the neighbor table,
    reproduce W @ x exactly (up to f32)."""
    topo = build_topology(name, n, impl="neighbor")
    mesh = _row_mesh(topo)
    op = make_halo_mixing_op(topo, mesh)
    assert op.impl == "halo_shift"
    x_host = rng.normal(size=(n, 7)).astype(np.float32)
    x = shard_over_workers(mesh, jnp.asarray(x_host))
    _assert_is_the_dense_round(op, x, x_host, build_topology(name, n))


@pytest.mark.parametrize("n", [8, 16])
def test_gspmd_fc_stencil_on_sharded_input_equals_dense(rng, n):
    """A complete graph has no neighbor table and no halo form: sharded, it
    runs the GSPMD stencil (the global mean, an AllReduce)."""
    topo = build_topology("fully_connected", n)
    mesh = _mesh(n)
    op = make_mixing_op(topo, impl="stencil")
    x_host = rng.normal(size=(n, 7)).astype(np.float32)
    x = shard_over_workers(mesh, jnp.asarray(x_host))
    _assert_is_the_dense_round(op, x, x_host, topo)


def test_halo_shift_under_jit_preserves_sharding(rng):
    n = 16
    topo = build_topology("ring", n, impl="neighbor")
    mesh = _mesh(n)
    op = make_halo_mixing_op(topo, mesh)
    assert op.impl == "halo_shift"
    x = shard_over_workers(mesh, jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32)))
    out = jax.jit(op.apply)(x)
    np.testing.assert_allclose(
        np.asarray(out), build_topology("ring", n).mixing_matrix @ np.asarray(x),
        rtol=1e-5, atol=1e-6,
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


def test_mesh_uses_multiple_devices():
    """The conftest 8-device CPU platform must actually be in effect."""
    assert len(jax.devices()) == 8
    assert make_worker_mesh(16).shape["workers"] == 8


# --------------------------------------------------------- compiled lowering
#
# parallel/collectives.py's module docstring makes two hardware claims that
# nothing above checks: the sharded mixing ops lower to real
# CollectivePermute/AllReduce instructions (not all-gathers of the full
# state), and a ring round moves exactly 2·d floats per device, independent
# of N. These tests enforce both against the compiled HLO on the 8-device
# mesh, for the GSPMD stencils (where XLA, not we, chooses the collective —
# the roll-stencil only embeds as boundary permutes if the compiler
# recognizes it) and, on a torus, for the worker mesh's shifts beside them
# (a ring's: tests/test_worker_mesh.py).

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


@pytest.mark.parametrize("impl", ["stencil"])
@pytest.mark.parametrize("n", [16, 24])
def test_ring_lowers_to_boundary_permutes_with_2d_floats(impl, n):
    """Ring mixing on D devices compiles to exactly two boundary
    CollectivePermutes of [1, d] each — 2·d floats sent per device per
    round, independent of N — and no all-gather of the [N, d] state."""
    d = 7
    topo = build_topology("ring", n)
    mesh = _mesh(n)
    op = make_mixing_op(topo, impl=impl)
    x = shard_over_workers(mesh, jnp.zeros((n, d), jnp.float32))
    hlo = _compiled_hlo(op.apply, x)
    payloads = _permute_payload_floats(hlo)
    assert len(payloads) == 2, f"expected 2 boundary permutes, got {payloads}"
    assert sum(payloads) == 2 * d
    assert "all-gather" not in hlo
    assert "all-reduce" not in hlo


@pytest.mark.parametrize("impl", ["stencil"])
def test_fc_lowers_to_all_reduce(impl):
    """Fully-connected mixing is the global mean: one AllReduce spanning all
    devices, no permutes, no gather of the full state."""
    n, d = 16, 7
    topo = build_topology("fully_connected", n)
    mesh = _mesh(n)
    op = make_mixing_op(topo, impl=impl)
    x = shard_over_workers(mesh, jnp.zeros((n, d), jnp.float32))
    hlo = _compiled_hlo(op.apply, x)
    assert re.search(r"all-reduce(-start)?\(", hlo)
    assert not _permute_payload_floats(hlo)
    assert "all-gather" not in hlo


@pytest.mark.parametrize("route", ["stencil", "worker_mesh"])
def test_grid_lowers_to_row_permutes(route):
    """A torus with whole grid rows blocked over devices, by the GSPMD
    stencil on an auto mesh and by ``worker_mesh``'s halo shifts (ISSUE 53):
    two boundary grid-row exchanges of [cols, d] each, 2·cols·d floats per
    device per round, and no gather and no neighbor table in the compiled
    text."""
    n, d = 64, 7
    topo = build_topology("grid", n)
    rows, cols = topo.grid_shape
    mesh = make_worker_mesh(rows)
    if route == "stencil":
        op = make_mixing_op(topo, impl="stencil")
    else:
        op = make_halo_mixing_op(topo, mesh)
        assert op.impl == "halo_shift" and op.tables is None
    x = shard_over_workers(mesh, jnp.zeros((n, d), jnp.float32))
    hlo = _compiled_hlo(op.apply, x)
    payloads = _permute_payload_floats(hlo)
    assert len(payloads) == 2
    assert payloads == [cols * d] * 2
    assert "all-gather" not in hlo
    assert not re.search(r"\bgather\(", hlo)
    assert not re.search(r"s32\[[\d,]*,4\]", hlo)  # no [.., k_max] table


def test_dense_mixing_on_sharded_input_gathers():
    """Contrast case: the dense [N, N] contraction cannot ride boundary
    permutes — under GSPMD it materializes the full state (all-gather or
    equivalent full-state movement), which is exactly why the stencil and
    halo forms exist for mesh-embeddable graphs."""
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
