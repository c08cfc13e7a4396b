"""Mixing-operator tests: stencil forms ≡ dense W @ x, mean preservation."""

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_optimization_tpu.ops.mixing import make_mixing_op
from distributed_optimization_tpu.parallel.topology import build_topology

STENCIL_CASES = [("ring", 8), ("ring", 25), ("grid", 9), ("grid", 25), ("fully_connected", 8)]


@pytest.mark.parametrize("name,n", STENCIL_CASES)
def test_stencil_equals_dense(rng, name, n):
    topo = build_topology(name, n)
    x = rng.normal(size=(n, 5)).astype(np.float32)
    dense = make_mixing_op(topo, impl="dense")
    stencil = make_mixing_op(topo, impl="stencil")
    np.testing.assert_allclose(
        np.asarray(stencil.apply(jnp.asarray(x))),
        np.asarray(dense.apply(jnp.asarray(x))),
        rtol=1e-5,
        atol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(stencil.neighbor_sum(jnp.asarray(x))),
        np.asarray(dense.neighbor_sum(jnp.asarray(x))),
        rtol=1e-5,
        atol=1e-5,
    )


@pytest.mark.parametrize("name,n", [("ring", 8), ("grid", 16), ("fully_connected", 8), ("erdos_renyi", 12), ("chain", 7), ("star", 7)])
def test_dense_matches_host_matmul(rng, name, n):
    topo = build_topology(name, n, seed=1)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    op = make_mixing_op(topo, impl="dense")
    np.testing.assert_allclose(
        np.asarray(op.apply(jnp.asarray(x))), topo.mixing_matrix @ x, rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(op.neighbor_sum(jnp.asarray(x))), topo.adjacency @ x, rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("name,n", STENCIL_CASES)
def test_mixing_preserves_mean(rng, name, n):
    """W is doubly stochastic ⇒ gossip preserves the network average."""
    topo = build_topology(name, n)
    op = make_mixing_op(topo)
    x = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(jnp.mean(op.apply(x), axis=0)),
        np.asarray(jnp.mean(x, axis=0)),
        rtol=1e-4,
        atol=1e-5,
    )


SPARSE_CASES = [("erdos_renyi", 12), ("chain", 9), ("star", 9),
                ("directed_erdos_renyi", 12), ("ring", 8)]


@pytest.mark.parametrize("name,n", SPARSE_CASES)
def test_sparse_equals_dense(rng, name, n):
    """The CSR segment-sum contraction is the same linear operator as the
    dense matmul, for undirected AND directed (column-stochastic) graphs."""
    topo = build_topology(name, n, seed=2, erdos_renyi_p=0.35)
    x = rng.normal(size=(n, 5)).astype(np.float32)
    dense = make_mixing_op(topo, impl="dense")
    sparse = make_mixing_op(topo, impl="sparse")
    assert sparse.impl == "sparse"
    np.testing.assert_allclose(
        np.asarray(sparse.apply(jnp.asarray(x))),
        np.asarray(dense.apply(jnp.asarray(x))),
        rtol=1e-5, atol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(sparse.neighbor_sum(jnp.asarray(x))),
        np.asarray(dense.neighbor_sum(jnp.asarray(x))),
        rtol=1e-5, atol=1e-5,
    )


def test_sparse_handles_trailing_dims_and_jit(rng):
    """[N]-trailing-shape variants (push-sum's [N, 1] mass) and jit both
    work through the segment-sum path."""
    import jax

    topo = build_topology("erdos_renyi", 10, seed=4)
    sparse = make_mixing_op(topo, impl="sparse")
    w = rng.normal(size=(10, 1)).astype(np.float32)
    expected = topo.mixing_matrix.astype(np.float32) @ w
    np.testing.assert_allclose(
        np.asarray(jax.jit(sparse.apply)(jnp.asarray(w))), expected,
        rtol=1e-5, atol=1e-6,
    )


def test_sparse_through_backend_matches_dense_run(rng):
    """End-to-end: a backend run with mixing_impl='sparse' reproduces the
    dense run's trajectory exactly (same linear operator, same batches)."""
    from conftest import small_backend_config
    from distributed_optimization_tpu.backends import jax_backend
    from distributed_optimization_tpu.utils.data import (
        generate_synthetic_dataset,
    )
    from distributed_optimization_tpu.utils.oracle import (
        compute_reference_optimum,
    )

    cfg = small_backend_config(topology="erdos_renyi", n_iterations=40,
                               dtype="float64")
    ds = generate_synthetic_dataset(cfg)
    _, f_opt = compute_reference_optimum(ds, cfg.reg_param)
    rd = jax_backend.run(cfg.replace(mixing_impl="dense"), ds, f_opt)
    rs = jax_backend.run(cfg.replace(mixing_impl="sparse"), ds, f_opt)
    np.testing.assert_allclose(rs.final_models, rd.final_models, rtol=1e-10)
    np.testing.assert_allclose(
        rs.history.objective, rd.history.objective, rtol=1e-9
    )


def test_stencil_rejected_for_irregular_graph():
    topo = build_topology("erdos_renyi", 10, seed=0)
    with pytest.raises(ValueError):
        make_mixing_op(topo, impl="stencil")


def test_auto_picks_stencil_for_regular_graphs():
    assert make_mixing_op(build_topology("ring", 8)).impl == "stencil"
    assert make_mixing_op(build_topology("erdos_renyi", 8, seed=0)).impl == "dense"


def test_sparse_is_opt_in_only():
    """docs/perf/sparse_mixing.json measured DENSE faster than the CSR
    form at every cell (N up to 4096, densities 0.05%-40%, both
    platforms), so auto keeps dense for irregular graphs at any scale and
    sparse is explicit opt-in."""
    assert make_mixing_op(build_topology("chain", 128)).impl == "dense"
    assert make_mixing_op(build_topology("chain", 16)).impl == "dense"
    assert make_mixing_op(
        build_topology("erdos_renyi", 128, seed=0, erdos_renyi_p=0.05)
    ).impl == "dense"
    # Regular graphs keep their stencils at any N.
    assert make_mixing_op(build_topology("ring", 256)).impl == "stencil"
    assert make_mixing_op(
        build_topology("chain", 128), impl="sparse"
    ).impl == "sparse"


# The two option values whose kernels no ledger line ever chose (ROADMAP C3,
# PR 42) are gone: a config, the operator builder and the CLI refuse them as
# they refuse any other unknown value.
REMOVED_VALUES = pytest.mark.parametrize("field,value,rule", [
    ("mixing_impl", "pallas", {}),
    ("robust_impl", "fused", dict(aggregation="trimmed_mean", robust_b=1)),
], ids=["mixing_impl-pallas", "robust_impl-fused"])


@REMOVED_VALUES
def test_removed_impl_values_are_unknown(field, value, rule):
    from distributed_optimization_tpu.config import ExperimentConfig

    with pytest.raises(ValueError, match=f"Unknown .* impl: {value}"):
        ExperimentConfig(**{field: value}, **rule)
    if field == "mixing_impl":
        with pytest.raises(ValueError, match=f"Unknown mixing impl: '{value}'"):
            make_mixing_op(build_topology("ring", 8), impl=value)


@REMOVED_VALUES
def test_removed_impl_values_are_no_cli_choice(field, value, rule, capsys):
    from distributed_optimization_tpu.cli import build_parser

    argv = [
        arg for key, val in {**rule, field: value}.items()
        for arg in ("--" + key.replace("_", "-"), str(val))
    ]
    with pytest.raises(SystemExit):
        build_parser().parse_args(argv)
    assert f"invalid choice: '{value}'" in capsys.readouterr().err
