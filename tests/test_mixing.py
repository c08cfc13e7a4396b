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


# Undirected graphs with no stencil (and a ring, which has one): the gather
# over the live slots against the dense matrix. The drawn graph as the
# matrix-free tables the drawn-graph cell runs, against the dense build of
# the same seed.
GATHER_CASES = [("erdos_renyi", 12, "neighbor"), ("chain", 9, "dense"),
                ("star", 9, "dense"), ("ring", 8, "dense")]


@pytest.mark.parametrize("name,n,representation", GATHER_CASES)
def test_gather_equals_dense(rng, name, n, representation):
    """The table-driven gather is the same linear operator as the dense
    matmul, from a dense build's derived tables and from a matrix-free
    build's own."""
    kw = dict(seed=2, erdos_renyi_p=0.35)
    dense_topo = build_topology(name, n, **kw)
    x = rng.normal(size=(n, 5)).astype(np.float32)
    dense = make_mixing_op(dense_topo, impl="dense")
    gather = make_mixing_op(
        build_topology(name, n, impl=representation, **kw), impl="gather"
    )
    assert gather.impl == "gather"
    np.testing.assert_allclose(
        np.asarray(gather.apply(jnp.asarray(x))),
        np.asarray(dense.apply(jnp.asarray(x))),
        rtol=1e-5, atol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(gather.neighbor_sum(jnp.asarray(x))),
        np.asarray(dense.neighbor_sum(jnp.asarray(x))),
        rtol=1e-5, atol=1e-5,
    )


def test_gather_handles_trailing_dims_and_jit(rng):
    """[N]-trailing-shape variants (an [N, 1] mass column) and jit both
    work through the live-slot loop."""
    import jax

    topo = build_topology("erdos_renyi", 10, seed=4)
    gather = make_mixing_op(topo, impl="gather")
    w = rng.normal(size=(10, 1)).astype(np.float32)
    expected = topo.mixing_matrix.astype(np.float32) @ w
    np.testing.assert_allclose(
        np.asarray(jax.jit(gather.apply)(jnp.asarray(w))), expected,
        rtol=1e-5, atol=1e-6,
    )


def test_stencil_rejected_for_irregular_graph():
    topo = build_topology("erdos_renyi", 10, seed=0)
    with pytest.raises(ValueError):
        make_mixing_op(topo, impl="stencil")


def test_auto_picks_stencil_for_regular_graphs():
    assert make_mixing_op(build_topology("ring", 8)).impl == "stencil"
    assert make_mixing_op(build_topology("erdos_renyi", 8, seed=0)).impl == "dense"


# (topology, representation, N, devices of a worker mesh or None) -> the form
# the code takes, by no option: ``make_mixing_op``'s ``auto`` on one device
# or an auto mesh, ``make_halo_mixing_op`` under ``worker_mesh``. Rows that no
# other test holds (a ring and a small drawn graph: above; a matrix-free
# drawn graph and a large chain: tests/test_federated.py; the mesh's forms
# on matrix-free tables over four blocks: tests/test_worker_mesh.py's
# HALO_FORMS).
DECISION = [
    ("ring", "dense", 256, None, "stencil"),
    ("grid", "dense", 16, None, "stencil"),
    ("grid", "neighbor", 16, None, "stencil"),
    ("fully_connected", "dense", 8, None, "stencil"),
    ("directed_ring", "dense", 8, None, "stencil"),
    # a small irregular graph keeps its matrix, at any size one matmul holds
    ("chain", "dense", 16, None, "dense"),
    ("chain", "dense", 128, None, "dense"),
    ("erdos_renyi", "dense", 128, None, "dense"),
    ("directed_erdos_renyi", "dense", 12, None, "dense"),
    ("chain", "neighbor", 16, None, "gather"),
    # under a mesh the table is asked, whichever representation made it:
    # a dense build's derived tables are a ring's and a torus's too
    ("ring", "dense", 16, 8, "halo_shift"),
    ("grid", "dense", 64, 4, "halo_shift"),
    # 4 x 4 over eight devices: two workers a block, inside a grid row
    ("grid", "neighbor", 16, 8, "halo_gather"),
    ("erdos_renyi", "dense", 16, 4, "halo_gather"),
]


@pytest.mark.parametrize(
    "name,representation,n,devices,form", DECISION,
    ids=[f"{t}-{r}-{n}-" + (f"mesh{p}" if p else "one") for t, r, n, p, _ in DECISION],
)
def test_the_form_is_the_codes_choice(name, representation, n, devices, form):
    import jax
    from jax.sharding import Mesh

    from distributed_optimization_tpu.parallel.collectives import (
        make_halo_mixing_op,
    )

    topo = build_topology(
        name, n, seed=0, erdos_renyi_p=0.05 if n == 128 else 0.4,
        impl=representation,
    )
    if devices is None:
        assert make_mixing_op(topo).impl == form
    else:
        mesh = Mesh(np.array(jax.devices()[:devices]), ("workers",))
        assert make_halo_mixing_op(topo, mesh).impl == form


# The option values that no ledger line ever chose (ROADMAP C3; the kernels'
# two in PR 42, the explicit-collective stencils and the edge-list
# contraction in PR 54) are gone: a config, the operator builder and the CLI
# refuse them as they refuse any other unknown value.
REMOVED_VALUES = pytest.mark.parametrize("field,value,rule", [
    ("mixing_impl", "pallas", {}),
    ("robust_impl", "fused", dict(aggregation="trimmed_mean", robust_b=1)),
    ("mixing_impl", "shard_map", {}),
    ("mixing_impl", "sparse", {}),
], ids=["mixing_impl-pallas", "robust_impl-fused", "mixing_impl-shard_map",
        "mixing_impl-sparse"])


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


# ``halo_overlap`` (PR 54) is no field at all: the halo gather has one body.


def test_removed_field_is_no_config_argument():
    from distributed_optimization_tpu.config import ExperimentConfig

    with pytest.raises(TypeError, match="halo_overlap"):
        ExperimentConfig(halo_overlap="off")


def test_removed_field_is_no_cli_argument(capsys):
    from distributed_optimization_tpu.cli import build_parser

    with pytest.raises(SystemExit):
        build_parser().parse_args(["--halo-overlap", "off"])
    assert "unrecognized arguments: --halo-overlap" in capsys.readouterr().err


def test_a_manifest_that_carries_the_removed_field_still_loads():
    """``from_dict`` drops keys it does not know: a manifest or a checkpoint
    written before PR 54 (docs/perf/*.manifest.json hold ``halo_overlap``)
    gives the config it described."""
    from distributed_optimization_tpu.config import ExperimentConfig

    cfg = ExperimentConfig(n_workers=16, topology="ring")
    old = {**cfg.to_dict(), "halo_overlap": "off"}
    assert ExperimentConfig.from_dict(old) == cfg
