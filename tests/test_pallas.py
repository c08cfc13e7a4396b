"""Pallas kernel tests (interpreter mode on CPU — same code path Mosaic
compiles on real TPU).

Equivalence oracle: the dense mixing matrix (the reference's own W,
reference ``trainer.py:91-136``).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import assert_ulps_of_scale

from distributed_optimization_tpu.backends import jax_backend
from distributed_optimization_tpu.config import ExperimentConfig
from distributed_optimization_tpu.ops import losses
from distributed_optimization_tpu.ops import pallas_kernels as pk
from distributed_optimization_tpu.ops.mixing import make_mixing_op
from distributed_optimization_tpu.parallel import build_topology
from distributed_optimization_tpu.utils.data import generate_synthetic_dataset
from distributed_optimization_tpu.utils.oracle import compute_reference_optimum


@pytest.fixture
def x(rng):
    return jnp.asarray(rng.standard_normal((8, 12)), dtype=jnp.float32)


def test_ring_mix_matches_dense_W(x):
    topo = build_topology("ring", 8)
    want = topo.mixing_matrix @ np.asarray(x, dtype=np.float64)
    got = np.asarray(pk.ring_mix(x))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_fc_mix_matches_dense_W(x):
    topo = build_topology("fully_connected", 8)
    want = topo.mixing_matrix @ np.asarray(x, dtype=np.float64)
    got = np.asarray(pk.fc_mix(x))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_fused_step_equals_mix_then_step(x, rng):
    g = jnp.asarray(rng.standard_normal(x.shape), dtype=jnp.float32)
    eta = 0.07
    got = np.asarray(pk.fused_ring_dsgd_step(x, g, eta))
    want = np.asarray(pk.ring_mix(x)) - eta * np.asarray(g)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


def test_mixing_op_pallas_ring_and_fc(x):
    for name in ("ring", "fully_connected"):
        topo = build_topology(name, 8)
        op = make_mixing_op(topo, impl="pallas")
        assert op.impl == "pallas"
        np.testing.assert_allclose(
            np.asarray(op.apply(x)),
            topo.mixing_matrix @ np.asarray(x, dtype=np.float64),
            rtol=1e-5, atol=1e-6,
        )
        # Direct roll/sum kernels — exact to fp32 accumulation.
        np.testing.assert_allclose(
            np.asarray(op.neighbor_sum(x)),
            topo.adjacency @ np.asarray(x, dtype=np.float64),
            rtol=1e-5, atol=1e-6,
        )


def test_pallas_rejects_unsupported_topology():
    with pytest.raises(ValueError, match="pallas mixing supports"):
        make_mixing_op(build_topology("grid", 9), impl="pallas")


def test_end_to_end_run_with_pallas_mixing():
    cfg = ExperimentConfig(
        n_workers=8, n_samples=320, n_features=8, n_informative_features=4,
        n_iterations=200, local_batch_size=8, problem_type="quadratic",
        algorithm="dsgd", topology="ring", mixing_impl="pallas",
        eval_every=20,
    )
    ds = generate_synthetic_dataset(cfg)
    _, f_opt = compute_reference_optimum(ds, cfg.reg_param)
    pallas_run = jax_backend.run(cfg, ds, f_opt, use_mesh=False)
    stencil_run = jax_backend.run(
        cfg.replace(mixing_impl="stencil"), ds, f_opt, use_mesh=False
    )
    # Identical batches (same counter-keyed RNG) => identical trajectories.
    np.testing.assert_allclose(
        pallas_run.history.objective, stencil_run.history.objective,
        rtol=1e-4, atol=1e-6,
    )
    np.testing.assert_allclose(
        pallas_run.final_models, stencil_run.final_models,
        rtol=1e-4, atol=1e-6,
    )


# --- the shard visit (ISSUE 41): one read of a GLM's shards ----------------

LINKS = {
    "logistic": losses.LOGISTIC, "quadratic": losses.QUADRATIC,
    "huber": losses.huber_link(1.0),
}
# (workers, rows, features): one block narrower than a strip of 128 lanes; a
# full strip and a tail at the study's shard (L = 53 is no multiple of the 8
# sublanes); more workers than a block of 512, the last block ragged.
VISITS = {
    "under_a_strip": (8, 7, 10), "strip_and_tail": (200, 53, 81),
    "ragged_last_block": (700, 13, 9),
}


def visit_case(rng, n, rows, d, dtype):
    """A stack with ragged ``n_valid`` (an empty shard among them), batch
    weights on a third of the valid rows."""
    X = jnp.asarray(rng.standard_normal((n, rows, d)), dtype)
    y = jnp.asarray(rng.choice([-1.0, 1.0], size=(n, rows)), dtype)
    x = jnp.asarray(0.3 * rng.standard_normal((n, d)), dtype)
    n_valid = rng.integers(1, rows + 1, size=n)
    n_valid[0] = 0
    valid = np.arange(rows)[None, :] < n_valid[:, None]
    drawn = valid & (rng.uniform(size=(n, rows)) < 1 / 3)
    wts = jnp.asarray(drawn / np.maximum(drawn.sum(1, keepdims=True), 1), dtype)
    return X, y, x, jnp.mean(x, axis=0), wts, jnp.asarray(n_valid, jnp.int32)


def two_passes(link, X, y, x, xbar, wts, n_valid):
    """What XLA runs where the visit does not: the paired margins, the
    gradient at them, the loss at x̄ over a worker's real rows."""
    z, zbar = losses.paired_margins(X, x, xbar)
    g = jax.vmap(link.gradient_at, in_axes=(0, 0, 0, 0, 0, None))(
        z, x, X, y, wts, 0.0
    )
    valid = jnp.arange(X.shape[1])[None, :] < n_valid[:, None]
    return g, jnp.sum(valid * link.loss(zbar, y), axis=1)


VISIT_ULPS = 64  # of each result's scale: the sum over d runs in another order


@pytest.mark.parametrize("dtype,visit", [
    ("float64", "under_a_strip"), ("float64", "strip_and_tail"),
    ("float64", "ragged_last_block"), ("float32", "strip_and_tail"),
])
@pytest.mark.parametrize("family", sorted(LINKS))
def test_shard_visit_is_the_two_passes(family, dtype, visit, rng):
    """g and the objective's partials from ONE visit are the gradient at the
    paired margins and the loss at x̄ over the real rows: to 1e-12 of their
    scale in f64 (64 units of 2.2e-16), as tight in f32's own units. Zero-weight and padding rows give nothing;
    lanes past N in a ragged last block reach no result."""
    with jax.enable_x64(dtype == "float64"):
        case = visit_case(rng, *VISITS[visit], dtype)
        g, f = jax.jit(functools.partial(pk.glm_shard_visit, LINKS[family]))(*case)
        want_g, want_f = two_passes(LINKS[family], *case)
        assert g.shape == want_g.shape and f.shape == want_f.shape
        assert g.dtype == f.dtype == jnp.dtype(dtype)
        assert_ulps_of_scale(g, want_g, VISIT_ULPS)
        assert_ulps_of_scale(f, want_f, VISIT_ULPS)
        assert float(jnp.max(jnp.abs(g[0]))) == 0.0 == float(f[0])  # empty shard


def test_shard_visit_block_is_sized_by_the_budget():
    """The block's width is derived: the constant where the workers fill it,
    the workers where they do not, narrower where the shard is long, and
    none where 128 workers' shards do not fit the budget twice."""
    assert pk.shard_visit_lanes(1 << 18, 53, 81) == pk.SHARD_VISIT_LANES
    assert pk.shard_visit_lanes(100, 53, 81) == 100
    rows = 8 * (pk.SHARD_VISIT_VMEM_BYTES // (2 * 81 * 384 * 4 * 8))
    assert pk.shard_visit_lanes(1 << 18, rows, 81) == 256
    assert pk.shard_visit_lanes(1 << 18, 100_000, 81) is None
    X = jnp.zeros((4, 100_000, 81), jnp.float32)
    with pytest.raises(ValueError, match="VMEM budget"):
        pk.glm_shard_visit(
            LINKS["logistic"], X, X[:, :, 0], X[:, 0], X[0, 0], X[:, :, 0],
            jnp.zeros(4, jnp.int32),
        )
