"""Hypothesis property tests for the math-critical invariants.

Broader input coverage than the example-based suites: every topology's
mixing matrix must be symmetric, row-stochastic, and average-preserving for
ANY valid (topology, N); the fault-realized matrices must keep those
properties for ANY drop probability; compression must always be a
contraction.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# Optional dep: a missing hypothesis must SKIP this module, not error the
# whole collection (listed in requirements-test.txt).
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from distributed_optimization_tpu.ops.compression import make_compressor
from distributed_optimization_tpu.parallel import build_topology
from distributed_optimization_tpu.parallel.faults import (
    metropolis_hastings_weights,
    sample_surviving_adjacency,
)

SETTINGS = dict(max_examples=25, deadline=None)


def _check_mixing_matrix(W: np.ndarray, atol: float = 1e-9):
    np.testing.assert_allclose(W, W.T, atol=atol)
    np.testing.assert_allclose(W.sum(axis=1), 1.0, atol=atol)
    assert np.all(W >= -atol)
    # Average preservation: (1/N) 1^T W x == (1/N) 1^T x for all x.
    rng = np.random.default_rng(0)
    x = rng.standard_normal((W.shape[0], 3))
    np.testing.assert_allclose((W @ x).mean(0), x.mean(0), atol=max(atol, 1e-7) * 100)


@settings(**SETTINGS)
@given(
    topology=st.sampled_from(["ring", "fully_connected", "chain", "star",
                              "erdos_renyi"]),
    n=st.integers(min_value=3, max_value=40),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_mixing_matrix_invariants(topology, n, seed):
    topo = build_topology(topology, n, erdos_renyi_p=0.5, seed=seed)
    _check_mixing_matrix(topo.mixing_matrix)
    assert 0.0 <= topo.spectral_gap <= 1.0 + 1e-9


@settings(**SETTINGS)
@given(side=st.integers(min_value=3, max_value=7))
def test_grid_mixing_matrix_invariants(side):
    topo = build_topology("grid", side * side)
    _check_mixing_matrix(topo.mixing_matrix)


@settings(**SETTINGS)
@given(
    n=st.integers(min_value=3, max_value=24),
    drop=st.floats(min_value=0.0, max_value=0.95),
    t=st.integers(min_value=0, max_value=10_000),
)
def test_fault_realized_matrix_invariants(n, drop, t):
    topo = build_topology("fully_connected", n)
    key = jax.random.fold_in(jax.random.key(9), t)
    At = sample_surviving_adjacency(
        key, jnp.asarray(topo.adjacency, dtype=jnp.float32), drop
    )
    # float32 device dtype: row sums accurate to ~1e-6.
    _check_mixing_matrix(
        np.asarray(metropolis_hastings_weights(At), dtype=np.float64),
        atol=1e-5,
    )


@settings(**SETTINGS)
@given(
    d=st.integers(min_value=2, max_value=64),
    data=st.data(),
    name=st.sampled_from(["top_k", "random_k"]),
)
def test_compression_is_contraction(d, data, name):
    k = data.draw(st.integers(min_value=1, max_value=d))
    comp = make_compressor(name, d=d, k=k)
    rng = np.random.default_rng(d * 1000 + k)
    v = jnp.asarray(rng.standard_normal((5, d)), dtype=jnp.float32)
    q = np.asarray(comp.apply(jax.random.key(0), v))
    # Contraction: ||v - Q(v)||^2 <= (1 - k/d)||v||^2 row-wise for top_k;
    # for random_k the masked-out energy is at most the total energy.
    err = np.sum((np.asarray(v) - q) ** 2, axis=1)
    total = np.sum(np.asarray(v) ** 2, axis=1)
    if name == "top_k":
        assert np.all(err <= (1 - k / d) * total + 1e-5)
    else:
        assert np.all(err <= total + 1e-6)
    assert np.all(np.count_nonzero(q, axis=1) <= k)


@settings(**SETTINGS)
@given(
    n_workers=st.integers(min_value=1, max_value=12),
    n_local=st.integers(min_value=1, max_value=40),
    batch=st.integers(min_value=1, max_value=48),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    step=st.integers(min_value=0, max_value=10_000),
    data=st.data(),
)
def test_dense_sampling_subset_identity(n_workers, n_local, batch, seed, step, data):
    """For ANY (shapes, key, step, ragged n_valid): the dense weight vectors
    select exactly the rows the gather path's top-k selects, with weight
    1/b_eff each (the structural invariant behind sampling_impl='dense')."""
    from distributed_optimization_tpu.ops.sampling import (
        _worker_keys,
        sample_batch_indices,
        sample_worker_batch_weights,
    )

    n_valid = jnp.asarray(
        [data.draw(st.integers(min_value=0, max_value=n_local))
         for _ in range(n_workers)],
        dtype=jnp.int32,
    )
    key = jax.random.key(seed)
    dense = np.asarray(
        sample_worker_batch_weights(key, step, n_valid, n_local, batch)
    )
    worker_keys = _worker_keys(key, step, n_workers)
    for i in range(n_workers):
        idx, w = sample_batch_indices(worker_keys[i], n_local, n_valid[i], batch)
        gather_rows = np.unique(np.asarray(idx)[np.asarray(w) > 0])
        dense_rows = np.nonzero(dense[i] > 0)[0]
        np.testing.assert_array_equal(np.sort(dense_rows), gather_rows)
        eff = min(batch, int(n_valid[i]), n_local)
        if eff > 0:
            np.testing.assert_allclose(dense[i][dense_rows], 1.0 / eff, rtol=1e-6)
            assert dense_rows.size == eff
            np.testing.assert_allclose(dense[i].sum(), 1.0, rtol=1e-5)
        else:
            assert dense_rows.size == 0


@settings(**SETTINGS)
@given(
    n=st.integers(min_value=3, max_value=24),
    drop=st.floats(min_value=0.0, max_value=0.95),
    t=st.integers(min_value=0, max_value=10_000),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_directed_fault_realized_matrix_invariants(n, drop, t, seed):
    """Round 5: every realized directed-fault matrix is column-stochastic
    (mass conservation — push-sum's invariant), nonnegative, supported on
    surviving base edges + diagonal, with drops INDEPENDENT per direction
    (no symmetrization)."""
    from distributed_optimization_tpu.parallel.faults import (
        column_stochastic_weights,
        sample_surviving_directed_adjacency,
    )

    topo = build_topology("directed_erdos_renyi", n, erdos_renyi_p=0.5,
                          seed=seed)
    key = jax.random.fold_in(jax.random.key(11), t)
    At = np.asarray(
        sample_surviving_directed_adjacency(
            key, jnp.asarray(topo.adjacency, dtype=jnp.float32), drop
        )
    )
    # Survivors only ever come from base edges.
    assert np.all(At <= topo.adjacency + 1e-12)
    W = np.asarray(
        column_stochastic_weights(jnp.asarray(At, dtype=jnp.float32)),
        dtype=np.float64,
    )
    np.testing.assert_allclose(W.sum(axis=0), 1.0, atol=1e-5)
    assert np.all(W >= -1e-6)
    assert np.all(W[(topo.adjacency + np.eye(n)) == 0] == 0)
    # Mass conservation through the operator itself: sum(Wx) == sum(x).
    rng = np.random.default_rng(3)
    x = rng.standard_normal((n, 2))
    np.testing.assert_allclose((W @ x).sum(0), x.sum(0), atol=1e-4)


@settings(**SETTINGS)
@given(
    topology=st.sampled_from(["chain", "star", "erdos_renyi", "ring"]),
    n=st.integers(min_value=3, max_value=32),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_gather_mixing_equals_dense_property(topology, n, seed):
    """The table-driven gather over the live slots is the same linear
    operator as the dense matmul for arbitrary undirected graphs, apply
    and neighbor_sum."""
    from distributed_optimization_tpu.ops.mixing import make_mixing_op

    topo = build_topology(topology, n, erdos_renyi_p=0.5, seed=seed)
    rng = np.random.default_rng(seed % 2**16)
    x = jnp.asarray(rng.standard_normal((n, 3)), dtype=jnp.float32)
    dense = make_mixing_op(topo, impl="dense")
    gather = make_mixing_op(topo, impl="gather")
    np.testing.assert_allclose(np.asarray(gather.apply(x)),
                               np.asarray(dense.apply(x)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gather.neighbor_sum(x)),
                               np.asarray(dense.neighbor_sum(x)),
                               rtol=1e-5, atol=1e-5)


def test_directed_drops_are_independent_per_direction():
    """The directed sampler must NOT symmetrize: on a complete directed
    graph at drop=0.5, reciprocal pairs (i,j)/(j,i) must differ in some
    realization (a regression to the undirected symmetric draw would make
    every realization symmetric)."""
    from distributed_optimization_tpu.parallel.faults import (
        sample_surviving_directed_adjacency,
    )

    n = 8
    base = jnp.asarray(np.ones((n, n)) - np.eye(n), dtype=jnp.float32)
    saw_asymmetry = False
    for t in range(10):
        key = jax.random.fold_in(jax.random.key(17), t)
        At = np.asarray(
            sample_surviving_directed_adjacency(key, base, 0.5)
        )
        if not np.array_equal(At, At.T):
            saw_asymmetry = True
            break
    assert saw_asymmetry  # P(all 10 draws symmetric) ~ 2^-280
