"""Pallas kernel tests (interpreter mode on CPU — same code path Mosaic
compiles on real TPU): the shard visit against XLA's two passes, the visit
without its objective half against the visit, and how a call decides between
the interpreter and Mosaic.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import assert_ulps_of_scale

from distributed_optimization_tpu.ops import losses
from distributed_optimization_tpu.ops import pallas_kernels as pk


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


# A full strip and a lane tail of two at a width an interpreted compile is
# quick at (the visit's own cases hold the study's shard), and the ragged
# last block.
GRADIENTS = {"strip_and_tail": (130, 13, 9),
             "ragged_last_block": VISITS["ragged_last_block"]}


@pytest.mark.parametrize("visit", sorted(GRADIENTS))
@pytest.mark.parametrize("family", ["logistic", "quadratic"])
def test_shard_gradient_is_the_visits_g_to_the_bit(family, visit, rng):
    """The kernel without its objective half (ISSUE 51) makes the visit's g
    itself: the same products and sums in the same order, so equal to the
    bit, under a ragged ``n_valid`` (which it never reads: the weights are 0
    on the padding), with a lane tail and with a ragged last block."""
    X, y, x, xbar, wts, n_valid = visit_case(rng, *GRADIENTS[visit], "float32")
    link = LINKS[family]
    g, _ = jax.jit(functools.partial(pk.glm_shard_visit, link))(
        X, y, x, xbar, wts, n_valid)
    alone = jax.jit(functools.partial(pk.glm_shard_gradient, link))(X, y, x, wts)
    assert alone.shape == g.shape and alone.dtype == g.dtype
    np.testing.assert_array_equal(np.asarray(alone), np.asarray(g))
    assert float(jnp.max(jnp.abs(alone[0]))) == 0.0  # the empty shard


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


# --- interpreter or Mosaic: what a call reads ------------------------------

def test_resolve_interpret_explicit_override_wins():
    x = jnp.zeros((4, 4))
    assert pk.resolve_interpret(x, interpret=True) is True
    assert pk.resolve_interpret(x, interpret=False) is False


def test_resolve_interpret_uses_committed_platform():
    """On this CPU-only container every committed array lives on cpu, and
    the resolver must read THAT (not the global devices list) — including
    under an explicit jax.default_device scope, in BOTH forms jax
    accepts (a Device object and a platform string — the latter leaves a
    plain str in jax.config.jax_default_device)."""
    x = jax.device_put(jnp.zeros((4, 4)), jax.devices("cpu")[0])
    assert pk.resolve_interpret(x) is True
    with jax.default_device(jax.devices("cpu")[0]):
        assert pk.resolve_interpret(None) is True
    with jax.default_device("cpu"):
        assert pk.resolve_interpret(None) is True


def test_resolve_interpret_handles_tracers():
    """Inside jit the operand is a tracer with no committed device; the
    resolver must fall back to the ambient platform instead of raising."""
    seen = {}

    @jax.jit
    def probe(x):
        seen["interp"] = pk.resolve_interpret(x)
        return x

    probe(jnp.zeros((2, 2)))
    assert seen["interp"] is True  # cpu container
