"""Objective and gradient kernels (pure JAX, jit/vmap/grad-compatible).

Capability parity with the reference's objective library (reference
``obj_problems.py:3-69``): L2-regularized logistic regression with the
numerically stable ``max(0, -z) + log1p(exp(-|z|))`` formulation, and
L2-regularized least squares ("quadratic"). Both come in two forms:

- the *plain* form matching the reference signature ``f(w, X, y, reg)``, used
  by the numpy fidelity backend and parity tests;
- a *weighted* form taking per-sample weights, which is what the TPU path uses:
  static shapes + a weight vector subsume the reference's dynamic empty-batch /
  short-batch guards (reference ``obj_problems.py:4,14,40,47``,
  ``worker.py:17-23``) without data-dependent control flow, so everything
  stays traceable under ``jit``/``scan``.

All functions are closed-form (no autodiff needed at runtime), but tests check
them against ``jax.grad`` of the objectives and finite differences.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from distributed_optimization_tpu.config import DEFAULT_HUBER_DELTA


def _softplus_neg(z: jax.Array) -> jax.Array:
    """log(1 + exp(-z)) computed stably as max(0, -z) + log1p(exp(-|z|))."""
    return jnp.maximum(0.0, -z) + jnp.log1p(jnp.exp(-jnp.abs(z)))


# ---------------------------------------------------------------------------
# The scalar-output GLMs, each stated ONCE as its per-row pair on the margin
# z = xᵀw:  loss φ(z, y)  and  coeff ψ(z, y) = ∂φ/∂z.  Every kernel of a
# family is the same four lines over its pair (``MarginLink``):
#
#   f(w)  = mean_i φ(z_i, y_i) + (λ/2)‖w‖²        ∇f(w) = Xᵀψ(z, y)/n + λw
#   weighted:  Σ_i weights_i·φ(z_i, y_i) + (λ/2)‖w‖²,  Xᵀ(weights·ψ(z, y)) + λw
#
# so a caller that already holds the margins (the jax scan carries X·x from
# the eval's pass over X to the next step, ``paired_margins`` below) asks for
# the gradient at them (``gradient_at``) and reads the shard once less; on a
# TPU ``ops.pallas_kernels.glm_shard_visit`` takes the pair's two functions
# into one kernel that reads it once in all.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MarginLink:
    """One GLM family: ``loss(z, y)`` and ``coeff(z, y)`` per row of the
    margin ``z = X @ w``, and the kernels every family shares over them."""

    loss: Callable[[jax.Array, jax.Array], jax.Array]
    coeff: Callable[[jax.Array, jax.Array], jax.Array]

    def objective(self, w, X, y, lam):
        """Full-batch mean objective (reference obj_problems.py:3-11, 39-44)."""
        return jnp.mean(self.loss(X @ w, y)) + 0.5 * lam * jnp.dot(w, w)

    def gradient(self, w, X, y, lam):
        """Mean gradient over the given rows (reference obj_problems.py:13-20,
        46-53; applied to a full shard, its dead full-gradient code)."""
        return X.T @ self.coeff(X @ w, y) / X.shape[0] + lam * w

    def objective_weighted(self, w, X, y, weights, lam):
        """Σ_i weights_i·loss_i + (λ/2)‖w‖². With ``weights = mask / count``
        the reference's mean over the valid rows; with all-zero weights the
        pure regularizer (the reference returns 0.0 for an empty batch,
        obj_problems.py:4-5; the sampling layer guarantees nonempty batches
        whenever a worker has data)."""
        return jnp.sum(weights * self.loss(X @ w, y)) + 0.5 * lam * jnp.dot(w, w)

    def gradient_at(self, z, w, X, y, weights, lam):
        """The weighted gradient at ``w`` given its margins ``z = X @ w``."""
        return X.T @ (weights * self.coeff(z, y)) + lam * w

    def gradient_weighted(self, w, X, y, weights, lam):
        return self.gradient_at(X @ w, w, X, y, weights, lam)


def paired_margins(X: jax.Array, x: jax.Array, xbar: jax.Array):
    """``(X·x_i, X·x̄)`` for every worker's shard in ONE read of the stack:
    X ``[N, L, d]``, x ``[N, d]`` (each worker's own model), x̄ ``[d]`` (one
    model for all) -> two ``[N, L]`` margin arrays. One reduction over d with
    two accumulators (a single variadic ``reduce``), so the bandwidth-bound
    pass over X is paid once for both; two dots side by side are two reads.
    Elementwise products in at least f32: no matmul precision applies."""
    acc = jnp.promote_types(X.dtype, jnp.float32)
    Xa = X.astype(acc)
    zero = np.zeros((), acc)
    z, zbar = jax.lax.reduce(
        (Xa * x.astype(acc)[:, None, :], Xa * xbar.astype(acc)[None, None, :]),
        (zero, zero),
        lambda a, b: (a[0] + b[0], a[1] + b[1]),
        dimensions=(2,),
    )
    return z.astype(X.dtype), zbar.astype(X.dtype)


# Logistic regression (convex), labels in {-1, +1}:
#   φ = log(1 + exp(−y z)),  ψ = −y σ(−y z)
LOGISTIC = MarginLink(
    loss=lambda z, y: _softplus_neg(y * z),
    coeff=lambda z, y: -y * jax.nn.sigmoid(-(y * z)),
)
logistic_objective = LOGISTIC.objective
logistic_gradient = LOGISTIC.gradient
logistic_objective_weighted = LOGISTIC.objective_weighted
logistic_gradient_weighted = LOGISTIC.gradient_weighted

# Quadratic / least squares (strongly convex):  φ = ½(z − y)²,  ψ = z − y
QUADRATIC = MarginLink(
    loss=lambda z, y: 0.5 * (z - y) ** 2,
    coeff=lambda z, y: z - y,
)
quadratic_objective = QUADRATIC.objective
quadratic_gradient = QUADRATIC.gradient
quadratic_objective_weighted = QUADRATIC.objective_weighted
quadratic_gradient_weighted = QUADRATIC.gradient_weighted

# ---------------------------------------------------------------------------
# Huber regression (convex, robust):  φ = H_δ(z − y),  ψ = clip(z − y, −δ, δ),
#   H_δ(r) = ½r² for |r| ≤ δ, else δ(|r| − ½δ)
#
# Not in the reference — the framework's third objective family: a robust
# regression between the study's two (quadratic tails hurt under the heavy
# noise make_regression injects; Huber caps the per-sample gradient at δ‖x‖).
# δ defaults to the synthetic data's noise scale (make_regression noise=10.0,
# utils/data.py), i.e. the transition sits at ~1σ of the residuals at the
# optimum — the classical choice — and is configurable
# (``ExperimentConfig.huber_delta``) because it is data-scale-dependent; the
# single source of the default is config.DEFAULT_HUBER_DELTA. Closed forms
# only: the coefficient is smooth everywhere (H_δ is C¹).
# ---------------------------------------------------------------------------

# Backward-compatible alias; the definition lives in config (jax-free) so the
# numpy twins and the C-ABI default share it without importing this module.
HUBER_DELTA = DEFAULT_HUBER_DELTA


def _huber(r: jax.Array, delta: float) -> jax.Array:
    a = jnp.abs(r)
    return jnp.where(a <= delta, 0.5 * r * r, delta * (a - 0.5 * delta))


@functools.lru_cache(maxsize=None)
def huber_link(delta: float) -> MarginLink:
    """The Huber pair with the transition bound to ``delta``; cached, so a
    given δ is always the SAME object (jit static arguments stay identical)."""
    return MarginLink(
        loss=lambda z, y: _huber(z - y, delta),
        coeff=lambda z, y: jnp.clip(z - y, -delta, delta),
    )


def huber_objective(w, X, y, lam, delta=DEFAULT_HUBER_DELTA):
    return huber_link(delta).objective(w, X, y, lam)


def huber_gradient(w, X, y, lam, delta=DEFAULT_HUBER_DELTA):
    return huber_link(delta).gradient(w, X, y, lam)


def huber_objective_weighted(w, X, y, weights, lam, delta=DEFAULT_HUBER_DELTA):
    return huber_link(delta).objective_weighted(w, X, y, weights, lam)


def huber_gradient_weighted(w, X, y, weights, lam, delta=DEFAULT_HUBER_DELTA):
    return huber_link(delta).gradient_weighted(w, X, y, weights, lam)


# ---------------------------------------------------------------------------
# Multinomial (softmax) logistic regression (convex):
#   f(W) = mean_i [logsumexp(x_i^T W) − (x_i^T W)_{y_i}] + (λ/2)‖W‖_F²,
#   W ∈ R^{d×K}, labels y_i ∈ {0, …, K−1}
#
# Not in the reference (its GLMs are scalar-output, reference
# obj_problems.py:3-69) — this is the framework's COMPUTE-BOUND tier: the
# scalar GLM gradients are matvecs (arithmetic intensity O(1), forever
# HBM-bound on TPU), while the softmax forward X @ W [b,K] and backward
# X^T @ (P − Y) [d,K] are real matmuls with 2·b·d·K FLOPs each that tile
# onto the MXU. docs/PERF.md §compute-bound measures the MFU this family
# reaches where the toy tier cannot.
#
# Rank-polymorphic in the parameter: a [d, K] matrix in gives a [d, K]
# gradient out (what the jax scan carries — parameter shape inside the scan,
# flat at the boundary, models/base.Problem.param_shape), a flat [d·K] vector
# in gives a flat vector out (the numpy/C++ tiers' layout, ``run_batch``, the
# event scan, the benchmark's reference). ``w.reshape(d, -1)`` is the
# identity on a matrix, so the matrix path has no relayout in it. K is
# inferred from the static shapes (w.size / X.shape[-1]), so the kernels need
# no bound class count.
# ---------------------------------------------------------------------------


def sq_norm(w: jax.Array) -> jax.Array:
    """‖w‖² of a parameter of either form (Frobenius for a matrix)."""
    return jnp.dot(w, w) if w.ndim == 1 else jnp.sum(w * w)


def _softmax_ce(logits: jax.Array, y: jax.Array) -> jax.Array:
    """Per-sample cross-entropy: logsumexp(logits) − logits[y] (stable)."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    true = jnp.take_along_axis(
        logits, y.astype(jnp.int32)[:, None], axis=-1
    )[:, 0]
    return lse - true


def softmax_objective(w: jax.Array, X: jax.Array, y: jax.Array, lam: float) -> jax.Array:
    logits = X @ w.reshape(X.shape[-1], -1)
    return jnp.mean(_softmax_ce(logits, y)) + 0.5 * lam * sq_norm(w)


def softmax_gradient(w: jax.Array, X: jax.Array, y: jax.Array, lam: float) -> jax.Array:
    W = w.reshape(X.shape[-1], -1)
    logits = X @ W
    P = jax.nn.softmax(logits, axis=-1)
    Y = jax.nn.one_hot(y.astype(jnp.int32), W.shape[1], dtype=X.dtype)
    G = X.T @ (P - Y) / X.shape[0] + lam * W
    return G.reshape(w.shape)


def softmax_objective_weighted(
    w: jax.Array, X: jax.Array, y: jax.Array, weights: jax.Array, lam: float
) -> jax.Array:
    logits = X @ w.reshape(X.shape[-1], -1)
    return jnp.sum(weights * _softmax_ce(logits, y)) + 0.5 * lam * sq_norm(w)


def softmax_gradient_weighted(
    w: jax.Array, X: jax.Array, y: jax.Array, weights: jax.Array, lam: float
) -> jax.Array:
    W = w.reshape(X.shape[-1], -1)
    logits = X @ W
    P = jax.nn.softmax(logits, axis=-1)
    Y = jax.nn.one_hot(y.astype(jnp.int32), W.shape[1], dtype=X.dtype)
    G = X.T @ (weights[:, None] * (P - Y)) + lam * W
    return G.reshape(w.shape)


def batch_weights(mask: jax.Array) -> jax.Array:
    """Turn a validity mask into mean-weights: mask / max(1, sum(mask)).

    Encodes the reference's "effective batch = min(b, n_local)" semantics
    (reference worker.py:21) without dynamic shapes: invalid rows get weight 0
    and valid rows 1/count, so the weighted sum is the mean over valid rows.
    """
    count = jnp.sum(mask)
    return mask / jnp.maximum(count, 1.0)
