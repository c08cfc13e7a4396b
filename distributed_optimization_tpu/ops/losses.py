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

import jax
import jax.numpy as jnp

from distributed_optimization_tpu.config import DEFAULT_HUBER_DELTA


def _softplus_neg(z: jax.Array) -> jax.Array:
    """log(1 + exp(-z)) computed stably as max(0, -z) + log1p(exp(-|z|))."""
    return jnp.maximum(0.0, -z) + jnp.log1p(jnp.exp(-jnp.abs(z)))


# ---------------------------------------------------------------------------
# Logistic regression (convex):  f(w) = mean_i log(1+exp(-y_i x_i^T w)) + (λ/2)‖w‖²
# ---------------------------------------------------------------------------


def logistic_objective(w: jax.Array, X: jax.Array, y: jax.Array, lam: float) -> jax.Array:
    """Full-batch logistic objective. Parity: reference obj_problems.py:3-11."""
    margins = y * (X @ w)
    data_loss = jnp.mean(_softplus_neg(margins))
    return data_loss + 0.5 * lam * jnp.dot(w, w)


def logistic_gradient(w: jax.Array, X: jax.Array, y: jax.Array, lam: float) -> jax.Array:
    """Mini-batch (or full-batch) logistic gradient.

    Parity: reference obj_problems.py:13-20 (stochastic) and, applied to a full
    shard, obj_problems.py:22-36 (the reference's dead full-gradient code).
    """
    margins = y * (X @ w)
    coeff = -y * jax.nn.sigmoid(-margins)  # d/dlogit of the loss, per sample
    return X.T @ coeff / X.shape[0] + lam * w


def logistic_objective_weighted(
    w: jax.Array, X: jax.Array, y: jax.Array, weights: jax.Array, lam: float
) -> jax.Array:
    """Weighted logistic objective: sum_i weights_i * loss_i + (λ/2)‖w‖².

    With ``weights = mask / count`` this equals the reference's mean over the
    valid rows; with all-zero weights it degrades to the pure regularizer
    (reference returns 0.0 for an empty batch, obj_problems.py:4-5 — the
    regularizer-only value is used here instead so the function stays smooth;
    the sampling layer guarantees nonempty batches whenever a worker has data).
    """
    margins = y * (X @ w)
    data_loss = jnp.sum(weights * _softplus_neg(margins))
    return data_loss + 0.5 * lam * jnp.dot(w, w)


def logistic_gradient_weighted(
    w: jax.Array, X: jax.Array, y: jax.Array, weights: jax.Array, lam: float
) -> jax.Array:
    margins = y * (X @ w)
    coeff = weights * (-y) * jax.nn.sigmoid(-margins)
    return X.T @ coeff + lam * w


# ---------------------------------------------------------------------------
# Quadratic / least squares (strongly convex):
#   f(w) = ½ mean_i (x_i^T w − y_i)² + (μ/2)‖w‖²
# ---------------------------------------------------------------------------


def quadratic_objective(w: jax.Array, X: jax.Array, y: jax.Array, mu: float) -> jax.Array:
    """Parity: reference obj_problems.py:39-44."""
    residuals = X @ w - y
    return 0.5 * jnp.mean(residuals**2) + 0.5 * mu * jnp.dot(w, w)


def quadratic_gradient(w: jax.Array, X: jax.Array, y: jax.Array, mu: float) -> jax.Array:
    """Parity: reference obj_problems.py:46-53 (and dead code 55-69)."""
    residuals = X @ w - y
    return X.T @ residuals / X.shape[0] + mu * w


def quadratic_objective_weighted(
    w: jax.Array, X: jax.Array, y: jax.Array, weights: jax.Array, mu: float
) -> jax.Array:
    residuals = X @ w - y
    return 0.5 * jnp.sum(weights * residuals**2) + 0.5 * mu * jnp.dot(w, w)


def quadratic_gradient_weighted(
    w: jax.Array, X: jax.Array, y: jax.Array, weights: jax.Array, mu: float
) -> jax.Array:
    residuals = X @ w - y
    return X.T @ (weights * residuals) + mu * w


# ---------------------------------------------------------------------------
# Huber regression (convex, robust):
#   f(w) = mean_i H_δ(x_i^T w − y_i) + (λ/2)‖w‖²,
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
# only: the gradient coefficient is clip(r, −δ, δ), smooth everywhere
# (H_δ is C¹).
# ---------------------------------------------------------------------------

# Backward-compatible alias; the definition lives in config (jax-free) so the
# numpy twins and the C-ABI default share it without importing this module.
HUBER_DELTA = DEFAULT_HUBER_DELTA


def _huber(r: jax.Array, delta: float) -> jax.Array:
    a = jnp.abs(r)
    return jnp.where(a <= delta, 0.5 * r * r, delta * (a - 0.5 * delta))


def huber_objective(
    w: jax.Array, X: jax.Array, y: jax.Array, lam: float,
    delta: float = DEFAULT_HUBER_DELTA,
) -> jax.Array:
    r = X @ w - y
    return jnp.mean(_huber(r, delta)) + 0.5 * lam * jnp.dot(w, w)


def huber_gradient(
    w: jax.Array, X: jax.Array, y: jax.Array, lam: float,
    delta: float = DEFAULT_HUBER_DELTA,
) -> jax.Array:
    r = X @ w - y
    coeff = jnp.clip(r, -delta, delta)
    return X.T @ coeff / X.shape[0] + lam * w


def huber_objective_weighted(
    w: jax.Array, X: jax.Array, y: jax.Array, weights: jax.Array, lam: float,
    delta: float = DEFAULT_HUBER_DELTA,
) -> jax.Array:
    r = X @ w - y
    return jnp.sum(weights * _huber(r, delta)) + 0.5 * lam * jnp.dot(w, w)


def huber_gradient_weighted(
    w: jax.Array, X: jax.Array, y: jax.Array, weights: jax.Array, lam: float,
    delta: float = DEFAULT_HUBER_DELTA,
) -> jax.Array:
    r = X @ w - y
    coeff = weights * jnp.clip(r, -delta, delta)
    return X.T @ coeff + lam * w


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
