"""Plain L2-regularised least squares, real targets.

Written from the textbook form; imports nothing of the package under test.
``w`` is one worker's parameter vector [d]; ``X`` [L, d]; ``y`` [L];
``weights`` [L] sums to 1 over the rows that count.
"""

import jax.numpy as jnp


def param_dim(n_features, config):
    return n_features


def data_loss(w, X, y, weights, mm):
    residual = mm(X, w) - y
    return jnp.sum(weights * 0.5 * residual * residual)


def gradient(w, X, y, weights, mu, mm):
    residual = mm(X, w) - y
    return mm(X.T, weights * residual) + mu * w
