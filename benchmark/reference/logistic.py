"""Plain L2-regularised logistic regression, labels in {-1, +1}.

Written from the textbook form; imports nothing of the package under test.
``w`` is one worker's parameter vector [d]; ``X`` [L, d]; ``y`` [L];
``weights`` [L] sums to 1 over the rows that count.
"""

import jax
import jax.numpy as jnp


def param_dim(n_features, config):
    return n_features


def data_loss(w, X, y, weights, mm):
    margins = y * mm(X, w)
    return jnp.sum(weights * jnp.logaddexp(0.0, -margins))


def gradient(w, X, y, weights, lam, mm):
    margins = y * mm(X, w)
    coeff = weights * (-y) * jax.nn.sigmoid(-margins)
    return mm(X.T, coeff) + lam * w
