"""Plain L2-regularised multinomial (softmax) regression.

``w`` is one worker's [d*K] vector, the row-major flattening of a [d, K]
matrix; ``y`` holds class indices. Imports nothing of the package under test.
"""

import jax
import jax.numpy as jnp


def param_dim(n_features, config):
    return n_features * int(config["experiment"]["n_classes"])


def data_loss(w, X, y, weights, mm):
    logits = mm(X, w.reshape(X.shape[-1], -1))
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    true = jnp.take_along_axis(logits, y.astype(jnp.int32)[:, None], axis=-1)[:, 0]
    return jnp.sum(weights * (lse - true))


def gradient(w, X, y, weights, lam, mm):
    W = w.reshape(X.shape[-1], -1)
    P = jax.nn.softmax(mm(X, W), axis=-1)
    Y = jax.nn.one_hot(y.astype(jnp.int32), W.shape[1], dtype=P.dtype)
    G = mm(X.T, weights[:, None] * (P - Y)) + lam * W
    return G.reshape(-1)
