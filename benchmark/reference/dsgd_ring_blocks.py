"""Plain D-SGD on a ring for more workers than one device's memory holds:
``dsgd_ring``'s equations, term for term, with the shards held in blocks of
workers. Straightforward ``jax.numpy``, float32, matmuls at ``highest``; no
kernels, no scan, no ``shard_map``, no collective, nothing imported from the
package under test. The batches and the matmul are ``dsgd_ring.py``'s.

One iteration, for every worker i at once (``dsgd_ring.py`` has the
derivation):

    g_i   = grad f_i(x_i; batch_i(t)) + lam * x_i
    x_i'  = (x_{i-1} + x_i + x_{i+1}) / 3  -  eta0 / sqrt(t + 1) * g_i

What differs is where things lie. The state ``[N, D]`` is whole on the first
device, and there the ring is mixed by a ``jnp.roll`` over all N rows and the
batches are drawn for all N workers. The shards ``[N, L, d]`` never exist as
one array on any device: they are cut into ``reference_blocks`` (the
configuration's) blocks of consecutive workers, each sent from the host by a
plain ``jax.device_put`` to one of the devices in turn, and small enough to
go as one copy under the TPU runtime's 2**32-byte slow path. An iteration
sends each block's rows of the state and of the batch weights to the block's
device, computes the block's gradients there, brings them back and puts them
side by side; an evaluation sends the mean model out and brings the
per-worker losses back, so the sums run over all N on one device, as
``dsgd_ring`` has them.

``precision`` as in ``dsgd_ring``: ``reference``, or ``bfloat16`` (state,
shards and matmul operands rounded to bfloat16), the control the limits are
shown to fail.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.dsgd_ring import PRECISIONS, _make_mm, batch_weights


def run(config, traffic, X, y, seed, precision="reference"):
    """Follow one experiment's first ``check_iterations``. ``X`` [N*L, d] and
    ``y`` [N*L] are the host arrays the program was given, worker after worker.
    Returns host arrays ``objective`` and ``consensus``, one row per evaluation
    up to there."""
    exp = config["experiment"]
    if exp["topology"] != "ring" or exp["algorithm"] != "dsgd":
        raise ValueError("dsgd_ring_blocks reference covers D-SGD on a ring only")
    problem = importlib.import_module(f"benchmark.reference.{exp['problem_type']}")
    prec = PRECISIONS[precision]
    mm = _make_mm(prec["operand"])
    state_dtype = prec["state"]
    N = int(exp["n_workers"])
    L, d = X.shape[0] // N, X.shape[1]
    X, y = X.reshape(N, L, d), y.reshape(N, L)
    D = problem.param_dim(d, config)
    T = int(traffic.get("check_iterations", traffic["n_iterations"]))
    eval_every = int(traffic["eval_every"])
    b = int(exp["local_batch_size"])
    eta0 = float(exp["learning_rate_eta0"])
    lam = float(exp["l2_regularization_lambda"])
    n_blocks = int(config["reference_blocks"])
    if N % n_blocks:
        raise ValueError(f"{N} workers do not cut into {n_blocks} equal blocks")
    S = N // n_blocks

    devices = jax.devices()
    home = devices[0]
    blocks = []  # (rows of the state, device, shards there, labels there)
    for k in range(n_blocks):
        rows, dev = slice(k * S, (k + 1) * S), devices[k % len(devices)]
        Xk = jax.device_put(X[rows], dev)
        if state_dtype != jnp.float32:
            Xk = Xk.astype(state_dtype)
        blocks.append((rows, dev, Xk, jax.device_put(y[rows], dev)))

    def per_worker(fn, *args):
        return jax.lax.map(lambda a: fn(*a), args, batch_size=S)

    @jax.jit
    def weights(t):
        return batch_weights(seed, t, N, L, b)

    # A block's data are arguments, never captured (see dsgd_ring).
    @jax.jit
    def block_gradients(xk, wk, Xk, yk):
        return per_worker(
            lambda xi, Xi, yi, wi: problem.gradient(
                xi.astype(jnp.float32), Xi, yi, wi, lam, mm),
            xk, Xk, yk, wk,
        )

    @jax.jit
    def update(x, g, t):
        xf = x.astype(jnp.float32)
        mixed = (jnp.roll(xf, 1, axis=0) + xf + jnp.roll(xf, -1, axis=0)) / 3.0
        eta = eta0 / jnp.sqrt(t.astype(jnp.float32) + 1.0)
        return (mixed - eta * g).astype(state_dtype)

    @jax.jit
    def mean_model(x):
        return jnp.mean(x.astype(jnp.float32), axis=0)

    @jax.jit
    def block_losses(xbar, Xk, yk):
        even = jnp.full((L,), 1.0 / (N * L), jnp.float32)
        return per_worker(
            lambda Xi, yi: problem.data_loss(xbar, Xi, yi, even, mm), Xk, yk)

    @jax.jit
    def totals(x, xbar, losses):
        xf = x.astype(jnp.float32)
        objective = jnp.sum(losses) + 0.5 * lam * jnp.dot(xbar, xbar)
        consensus = jnp.mean(jnp.sum((xf - xbar[None, :]) ** 2, axis=1))
        return objective, consensus

    def gathered(parts):
        """The blocks' results side by side on the state's device."""
        return jnp.concatenate([jax.device_put(p, home) for p in parts])

    x = jnp.zeros((N, D), state_dtype, device=home)
    objective, consensus = [], []
    for t in range(T):
        tt = jax.device_put(np.int32(t), home)
        w = weights(tt)
        g = gathered([
            block_gradients(
                jax.device_put(x[rows], dev), jax.device_put(w[rows], dev), Xk, yk)
            for rows, dev, Xk, yk in blocks
        ])
        x = update(x, g, tt)
        if (t + 1) % eval_every == 0:
            xbar = mean_model(x)
            losses = gathered([
                block_losses(jax.device_put(xbar, dev), Xk, yk)
                for _, dev, Xk, yk in blocks
            ])
            o, c = totals(x, xbar, losses)
            objective.append(o)
            consensus.append(c)
    return {
        "objective": np.asarray(jnp.stack(objective), dtype=np.float64),
        "consensus": np.asarray(jnp.stack(consensus), dtype=np.float64),
    }
