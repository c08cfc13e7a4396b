"""Plain D-SGD on a toroidal grid for more workers than one device's memory
holds: ``dsgd_ring_blocks``'s equations and blocks, term for term, with ONE
line different, the mixing. Straightforward ``jax.numpy``, float32, matmuls
at ``highest``; no kernels, no scan, no ``shard_map``, no collective, no
neighbor table, nothing imported from the package under test. The batches,
the matmul and the precisions are ``dsgd_ring.py``'s.

One iteration, for the worker at grid row r and column c of an R x C torus
(worker i = r * C + c, row-major, as networkx ``grid_2d_graph``'s nodes sort;
every worker has four neighbours, so the Metropolis-Hastings weights are 1/5
on the worker and on each of them):

    g_rc   = grad f_rc(x_rc; batch_rc(t)) + lam * x_rc
    x_rc'  = (x_rc + x_{r-1,c} + x_{r+1,c} + x_{r,c-1} + x_{r,c+1}) / 5
             -  eta0 / sqrt(t + 1) * g_rc          (indices mod R and mod C)

The state ``[N, D]`` is whole on the first device, viewed ``[R, C, D]`` for
the mixing: four ``jnp.roll``s over all N rows. The shards lie in
``reference_blocks`` blocks of consecutive workers, one ``jax.device_put``
each, as ``dsgd_ring_blocks`` has them, and the sums of an evaluation run over
all N on one device.

``precision``: ``reference``, or ``bfloat16`` (state, shards and matmul
operands rounded to bfloat16), the control of the arithmetic. ``mixing``:
``torus``, or one of two controls of the graph, each of which the cell's
limits are shown to fail:

    ring_mix   the two column neighbours only, weights 1/3: a ring along every
               grid row, as if the rows that come from the other chips (and
               from the block's own other grid rows) did not matter
    no_wrap    grid rows 0 and R - 1 not joined (a cylinder: those workers
               keep the missing neighbour's 1/5 themselves, W still symmetric
               and doubly stochastic): the rotation from the last chip to the
               first lost
"""

import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.dsgd_ring import PRECISIONS, _make_mm, batch_weights

MIXINGS = ("torus", "ring_mix", "no_wrap")


def torus_shape(n_workers):
    """(rows, columns) of the square torus of ``n_workers``."""
    side = math.isqrt(int(n_workers))
    if side * side != int(n_workers) or side < 3:
        raise ValueError(f"{n_workers} workers are no square torus of side >= 3")
    return side, side


def mix(x, shape, mixing="torus"):
    """W x for the state ``x`` [N, D] float32 on the torus of ``shape``."""
    g = x.reshape(*shape, x.shape[-1])
    lateral = jnp.roll(g, 1, axis=1) + jnp.roll(g, -1, axis=1)
    if mixing == "ring_mix":
        return ((g + lateral) / 3.0).reshape(x.shape)
    up, down = jnp.roll(g, 1, axis=0), jnp.roll(g, -1, axis=0)
    if mixing == "no_wrap":
        up = up.at[0].set(g[0])
        down = down.at[-1].set(g[-1])
    return ((g + up + down + lateral) / 5.0).reshape(x.shape)


def run(config, traffic, X, y, seed, precision="reference", mixing="torus"):
    """Follow one experiment's first ``check_iterations``. ``X`` [N*L, d] and
    ``y`` [N*L] are the host arrays the program was given, worker after worker.
    Returns host arrays ``objective`` and ``consensus``, one row per evaluation
    up to there."""
    exp = config["experiment"]
    if exp["topology"] != "grid" or exp["algorithm"] != "dsgd":
        raise ValueError("dsgd_torus_blocks reference covers D-SGD on a toroidal grid only")
    if mixing not in MIXINGS:
        raise ValueError(f"unknown mixing {mixing!r}; known: {MIXINGS}")
    problem = importlib.import_module(f"benchmark.reference.{exp['problem_type']}")
    prec = PRECISIONS[precision]
    mm = _make_mm(prec["operand"])
    state_dtype = prec["state"]
    N = int(exp["n_workers"])
    shape = torus_shape(N)
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
        mixed = mix(x.astype(jnp.float32), shape, mixing)
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
