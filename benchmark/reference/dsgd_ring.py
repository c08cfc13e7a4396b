"""Plain D-SGD on a ring: the benchmark's yardstick for what one experiment
must produce. Straightforward ``jax.numpy``, float32, matmuls at ``highest``;
no kernels, no scan, no sharding, nothing imported from the package under
test.

One iteration, for every worker i at once (Lian et al. 2017, D-PSGD order):

    g_i   = grad f_i(x_i; batch_i(t)) + lam * x_i
    x_i'  = (x_{i-1} + x_i + x_{i+1}) / 3  -  eta0 / sqrt(t + 1) * g_i

(Metropolis-Hastings weights on a ring are 1/3 each). After iteration t
(counting from 1) with t a multiple of ``eval_every``: the full-data
objective at the mean model, and the consensus error mean_i |x_i - xbar|^2.

``batch_i(t)`` is the whole shard when b >= L. Otherwise it is the package's
documented seed-pure rule (ops/sampling.py docstring), restated here from
that description: the b rows with the largest of L uniforms drawn from
``fold_in(fold_in(fold_in(key(seed), 0), t), i)``, t counted from 0.

It follows the first ``check_iterations`` of the traffic mix (all of them where
the mix names none): the program's rows up to there are what is compared, so
the check costs less than the window it judges.

``precision`` is how the yardstick itself is computed:
  reference  float32 state, operands as given, float32 accumulation
  bfloat16   state and matmul operands rounded to bfloat16   (control for float32)
The control exists so that the limits can be shown to fail it.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = {
    "reference": {"state": jnp.float32, "operand": None},
    "bfloat16": {"state": jnp.bfloat16, "operand": jnp.bfloat16},
}


def _round_to(a, operand):
    """``a`` as the operand type holds it, back in float32."""
    a = a.astype(jnp.float32)
    return a if operand is None else a.astype(operand).astype(jnp.float32)


def _make_mm(operand):
    def mm(a, b):
        return jnp.matmul(
            _round_to(a, operand), _round_to(b, operand),
            precision=jax.lax.Precision.HIGHEST,
        )

    return mm


def batch_weights(seed, t, n_workers, n_local, batch_size):
    """[N, L] weights: 1/b on this iteration's sampled rows, 0 elsewhere."""
    if batch_size >= n_local:
        return jnp.full((n_workers, n_local), 1.0 / n_local, jnp.float32)
    step_key = jax.random.fold_in(jax.random.fold_in(jax.random.key(seed), 0), t)

    def one(i):
        u = jax.random.uniform(jax.random.fold_in(step_key, i), (n_local,))
        _, idx = jax.lax.top_k(u, batch_size)
        return jnp.zeros((n_local,), jnp.float32).at[idx].set(1.0 / batch_size)

    return jax.vmap(one)(jnp.arange(n_workers))


def run(config, traffic, X, y, seed, precision="reference"):
    """Follow one experiment's first ``check_iterations``. ``X`` [N*L, d] and
    ``y`` [N*L] are the host arrays the program was given, worker after worker.
    Returns host arrays ``objective`` and ``consensus``, one row per evaluation
    up to there."""
    exp = config["experiment"]
    if exp["topology"] != "ring" or exp["algorithm"] != "dsgd":
        raise ValueError("dsgd_ring reference covers D-SGD on a ring only")
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
    block = int(config.get("reference_block_workers", N))

    Xd = jnp.asarray(X)
    yd = jnp.asarray(y)
    if prec["state"] != jnp.float32:
        Xd = Xd.astype(prec["state"])

    def per_worker(fn, *args):
        return jax.lax.map(lambda a: fn(*a), args, batch_size=block)

    # The data are arguments, never captured: a captured array is baked into
    # the program as a constant, and gigabytes of constants sink the compiler.
    @jax.jit
    def step(x, t, Xd, yd):
        w = batch_weights(seed, t, N, L, b)
        g = per_worker(
            lambda xi, Xi, yi, wi: problem.gradient(
                xi.astype(jnp.float32), Xi, yi, wi, lam, mm),
            x, Xd, yd, w,
        )
        xf = x.astype(jnp.float32)
        mixed = (jnp.roll(xf, 1, axis=0) + xf + jnp.roll(xf, -1, axis=0)) / 3.0
        eta = eta0 / jnp.sqrt(t.astype(jnp.float32) + 1.0)
        return (mixed - eta * g).astype(state_dtype)

    @jax.jit
    def evaluate(x, Xd, yd):
        xf = x.astype(jnp.float32)
        xbar = jnp.mean(xf, axis=0)
        even = jnp.full((L,), 1.0 / (N * L), jnp.float32)
        losses = per_worker(
            lambda Xi, yi: problem.data_loss(xbar, Xi, yi, even, mm), Xd, yd)
        objective = jnp.sum(losses) + 0.5 * lam * jnp.dot(xbar, xbar)
        consensus = jnp.mean(jnp.sum((xf - xbar[None, :]) ** 2, axis=1))
        return objective, consensus

    x = jnp.zeros((N, D), state_dtype)
    objective, consensus = [], []
    for t in range(T):
        x = step(x, jnp.asarray(t, jnp.int32), Xd, yd)
        if (t + 1) % eval_every == 0:
            o, c = evaluate(x, Xd, yd)
            objective.append(o)
            consensus.append(c)
    return {
        "objective": np.asarray(jnp.stack(objective), dtype=np.float64),
        "consensus": np.asarray(jnp.stack(consensus), dtype=np.float64),
    }
