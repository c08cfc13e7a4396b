"""Plain gradient tracking (DIGing; Nedić, Olshevsky & Shi 2017) on a 2-D
torus: the yardstick of the tracker cell. ``dsgd_ring``'s layout:
straightforward ``jax.numpy``, float32, matmuls at ``highest``; no kernels,
no scan, no sharding, nothing imported from the package under test. The
batches, the matmul and the problem modules are ``dsgd_ring.py``'s.

Worker i sits at grid place (i // cols, i % cols) of a rows x cols torus,
rows = cols = sqrt(N); every worker has four neighbours, so the
Metropolis-Hastings weights are 1/5 each:

    (W u)_{r,c} = (u_{r,c} + u_{r-1,c} + u_{r+1,c} + u_{r,c-1} + u_{r,c+1}) / 5

indices mod rows and cols. One iteration t (counted from 0), for every
worker at once, from x_0 = y_0 = g_0 = 0 and a CONSTANT step eta:

    x_{t+1} = W x_t - eta * y_t
    g_{t+1} = grad f_i(x_{t+1}; batch_i(t)) + mu * x_{t+1}
    y_{t+1} = W y_t + g_{t+1} - g_t

so iteration 0 is pure gossip from zero and y_1 = g_1; W is symmetric and
doubly stochastic, so mean_i y_t = mean_i g_t at every t >= 1. After
iteration t (counting from 1) with t a multiple of ``eval_every``: the
full-data objective at the mean model and the consensus error
mean_i |x_i - xbar|^2. It follows the first ``check_iterations`` of the
traffic mix.

``precision`` as in ``dsgd_ring``: ``reference``, or ``bfloat16`` (all three
state leaves, shards and matmul operands rounded to bfloat16; the mixing
arithmetic stays float32). ``rule`` is what is iterated:
  tracking        the rule above
  grad_at_old_x   g_{t+1} taken at x_t, the other order found in the
                  literature: another trajectory from iteration 2 on
  no_tracking     y_{t+1} = g_{t+1}: constant-step D-SGD with the step a
                  round late, the bias floor the tracker removes
``stencil`` is the order of the five additions:
  self_first      ((((u + up) + down) + left) + right) / 5
  pairs           (u + ((up + down) + (left + right))) / 5
The two rules and ``bfloat16`` are controls: the limits are shown to fail
each. ``pairs`` is sound: the limits are shown to pass it.
"""

import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.dsgd_ring import PRECISIONS, _make_mm, batch_weights

RULES = ("tracking", "grad_at_old_x", "no_tracking")
STENCILS = ("self_first", "pairs")


def torus_mix(u, side, stencil="self_first"):
    """W u for u [side * side, D], worker i at (i // side, i % side)."""
    g = u.reshape(side, side, -1)
    up, down = jnp.roll(g, 1, axis=0), jnp.roll(g, -1, axis=0)
    left, right = jnp.roll(g, 1, axis=1), jnp.roll(g, -1, axis=1)
    if stencil == "pairs":
        total = g + ((up + down) + (left + right))
    else:
        total = (((g + up) + down) + left) + right
    return (total / 5.0).reshape(u.shape)


def run(config, traffic, X, y, seed, precision="reference", rule="tracking",
        stencil="self_first"):
    """Follow one experiment's first ``check_iterations``. ``X`` [N*L, d] and
    ``y`` [N*L] are the host arrays the program was given, worker after worker.
    Returns host arrays ``objective`` and ``consensus``, one row per evaluation
    up to there."""
    exp = config["experiment"]
    if exp["topology"] != "grid" or exp["algorithm"] != "gradient_tracking":
        raise ValueError("gt_torus reference covers gradient tracking on a torus only")
    if rule not in RULES or stencil not in STENCILS:
        raise ValueError(f"rule is one of {RULES} and stencil one of {STENCILS}")
    problem = importlib.import_module(f"benchmark.reference.{exp['problem_type']}")
    prec = PRECISIONS[precision]
    mm = _make_mm(prec["operand"])
    state_dtype = prec["state"]
    N = int(exp["n_workers"])
    side = math.isqrt(N)
    if side * side != N:
        raise ValueError(f"a torus needs a square number of workers, not {N}")
    L, d = X.shape[0] // N, X.shape[1]
    X, y = X.reshape(N, L, d), y.reshape(N, L)
    D = problem.param_dim(d, config)
    T = int(traffic.get("check_iterations", traffic["n_iterations"]))
    eval_every = int(traffic["eval_every"])
    b = int(exp["local_batch_size"])
    eta = float(exp["learning_rate_eta0"])
    # The strongly convex objective is regularised by mu, which the study
    # leaves at lambda's value.
    mu = float(exp.get("strong_convexity_mu", exp["l2_regularization_lambda"]))
    block = int(config.get("reference_block_workers", N))

    Xd = jnp.asarray(X)
    yd = jnp.asarray(y)
    if prec["state"] != jnp.float32:
        Xd = Xd.astype(prec["state"])

    def per_worker(fn, *args):
        return jax.lax.map(lambda a: fn(*a), args, batch_size=block)

    # The data are arguments, never captured (dsgd_ring.py says why).
    @jax.jit
    def step(x, trk, g_prev, t, Xd, yd):
        xf, yf, gf = (a.astype(jnp.float32) for a in (x, trk, g_prev))
        x_new = torus_mix(xf, side, stencil) - eta * yf
        at = xf if rule == "grad_at_old_x" else x_new
        w = batch_weights(seed, t, N, L, b)
        g_new = per_worker(
            lambda xi, Xi, yi, wi: problem.gradient(
                xi.astype(state_dtype).astype(jnp.float32), Xi, yi, wi, mu, mm),
            at, Xd, yd, w,
        )
        if rule == "no_tracking":
            trk_new = g_new
        else:
            trk_new = torus_mix(yf, side, stencil) + g_new - gf
        return tuple(a.astype(state_dtype) for a in (x_new, trk_new, g_new))

    @jax.jit
    def evaluate(x, Xd, yd):
        xf = x.astype(jnp.float32)
        xbar = jnp.mean(xf, axis=0)
        even = jnp.full((L,), 1.0 / (N * L), jnp.float32)
        losses = per_worker(
            lambda Xi, yi: problem.data_loss(xbar, Xi, yi, even, mm), Xd, yd)
        objective = jnp.sum(losses) + 0.5 * mu * jnp.dot(xbar, xbar)
        consensus = jnp.mean(jnp.sum((xf - xbar[None, :]) ** 2, axis=1))
        return objective, consensus

    x = trk = g_prev = jnp.zeros((N, D), state_dtype)
    objective, consensus = [], []
    for t in range(T):
        x, trk, g_prev = step(x, trk, g_prev, jnp.asarray(t, jnp.int32), Xd, yd)
        if (t + 1) % eval_every == 0:
            o, c = evaluate(x, Xd, yd)
            objective.append(o)
            consensus.append(c)
    return {
        "objective": np.asarray(jnp.stack(objective), dtype=np.float64),
        "consensus": np.asarray(jnp.stack(consensus), dtype=np.float64),
    }
