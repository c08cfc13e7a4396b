"""Plain D-SGD on a ring whose links drop and whose workers straggle:
``dsgd_ring``'s equations with a graph that changes every round.
Straightforward ``jax.numpy``, float32, matmuls at ``highest``; no kernels,
no scan, no gather through a neighbor table, nothing imported from the package
under test. The batches, the matmul and the problem modules are
``dsgd_ring.py``'s.

The faults are memoryless, and every draw is a function of ``(seed, t)``
alone (t counted from 0), by the package's documented rule
(parallel/faults.py docstring), restated here from that description:

    up_e(t)  = u_e >= p,  u = uniform(fold_in(fold_in(key(seed), 0x0FA17), t), (E,), float32)
    m_i(t)   = u_i >= q,  u = uniform(fold_in(fold_in(key(seed), 0x57A66), t), (N,), float32)

one uniform an edge, the edges in the order of the documented edge list: the
``i < j`` rows of the ascending neighbor table read row by row, which on a
ring of N workers is NOT ``{i, i+1}`` in turn but

    (0, 1), (0, N-1), (1, 2), (2, 3), ..., (N-2, N-1)

so the edge ``{i, i+1}`` has the number ``i + 1`` for ``1 <= i <= N-2``, the
edge ``{0, 1}`` the number 0 and the edge ``{N-1, 0}`` the number 1. A link
carries a model in round t iff it is up and both its ends are:

    live_ij = up_ij * m_i * m_j
    deg_i   = sum_j live_ij
    w_ij    = live_ij / (1 + max(deg_i, deg_j))        (Metropolis-Hastings
    w_ii    = 1 - sum_j w_ij                            on the realized graph)

so every W_t is symmetric and doubly stochastic and mixing never moves the
network mean. One iteration, for every worker i at once:

    g_i   = grad f_i(x_i; batch_i(t)) + lam * x_i
    x_i'  = sum_j w_ij x_j  -  eta0 / sqrt(t + 1) * g_i      if m_i = 1
    x_i'  = x_i                                              if m_i = 0

A straggler takes no step and exchanges nothing. After iteration t (counting
from 1) with t a multiple of ``eval_every``: the full-data objective at the
mean model and the consensus error over ALL N workers, stragglers included.

``precision`` as in ``dsgd_ring``: ``reference``, or ``bfloat16`` (state,
shards and matmul operands rounded to bfloat16; the fault arithmetic stays
float32), the control the limits are shown to fail. ``faults`` is how the
graph is realized:
  drawn      the rule above
  no_freeze  a straggler's links drop but it steps: x_i' = x_i - eta g_i
  static     p = q = 0: the fault-free ring, weights 1/3
The last two are controls: the limits are shown to fail them too.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.dsgd_ring import PRECISIONS, _make_mm, batch_weights

FAULTS = ("drawn", "no_freeze", "static")


def ring_liveness(seed, t, n_workers, drop_prob, straggler_prob):
    """(right [N], m [N]) in float32: ``right[i]`` is 1 where the link
    ``{i, i+1 mod N}`` carries a model in round t, ``m[i]`` where worker i
    takes part."""
    base = jax.random.key(seed)
    u_edge = jax.random.uniform(
        jax.random.fold_in(jax.random.fold_in(base, 0x0FA17), t),
        (n_workers,), dtype=jnp.float32)
    u_node = jax.random.uniform(
        jax.random.fold_in(jax.random.fold_in(base, 0x57A66), t),
        (n_workers,), dtype=jnp.float32)
    up = (u_edge >= jnp.float32(drop_prob)).astype(jnp.float32)
    m = (u_node >= jnp.float32(straggler_prob)).astype(jnp.float32)
    # edge numbers to ring positions: {0,1} is 0, {N-1,0} is 1, {i,i+1} is i+1
    link = jnp.concatenate([up[0:1], up[2:], up[1:2]])
    return link * m * jnp.roll(m, -1), m


def run(config, traffic, X, y, seed, precision="reference", faults="drawn"):
    """Follow one experiment's first ``check_iterations``. ``X`` [N*L, d] and
    ``y`` [N*L] are the host arrays the program was given, worker after worker.
    Returns host arrays ``objective`` and ``consensus``, one row per evaluation
    up to there."""
    exp = config["experiment"]
    if exp["topology"] != "ring" or exp["algorithm"] != "dsgd":
        raise ValueError("dsgd_ring_faulty reference covers D-SGD on a ring only")
    if faults not in FAULTS:
        raise ValueError(f"faults is one of {FAULTS}, not {faults!r}")
    problem = importlib.import_module(f"benchmark.reference.{exp['problem_type']}")
    prec = PRECISIONS[precision]
    mm = _make_mm(prec["operand"])
    state_dtype = prec["state"]
    N = int(exp["n_workers"])
    if N < 3:
        raise ValueError("a ring of fewer than 3 workers has no two distinct links a worker")
    L, d = X.shape[0] // N, X.shape[1]
    X, y = X.reshape(N, L, d), y.reshape(N, L)
    D = problem.param_dim(d, config)
    T = int(traffic.get("check_iterations", traffic["n_iterations"]))
    eval_every = int(traffic["eval_every"])
    b = int(exp["local_batch_size"])
    eta0 = float(exp["learning_rate_eta0"])
    lam = float(exp["l2_regularization_lambda"])
    p = 0.0 if faults == "static" else float(exp.get("edge_drop_prob", 0.0))
    q = 0.0 if faults == "static" else float(exp.get("straggler_prob", 0.0))
    block = int(config.get("reference_block_workers", N))

    Xd = jnp.asarray(X)
    yd = jnp.asarray(y)
    if prec["state"] != jnp.float32:
        Xd = Xd.astype(prec["state"])

    def per_worker(fn, *args):
        return jax.lax.map(lambda a: fn(*a), args, batch_size=block)

    # The data are arguments, never captured (dsgd_ring.py says why).
    @jax.jit
    def step(x, t, Xd, yd):
        w = batch_weights(seed, t, N, L, b)
        g = per_worker(
            lambda xi, Xi, yi, wi: problem.gradient(
                xi.astype(jnp.float32), Xi, yi, wi, lam, mm),
            x, Xd, yd, w,
        )
        xf = x.astype(jnp.float32)
        right, m = ring_liveness(seed, t, N, p, q)
        left = jnp.roll(right, 1)                       # the link {i-1, i}
        deg = left + right
        w_right = right / (1.0 + jnp.maximum(deg, jnp.roll(deg, -1)))
        w_left = left / (1.0 + jnp.maximum(deg, jnp.roll(deg, 1)))
        w_self = 1.0 - (w_left + w_right)
        mixed = (w_self[:, None] * xf + w_left[:, None] * jnp.roll(xf, 1, axis=0)
                 + w_right[:, None] * jnp.roll(xf, -1, axis=0))
        eta = eta0 / jnp.sqrt(t.astype(jnp.float32) + 1.0)
        stepped = mixed - eta * g
        if faults != "no_freeze":
            stepped = jnp.where(m[:, None] > 0, stepped, xf)
        return stepped.astype(state_dtype)

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
