"""Plain D-SGD on a ring with LOCAL UPDATES and CLIENT SAMPLING: tau gradient
steps a gossip round, a worker taking part in a round with probability
``participation_rate`` (Koloskova, Loizou, Boreiri, Jaggi & Stich, ICML 2020,
arXiv:2003.10422: decentralized SGD with local updates on a changing graph).
Straightforward ``jax.numpy``, float32, matmuls at ``highest``; no kernels, no
scan, no timeline, no neighbor table, nothing imported from the package under
test. The matmul and the problem modules are ``dsgd_ring.py``'s.

Every draw is a function of the seed and of counters alone (t the round,
counted from 0; s the slot of a gradient inside its round, 0 <= s < tau), by
the package's documented rules (ops/sampling.py and parallel/faults.py
docstrings), restated here from those descriptions:

    batch_i(t, s) = the b rows with the largest of L uniforms drawn from
                    fold_in(fold_in(fold_in(key(seed), s), t), i)
    a_i(t)        = u_i >= 1 - rate     (float32 against float32),
                    u = uniform(fold_in(fold_in(key(seed), 0x9AC70), t), (N,), float32)

so slot 0's batches are ``dsgd_ring.batch_weights``'s, and a worker's
participation is independent across rounds and workers. A link carries a
model in round t iff both its ends take part:

    live_ij = a_i * a_j
    deg_i   = sum_j live_ij
    w_ij    = live_ij / (1 + max(deg_i, deg_j))        (Metropolis-Hastings
    w_ii    = 1 - sum_j w_ij                            on the realized graph)

so every W_t is symmetric and doubly stochastic and mixing never moves the
network mean. One ROUND, for every worker i at once, every step of it at the
round's step size eta_t = eta0 / sqrt(t + 1):

    v_i  = sum_j w_ij x_j  -  eta_t * (grad f_i(x_i; batch_i(t, 0)) + lam * x_i)
    v_i  = v_i  -  eta_t * (grad f_i(v_i; batch_i(t, s)) + lam * v_i)     s = 1 .. tau-1
    x_i' = v_i                                          if a_i = 1
    x_i' = x_i                                          if a_i = 0

The first descent is fused with the gossip (its gradient at the models
BEFORE mixing, D-PSGD's order); the tau - 1 later ones are purely local, each
at the models the descent before it made. A sampled-out worker exchanges
nothing, takes NONE of the tau steps and keeps its row. After round t
(counting from 1) with t a multiple of ``eval_every``: the full-data
objective at the mean model and the consensus error over ALL N workers,
sampled-out ones included, after the round's last local step.

``precision`` as in ``dsgd_ring``: ``reference``, or ``bfloat16`` (state,
shards and matmul operands rounded to bfloat16; the participation arithmetic
stays float32), the control the limits are shown to fail. ``rounds`` is how a
round is realized:
  local          the rule above
  one_step       the local descents left out: tau = 1 on the same graphs
  same_batch     every local descent re-uses slot 0's batch
  no_freeze      a sampled-out worker's links drop but it steps
  decayed_local  descent s of round t at eta0 / sqrt(tau * t + s + 1), the
                 step size of a global counter of gradient steps, not the
                 round's
The last four are controls: the limits are shown to fail them too.

``sampled_out_share`` counts the (round, worker) pairs sampled out over a
whole horizon: what the program's root argument of that name says.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.dsgd_ring import PRECISIONS, _make_mm

ROUNDS = ("local", "one_step", "same_batch", "no_freeze", "decayed_local")


def slot_batch_weights(seed, slot, t, n_workers, n_local, batch_size):
    """[N, L] weights of slot ``slot`` of round t: 1/b on the sampled rows,
    0 elsewhere."""
    if batch_size >= n_local:
        return jnp.full((n_workers, n_local), 1.0 / n_local, jnp.float32)
    step_key = jax.random.fold_in(
        jax.random.fold_in(jax.random.key(seed), slot), t)

    def one(i):
        u = jax.random.uniform(jax.random.fold_in(step_key, i), (n_local,))
        _, idx = jax.lax.top_k(u, batch_size)
        return jnp.zeros((n_local,), jnp.float32).at[idx].set(1.0 / batch_size)

    return jax.vmap(one)(jnp.arange(n_workers))


def taking_part(seed, t, n_workers, rate):
    """[N] bool: the workers that take part in round t."""
    u = jax.random.uniform(
        jax.random.fold_in(jax.random.fold_in(jax.random.key(seed), 0x9AC70), t),
        (n_workers,), dtype=jnp.float32)
    return u >= np.float32(1.0 - rate)


def sampled_out_share(seed, n_workers, horizon, rate):
    """Share of the (round, worker) pairs of ``horizon`` rounds sampled out."""
    count = jax.jit(lambda t: jnp.sum(~taking_part(seed, t, n_workers, rate)))
    out = sum(int(count(jnp.asarray(t, jnp.int32))) for t in range(horizon))
    return out / (horizon * n_workers)


def run(config, traffic, X, y, seed, precision="reference", rounds="local"):
    """Follow one experiment's first ``check_iterations`` rounds. ``X``
    [N*L, d] and ``y`` [N*L] are the host arrays the program was given, worker
    after worker. Returns host arrays ``objective`` and ``consensus``, one row
    per evaluation up to there, and ``sampled_out``, the workers sampled out
    of each round."""
    exp = config["experiment"]
    if exp["topology"] != "ring" or exp["algorithm"] != "dsgd":
        raise ValueError("dsgd_ring_local reference covers D-SGD on a ring only")
    if rounds not in ROUNDS:
        raise ValueError(f"rounds is one of {ROUNDS}, not {rounds!r}")
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
    tau = int(exp.get("local_steps", 1))
    rate = float(exp.get("participation_rate", 1.0))
    block = int(config.get("reference_block_workers", N))

    Xd = jnp.asarray(X)
    yd = jnp.asarray(y)
    if prec["state"] != jnp.float32:
        Xd = Xd.astype(prec["state"])

    def per_worker(fn, *args):
        return jax.lax.map(lambda a: fn(*a), args, batch_size=block)

    def gradient(v, slot, t, Xd, yd):
        """[N, D] float32: every worker's gradient at its row of ``v`` (as
        the state holds it) on its batch of (t, slot)."""
        w = slot_batch_weights(seed, slot, t, N, L, b)
        return per_worker(
            lambda vi, Xi, yi, wi: problem.gradient(
                vi.astype(jnp.float32), Xi, yi, wi, lam, mm),
            v, Xd, yd, w,
        )

    def step_size(t, s):
        count = tau * t + s if rounds == "decayed_local" else t
        return eta0 / jnp.sqrt(count.astype(jnp.float32) + 1.0)

    # The data are arguments, never captured (dsgd_ring.py says why).
    @jax.jit
    def round_(x, t, Xd, yd):
        part = taking_part(seed, t, N, rate)
        a = part.astype(jnp.float32)
        right = a * jnp.roll(a, -1)                     # the link {i, i+1}
        left = jnp.roll(right, 1)                       # the link {i-1, i}
        deg = left + right
        w_right = right / (1.0 + jnp.maximum(deg, jnp.roll(deg, -1)))
        w_left = left / (1.0 + jnp.maximum(deg, jnp.roll(deg, 1)))
        w_self = 1.0 - (w_left + w_right)

        xf = x.astype(jnp.float32)
        mixed = (w_self[:, None] * xf + w_left[:, None] * jnp.roll(xf, 1, axis=0)
                 + w_right[:, None] * jnp.roll(xf, -1, axis=0))
        v = (mixed - step_size(t, 0) * gradient(x, 0, t, Xd, yd)).astype(state_dtype)
        if rounds != "one_step":
            for s in range(1, tau):                     # the purely local descents
                slot = 0 if rounds == "same_batch" else s
                vf = v.astype(jnp.float32)
                v = (vf - step_size(t, s) * gradient(v, slot, t, Xd, yd)).astype(state_dtype)
        if rounds != "no_freeze":
            v = jnp.where(part[:, None], v, x)
        return v, jnp.sum(~part)

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
    objective, consensus, sampled_out = [], [], []
    for t in range(T):
        x, out = round_(x, jnp.asarray(t, jnp.int32), Xd, yd)
        sampled_out.append(out)
        if (t + 1) % eval_every == 0:
            o, c = evaluate(x, Xd, yd)
            objective.append(o)
            consensus.append(c)
    return {
        "objective": np.asarray(jnp.stack(objective), dtype=np.float64),
        "consensus": np.asarray(jnp.stack(consensus), dtype=np.float64),
        "sampled_out": np.asarray(jnp.stack(sampled_out), dtype=np.int64),
    }
