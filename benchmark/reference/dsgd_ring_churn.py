"""Plain D-SGD on a ring whose links fail in bursts and whose workers crash,
stay down and come back: ``dsgd_ring_faulty``'s equations with faults that
have MEMORY, and a warm restart on the round a worker comes back.
Straightforward ``jax.numpy``, float32, matmuls at ``highest``; no kernels,
no scan, no timeline, no neighbor table, nothing imported from the package
under test. The batches, the matmul and the problem modules are
``dsgd_ring.py``'s.

The uniforms are the sibling's, a function of ``(seed, tag, t)`` alone (t
counted from 0), one float32 an edge and one a node:

    u_e(t) = uniform(fold_in(fold_in(key(seed), 0x0FA17), t), (E,), float32)
    u_i(t) = uniform(fold_in(fold_in(key(seed), 0x57A66), t), (N,), float32)

the edges in the order of the documented edge list, which on a ring of N
workers is (0, 1), (0, N-1), (1, 2), (2, 3), ..., (N-2, N-1): the edge
``{i, i+1}`` has the number ``i + 1`` for ``1 <= i <= N-2``, ``{0, 1}`` the
number 0 and ``{N-1, 0}`` the number 1. What they are held against is the
two-state chain of docs/CHURN.md, its state carried from round to round here
in a Python loop (every threshold rounded to float32 before it is compared):

    link e (Gilbert-Elliott, marginal p, burst multiplier B):
        up_e(0) = u_e(0) >= p                              (the stationary marginal)
        up_e(t) = u_e(t) >= p / B              if up_e(t-1)
                  u_e(t) >= 1 - (1 - p) / B    otherwise
    worker i (crash and recovery, mean up-time F rounds, mean outage R):
        up_i(0) = u_i(0) >= R / (F + R)
        up_i(t) = u_i(t) >= 1 / F              if up_i(t-1)
                  u_i(t) >= 1 - 1 / R          otherwise
        back_i(t) = up_i(t) and not up_i(t-1)              (up_i(-1) = up)

A link carries a model in round t iff it is up and both its ends are:

    live_ij = up_ij * up_i * up_j
    deg_i   = sum_j live_ij
    w_ij    = live_ij / (1 + max(deg_i, deg_j))        (Metropolis-Hastings
    w_ii    = 1 - sum_j w_ij                            on the realized graph)

One iteration, for every worker i at once. First the restart
(``rejoin: neighbor_restart``): a worker that is back this round and has a
live link takes the mean of its realized neighbours' rows, before anything
else; one that is back with no live link keeps its stale row:

    x_i  <-  sum_j live_ij x_j / deg_i                 if back_i and deg_i > 0

then the step at the restarted models, which a down worker sits out, keeping
its row for the whole outage:

    g_i   = grad f_i(x_i; batch_i(t)) + lam * x_i
    x_i'  = sum_j w_ij x_j  -  eta0 / sqrt(t + 1) * g_i      if up_i
    x_i'  = x_i                                              otherwise

After iteration t (counting from 1) with t a multiple of ``eval_every``: the
full-data objective at the mean model and the consensus error over ALL N
workers, the down ones included.

``precision`` as in ``dsgd_ring``: ``reference``, or ``bfloat16`` (state,
shards and matmul operands rounded to bfloat16; the fault arithmetic stays
float32), the control the limits are shown to fail. ``faults`` is how the
rounds are realized:
  chains         the rule above
  frozen_rejoin  no restart: a worker comes back with its stale row
  memoryless     the same uniforms against thresholds that forget the round
                 before, p and R / (F + R): a program that ignored the
                 chains' memory, at the same marginals
  no_freeze      a down worker's links drop but it steps: x_i' = x_i - eta g_i
The last three are controls: the limits are shown to fail them too.

``chain_counts`` unrolls the workers' chain alone over a whole horizon: the
number of (round, worker) pairs with ``back`` set, which is what the
program's ``rejoin_rows`` counts, and the share of them down.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.dsgd_ring import PRECISIONS, _make_mm, batch_weights

FAULTS = ("chains", "frozen_rejoin", "memoryless", "no_freeze")


def thresholds(exp, memoryless=False):
    """((link: first round, after up, after down), (worker: the same)) as
    float32 numbers."""
    p, B = float(exp["edge_drop_prob"]), float(exp["burst_len"])
    F, R = float(exp["mttf"]), float(exp["mttr"])
    link = (p, p / B, 1.0 - (1.0 - p) / B)
    node = (R / (F + R), 1.0 / F, 1.0 - 1.0 / R)
    if memoryless:
        link, node = (link[0],) * 3, (node[0],) * 3
    return (tuple(np.float32(v) for v in link), tuple(np.float32(v) for v in node))


def uniforms(seed, t, n_workers):
    """(one float32 uniform an edge, one a worker) of round t."""
    base = jax.random.key(seed)
    u_edge = jax.random.uniform(
        jax.random.fold_in(jax.random.fold_in(base, 0x0FA17), t),
        (n_workers,), dtype=jnp.float32)
    u_node = jax.random.uniform(
        jax.random.fold_in(jax.random.fold_in(base, 0x57A66), t),
        (n_workers,), dtype=jnp.float32)
    return u_edge, u_node


def chain_round(u, t, up_prev, first, after_up, after_down):
    """This round's state of every chain: bool, up where the uniform is at
    least the threshold its last state names."""
    return u >= jnp.where(t == 0, first, jnp.where(up_prev, after_up, after_down))


def chain_counts(seed, n_workers, horizon, exp):
    """(rounds a worker came back, share of (round, worker) pairs down) of
    the workers' chain over ``horizon`` rounds."""
    _, node = thresholds(exp)

    @jax.jit
    def one(t, up_prev):
        up = chain_round(uniforms(seed, t, n_workers)[1], t, up_prev, *node)
        return up, jnp.sum(up & ~up_prev), jnp.sum(~up)

    up = jnp.ones((n_workers,), bool)
    back = down = 0
    for t in range(horizon):
        up, b, d = one(jnp.asarray(t, jnp.int32), up)
        back, down = back + int(b), down + int(d)
    return back, down / (horizon * n_workers)


def run(config, traffic, X, y, seed, precision="reference", faults="chains"):
    """Follow one experiment's first ``check_iterations``. ``X`` [N*L, d] and
    ``y`` [N*L] are the host arrays the program was given, worker after worker.
    Returns host arrays ``objective`` and ``consensus``, one row per evaluation
    up to there, and ``restarts``, the rows restarted in each iteration."""
    exp = config["experiment"]
    if exp["topology"] != "ring" or exp["algorithm"] != "dsgd":
        raise ValueError("dsgd_ring_churn reference covers D-SGD on a ring only")
    if exp.get("rejoin") != "neighbor_restart":
        raise ValueError("dsgd_ring_churn reference restarts a rejoining row")
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
    link_t, node_t = thresholds(exp, memoryless=faults == "memoryless")
    block = int(config.get("reference_block_workers", N))

    Xd = jnp.asarray(X)
    yd = jnp.asarray(y)
    if prec["state"] != jnp.float32:
        Xd = Xd.astype(prec["state"])

    def per_worker(fn, *args):
        return jax.lax.map(lambda a: fn(*a), args, batch_size=block)

    # The data are arguments, never captured (dsgd_ring.py says why).
    @jax.jit
    def step(x, t, link_prev, node_prev, Xd, yd):
        u_edge, u_node = uniforms(seed, t, N)
        link_up = chain_round(u_edge, t, link_prev, *link_t)
        node_up = chain_round(u_node, t, node_prev, *node_t)
        back = node_up & ~node_prev
        m = node_up.astype(jnp.float32)
        up = link_up.astype(jnp.float32)
        # edge numbers to ring positions: {0,1} is 0, {N-1,0} is 1, {i,i+1} is i+1
        right = jnp.concatenate([up[0:1], up[2:], up[1:2]]) * m * jnp.roll(m, -1)
        left = jnp.roll(right, 1)                       # the link {i-1, i}
        deg = left + right

        xf = x.astype(jnp.float32)
        take = back & (deg > 0)
        if faults != "frozen_rejoin":
            around = (left[:, None] * jnp.roll(xf, 1, axis=0)
                      + right[:, None] * jnp.roll(xf, -1, axis=0))
            xf = jnp.where(take[:, None], around / jnp.maximum(deg, 1.0)[:, None], xf)
        xs = xf.astype(state_dtype)                     # the restarted models

        w = batch_weights(seed, t, N, L, b)
        g = per_worker(
            lambda xi, Xi, yi, wi: problem.gradient(
                xi.astype(jnp.float32), Xi, yi, wi, lam, mm),
            xs, Xd, yd, w,
        )
        xf = xs.astype(jnp.float32)
        w_right = right / (1.0 + jnp.maximum(deg, jnp.roll(deg, -1)))
        w_left = left / (1.0 + jnp.maximum(deg, jnp.roll(deg, 1)))
        w_self = 1.0 - (w_left + w_right)
        mixed = (w_self[:, None] * xf + w_left[:, None] * jnp.roll(xf, 1, axis=0)
                 + w_right[:, None] * jnp.roll(xf, -1, axis=0))
        eta = eta0 / jnp.sqrt(t.astype(jnp.float32) + 1.0)
        stepped = mixed - eta * g
        if faults != "no_freeze":
            stepped = jnp.where(m[:, None] > 0, stepped, xf)
        return stepped.astype(state_dtype), link_up, node_up, jnp.sum(take)

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
    link_up = jnp.ones((N,), bool)
    node_up = jnp.ones((N,), bool)
    objective, consensus, restarts = [], [], []
    for t in range(T):
        x, link_up, node_up, taken = step(
            x, jnp.asarray(t, jnp.int32), link_up, node_up, Xd, yd)
        restarts.append(taken)
        if (t + 1) % eval_every == 0:
            o, c = evaluate(x, Xd, yd)
            objective.append(o)
            consensus.append(c)
    return {
        "objective": np.asarray(jnp.stack(objective), dtype=np.float64),
        "consensus": np.asarray(jnp.stack(consensus), dtype=np.float64),
        "restarts": np.asarray(jnp.stack(restarts), dtype=np.int64),
    }
