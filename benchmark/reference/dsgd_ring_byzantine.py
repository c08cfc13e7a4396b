"""Plain D-SGD on a ring with sign-flipping Byzantine workers, screened by the
coordinate-wise trimmed mean: ``dsgd_ring``'s equations with a set B of
workers that lie on the wire. Straightforward ``jax.numpy``, float32,
matmuls at ``highest``; no kernels, no scan, no neighbor table, no gather,
nothing imported from the package under test. The batches, the matmul and
the problem modules are ``dsgd_ring.py``'s.

**Who lies** (docs/BYZANTINE.md "Placement", restated here from that
description): the workers in the order ``permutation(N)`` of numpy's
``default_rng([seed, 0xB12A])``; a candidate c becomes an attacker iff each
of its two neighbours c - 1 and c + 1 (mod N) either is an attacker already
or counts fewer than b attackers among its own two neighbours; stop at f
attackers. So every honest worker keeps at most b attacking neighbours.

**One iteration**, for every worker i at once, H the complement of B:

    xt_j  = -scale * x_j   for j in B,   x_j otherwise        (as transmitted)
    m_i   = mean of sort(x_i, xt_{i-1}, xt_{i+1})[b : 3 - b]  for i in H, per coordinate
            (b = 1 on a ring: the median of three)
    m_i   = (x_{i-1} + x_i + x_{i+1}) / 3                     for i in B, on the TRUE stack
            (an attacker runs honest dynamics and lies only on the wire)
    g_i   = grad f_i(x_i; batch_i(t)) + lam * x_i
    x_i'  = m_i  -  eta0 / sqrt(t + 1) * g_i

After iteration t (counting from 1) with t a multiple of ``eval_every``: the
full-data objective (ALL the data, the attackers' shards too) at the mean of
the HONEST models, and the honest consensus error mean_{i in H} |x_i - xbar_H|^2.

``precision`` as in ``dsgd_ring``: ``reference``, or ``bfloat16`` (state,
shards and matmul operands rounded to bfloat16), the control the limits are
shown to fail. ``variant`` is what is computed in the program's place:
  screened          the rule above
  no_screening      honest workers average what they receive, 1/3 each: plain
                    gossip of the transmitted stack, which the attack breaks
  all_rows_metrics  the rule above, but the mean model and the consensus
                    error taken over ALL workers, attackers included
The last two are controls: the limits are shown to fail them too.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.dsgd_ring import PRECISIONS, _make_mm, batch_weights

VARIANTS = ("screened", "no_screening", "all_rows_metrics")


def attackers_on_a_ring(seed, n_workers, n_byzantine, budget):
    """Host [N] bool: the attacker set, by the placement rule above."""
    order = np.random.default_rng([int(seed), 0xB12A]).permutation(n_workers)
    attacker = np.zeros(n_workers, dtype=bool)
    lying_neighbours = np.zeros(n_workers, dtype=np.int64)
    placed = 0
    for c in order.tolist():
        if placed == n_byzantine:
            break
        pair = ((c - 1) % n_workers, (c + 1) % n_workers)
        if all(attacker[j] or lying_neighbours[j] < budget for j in pair):
            attacker[c] = True
            for j in pair:
                lying_neighbours[j] += 1
            placed += 1
    if placed < n_byzantine:
        raise ValueError(
            f"the ring of {n_workers} holds {placed} attackers within the "
            f"budget {budget} in this order, not {n_byzantine}")
    return attacker


def run(config, traffic, X, y, seed, precision="reference", variant="screened"):
    """Follow one experiment's first ``check_iterations``. ``X`` [N*L, d] and
    ``y`` [N*L] are the host arrays the program was given, worker after worker.
    Returns host arrays ``objective`` and ``consensus``, one row per evaluation
    up to there."""
    exp = config["experiment"]
    if exp["topology"] != "ring" or exp["algorithm"] != "dsgd":
        raise ValueError("dsgd_ring_byzantine reference covers D-SGD on a ring only")
    if (exp["attack"], exp["aggregation"]) != ("sign_flip", "trimmed_mean"):
        raise ValueError("dsgd_ring_byzantine covers sign_flip under the trimmed mean only")
    if exp.get("byzantine_placement") != "within_budget":
        raise ValueError("dsgd_ring_byzantine restates the within_budget placement only")
    if variant not in VARIANTS:
        raise ValueError(f"variant is one of {VARIANTS}, not {variant!r}")
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
    scale = float(exp["attack_scale"])
    budget = int(exp["robust_b"])
    if budget != 1:
        raise ValueError("a ring's closed neighbourhood of three trims b = 1 a tail")
    block = int(config.get("reference_block_workers", N))

    attacker = attackers_on_a_ring(seed, N, int(exp["n_byzantine"]), budget)
    lies = jnp.asarray(attacker)[:, None]
    counted = jnp.asarray(
        np.ones(N, np.float32) if variant == "all_rows_metrics"
        else (~attacker).astype(np.float32))

    Xd = jnp.asarray(X)
    yd = jnp.asarray(y)
    if prec["state"] != jnp.float32:
        Xd = Xd.astype(prec["state"])

    def per_worker(fn, *args):
        return jax.lax.map(lambda a: fn(*a), args, batch_size=block)

    def third(a, c, e):
        return (a + c + e) / 3.0

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
        sent = jnp.where(lies, -scale * xf, xf)
        received = (jnp.roll(sent, 1, axis=0), jnp.roll(sent, -1, axis=0))
        if variant == "no_screening":
            honest = third(received[0], xf, received[1])
        else:
            in_order = jnp.sort(jnp.stack([xf, *received]), axis=0)
            kept = in_order[budget:3 - budget]
            honest = jnp.sum(kept, axis=0) / kept.shape[0]
        benign = third(jnp.roll(xf, 1, axis=0), xf, jnp.roll(xf, -1, axis=0))
        mixed = jnp.where(lies, benign, honest)
        eta = eta0 / jnp.sqrt(t.astype(jnp.float32) + 1.0)
        return (mixed - eta * g).astype(state_dtype)

    @jax.jit
    def evaluate(x, Xd, yd):
        xf = x.astype(jnp.float32)
        n_counted = jnp.sum(counted)
        xbar = jnp.sum(counted[:, None] * xf, axis=0) / n_counted
        even = jnp.full((L,), 1.0 / (N * L), jnp.float32)
        losses = per_worker(
            lambda Xi, yi: problem.data_loss(xbar, Xi, yi, even, mm), Xd, yd)
        objective = jnp.sum(losses) + 0.5 * lam * jnp.dot(xbar, xbar)
        consensus = jnp.sum(
            counted * jnp.sum((xf - xbar[None, :]) ** 2, axis=1)) / n_counted
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
