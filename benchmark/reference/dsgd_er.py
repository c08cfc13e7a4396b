"""Plain D-SGD on a connected Erdős–Rényi graph: ``dsgd_ring``'s equations
and batch rule with the mixing stated on the EDGE LIST. Straightforward
``numpy`` for the graph and ``jax.numpy`` for the iteration, float32,
matmuls at ``highest``; no kernels, no scan over iterations, no neighbor
table, no padded slot, nothing imported from the package under test. The
batches, the matmul and the problem modules are ``dsgd_ring.py``'s.

The graph is a function of ``(n, p, topology_seed)`` alone, by the package's
documented rule for its sparse sampler (parallel/topology.py docstring),
restated here from that description, on ``numpy.random.default_rng(
topology_seed)``, try after try on the one stream until a try is connected:

    counts_i  ~ Binomial(n - 1 - i, p)                 forward degrees, one draw
    partner   = i + 1 + floor(u * (n - 1 - i))         u uniform, WITH replacement,
                                                       one draw for all proposals
    duplicates collapse; while any row i holds fewer than counts_i distinct
    partners, its deficit is proposed again the same way (one draw a round)
    and merged, until no row lacks any

so the edges are the distinct pairs (i, j), i < j, in ascending order of
``i * n + j``. Connectivity is scipy's ``connected_components``. Then, with
``deg`` the ``bincount`` of both ends (Metropolis–Hastings on the drawn
degrees):

    w_e   = 1 / (1 + max(deg_i, deg_j))                     for e = {i, j}
    (Wx)_i = x_i + sum over edges {i, j} of w_e (x_j - x_i)

as two scatter-adds over the edge list, one a direction: W is symmetric and
doubly stochastic by construction, and the weight a worker keeps is never
formed. One iteration, for every worker i at once (D-PSGD order):

    g_i   = grad f_i(x_i; batch_i(t)) + lam * x_i
    x_i'  = (Wx)_i  -  eta0 / sqrt(t + 1) * g_i

After iteration t (counting from 1) with t a multiple of ``eval_every``: the
full-data objective at the mean model and the consensus error
mean_i |x_i - xbar|^2. It follows the first ``check_iterations`` of the
traffic mix.

``precision`` as in ``dsgd_ring``: ``reference``, or ``bfloat16`` (state,
shards and matmul operands rounded to bfloat16; the mixing arithmetic stays
float32), the control the limits are shown to fail. ``weights`` is how an
edge is weighed:
  metropolis          the rule above
  max_degree_weights  every edge 1 / (1 + k_max), k_max the largest degree:
                      symmetric and doubly stochastic too, another matrix
The second is a control: the limits are shown to fail it.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.dsgd_ring import PRECISIONS, _make_mm, batch_weights

WEIGHTS = ("metropolis", "max_degree_weights")
TRIES = 1000
TOPUP_ROUNDS = 200
EDGE_BLOCK = 1 << 18


def _connected(src, dst, n):
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components

    graph = coo_matrix((np.ones(src.size, np.int8), (src, dst)), shape=(n, n))
    return connected_components(graph, directed=False, return_labels=False) == 1


def draw_edges(n, p, topology_seed):
    """(src, dst, tries): the forward edges, src < dst, of the connected
    G(n, p) that ``topology_seed`` draws, and how many tries it took."""
    rng = np.random.default_rng(topology_seed)
    ids = np.arange(n, dtype=np.int64)
    tail = (n - 1) - ids

    def propose(counts):
        src = np.repeat(ids, counts)
        return src * n + src + 1 + np.floor(rng.random(src.size) * tail[src]).astype(np.int64)

    for attempt in range(1, TRIES + 1):
        counts = rng.binomial(tail, p)
        keys = np.unique(propose(counts))
        for _ in range(TOPUP_ROUNDS):
            deficit = counts - np.bincount(keys // n, minlength=n)
            if not (deficit > 0).any():
                break
            keys = np.unique(np.concatenate([keys, propose(np.maximum(deficit, 0))]))
        else:
            raise RuntimeError(f"G({n}, {p}): duplicates left after {TOPUP_ROUNDS} rounds")
        src, dst = keys // n, keys % n
        if _connected(src, dst, n):
            return src, dst, attempt
    raise RuntimeError(f"no connected G({n}, {p}) in {TRIES} tries")


def edge_weights(src, dst, n, weights="metropolis"):
    """float32 [E]: what each edge moves of the difference of its ends."""
    deg = np.bincount(np.concatenate([src, dst]), minlength=n)
    if weights == "max_degree_weights":
        return np.full(src.shape, 1.0 / (1.0 + deg.max()), np.float32)
    return (1.0 / (1.0 + np.maximum(deg[src], deg[dst]))).astype(np.float32)


def mix(x, src, dst, w):
    """Wx = x + both directions of w_e (x_j - x_i), the edge list in blocks
    (``edge_blocks``) so that the differences of one block are all that is
    held."""
    def one_block(out, block):
        s, d, we = block
        flow = we[:, None] * (x[d] - x[s])
        return out.at[s].add(flow).at[d].add(-flow), None

    out, _ = jax.lax.scan(one_block, x, (src, dst, w))
    return out


def edge_blocks(src, dst, w, block=EDGE_BLOCK):
    """``mix``'s three arguments: the edge list as device arrays
    ``[B, block]``, padded with edges {0, 0} of weight 0."""
    block = min(block, src.size)
    pad = (-src.size) % block
    return tuple(
        jnp.asarray(np.concatenate([a, np.zeros(pad, a.dtype)]).reshape(-1, block))
        for a in (src.astype(np.int32), dst.astype(np.int32), w.astype(np.float32)))


def run(config, traffic, X, y, seed, precision="reference", weights="metropolis"):
    """Follow one experiment's first ``check_iterations``. ``X`` [N*L, d] and
    ``y`` [N*L] are the host arrays the program was given, worker after worker.
    Returns host arrays ``objective`` and ``consensus``, one row per evaluation
    up to there."""
    exp = config["experiment"]
    if exp["topology"] != "erdos_renyi" or exp["algorithm"] != "dsgd":
        raise ValueError("dsgd_er reference covers D-SGD on an Erdos-Renyi graph only")
    if weights not in WEIGHTS:
        raise ValueError(f"weights is one of {WEIGHTS}, not {weights!r}")
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
    # The graph follows the topology seed where the file pins one, else the
    # program's seed, as the program's own rule has it.
    topology_seed = int(exp.get("topology_seed", -1))
    if topology_seed < 0:
        topology_seed = int(seed)

    src, dst, _ = draw_edges(N, float(exp["erdos_renyi_p"]), topology_seed)
    graph = edge_blocks(src, dst, edge_weights(src, dst, N, weights))

    Xd = jnp.asarray(X)
    yd = jnp.asarray(y)
    if prec["state"] != jnp.float32:
        Xd = Xd.astype(prec["state"])

    def per_worker(fn, *args):
        return jax.lax.map(lambda a: fn(*a), args, batch_size=block)

    # The data and the edge list are arguments, never captured
    # (dsgd_ring.py says why).
    @jax.jit
    def step(x, t, Xd, yd, graph):
        bw = batch_weights(seed, t, N, L, b)
        g = per_worker(
            lambda xi, Xi, yi, wi: problem.gradient(
                xi.astype(jnp.float32), Xi, yi, wi, lam, mm),
            x, Xd, yd, bw,
        )
        mixed = mix(x.astype(jnp.float32), *graph)
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
        x = step(x, jnp.asarray(t, jnp.int32), Xd, yd, graph)
        if (t + 1) % eval_every == 0:
            o, c = evaluate(x, Xd, yd)
            objective.append(o)
            consensus.append(c)
    return {
        "objective": np.asarray(jnp.stack(objective), dtype=np.float64),
        "consensus": np.asarray(jnp.stack(consensus), dtype=np.float64),
    }
