"""Plain CHOCO-SGD on a ring with top-k gossip: the yardstick for what one
experiment of a compressed-gossip cell must produce. Straightforward
``jax.numpy``, float32 state, matmuls at ``highest`` over the operands the
configuration states (below), flat ``[N, D]`` rows; no kernels, no scan,
nothing imported from the package under test. The loss and
the gradient are the problem files' (``softmax.py``, ``logistic.py``), the
batches and the matmul are ``dsgd_ring.py``'s.

One iteration, for every worker i at once (Koloskova, Stich & Jaggi, ICML
2019, arXiv:1902.00340, Algorithm 2), x and the public copies xhat starting
at 0:

    x_half = x - eta_t * (grad f_i(x; batch_i(t)) + lam * x)
    q      = top_k(x_half - xhat)          the only numbers on the wire
    xhat   = xhat + q
    x      = x_half + gamma * (W xhat - xhat)

with ``eta_t = eta0 / sqrt(t + 1)`` (``lr_schedule: sqrt_decay``) or ``eta0``
(``constant``), W the ring with Metropolis-Hastings weights 1/3, and
``top_k`` keeping, of each worker's D numbers, the k of largest magnitude and
setting the others to 0. Of numbers of equal magnitude the one at the lower
index is kept, so every row of q has exactly k entries however many tie at
the threshold; it is worked out here from the k-th largest magnitude (one
sort of the magnitudes), not with a top-k primitive. After iteration t
(counting from 1) with t a multiple of ``eval_every``: the full-data
objective at the mean of the x_i, and the consensus error
mean_i |x_i - xbar|^2, as ``dsgd_ring`` evaluates them.

With the identity in top_k's place and gamma = 1 the iteration is
x = W (x - eta_t g): D-SGD in its adapt-then-combine order, where
``dsgd_ring`` combines first (x = W x - eta_t g). From x = 0 the two have the
same mean model after one iteration and drift apart by O(eta^2) after.

Matmul operands follow the arithmetic the configuration states
(``matmul_precision``): ``highest`` is float32 operands; ``default`` is what
the chip does with it, operands rounded to bfloat16 and accumulated in
float32, which the plain matmul of rounded operands at ``highest`` reproduces
(exact products, float32 sums). That is the configuration's own arithmetic,
not a loosening, and the state stays float32. Why not float32 operands
throughout, as ``dsgd_ring`` has them: the selection is discontinuous, and at
the cell's size the one bfloat16 pass moves enough entries across the top-k
threshold that the program read 0.001386 on the consensus error against a
float32-operand reference on every seed, and the bfloat16-state control
0.00156-0.00157 against it: 1.13x apart, no limit between (PR 26 chip runs,
seeds 2600000101/102). Against this reference the program reads 0 to 1.3e-7
and that control 3.6e-4.

``precision`` is how the yardstick itself is computed:
  reference  float32 state, operands as the configuration states
  bfloat16   x, xhat and matmul operands rounded to bfloat16    (control)
  identity   the reference with q = x_half - xhat: no compressor (control)
The controls exist so that the limits can be shown to fail them.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.dsgd_ring import _make_mm, batch_weights

# precision -> (state type, compressor on). The matmul operands are bfloat16
# under a bfloat16 state, else what the configuration's matmul_precision states.
PRECISIONS = {
    "reference": (jnp.float32, True),
    "bfloat16": (jnp.bfloat16, True),
    "identity": (jnp.float32, False),
}
OPERANDS = {"highest": None, "default": jnp.bfloat16}


def top_k_rows(v, k):
    """``v`` [N, D] with all but the k largest-magnitude entries of each row
    set to 0; ties go to the lower index."""
    mag = jnp.abs(v)
    threshold = jnp.sort(mag, axis=1)[:, -k][:, None]  # the k-th largest
    above = mag > threshold
    tied = mag == threshold
    room = k - jnp.sum(above, axis=1, keepdims=True)
    keep = above | (tied & (jnp.cumsum(tied, axis=1) <= room))
    return jnp.where(keep, v, 0.0)


def follow(config, traffic, X, y, seed, precision="reference"):
    """The rows of ``run`` and the final float32 ``x`` and ``xhat`` [N, D],
    on the device."""
    exp = config["experiment"]
    if exp["topology"] != "ring" or exp["algorithm"] != "choco" \
            or exp["compression"] != "top_k":
        raise ValueError("choco_ring reference covers CHOCO-SGD with top_k on a ring only")
    schedule = exp.get("lr_schedule", "constant")
    if schedule not in ("sqrt_decay", "constant"):
        raise ValueError(f"choco_ring: state the step-size schedule, not {schedule!r}")
    problem = importlib.import_module(f"benchmark.reference.{exp['problem_type']}")
    state_dtype, compress = PRECISIONS[precision]
    mm = _make_mm(jnp.bfloat16 if state_dtype == jnp.bfloat16
                  else OPERANDS[exp["matmul_precision"]])
    N = int(exp["n_workers"])
    L, d = X.shape[0] // N, X.shape[1]
    X, y = X.reshape(N, L, d), y.reshape(N, L)
    D = problem.param_dim(d, config)
    T = int(traffic.get("check_iterations", traffic["n_iterations"]))
    eval_every = int(traffic["eval_every"])
    b = int(exp["local_batch_size"])
    eta0 = float(exp["learning_rate_eta0"])
    lam = float(exp["l2_regularization_lambda"])
    gamma = float(exp["choco_gamma"])
    k = int(exp["compression_k"])
    block = int(config.get("reference_block_workers", N))

    Xd = jnp.asarray(X)
    yd = jnp.asarray(y)
    if state_dtype != jnp.float32:
        Xd = Xd.astype(state_dtype)

    def per_worker(fn, *args):
        return jax.lax.map(lambda a: fn(*a), args, batch_size=block)

    # The data are arguments, never captured (see dsgd_ring).
    @jax.jit
    def step(x, xhat, t, Xd, yd):
        w = batch_weights(seed, t, N, L, b)
        xf, hf = x.astype(jnp.float32), xhat.astype(jnp.float32)
        g = per_worker(
            lambda xi, Xi, yi, wi: problem.gradient(xi, Xi, yi, wi, lam, mm),
            xf, Xd, yd, w,
        )
        eta = eta0 / jnp.sqrt(t.astype(jnp.float32) + 1.0) if schedule == "sqrt_decay" else eta0
        x_half = xf - eta * g
        q = top_k_rows(x_half - hf, k) if compress else x_half - hf
        hf = hf + q
        mixed = (jnp.roll(hf, 1, axis=0) + hf + jnp.roll(hf, -1, axis=0)) / 3.0
        x_new = x_half + gamma * (mixed - hf)
        return x_new.astype(state_dtype), hf.astype(state_dtype)

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
    xhat = jnp.zeros((N, D), state_dtype)
    objective, consensus = [], []
    for t in range(T):
        x, xhat = step(x, xhat, jnp.asarray(t, jnp.int32), Xd, yd)
        if (t + 1) % eval_every == 0:
            o, c = evaluate(x, Xd, yd)
            objective.append(o)
            consensus.append(c)
    rows = {
        "objective": np.asarray(jnp.stack(objective), dtype=np.float64),
        "consensus": np.asarray(jnp.stack(consensus), dtype=np.float64),
    }
    return rows, x.astype(jnp.float32), xhat.astype(jnp.float32)


def run(config, traffic, X, y, seed, precision="reference"):
    """Follow one experiment's first ``check_iterations``. ``X`` [N*L, d] and
    ``y`` [N*L] are the host arrays the program was given, worker after worker.
    Returns host arrays ``objective`` and ``consensus``, one row per evaluation
    up to there."""
    return follow(config, traffic, X, y, seed, precision)[0]
