"""L2-regularized multinomial (softmax) logistic regression — the
compute-bound objective family.

Not in the reference (``obj_problems.py``'s GLMs are all scalar-output) —
this is the framework's MXU tier: the [d, K] weight matrix makes the
per-worker gradient a pair of real matmuls (X @ W forward, X^T @ (P − Y)
backward, 2·b·d·K FLOPs each) instead of the scalar GLMs' matvecs, so wide
(d, K) configurations load the systolic array instead of the memory bus
(measured: docs/perf/compute_bound.json, docs/PERF.md §compute-bound).

Parameters keep their own shape inside the jax scan and are flat at every
boundary: ``param_shape`` is ``(d, K)``, the scan carries ``[N, d, K]`` — the
layout the two matmuls read and write, so no step copies the models between
a flat and a matrix form (on the TPU that copy was a third of an iteration:
PERF.md §6, PR 25) — and the run builder flattens once, on the host, at
harvest (``final_models`` is ``[N, d·K]``). ``param_dim`` is the flat
length. The kernels take either form and infer K from static shapes
(``ops/losses.py`` softmax section), so the bound class count only sizes
the parameter.
"""

import functools

from distributed_optimization_tpu.models.base import Problem, register_problem
from distributed_optimization_tpu.ops import losses

DEFAULT_N_CLASSES = 10


@functools.lru_cache(maxsize=None)
def make_softmax_problem(n_classes: int) -> Problem:
    """Softmax Problem with the class count bound to ``n_classes``.

    Cached per K so a given class count always yields the SAME callable
    objects — the backends pass these as jit static arguments, and a fresh
    instance per call would defeat XLA's compilation cache.
    """
    if n_classes < 2:
        raise ValueError(f"softmax needs n_classes >= 2, got {n_classes}")
    return Problem(
        name="softmax",
        objective=losses.softmax_objective,
        gradient=losses.softmax_gradient,
        objective_weighted=losses.softmax_objective_weighted,
        gradient_weighted=losses.softmax_gradient_weighted,
        param_shape=lambda d: (d, n_classes),
    )


SOFTMAX = register_problem(make_softmax_problem(DEFAULT_N_CLASSES))
