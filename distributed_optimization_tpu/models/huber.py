"""L2-regularized Huber regression (convex, robust).

Not in the reference (``obj_problems.py`` has logistic + least squares);
this is the framework's third objective family — robust regression with the
per-sample gradient capped at δ‖x‖ (δ fixed at the synthetic data's noise
scale; see ``ops/losses.py``). Uses the same regression data pipeline as
the quadratic problem and a scipy L-BFGS reference optimum
(``utils/oracle.py`` — sklearn's HuberRegressor jointly estimates a scale
parameter and does not minimize this objective).
"""

import functools

from distributed_optimization_tpu.models.base import (
    Problem,
    glm_problem,
    register_problem,
)
from distributed_optimization_tpu.ops import losses


@functools.lru_cache(maxsize=None)
def make_huber_problem(delta: float) -> Problem:
    """Huber Problem with the transition point bound to ``delta``.

    Cached per δ so a given δ always yields the SAME callable objects —
    the backends pass these as jit static arguments, and a fresh instance
    per call would defeat XLA's compilation cache.
    """
    return glm_problem("huber", losses.huber_link(delta))


HUBER = register_problem(make_huber_problem(losses.HUBER_DELTA))
