"""L2-regularized binary logistic regression (labels in {-1, +1}).

Capability parity with reference ``obj_problems.py:3-36`` (the convex test
problem of the study, PDF §II-B).
"""

from distributed_optimization_tpu.models.base import glm_problem, register_problem
from distributed_optimization_tpu.ops import losses

LOGISTIC = register_problem(glm_problem("logistic", losses.LOGISTIC))
