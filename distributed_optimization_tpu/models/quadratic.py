"""L2-regularized least squares ("quadratic" — strongly convex).

Capability parity with reference ``obj_problems.py:39-69`` (the strongly
convex test problem of the study, PDF §II-B).
"""

from distributed_optimization_tpu.models.base import glm_problem, register_problem
from distributed_optimization_tpu.ops import losses

QUADRATIC = register_problem(glm_problem("quadratic", losses.QUADRATIC))
