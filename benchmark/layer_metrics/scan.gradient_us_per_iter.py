"""Device microseconds per scan iteration in the gradient kernels: the ``vmap``
of ``gradient_weighted`` / ``link.gradient_at`` over the workers
(``dopt.gradient``). Where XLA fuses the step's update into the gradient
matmul's output the whole fusion is billed here, and the row says ``also:
update``. The op table's rows joined through the program's scope table
(``benchmark/scope_reduce.py``): low, never high."""

from benchmark import scope_reduce


def read(trace, facts, config):
    return scope_reduce.us_per_iter(trace, facts, config, "gradient")
