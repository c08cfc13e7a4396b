"""Device microseconds per scan iteration in the inline eval: the objective of
the mean model and the consensus error, and since PR 31 the paired pass over
the shards that also makes the NEXT gradient's forward product X·x: billed
where it is built, here (``dopt.eval``). The op table's rows joined through
the program's scope table (``benchmark/scope_reduce.py``): low, never high."""

from benchmark import scope_reduce


def read(trace, facts, config):
    return scope_reduce.us_per_iter(trace, facts, config, "eval")
